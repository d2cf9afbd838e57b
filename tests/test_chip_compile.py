"""The round's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each case lowers a kernel (or its gradient) from shapes
for a described ``v5e`` device and compiles it with the TPU compiler,
which refuses what interpret mode accepts — slices not aligned to the
tiling, more VMEM than a kernel may use. Every case asserts that the
compiled text holds the Mosaic kernel (``tpu_custom_call``), so the
compiled path, not interpret mode, is what passed.

Shapes: the Shakespeare LSTM's gate factors at ``LSTMConfig()`` widths
(8x1024 and 256x1024 at the ranks its gamma gives) with the FL client
batch, a (4096, 14336, r=64) bf16 FFN layer, granite-4.0-h-micro's
projections (Mamba-2 in/out, MLP gate and down, attention k) at 4096
bf16 rows, vmapped over a chunk of 2 clients, the int8 upload fold at a
16-client chunk and at one client, and the serve kernels. On a v5e:2x2
mesh: the LSTM's held-out loss and its gradient on a global model
replicated over the chips, and the int8 fold of client-sharded wires
into a replicated accumulator — XLA cannot partition a Mosaic kernel,
so these compile only through ``repro.kernels.spmd``.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers import every test file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.nn.recurrent import LSTMConfig, init_lstm, lstm_loss

CLIENT_BATCH = 32


def _lstm_gate_shapes():
    params = jax.eval_shape(lambda: init_lstm(jax.random.PRNGKey(0),
                                              LSTMConfig()))
    return sorted({(g["x1"].shape[0], g["y1"].shape[0], g["x1"].shape[1])
                   for cell in params["cells"] for g in (cell["wi"],
                                                         cell["wh"])})


LSTM_SHAPES = [(CLIENT_BATCH, m, n, r, "float32")
               for m, n, r in _lstm_gate_shapes()]
FFN_SHAPE = (128, 4096, 14336, 64, "bfloat16")
# (rows, m, n, r) of granite-4.0-h-micro's projections at gamma 0.1: one
# client's 4096-token document
GRANITE_SHAPES = [(4096, 2048, 8512, 124, "bfloat16"),    # Mamba-2 in_proj
                  (4096, 4096, 2048, 110, "bfloat16"),    # Mamba-2 out_proj
                  (4096, 2048, 8192, 123, "bfloat16"),    # MLP gate / up
                  (4096, 8192, 2048, 123, "bfloat16"),    # MLP down
                  (4096, 2048, 512, 41, "bfloat16")]      # attention k / v


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("clients",))


@pytest.fixture
def compiled_kernels(monkeypatch):
    # code that picks interpret mode by asking for the backend takes
    # the chip's branch, as it does on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_text(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


def _factor_args(sharding, b, m, n, r, dtype, lead=()):
    def sds(*shape):
        return jax.ShapeDtypeStruct(lead + shape, jnp.dtype(dtype),
                                    sharding=sharding)

    return sds(b, m), sds(m, r), sds(n, r), sds(m, r), sds(n, r)


def _loss(x, x1, y1, x2, y2):
    # squared, so the cotangent needs the forward kernel's output
    return jnp.square(ops.fedpara_matmul(x, x1, y1, x2, y2, interpret=False,
                                         out_dtype=jnp.float32)).sum()


_GRAD = jax.grad(_loss, argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("shape", LSTM_SHAPES + [FFN_SHAPE], ids=str)
def test_fedpara_forward_compiles(one_chip, shape):
    text = _compiled_text(
        lambda *a: ops.fedpara_matmul(*a, interpret=False),
        *_factor_args(one_chip, *shape))
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("shape", LSTM_SHAPES + [FFN_SHAPE], ids=str)
def test_fedpara_vjp_compiles(one_chip, shape):
    # forward (for the residuals) + the dx, dX and dY bodies
    text = _compiled_text(_GRAD, *_factor_args(one_chip, *shape))
    assert text.count("tpu_custom_call") >= 4


@pytest.mark.parametrize("shape", LSTM_SHAPES, ids=str)
def test_client_vmapped_vjp_compiles(one_chip, shape):
    text = _compiled_text(jax.vmap(_GRAD),
                          *_factor_args(one_chip, *shape, lead=(16,)))
    assert text.count("tpu_custom_call") >= 4


@pytest.mark.parametrize("shape", GRANITE_SHAPES, ids=str)
def test_granite_projection_forward_and_vjp_compile(one_chip, shape):
    # a chunk of 2 clients, as the granite cell's round program runs it
    args = _factor_args(one_chip, *shape, lead=(2,))
    fwd = _compiled_text(
        jax.vmap(lambda *a: ops.fedpara_matmul(*a, interpret=False)), *args)
    assert fwd.count("tpu_custom_call") >= 1
    assert _compiled_text(jax.vmap(_GRAD), *args).count("tpu_custom_call") >= 4


@pytest.mark.parametrize("clients", [16, 1])
def test_int8_fold_compiles(one_chip, clients):
    params = jax.eval_shape(lambda: init_lstm(jax.random.PRNGKey(0),
                                              LSTMConfig()))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        lambda acc, q, c: ops.dequant_acc(acc, q, c, interpret=False),
        sds((n,), jnp.float32), sds((clients, n), jnp.int8),
        sds((clients,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["w8_matmul", "cache_residual_matmul"])
def test_serve_kernel_compiles(one_chip, kernel):
    b, m, n, r = 8, 1024, 4096, 32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, w, scale = (sds((b, m), jnp.bfloat16), sds((m, n), jnp.int8),
                   sds((1, n), jnp.float32))
    if kernel == "w8_matmul":
        text = _compiled_text(
            lambda *a: ops.w8_matmul(*a, interpret=False), x, w, scale)
    else:
        text = _compiled_text(
            lambda *a: ops.cache_residual_matmul(*a, interpret=False),
            x, w, scale, sds((m, r), jnp.bfloat16), sds((n, r), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("what", ["loss", "grad"])
def test_pallas_lstm_on_replicated_mesh_compiles(four_chips,
                                                 compiled_kernels, what):
    # an FL server on a mesh holds its global model replicated over the
    # chips; its held-out loss (and a sequential step) runs the fused
    # layers on it
    cfg = LSTMConfig()
    cfg = dataclasses.replace(
        cfg, param=dataclasses.replace(cfg.param, use_pallas=True))
    rep = NamedSharding(four_chips, P())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(lambda: init_lstm(jax.random.PRNGKey(0), cfg)))
    test = {"tokens": np.zeros((256, 80), np.int32)}

    def loss(p):
        return lstm_loss(p, cfg, test)

    text = _compiled_text(loss if what == "loss" else jax.grad(loss), params)
    assert "tpu_custom_call" in text


def test_int8_fold_on_mesh_compiles(four_chips, compiled_kernels):
    # a chunk that does not divide over the mesh, and the async
    # engine's arrival fold: client-sharded wires, replicated sum
    n = 1000
    rep, split = NamedSharding(four_chips, P()), NamedSharding(
        four_chips, P("clients"))
    text = _compiled_text(
        lambda acc, q, c: ops.tree_dequant_acc(acc, q, c),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((16, n), jnp.int8, sharding=split),
        jax.ShapeDtypeStruct((16,), jnp.float32, sharding=split))
    assert "tpu_custom_call" in text
