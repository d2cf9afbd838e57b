"""Each cell's round program compiles for a TPU v5e chip and fits its
memory.

Nothing runs: the streaming engine's round program (local training of a
whole client chunk, vmapped over its clients, the int8 uplink and the
fused fold) is lowered from the cell's shapes for a described ``v5e``
device and compiled by the TPU compiler, with the kernels on their
compiled branch. ``memory_analysis()`` then has to fit the chip's
16 GiB.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers import every test file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import manifest  # noqa: E402

CHIP_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def round_program_args(cell, sharding):
    """The streaming round program and the shapes of one round's
    arguments, as the engine passes them for this cell."""
    from repro.fl import ClientConfig, make_strategy
    from repro.fl.codecs import make_codec
    from repro.fl.stream_engine import StreamingRound

    spec, traffic = cell.spec, cell.traffic
    chunk = traffic["client_chunk"]
    n_chunks = -(-traffic["cohort"] // chunk)
    steps = (spec["samples_per_client"] // spec["batch"]) * spec["epochs"]
    engine = StreamingRound(
        loss_fn=cell.config.program_loss(spec), strategy=make_strategy("fedavg"),
        client_cfg=ClientConfig(lr=spec["lr"], batch=spec["batch"],
                                epochs=spec["epochs"]),
        uplink_codec=make_codec(traffic["uplink_codec"]), chunk=chunk)
    data = jax.eval_shape(lambda: cell.config.make_data(
        jax.random.PRNGKey(0), spec))
    params = jax.eval_shape(lambda: cell.config.init_params(
        jax.random.PRNGKey(0), spec))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    lead = (n_chunks, chunk)
    batches = {k: sds(lead + (steps, spec["batch"]) + v.shape[1:], v.dtype)
               for k, v in data.items()}
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
    args = ({}, None, batches, sds(lead + (steps,), jnp.float32),
            sds(lead, jnp.float32), sds(lead, jnp.float32),
            sds(lead + (2,), jnp.uint32), sds((), jnp.float32), {},
            params, params, None, None, None)
    return engine._program, args


@pytest.mark.parametrize("cell_name", ["lstm_shakespeare.c16",
                                       "vgg16_cifar10.c16"])
def test_round_program_compiles_and_fits(one_chip, cell_name, monkeypatch):
    cell = manifest.resolve(harness.ROOT, cell_name)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    program, args = round_program_args(cell, one_chip)
    compiled = program.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{cell_name}: temp {mem.temp_size_in_bytes} B, arguments "
          f"{mem.argument_size_in_bytes} B, total {total} B")
    assert "tpu_custom_call" in compiled.as_text()
    assert total < CHIP_BYTES
