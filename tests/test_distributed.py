"""Distributed runtime tests — run in subprocesses with 8 forced host
devices (device count is locked at first jax init, so in-process tests
can't change it)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.collectives import (
    plan_buckets,
    powersgd_compress,
    powersgd_decompress,
    powersgd_init,
)
from repro.distributed.fedpod import sync_mask
from repro.analysis import hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_powersgd_error_feedback_bounded():
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (64, 32))
    st = powersgd_init(g.shape, 4, key)
    sent = jnp.zeros_like(g)
    for _ in range(30):
        p, q, st = powersgd_compress(g, st)
        sent = sent + powersgd_decompress(p, q)
    rel = float(jnp.linalg.norm(sent - 30 * g) / jnp.linalg.norm(30 * g))
    assert rel < 0.5  # cumulative transmitted ~ cumulative gradient
    # full-rank compression is exact (up to fp32 QR/matmul roundoff)
    st2 = powersgd_init(g.shape, 32, key)
    p, q, st2 = powersgd_compress(g, st2)
    rel_full = float(jnp.linalg.norm(powersgd_decompress(p, q) - g)
                     / jnp.linalg.norm(g))
    assert rel_full < 1e-3


def test_bucket_plan_respects_size():
    tree = {f"w{i}": jnp.zeros((1024,)) for i in range(10)}  # 4KB each
    buckets = plan_buckets(tree, bucket_bytes=8192)
    assert all(len(b) <= 2 for b in buckets)
    assert sum(len(b) for b in buckets) == 10


def test_sync_mask_keeps_embeddings_local():
    params = {"embed": {"w": jnp.zeros((8, 4))},
              "layers": {"wq": {"x1": jnp.zeros((4, 2))}},
              "unembed": {"w": jnp.zeros((4, 8))}}
    mask = sync_mask(params, "factors")
    assert mask["embed"]["w"] is False
    assert mask["unembed"]["w"] is False
    assert mask["layers"]["wq"]["x1"] is True
    mask_full = sync_mask(params, "full")
    assert all(jax.tree.leaves(mask_full))


def test_fedpod_round_semantics():
    """2 pods diverge during local steps; after FedAvg the synced leaves
    are equal across pods and equal to the mean, embeddings stay local."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.distributed.fedpod import make_fed_round, stack_for_pods, sync_mask
        from repro.optim import sgd

        def loss_fn(params, batch):
            h = batch['x'] @ params['wq']['x1']
            h = h @ params['embed']['w']
            return jnp.mean((h - batch['y'])**2)

        params = {'wq': {'x1': jnp.ones((4, 3))},
                  'embed': {'w': jnp.ones((3, 2)) * 0.5}}
        opt = sgd(0.05)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4, 1),
                    ('pod', 'data', 'model'))
        stacked = stack_for_pods(params, 2)
        opt_state = jax.tree.map(lambda a: jnp.stack([a, a]),
                                 opt.init(params))
        K, B = 3, 8
        key = jax.random.PRNGKey(0)
        batches = {'x': jax.random.normal(key, (2, K, B, 4)),
                   'y': jax.random.normal(key, (2, K, B, 2))}
        step = make_fed_round(loss_fn, opt, local_steps=K, sync='factors')
        with mesh:
            new_params, opt_state, loss = jax.jit(step)(stacked, opt_state, batches)
        x1 = np.asarray(new_params['wq']['x1'])
        emb = np.asarray(new_params['embed']['w'])
        assert np.allclose(x1[0], x1[1]), 'factors must be pod-synced'
        assert not np.allclose(emb[0], emb[1]), 'embeddings stay pod-local'
        print('OK', float(loss))
    """)


def test_quick_dryrun_cell_via_subprocess():
    """End-to-end dryrun machinery on a small mesh (8 devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
         "--shape", "decode_32k", "--mesh", "multi", "--quick",
         "--skip-cost", "--out", "/tmp/dryrun_pytest"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    art = json.load(open("/tmp/dryrun_pytest/xlstm-125m_decode_32k_multi.json"))
    assert "memory" in art and art["memory"]["argument_bytes"] > 0


def test_batched_local_update_pads_nondivisible_client_batch():
    """Regression: C not divisible by the mesh axis used to warn and
    silently fall back to single-device vmap; now the batch is padded
    with masked dummies, stays on the shard_map path, and matches the
    plain vmap result exactly."""
    run_sub("""
        import warnings
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.fl.batch_engine import batched_local_update
        from repro.fl.client import ClientConfig

        def loss_fn(p, b):
            return jnp.mean((b['x'] @ p['w'] - b['y']) ** 2)

        C, S, B = 6, 3, 4          # 6 clients on an 8-device axis
        key = jax.random.PRNGKey(0)
        params = {'w': jax.random.normal(key, (C, 5, 2))}
        batches = {'x': jax.random.normal(key, (C, S, B, 5)),
                   'y': jax.random.normal(key, (C, S, B, 2))}
        smask = jnp.ones((C, S), jnp.float32).at[2, 2:].set(0.0)
        cfg = ClientConfig(lr=0.1)
        args = (params, {}, batches, smask, loss_fn, cfg, 'fedavg', 0.1)

        ref = batched_local_update(*args)            # single-device vmap
        mesh = Mesh(np.array(jax.devices()[:8]), ('clients',))
        with warnings.catch_warnings():
            warnings.simplefilter('error')           # no fallback warning
            out = batched_local_update(*args, mesh=mesh)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        print('OK padded shard_map matches vmap')
    """)


def test_sharded_dequant_acc_two_level():
    """Two-level streaming aggregation: per-shard fused partial sums +
    one psum must equal the dense oracle over the full client stack."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.fl import comm
        from repro.kernels import agg, ref

        C = 8
        key = jax.random.PRNGKey(0)
        payload = {'w': jax.random.normal(key, (C, 12, 5)),
                   'b': jax.random.normal(key, (C, 7))}
        wire = jax.vmap(comm.quantize_int8)(
            payload, jax.random.split(key, C))
        w = jnp.abs(jax.random.normal(key, (C,)))
        mesh = Mesh(np.array(jax.devices()[:8]), ('clients',))
        with mesh:
            out = jax.jit(lambda t, ww: agg.sharded_tree_dequant_acc(
                t, ww, mesh, 'clients', interpret=True))(wire, w)
        want = ref.tree_dequant_acc_ref(agg.acc_zeros_like(wire), wire, w)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
        print('OK two-level')
    """)


def test_streaming_engine_on_client_mesh():
    """Full streaming round on a ('clients',) mesh: chunk sharded over
    devices, two-level aggregation — must match the meshless run."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs.base import ParamCfg
        from repro.data import iid_partition, make_image_dataset, \
            train_test_split
        from repro.fl import ClientConfig, FLServer, ServerConfig, \
            make_strategy
        from repro.nn import recurrent as rec

        ds = make_image_dataset(640, 10, size=8, channels=1, noise=0.3)
        data = {'x': ds['x'].reshape(len(ds['y']), -1), 'y': ds['y']}
        tr, _ = train_test_split(data)
        cfg = rec.MLPConfig(in_dim=64, hidden=32, classes=10,
                            param=ParamCfg(kind='fedpara', gamma=0.3,
                                           min_dim_for_factorization=8))
        params = rec.init_mlp_model(jax.random.PRNGKey(0), cfg)
        parts = iid_partition(len(tr['y']), 8, 0)
        def loss_fn(p, b):
            return rec.mlp_loss(p, cfg, b)
        def build(mesh):
            return FLServer(loss_fn, params, tr, parts,
                            make_strategy('fedavg'),
                            ClientConfig(lr=0.1, batch=16, epochs=1),
                            ServerConfig(clients=8, participation=1.0,
                                         rounds=1, engine='streaming',
                                         client_chunk=8,
                                         uplink_codec='int8'),
                            mesh=mesh)
        srv0 = build(None); srv0.run()
        mesh = Mesh(np.array(jax.devices()[:8]), ('clients',))
        srv1 = build(mesh); srv1.run()
        d = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(srv0.global_params),
            jax.tree.leaves(srv1.global_params)))
        assert d < 1e-4, d
        print('OK mesh streaming', d)
    """)


def test_kernels_on_replicated_mesh_operands():
    """Pallas kernels whose operands sit on a multi-device mesh (a
    server's replicated global model, client-sharded wires) run under a
    replicated shard_map and give the single-device results: forward,
    gradient through the custom VJP, and the int8 fold."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.kernels import ops, spmd

        mesh = Mesh(np.array(jax.devices()[:4]), ('clients',))
        rep = NamedSharding(mesh, P())
        k = jax.random.split(jax.random.PRNGKey(0), 5)
        args = (jax.random.normal(k[0], (16, 64)),
                jax.random.normal(k[1], (64, 8)),
                jax.random.normal(k[2], (32, 8)),
                jax.random.normal(k[3], (64, 8)),
                jax.random.normal(k[4], (32, 8)))
        loss = jax.jit(jax.value_and_grad(
            lambda *a: jnp.square(ops.fedpara_matmul(*a)).sum(),
            argnums=(0, 1, 2, 3, 4)))
        one, four = loss(*args), loss(*jax.device_put(args, rep))
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(four)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert spmd.free_mesh(jax.device_put(args, rep))[1] == ('clients',)
        assert spmd.free_mesh(args) == (None, ())
        text = jax.jit(ops.fedpara_matmul).lower(
            *jax.device_put(args, rep)).as_text()
        assert 'manual_computation' in text or 'shard_map' in text

        acc = jnp.zeros((100,))
        q = jax.random.randint(k[0], (4, 100), -127, 128).astype(jnp.int8)
        c = jnp.arange(1.0, 5.0)
        fold = jax.jit(lambda a, q, c: ops.tree_dequant_acc(a, q, c))
        want = fold(acc, q, c)
        got = fold(jax.device_put(acc, rep),
                   jax.device_put(q, NamedSharding(mesh, P('clients'))),
                   jax.device_put(c, NamedSharding(mesh, P('clients'))))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        print('OK replicated kernels')
    """, devices=4)


def test_bucketed_pmean_subprocess():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.distributed.collectives import bucketed_pmean
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ('pod', 'data'))
        tree = {'a': jnp.arange(8.0), 'b': jnp.ones((3, 3))}
        with mesh:
            out = jax.jit(lambda t: bucketed_pmean(t, mesh, 'pod'))(tree)
        np.testing.assert_allclose(np.asarray(out['a']), np.arange(8.0))
        print('OK')
    """)


# ---------------------------------------------------------------- HLO parse

SAMPLE_HLO = """
  %p0 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0), replica_groups={{0,1},{2,3}}, to_apply=%add
  %ag = bf16[16,512]{1,0} all-gather(%agin), dimensions={1}, replica_groups=[2,4]<=[8]
  %agin = bf16[16,128]{1,0} parameter(1)
  %rs = f32[4,32]{1,0} reduce-scatter(%p0), dimensions={1}, replica_groups={{0,1,2,3}}
  %cp = f32[8]{0} collective-permute(%cpi), source_target_pairs={{0,1},{1,0}}
  %cpi = f32[8]{0} parameter(2)
"""


def test_collective_stats_operand_accounting():
    st = hlo.collective_stats(SAMPLE_HLO, pod_size=0)
    # all-reduce operand = 16*128*4 = 8192
    assert st["all-reduce:intra_pod"]["bytes"] == 8192
    # all-gather operand resolved through defs: bf16 16*128*2 = 4096
    assert st["all-gather:intra_pod"]["bytes"] == 4096
    # reduce-scatter operand = full f32 input 8192
    assert st["reduce-scatter:intra_pod"]["bytes"] == 8192
    assert st["collective-permute:intra_pod"]["bytes"] == 32
    assert st["total"]["count"] == 4


def test_replica_group_formats_and_domain():
    groups = hlo.parse_replica_groups("[2,4]<=[8]")
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    gt = hlo.parse_replica_groups("[4,2]<=[2,4]T(1,0)")
    assert gt == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert hlo.classify_domain([[0, 4]], pod_size=4) == "cross_pod"
    assert hlo.classify_domain([[0, 1, 2, 3]], pod_size=4) == "intra_pod"


def test_extrapolation_linear():
    u1 = {"total": {"bytes": 10, "ring_bytes": 5.0, "count": 2}}
    u2 = {"total": {"bytes": 16, "ring_bytes": 8.0, "count": 3}}
    out = hlo.extrapolate(u1, u2, periods=10)
    assert out["total"]["bytes"] == 10 + 9 * 6
    assert out["total"]["count"] == 2 + 9 * 1
