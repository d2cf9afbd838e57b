"""Arena-vs-dict client-state parity + fleet data-path invariants.

The device-resident arena (``repro.fl.arena``) must be an invisible
substrate swap: gather → local-update → scatter round-trips have to
reproduce the dict-based engines bitwise-masked and fp32-tol in params
for every strategy × personalization mode × codec (error feedback
threaded through the stacked rows), with identical wire bytes. The
streamed data path (``ChunkBatchSource``) must materialize bit-identical
batches to the eager full-cohort stack, and the pre-sized pad slots must
equal what the old concatenate path produced. The stack gathered on the
device from the resident dataset (``DeviceDataset``) must equal the host
stack bit for bit, and whole rounds on either must agree. Shared harness:
``tests/parity.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parity import (
    HAVE_HYPOTHESIS,
    N_CLIENTS,
    assert_parity,
    get_task,
    given,
    maxdiff,
    run_server,
    settings,
    st,
)
from repro.data import (
    ChunkBatchSource,
    VirtualPartitions,
    dirichlet_partition,
    loader,
    stack_client_epochs,
)


@pytest.fixture(scope="module")
def task():
    return get_task()


def _run(task, engine, *, chunk=3, **kw):
    return run_server(task, engine, chunk=chunk, **kw)


# ------------------------------------------------------------------ tentpole
@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=8, deadline=None)
@given(engine=st.sampled_from(["batched", "streaming"]),
       strategy=st.sampled_from(["fedavg", "fedprox", "scaffold", "feddyn"]),
       mode=st.sampled_from(["none", "pfedpara", "fedper", "local"]),
       codec=st.sampled_from(["", "int8", "delta|topk0.1|int8"]))
def test_arena_roundtrip_property(engine, strategy, mode, codec):
    """Acceptance: gather → local-update → scatter equals the dict path
    for random strategy × personalization × codec draws, EF accumulators
    threaded through the stacked arena rows."""
    task = get_task()
    kw = dict(strategy=strategy, personalization=mode, uplink_codec=codec)
    ref = _run(task, engine, **kw)
    got = _run(task, engine, state_store="arena", **kw)
    assert_parity(ref, got)


@pytest.mark.parametrize("engine,strategy,mode,codec", [
    ("batched", "feddyn", "none", "int8"),
    ("streaming", "scaffold", "pfedpara", ""),
    ("streaming", "fedprox", "fedper", "delta|topk0.1|int8"),
    ("batched", "scaffold", "local", "int8"),
])
def test_arena_roundtrip_matrix(task, engine, strategy, mode, codec):
    """Pinned strategy × mode × codec cells (runs with or without
    hypothesis — the property test above widens the same check)."""
    kw = dict(strategy=strategy, personalization=mode, uplink_codec=codec)
    ref = _run(task, engine, **kw)
    got = _run(task, engine, state_store="arena", **kw)
    assert_parity(ref, got)


@pytest.mark.parametrize("engine", ["batched", "streaming"])
def test_arena_parity_ef_both_links(task, engine):
    """Non-identity codecs on BOTH links, multi-round, EF threaded."""
    kw = dict(uplink_codec="delta|topk0.1|int8",
              downlink_codec="delta|topk0.1|int8", rounds=3)
    ref = _run(task, engine, **kw)
    got = _run(task, engine, state_store="arena", **kw)
    assert_parity(ref, got)


def test_arena_parity_hetero_tiers(task):
    """Rank tiers price and mask identically off the arena."""
    kw = dict(gamma_tiers=(0.1, 0.2, 0.3), strategy="scaffold")
    for engine in ("batched", "streaming"):
        ref = _run(task, engine, **kw)
        got = _run(task, engine, state_store="arena", **kw)
        assert_parity(ref, got)


def test_arena_participation_counters(task):
    """The int32 counter row equals a host tally of the arrival masks."""
    srv = _run(task, "streaming", state_store="arena", rounds=3,
               strategy="scaffold")
    tally = np.zeros(N_CLIENTS, np.int64)
    for r in srv.history:
        for cid, hit in zip(r["sampled"], r["arrived_mask"]):
            tally[cid] += hit
    np.testing.assert_array_equal(srv.participation_counts(), tally)
    # the scratch row absorbs pad-slot scatters but never a real arrival
    assert int(np.asarray(srv.arena.participation)[-1]) == 0


def test_arena_scratch_row_stays_pristine(task):
    """chunk=3 over cohorts of 4 forces pad slots every round; the
    scratch row they all address must keep its template value."""
    srv = _run(task, "streaming", state_store="arena", chunk=3,
               strategy="scaffold", rounds=3)
    tmpl = srv.arena.client_state(0)  # row 0 mutated; compare structure
    scratch = srv.arena.client_state(srv.arena.scratch_row)
    for leaf in jax.tree.leaves(scratch):   # scaffold init = all zeros
        assert not np.asarray(leaf).any()
    assert set(scratch) == set(tmpl)


# ---------------------------------------------------------------- data path
def test_chunked_data_stream_bitwise(task):
    """Lazy per-chunk materialization is bit-identical to the eager
    full-cohort stack (shared row-fill helper), dict and arena stores."""
    ref = _run(task, "streaming", rounds=3)
    for kw in (dict(data_stream="chunked"),
               dict(data_stream="chunked", state_store="arena")):
        got = _run(task, "streaming", rounds=3, **kw)
        assert ([r.get("arrived_mask") for r in ref.history]
                == [r.get("arrived_mask") for r in got.history])
        assert maxdiff(ref.global_params, got.global_params) == 0.0


def test_chunk_batch_source_matches_eager_stack(task):
    """fetch(i) rows == the eager stack's rows, bitwise, pads included."""
    tr, parts = task["tr"], task["parts"]
    cids = [1, 3, 4, 6, 7]
    seeds = [11, 22, 33, 44, 55]
    chunk, n_chunks, pad = 2, 3, 1
    batches, step_mask = stack_client_epochs(
        tr, parts, cids, batch=16, epochs=1, seeds=seeds,
        pad_steps=None, pad_clients=pad)
    S = step_mask.shape[1]
    src = ChunkBatchSource(tr, parts, cids, batch=16, epochs=1, seeds=seeds,
                           chunk=chunk, n_chunks=n_chunks, pad_steps=S)
    np.testing.assert_array_equal(src.step_mask(), step_mask)
    for ci in range(n_chunks):
        got = src.fetch(ci)
        for k in batches:
            np.testing.assert_array_equal(
                got[k], batches[k][ci * chunk:(ci + 1) * chunk])
    struct = src.chunk_struct()
    for k in batches:
        assert struct[k].shape == (chunk,) + batches[k].shape[1:]
        assert struct[k].dtype == batches[k].dtype


def test_stack_pad_clients_presized(task):
    """pad_clients pre-sizes the allocation: leading rows match the
    unpadded stack bitwise, pad rows are zero batches + zero mask."""
    tr, parts = task["tr"], task["parts"]
    cids, seeds = [0, 2, 5], [7, 8, 9]
    plain, mask = stack_client_epochs(tr, parts, cids, 16, 1, seeds)
    padded, pmask = stack_client_epochs(tr, parts, cids, 16, 1, seeds,
                                        pad_clients=2)
    for k in plain:
        np.testing.assert_array_equal(plain[k], padded[k][:3])
        assert not padded[k][3:].any()
    np.testing.assert_array_equal(mask, pmask[:3])
    assert not pmask[3:].any()


def _ragged_parts(tr):
    """A dirichlet partition with one client smaller than a batch of 16
    (client 1) and one empty client (client 2)."""
    parts = dirichlet_partition(tr["y"], N_CLIENTS, 0.5, seed=1)
    parts[1], parts[2] = parts[1][:5], parts[2][:0]
    return parts


@pytest.mark.parametrize("extra_steps,pad_clients", [(None, 0), (3, 3)],
                         ids=["tiny-and-empty", "pad-steps-and-clients"])
def test_device_gather_matches_host_stack(task, extra_steps, pad_clients):
    """The device gather of ``epoch_indices`` == ``stack_client_epochs``,
    bit for bit and dtype for dtype: tiny-client wrap, empty client,
    repeated pad steps and pad client rows included, for samples of one
    axis and of several (kept flat on the device)."""
    tr = task["tr"]
    parts = _ragged_parts(tr)
    tr = {**tr, "img": tr["x"].reshape(len(tr["x"]), 4, 8, 8)}
    cids = [0, 1, 2, 3, 5]
    seeds = [11, 22, 33, 2 ** 40 + 7, 55]
    real_steps = max(len(parts[c]) // 16 for c in cids) * 2
    pad_steps = None if extra_steps is None else real_steps + extra_steps
    host, mask = stack_client_epochs(tr, parts, cids, 16, 2, seeds,
                                     pad_steps=pad_steps,
                                     pad_clients=pad_clients)
    ids, zero_rows, dmask = loader.epoch_indices(
        parts, cids, 16, 2, seeds, pad_steps, pad_clients)
    assert mask.shape[1] == (pad_steps or real_steps)
    assert mask.sum(1).max() == real_steps
    np.testing.assert_array_equal(dmask, mask)
    np.testing.assert_array_equal(
        zero_rows, [False, False, True, False, False] + [True] * pad_clients)
    dev = loader.DeviceDataset(tr).gather(ids, zero_rows)
    assert set(dev) == set(host)
    for k in host:
        assert dev[k].dtype == host[k].dtype
        assert np.asarray(dev[k]).tobytes() == host[k].tobytes()
    assert not host["x"][2].any() and not host["x"][len(cids):].any()


@pytest.mark.parametrize("engine,kw,other", [
    ("batched", {}, "host"),
    ("streaming", dict(chunk=3), "host"),
    ("streaming", dict(chunk=3), "chunked"),
    ("async", dict(chunk=3), "host"),
])
def test_round_history_device_gather_equals_host(task, monkeypatch, engine,
                                                 kw, other):
    """Whole rounds on the device-gathered stack == rounds on the host
    stack (the budget helper forced to refuse the device copy) or, for
    the streaming engine, on the chunked stream: same losses, bytes,
    arrivals and global model, bit for bit."""
    ragged = {**task, "parts": _ragged_parts(task["tr"])}
    ref = run_server(ragged, engine, rounds=3, participation=0.75, **kw)
    if other == "chunked":
        got = run_server(ragged, engine, rounds=3, participation=0.75,
                         data_stream="chunked", **kw)
    else:
        monkeypatch.setattr(loader, "device_data_budget", lambda device: 0)
        got = run_server(ragged, engine, rounds=3, participation=0.75,
                         **kw)
    assert ref._device_data and not got._device_data
    for r, g in zip(ref.history, got.history, strict=True):
        for key in ("sampled", "arrived_mask", "mean_loss", "down_bytes",
                    "up_bytes", "comm_gb"):
            assert r.get(key) == g.get(key), key
        if engine != "async":
            assert (r["batch_gather"], g["batch_gather"]) == ("device",
                                                              "host")
    assert maxdiff(ref.global_params, got.global_params) == 0.0


def test_virtual_partitions_deterministic():
    """O(1)-per-client views: stable across instances, distinct sorted
    sample ids in range, scalar indexing only."""
    a = VirtualPartitions(pool_size=10_000, clients=1_000_000,
                          samples_per_client=32, seed=3)
    b = VirtualPartitions(pool_size=10_000, clients=1_000_000,
                          samples_per_client=32, seed=3)
    assert len(a) == 1_000_000
    for cid in (0, 999_999, 123_456):
        idx = a[cid]
        np.testing.assert_array_equal(idx, b[cid])
        assert len(idx) == 32 == len(set(int(i) for i in idx))
        assert idx.min() >= 0 and idx.max() < 10_000
        assert np.all(np.diff(idx) > 0)
    assert not np.array_equal(a[0], a[1])
    assert np.array_equal(a[-1], a[999_999])
    np.testing.assert_array_equal(a.sizes([4, 5]), [32, 32])
    with pytest.raises(TypeError):
        a[[0, 1]]
    with pytest.raises(IndexError):
        a[1_000_000]


# ----------------------------------------------------------------- seeding
def test_quant_keys_vmap_matches_fold_in_loop(task):
    """The vectorized per-client quantization keys are value-identical
    to the historical per-client fold_in loop."""
    srv = _run(task, "batched", rounds=1)
    got = srv._quant_keys(7)
    base = jax.random.PRNGKey(srv.round_idx)
    want = jnp.stack([jax.random.fold_in(base, i) for i in range(7)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
