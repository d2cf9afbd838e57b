"""The federated round's profiler spans and its batch-bytes counter.

``FLServer.run_round`` records ``fl.round`` and one span per phase with
``jax.profiler.TraceAnnotation``; a benchmark reads them from the same
trace as the device's operations. Each case runs two rounds of the
shared tiny FedAvg task under ``jax.profiler.trace`` and reads the
``.xplane.pb`` back: every span the engine records sits on the calling
thread's line, inside its round's ``fl.round``, in the order the round
runs its phases. ``host_batch_bytes`` counts what the host builds for
the round's batches: the int32 index array where the stack is gathered
on the device (``batch_gather`` "device", the eager path here), the
byte size of the stack where the host builds it (``"host"``: the
chunked stream). ``train_tokens`` counts the target positions of the
real local steps of the clients whose uploads arrive.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parity import get_task, run_server
from repro.data.loader import epoch_indices, stack_client_epochs

PHASES = ("fl.round.select", "fl.round.arena_gather",
          "fl.round.stack_batches", "fl.round.put_batches",
          "fl.round.dispatch", "fl.round.wait", "fl.round.commit")
CALLER = "test_caller"


@pytest.fixture(scope="module")
def task():
    return get_task()


def _caller_line(logdir):
    """The events of the host line that holds the ``CALLER`` span."""
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    data = jax.profiler.ProfileData.from_file(paths[0])
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if any(name == CALLER for name, _, _ in events):
                return sorted(events, key=lambda e: e[1])
    raise AssertionError(f"no host line holds the {CALLER!r} span")


@pytest.mark.parametrize("engine,server_kw,phases", [
    ("streaming", dict(chunk=3, state_store="arena"), PHASES),
    ("batched", {}, PHASES),
    ("sequential", {}, ("fl.round.select", "fl.round.commit")),
    ("async", dict(chunk=3), ()),
])
def test_round_phases_are_spans_on_the_calling_thread(task, tmp_path, engine,
                                                      server_kw, phases):
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(CALLER):
            srv = run_server(task, engine, rounds=2, **server_kw)
    assert len(srv.history) == 2
    events = _caller_line(str(tmp_path))
    rounds = [e for e in events if e[0] == "fl.round"]
    assert len(rounds) == 2
    recorded = [e for e in events if e[0].startswith("fl.round.")]
    assert {e[0] for e in recorded} == set(phases)
    for _, lo, hi in rounds:
        inside = [name for name, s, e in recorded if lo <= s and e <= hi]
        assert tuple(inside) == phases
    # every phase span lies in some round
    assert sum(len(phases) for _ in rounds) == len(recorded)


def _stack_nbytes(task, cids, batch, pad):
    batches, step_mask = stack_client_epochs(
        task["tr"], task["parts"], cids, batch, 1, [0] * len(cids),
        pad_clients=pad)
    assert step_mask.shape[0] == len(cids) + pad   # the mask is not counted
    return sum(int(b.nbytes) for b in batches.values())


def _index_nbytes(task, cids, batch, pad):
    ids, zero_rows, step_mask = epoch_indices(
        task["parts"], cids, batch, 1, [0] * len(cids), pad_clients=pad)
    assert ids.dtype == np.int32
    assert ids.shape == step_mask.shape + (batch,)   # (C + pad, S, B)
    return int(ids.nbytes)


def test_batched_round_counts_its_batch_stack(task):
    srv = run_server(task, "batched", rounds=2)
    for rec in srv.history:
        assert rec["batch_gather"] == "device"
        assert rec["host_batch_bytes"] == _index_nbytes(
            task, rec["sampled"], 16, pad=0)
        assert rec["host_batch_bytes"] < _stack_nbytes(
            task, rec["sampled"], 16, pad=0)


def test_streaming_round_counts_its_batch_stack_eager_or_chunked(task):
    # cohort 4 in chunks of 3: two chunks, two pad slots counted
    eager = run_server(task, "streaming", chunk=3, rounds=2)
    chunked = run_server(task, "streaming", chunk=3, rounds=2,
                         data_stream="chunked")
    for a, b in zip(eager.history, chunked.history):
        assert a["sampled"] == b["sampled"]
        pad = a["chunks"] * a["client_chunk"] - len(a["sampled"])
        assert pad == 2
        assert (a["batch_gather"], b["batch_gather"]) == ("device", "host")
        assert a["host_batch_bytes"] == _index_nbytes(task, a["sampled"],
                                                      16, pad)
        assert b["host_batch_bytes"] == _stack_nbytes(task, b["sampled"],
                                                      16, pad)
        assert 0 < a["host_batch_bytes"] < b["host_batch_bytes"]
    assert np.isfinite(eager.history[-1]["mean_loss"])


@pytest.mark.parametrize("engine,server_kw", [
    ("batched", {}),
    ("streaming", dict(client_chunk=3, state_store="arena")),
    ("streaming", dict(client_chunk=3, data_stream="chunked")),
])
def test_rounds_count_their_trained_tokens(engine, server_kw):
    """Documents of 12 + 1 tokens over clients of 5 to 40 documents: a
    client's real steps are ``size // batch`` a local epoch, the pad slots
    and a padded step's positions are not counted."""
    from repro.fl import ClientConfig, FLServer, ServerConfig, make_strategy

    sizes = [5, 9, 16, 23, 40, 7]
    seq, batch, epochs = 12, 4, 2
    tokens = np.random.RandomState(0).randint(0, 50, (sum(sizes), seq + 1))
    bounds = np.cumsum([0] + sizes)
    parts = [np.arange(a, b) for a, b in zip(bounds[:-1], bounds[1:])]

    def loss_fn(p, b):
        emb = p["w"][b["tokens"][:, :-1]]
        return jnp.mean((emb.sum(-1) - b["tokens"][:, 1:]) ** 2)

    srv = FLServer(loss_fn, {"w": jnp.ones((50, 8)) * 0.1},
                   {"tokens": tokens.astype(np.int32)}, parts,
                   make_strategy("fedavg"),
                   ClientConfig(lr=0.01, batch=batch, epochs=epochs),
                   ServerConfig(clients=len(sizes), participation=4 / 6,
                                rounds=2, engine=engine, **server_kw))
    srv.run()
    for rec in srv.history:
        steps = sum(sizes[c] // batch * epochs for c in rec["sampled"])
        assert rec["train_tokens"] == steps * batch * seq
