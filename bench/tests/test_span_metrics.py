"""The per-layer metrics that read the program's round spans and its
batch-bytes counter, on a hand-built trace with known span times.

A traced round is the harness's ``run_round`` span around the
program's ``fl.round`` and its phase spans (``FLServer.run_round``).
Where the program records none of them, as a program without the
spans does, every metric gives None; the harness's idle-gap breakdown
names a gap by the phase span the host was in.
"""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import manifest  # noqa: E402
import xplane  # noqa: E402

MS = 1e6   # nanoseconds


def metric(name):
    return manifest.load_module(BENCH / "metrics" / f"{name}.py",
                                f"test_span_metric_{name}")


def ev(name, start_ms, end_ms):
    return xplane.Event(name, start_ms * MS, (end_ms - start_ms) * MS)


def one_round(t0, stack_ms, wait_ms):
    """A 100 ms harness round from ``t0``: 1 ms of harness before the
    program's round, then select 1, gather 2, stack ``stack_ms``, put 6,
    dispatch 2, wait ``wait_ms``, commit 3 ms. Returns its host spans
    and the device op the round program runs from dispatch to wait."""
    phases = [("fl.round.select", 1), ("fl.round.arena_gather", 2),
              ("fl.round.stack_batches", stack_ms),
              ("fl.round.put_batches", 6), ("fl.round.dispatch", 2),
              ("fl.round.wait", wait_ms), ("fl.round.commit", 3)]
    t, spans = t0 + 1, []
    for name, dur in phases:
        spans.append(ev(name, t, t + dur))
        t += dur
    host = [ev("run_round", t0, t0 + 100), ev("fl.round", t0 + 1, t),
            *spans, ev("between_rounds", t0 + 100, t0 + 101)]
    dispatch, wait = spans[4], spans[5]
    op = xplane.Event("fusion.1", dispatch.start + 0.5 * MS,
                      wait.end - dispatch.start - 1.0 * MS)
    return host, op


def context(rounds, records=()):
    host, ops = [], []
    for h, op in rounds:
        host += h
        ops.append(op)
    host.sort(key=lambda e: e.start)
    spans = [e for e in host if e.name in xplane.SPANS]
    trace = xplane.Trace(devices={0: ops}, host=host, spans=spans,
                         rounds=len(rounds))
    cell = manifest.resolve(ROOT, "vgg16_cifar10.c16")
    window = harness.Window(rounds=len(rounds), trace=trace,
                            records=list(records))
    return harness.Context(cell, 0.0, window, {},
                           [type("D", (), {"id": 0})()])


@pytest.fixture
def traced():
    # stack 40 then 20 ms, wait 30 then 50 ms
    return context([one_round(0, 40, 30), one_round(101, 20, 50)],
                   records=[{"host_batch_bytes": 440_545_280},
                            {"host_batch_bytes": 440_545_280}])


@pytest.mark.parametrize("name,expected", [
    # dispatch starts after select 1 + gather 2 + stack + put 6 ms
    ("host_prep_ms_per_round", ((1 + 2 + 40 + 6) + (1 + 2 + 20 + 6)) / 2),
    ("batch_stack_ms_per_round", (40 + 20) / 2),
    ("device_wait_ms_per_round", (30 + 50) / 2),
    ("host_batch_mb_per_round", 440.54528),
])
def test_span_metric_reads_its_spans(traced, name, expected):
    assert metric(name).compute(traced) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["host_prep_ms_per_round",
                                  "batch_stack_ms_per_round",
                                  "device_wait_ms_per_round",
                                  "host_batch_mb_per_round"])
def test_span_metric_is_none_without_program_spans(name):
    # what a program without the spans and the counter leaves: the
    # harness's own spans, a device op, records with the wire bytes only
    host = [ev("run_round", 0, 100), ev("between_rounds", 100, 101)]
    ctx = context([(host, xplane.Event("fusion.1", 50 * MS, 40 * MS))],
                  records=[{"up_bytes": 1, "down_bytes": 2}])
    assert metric(name).compute(ctx) is None


def test_span_outside_the_traced_window_is_not_read():
    # the window is what the harness's spans cover; a program span
    # after the last traced round does not count
    ctx = context([one_round(0, 40, 30)])
    ctx.trace.host.append(ev("fl.round.stack_batches", 200, 300))
    assert metric("batch_stack_ms_per_round").compute(ctx) == pytest.approx(40)


def test_breakdown_names_an_idle_gap_by_its_phase_span(traced):
    gaps = harness.breakdown(traced)["idle_gaps"]
    name, seconds = gaps[0]
    # the device is idle from the window's start, through the first
    # round's select, gather, stack and put, until 0.5 ms into dispatch:
    # 50.5 ms, whose middle falls in the stacking
    assert name == "run_round>fl.round.stack_batches"
    assert seconds == pytest.approx(50.5e-3)
