"""The profiler trace of a run's window, read back and reduced.

``jax.profiler`` writes an ``.xplane.pb``; :func:`read` keeps what the
metrics need: the operations each device ran (the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane), the host events of the benchmark's own
thread (its ``TraceAnnotation`` spans and the events JAX records while
dispatching), and the window the benchmark's spans cover. A device op keeps its HLO
instruction name (``fedpara_dx.37``, ``all-reduce.3``); the profiler
gives the whole instruction text. All times are nanoseconds on the
profiler's one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("run_round", "between_rounds")


@dataclass(frozen=True)
class Event:
    name: str               # a device op's HLO instruction name
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def base(self) -> str:
        """The instruction name without its ``.<n>`` suffix: every
        instance of one kernel or collective shares it."""
        head, _, tail = self.name.rpartition(".")
        return head if head and tail.isdigit() else self.name


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # device id -> [Event]
    host: list = field(default_factory=list)      # benchmark thread
    spans: list = field(default_factory=list)     # benchmark's own
    rounds: int = 0

    @property
    def window(self) -> tuple:
        if not self.spans:
            return (0.0, 0.0)
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def read(logdir: str, rounds: int) -> Trace:
    """The newest trace under ``logdir``; ``rounds`` is how many rounds
    its window ran."""
    import jax

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = Trace(rounds=rounds)
    host_lines = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.devices[int(m.group(1))] = sorted(
                        (Event(op_name(e.name), e.start_ns, e.duration_ns)
                         for e in line.events), key=lambda e: e.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                host_lines.append(evs)
    for evs in host_lines:
        if any(e.name in SPANS for e in evs):
            out.host = sorted(evs, key=lambda e: e.start)
            out.spans = [e for e in out.host if e.name in SPANS]
            break
    return out


# ------------------------------------------------------------ reductions

def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of ``intervals`` clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(x) for x in merged]


def busy_ns(events, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which at least one event ran."""
    return sum(e - s for s, e in union(((x.start, x.end) for x in events),
                                       lo, hi))


def gaps(events, lo: float, hi: float) -> list:
    """``[(start, end)]`` of [lo, hi] during which no event ran."""
    out, cur = [], lo
    for s, e in union(((x.start, x.end) for x in events), lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def leaves(events) -> list:
    """The events that contain no other event of the same line: the
    operations themselves, without loops or calls around them."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start < e.end and nxt.end <= e.end:
            continue
        out.append(e)
    return out


def matching(events, bases) -> list:
    """Events whose base name (see :attr:`Event.base`) is in ``bases``."""
    return [e for e in events if e.base in bases]


def in_window(events, lo: float, hi: float) -> list:
    return [e for e in events if e.start >= lo and e.end <= hi]


def host_activity(trace: Trace, t: float) -> str:
    """What the benchmark's thread was doing at ``t``: its own span and
    the innermost event JAX recorded under it, as ``span>event``."""
    covering = [e for e in trace.host if e.start <= t < e.end]
    if not covering:
        return "outside_spans"
    span = next((e.name for e in covering if e.name in SPANS), "outside_spans")
    inner = min(covering, key=lambda e: e.dur)
    return span if inner.name == span else f"{span}>{inner.name}"
