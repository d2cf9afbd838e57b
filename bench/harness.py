"""One run of one cell: set-up, the measured window, the output check,
and the result line. ``run.py`` is the command; this module is also
driven by ``calibrate.py`` and the tests, which share every step but
the window.
"""
from __future__ import annotations

import functools
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 3.0   # how much of the window a traced run profiles


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the checkout's fixed ``.jax_cache``; every program
    is cached, however quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def import_program():
    """Put the checkout's ``src`` on the path; raise if it is not there."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise FileNotFoundError(f"the system under test is not at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def seeds(seed: int) -> dict:
    """Independent 32-bit sub-seeds of any whole number ``seed``."""
    words = np.random.SeedSequence(int(seed) % 2 ** 128).generate_state(4)
    return dict(zip(("init", "data", "partition", "server"),
                    (int(w) for w in words)))


@dataclass
class Setup:
    """A cell built from one seed, with its checked first rounds run."""

    cell: object
    server: object
    params0: dict                  # host copy of the initial weights
    data: dict
    partitions: list
    server_seed: int
    devices: list
    after: list = field(default_factory=list)     # host global, each round
    losses: list = field(default_factory=list)
    cohorts: list = field(default_factory=list)
    records: list = field(default_factory=list)


def build(cell, seed: int, devices) -> Setup:
    """Weights, data and the program's server for ``seed``; then the
    cell's checked rounds, which also compile every program the window
    runs."""
    import jax

    from program import client_mesh, make_server

    s = seeds(seed)
    cfg, spec = cell.config, cell.spec
    with jax.default_device(devices[0]):
        params0 = jax.jit(lambda k: cfg.init_params(k, spec))(
            jax.random.PRNGKey(s["init"]))
        data = {k: np.asarray(v) for k, v in
                cfg.make_data(jax.random.PRNGKey(s["data"]), spec).items()}
    n = spec["clients"] * spec["samples_per_client"]
    partitions = cfg.partition(n, spec["clients"], s["partition"])
    host0 = jax.device_get(params0)
    server = make_server(cell, cfg.program_loss(spec), params0,
                         data, partitions, s["server"],
                         mesh=client_mesh(devices))
    out = Setup(cell, server, host0, data, partitions, s["server"], devices)
    rounds = cell.traffic["check_rounds"]
    for r in range(rounds):
        rec = server.run_round()
        jax.block_until_ready(server.global_params)
        out.records.append(rec)
        out.losses.append(float(rec["mean_loss"]))
        out.cohorts.append([int(c) for c in rec["sampled"]])
        out.after.append(jax.device_get(server.global_params))
    return out


class CompileCounter:
    """Counts compilations and persistent-cache loads while active."""

    def __init__(self):
        import jax

        self.count, self.active = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, *_a, **_k):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, name, *_a, **_k):
        if self.active and name == "/jax/compilation_cache/cache_hits":
            self.count += 1


@dataclass
class Window:
    rounds: int = 0
    seconds: float = 0.0
    attempted: int = 0
    failed: int = 0
    nonfinite: int = 0
    wire_bytes: int = 0
    compiles: int = 0
    records: list = field(default_factory=list)
    error: str = ""
    trace: object = None


def measure(setup: Setup, seconds: float, trace: bool,
            counter: CompileCounter) -> Window:
    """Run rounds back to back until ``seconds`` have passed; each round
    ends when its new global model is ready. With ``trace`` the profiler
    records the rounds that end in the first ``TRACE_SECONDS`` of the
    window (at least one), read back once the window has closed."""
    import jax

    import xplane as trace_lib

    srv, cohort = setup.server, setup.cell.traffic["cohort"]
    w = Window()
    bytes0 = srv.comm_log.up_bytes + srv.comm_log.down_bytes
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    tracing = trace
    traced_rounds = 0
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    counter.active = True
    t0 = time.perf_counter()
    while True:
        try:
            with jax.profiler.TraceAnnotation("run_round"):
                rec = srv.run_round()
                jax.block_until_ready(srv.global_params)
        except Exception as e:  # the round failed: every update in it
            w.attempted += cohort
            w.failed += cohort
            w.error = f"{type(e).__name__}: {e}"
            break
        with jax.profiler.TraceAnnotation("between_rounds"):
            w.rounds += 1
            w.attempted += cohort
            bad = int(rec.get("nonfinite_losses", 0))
            if not math.isfinite(rec.get("mean_loss", float("nan"))):
                bad = max(bad, 1)
            w.nonfinite += bad
            w.failed += bad
            w.records.append(rec)
            elapsed = time.perf_counter() - t0
        if tracing and elapsed >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing, traced_rounds = False, w.rounds
        if elapsed >= seconds:
            break
    w.seconds = time.perf_counter() - t0
    counter.active = False
    w.compiles = counter.count
    if tracing:
        jax.profiler.stop_trace()
        traced_rounds = w.rounds
    if trace:
        w.trace = trace_lib.read(logdir, traced_rounds)
        shutil.rmtree(logdir, ignore_errors=True)
    w.wire_bytes = srv.comm_log.up_bytes + srv.comm_log.down_bytes - bytes0
    return w


def check_bytes(setup: Setup, records: list) -> int:
    """Largest gap between a round's charged bytes and the own count."""
    from check import wire_bytes_per_client

    traffic = setup.cell.traffic
    up, down = wire_bytes_per_client(setup.params0, traffic["uplink_codec"])
    if traffic["downlink_codec"] not in ("", "fp32"):
        raise ValueError("no byte count for a downlink codec")
    worst = 0
    for rec in records:
        n = int(rec["participants"])
        worst = max(worst, abs(int(rec["up_bytes"]) - n * up)
                    + abs(int(rec["down_bytes"]) - n * down))
    return worst


def run_reference(setup: Setup, dtype=None, precision=None):
    """The plain reference over the checked rounds, in float32 at the
    matmul precision the configuration names for it unless told
    otherwise."""
    import jax.numpy as jnp

    import reference

    cell = setup.cell
    loss = functools.partial(cell.config.reference_loss, spec=cell.spec)
    return reference.run(loss, cell.spec, cell.traffic,
                         setup.params0, setup.data, setup.partitions,
                         setup.server_seed, cell.traffic["check_rounds"],
                         dtype=dtype or jnp.float32,
                         precision=(precision
                                    or cell.spec["reference_precision"]),
                         block=cell.traffic["reference_block"])


def reference_numbers(setup: Setup, precision=None) -> tuple:
    """Run the plain reference over the checked rounds and compare.
    Returns (numbers, reference rounds)."""
    import check

    ref = run_reference(setup, precision=precision)
    numbers = check.compare(setup.params0, setup.after, setup.losses,
                            setup.cohorts, ref)
    log(losses=setup.losses, ref_losses=ref.losses,
        **check.leaf_table(setup.params0, setup.after, ref))
    return numbers, ref


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclass
class Context:
    """What a metric's ``compute`` reads."""

    cell: object
    setup_s: float
    window: Window
    peaks: dict
    devices: list

    @property
    def trace(self):
        return self.window.trace

    @property
    def device0(self) -> int:
        return min(d.id for d in self.devices)

    def steps_per_client(self) -> int:
        spec = self.cell.spec
        return (spec["samples_per_client"] // spec["batch"]) * spec["epochs"]

    def flops_per_round(self) -> float:
        """Required local-training FLOPs of one round: dense-equivalent
        forward + backward over the real samples, plus composing each
        FedPara weight and its factor gradients once per client and
        local step."""
        cfg, spec = self.cell.config, self.cell.spec
        steps = self.steps_per_client()
        per_client = (steps * spec["batch"] * cfg.flops_per_sample(spec)
                      + steps * cfg.compose_flops_per_step(spec))
        return self.cell.traffic["cohort"] * per_client


def breakdown(ctx: Context) -> dict:
    """Device 0's ten costliest operations in the traced window, and its
    ten longest idle gaps, each named by what the host was doing."""
    import xplane as trace_lib

    tr = ctx.trace
    lo, hi = tr.window
    ops = trace_lib.in_window(trace_lib.leaves(tr.devices.get(ctx.device0, [])),
                              lo, hi)
    by_name = {}
    for e in ops:
        by_name[e.name] = by_name.get(e.name, 0.0) + e.dur * 1e-9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(trace_lib.gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[trace_lib.host_activity(tr, (a + b) / 2),
                           (b - a) * 1e-9] for a, b in idle]}


def log(**fields):
    print(json.dumps(fields), file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> int:
    """One run of the command. Returns the exit code."""
    import manifest

    try:
        cell = manifest.resolve(ROOT, workload)
        import_program()
        devices = require_chips(cell.chips)
    except (KeyError, FileNotFoundError, NoChip) as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r}; nothing was run",
              file=sys.stderr)
        return 2
    result = execute(cell, devices, peaks[kind], seed, seconds, trace,
                     t_start)
    print(json.dumps(result), flush=True)
    return 0


def execute(cell, devices, peaks: dict, seed: int, seconds: float,
            trace: bool, t_start: float) -> dict:
    """Set-up, window and output check of ``cell`` on ``devices``; the
    result line as a dict. Prints the numbers compared, each with its
    limit, as the last lines on standard error."""
    import jax

    import check

    cache = enable_compile_cache()
    counter = CompileCounter()
    log(cell=cell.name, seed=seed, cache=cache, jax=jax.__version__,
        device_kind=devices[0].device_kind, chips=len(devices))

    setup = build(cell, seed, devices)
    setup_s = time.perf_counter() - t_start
    w = measure(setup, seconds, trace, counter)
    peak = memory_peak(devices)
    log(setup_s=setup_s, window_rounds=w.rounds, window_s=w.seconds,
        compiles_in_window=w.compiles, error=w.error or None)

    ctx = Context(cell, setup_s, w, peaks, devices)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.module.compute(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    extra = {}
    if trace:
        import xplane

        tr = w.trace
        lo, hi = tr.window
        busy = [xplane.busy_ns(xplane.leaves(tr.devices.get(d.id, [])),
                               lo, hi) * 1e-9 for d in devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = tr.window_s
        extra["breakdown"] = breakdown(ctx)

    # the output check, once the window has closed, the peak is read
    # and the program's state is freed
    records = setup.records + w.records
    bytes_off = check_bytes(setup, records)
    setup.server = None
    gc.collect()
    t_ref = time.perf_counter()
    numbers, _ = reference_numbers(setup)
    numbers["wire_bytes_off"] = bytes_off
    numbers["nonfinite_losses"] = w.nonfinite + sum(
        int(r.get("nonfinite_losses", 0)) for r in setup.records)
    log(reference_s=time.perf_counter() - t_ref, numbers=numbers)
    correct, checks = check.judge(numbers, cell.limits)
    correct = correct and not w.error and w.rounds > 0
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": w.attempted,
            "failed": w.failed, "metrics": metrics, "device": device,
            **extra, "checks": checks}
