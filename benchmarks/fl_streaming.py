"""Streaming-vs-batched-vs-sequential FL round benchmark.

Measures, per engine and cohort size, on a model-dominated FedPara MLP
task (the paper's regime: model bytes >> one round's minibatches):

1. ``peak_bytes``: XLA ``memory_analysis`` of the engine's compiled
   round program (argument + temp + output live bytes — the program's
   high-water mark). The batched engine's grows linearly with C (the
   stacked (C, model) params/opt/upload trees); the streaming engine's
   is pinned at O(chunk · model + model) plus the round's data batches.
2. ``round_s``: measured steady-state wall-clock per round (median,
   compile excluded).
3. ``scale_1024``: a REAL 1024-client streaming round executed on this
   host, next to the batched program's compile-time byte estimate at
   the same cohort (lowered from ShapeDtypeStructs — nothing is
   allocated): the stacked engine needs ~64x the streaming high-water
   mark there, which is exactly why it cannot hold large cohorts.
4. ``kernel``: ``cost_analysis`` bytes-accessed of the fused
   dequant-accumulate kernel vs the decode-then-reduce dense path
   (dequantize the (C, L) int8 stack to fp32, then reduce), plus the
   analytic roofline. On CPU hosts the kernel runs in INTERPRET mode
   (grid emulation inflates its measured bytes); the analytic terms are
   the hardware-relevant story: C·L + 8·L vs 9·C·L bytes.
5. ``fleet``: 10k / 100k / 1M-client fleets at 1% participation on the
   fleet substrate (arena client state + FleetTrace sampling + chunked
   batch streaming — docs/fleet.md): measured steady-state round
   latency and per-size host RSS (``ru_maxrss``, one subprocess per
   size), with the max/min RSS ratio pinned flat (≤ 1.5x acceptance).

Writes ``BENCH_streaming.json`` (canonical under benchmarks/artifacts/,
mirrored to the repo root for the perf-trajectory tooling).

Run: PYTHONPATH=src python -m benchmarks.fl_streaming [--clients 256]
     PYTHONPATH=src python -m benchmarks.fl_streaming --fleet-smoke
"""
import argparse
import json
import time


def build_server(engine: str, clients: int, chunk: int = 16, seed: int = 0,
                 samples_per_client: int = 32):
    """Model-dominated miniature: wide FedPara MLP, one local epoch, so
    round memory is parameter traffic, not data."""
    import jax

    from repro.configs.base import ParamCfg
    from repro.data import iid_partition, make_image_dataset, train_test_split
    from repro.fl import ClientConfig, FLServer, ServerConfig, make_strategy
    from repro.nn import recurrent as rec

    n_train = samples_per_client * clients
    ds = make_image_dataset(int(n_train / 0.9) + 1, 10, size=16, channels=1,
                            noise=0.3, seed=seed)
    data = {"x": ds["x"].reshape(len(ds["y"]), -1), "y": ds["y"]}
    tr, _ = train_test_split(data)
    cfg = rec.MLPConfig(in_dim=256, hidden=512, classes=10,
                        param=ParamCfg(kind="fedpara", gamma=0.5,
                                       min_dim_for_factorization=8))
    params = rec.init_mlp_model(jax.random.PRNGKey(seed), cfg)
    parts = iid_partition(len(tr["y"]), clients, seed)

    def loss_fn(p, b):
        return rec.mlp_loss(p, cfg, b)

    return FLServer(loss_fn, params, tr, parts, make_strategy("fedavg"),
                    ClientConfig(lr=0.1, batch=32, epochs=1),
                    ServerConfig(clients=clients, participation=1.0,
                                 rounds=1, engine=engine, client_chunk=chunk,
                                 uplink_codec="int8", seed=seed))


def _spy_program(srv):
    """Intercept the engine's jitted round program to capture its call
    args (first call only, then the spy steps aside), so the identical
    computation can be re-lowered for memory_analysis."""
    eng = srv._stream if srv._stream is not None else srv._engine
    captured = {}
    orig = eng._program

    def spy(*args):
        captured["args"] = args
        eng._program = orig
        return orig(*args)

    eng._program = spy
    return eng, captured


def _mem_stats(fn, args, donate=()):
    import jax

    # abstract the captured args: donated buffers are already deleted,
    # and lowering only needs shapes/dtypes anyway
    def abstract(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    args = jax.tree.map(abstract, args)
    co = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    ma = co.memory_analysis()
    if ma is None:
        return None
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "peak_bytes": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                          + ma.output_size_in_bytes),
    }


def engine_row(engine: str, clients: int, chunk: int, rounds: int = 3) -> dict:
    srv = build_server(engine, clients, chunk)
    row = {"engine": engine, "clients": clients}
    if engine == "streaming":
        row["client_chunk"] = chunk
    if engine == "sequential":
        srv.run_round()   # warmup
    else:
        eng, captured = _spy_program(srv)
        srv.run_round()   # warmup: compile + capture args
        donate = (0, 1) if engine == "streaming" else ()
        mem = _mem_stats(eng._round_program, captured["args"], donate)
        if mem:
            row.update(mem)
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        srv.run_round()
        times.append(time.perf_counter() - t0)
    times.sort()
    row["round_s"] = times[len(times) // 2]
    return row


def scale_1024(chunk: int = 16) -> dict:
    """A real 1024-client streaming round, plus the batched program's
    compile-time footprint at the same cohort (no buffers allocated)."""
    import jax

    C = 1024
    srv = build_server("streaming", C, chunk, samples_per_client=32)
    eng, captured = _spy_program(srv)
    t0 = time.perf_counter()
    rec = srv.run_round()
    wall = time.perf_counter() - t0
    stream_mem = _mem_stats(eng._round_program, captured["args"], (0, 1))

    # batched at 1024: lower from ShapeDtypeStructs captured at a small
    # cohort, with every client-stacked leading axis rewritten to 1024
    small_c = 64
    bsrv = build_server("batched", small_c, chunk, samples_per_client=32)
    beng, bcap = _spy_program(bsrv)
    bsrv.run_round()

    def scale_axis(x):
        shape = tuple(x.shape)
        assert shape and shape[0] == small_c, shape
        return jax.ShapeDtypeStruct((C,) + shape[1:], x.dtype)

    # ClientBatch._round_program args: only the client-stacked positions
    # get their leading axis rewritten; lr / server_state / agg_target /
    # down_payload (6, 8, 9, 10) are cohort-size independent
    client_stacked = {0, 1, 2, 3, 4, 5, 7}
    bargs = tuple(
        jax.tree.map(scale_axis, a) if i in client_stacked else a
        for i, a in enumerate(bcap["args"]))
    batched_mem = _mem_stats(beng._round_program, bargs)
    out = {
        "clients": C,
        "client_chunk": chunk,
        "streaming_round_s": wall,
        "streaming_participants": rec["participants"],
        "streaming": stream_mem,
        "batched_estimated": batched_mem,
    }
    if stream_mem and batched_mem:
        out["batched_over_streaming_peak"] = (
            batched_mem["peak_bytes"] / stream_mem["peak_bytes"])
    return out


def kernel_rows(C: int = 256, L: int = 1 << 16) -> dict:
    """Fused dequant-accumulate vs decode-then-reduce, cost_analysis
    bytes accessed + analytic roofline."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as SDS

    from repro.kernels import agg

    def cost_bytes(fn, *args):
        c = jax.jit(fn).lower(*args).compile()
        d = c.cost_analysis() or {}
        if isinstance(d, (list, tuple)):
            d = d[0] if d else {}
        return float(d.get("bytes accessed", 0.0))

    acc = SDS((L,), jnp.float32)
    q = SDS((C, L), jnp.int8)
    coeff = SDS((C,), jnp.float32)
    interpret = jax.default_backend() != "tpu"
    fused = cost_bytes(
        lambda a, qq, cc: agg.dequant_acc(a, qq, cc, interpret=interpret),
        acc, q, coeff)

    def dense(a, qq, cc):
        deq = qq.astype(jnp.float32)      # materialized (C, L) dequant
        return a + jnp.tensordot(cc, deq, axes=1)

    dense_b = cost_bytes(dense, acc, q, coeff)
    return {
        "C": C, "L": L,
        "fused_bytes": fused,
        "decode_then_reduce_bytes": dense_b,
        "reduction": dense_b / max(fused, 1.0),
        # ideal HBM traffic: wire once at 1 B/elt + accumulator r/w
        "analytic_fused_bytes": C * L + 8.0 * L,
        # int8 read + fp32 write + fp32 read of the dequant stack + out
        "analytic_dense_bytes": 9.0 * C * L + 8.0 * L,
        "pallas_interpret_emulation": interpret,
    }


def build_fleet_server(clients: int, participation: float = 0.01,
                       chunk: int = 64, seed: int = 0, rounds: int = 2):
    """Fleet-scale configuration: the data pool, model and cohort stay
    fixed while the FLEET size grows — virtual O(1) per-client
    partition views over a shared pool, a FleetTrace for O(cohort)
    sampling, the device-resident arena for client state, and chunked
    batch streaming so no O(cohort·data) host stack ever exists."""
    import jax

    from repro.configs.base import ParamCfg
    from repro.data import VirtualPartitions, make_image_dataset, \
        train_test_split
    from repro.fl import ClientConfig, FLServer, FleetTrace, ServerConfig, \
        make_strategy
    from repro.nn import recurrent as rec

    ds = make_image_dataset(4096, 10, size=8, channels=1, noise=0.3,
                            seed=seed)
    data = {"x": ds["x"].reshape(len(ds["y"]), -1), "y": ds["y"]}
    tr, _ = train_test_split(data)
    cfg = rec.MLPConfig(in_dim=64, hidden=64, classes=10,
                        param=ParamCfg(kind="fedpara", gamma=0.5,
                                       min_dim_for_factorization=8))
    params = rec.init_mlp_model(jax.random.PRNGKey(seed), cfg)
    parts = VirtualPartitions(pool_size=len(tr["y"]), clients=clients,
                              samples_per_client=32, seed=seed)
    trace = FleetTrace(clients=clients, dropout=0.05,
                       diurnal_amplitude=0.3, seed=seed)

    def loss_fn(p, b):
        return rec.mlp_loss(p, cfg, b)

    return FLServer(loss_fn, params, tr, parts, make_strategy("fedavg"),
                    ClientConfig(lr=0.1, batch=16, epochs=1),
                    ServerConfig(clients=clients, participation=participation,
                                 rounds=rounds, engine="streaming",
                                 client_chunk=chunk, uplink_codec="int8",
                                 state_store="arena", data_stream="chunked",
                                 trace=trace, seed=seed))


def _host_rss_peak_kb() -> float:
    """This process's host-RSS high-water mark, in KB. Prefers
    ``/proc/self/status`` VmHWM, which resets on exec — ``ru_maxrss``
    survives ``fork``+exec, so a subprocess forked from a large parent
    would report the PARENT's footprint. Falls back to ``ru_maxrss``
    off Linux."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return float(line.split()[1])
    except OSError:
        pass
    import resource

    return float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)


def fleet_one(clients: int, rounds: int = 2, participation: float = 0.01,
              chunk: int = 64) -> dict:
    """One fleet config measured IN THIS PROCESS: run ``rounds`` real
    streaming rounds and report median round latency plus the process
    host-RSS high-water mark (monotonic per process, which is why the
    parent launches one subprocess per fleet size)."""
    srv = build_fleet_server(clients, participation, chunk, rounds=rounds)
    times, participants = [], 0
    for _ in range(rounds):
        t0 = time.perf_counter()
        rec = srv.run_round()
        times.append(time.perf_counter() - t0)
        participants = rec["participants"]
    times.sort()
    rss_kb = _host_rss_peak_kb()
    return {
        "clients": clients,
        "participation": participation,
        "cohort": len(rec["sampled"]),
        "participants": participants,
        "client_chunk": chunk,
        "rounds": rounds,
        "round_s": times[(len(times) - 1) // 2],   # steady-state median
        "first_round_s": max(times),               # includes compile
        "host_rss_mb": rss_kb / 1024.0,
    }


def fleet_section(sizes=(10_000, 100_000, 1_000_000), rounds: int = 2,
                  participation: float = 0.01) -> dict:
    """Acceptance: host RSS stays flat (within 1.5x) from 10k to 1M
    clients at 1% participation. Each size runs in a fresh subprocess
    so ``ru_maxrss`` measures that fleet alone.

    The children use the device, and a device belongs to one process:
    a parent that has started JAX would hold it, and the children would
    fail or hang. So this runs only in a process that has not imported
    JAX yet."""
    import os
    import subprocess
    import sys

    if "jax" in sys.modules:
        raise RuntimeError(
            "fleet_section starts one device-using child per fleet size; "
            "call it before this process imports JAX")
    rows = []
    for n in sizes:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.fl_streaming",
             "--fleet-one", str(n), "--rounds", str(rounds),
             "--participation", str(participation)],
            capture_output=True, text=True, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env={**os.environ, "PYTHONPATH": "src"})
        rows.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    rss = [r["host_rss_mb"] for r in rows]
    return {
        "participation": participation,
        "rounds": rounds,
        "rows": rows,
        "rss_max_over_min": max(rss) / min(rss),
        "rss_flat_within_1p5x": max(rss) / min(rss) <= 1.5,
    }


def fleet_smoke(clients: int = 10_000, rounds: int = 2,
                rss_budget_mb: float = 4096.0) -> dict:
    """Fast blocking-CI gate: a 10k-client 1%-participation fleet round
    must complete and the process must stay under the host-RSS budget."""
    row = fleet_one(clients, rounds=rounds)
    row["rss_budget_mb"] = rss_budget_mb
    row["ok"] = row["host_rss_mb"] < rss_budget_mb and row["cohort"] > 0
    return row


def run_bench(clients: int = 256, chunk: int = 16, rounds: int = 3,
              fleet_sizes=(10_000, 100_000, 1_000_000),
              fleet: dict = None) -> dict:
    """The whole artifact. ``fleet`` is a :func:`fleet_section` result
    measured earlier; without one, a non-empty ``fleet_sizes`` runs the
    fleet children first, while this process has not touched JAX."""
    if fleet is None and fleet_sizes:
        fleet = fleet_section(fleet_sizes)
    rows = [
        engine_row("sequential", min(clients, 64), chunk, rounds=1),
        engine_row("batched", clients, chunk, rounds=rounds),
        engine_row("streaming", clients, chunk, rounds=rounds),
    ]
    bat = next(r for r in rows if r["engine"] == "batched")
    stream = next(r for r in rows if r["engine"] == "streaming")
    art = {
        "benchmark": "fl_streaming",
        "what": "peak live bytes + round latency per FL engine; fused "
                "dequant-aggregate kernel traffic",
        "engines": rows,
        "scale_1024": scale_1024(chunk),
        "kernel": kernel_rows(),
    }
    if fleet is not None:
        art["fleet"] = fleet
    if "peak_bytes" in bat and "peak_bytes" in stream:
        art["peak_reduction_at_%d" % clients] = (
            bat["peak_bytes"] / stream["peak_bytes"])
        art["latency_ratio_stream_over_batched"] = (
            stream["round_s"] / bat["round_s"])
    from benchmarks.common import write_artifact

    write_artifact("BENCH_streaming.json", art)
    return art


def csv_rows(clients: int = 256, chunk: int = 16, fleet: dict = None):
    """Rows for benchmarks.run CSV: (name, us_per_call, derived).
    ``benchmarks.run`` has touched JAX before it gets here, so it runs
    the fleet sweep (device-using children) first and passes it in as
    ``fleet``; without it the ``fl_fleet_<N>c`` row is left out."""
    art = run_bench(clients, chunk, fleet_sizes=(), fleet=fleet)
    rows = []
    for r in art["engines"]:
        name = f"fl_{r['engine']}_{r['clients']}c"
        derived = (f"peak_mb={r['peak_bytes'] / 1e6:.1f}"
                   if "peak_bytes" in r else "")
        rows.append((name, r["round_s"] * 1e6, derived))
    k = art["kernel"]
    rows.append(("dequant_agg_kernel", 0.0,
                 f"bytes_reduction={k['reduction']:.2f}x"))
    s = art["scale_1024"]
    rows.append(("fl_streaming_1024c", s["streaming_round_s"] * 1e6,
                 f"batched_peak_est_x={s.get('batched_over_streaming_peak', 0):.1f}"))
    if "fleet" in art:
        f = art["fleet"]
        biggest = f["rows"][-1]
        rows.append((f"fl_fleet_{biggest['clients']}c",
                     biggest["round_s"] * 1e6,
                     f"rss_max_over_min={f['rss_max_over_min']:.2f}x"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--participation", type=float, default=0.01,
                    help="fleet modes: cohort fraction of the fleet")
    ap.add_argument("--fleet-one", type=int, default=0, metavar="N",
                    help="measure ONE N-client fleet config in this "
                         "process and print its JSON row (used by the "
                         "parent, one subprocess per size so ru_maxrss "
                         "is per-config)")
    ap.add_argument("--fleet-smoke", action="store_true",
                    help="fast CI gate: 10k-client fleet round under a "
                         "host-RSS budget; exit 1 on failure")
    args = ap.parse_args()
    if args.fleet_one:
        print(json.dumps(fleet_one(args.fleet_one, rounds=args.rounds,
                                   participation=args.participation)))
        return
    if args.fleet_smoke:
        row = fleet_smoke(rounds=args.rounds)
        print(json.dumps(row, indent=1))
        if not row["ok"]:
            raise SystemExit("fleet smoke failed: RSS "
                             f"{row['host_rss_mb']:.0f} MB over budget "
                             f"{row['rss_budget_mb']:.0f} MB")
        return
    art = run_bench(args.clients, args.chunk, args.rounds)
    print(json.dumps(art, indent=1))


if __name__ == "__main__":
    main()
