"""Bring-up smoke test: the federated round on a TPU chip.

Trains the paper's Shakespeare character LSTM at its published widths
(``LSTMConfig()``: vocab 80, embed 8, hidden 256, 2 layers, every gate
matrix FedPara-factorized) through the library's entry point,
``FLServer(...).run_round()``:

* system: the streaming engine with ``ParamCfg(use_pallas=True)`` —
  local steps through the fused custom-VJP ``fedpara_matmul`` kernels,
  an int8 uplink, the fused ``dequant_acc`` fold, client state in the
  device arena;
* reference: the same seed through the sequential engine on plain XLA
  ops at ``highest`` matmul precision.

The two must agree within the bounds below, every round's loss must be
finite, and both must charge the same wire bytes. Data is a synthetic
Shakespeare-like character corpus and the weights are random, both made
from ``--seed``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the round on a 4-chip
                                     # ("clients",) mesh vs on one chip

Exits non-zero, printing no result, when JAX finds no TPU. The last line
of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.data import iid_partition, make_char_corpus  # noqa: E402
from repro.fl import (  # noqa: E402
    ClientConfig, FLServer, ServerConfig, make_strategy)
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.nn.recurrent import LSTMConfig, init_lstm, lstm_loss  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Bounds:
    """How far two runs of the same rounds may drift apart.

    Parameters are compared in units of one int8 quantum of each leaf
    (max|leaf| / 127). Both runs quantize every upload with the same
    stochastic-rounding keys, so their wires agree except where numeric
    noise carries a value across a rounding boundary; such a flip moves
    one client's upload by one quantum, and 1/cohort of it reaches the
    mean. Losses (per-round mean training loss, held-out next-character
    loss) are in nats, about 4.4 = ln 80 at the start.
    """

    max_quanta: float
    mean_quanta: float
    loss: float

    def widened(self, floor: dict, factor: float) -> "Bounds":
        """These bounds plus ``factor`` times a measured drift."""
        return Bounds(
            self.max_quanta + factor * floor["param_max_quanta"],
            self.mean_quanta + factor * floor["param_mean_quanta"],
            self.loss + factor * max(floor["train_loss_diff"],
                                     floor["eval_loss_diff"]))


# Same matmul precision on both sides (fp32 accumulation everywhere):
# only accumulation order differs, flips are rare and scattered. On the
# CPU the streaming engine sits 0.063 quanta (one flip) from the
# sequential one at worst and 6e-5 on average, losses within 2e-7; a
# dX2/dY2 gradient 30% off moves a small model's leaves by 0.24 quanta
# on average and its losses by 3e-3.
MATCHED = Bounds(max_quanta=0.5, mean_quanta=0.01, loss=5e-4)
# At JAX's default precision the TPU runs fp32 matmuls as single bf16
# passes, XLA's and the Mosaic kernels' alike (a fused FedPara product
# at the LSTM's shapes then sits 4e-3..5e-3 of max|y| from a float64
# one on a v5e, 2e-7 at ``highest``), and every upload and loss moves
# with them. How far is
# measured in the same run: the sequential XLA reference at default
# precision against itself at ``highest`` (the floor). The Pallas round
# at default precision may drift from the ``highest`` reference by
# MATCHED plus this many floors; a wrong kernel or a lost client lands
# orders of magnitude further out.
FLOOR_FACTOR = 3.0


@dataclasses.dataclass(frozen=True)
class Setup:
    """One run's shape; the defaults are the published configuration."""

    model: LSTMConfig = LSTMConfig()
    clients: int = 127          # + 1 scratch row = 128 arena rows: 4 | 128
    cohort: int = 16
    chunk: int = 16
    batch: int = 32
    seq_len: int = 80
    samples_per_client: int = 64
    test_seqs: int = 256
    rounds: int = 3
    lr: float = 1.0
    seed: int = 0


class ParityError(RuntimeError):
    """A run disagreed with what it is compared with."""


def _require(ok, what: str) -> None:
    if not ok:
        raise ParityError(what)


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _task(s: Setup):
    n_train = s.clients * s.samples_per_client
    corpus = make_char_corpus(n_train + s.test_seqs, s.seq_len,
                              vocab=s.model.vocab, seed=s.seed)
    return ({"tokens": corpus[:n_train]}, {"tokens": corpus[n_train:]},
            iid_partition(n_train, s.clients, s.seed))


def make_server(s: Setup, task, *, engine: str, use_pallas: bool,
                mesh=None) -> FLServer:
    """The LSTM federation through the public API: the streaming engine
    keeps client state in the device arena, the sequential reference in
    host dicts (the only store it has). The held-out loss runs on the
    same layers as training, Pallas kernels included; on a mesh it reads
    the global model replicated over the devices."""
    cfg = dataclasses.replace(
        s.model, param=dataclasses.replace(s.model.param,
                                           use_pallas=use_pallas))
    train, test, parts = task
    eval_loss = jax.jit(lambda p: lstm_loss(p, cfg, test))

    def loss_fn(p, b):
        return lstm_loss(p, cfg, b)

    return FLServer(
        loss_fn, init_lstm(jax.random.PRNGKey(s.seed), cfg), train, parts,
        make_strategy("fedavg"),
        ClientConfig(lr=s.lr, batch=s.batch, epochs=1),
        ServerConfig(clients=s.clients, participation=s.cohort / s.clients,
                     rounds=s.rounds, engine=engine, client_chunk=s.chunk,
                     uplink_codec="int8",
                     state_store="arena" if engine == "streaming" else "dict",
                     seed=s.seed),
        eval_fn=lambda p: float(eval_loss(p)), mesh=mesh)


def run_rounds(srv: FLServer, rounds: int):
    """``rounds`` rounds; each time is host clock around ``run_round``
    up to ``block_until_ready`` of the new global model."""
    recs, secs = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        rec = srv.run_round()
        jax.block_until_ready(srv.global_params)
        secs.append(time.perf_counter() - t0)
        recs.append(rec)
    return recs, secs


def _times(secs) -> dict:
    """Round 1 includes compiling; later rounds reuse the programs."""
    return {"first_round_s": secs[0], "steady_round_s": secs[1:]}


def compare(a: FLServer, recs_a, b: FLServer, recs_b) -> dict:
    """Hold run ``a`` to run ``b`` (the one it is compared with) where
    they must agree exactly: same cohorts and arrivals, identical wire
    bytes, finite losses and parameters. Returns the numeric residuals,
    which :func:`check` holds to a :class:`Bounds`."""
    loss_d = eval_d = 0.0
    for ra, rb in zip(recs_a, recs_b, strict=True):
        r = ra["round"]
        _require(ra["sampled"] == rb["sampled"]
                 and ra["arrived_mask"] == rb["arrived_mask"],
                 f"round {r}: cohorts differ")
        _require((ra["up_bytes"], ra["down_bytes"])
                 == (rb["up_bytes"], rb["down_bytes"]),
                 f"round {r}: wire bytes differ: "
                 f"{(ra['up_bytes'], ra['down_bytes'])} vs "
                 f"{(rb['up_bytes'], rb['down_bytes'])}")
        for rec in (ra, rb):
            _require(np.isfinite(rec["mean_loss"])
                     and rec["nonfinite_losses"] == 0
                     and np.isfinite(rec["eval"]),
                     f"round {r}: non-finite loss {rec['mean_loss']}")
        loss_d = max(loss_d, abs(ra["mean_loss"] - rb["mean_loss"]))
        eval_d = max(eval_d, abs(ra["eval"] - rb["eval"]))
    _require((a.comm_log.up_bytes, a.comm_log.down_bytes)
             == (b.comm_log.up_bytes, b.comm_log.down_bytes),
             "total wire bytes differ")

    worst_max = worst_mean = 0.0
    flat_b = jax.tree_util.tree_flatten_with_path(b.global_params)[0]
    for (path, y), x in zip(flat_b, jax.tree.leaves(a.global_params),
                            strict=True):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        _require(np.isfinite(x).all(),
                 f"non-finite {jax.tree_util.keystr(path)}")
        quantum = max(np.abs(y).max(), 1e-12) / 127.0
        d = np.abs(x - y) / quantum
        worst_max = max(worst_max, d.max())
        worst_mean = max(worst_mean, d.mean())
    return {"param_max_quanta": float(worst_max),
            "param_mean_quanta": float(worst_mean),
            "train_loss_diff": loss_d, "eval_loss_diff": eval_d,
            "up_bytes": a.comm_log.up_bytes,
            "down_bytes": a.comm_log.down_bytes,
            "eval": [r["eval"] for r in recs_a],
            "train_loss": [r["mean_loss"] for r in recs_a]}


def check(out: dict, bounds: dict) -> None:
    """Raise :class:`ParityError` naming every residual of ``out[name]``
    over ``bounds[name]``, with all of ``out``'s residuals attached."""
    over = []
    for name, b in bounds.items():
        r = out[name]
        for key, lim in (("param_max_quanta", b.max_quanta),
                         ("param_mean_quanta", b.mean_quanta),
                         ("train_loss_diff", b.loss),
                         ("eval_loss_diff", b.loss)):
            if not r[key] <= lim:
                over.append(f"{name}.{key} = {r[key]} > {lim}")
    _require(not over, "; ".join(over) + "\n" + json.dumps(out))


def smoke_phase(s: Setup = Setup()) -> dict:
    """Default phase: the streaming Pallas round against the sequential
    XLA reference at ``highest`` precision, once at ``highest`` too
    (MATCHED bounds test the kernels' arithmetic) and once at JAX's
    default precision, as users run it (bounds widened by the
    reference's own drift at default precision). Raises
    :class:`ParityError` on any disagreement."""
    task = _task(s)
    with jax.default_matmul_precision("highest"):
        ref = make_server(s, task, engine="sequential", use_pallas=False)
        ref_recs, ref_secs = run_rounds(ref, s.rounds)
        exact = make_server(s, task, engine="streaming", use_pallas=True)
        exact_recs, exact_secs = run_rounds(exact, s.rounds)
    floor = make_server(s, task, engine="sequential", use_pallas=False)
    floor_recs, _ = run_rounds(floor, s.rounds)
    srv = make_server(s, task, engine="streaming", use_pallas=True)
    recs, secs = run_rounds(srv, s.rounds)
    out = {"highest_precision": compare(exact, exact_recs, ref, ref_recs),
           "default_precision": compare(srv, recs, ref, ref_recs),
           "reference_default_vs_highest": compare(floor, floor_recs,
                                                   ref, ref_recs),
           "streaming": _times(secs),
           "streaming_highest": _times(exact_secs),
           "sequential_reference": _times(ref_secs)}
    default = MATCHED.widened(out["reference_default_vs_highest"],
                              FLOOR_FACTOR)
    out["default_bounds"] = dataclasses.asdict(default)
    check(out, {"highest_precision": MATCHED, "default_precision": default})
    return out


def mesh_phase(devices, s: Setup = Setup()) -> dict:
    """``--chips N``: the streaming round on an N-device ``("clients",)``
    mesh vs the same round on one device. Reads the arena's and the
    chunk's shardings instead of assuming them."""
    n = len(devices)
    task = _task(s)
    single = make_server(s, task, engine="streaming", use_pallas=True)
    one_recs, one_secs = run_rounds(single, s.rounds)

    mesh = Mesh(np.array(devices), ("clients",))
    srv = make_server(s, task, engine="streaming", use_pallas=True, mesh=mesh)
    recs, secs = run_rounds(srv, s.rounds)
    placed = srv.round_engine.placement   # the last round's chunk inputs

    def split_rows(arr, axis: int, spec) -> list:
        sh = arr.sharding
        _require(isinstance(sh, NamedSharding) and sh.spec == spec
                 and len(sh.device_set) == n,
                 f"{spec} not sharded over {n} devices: {sh}")
        rows = sorted(len(range(*idx[axis].indices(arr.shape[axis])))
                      for idx in sh.devices_indices_map(arr.shape).values())
        _require(rows == [arr.shape[axis] // n] * n,
                 f"uneven shards {rows} of {arr.shape}")
        return rows

    out = {"mesh_vs_one_device": compare(srv, recs, single, one_recs),
           "arena_rows_per_device": split_rows(
               srv.arena.participation, 0, P("clients")),
           "chunk_clients_per_device": split_rows(
               placed["batches"], 1, P(None, "clients")),
           "mask_clients_per_device": split_rows(
               placed["mask"], 1, P(None, "clients")),
           "mesh": _times(secs),
           "one_device": _times(one_secs)}
    check(out, {"mesh_vs_one_device": MATCHED})
    return out


def check_mosaic(s: Setup = Setup()) -> dict:
    """Compile the round's kernels at the model's shapes, interpret mode
    left to the library's own choice, and require Mosaic
    (``tpu_custom_call``) in the compiled text: forward + VJP of every
    distinct gate-factor shape, and the int8 fold."""
    params = jax.eval_shape(lambda: init_lstm(jax.random.PRNGKey(0),
                                              s.model))
    shapes = sorted({(g["x1"].shape[0], g["y1"].shape[0], g["x1"].shape[1])
                     for cell in params["cells"] for g in (cell["wi"],
                                                           cell["wh"])})

    def compiled(f, *args):
        return jax.jit(f).lower(*args).compile().as_text()

    out = {}
    for m, n, r in shapes:
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        args = (f32(s.batch, m), f32(m, r), f32(n, r), f32(m, r), f32(n, r))

        def loss(*a):
            return ops.fedpara_matmul(*a).sum()

        text = compiled(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args)
        out[f"fedpara_{m}x{n}_r{r}"] = text.count("tpu_custom_call")
    n_payload = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    text = compiled(
        lambda acc, q, c: ops.dequant_acc(acc, q, c),
        jax.ShapeDtypeStruct((n_payload,), jnp.float32),
        jax.ShapeDtypeStruct((s.cohort, n_payload), jnp.int8),
        jax.ShapeDtypeStruct((s.cohort,), jnp.float32))
    out["dequant_acc_int8"] = text.count("tpu_custom_call")
    for name, count in out.items():
        _require(count > 0, f"{name}: no Mosaic kernel in the compiled text")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-chip mesh round and the "
                         "1-chip round it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "visible", file=sys.stderr)
        return 1
    _log(cache_dir=enable_compile_cache(), jax=jax.__version__,
         device_kind=devices[0].device_kind, visible=len(devices))
    s = Setup(seed=args.seed)
    if args.chips == 1:
        _log(phase="mosaic", kernels=check_mosaic(s))
        _log(phase="streaming_vs_sequential", **smoke_phase(s))
    else:
        _log(phase="mesh_vs_one_device",
             **mesh_phase(devices[:args.chips], s))
    stats = devices[0].memory_stats() or {}
    _log(peak_bytes_in_use_device0=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
