"""Serving driver: FL checkpoint -> planned decode engine.

Loads a trained federation from a :class:`CheckpointManager` directory
(``--ckpt``; without one it trains a tiny pFedPara federation first so
the full checkpoint->serve handoff always runs) and serves it through
:class:`repro.serve.ServeEngine`:

* ``--mode {precompose,fused,auto}`` — per-layer weight layout: the
  load-time composed cache (fp16 / int8 + per-channel scales), the
  never-materialize fused path (Gram identity / tile kernel), or the
  cost-model pick. The per-layer decision table is printed.
* ``--users N`` — pFedPara: serve a rotating cohort of N distinct
  users per step from the resident :class:`repro.serve.UserArena`.
* ``--smoke`` — CI gate: tiny checkpoint, decode 8 tokens under BOTH
  modes, assert cross-mode parity and exactly zero recompiles after
  the single warmup step.

Timing discipline (the numbers this driver reports):

* prefill and decode are timed SEPARATELY — they answer different
  questions (time-to-first-token vs steady-state tokens/sec);
* one untimed warmup step triggers compilation before any clock
  starts, so reported numbers are steady-state;
* every timed region ends with ``jax.block_until_ready`` INSIDE the
  region — async dispatch otherwise stops the clock before the device
  finishes.

Runs for real on CPU (Pallas serve kernels auto-disable off-TPU; the
XLA paths are numerically identical).
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import iid_partition, make_token_lm_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.nn.transformer import ModelOptions, build_model
from repro.serve import ServeEngine


def tiny_fl_checkpoint(workdir: str, *, arch: str = "qwen3-8b",
                       rounds: int = 2, clients: int = 4,
                       kind: str = "pfedpara", seed: int = 0):
    """Train a miniature federation and checkpoint it; returns
    ``(ckpt_dir, cfg, opts)`` ready for ``ServeEngine.from_checkpoint``.

    This is the demo/CI path — real deployments pass ``--ckpt`` from a
    full training run instead.
    """
    from repro.fl.client import ClientConfig
    from repro.fl.server import FLServer, ServerConfig
    from repro.fl.strategies import make_strategy

    cfg = get_arch(arch).reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, param=dataclasses.replace(
        cfg.param, kind=kind, min_dim_for_factorization=8, gamma=0.5))
    opts = ModelOptions(attn_chunk=8, ssm_chunk=8, logit_chunk=16,
                        dtype=jnp.float32)
    model = build_model(cfg, opts)
    params = model.init_params(jax.random.PRNGKey(seed))

    toks = make_token_lm_dataset(12 * clients, 16, cfg.vocab_size, seed=seed)
    parts = iid_partition(len(toks), clients)
    personalization = "pfedpara" if kind == "pfedpara" else "none"
    srv = FLServer(lambda p, b: model.loss(p, b), params,
                   {"tokens": toks}, parts, make_strategy("fedavg"),
                   ClientConfig(lr=0.05, batch=8, epochs=1),
                   ServerConfig(clients=clients, participation=1.0,
                                rounds=rounds,
                                personalization=personalization))
    srv.run()
    srv.save_checkpoint(CheckpointManager(workdir))
    return workdir, cfg, opts


def _print_plan(eng: ServeEngine) -> None:
    rows = eng.decision_table()
    by_mode = {}
    for r in rows:
        by_mode[r["mode"]] = by_mode.get(r["mode"], 0) + 1
    print(f"plan: {len(rows)} layers "
          + " ".join(f"{k}={v}" for k, v in sorted(by_mode.items()))
          + f" | serve weights {eng.state_bytes() / 1e6:.2f} MB"
          + (f" | user arena {eng.arena_bytes() / 1e6:.2f} MB"
             f" ({eng.arena.n_users} residents)" if eng.arena else ""))
    print(f"{'path':40s} {'m':>6s} {'n':>6s} {'r':>4s} "
          f"{'mode':>10s} {'impl':>14s} {'B*':>5s}")
    for r in rows:
        print(f"{r['path'][:40]:40s} {r['m']:6d} {r['n']:6d} {r['r']:4d} "
              f"{r['mode']:>10s} {r['impl']:>14s} "
              f"{r['crossover_batch']:5d}")


def serve_timed(eng: ServeEngine, prompts, gen_len: int,
                user_ids=None) -> dict:
    """Warmed-up prefill + decode with the timing discipline from the
    module docstring; returns the report dict (times in seconds)."""
    tokens = jnp.asarray(prompts)
    B, S = tokens.shape

    # untimed warmup: compile prefill + decode on a throwaway cache
    wcache = eng.init_cache(B, S + gen_len)
    wcache, wlogits = eng.prefill(tokens, wcache, user_ids)
    wtok = jnp.argmax(wlogits, -1)[:, None]
    wlogits, wcache = eng.decode_step(wcache, wtok, S, user_ids)
    jax.block_until_ready(wlogits)
    del wcache

    cache = eng.init_cache(B, S + gen_len)
    t0 = time.perf_counter()
    cache, logits = eng.prefill(tokens, cache, user_ids)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0

    out = []
    tok = jnp.argmax(logits, -1)[:, None]
    t0 = time.perf_counter()
    for i in range(gen_len):
        out.append(np.asarray(tok)[:, 0])
        logits, cache = eng.decode_step(cache, tok, S + i, user_ids)
        tok = jnp.argmax(logits, -1)[:, None]
    jax.block_until_ready(logits)
    decode_s = time.perf_counter() - t0

    return {
        "batch": B, "prompt_len": S, "gen_len": gen_len,
        "prefill_s": prefill_s,
        "prefill_tok_s": B * S / max(prefill_s, 1e-9),
        "decode_s": decode_s,
        "decode_tok_s": B * gen_len / max(decode_s, 1e-9),
        "tokens": np.stack(out, 1),
    }


def run_smoke(args) -> None:
    """CI gate: tiny checkpoint -> decode 8 tokens under both modes ->
    cross-mode parity + zero recompiles after one warmup step."""
    from repro.analysis.program_check import CompileCounter

    with tempfile.TemporaryDirectory() as d:
        ckpt, cfg, opts = tiny_fl_checkpoint(d, rounds=1, clients=2,
                                             seed=args.seed)
        uids = [0, 1]
        prompts = make_token_lm_dataset(2, 8, cfg.vocab_size, seed=1)
        tokens = jnp.asarray(prompts)
        logits_by_mode = {}
        for mode in ("precompose", "fused"):
            eng = ServeEngine.from_checkpoint(
                ckpt, cfg, mode=mode, cache_dtype="fp16", batch=2,
                use_pallas=args.use_pallas, opts=opts)
            cache = eng.init_cache(2, 8 + 8)
            cache, logits = eng.prefill(tokens, cache, user_ids=uids)
            tok = jnp.argmax(logits, -1)[:, None]
            # warmup = the first decode step; the remaining 7 (and a
            # second user cohort) must not trigger a single compile
            logits, cache = eng.decode_step(cache, tok, 8, user_ids=uids)
            tok = jnp.argmax(logits, -1)[:, None]
            with CompileCounter() as cc:
                for i in range(1, 8):
                    cohort = uids if i % 2 else uids[::-1]
                    logits, cache = eng.decode_step(cache, tok, 8 + i,
                                                    user_ids=cohort)
                    tok = jnp.argmax(logits, -1)[:, None]
                jax.block_until_ready(logits)
            assert len(cc.events) == 0, (
                f"{mode}: decode recompiled: {cc.events}")
            logits_by_mode[mode] = np.asarray(logits)
            print(f"smoke {mode}: 8 decode steps, 2 cohorts, "
                  f"0 recompiles after warmup")
        a, b = logits_by_mode["precompose"], logits_by_mode["fused"]
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
        assert rel < 2e-2, f"mode parity: rel err {rel:.3e}"
        print(f"smoke parity: precompose-vs-fused rel err {rel:.2e} OK")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt", default=None,
                    help="CheckpointManager dir from an FL run; omitted ->"
                         " a tiny pFedPara federation is trained first")
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--kind", default=None,
                    choices=["fedpara", "fedpara_tanh", "pfedpara"],
                    help="factorization the --ckpt run trained with "
                         "(self-made checkpoints pick from --users)")
    ap.add_argument("--mode", default="auto",
                    choices=["precompose", "fused", "auto"])
    ap.add_argument("--cache-dtype", default="int8",
                    choices=["int8", "fp16"])
    ap.add_argument("--users", type=int, default=0,
                    help="pFedPara cohort width (0 = global model only)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=2,
                    help="training rounds for the self-made checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-pallas", action="store_true", default=None,
                    help="force the Pallas serve kernels (default: TPU only)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: both modes, parity + 0-recompile asserts")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        run_smoke(args)
        return

    if args.ckpt:
        # the serve config must mirror the training one — same tiny
        # reduction tiny_fl_checkpoint used (full-scale runs would load
        # their own ArchConfig here)
        kind = args.kind or ("pfedpara" if args.users else "fedpara")
        cfg = get_arch(args.arch).reduced()
        cfg = dataclasses.replace(cfg, n_layers=2, param=dataclasses.replace(
            cfg.param, kind=kind, min_dim_for_factorization=8, gamma=0.5))
        opts = ModelOptions(attn_chunk=8, ssm_chunk=8, logit_chunk=16,
                            dtype=jnp.float32)
        ckpt = args.ckpt
        tmp = None
    else:
        tmp = tempfile.TemporaryDirectory()
        kind = args.kind or ("pfedpara" if args.users else "fedpara")
        t0 = time.perf_counter()
        ckpt, cfg, opts = tiny_fl_checkpoint(
            tmp.name, arch=args.arch, rounds=args.rounds,
            clients=max(2, args.users), kind=kind, seed=args.seed)
        print(f"trained + checkpointed tiny federation "
              f"({args.rounds} rounds): {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    eng = ServeEngine.from_checkpoint(
        ckpt, cfg, mode=args.mode, cache_dtype=args.cache_dtype,
        batch=args.batch, use_pallas=args.use_pallas, opts=opts)
    print(f"engine ({args.mode}, cache={args.cache_dtype}): "
          f"{time.perf_counter() - t0:.2f}s to plan + build caches")
    _print_plan(eng)

    uids = None
    if eng.arena is not None:
        uids = [eng.arena.uids[i % eng.arena.n_users]
                for i in range(args.batch)]
        print(f"cohort: users {uids}")

    prompts = make_token_lm_dataset(args.batch, args.prompt_len,
                                    cfg.vocab_size, seed=args.seed + 1)
    rep = serve_timed(eng, prompts, args.gen_len, uids)
    print(f"prefill {rep['batch']}x{rep['prompt_len']}: "
          f"{rep['prefill_s'] * 1e3:.1f} ms "
          f"({rep['prefill_tok_s']:.0f} tok/s)")
    print(f"decode {rep['gen_len']} steps x{rep['batch']}: "
          f"{rep['decode_s'] * 1e3:.1f} ms "
          f"({rep['decode_tok_s']:.1f} tok/s)")
    print("sample generations (token ids):")
    for row in rep["tokens"][:2]:
        print("  ", row[:12].tolist())
    if tmp is not None:
        tmp.cleanup()


if __name__ == "__main__":
    main()
