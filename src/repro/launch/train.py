"""End-to-end training driver.

Two modes:
  --mode pods   Cross-pod federated local-SGD (the paper's protocol on
                the 'pod' mesh axis) or plain DP/TP — runs on whatever
                devices exist (use dryrun.py for the 512-device lowering
                proof; this driver EXECUTES on real hardware or small
                CPU meshes).
  --mode fl     Classic client/server FL simulation (VGG/LSTM/MLP on
                synthetic datasets) — the paper's own experimental
                regime.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode fl --model mlp --rounds 10
  PYTHONPATH=src python -m repro.launch.train --mode pods --arch qwen3-8b \
      --preset cpu-small --steps 20
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.configs.base import ParamCfg, ShapeCfg
from repro.data import ShardedBatcher, make_token_lm_dataset
from repro.distributed.fedpod import (
    make_dp_step, make_fed_round, stack_for_pods)
from repro.distributed.sharding import use_rules
from repro.launch import specs as specs_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.nn.transformer import ModelOptions, build_model
from repro.optim import adamw, chain_clip


def cpu_small(cfg):
    """Shrink an arch config so it trains for real on CPU."""
    return cfg.with_(
        n_layers=max(2, min(4, cfg.n_layers)),
        d_model=128, n_heads=4,
        n_kv_heads=min(4, cfg.n_kv_heads), head_dim=32,
        d_ff=256 if cfg.d_ff else 0, vocab_size=512,
        **({"n_experts": 4, "experts_per_token": min(2, cfg.experts_per_token)}
           if cfg.n_experts else {}),
        **({"encoder_layers": 2, "encoder_seq": 16} if cfg.encoder_layers else {}),
    )


def train_pods(args):
    cfg = get_arch(args.arch)
    if args.preset == "cpu-small":
        cfg = cpu_small(cfg)
    seq, batch = args.seq, args.batch
    devices = jax.devices()
    n_pods = args.pods
    if len(devices) >= 2 * n_pods and n_pods > 1:
        dp = len(devices) // n_pods
        mesh = Mesh(np.array(devices[: n_pods * dp]).reshape(n_pods, dp, 1),
                    ("pod", "data", "model"))
    else:
        mesh = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
        n_pods = 1

    shape = ShapeCfg("custom", seq, batch, "train")
    opts = ModelOptions(attn_chunk=min(512, seq), ssm_chunk=min(256, seq),
                        logit_chunk=min(1024, seq), scan_layers=True,
                        use_pallas=args.use_pallas)
    model = build_model(cfg, opts)
    rules = specs_mod.rules_for(mesh, shape, fed=n_pods > 1)
    key = jax.random.PRNGKey(args.seed)

    with use_rules(rules):
        params = model.init_params(key)
    opt = chain_clip(adamw(args.lr), 1.0)
    opt_state = opt.init(params)

    data = make_token_lm_dataset(max(512, batch * 8), seq + 1, cfg.vocab_size,
                                 seed=args.seed)
    fed = n_pods > 1
    ckpt = CheckpointManager(args.ckpt_dir, keep=2, async_save=True) \
        if args.ckpt_dir else None

    if fed:
        K = args.local_steps
        params = stack_for_pods(params, n_pods)
        opt_state = stack_for_pods(opt_state, n_pods)
        step_fn = jax.jit(make_fed_round(model.loss, opt, local_steps=K,
                                         sync=args.sync))
        batcher = ShardedBatcher({"tokens": data}, batch * K)
        start = 0
        if ckpt and ckpt.latest_step() is not None:
            (params, opt_state), extra = ckpt.restore(
                None, (params, opt_state))
            start = extra.get("step", 0)
            batcher.restore(extra.get("stream", batcher.position()))
        batcher.start()
        for step in range(start, args.steps):
            raw = batcher.get()["tokens"]
            tokens = raw.reshape(n_pods, K, batch // n_pods, seq + 1)
            t0 = time.time()
            with use_rules(rules):
                params, opt_state, loss = step_fn(params, opt_state,
                                                  {"tokens": jnp.asarray(tokens)})
            if step % args.log_every == 0:
                print(f"round {step} loss {float(loss):.4f} "
                      f"({time.time()-t0:.2f}s)", flush=True)
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save(step, (params, opt_state),
                          extra={"step": step, "stream": batcher.position()})
        batcher.stop()
    else:
        step_fn = jax.jit(make_dp_step(model.loss, opt), donate_argnums=(0, 1))
        batcher = ShardedBatcher({"tokens": data}, batch)
        start = 0
        if ckpt and ckpt.latest_step() is not None:
            (params, opt_state), extra = ckpt.restore(None, (params, opt_state))
            start = extra.get("step", 0)
            batcher.restore(extra.get("stream", batcher.position()))
        batcher.start()
        for step in range(start, args.steps):
            batch_np = batcher.get()
            t0 = time.time()
            with use_rules(rules):
                params, opt_state, loss = step_fn(
                    params, opt_state, {"tokens": jnp.asarray(batch_np["tokens"])})
            if step % args.log_every == 0:
                print(f"step {step} loss {float(loss):.4f} "
                      f"({time.time()-t0:.2f}s)", flush=True)
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save(step, (params, opt_state),
                          extra={"step": step, "stream": batcher.position()})
        batcher.stop()
    if ckpt:
        ckpt.save(args.steps, (params, opt_state), extra={"step": args.steps})
        ckpt.wait()
    print("done")


def train_fl(args):
    """Paper-regime FL simulation on synthetic data."""
    from repro.data import dirichlet_partition, make_image_dataset, train_test_split
    from repro.fl import (ClientConfig, FaultPlan, FLServer, ServerConfig,
                          make_strategy)
    from repro.nn import recurrent as rec

    if args.model == "mlp":
        ds = make_image_dataset(4000, 10, size=28, channels=1, noise=0.4,
                                seed=args.seed)
        data = {"x": ds["x"].reshape(len(ds["y"]), -1), "y": ds["y"]}
        tr, te = train_test_split(data)
        cfg = rec.MLPConfig(in_dim=784, hidden=256, classes=10,
                            param=ParamCfg(kind=args.param, gamma=args.gamma,
                                           min_dim_for_factorization=8,
                                           use_pallas=args.use_pallas))
        params = rec.init_mlp_model(jax.random.PRNGKey(args.seed), cfg)
        loss_fn = functools.partial(_mlp_loss, cfg)
        def eval_fn(p):
            return float(rec.mlp_accuracy(p, cfg, {"x": te["x"][:1000],
                                                   "y": te["y"][:1000]}))
    else:
        raise SystemExit("--mode fl supports --model mlp here; use "
                         "benchmarks/ for VGG16/LSTM experiments")

    parts = dirichlet_partition(tr["y"], args.clients, 0.5, seed=args.seed)
    devices = jax.devices()
    if args.devices > len(devices):
        raise SystemExit(f"--devices {args.devices}: only {len(devices)} "
                         "visible")
    mesh = None   # one device: every array stays on JAX's default, devices[0]
    if args.devices > 1:
        if args.engine == "sequential":
            raise SystemExit("--devices > 1 needs the batched, streaming "
                             "or async engine")
        mesh = Mesh(np.array(devices[: args.devices]), ("clients",))
    gamma_tiers = tuple(float(g) for g in args.gamma_tiers.split(",")
                        if g.strip()) if args.gamma_tiers else ()
    plan = (FaultPlan(rate=args.fault_rate, seed=args.seed)
            if args.fault_rate > 0 else None)
    srv = FLServer(loss_fn, params, tr, parts, make_strategy(args.strategy),
                   ClientConfig(lr=args.lr, batch=64, epochs=args.local_epochs),
                   ServerConfig(clients=args.clients, participation=0.16,
                                rounds=args.rounds,
                                personalization=args.personalization,
                                uplink_codec=args.uplink_codec,
                                downlink_codec=args.downlink_codec,
                                engine=args.engine,
                                client_chunk=args.client_chunk,
                                gamma_tiers=gamma_tiers,
                                tier_assignment=args.tier_assignment,
                                state_store=args.state_store,
                                data_stream=args.data_stream,
                                defense=args.defense, faults=plan,
                                recover_retries=args.recover_retries,
                                buffer_k=args.buffer_k,
                                staleness=args.staleness,
                                max_staleness=args.max_staleness),
                   eval_fn=eval_fn, mesh=mesh)
    ckpt = (CheckpointManager(args.ckpt_dir, keep=2)
            if args.ckpt_dir else None)
    if args.resume:
        if ckpt is None:
            raise SystemExit("--resume requires --ckpt-dir")
        if ckpt.latest_step() is not None:
            step = srv.restore_checkpoint(ckpt)
            print(f"resumed at round {step}", flush=True)
    hist = srv.run(log_every=1, ckpt=ckpt,
                   ckpt_every=max(1, args.ckpt_every) if ckpt else 1)
    hist[-1]["comm_up_mb"] = srv.comm_log.up_bytes / 1e6
    hist[-1]["comm_down_mb"] = srv.comm_log.down_bytes / 1e6
    print(json.dumps(hist[-1], indent=1))


def _mlp_loss(cfg, p, b):
    from repro.nn import recurrent as rec

    return rec.mlp_loss(p, cfg, b)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="pods", choices=["pods", "fl"])
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--preset", default="cpu-small", choices=["cpu-small", "full"])
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--sync", default="factors", choices=["factors", "full"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    # fl mode
    ap.add_argument("--model", default="mlp")
    ap.add_argument("--strategy", default="fedavg")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--param", default="fedpara")
    ap.add_argument("--gamma", type=float, default=0.3)
    ap.add_argument("--personalization", default="none")
    ap.add_argument("--uplink-codec", default="",
                    help="uplink codec spec, e.g. 'delta|topk0.1|int8' "
                         "(stages: delta, topk<f>, lowrank<r>, int8, fp16)")
    ap.add_argument("--downlink-codec", default="",
                    help="downlink codec spec (same grammar); applied to "
                         "the payload clients actually train on")
    ap.add_argument("--engine", default="batched",
                    choices=["sequential", "batched", "streaming", "async"],
                    help="FL round engine: sequential reference loop, the "
                         "client-batched vmap/shard_map program, the "
                         "streaming chunked scan (O(chunk) round memory — "
                         "use for cohorts the stacked engine cannot hold), "
                         "or the event-driven async buffered engine "
                         "(FedBuff-style; see docs/async.md and "
                         "--buffer-k/--staleness/--max-staleness)")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="async engine: folded arrivals per version bump "
                         "(0 = the sync participation target, the parity "
                         "regime)")
    ap.add_argument("--staleness", default="constant",
                    help="async engine staleness weight s(tau): constant, "
                         "poly[:a] = (1+tau)^-a, or hinge[:b] (flat up to "
                         "b versions, hyperbolic decay past it)")
    ap.add_argument("--max-staleness", type=int, default=-1,
                    help="async engine: drop arrivals staler than this "
                         "many versions (-1 = never drop)")
    ap.add_argument("--devices", type=int, default=1,
                    help="fl mode: chips the round runs on. 1 (default) "
                         "uses the first device only; N > 1 shards the "
                         "client axis over a ('clients',) mesh of the "
                         "first N devices")
    ap.add_argument("--client-chunk", type=int, default=16,
                    help="streaming engine: clients per scan step; round "
                         "memory peaks at O(client_chunk * model)")
    ap.add_argument("--state-store", default="dict",
                    choices=["dict", "arena"],
                    help="per-client state residency: host dicts, or the "
                         "device-resident index-addressed arena (one "
                         "vectorized gather/scatter per round; batched "
                         "and streaming engines only)")
    ap.add_argument("--data-stream", default="eager",
                    choices=["eager", "chunked"],
                    help="cohort batch materialization: eager full-cohort "
                         "host stack, or chunked per-scan-step host "
                         "callbacks (streaming engine only; host memory "
                         "stays O(client_chunk))")
    ap.add_argument("--gamma-tiers", default="",
                    help="heterogeneous capacity tiers: comma-separated "
                         "rank gammas, one per device tier (e.g. "
                         "'0.05,0.1,0.3'); each client trains/uploads "
                         "only the leading tier-rank factor columns and "
                         "is charged the sliced wire bytes. Empty = "
                         "uniform full-rank clients")
    ap.add_argument("--tier-assignment", default="round_robin",
                    choices=["round_robin", "random", "size"],
                    help="client->tier rule for --gamma-tiers: cid mod T, "
                         "seeded uniform draw, or by local dataset size "
                         "(more data -> larger-gamma tier)")
    ap.add_argument("--defense", default="none",
                    choices=["none", "clip", "trimmed"],
                    help="compiled upload screening + robust aggregation: "
                         "clip (all engines; median-norm clipping), "
                         "trimmed (batched engine only: coordinate-wise "
                         "trimmed mean). See docs/robustness.md")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos injection: per-client per-round fault "
                         "probability (deterministic in --seed; kinds: "
                         "crash/nan/bitflip/byzantine/stale)")
    ap.add_argument("--recover-retries", type=int, default=0,
                    help="round-level recovery: re-sample a replacement "
                         "cohort up to N times when crashed+rejected "
                         "clients exceed half the participants")
    ap.add_argument("--resume", action="store_true",
                    help="fl mode: restore the latest checkpoint in "
                         "--ckpt-dir and continue to --rounds (bitwise "
                         "identical to the uninterrupted run)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route every FedPara dense() through the fused "
                         "differentiable Pallas kernels: local training "
                         "never materializes the dense W (custom VJP; "
                         "O(r(m+n)) HBM instead of O(mn) per layer/step). "
                         "Applies to both --mode fl (MLP param cfg) and "
                         "--mode pods (transformer ModelOptions)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "pods":
        train_pods(args)
    else:
        train_fl(args)


if __name__ == "__main__":
    main()
