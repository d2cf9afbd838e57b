"""Shared miniature FL experiment harness for the paper-table benchmarks.

The container is CPU-only, so each benchmark runs a scaled-down version
of the paper's experiment (VGG-small / tiny LSTM / MLP on deterministic
synthetic datasets) that preserves the COMPARISON the table makes —
parameterization capacity, communication cost, optimizer compatibility,
personalization — not the absolute CIFAR numbers.
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParamCfg
from repro.core.parameterization import num_params
from repro.data import (
    dirichlet_partition,
    iid_partition,
    make_char_corpus,
    make_image_dataset,
    train_test_split,
    two_class_partition,
)
from repro.fl import ClientConfig, FLServer, ServerConfig, make_strategy
from repro.launch.compile_cache import enable_compile_cache
from repro.nn.recurrent import (
    LSTMConfig,
    MLPConfig,
    init_lstm,
    init_mlp_model,
    lstm_accuracy,
    lstm_loss,
    mlp_accuracy,
    mlp_loss,
)
from repro.nn.vision import VGG_SMALL_PLAN, VGGConfig, init_vgg, vgg_accuracy, vgg_loss


ART_DIR = os.path.join(os.path.dirname(__file__), "artifacts")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# on from the first import (``benchmarks.run`` imports this module
# before its first compile)
enable_compile_cache()


def write_artifact(name: str, art: dict) -> str:
    """Write a BENCH_*.json artifact under benchmarks/artifacts/ (the
    canonical location) and mirror it to the repo root, where the
    perf-trajectory tooling looks for BENCH_*.json files. Returns the
    canonical path."""
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, name)
    payload = json.dumps(art, indent=1)
    with open(path, "w") as f:
        f.write(payload)
    with open(os.path.join(REPO_ROOT, name), "w") as f:
        f.write(payload)
    return path


def timer(fn):
    t0 = time.time()
    out = fn()
    return out, (time.time() - t0) * 1e6


# ------------------------------------------------------------- image task

_IMG_CACHE = {}


def image_task(n=2400, classes=10, size=16, seed=0):
    key = (n, classes, size, seed)
    if key not in _IMG_CACHE:
        ds = make_image_dataset(n, classes, size=size, channels=3, noise=0.5,
                                seed=seed)
        _IMG_CACHE[key] = train_test_split(ds)
    return _IMG_CACHE[key]


def run_vgg_fl(kind: str, gamma: float, *, rounds: int = 3, iid: bool = True,
               strategy: str = "fedavg", clients: int = 10, epochs: int = 1,
               uplink_quant: str = "fp32", seed: int = 0,
               size: int = 16) -> Dict:
    tr, te = image_task(size=size, seed=seed)
    cfg = VGGConfig(plan=VGG_SMALL_PLAN, fc_dims=(64,), classes=10,
                    image_size=size, gn_groups=8,
                    param=ParamCfg(kind=kind, gamma=gamma))
    params = init_vgg(jax.random.PRNGKey(seed), cfg)
    parts = (iid_partition(len(tr["y"]), clients, seed)
             if iid else dirichlet_partition(tr["y"], clients, 0.5, seed))

    def loss_fn(p, b):
        return vgg_loss(p, cfg, b)

    def eval_fn(p):
        return float(vgg_accuracy(p, cfg, {"x": te["x"][:300], "y": te["y"][:300]}))

    kw = {}
    if strategy == "fedprox":
        kw = {"mu": 0.1}
    elif strategy == "feddyn":
        kw = {"alpha": 0.1}
    srv = FLServer(loss_fn, params, tr, parts, make_strategy(strategy, **kw),
                   ClientConfig(lr=0.05, batch=32, epochs=epochs),
                   ServerConfig(clients=clients, participation=0.4,
                                rounds=rounds, uplink_quant=uplink_quant,
                                seed=seed),
                   eval_fn=eval_fn)
    hist = srv.run()
    return {"acc": hist[-1]["eval"], "acc0": hist[0]["eval"],
            "comm_gb": srv.comm_log.total_gb, "params": num_params(params),
            "history": hist, "server": srv, "cfg": cfg}


def run_lstm_fl(kind: str, gamma: float, *, rounds: int = 3, seed: int = 0) -> Dict:
    data = make_char_corpus(600, 65, vocab=40, seed=seed)
    cfg = LSTMConfig(vocab=40, embed=8, hidden=64,
                     param=ParamCfg(kind=kind, gamma=gamma,
                                    min_dim_for_factorization=8))
    params = init_lstm(jax.random.PRNGKey(seed), cfg)
    tr = {"tokens": data[:500]}
    te = {"tokens": data[500:]}
    parts = iid_partition(500, 10, seed)

    def loss_fn(p, b):
        return lstm_loss(p, cfg, b)

    def eval_fn(p):
        return float(lstm_accuracy(p, cfg, te))

    srv = FLServer(loss_fn, params, tr, parts, make_strategy("fedavg"),
                   ClientConfig(lr=0.5, batch=25, epochs=1),
                   ServerConfig(clients=10, participation=0.4, rounds=rounds,
                                seed=seed),
                   eval_fn=eval_fn)
    hist = srv.run()
    return {"acc": hist[-1]["eval"], "comm_gb": srv.comm_log.total_gb,
            "params": num_params(params), "history": hist}


def run_mlp_personalization(mode: str, *, rounds: int = 4, scenario: int = 3,
                            frac: float = 1.0, seed: int = 0) -> Dict:
    """Fig. 5 scenarios: 1) full data non-IID, 2) 20% data, 3) two-class skew."""
    ds = make_image_dataset(2000, 10, size=16, channels=1, noise=0.45, seed=seed)
    data = {"x": ds["x"].reshape(len(ds["y"]), -1), "y": ds["y"]}
    tr, te = train_test_split(data)
    kind = {"pfedpara": "pfedpara", "fedper": "fedpara"}.get(mode, "fedpara")
    cfg = MLPConfig(in_dim=256, hidden=128, classes=10,
                    param=ParamCfg(kind=kind, gamma=0.5,
                                   min_dim_for_factorization=8))
    params = init_mlp_model(jax.random.PRNGKey(seed), cfg)
    if scenario == 3:
        parts = two_class_partition(tr["y"], 10, seed)
    else:
        parts = dirichlet_partition(tr["y"], 10, 0.5, seed)
    if frac < 1.0:
        parts = [p[: max(10, int(len(p) * frac))] for p in parts]

    def loss_fn(p, b):
        return mlp_loss(p, cfg, b)

    personalization = {"pfedpara": "pfedpara", "fedper": "fedper",
                       "fedpaq_local": "local"}.get(mode, "none")
    srv = FLServer(loss_fn, params, tr, parts, make_strategy("fedavg"),
                   ClientConfig(lr=0.05, batch=20, epochs=2),
                   ServerConfig(clients=10, participation=1.0, rounds=rounds,
                                personalization=personalization, seed=seed))
    srv.run()

    def ev(p, cid):
        idx = parts[cid][:60]
        return mlp_accuracy(p, cfg, {"x": tr["x"][idx], "y": tr["y"][idx]})

    accs = srv.personalized_eval(ev)
    return {"acc_mean": float(np.mean(accs)), "acc_std": float(np.std(accs)),
            "comm_gb": srv.comm_log.total_gb, "params": num_params(params)}
