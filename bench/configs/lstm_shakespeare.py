"""The FedPara paper's Shakespeare character LSTM.

Sizes are in ``lstm_shakespeare.json``. This module holds what the
benchmark owns for the configuration: weights and data drawn from the
seed, the plain reference loss, and the FLOP and kernel-call counts.
Only :func:`program_loss` touches the system under test.

Every gate matrix is FedPara: W = (X1 Y1^T) * (X2 Y2^T), the embedding
and the output head dense, as the paper keeps small and last layers.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

SPEC = json.load(open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "lstm_shakespeare.json")))


def _gate_shapes(spec=SPEC):
    """[(name, m, n, r)] of every FedPara gate matrix, layer by layer."""
    out, d_in, n = [], spec["embed"], 4 * spec["hidden"]
    for layer, (r_i, r_h) in enumerate(spec["gate_ranks"]):
        out.append((f"wi{layer}", d_in, n, r_i))
        out.append((f"wh{layer}", spec["hidden"], n, r_h))
        d_in = spec["hidden"]
    return out


def init_params(key, spec=SPEC) -> dict:
    """Seeded float32 weights in the layout the program reads. Factor std
    makes the composed W match He variance: sigma = (2/m)^(1/8) / r^(1/4);
    the forget-gate bias starts at 1."""
    hidden, vocab = spec["hidden"], spec["vocab"]
    keys = iter(jax.random.split(key, 2 + 4 * len(_gate_shapes(spec))))
    params = {
        "embed": {"w": 0.1 * jax.random.normal(next(keys), (vocab, spec["embed"]))},
        "head": {"w": jax.random.normal(next(keys), (hidden, vocab))
                 * (1.0 / hidden) ** 0.5},
        "cells": [],
    }
    gates = _gate_shapes(spec)
    for layer in range(spec["layers"]):
        cell = {"b": jnp.zeros((4 * hidden,)).at[hidden:2 * hidden].set(1.0)}
        for name, m, n, r in gates[2 * layer: 2 * layer + 2]:
            std = (2.0 / m) ** 0.125 / r ** 0.25
            cell[name[:2]] = {
                "x1": jax.random.normal(next(keys), (m, r)) * std,
                "y1": jax.random.normal(next(keys), (n, r)) * std,
                "x2": jax.random.normal(next(keys), (m, r)) * std,
                "y2": jax.random.normal(next(keys), (n, r)) * std,
            }
        params["cells"].append(cell)
    return params


def make_data(key, spec=SPEC) -> dict:
    """Synthetic Shakespeare-like corpus: one order-1 Markov chain over
    the characters with sparse, peaked transitions (Dirichlet(0.05)
    rows raised to the power 2), ``clients * samples_per_client``
    sequences, drawn on the device in one program."""
    vocab, seq_len = spec["vocab"], spec["seq_len"]
    n = spec["clients"] * spec["samples_per_client"]

    def draw(key):
        k_trans, k_init, k_steps = jax.random.split(key, 3)
        logits = 2.0 * jax.random.loggamma(k_trans, 0.05, (vocab, vocab))
        state0 = jax.random.randint(k_init, (n,), 0, vocab)

        def step(state, k):
            nxt = jax.random.categorical(k, logits[state])
            return nxt, state

        _, seqs = jax.lax.scan(step, state0,
                               jax.random.split(k_steps, seq_len))
        return seqs.T.astype(jnp.int32)

    return {"tokens": jax.jit(draw)(key)}


def program_loss(spec=SPEC):
    """The system under test's loss for these weights: the repository's
    LSTM with every gate through the fused FedPara kernels."""
    from repro.configs.base import ParamCfg
    from repro.nn.recurrent import LSTMConfig, lstm_loss

    cfg = LSTMConfig(vocab=spec["vocab"], embed=spec["embed"],
                     hidden=spec["hidden"], layers=spec["layers"],
                     param=ParamCfg(gamma=spec["gamma"],
                                    min_dim_for_factorization=8,
                                    use_pallas=spec["fused_kernels"]))

    def loss_fn(params, batch):
        return lstm_loss(params, cfg, batch)

    return loss_fn


# ------------------------------------------------------------ reference

def _compose(f):
    return (f["x1"] @ f["y1"].T) * (f["x2"] @ f["y2"].T)


def reference_loss(params, batch, spec=SPEC):
    """Mean next-character cross-entropy, written out plainly: compose
    each gate matrix, run the two LSTM layers step by step, dense head.
    Computes in the dtype of ``params``."""
    tokens = batch["tokens"]
    dtype = params["head"]["w"].dtype
    x = params["embed"]["w"][tokens[:, :-1]]            # (B, S, embed)
    hidden = spec["hidden"]
    for cell in params["cells"]:
        wi, wh, b = _compose(cell["wi"]), _compose(cell["wh"]), cell["b"]
        h0 = jnp.zeros((x.shape[0], hidden), dtype)

        def step(carry, x_t, wi=wi, wh=wh, b=b):
            h, c = carry
            z = x_t @ wi + h @ wh + b
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(step, (h0, h0), jnp.swapaxes(x, 0, 1))
        x = jnp.swapaxes(hs, 0, 1)
    logp = jax.nn.log_softmax(x @ params["head"]["w"])
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return nll.mean()


# --------------------------------------------------------------- counts

def flops_per_sample(spec=SPEC) -> float:
    """Dense-equivalent forward + backward matmul FLOPs of one sequence:
    every gate matrix and the head at each of the ``seq_len - 1`` input
    positions, times 3 for forward plus backward."""
    per_pos = sum(2 * m * n for _, m, n, _ in _gate_shapes(spec))
    per_pos += 2 * spec["hidden"] * spec["vocab"]
    return 3.0 * per_pos * (spec["seq_len"] - 1)


def compose_flops_per_step(spec=SPEC) -> float:
    """Composing each FedPara weight once (two rank-r products and the
    Hadamard product) and its factor gradients once (the two Hadamard
    products and four rank-r products), per client per local step."""
    return float(sum(12 * m * n * r + 3 * m * n
                     for _, m, n, r in _gate_shapes(spec)))


def kernel_calls(rows: int, clients: int, spec=SPEC) -> list:
    """The fused kernel calls of one local step of ``clients`` clients
    that run together, each client's batch of ``rows`` sequences: per
    input position and gate matrix one forward, one input-gradient and
    two factor-gradient launches, each over all the clients at once.
    Operations and bytes are those of the call's unpadded shapes, with
    the weight composed once (one batch tile: ``rows`` is at most the
    kernels' 128-row batch block); bytes read and written once at 4
    bytes a value."""
    if rows > 128:
        raise ValueError("more than one batch tile per client")
    steps = spec["seq_len"] - 1
    calls = []
    for name, m, n, r in _gate_shapes(spec):
        compose = 4 * m * n * r + m * n
        factors = 2 * (m + n) * r
        specs = {
            "fwd": (2 * rows * m * n + compose,
                    rows * m + factors + rows * n),
            "dx": (2 * rows * m * n + compose,
                   rows * n + factors + rows * m),
            "dfactors_x": (2 * rows * m * n + compose + 2 * m * n
                           + 4 * m * n * r,
                           rows * m + rows * n + factors + 2 * m * r),
            "dfactors_y": (2 * rows * m * n + compose + 2 * m * n
                           + 4 * m * n * r,
                           rows * m + rows * n + factors + 2 * n * r),
        }
        for kind, (flops, values) in specs.items():
            calls.append({"matrix": name, "kind": kind, "count": steps,
                          "flops": float(clients * flops),
                          "bytes": float(clients * 4 * values)})
    return calls


def partition(n: int, clients: int, seed: int) -> list:
    """IID split of ``n`` sample indices into equal client shards."""
    idx = np.random.RandomState(seed).permutation(n)
    return [np.sort(p) for p in np.array_split(idx, clients)]
