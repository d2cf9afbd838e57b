"""Plain reference of the federated round that the benchmark times.

FedAvg with an int8 uplink, written out from the method's description
and computed here without any code of the system under test: the same
seed gives the same cohort, the same client data order, the same
stochastic-rounding noise and the same arithmetic, in float32 at the
matmul precision the configuration's file names for the reference
(``reference_precision``), or, for the control, in bfloat16. The rules the round follows are the ones a user of the
system configures:

* cohort: ``numpy.random.RandomState(server_seed)`` draws ``cohort`` of
  ``clients`` without replacement each round, then one lognormal
  latency (sigma 0.5) and one uniform dropout draw per sampled client;
  with no over-sampling, no deadline and no dropout every sampled
  client arrives;
* client data order: a 64-bit seed per client and round from
  ``numpy.random.SeedSequence((server_seed, 0x5EEDF1EE, round))``
  spawned once per cohort position; each local epoch is a fresh
  permutation of the client's samples in full batches;
* local training: plain SGD at ``lr * lr_decay**round`` over every full
  batch, starting from the broadcast global model;
* uplink: each trained model, per tensor, scaled to max |w| / 127 and
  rounded stochastically to int8 with uniform noise keyed by
  ``fold_in(PRNGKey(round), position)`` split once per tensor;
* server: the mean of the dequantized uploads weighted by client data
  size becomes the new global model.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

SEED_TAG = 0x5EEDF1EE
STRAGGLER_SIGMA = 0.5


def cohort_draws(server_seed: int, clients: int, cohort: int, rounds: int):
    """[(client ids, per-client data seeds)] of the first ``rounds``."""
    rng = np.random.RandomState(server_seed)
    out = []
    for r in range(rounds):
        ids = rng.choice(clients, size=cohort, replace=False)
        rng.lognormal(mean=0.0, sigma=STRAGGLER_SIGMA, size=cohort)
        rng.rand(cohort)
        root = np.random.SeedSequence((int(server_seed), SEED_TAG, r))
        seeds = [int(c.generate_state(1, np.uint64)[0])
                 for c in root.spawn(cohort)]
        out.append(([int(i) for i in ids], seeds))
    return out


def epoch_batches(idx: np.ndarray, batch: int, epochs: int, seed: int):
    """One client's local batches as index arrays, epoch by epoch."""
    rng = (np.random.RandomState(seed) if 0 <= seed < 2 ** 32 else
           np.random.RandomState(np.random.SeedSequence(seed).generate_state(4)))
    out = []
    for _ in range(epochs):
        order = rng.permutation(len(idx))
        for i in range(0, len(order) - batch + 1, batch):
            out.append(idx[order[i: i + batch]])
    return out


def quantize_dequantize(tree, key):
    """Per-tensor symmetric int8 with stochastic rounding, and back."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for x, k in zip(leaves, keys):
        x = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(x).max(), 1e-12) / 127.0
        noise = jax.random.uniform(k, x.shape, jnp.float32) - 0.5
        q = jnp.clip(jnp.round(x / scale + noise), -127, 127)
        out.append(q * scale)
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclass
class Rounds:
    """What the reference computed: per round the ids, the mean of the
    clients' last local losses, and the global model after it."""

    cohorts: list
    losses: list
    params: list


def run(loss, spec: dict, traffic: dict, params0, data: dict,
        partitions: list, server_seed: int, rounds: int,
        dtype=jnp.float32, precision: str = "default",
        block: int = 16) -> Rounds:
    """Run ``rounds`` rounds from ``params0``. ``loss(params, batch)``
    is the configuration's plain loss; ``dtype`` is the type the whole
    local training computes and keeps its weights in, ``precision`` the
    matmul precision (``default`` or ``highest``). Clients train
    ``block`` at a time, so that memory stays bounded."""

    def train(glob, batches, lr):
        def one(client_batches):
            def step(p, b):
                value, grads = jax.value_and_grad(loss)(p, b)
                p = jax.tree.map(lambda w, g: (w - lr * g).astype(dtype),
                                 p, grads)
                return p, value.astype(jnp.float32)

            return jax.lax.scan(step, glob, client_batches)

        trained, values = jax.vmap(one)(batches)
        return trained, values[:, -1]

    def upload(trained, keys):
        return jax.vmap(quantize_dequantize)(trained, keys)

    with jax.default_matmul_precision(precision):
        train_j, upload_j = jax.jit(train), jax.jit(upload)
        glob = jax.tree.map(lambda x: jnp.asarray(x, dtype), params0)
        out = Rounds([], [], [])
        draws = cohort_draws(server_seed, spec["clients"], traffic["cohort"],
                             rounds)
        for r, (ids, seeds) in enumerate(draws):
            lr = jnp.float32(spec["lr"] * spec["lr_decay"] ** r)
            keys = jax.vmap(lambda i, r=r: jax.random.fold_in(
                jax.random.PRNGKey(r), i))(jnp.arange(len(ids), dtype=jnp.uint32))
            total, weight, last = None, 0.0, []
            for lo in range(0, len(ids), block):
                sel = range(lo, min(lo + block, len(ids)))
                idx = np.stack([np.stack(epoch_batches(
                    partitions[ids[c]], spec["batch"], spec["epochs"], seeds[c]))
                    for c in sel])                         # (block, S, B)
                batches = {k: jnp.asarray(v[idx]) for k, v in data.items()}
                trained, values = train_j(glob, batches, lr)
                deq = upload_j(trained, keys[lo: lo + len(sel)])
                sizes = np.array([len(partitions[ids[c]]) for c in sel],
                                 np.float32)
                part = jax.tree.map(
                    lambda u: jnp.tensordot(jnp.asarray(sizes), u, axes=1), deq)
                total = part if total is None else jax.tree.map(
                    jnp.add, total, part)
                weight += float(sizes.sum())
                last.append(np.asarray(values, np.float64))
            mean = jax.tree.map(lambda t: t / weight, total)
            glob = jax.tree.map(lambda x: x.astype(dtype), mean)
            out.cohorts.append(ids)
            out.losses.append(float(np.concatenate(last).mean()))
            out.params.append(jax.tree.map(
                lambda x: np.asarray(x, np.float32), mean))
    return out
