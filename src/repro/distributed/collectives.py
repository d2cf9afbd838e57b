"""Explicit collectives: bucketed cross-pod all-reduce (shard_map) and
PowerSGD-style low-rank gradient compression with error feedback.

The bucketed all-reduce groups leaves into ~``bucket_bytes`` flat
buffers so the runtime can overlap sync of early buckets with the
compute that produces later ones (the classic DDP overlap trick);
bucket boundaries are stable across steps, so XLA can pipeline them.

PowerSGD (Vogels et al. 2019 — cited by the paper as the distributed
counterpart of its low-rank idea) compresses a dense gradient G ≈ P Qᵀ
with one power-iteration per step and error feedback. We use it for the
*dense* leaves (embeddings) that FedPara leaves unfactorized, so the
cross-pod payload of the 'full' sync mode drops too.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# ----------------------------------------------------------- bucketed psum

def plan_buckets(tree: Any, bucket_bytes: int = 4 << 20) -> List[List[int]]:
    """Group leaf indices into buckets of ~bucket_bytes."""
    leaves = jax.tree.leaves(tree)
    buckets, cur, cur_b = [], [], 0
    for i, leaf in enumerate(leaves):
        b = leaf.size * leaf.dtype.itemsize
        if cur and cur_b + b > bucket_bytes:
            buckets.append(cur)
            cur, cur_b = [], 0
        cur.append(i)
        cur_b += b
    if cur:
        buckets.append(cur)
    return buckets


def bucketed_pmean(tree: Any, mesh: Mesh, axis: str = "pod",
                   bucket_bytes: int = 4 << 20) -> Any:
    """Mean-reduce every leaf across ``axis`` using flat per-bucket psums."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets = plan_buckets(tree, bucket_bytes)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=P(), out_specs=P(),
        check_vma=False,
    )
    def psum_flat(flat):
        return jax.lax.pmean(flat, axis)

    out = list(leaves)
    for bucket in buckets:
        flat = jnp.concatenate([leaves[i].reshape(-1).astype(jnp.float32)
                                for i in bucket])
        red = psum_flat(flat)
        off = 0
        for i in bucket:
            n = leaves[i].size
            out[i] = red[off: off + n].reshape(leaves[i].shape).astype(leaves[i].dtype)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- PowerSGD

def powersgd_init(shape: Tuple[int, int], rank: int, key: jax.Array) -> Dict:
    m, n = shape
    return {
        "q": jax.random.normal(key, (n, rank), jnp.float32),
        "error": jnp.zeros(shape, jnp.float32),
    }


def powersgd_compress(grad: jax.Array, state: Dict) -> Tuple[jax.Array, jax.Array, Dict]:
    """One power iteration: G' = G + error; P = G'Q; Q' = orth(G'ᵀP).
    Returns (P, Q', state with new error feedback)."""
    g = grad.astype(jnp.float32) + state["error"]
    p = g @ state["q"]                       # (m, r)
    p, _ = jnp.linalg.qr(p)
    q = g.T @ p                              # (n, r)
    approx = p @ q.T
    return p, q, {"q": q, "error": g - approx}


def powersgd_decompress(p: jax.Array, q: jax.Array) -> jax.Array:
    return p @ q.T


def compressed_bytes(p: jax.Array, q: jax.Array) -> int:
    return (p.size + q.size) * 4


# -------------------------------------------------- quantized pod all-reduce

def quantized_pmean(tree: Any, mesh: Mesh, axis: str = "pod") -> Any:
    """bf16-quantized cross-pod mean (2x DCN traffic cut; FedPAQ-style
    uplink quantization applied to the pod sync)."""
    def one(x):
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                           out_specs=P(), check_vma=False)
        def red(v):
            return jax.lax.pmean(v, axis)

        return red(x.astype(jnp.bfloat16)).astype(x.dtype)

    return jax.tree.map(one, tree)
