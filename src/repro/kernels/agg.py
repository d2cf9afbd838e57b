"""Fused dequant-and-accumulate Pallas kernels for streaming aggregation.

The FL server's hot reduction is  acc += Σ_c coeff_c · dequant(q_c)
over a client-stacked uplink wire buffer, where ``coeff_c`` folds the
arrival mask, the aggregation weight and (for int8 payloads) the
per-client quantizer scale into one fp32 scalar. The dense path
dequantizes the whole (C, L) int8 stack to fp32 in HBM (writing and
re-reading 4 bytes per element) before reducing it; the fused kernel
consumes the int8 values directly — each (bc, bl) wire tile is loaded
ONCE at 1 byte/element, converted in VMEM, and contracted against the
(1, bc) coefficient row into a resident (1, bl) fp32 accumulator tile.
HBM traffic drops from ≈ 9·C·L bytes (int8 read + fp32 write + fp32
read + reduce) to C·L + 8·L bytes.

Kernel layout: inputs are flattened to (C, L); grid is (L/bl, C/bc)
with the client axis innermost/sequential. Each L-tile's accumulator
lives in VMEM scratch, seeded from the incoming ``acc`` block at the
first client step and written to the (aliased) output at the last, so
the accumulation is one pass and ``acc`` can be donated by the caller.
Masked / padded clients carry coefficient 0.0 and int8 payloads are
finite by construction, so padding rows contribute exact zeros.

Tree-level API: :func:`tree_dequant_acc` walks a codec wire tree
(``{"q", "scale"}`` int8 nodes, fp16 or fp32 dense leaves — see
``Codec.encode_for_agg``) against a payload-structured fp32 accumulator
tree. :func:`sharded_tree_dequant_acc` is the two-level path for
shard_map meshes: each device reduces its client shard with the kernel
(partial sums), then one ``psum`` over the mesh axis combines the
per-shard partials — the classic hierarchical aggregation tree.

Oracle: ``repro.kernels.ref.tree_dequant_acc_ref`` (dense jnp).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocks, spmd

_QKEYS = frozenset(("q", "scale"))


def _is_qnode(n: Any) -> bool:
    return isinstance(n, dict) and set(n) == _QKEYS


def _pad_axis(a: jax.Array, axis: int, mult: int) -> jax.Array:
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


# ------------------------------------------------------------------ kernel

def _agg_body(coeff_ref, q_ref, acc_ref, o_ref, scratch_ref, *, n_kc: int):
    """One (bc, bl) wire tile: scratch(1, bl) += coeff(1, bc) @ deq(q)."""
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _seed():
        scratch_ref[...] = acc_ref[...].astype(jnp.float32)

    # The dequant happens here: the tile is loaded at its wire itemsize
    # (1 B for int8) and widened to fp32 in VMEM only.
    qf = q_ref[...].astype(jnp.float32)
    scratch_ref[...] += jax.lax.dot_general(
        coeff_ref[...], qf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kc == n_kc - 1)
    def _done():
        o_ref[...] = scratch_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_l", "interpret"))
def dequant_acc(
    acc: jax.Array,
    q: jax.Array,
    coeff: jax.Array,
    *,
    block_c: Optional[int] = None,
    block_l: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """acc (L,) fp32 += coeff (C,) fp32 @ dequant(q (C, L)) in one pass.

    ``q`` may be int8 (codec wire), fp16 or fp32 — conversion happens
    per-tile in VMEM. Per-client quantizer scales must be pre-folded
    into ``coeff`` (dequant is linear: Σ w_c s_c q_c = Σ (w_c s_c) q_c).
    """
    C, L = q.shape
    tc, tl = blocks.select_agg_blocks(C, L)
    bc, bl = block_c or tc, block_l or tl
    qp = _pad_axis(_pad_axis(q, 0, bc), 1, bl)
    accp = _pad_axis(acc.reshape(1, -1), 1, bl)
    coeffp = _pad_axis(coeff.reshape(1, -1).astype(jnp.float32), 1, bc)
    Cp, Lp = qp.shape
    grid = (Lp // bl, Cp // bc)   # client axis innermost => sequential

    out = spmd.call(pl.pallas_call(
        functools.partial(_agg_body, n_kc=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc), lambda i, c: (0, c)),
            pl.BlockSpec((bc, bl), lambda i, c: (c, i)),
            pl.BlockSpec((1, bl), lambda i, c: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bl), lambda i, c: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Lp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bl), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=interpret,
    ), coeffp, qp, accp)
    return out[0, :L]


# -------------------------------------------------------------- tree level

def acc_zeros_like(wire: Any) -> Any:
    """fp32 zero accumulator tree with the payload structure of ``wire``:
    one dense leaf per ``{"q", "scale"}`` node (client axis dropped)."""
    def walk(n):
        if _is_qnode(n):
            return jnp.zeros(n["q"].shape[1:], jnp.float32)
        if isinstance(n, dict):
            return {k: walk(v) for k, v in n.items()}
        if isinstance(n, (list, tuple)):
            return type(n)(walk(v) for v in n)
        return jnp.zeros(jnp.shape(n)[1:], jnp.float32)

    return walk(wire)


def tree_dequant_acc(acc_tree: Any, wire: Any, weights: jax.Array, *,
                     interpret: Optional[bool] = None,
                     use_pallas: bool = True) -> Any:
    """Fold one client-stacked wire tree into a running fp32 accumulator.

    ``wire`` leaves are ``{"q": (C, ...), "scale": (C,)}`` int8 nodes or
    dense ``(C, ...)`` arrays (fp16/fp32); ``weights`` is the (C,)
    mask·weight vector; ``acc_tree`` mirrors the payload structure with
    fp32 leaves. Returns the updated accumulator (callers should donate
    ``acc_tree`` — the kernel aliases it through to the output).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    w = weights.astype(jnp.float32)

    def one(acc, q, coeff):
        C = q.shape[0]
        if not use_pallas:
            from repro.kernels import ref
            return ref.dequant_acc_ref(acc.reshape(-1), q.reshape(C, -1),
                                       coeff).reshape(acc.shape)
        flat = dequant_acc(acc.reshape(-1), q.reshape(C, -1), coeff,
                           interpret=interpret)
        return flat.reshape(acc.shape)

    def walk(acc, n):
        if _is_qnode(n):
            scale = n["scale"].reshape(n["q"].shape[0]).astype(jnp.float32)
            return one(acc, n["q"], w * scale)
        if isinstance(n, dict):
            return {k: walk(acc[k], v) for k, v in n.items()}
        if isinstance(n, (list, tuple)):
            return type(n)(walk(a, v) for a, v in zip(acc, n))
        return one(acc, n, w)

    return walk(acc_tree, wire)


def sharded_tree_dequant_acc(wire: Any, weights: jax.Array, mesh, axis: str,
                             *, interpret: Optional[bool] = None,
                             use_pallas: bool = True) -> Any:
    """Two-level hierarchical reduction for shard_map meshes.

    The client axis of ``wire``/``weights`` is sharded over ``axis``;
    each device reduces ITS shard with the fused kernel (level one:
    per-shard partial sums, O(C/devices · L) wire bytes touched per
    device) and a single ``psum`` over the mesh axis combines the fp32
    partials (level two: O(L) per hop). Returns the replicated summed
    tree — the caller adds it to its running accumulator.
    """
    from jax.sharding import PartitionSpec as P

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis)), out_specs=P(),
        check_vma=False)
    def reduce_shard(wire_s, w_s):
        part = tree_dequant_acc(acc_zeros_like(wire_s), wire_s, w_s,
                                interpret=interpret, use_pallas=use_pallas)
        return jax.tree.map(lambda x: jax.lax.psum(x, axis), part)

    return reduce_shard(wire, weights)
