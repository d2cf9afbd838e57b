"""Tiled FedPara compose Pallas-TPU kernel.

W = (X1 Y1ᵀ) ⊙ (X2 Y2ᵀ)  (optionally with tanh, or the pFedPara
"+1 switch"), produced tile-by-tile. Used on the serving path where the
paper pre-composes W once ("at the inference phase, we pre-compose and
maintain W") and by the training path when XLA's native fusion is
bypassed. Output tiles are MXU-aligned (multiples of 128) and each tile's
working set (two factor slices + the fp32 tile) stays in VMEM.

Batched (client-leading-dim) path: when the factors carry a leading
client axis — Xi: (C, m, r), Yi: (C, n, r), as produced by the
client-batched FL engine (`repro.fl.batch_engine`) — the same kernel
runs on a (C, m/bm, n/bn) grid, one client per leading grid step, so a
vmapped loss can compose every client's W in one kernel launch instead
of C sequential calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x1_ref, y1_ref, x2_ref, y2_ref, o_ref, *, use_tanh: bool, plus_one: bool):
    w1 = jax.lax.dot_general(
        x1_ref[...], y1_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w2 = jax.lax.dot_general(
        x2_ref[...], y2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if use_tanh:
        w1, w2 = jnp.tanh(w1), jnp.tanh(w2)
    if plus_one:
        w2 = w2 + 1.0
    o_ref[...] = (w1 * w2).astype(o_ref.dtype)


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


@functools.partial(
    jax.jit,
    static_argnames=("use_tanh", "plus_one", "block_m", "block_n", "interpret", "out_dtype"),
)
def fedpara_compose(
    x1: jax.Array,
    y1: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    *,
    use_tanh: bool = False,
    plus_one: bool = False,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Compose W ∈ (m, n) from Xi: (m, r), Yi: (n, r) — or, with a
    leading client axis, W ∈ (C, m, n) from Xi: (C, m, r), Yi: (C, n, r)
    on a (C, m/bm, n/bn) grid."""
    if x1.ndim == 3:
        return _fedpara_compose_batched(
            x1, y1, x2, y2, use_tanh=use_tanh, plus_one=plus_one,
            block_m=block_m, block_n=block_n, interpret=interpret,
            out_dtype=out_dtype)
    m, r = x1.shape
    n = y1.shape[0]
    out_dtype = out_dtype or x1.dtype
    bm, bn = block_m, block_n
    x1p, x2p = _pad_to(x1, 0, bm), _pad_to(x2, 0, bm)
    y1p, y2p = _pad_to(y1, 0, bn), _pad_to(y2, 0, bn)
    mp, np_ = x1p.shape[0], y1p.shape[0]
    grid = (mp // bm, np_ // bn)

    out = pl.pallas_call(
        functools.partial(_kernel, use_tanh=use_tanh, plus_one=plus_one),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        name="fedpara_compose",
        interpret=interpret,
    )(x1p, y1p, x2p, y2p)
    return out[:m, :n]


def _kernel_batched(x1_ref, y1_ref, x2_ref, y2_ref, o_ref, *,
                    use_tanh: bool, plus_one: bool):
    # refs are (1, bm, r)/(1, bn, r)/(1, bm, bn): one client per grid step
    w1 = jax.lax.dot_general(
        x1_ref[0], y1_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w2 = jax.lax.dot_general(
        x2_ref[0], y2_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if use_tanh:
        w1, w2 = jnp.tanh(w1), jnp.tanh(w2)
    if plus_one:
        w2 = w2 + 1.0
    o_ref[0] = (w1 * w2).astype(o_ref.dtype)


def _fedpara_compose_batched(x1, y1, x2, y2, *, use_tanh, plus_one,
                             block_m, block_n, interpret, out_dtype):
    C, m, r = x1.shape
    n = y1.shape[1]
    out_dtype = out_dtype or x1.dtype
    bm, bn = block_m, block_n
    x1p, x2p = _pad_to(x1, 1, bm), _pad_to(x2, 1, bm)
    y1p, y2p = _pad_to(y1, 1, bn), _pad_to(y2, 1, bn)
    mp, np_ = x1p.shape[1], y1p.shape[1]
    grid = (C, mp // bm, np_ // bn)

    out = pl.pallas_call(
        functools.partial(_kernel_batched, use_tanh=use_tanh, plus_one=plus_one),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, r), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, bn, r), lambda c, i, j: (c, j, 0)),
            pl.BlockSpec((1, bm, r), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, bn, r), lambda c, i, j: (c, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, mp, np_), out_dtype),
        name="fedpara_compose",
        interpret=interpret,
    )(x1p, y1p, x2p, y2p)
    return out[:, :m, :n]
