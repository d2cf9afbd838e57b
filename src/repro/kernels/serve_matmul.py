"""Serving-path Pallas kernels: int8 weight cache × activation matmul
and the pFedPara "cache + residual" matmul (single- and multi-user).

Three kernel bodies back ``repro.serve``'s two weight layouts:

``_w8_kernel``
    y = (x @ W_q) · s  for a pre-composed weight cache stored int8 (or
    fp16) with per-output-channel scales s (1, n). The cache tile enters
    VMEM at wire width (1 B/elt for int8) and is widened there — the
    int8 array is NEVER widened in HBM, which the serve program contract
    (``repro.analysis.program_check.check_serve_widening``) enforces.
    Because s depends only on the output channel, it commutes with the
    row contraction: the scale multiply happens once on the fp32
    accumulator at the final grid step, not per weight tile.

``_resid_kernel``
    pFedPara decode for ONE personalized user against the shared cache:
    W_u = W1 ⊙ (X2ᵤY2ᵤᵀ + 1) where W1 = X1Y1ᵀ is the globally-shared
    half, cached as W_q·s. Each (bm, bn) residual tile X2ᵤY2ᵤᵀ is
    composed in VMEM from factor slices, the "+1 switch" applied, and
    Hadamard-multiplied into the dequantized cache tile — W_u never
    exists in HBM. The scale still commutes:
    (W_q·s) ⊙ (R+1) = (W_q ⊙ (R+1))·s.

``_resid_kernel_users``
    The many-user variant: x (U, t, m) carries one row-block per user,
    per-user factors are (U, m, r)/(U, n, r) slices gathered from the
    serve user arena, and the W1 cache is SHARED — its BlockSpec index
    map ignores the user grid axis, so serving B distinct users is one
    launch that streams B factor sets plus one cache through VMEM with
    zero per-user W materialization.

Grids put the m (contraction) axis innermost-sequential with an fp32
VMEM scratch accumulator, like ``repro.kernels.fedpara_matmul``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fedpara_matmul import _ceil_mult, _pad_to


def _w8_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_km: int):
    km = pl.program_id(2)

    @pl.when(km == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Widen the cache tile in VMEM only (int8 -> activation dtype).
    w_tile = w_ref[...].astype(x_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_tile, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(km == n_km - 1)
    def _done():
        # per-output-channel scale commutes with the row sum: apply once.
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _resid_kernel(x_ref, w_ref, s_ref, x2_ref, y2_ref, o_ref, acc_ref, *,
                  n_km: int):
    km = pl.program_id(2)

    @pl.when(km == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (bm, bn) residual tile from factor slices; "+1 switch" in VMEM.
    r_tile = jax.lax.dot_general(
        x2_ref[...], y2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w_tile = w_ref[...].astype(jnp.float32) * (r_tile + 1.0)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_tile.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(km == n_km - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _resid_kernel_users(x_ref, w_ref, s_ref, x2_ref, y2_ref, o_ref, acc_ref,
                        *, n_km: int):
    # x/x2/y2/o carry a leading (1,) user dim; w/s are user-shared.
    km = pl.program_id(3)

    @pl.when(km == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r_tile = jax.lax.dot_general(
        x2_ref[0], y2_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w_tile = w_ref[...].astype(jnp.float32) * (r_tile + 1.0)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_tile.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(km == n_km - 1)
    def _done():
        o_ref[0] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _scale_row(scale, w, n: int):
    """Normalize per-channel scales to a padded (1, n) fp32 row (ones
    when the cache is not quantized)."""
    if scale is None:
        return jnp.ones((1, n), jnp.float32)
    return _pad_to(scale.reshape(1, -1).astype(jnp.float32), 1, n)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_m", "block_n", "interpret",
                     "out_dtype"),
)
def w8_matmul(x, w, scale=None, *, block_b: int = 64, block_m: int = 256,
              block_n: int = 256, interpret: bool = False, out_dtype=None):
    """y = (x @ W) · s;  x: (B, m), W: (m, n) int8/fp16, s: (1, n)."""
    b, m = x.shape
    n = w.shape[1]
    out_dtype = out_dtype or x.dtype
    bb, bm, bn = min(block_b, _ceil_mult(b, 8)), block_m, block_n
    xp = _pad_to(_pad_to(x, 0, bb), 1, bm)
    wp = _pad_to(_pad_to(w, 0, bm), 1, bn)
    bp, mp = xp.shape
    np_ = wp.shape[1]
    sp = _scale_row(scale, wp, np_)
    grid = (bp // bb, np_ // bn, mp // bm)

    out = pl.pallas_call(
        functools.partial(_w8_kernel, n_km=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        name="w8_matmul",
        interpret=interpret,
    )(xp, wp, sp)
    return out[:b, :n]


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_m", "block_n", "interpret",
                     "out_dtype"),
)
def cache_residual_matmul(x, w, scale, x2, y2, *, block_b: int = 64,
                          block_m: int = 256, block_n: int = 256,
                          interpret: bool = False, out_dtype=None):
    """y = (x @ (W ⊙ (X2Y2ᵀ + 1))) · s — pFedPara cache + residual.

    Single user: x (B, m), X2 (m, r), Y2 (n, r). Many users: x (U, t, m)
    with per-user factors X2 (U, m, r), Y2 (U, n, r) and a SHARED cache
    W (m, n) — one launch serves all U users.
    """
    if x.ndim == 3:
        return _cache_residual_users(
            x, w, scale, x2, y2, block_b=block_b, block_m=block_m,
            block_n=block_n, interpret=interpret, out_dtype=out_dtype)
    b, m = x.shape
    n = w.shape[1]
    r = x2.shape[1]
    out_dtype = out_dtype or x.dtype
    bb, bm, bn = min(block_b, _ceil_mult(b, 8)), block_m, block_n
    xp = _pad_to(_pad_to(x, 0, bb), 1, bm)
    wp = _pad_to(_pad_to(w, 0, bm), 1, bn)
    x2p = _pad_to(x2, 0, bm)
    y2p = _pad_to(y2, 0, bn)
    bp, mp = xp.shape
    np_ = wp.shape[1]
    sp = _scale_row(scale, wp, np_)
    grid = (bp // bb, np_ // bn, mp // bm)

    out = pl.pallas_call(
        functools.partial(_resid_kernel, n_km=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        name="cache_residual_matmul",
        interpret=interpret,
    )(xp, wp, sp, x2p, y2p)
    return out[:b, :n]


def _cache_residual_users(x, w, scale, x2, y2, *, block_b, block_m, block_n,
                          interpret, out_dtype):
    U, t, m = x.shape
    n = w.shape[1]
    r = x2.shape[2]
    out_dtype = out_dtype or x.dtype
    bb, bm, bn = min(block_b, _ceil_mult(t, 8)), block_m, block_n
    xp = _pad_to(_pad_to(x, 1, bb), 2, bm)
    wp = _pad_to(_pad_to(w, 0, bm), 1, bn)
    x2p = _pad_to(x2, 1, bm)
    y2p = _pad_to(y2, 1, bn)
    tp, mp = xp.shape[1], xp.shape[2]
    np_ = wp.shape[1]
    sp = _scale_row(scale, wp, np_)
    grid = (U, tp // bb, np_ // bn, mp // bm)

    out = pl.pallas_call(
        functools.partial(_resid_kernel_users, n_km=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bb, bm), lambda u, i, j, k: (u, i, k)),
            # the shared cache ignores the user axis: one W1 for all U
            pl.BlockSpec((bm, bn), lambda u, i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda u, i, j, k: (0, j)),
            pl.BlockSpec((1, bm, r), lambda u, i, j, k: (u, k, 0)),
            pl.BlockSpec((1, bn, r), lambda u, i, j, k: (u, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb, bn), lambda u, i, j, k: (u, i, j)),
        out_shape=jax.ShapeDtypeStruct((U, tp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        name="cache_residual_matmul",
        interpret=interpret,
    )(xp, wp, sp, x2p, y2p)
    return out[:, :t, :n]


# ------------------------------------------------------- Gram decode path
#
# At decode batch sizes the fused tile kernel recomposes every (bm, bn)
# W tile for a handful of activation rows — O(m·n·r) compose FLOPs per
# token. The Hadamard-Gram identity removes the (m, n) object entirely:
#
#   y_n = Σ_m x_m (X1Y1ᵀ)_mn (X2Y2ᵀ)_mn
#       = Σ_{i,j} Y1_ni Y2_nj · G_ij,   G = X1ᵀ diag(x) X2   (r1 × r2)
#
# so  y = rowsum((Y1 G) ⊙ Y2)  at O(r²(m+n)) FLOPs per token and factor
# bytes only. No Pallas kernel is needed: there is no dense (m, n)
# intermediate anywhere for XLA to materialize. Invalid for the tanh
# variant (tanh(X1Y1ᵀ) is not low-rank); pFedPara's "+1 switch" adds the
# rank-r term x@X1@Y1ᵀ.

def fedpara_gram_decode(x, x1, y1, x2, y2, *, kind: str = "fedpara",
                        out_dtype=None):
    """y = x @ (X1Y1ᵀ ⊙ f2(X2Y2ᵀ)) via the Gram identity (decode path).

    x: (B, m) with shared factors, or (U, t, m) with per-user residual
    factors x2/y2: (U, m, r)/(U, n, r) (x1/y1 always shared).
    """
    if kind not in ("fedpara", "pfedpara"):
        raise ValueError(f"gram decode is invalid for kind {kind!r}")
    out_dtype = out_dtype or x.dtype
    xf = x.astype(jnp.float32)
    x1f, y1f = x1.astype(jnp.float32), y1.astype(jnp.float32)
    x2f, y2f = x2.astype(jnp.float32), y2.astype(jnp.float32)
    if x.ndim == 3:
        g = jnp.einsum("utm,mi,umj->utij", xf, x1f, x2f)
        y = jnp.einsum("ni,utij,unj->utn", y1f, g, y2f)
        if kind == "pfedpara":
            y = y + jnp.einsum("utm,mi,ni->utn", xf, x1f, y1f)
        return y.astype(out_dtype)
    g = jnp.einsum("bm,mi,mj->bij", xf, x1f, x2f)
    y = jnp.einsum("ni,bij,nj->bn", y1f, g, y2f)
    if kind == "pfedpara":
        y = y + (xf @ x1f) @ y1f.T
    return y.astype(out_dtype)
