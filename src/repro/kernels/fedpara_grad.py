"""Fused FedPara backward Pallas-TPU kernels + the custom-VJP wiring.

Gradients of  y = x @ W,  W = f1(W1) ⊙ f2(W2),  W1 = X1 Y1ᵀ, W2 = X2 Y2ᵀ,
with (f1, f2) covering identity (fedpara), tanh (fedpara_tanh) and the
pFedPara "+1 switch" f2(w) = w + 1:

  dx  = dy @ Wᵀ
  dW  = xᵀ dy                            (never materialized)
  G1  = dW ⊙ f2(W2) ⊙ f1'(W1)           dX1 = G1 Y1,   dY1 = G1ᵀ X1
  G2  = dW ⊙ f1(W1) ⊙ f2'(W2)           dX2 = G2 Y2,   dY2 = G2ᵀ X2

Three kernel bodies, each composing every (bm, bn) tile of W / dW in
VMEM from factor slices and contracting it on the spot, so the dense
(m, n) weight and its cotangent never touch HBM on the backward either:

  _dx_body        grid (B/bb, m/bm, n/bn), n sequential: compose W tile,
                  acc(bb, bm) += dy_tile @ W_tileᵀ.
  _dfactors_body  side="x": grid (m/bm, n/bn, B/bb) — dW tile
                  accumulated over the batch axis in VMEM scratch; at
                  the last batch step the tile is composed into G1/G2
                  and contracted against Y1/Y2 slices into (bm, r)
                  accumulators; dX1/dX2 are written once per m-tile
                  after the n sweep. side="y": grid (n/bn, m/bm, B/bb),
                  the transpose dance — G1ᵀ X1 / G2ᵀ X2 into (bn, r)
                  accumulators for dY1/dY2.

The dX and dY halves are two kernel launches, each re-accumulating the
dW tiles: fusing them would need the full (n, r) dY accumulators
resident in VMEM (27 MB fp32 at the 405B-FFN config — over budget) or
o_ref revisit traffic of O((m/bm)·n·r) — worse than the duplicate
compute. The price is one extra MXU pass and one extra HBM read of
x/dy, still free of any (m, n) term.

All accumulation is fp32 VMEM scratch over sequential grid axes. Every
body also runs with a leading client axis (stacked (C, ...) factors from
the client-batched FL engine) by prepending C to the grid — one launch
per layer for the whole client batch. ``jax.vmap`` over the custom-VJP
entry point lowers the same way: Pallas' batching rule folds the mapped
axis into a leading grid dimension, so the ``ClientBatch`` vmap program
also issues a single launch per layer.

HBM roofline of a full training step (fwd+bwd) per layer: factors are
read 3× and written once (≈4·2r(m+n)·4 B); x is read on the forward and
twice on the backward, dy three times on the backward — ≈5·B(m+n)·4 B.
O(r·(m+n) + B·(m+n)) total, vs the materialize path's O(m·n) for
writing + re-reading W (and dW, and the chain-rule Hadamards) on
forward and backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fedpara_matmul import (
    _ceil_mult,
    _pad_to,
    apply_variant,
    fedpara_matmul,
)


def _tile_factor_grads(dw, w1, w2, *, use_tanh: bool, plus_one: bool):
    """(G1, G2) tiles from a dW tile and the PRE-activation W1/W2 tiles."""
    if use_tanh:
        t1, t2 = jnp.tanh(w1), jnp.tanh(w2)
        f1, f2 = t1, (t2 + 1.0 if plus_one else t2)
        g1 = dw * f2 * (1.0 - t1 * t1)
        g2 = dw * f1 * (1.0 - t2 * t2)
        return g1, g2
    f2 = w2 + 1.0 if plus_one else w2
    return dw * f2, dw * w1


# --------------------------------------------------------------- dx kernel

def _dx_body(dy_ref, x1_ref, y1_ref, x2_ref, y2_ref, o_ref, acc_ref, *,
             use_tanh: bool, plus_one: bool, n_kn: int, lead: bool):
    kn = pl.program_id(3 if lead else 2)
    ld = (lambda ref: ref[0]) if lead else (lambda ref: ref[...])

    @pl.when(kn == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w1 = jax.lax.dot_general(
        ld(x1_ref), ld(y1_ref), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    w2 = jax.lax.dot_general(
        ld(x2_ref), ld(y2_ref), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    w1, w2 = apply_variant(w1, w2, use_tanh=use_tanh, plus_one=plus_one)
    w_tile = w1 * w2  # (bm, bn)

    # dx tile += dy_tile @ W_tileᵀ  (contract the shared n dim).
    acc_ref[...] += jax.lax.dot_general(
        ld(dy_ref), w_tile.astype(dy_ref.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kn == n_kn - 1)
    def _done():
        out = acc_ref[...].astype(o_ref.dtype)
        if lead:
            o_ref[0] = out
        else:
            o_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("use_tanh", "plus_one", "block_b", "block_m", "block_n",
                     "interpret", "out_dtype"),
)
def fedpara_dx(
    dy: jax.Array,
    x1: jax.Array,
    y1: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    *,
    use_tanh: bool = False,
    plus_one: bool = False,
    block_b: int = 128,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """dx = dy @ Wᵀ without materializing W; dy: (B, n) -> dx: (B, m).

    A leading client axis (dy: (C, B, n), Xi: (C, m, r)) selects the
    batched grid.
    """
    lead = dy.ndim == 3
    m = x1.shape[-2]
    n = y1.shape[-2]
    r = x1.shape[-1]
    b = dy.shape[-2]
    out_dtype = out_dtype or dy.dtype
    bb, bm, bn = min(block_b, _ceil_mult(b, 8)), block_m, block_n
    ax = 1 if lead else 0
    dyp = _pad_to(_pad_to(dy, ax, bb), ax + 1, bn)
    x1p, x2p = _pad_to(x1, ax, bm), _pad_to(x2, ax, bm)
    y1p, y2p = _pad_to(y1, ax, bn), _pad_to(y2, ax, bn)
    bp, np_ = dyp.shape[-2], dyp.shape[-1]
    mp = x1p.shape[-2]
    core = (bp // bb, mp // bm, np_ // bn)

    if lead:
        C = dy.shape[0]
        grid = (C,) + core
        in_specs = [
            pl.BlockSpec((1, bb, bn), lambda c, i, j, k: (c, i, k)),
            pl.BlockSpec((1, bm, r), lambda c, i, j, k: (c, j, 0)),
            pl.BlockSpec((1, bn, r), lambda c, i, j, k: (c, k, 0)),
            pl.BlockSpec((1, bm, r), lambda c, i, j, k: (c, j, 0)),
            pl.BlockSpec((1, bn, r), lambda c, i, j, k: (c, k, 0)),
        ]
        out_specs = pl.BlockSpec((1, bb, bm), lambda c, i, j, k: (c, i, j))
        out_shape = jax.ShapeDtypeStruct((C, bp, mp), out_dtype)
    else:
        grid = core
        in_specs = [
            pl.BlockSpec((bb, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, r), lambda i, j, k: (j, 0)),
            pl.BlockSpec((bn, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((bm, r), lambda i, j, k: (j, 0)),
            pl.BlockSpec((bn, r), lambda i, j, k: (k, 0)),
        ]
        out_specs = pl.BlockSpec((bb, bm), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((bp, mp), out_dtype)

    out = pl.pallas_call(
        functools.partial(_dx_body, use_tanh=use_tanh, plus_one=plus_one,
                          n_kn=core[2], lead=lead),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bb, bm), jnp.float32)],
        name="fedpara_dx",
        interpret=interpret,
    )(dyp, x1p, y1p, x2p, y2p)
    return out[..., :b, :m]


# ----------------------------------------------- dX1/dX2, dY1/dY2 kernel

def _dfactors_body(x_ref, dy_ref, x1_ref, y1_ref, x2_ref, y2_ref,
                   d1_ref, d2_ref, dw_ref, a1_ref, a2_ref, *,
                   side: str, use_tanh: bool, plus_one: bool,
                   n_inner: int, n_kb: int, lead: bool):
    """side="x": outputs (dX1, dX2), the inner sweep axis is n tiles.
    side="y": outputs (dY1, dY2), the inner sweep axis is m tiles."""
    off = 1 if lead else 0
    inner = pl.program_id(off + 1)
    kb = pl.program_id(off + 2)
    ld = (lambda ref: ref[0]) if lead else (lambda ref: ref[...])

    @pl.when(kb == 0)
    def _init_dw():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when((kb == 0) & (inner == 0))
    def _init_acc():
        a1_ref[...] = jnp.zeros_like(a1_ref)
        a2_ref[...] = jnp.zeros_like(a2_ref)

    # dW tile += x_tileᵀ @ dy_tile  (contract the shared batch dim).
    dw_ref[...] += jax.lax.dot_general(
        ld(x_ref), ld(dy_ref), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _contract():
        w1 = jax.lax.dot_general(
            ld(x1_ref), ld(y1_ref), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        w2 = jax.lax.dot_general(
            ld(x2_ref), ld(y2_ref), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        g1, g2 = _tile_factor_grads(dw_ref[...], w1, w2,
                                    use_tanh=use_tanh, plus_one=plus_one)
        if side == "x":
            # dX tiles += G @ Y slices  (bm, bn) x (bn, r) -> (bm, r)
            dims, f1_ref, f2_ref = (((1,), (0,)), ((), ())), y1_ref, y2_ref
        else:
            # dY tiles += Gᵀ @ X slices (bm, bn)ᵀ x (bm, r) -> (bn, r)
            dims, f1_ref, f2_ref = (((0,), (0,)), ((), ())), x1_ref, x2_ref
        a1_ref[...] += jax.lax.dot_general(
            g1, ld(f1_ref).astype(jnp.float32), dims,
            preferred_element_type=jnp.float32)
        a2_ref[...] += jax.lax.dot_general(
            g2, ld(f2_ref).astype(jnp.float32), dims,
            preferred_element_type=jnp.float32)

    @pl.when((kb == n_kb - 1) & (inner == n_inner - 1))
    def _done():
        if lead:
            d1_ref[0] = a1_ref[...].astype(d1_ref.dtype)
            d2_ref[0] = a2_ref[...].astype(d2_ref.dtype)
        else:
            d1_ref[...] = a1_ref[...].astype(d1_ref.dtype)
            d2_ref[...] = a2_ref[...].astype(d2_ref.dtype)


def _dfactors(x, dy, x1, y1, x2, y2, *, side: str, use_tanh, plus_one,
              block_b, block_m, block_n, interpret):
    """Shared wrapper for the dX (side='x') / dY (side='y') kernels."""
    lead = x.ndim == 3
    b, m = x.shape[-2], x.shape[-1]
    n = dy.shape[-1]
    r = x1.shape[-1]
    bb, bm, bn = min(block_b, _ceil_mult(b, 8)), block_m, block_n
    ax = 1 if lead else 0
    xp = _pad_to(_pad_to(x, ax, bb), ax + 1, bm)
    dyp = _pad_to(_pad_to(dy, ax, bb), ax + 1, bn)
    x1p, x2p = _pad_to(x1, ax, bm), _pad_to(x2, ax, bm)
    y1p, y2p = _pad_to(y1, ax, bn), _pad_to(y2, ax, bn)
    bp, mp = xp.shape[-2], xp.shape[-1]
    np_ = dyp.shape[-1]
    n_ki, n_kj, n_kb = mp // bm, np_ // bn, bp // bb

    if side == "x":
        core = (n_ki, n_kj, n_kb)         # (i, j, kb): j, kb sequential
        # grid ids within core: a=i (m tile), b=j (n tile), k=batch tile
        i_of, j_of = (lambda a, b: a), (lambda a, b: b)
        out_rows, out_blk = mp, bm
    else:
        core = (n_kj, n_ki, n_kb)         # (j, i, kb): i, kb sequential
        i_of, j_of = (lambda a, b: b), (lambda a, b: a)
        out_rows, out_blk = np_, bn
    body = functools.partial(_dfactors_body, side=side, use_tanh=use_tanh,
                             plus_one=plus_one, n_inner=core[1], n_kb=n_kb,
                             lead=lead)

    if lead:
        C = x.shape[0]
        grid = (C,) + core
        in_specs = [
            pl.BlockSpec((1, bb, bm), lambda c, a, b, k: (c, k, i_of(a, b))),
            pl.BlockSpec((1, bb, bn), lambda c, a, b, k: (c, k, j_of(a, b))),
            pl.BlockSpec((1, bm, r), lambda c, a, b, k: (c, i_of(a, b), 0)),
            pl.BlockSpec((1, bn, r), lambda c, a, b, k: (c, j_of(a, b), 0)),
            pl.BlockSpec((1, bm, r), lambda c, a, b, k: (c, i_of(a, b), 0)),
            pl.BlockSpec((1, bn, r), lambda c, a, b, k: (c, j_of(a, b), 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, out_blk, r), lambda c, a, b, k: (c, a, 0)),
            pl.BlockSpec((1, out_blk, r), lambda c, a, b, k: (c, a, 0)),
        ]
        out_shape = [jax.ShapeDtypeStruct((C, out_rows, r), jnp.float32)] * 2
    else:
        grid = core
        in_specs = [
            pl.BlockSpec((bb, bm), lambda a, b, k: (k, i_of(a, b))),
            pl.BlockSpec((bb, bn), lambda a, b, k: (k, j_of(a, b))),
            pl.BlockSpec((bm, r), lambda a, b, k: (i_of(a, b), 0)),
            pl.BlockSpec((bn, r), lambda a, b, k: (j_of(a, b), 0)),
            pl.BlockSpec((bm, r), lambda a, b, k: (i_of(a, b), 0)),
            pl.BlockSpec((bn, r), lambda a, b, k: (j_of(a, b), 0)),
        ]
        out_specs = [
            pl.BlockSpec((out_blk, r), lambda a, b, k: (a, 0)),
            pl.BlockSpec((out_blk, r), lambda a, b, k: (a, 0)),
        ]
        out_shape = [jax.ShapeDtypeStruct((out_rows, r), jnp.float32)] * 2

    d1, d2 = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),     # dW tile accumulator
            pltpu.VMEM((out_blk, r), jnp.float32),
            pltpu.VMEM((out_blk, r), jnp.float32),
        ],
        name=f"fedpara_d{side}_factors",
        interpret=interpret,
    )(xp, dyp, x1p, y1p, x2p, y2p)
    rows = m if side == "x" else n
    return d1[..., :rows, :], d2[..., :rows, :]


@functools.partial(
    jax.jit,
    static_argnames=("use_tanh", "plus_one", "block_b", "block_m", "block_n",
                     "interpret"),
)
def fedpara_dx_factors(x, dy, x1, y1, x2, y2, *, use_tanh=False,
                       plus_one=False, block_b=128, block_m=256,
                       block_n=256, interpret=False):
    """(dX1, dX2) = (G1 Y1, G2 Y2) with dW/W tiles composed in VMEM."""
    return _dfactors(x, dy, x1, y1, x2, y2, side="x", use_tanh=use_tanh,
                     plus_one=plus_one, block_b=block_b, block_m=block_m,
                     block_n=block_n, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("use_tanh", "plus_one", "block_b", "block_m", "block_n",
                     "interpret"),
)
def fedpara_dy_factors(x, dy, x1, y1, x2, y2, *, use_tanh=False,
                       plus_one=False, block_b=128, block_m=256,
                       block_n=256, interpret=False):
    """(dY1, dY2) = (G1ᵀ X1, G2ᵀ X2) with dW/W tiles composed in VMEM."""
    return _dfactors(x, dy, x1, y1, x2, y2, side="y", use_tanh=use_tanh,
                     plus_one=plus_one, block_b=block_b, block_m=block_m,
                     block_n=block_n, interpret=interpret)


# ------------------------------------------------------------- custom VJP

@functools.lru_cache(maxsize=None)
def differentiable_matmul(use_tanh: bool, plus_one: bool, block_b: int,
                          block_m: int, block_n: int, interpret: bool,
                          out_dtype=None):
    """A ``jax.custom_vjp`` around the fused matmul: forward saves only
    the factors and activations (never W), backward runs the fused grad
    kernels. Cached per static config so repeated traces reuse one
    primitive. Works on (B, m) inputs and on client-stacked (C, B, m)
    inputs (batched grids), and composes with ``jax.vmap`` (Pallas'
    batching rule folds the mapped axis into the grid — one launch)."""
    kw = dict(use_tanh=use_tanh, plus_one=plus_one, block_b=block_b,
              block_m=block_m, block_n=block_n, interpret=interpret)

    @jax.custom_vjp
    def matmul(x, x1, y1, x2, y2):
        return fedpara_matmul(x, x1, y1, x2, y2, out_dtype=out_dtype, **kw)

    def fwd(x, x1, y1, x2, y2):
        return matmul(x, x1, y1, x2, y2), (x, x1, y1, x2, y2)

    def bwd(res, dy):
        x, x1, y1, x2, y2 = res
        dx = fedpara_dx(dy, x1, y1, x2, y2, out_dtype=x.dtype, **kw)
        dx1, dx2 = fedpara_dx_factors(x, dy, x1, y1, x2, y2, **kw)
        dy1, dy2 = fedpara_dy_factors(x, dy, x1, y1, x2, y2, **kw)
        return (dx, dx1.astype(x1.dtype), dy1.astype(y1.dtype),
                dx2.astype(x2.dtype), dy2.astype(y2.dtype))

    matmul.defvjp(fwd, bwd)
    return matmul
