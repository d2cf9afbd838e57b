"""Fused FedPara matmul Pallas-TPU kernel (forward).

Computes  y = x @ W  with  W = f1(X1 Y1ᵀ) ⊙ f2(X2 Y2ᵀ)  WITHOUT
materializing the dense (m, n) weight in HBM: each (bm, bn) tile of W is
composed in VMEM from factor slices and immediately contracted against
the matching x tile on the MXU. The elementwise pair (f1, f2) covers all
paper variants: identity (fedpara), tanh (fedpara_tanh, supp. B) and the
pFedPara "+1 switch" f2(w) = w + 1 (§2.3).

Memory-roofline rationale (TPU v5e, 819 GB/s HBM): the unfused path
writes + reads W once per step — 2·m·n·2 bytes of HBM traffic per layer.
For a (16384, 53248) LLaMA-405B FFN weight that is 3.5 GB; fused, HBM
traffic is only the factors (≈2·2R(m+n)·2 bytes ≈ 71 MB at R=128) plus
x/y activations. Compose FLOPs run on the MXU at bm×bn×r granularity.

Grid = (B/bb, n/bn, m/bm); the last (m) axis is the sequential reduction
axis on TPU, accumulated in an fp32 VMEM scratch. With a leading client
axis — x: (C, B, m), Xi: (C, m, r), Yi: (C, n, r), the stacked layout of
the client-batched FL engine — the same body runs on a
(C, B/bb, n/bn, m/bm) grid: one launch composes every client's tiles.

The matching backward kernels (``repro.kernels.fedpara_grad``) keep the
whole training step dense-W-free; ``repro.kernels.ops.fedpara_matmul``
wires them together as a ``jax.custom_vjp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def apply_variant(w1, w2, *, use_tanh: bool, plus_one: bool):
    """(f1(W1), f2(W2)) tiles for the active FedPara variant."""
    if use_tanh:
        w1, w2 = jnp.tanh(w1), jnp.tanh(w2)
    if plus_one:
        w2 = w2 + 1.0
    return w1, w2


def _kernel(x_ref, x1_ref, y1_ref, x2_ref, y2_ref, o_ref, acc_ref, *,
            use_tanh: bool, plus_one: bool, n_km: int):
    km = pl.program_id(2)

    @pl.when(km == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Compose the (bm, bn) weight tile in VMEM (fp32 on the MXU).
    w1 = jax.lax.dot_general(
        x1_ref[...], y1_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w2 = jax.lax.dot_general(
        x2_ref[...], y2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w1, w2 = apply_variant(w1, w2, use_tanh=use_tanh, plus_one=plus_one)
    w_tile = w1 * w2  # (bm, bn)

    # Contract the x tile against the composed tile; accumulate fp32.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_tile.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(km == n_km - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_batched(x_ref, x1_ref, y1_ref, x2_ref, y2_ref, o_ref, acc_ref, *,
                    use_tanh: bool, plus_one: bool, n_km: int):
    # refs carry a leading (1,) client dim: one client per grid step.
    km = pl.program_id(3)

    @pl.when(km == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w1 = jax.lax.dot_general(
        x1_ref[0], y1_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w2 = jax.lax.dot_general(
        x2_ref[0], y2_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w1, w2 = apply_variant(w1, w2, use_tanh=use_tanh, plus_one=plus_one)
    w_tile = w1 * w2

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_tile.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(km == n_km - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


@functools.partial(
    jax.jit,
    static_argnames=("use_tanh", "plus_one", "block_b", "block_m", "block_n",
                     "interpret", "out_dtype"),
)
def fedpara_matmul(
    x: jax.Array,
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
    """y = x @ (f1(X1Y1ᵀ)⊙f2(X2Y2ᵀ));  x: (B, m), Xi: (m, r), Yi: (n, r).

    With a leading client axis (x: (C, B, m), Xi: (C, m, r)) the batched
    grid variant runs — one launch for all C clients.
    """
    if x.ndim == 3:
        return _fedpara_matmul_batched(
            x, x1, y1, x2, y2, use_tanh=use_tanh, plus_one=plus_one,
            block_b=block_b, block_m=block_m, block_n=block_n,
            interpret=interpret, out_dtype=out_dtype)
    b, m = x.shape
    n = y1.shape[0]
    r = x1.shape[1]
    out_dtype = out_dtype or x.dtype

    bb, bm, bn = min(block_b, _ceil_mult(b, 8)), block_m, block_n
    xp = _pad_to(_pad_to(x, 0, bb), 1, bm)
    x1p, x2p = _pad_to(x1, 0, bm), _pad_to(x2, 0, bm)
    y1p, y2p = _pad_to(y1, 0, bn), _pad_to(y2, 0, bn)
    bp, mp = xp.shape
    np_ = y1p.shape[0]
    grid = (bp // bb, np_ // bn, mp // bm)

    out = pl.pallas_call(
        functools.partial(_kernel, use_tanh=use_tanh, plus_one=plus_one,
                          n_km=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),
            pl.BlockSpec((bm, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        name="fedpara_matmul",
        interpret=interpret,
    )(xp, x1p, y1p, x2p, y2p)
    return out[:b, :n]


def _fedpara_matmul_batched(x, x1, y1, x2, y2, *, use_tanh, plus_one,
                            block_b, block_m, block_n, interpret, out_dtype):
    C, b, m = x.shape
    n = y1.shape[1]
    r = x1.shape[2]
    out_dtype = out_dtype or x.dtype

    bb, bm, bn = min(block_b, _ceil_mult(b, 8)), block_m, block_n
    xp = _pad_to(_pad_to(x, 1, bb), 2, bm)
    x1p, x2p = _pad_to(x1, 1, bm), _pad_to(x2, 1, bm)
    y1p, y2p = _pad_to(y1, 1, bn), _pad_to(y2, 1, bn)
    bp, mp = xp.shape[1], xp.shape[2]
    np_ = y1p.shape[1]
    grid = (C, bp // bb, np_ // bn, mp // bm)

    out = pl.pallas_call(
        functools.partial(_kernel_batched, use_tanh=use_tanh,
                          plus_one=plus_one, n_km=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bb, bm), lambda c, i, j, k: (c, i, k)),
            pl.BlockSpec((1, bm, r), lambda c, i, j, k: (c, k, 0)),
            pl.BlockSpec((1, bn, r), lambda c, i, j, k: (c, j, 0)),
            pl.BlockSpec((1, bm, r), lambda c, i, j, k: (c, k, 0)),
            pl.BlockSpec((1, bn, r), lambda c, i, j, k: (c, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb, bn), lambda c, i, j, k: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, bp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        name="fedpara_matmul",
        interpret=interpret,
    )(xp, x1p, y1p, x2p, y2p)
    return out[:, :b, :n]


def _ceil_mult(v: int, mult: int) -> int:
    return max(mult, ((v + mult - 1) // mult) * mult)
