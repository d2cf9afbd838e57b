"""Public jit'd wrappers for the FedPara Pallas kernels.

``interpret`` defaults to True when no TPU is present so the same code
path runs (slowly but correctly) on CPU; on TPU backends the compiled
Mosaic kernels are used. Operands placed over a multi-device mesh run
each kernel replicated under ``shard_map`` (``repro.kernels.spmd``).

``fedpara_matmul`` is DIFFERENTIABLE: it is a ``jax.custom_vjp`` whose
forward and backward are both fused Pallas kernels
(``repro.kernels.fedpara_grad``), so ``jax.value_and_grad`` of a loss
through it never materializes the dense (m, n) weight or its cotangent
— in HBM the training step moves only factors and activations,
O(r·(m+n) + B·(m+n)) bytes instead of O(m·n). All three paper variants
(fedpara, fedpara_tanh, pfedpara) are supported, block sizes come from
one table shared by forward and backward (``repro.kernels.blocks``),
and client-stacked (C, ...) inputs — or ``jax.vmap`` over a client axis,
as in the batched FL engine — lower to a single launch per layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import agg, blocks, fedpara_grad, ref, serve_matmul, spmd
from repro.kernels.fedpara_compose import fedpara_compose as _compose


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_kind(kind=None, use_tanh: bool = False) -> str:
    """Normalize a fused-matmul variant name: ``kind`` wins when given
    (validated against fedpara | fedpara_tanh | pfedpara), else the
    legacy ``use_tanh`` flag selects fedpara vs fedpara_tanh."""
    if kind is None:
        return "fedpara_tanh" if use_tanh else "fedpara"
    if kind not in ("fedpara", "fedpara_tanh", "pfedpara"):
        raise ValueError(f"unsupported fused-matmul kind: {kind!r}")
    return kind


def _resolve_cfg(x1, y1, kind, use_tanh, interpret, block_b, block_m, block_n):
    kind = resolve_kind(kind, use_tanh)
    interpret = _default_interpret() if interpret is None else interpret
    m, n, r = x1.shape[-2], y1.shape[-2], x1.shape[-1]
    tb, tm, tn = blocks.select_blocks(m, n, r)
    return (kind, interpret, block_b or tb, block_m or tm, block_n or tn)


def fedpara_matmul(x, x1, y1, x2, y2, *, kind=None, use_tanh=False,
                   interpret=None, block_b=None, block_m=None, block_n=None,
                   out_dtype=None):
    """y = x @ (f1(X1Y1ᵀ)⊙f2(X2Y2ᵀ)) — fused AND differentiable; W never
    materialized in HBM on forward or backward.

    Args:
        x: activations ``(..., B, m)``.
        x1, x2: row factors ``(..., m, r)``.
        y1, y2: column factors ``(..., n, r)``.
        kind: ``fedpara`` (f = identity) | ``fedpara_tanh`` | ``pfedpara``
            (f2 adds the "+1 switch"); see :func:`resolve_kind`.
        interpret: force Pallas interpret mode (default: auto — compiled
            on TPU, interpret elsewhere).
        block_b/block_m/block_n: tile overrides (default: the shared
            ``repro.kernels.blocks`` table keyed on (m, n, r)).
        out_dtype: output dtype (default: x's dtype).

    Returns:
        ``(..., B, n)``. Leading batch dims (e.g. a client axis) fold
        into the kernel grid — one launch per layer even under vmap.
    """
    kind, interpret, bb, bm, bn = _resolve_cfg(
        x1, y1, kind, use_tanh, interpret, block_b, block_m, block_n)
    f = fedpara_grad.differentiable_matmul(
        kind == "fedpara_tanh", kind == "pfedpara", bb, bm, bn, interpret,
        jnp.dtype(out_dtype).name if out_dtype is not None else None)
    return spmd.call(f, x, x1, y1, x2, y2)


def fedpara_matmul_vjp(x, x1, y1, x2, y2, dy, *, kind=None, use_tanh=False,
                       interpret=None, block_b=None, block_m=None,
                       block_n=None):
    """Directly evaluate the fused backward: (dx, dX1, dY1, dX2, dY2).

    Exposed for tests/benchmarks; training paths get this implicitly via
    ``jax.grad`` through :func:`fedpara_matmul`.
    """
    kind, interpret, bb, bm, bn = _resolve_cfg(
        x1, y1, kind, use_tanh, interpret, block_b, block_m, block_n)
    kw = dict(use_tanh=kind == "fedpara_tanh", plus_one=kind == "pfedpara",
              block_b=bb, block_m=bm, block_n=bn, interpret=interpret)

    def vjp(x, x1, y1, x2, y2, dy):
        dx = fedpara_grad.fedpara_dx(dy, x1, y1, x2, y2, out_dtype=x.dtype,
                                     **kw)
        dx1, dx2 = fedpara_grad.fedpara_dx_factors(x, dy, x1, y1, x2, y2,
                                                   **kw)
        dy1, dy2 = fedpara_grad.fedpara_dy_factors(x, dy, x1, y1, x2, y2,
                                                   **kw)
        return dx, dx1, dy1, dx2, dy2

    return spmd.call(vjp, x, x1, y1, x2, y2, dy)


def fedpara_compose(x1, y1, x2, y2, *, use_tanh=False, interpret=None, **kw):
    """W = (X1Y1ᵀ)⊙(X2Y2ᵀ) — tiled compose (serving pre-composition)."""
    interpret = _default_interpret() if interpret is None else interpret
    return spmd.call(lambda *f: _compose(*f, use_tanh=use_tanh,
                                         interpret=interpret, **kw),
                     x1, y1, x2, y2)


def pfedpara_compose(x1, y1, x2, y2, *, interpret=None, **kw):
    """W = (X1Y1ᵀ) ⊙ (X2Y2ᵀ + 1) — pFedPara compose."""
    interpret = _default_interpret() if interpret is None else interpret
    return spmd.call(lambda *f: _compose(*f, plus_one=True,
                                         interpret=interpret, **kw),
                     x1, y1, x2, y2)


def _serve_blocks(m, n, r, block_b, block_m, block_n):
    tb, tm, tn = blocks.select_serve_blocks(m, n, r)
    return block_b or tb, block_m or tm, block_n or tn


def w8_matmul(x, w, scale=None, *, interpret=None, block_b=None,
              block_m=None, block_n=None, out_dtype=None):
    """y = (x @ W)·s against a pre-composed serving weight cache.

    Args:
        x: activations ``(B, m)``.
        w: cached weight ``(m, n)`` — int8 (with ``scale``) or fp16/bf16.
        scale: per-output-channel scales ``(1, n)`` fp32 (None for an
            unquantized cache).
        interpret: force Pallas interpret mode (default: auto).
        block_b/block_m/block_n: tile overrides (default: the serve tile
            table ``repro.kernels.blocks.select_serve_blocks``).
        out_dtype: output dtype (default: x's dtype).

    Returns:
        ``(B, n)``. An int8 cache is widened only inside the kernel's
        VMEM tiles — never in HBM (the serve program contract).
    """
    interpret = _default_interpret() if interpret is None else interpret
    bb, bm, bn = _serve_blocks(w.shape[0], w.shape[1], 0,
                               block_b, block_m, block_n)
    return spmd.call(lambda x, w, scale: serve_matmul.w8_matmul(
        x, w, scale, block_b=bb, block_m=bm, block_n=bn,
        interpret=interpret, out_dtype=out_dtype), x, w, scale)


def cache_residual_matmul(x, w, scale, x2, y2, *, interpret=None,
                          block_b=None, block_m=None, block_n=None,
                          out_dtype=None):
    """pFedPara serve matmul: y = (x @ (W ⊙ (X2Y2ᵀ + 1)))·s, where W is
    the shared composed-W1 cache (int8 or fp16) and (X2, Y2) are a
    user's personal factors — the per-user weight never exists.

    Args:
        x: activations — ``(B, m)`` for one user, or ``(U, t, m)`` for U
            distinct users (t tokens each, one launch total).
        w: shared cache ``(m, n)``; ``scale``: ``(1, n)`` fp32 or None.
        x2, y2: personal factors ``(m, r)``/``(n, r)``, with a leading
            user axis in the many-user layout.
        interpret / block_* / out_dtype: as :func:`w8_matmul`.

    Returns:
        ``(B, n)`` or ``(U, t, n)``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    bb, bm, bn = _serve_blocks(w.shape[0], w.shape[1], x2.shape[-1],
                               block_b, block_m, block_n)
    return spmd.call(lambda *a: serve_matmul.cache_residual_matmul(
        *a, block_b=bb, block_m=bm, block_n=bn, interpret=interpret,
        out_dtype=out_dtype), x, w, scale, x2, y2)


def fedpara_gram_decode(x, x1, y1, x2, y2, *, kind=None, out_dtype=None):
    """Decode-batch fused matmul via the Hadamard-Gram identity:
    y = rowsum((Y1·(X1ᵀ diag(x) X2)) ⊙ Y2) — O(r²(m+n)) FLOPs per token,
    factor bytes only, and NO dense (m, n) intermediate anywhere (so no
    Pallas kernel is needed; XLA has nothing to materialize).

    Args:
        x: activations ``(B, m)``, or ``(U, t, m)`` with per-user
            residual factors.
        x1, y1: shared factors ``(m, r1)``/``(n, r1)``.
        x2, y2: residual factors — shared ``(m, r2)``/``(n, r2)`` or
            per-user ``(U, m, r2)``/``(U, n, r2)``.
        kind: ``fedpara`` | ``pfedpara`` (the tanh variant is not
            low-rank and is rejected).
        out_dtype: output dtype (default: x's dtype).

    Returns:
        ``(B, n)`` or ``(U, t, n)``.
    """
    return serve_matmul.fedpara_gram_decode(
        x, x1, y1, x2, y2, kind=resolve_kind(kind), out_dtype=out_dtype)


def dequant_acc(acc, q, coeff, *, interpret=None, **kw):
    """acc += coeff @ dequant(q): fused streaming-aggregation reduction
    (interpret resolved like the matmul kernels)."""
    interpret = _default_interpret() if interpret is None else interpret
    return agg.dequant_acc(acc, q, coeff, interpret=interpret, **kw)


tree_dequant_acc = agg.tree_dequant_acc
sharded_tree_dequant_acc = agg.sharded_tree_dequant_acc

# Re-export oracles for convenience.
fedpara_matmul_ref = ref.fedpara_matmul_ref
fedpara_compose_ref = ref.fedpara_compose_ref
pfedpara_compose_ref = ref.pfedpara_compose_ref
fedpara_matmul_vjp_ref = ref.fedpara_matmul_vjp_ref
dequant_acc_ref = ref.dequant_acc_ref
tree_dequant_acc_ref = ref.tree_dequant_acc_ref
w8_matmul_ref = ref.w8_matmul_ref
cache_residual_ref = ref.cache_residual_ref
select_blocks = blocks.select_blocks
select_agg_blocks = blocks.select_agg_blocks
select_serve_blocks = blocks.select_serve_blocks
