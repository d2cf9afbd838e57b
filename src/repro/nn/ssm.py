"""Mamba2-style SSD block (for zamba2) — chunked train form + O(1) decode.

Simplified-but-faithful SSD: per head h with state N, scalar decay
a_t = exp(-softplus(dt_t)·exp(A_log)) and input/output projections B, C:

    S_t = a_t · S_{t-1} + dt·x_t ⊗ B_t          (state: (P, N))
    y_t = C_t · S_t + D ⊙ x_t

Training uses the chunkwise-parallel algorithm (quadratic within a
chunk, linear scan across chunks) — the TPU-native adaptation of the
Mamba2 kernel: each chunk's intra-term is a masked (C×C) matmul on the
MXU, the inter-term carries the (H, P, N) state.

The in/out/gate projections are FedPara-factorized; the SSM dynamics
parameters (A_log, D, dt bias, conv and its optional bias) are small and
stay dense. The in-projection's columns are laid out (x, z, B, C, dt).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.nn.layers import dense, init_dense


def ssm_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim
    H = d_inner // P
    return d_inner, H, P


def init_mamba(key: jax.Array, cfg: ArchConfig) -> Dict:
    d = cfg.d_model
    d_inner, H, P = ssm_dims(cfg)
    N = cfg.ssm_state
    ks = jax.random.split(key, 8)
    p = {
        "w_in": init_dense(ks[0], d, 2 * d_inner + 2 * N + H, cfg.param),  # x, z, B, C, dt
        "w_out": init_dense(ks[1], d_inner, d, cfg.param),
        "conv_w": jax.random.normal(ks[2], (cfg.ssm_conv, d_inner + 2 * N), jnp.float32) * 0.2,
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": {"scale": jnp.ones((d_inner,), jnp.float32)},
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((d_inner + 2 * N,), jnp.float32)
    return p


def _split_proj(proj, cfg):
    d_inner, H, P = ssm_dims(cfg)
    N = cfg.ssm_state
    xz, rest = proj[..., : 2 * d_inner], proj[..., 2 * d_inner:]
    xbc = xz[..., :d_inner]
    z = xz[..., d_inner:]
    B = rest[..., :N]
    C = rest[..., N: 2 * N]
    dt = rest[..., 2 * N:]
    return xbc, z, B, C, dt


def _causal_conv(x: jax.Array, w: jax.Array, state=None, b=None):
    """Depthwise causal conv along seq, then SiLU. x: (B,S,D), w: (K,D),
    optional bias b: (D,).

    Returns conv output and the trailing (K-1) inputs as next state."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    out = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(K))
    if b is not None:
        out = out + b
    new_state = xp[:, -(K - 1):] if K > 1 else state
    return jax.nn.silu(out), new_state


def mamba_forward(
    p: Dict,
    x: jax.Array,                      # (B, S, d)
    cfg: ArchConfig,
    *,
    chunk: int = 256,
    dtype=jnp.bfloat16,
    use_pallas: bool = False,
    return_state: bool = False,
):
    B, S, d = x.shape
    d_inner, H, P = ssm_dims(cfg)
    N = cfg.ssm_state

    proj = dense(p["w_in"], x, cfg.param, dtype, use_pallas)
    xbc_raw, z, Bmat, Cmat, dt = _split_proj(proj, cfg)
    conv_in = jnp.concatenate([xbc_raw, Bmat, Cmat], axis=-1).astype(jnp.float32)
    conv_out, _ = _causal_conv(conv_in, p["conv_w"], b=p.get("conv_b"))
    final_conv_state = conv_in[:, -(cfg.ssm_conv - 1):] if cfg.ssm_conv > 1 else None
    xs = conv_out[..., :d_inner]
    Bmat = conv_out[..., d_inner: d_inner + N]
    Cmat = conv_out[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,S,H)
    xh = xs.reshape(B, S, H, P)

    C = min(chunk, S)
    nc = (S + C - 1) // C
    Sp = nc * C
    if Sp != S:  # pad with dt=0 steps: a=1 (no decay), zero state input
        pad = Sp - S
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
        dt = dt * (jnp.arange(Sp) < S).astype(dt.dtype)[None, :, None]
    loga = -dt * jnp.exp(p["A_log"])                   # log decay, a in (0,1]

    def reshape_c(t):  # (B,S,...) -> (nc, B, C, ...)
        return jnp.moveaxis(t.reshape(B, nc, C, *t.shape[2:]), 1, 0)

    ac, dtc, xc = reshape_c(loga), reshape_c(dt), reshape_c(xh)
    Bc, Cc = reshape_c(Bmat), reshape_c(Cmat)

    def chunk_step(state, inp):
        loga, dt_, x_, B_, C_ = inp                                  # (B,C,H),(B,C,H),(B,C,H,P),(B,C,N)
        cum = jnp.cumsum(loga, axis=1)                               # (B,C,H)
        # intra-chunk: y_t += C_t · Σ_{s<=t} exp(cum_t - cum_s) dt_s x_s B_sᵀ
        rel = cum[:, :, None, :] - cum[:, None, :, :]                # (B,C,C,H) t,s
        mask = jnp.tril(jnp.ones((C, C), bool))
        # mask BEFORE exp: where(mask, exp(rel), 0) with inf in the dead
        # branch produces NaN gradients (inf * 0 cotangent)
        rel = jnp.where(mask[None, :, :, None], rel, -1e30)
        g = jnp.exp(rel)                                             # (B,C,C,H)
        kernel = jnp.einsum("btsh,btn,bsn,bsh->btsh", g, C_, B_, dt_)
        y_intra = jnp.einsum("btsh,bshp->bthp", kernel, x_)
        # inter-chunk: y_t += C_t · exp(cum_t) state
        y_inter = jnp.einsum("bth,btn,bhpn->bthp", jnp.exp(cum), C_, state)
        # state update: state' = exp(cum_C) state + Σ_s exp(cum_C - cum_s) dt_s x_s B_sᵀ
        tail = jnp.exp(cum[:, -1:, :] - cum)                         # (B,C,H)
        state = jnp.exp(cum[:, -1])[:, :, None, None] * state + jnp.einsum(
            "bsh,bsh,bshp,bsn->bhpn", tail, dt_, x_, B_
        )
        return state, y_intra + y_inter

    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    final_state, ys = jax.lax.scan(jax.checkpoint(chunk_step), s0,
                                   (ac, dtc, xc, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, Sp, H, P)[:, :S]
    y = y + p["D"][None, None, :, None] * xh[:, :S]
    y = y.reshape(B, S, d_inner).astype(dtype)
    y = y * jax.nn.silu(z.astype(dtype))
    # group RMS norm on d_inner (mamba2 style)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + cfg.norm_eps) * p["norm"]["scale"]).astype(dtype)
    out = dense(p["w_out"], y, cfg.param, dtype, use_pallas)
    if return_state:
        return out, (final_state, final_conv_state)
    return out


def init_mamba_cache(cfg: ArchConfig, batch: int, n_layers: int) -> Dict:
    d_inner, H, P = ssm_dims(cfg)
    N = cfg.ssm_state
    K = cfg.ssm_conv
    return {
        "ssm": jnp.zeros((n_layers, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((n_layers, batch, K - 1, d_inner + 2 * N), jnp.float32),
    }


def mamba_decode_step(
    p: Dict,
    x: jax.Array,                     # (B, 1, d)
    cfg: ArchConfig,
    cache: Tuple[jax.Array, jax.Array],  # ssm (B,H,P,N), conv (B,K-1,D)
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    B = x.shape[0]
    d_inner, H, P = ssm_dims(cfg)
    N = cfg.ssm_state
    ssm_state, conv_state = cache

    proj = dense(p["w_in"], x, cfg.param, dtype)
    xbc_raw, z, Bmat, Cmat, dt = _split_proj(proj, cfg)
    conv_in = jnp.concatenate([xbc_raw, Bmat, Cmat], axis=-1).astype(jnp.float32)
    conv_out, conv_state = _causal_conv(conv_in, p["conv_w"], conv_state,
                                        b=p.get("conv_b"))
    xs = conv_out[..., :d_inner]
    Bm = conv_out[:, 0, d_inner: d_inner + N]                        # (B,N)
    Cm = conv_out[:, 0, d_inner + N:]

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))
    xh = xs[:, 0].reshape(B, H, P)
    ssm_state = a[:, :, None, None] * ssm_state + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xh, Bm
    )
    y = jnp.einsum("bhpn,bn->bhp", ssm_state, Cm) + p["D"][None, :, None] * xh
    y = y.reshape(B, 1, d_inner).astype(dtype) * jax.nn.silu(z.astype(dtype))
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + cfg.norm_eps) * p["norm"]["scale"]).astype(dtype)
    return dense(p["w_out"], y, cfg.param, dtype), (ssm_state, conv_state)
