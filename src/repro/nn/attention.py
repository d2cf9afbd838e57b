"""GQA attention: chunked full-sequence form + single-token decode.

Features used by the assigned archs: grouped-query attention, rotary
embeddings (full / half "2d"), qk-norm (qwen3/gemma3/chameleon), sliding
windows (mixtral), per-layer local/global windows (gemma3, passed as a
traced scalar so layers can be scanned), cross-attention (whisper),
NoPE (``rope_style="none"``) with a configured score scale
(granite-4.0-h's ``attention_multiplier``), and ring-buffer KV caches
for windowed decode at 500k.

Full-sequence attention scans over query chunks (flash-style memory
behaviour: the (C, S) score tile is the only quadratic buffer alive).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.nn import layers
from repro.nn.layers import dense, init_dense, init_scale, rms_norm

NEG_INF = -1e30


def init_attention(key: jax.Array, cfg: ArchConfig, *, cross: bool = False) -> Dict:
    hd = cfg.resolved_head_dim()
    d, H, Hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": init_dense(ks[0], d, H * hd, cfg.param),
        "wk": init_dense(ks[1], d, Hkv * hd, cfg.param),
        "wv": init_dense(ks[2], d, Hkv * hd, cfg.param),
        "wo": init_dense(ks[3], H * hd, d, cfg.param),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_scale(hd)
        p["k_norm"] = init_scale(hd)
    return p


def score_scale(cfg: ArchConfig) -> float:
    """The factor on q·k: ``cfg.attention_multiplier``, else 1/sqrt(hd)."""
    return cfg.attention_multiplier or 1.0 / (cfg.resolved_head_dim() ** 0.5)


def _project_qkv(p, cfg: ArchConfig, xq, xkv, positions_q, positions_kv, dtype, use_pallas):
    """Project and rope q (from xq) and k,v (from xkv)."""
    hd = cfg.resolved_head_dim()
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    rotary_frac = 0.5 if cfg.rope_style == "half" else 1.0

    q = dense(p["wq"], xq, cfg.param, dtype, use_pallas)
    q = q.reshape(*xq.shape[:-1], H, hd)
    k = dense(p["wk"], xkv, cfg.param, dtype, use_pallas)
    k = k.reshape(*xkv.shape[:-1], Hkv, hd)
    v = dense(p["wv"], xkv, cfg.param, dtype, use_pallas)
    v = v.reshape(*xkv.shape[:-1], Hkv, hd)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions_q is not None and cfg.rope_style != "none":
        # rope disabled for cross-attn / whisper abs / NoPE
        q = layers.apply_rope(q, positions_q, cfg.rope_base, rotary_frac)
        k = layers.apply_rope(k, positions_kv, cfg.rope_base, rotary_frac)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,C,Hkv,G,hd), k: (B,S,Hkv,hd) -> (B,Hkv,G,C,S).

    bf16 in/out (MXU accumulates fp32 internally); callers cast to fp32
    at the softmax. Keeping the einsum in compute dtype keeps BACKWARD
    cotangents bf16 too — with preferred_element_type=f32 the fp32
    cotangents propagate into every TP all-reduce on the residual
    stream (measured 3x collective-byte inflation)."""
    return jnp.einsum("bckgh,bskh->bkgcs", q, k)


def _gqa_out(probs, v):
    """probs: (B,Hkv,G,C,S), v: (B,S,Hkv,hd) -> (B,C,Hkv,G,hd)."""
    return jnp.einsum("bkgcs,bskh->bckgh", probs.astype(v.dtype), v)


def full_attention(
    p: Dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    window,                      # traced or static; 0/None => full causal
    chunk: int = 512,
    causal: bool = True,
    use_rope: bool = True,
    xkv: Optional[jax.Array] = None,
    dtype=jnp.bfloat16,
    use_pallas: bool = False,
) -> jax.Array:
    """Full-sequence (train / prefill) attention, scanned over q chunks."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim()
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    G = H // Hkv
    xkv = x if xkv is None else xkv
    Skv = xkv.shape[1]

    pos_q = jnp.broadcast_to(jnp.arange(S), (B, S))
    pos_kv = jnp.broadcast_to(jnp.arange(Skv), (B, Skv))
    q, k, v = _project_qkv(
        p, cfg, x, xkv,
        pos_q if use_rope else None, pos_kv if use_rope else None,
        dtype, use_pallas,
    )
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", "kv_seq_attn", "kv_heads", None)
    v = constrain(v, "batch", "kv_seq_attn", "kv_heads", None)

    C = min(chunk, S)
    n_chunks = (S + C - 1) // C
    Spad = n_chunks * C
    if Spad != S:
        q = jnp.pad(q, ((0, 0), (0, Spad - S), (0, 0), (0, 0)))
    qc = q.reshape(B, n_chunks, C, Hkv, G, hd)
    scale = score_scale(cfg)

    kv_pos = jnp.arange(Skv)
    if window is None:
        window = 0
    w = jnp.asarray(window, jnp.int32)

    def chunk_fn(carry, qi_idx):
        qi, idx = qi_idx
        q_pos = idx * C + jnp.arange(C)
        s = _gqa_scores(qi, k).astype(jnp.float32) * scale   # (B,Hkv,G,C,S)
        s = constrain(s, "batch", None, None, None, "kv_seq_attn")
        if causal:
            m = q_pos[:, None] >= kv_pos[None, :]
            m &= jnp.where(w > 0, kv_pos[None, :] > q_pos[:, None] - w, True)
        else:
            m = jnp.ones((C, Skv), bool)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        pbs = jax.nn.softmax(s, axis=-1)
        return carry, _gqa_out(pbs, v)               # (B,C,Hkv,G,hd)

    _, outs = jax.lax.scan(
        jax.checkpoint(chunk_fn), 0,
        (jnp.moveaxis(qc, 1, 0), jnp.arange(n_chunks)),
    )
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Spad, H * hd)[:, :S]
    out = constrain(out.reshape(B, S, H, hd), "batch", None, "heads", None)
    y = dense(p["wo"], out.reshape(B, S, H * hd), cfg.param, dtype, use_pallas)
    return constrain(y, "batch", "seq", None)


# ----------------------------------------------------------------- KV cache

def init_kv_cache(cfg: ArchConfig, batch: int, max_seq: int, n_sites: int,
                  dtype=jnp.bfloat16, int8: bool = False) -> Dict[str, jax.Array]:
    """(sites, B, S_cache, Hkv, hd) ring-buffered when a sliding window
    bounds the reuse distance. ``int8=True`` stores K/V quantized with
    per-(position, head) scales — halves the decode-dominant KV
    streaming bytes (§Perf cell B)."""
    hd = cfg.resolved_head_dim()
    S_cache = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    shape = (n_sites, batch, S_cache, cfg.n_kv_heads, hd)
    if int8:
        sshape = (n_sites, batch, S_cache, cfg.n_kv_heads, 1)
        return {"k_q": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_q": jnp.zeros(shape, jnp.int8),
                "v_s": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def quantize_kv(x: jax.Array):
    """Per-(position, head) symmetric int8. x: (..., hd)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                                keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return q.astype(dtype) * scale.astype(dtype)


def prefill_attention(
    p: Dict,
    x: jax.Array,
    cfg: ArchConfig,
    cache_kv: Tuple[jax.Array, jax.Array],   # (B, S_cache, Hkv, hd) slices
    *,
    window,
    chunk: int = 512,
    use_rope: bool = True,
    dtype=jnp.bfloat16,
    use_pallas: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-seq attention that also fills the (single-site) KV cache.

    Assumes prompt length S <= S_cache (ring wrap handled by modulo
    scatter when windowed).
    """
    B, S, _ = x.shape
    ck, cv = cache_kv
    S_cache = ck.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    rotary = pos if use_rope else None
    q, k, v = _project_qkv(p, cfg, x, x, rotary, rotary, dtype, use_pallas)

    if S <= S_cache:
        # common case: prompt fits the cache — a plain slice write (the
        # modulo scatter materializes giant gather/scatter temporaries)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), 0, 1)
    elif S % S_cache == 0:
        # windowed ring with aligned wrap: the last S_cache positions land
        # on slots 0..S_cache-1 in order
        ck = k[:, -S_cache:].astype(ck.dtype)
        cv = v[:, -S_cache:].astype(cv.dtype)
    else:
        slots = jnp.arange(S) % S_cache               # general ring scatter
        ck = ck.at[:, slots].set(k.astype(ck.dtype))
        cv = cv.at[:, slots].set(v.astype(cv.dtype))

    # reuse the chunked path for the actual attention over (k, v)
    y = _chunked_attend(q, k, v, cfg, window=window, chunk=chunk)
    out = dense(p["wo"], y.reshape(B, S, -1), cfg.param, dtype, use_pallas)
    return out, (ck, cv)


def _chunked_attend(q, k, v, cfg, *, window, chunk):
    k = constrain(k, "batch", "kv_seq_attn", "kv_heads", None)
    v = constrain(v, "batch", "kv_seq_attn", "kv_heads", None)
    B, S, H, hd = q.shape
    Hkv = cfg.n_kv_heads
    G = H // Hkv
    C = min(chunk, S)
    n_chunks = (S + C - 1) // C
    Spad = n_chunks * C
    if Spad != S:
        q = jnp.pad(q, ((0, 0), (0, Spad - S), (0, 0), (0, 0)))
    qc = jnp.moveaxis(q.reshape(B, n_chunks, C, Hkv, G, hd), 1, 0)
    kv_pos = jnp.arange(S)
    w = jnp.asarray(0 if window is None else window, jnp.int32)
    scale = score_scale(cfg)

    def chunk_fn(carry, qi_idx):
        qi, idx = qi_idx
        q_pos = idx * C + jnp.arange(C)
        s = _gqa_scores(qi, k).astype(jnp.float32) * scale
        s = constrain(s, "batch", None, None, None, "kv_seq_attn")
        m = q_pos[:, None] >= kv_pos[None, :]
        m &= jnp.where(w > 0, kv_pos[None, :] > q_pos[:, None] - w, True)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        return carry, _gqa_out(jax.nn.softmax(s, axis=-1), v)

    _, outs = jax.lax.scan(jax.checkpoint(chunk_fn), 0, (qc, jnp.arange(n_chunks)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Spad, H, hd)[:, :S]


def decode_attention(
    p: Dict,
    x: jax.Array,                      # (B, 1, d)
    cfg: ArchConfig,
    cache_kv: Tuple[jax.Array, jax.Array],
    pos: jax.Array,                    # scalar int32: index of the new token
    *,
    window,
    use_rope: bool = True,
    dtype=jnp.bfloat16,
    use_pallas: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode against the (possibly ring-buffered) KV cache."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim()
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    G = H // Hkv
    ck, cv = cache_kv
    S_cache = ck.shape[1]

    pos_b = jnp.broadcast_to(pos, (B, 1)) if use_rope else None
    q, k, v = _project_qkv(p, cfg, x, x, pos_b, pos_b, dtype, use_pallas)

    slot = pos % S_cache
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), slot, axis=1)
    ck = constrain(ck, "batch", "kv_seq", None, None)
    cv = constrain(cv, "batch", "kv_seq", None, None)

    qh = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qh, ck).astype(jnp.float32)
    s = s * score_scale(cfg)
    # slot i holds global position: before wrap, i; after, the newest
    # S_cache positions — valid iff written (slot idx <= pos) and within window
    idx = jnp.arange(S_cache)
    written = idx <= pos
    if cfg.sliding_window:
        valid = written  # ring size == window: everything written is in-window
    else:
        w = jnp.asarray(0 if window is None else window, jnp.int32)
        valid = written & jnp.where(w > 0, idx > pos - w, True)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    pbs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", pbs.astype(cv.dtype), cv)
    out = out.reshape(B, 1, H * hd)
    return dense(p["wo"], out, cfg.param, dtype, use_pallas), (ck, cv)


def cross_decode_attention(
    p: Dict,
    x: jax.Array,                      # (B, 1, d)
    cfg: ArchConfig,
    kv: Tuple[jax.Array, jax.Array],   # precomputed encoder K/V (B, S_enc, Hkv, hd)
    dtype=jnp.bfloat16,
    use_pallas: bool = False,
) -> jax.Array:
    B = x.shape[0]
    hd = cfg.resolved_head_dim()
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    G = H // Hkv
    k, v = kv
    q = dense(p["wq"], x, cfg.param, dtype, use_pallas).reshape(B, Hkv, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q, k).astype(jnp.float32)
    pbs = jax.nn.softmax(s * score_scale(cfg), axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", pbs.astype(v.dtype), v).reshape(B, 1, H * hd)
    return dense(p["wo"], out, cfg.param, dtype, use_pallas)


def cross_kv(p: Dict, enc_out: jax.Array, cfg: ArchConfig, dtype=jnp.bfloat16):
    """Precompute cross-attention K/V once from encoder output."""
    hd = cfg.resolved_head_dim()
    Hkv = cfg.n_kv_heads
    B, S, _ = enc_out.shape
    k = dense(p["wk"], enc_out, cfg.param, dtype).reshape(B, S, Hkv, hd)
    v = dense(p["wv"], enc_out, cfg.param, dtype).reshape(B, S, Hkv, hd)
    return k, v
