"""Model assembly: decoder-only LMs, MoE, hybrid (zamba2's shared block,
granite-4.0-h's per-layer mixer pattern), xLSTM stacks, and the whisper
enc-dec — all driven by ArchConfig.

Layer iteration supports two modes:
  scan=True   lax.scan over stacked layer params (compact HLO, fast
              compiles, correct memory_analysis) — default.
  scan=False  python-unrolled (used by the dry-run cost-accounting
              variants, where every layer must appear in the HLO so
              cost_analysis/collective-byte counts are exact).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.nn import attention as attn
from repro.nn import moe as moe_mod
from repro.nn import ssm as ssm_mod
from repro.nn import xlstm as xlstm_mod
from repro.nn.layers import (
    act_fn,
    dense,
    init_dense,
    init_scale,
    precompose_tree,
    rms_norm,
)


@dataclass(frozen=True)
class ModelOptions:
    attn_chunk: int = 512
    ssm_chunk: int = 256
    logit_chunk: int = 1024
    scan_layers: bool = True
    remat: bool = True
    use_pallas: bool = False
    int8_kv: bool = False          # quantized decode KV cache (DecoderLM)
    dtype: Any = jnp.bfloat16


# ------------------------------------------------------------------ helpers

def _tree_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _remat_group(n: int, threshold: int = 32) -> int:
    """Divisor of n nearest sqrt(n) (1 => flat scan). Deep stacks (126
    layers x 128MB residuals = 16GB/chip for llama3-405B) use a nested
    sqrt-schedule scan: the outer scan saves only n/G group boundaries,
    the checkpointed inner scan re-runs G layers during backward —
    O(n/G + G) residuals instead of O(n)."""
    if n < threshold:
        return 1
    import math

    best = 1
    for g in range(2, n + 1):
        if n % g == 0 and abs(g - math.isqrt(n)) < abs(best - math.isqrt(n)):
            best = g
    return best


def iterate_layers(body, carry, stacked, xs, n: int, scan: bool, remat: bool):
    """Run ``body(carry, layer_params, x_i) -> carry`` over n layers."""
    def wrapped(c, px):
        p, x = px
        return body(c, p, x), None

    if remat:
        wrapped = jax.checkpoint(wrapped)
    if scan:
        g = _remat_group(n) if remat else 1
        if g > 1:
            grouped = jax.tree.map(
                lambda a: a.reshape(n // g, g, *a.shape[1:]), (stacked, xs))

            @jax.checkpoint
            def group(c, gx):
                return jax.lax.scan(wrapped, c, gx)

            carry, _ = jax.lax.scan(group, carry, grouped)
            return carry
        carry, _ = jax.lax.scan(wrapped, carry, (stacked, xs))
        return carry
    for i in range(n):
        carry, _ = wrapped(carry, (_tree_index(stacked, i), _tree_index(xs, i)))
    return carry


def iterate_layers_cache(body, carry, stacked, cache, n: int, scan: bool):
    """Like iterate_layers but threads and returns per-layer cache."""
    def wrapped(c, pc):
        p, cch = pc
        c, new_cch = body(c, p, cch)
        return c, new_cch

    if scan:
        carry, new_cache = jax.lax.scan(wrapped, carry, (stacked, cache))
        return carry, new_cache
    new_caches = []
    for i in range(n):
        carry, nc = wrapped(carry, (_tree_index(stacked, i), _tree_index(cache, i)))
        new_caches.append(nc)
    stacked_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
    return carry, stacked_cache


def sinusoidal_pos(positions: jax.Array, d: int) -> jax.Array:
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------- MLP/FFN

def init_mlp(key, cfg: ArchConfig, d_in: Optional[int] = None, d_ff: Optional[int] = None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":  # SwiGLU
        return {
            "w_gate": init_dense(ks[0], d, f, cfg.param),
            "w_up": init_dense(ks[1], d, f, cfg.param),
            "w_down": init_dense(ks[2], f, d, cfg.param),
        }
    return {
        "w_up": init_dense(ks[0], d, f, cfg.param),
        "w_down": init_dense(ks[1], f, d, cfg.param),
    }


def mlp(p, x, cfg: ArchConfig, dtype, use_pallas=False):
    a = act_fn(cfg.act)
    if "w_gate" in p:
        h = a(dense(p["w_gate"], x, cfg.param, dtype, use_pallas)) * dense(
            p["w_up"], x, cfg.param, dtype, use_pallas
        )
    else:
        h = a(dense(p["w_up"], x, cfg.param, dtype, use_pallas))
    h = constrain(h, "batch", None, "ffn")
    return constrain(dense(p["w_down"], h, cfg.param, dtype, use_pallas),
                     "batch", "seq", None)


# ----------------------------------------------------------- loss utilities

def chunked_ce_loss(h: jax.Array, unembed_w: jax.Array, targets: jax.Array,
                    mask: jax.Array, chunk: int,
                    logit_scale: float = 1.0) -> jax.Array:
    """Next-token CE, unembedding seq-chunk by seq-chunk (bounds the fp32
    logit buffer to (B, chunk, V)). The fp32 logits are multiplied by
    ``logit_scale`` before the softmax."""
    B, S, d = h.shape
    C = min(chunk, S)
    nc = (S + C - 1) // C
    Sp = nc * C
    if Sp != S:
        h = jnp.pad(h, ((0, 0), (0, Sp - S), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, Sp - S)))
        mask = jnp.pad(mask, ((0, 0), (0, Sp - S)))
    hc = jnp.moveaxis(h.reshape(B, nc, C, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, nc, C), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, nc, C), 1, 0)

    def step(acc, inp):
        hi, ti, mi = inp
        # bf16 matmul (fp32 MXU accumulation), fp32 softmax math. The
        # target logit is read with a one-hot contraction — a gather
        # across the vocab-sharded axis would force GSPMD to all-gather
        # the full fp32 logits.
        logits = jnp.einsum("bcd,dv->bcv", hi, unembed_w).astype(jnp.float32)
        if logit_scale != 1.0:
            logits = logits * logit_scale
        logits = constrain(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(ti, logits.shape[-1], dtype=logits.dtype)
        onehot = constrain(onehot, "batch", None, "vocab")
        tgt = jnp.sum(logits * onehot, axis=-1)
        nll = (lse - tgt) * mi
        return (acc[0] + nll.sum(), acc[1] + mi.sum()), None

    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(step),
                                 (jnp.zeros((), jnp.float32),) * 2, (hc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


# ============================================================ decoder-only LM

class DecoderLM:
    """dense / moe / vlm families (llama4, mixtral, chatglm3, llama3,
    gemma3, qwen3, chameleon)."""

    def __init__(self, cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
        self.cfg = cfg
        self.opts = opts

    # ---------------- init
    def init_layer(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p = {
            "ln1": init_scale(cfg.d_model),
            "attn": attn.init_attention(ks[0], cfg),
            "ln2": init_scale(cfg.d_model),
        }
        if cfg.n_experts:
            p["moe"] = moe_mod.init_moe(ks[1], cfg)
        else:
            p["mlp"] = init_mlp(ks[1], cfg)
        return p

    def init_params(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        layer_keys = jax.random.split(ks[0], cfg.n_layers)
        layers = jax.vmap(self.init_layer)(layer_keys)
        emb = jax.random.normal(ks[1], (cfg.vocab_size, cfg.d_model), jnp.float32)
        p = {
            "embed": {"w": emb * (1.0 / cfg.d_model ** 0.5)},
            "layers": layers,
            "final_norm": init_scale(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            unemb = jax.random.normal(ks[2], (cfg.d_model, cfg.vocab_size), jnp.float32)
            p["unembed"] = {"w": unemb * (1.0 / cfg.d_model ** 0.5)}
        return p

    # ---------------- per-layer window schedule (gemma3 local:global)
    def layer_windows(self, seq_hint: int) -> jax.Array:
        cfg = self.cfg
        L = cfg.n_layers
        if cfg.local_global_period:
            is_global = (jnp.arange(L) % cfg.local_global_period) == (
                cfg.local_global_period - 1
            )
            return jnp.where(is_global, 0, cfg.local_window).astype(jnp.int32)
        return jnp.full((L,), cfg.sliding_window, jnp.int32)

    # ---------------- train forward
    def hidden_states(self, params, tokens) -> jax.Array:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][tokens].astype(opts.dtype)
        h = constrain(h, "batch", "seq", None)
        windows = self.layer_windows(tokens.shape[1])

        def body(h, p, window):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            h = h + attn.full_attention(
                p["attn"], x, cfg, window=window, chunk=opts.attn_chunk,
                dtype=opts.dtype, use_pallas=opts.use_pallas,
            )
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            if cfg.n_experts:
                h = h + moe_mod.moe_ffn(p["moe"], x, cfg, opts.dtype)
            else:
                h = h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)
            return constrain(h, "batch", "seq", None)

        h = iterate_layers(body, h, params["layers"], windows,
                           cfg.n_layers, opts.scan_layers, opts.remat)
        return rms_norm(h, params["final_norm"], cfg.norm_eps)

    def unembed_w(self, params, dtype):
        if self.cfg.tie_embeddings:
            return params["embed"]["w"].astype(dtype).T
        return params["unembed"]["w"].astype(dtype)

    def loss(self, params, batch) -> jax.Array:
        tokens = batch["tokens"]
        h = self.hidden_states(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        return chunked_ce_loss(h, self.unembed_w(params, self.opts.dtype),
                               targets, mask, self.opts.logit_chunk)

    # ---------------- serving
    def init_cache(self, batch: int, max_seq: int) -> Dict:
        return attn.init_kv_cache(self.cfg, batch, max_seq, self.cfg.n_layers,
                                  dtype=self.opts.dtype,
                                  int8=self.opts.int8_kv)

    def prefill(self, params, tokens, cache) -> Tuple[Dict, jax.Array]:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][tokens].astype(opts.dtype)
        windows = self.layer_windows(tokens.shape[1])

        def body(h, p_cache_w):
            (p, kv, window) = p_cache_w
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            y, kv = attn.prefill_attention(
                p["attn"], x, cfg, kv, window=window, chunk=opts.attn_chunk,
                dtype=opts.dtype, use_pallas=opts.use_pallas,
            )
            h = h + y
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            if cfg.n_experts:
                h = h + moe_mod.moe_ffn(p["moe"], x, cfg, opts.dtype)
            else:
                h = h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)
            return h, kv

        def wrapped(c, pcw):
            p, kvc, w = pcw
            if "k_q" in kvc:
                kin = (attn.dequantize_kv(kvc["k_q"], kvc["k_s"], opts.dtype),
                       attn.dequantize_kv(kvc["v_q"], kvc["v_s"], opts.dtype))
                c, kv = body(c, (p, kin, w))
                kq, ks = attn.quantize_kv(kv[0])
                vq, vs = attn.quantize_kv(kv[1])
                return c, {"k_q": kq, "k_s": ks, "v_q": vq, "v_s": vs}
            c, kv = body(c, (p, (kvc["k"], kvc["v"]), w))
            return c, {"k": kv[0], "v": kv[1]}

        if opts.scan_layers:
            h, cache = jax.lax.scan(wrapped, h, (params["layers"], cache, windows))
        else:
            new = []
            for i in range(cfg.n_layers):
                h, kv = wrapped(h, (_tree_index(params["layers"], i),
                                    _tree_index(cache, i), windows[i]))
                new.append(kv)
            cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                            self.unembed_w(params, jnp.float32))
        return cache, logits

    def decode_step(self, params, cache, token, pos) -> Tuple[jax.Array, Dict]:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][token].astype(opts.dtype)   # (B,1,d)
        windows = self.layer_windows(0)

        def body(h, pcw):
            p, kvc, window = pcw
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            if "k_q" in kvc:  # int8 cache: dequant for attend, quant writes
                k = attn.dequantize_kv(kvc["k_q"], kvc["k_s"], opts.dtype)
                v = attn.dequantize_kv(kvc["v_q"], kvc["v_s"], opts.dtype)
                y, (ck, cv) = attn.decode_attention(
                    p["attn"], x, cfg, (k, v), pos,
                    window=window, dtype=opts.dtype,
                    use_pallas=opts.use_pallas)
                kq, ks = attn.quantize_kv(ck)
                vq, vs = attn.quantize_kv(cv)
                new_kvc = {"k_q": kq, "k_s": ks, "v_q": vq, "v_s": vs}
            else:
                y, (ck, cv) = attn.decode_attention(
                    p["attn"], x, cfg, (kvc["k"], kvc["v"]), pos,
                    window=window, dtype=opts.dtype,
                    use_pallas=opts.use_pallas,
                )
                new_kvc = {"k": ck, "v": cv}
            h = h + y
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            if cfg.n_experts:
                h = h + moe_mod.moe_ffn(p["moe"], x, cfg, opts.dtype)
            else:
                h = h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)
            return h, new_kvc

        h, cache = self._decode_layers(body, h, params, cache, windows)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bod,dv->bov", h.astype(jnp.float32),
                            self.unembed_w(params, jnp.float32))
        return logits[:, 0], cache

    def _decode_layers(self, body, h, params, cache, windows):
        cfg, opts = self.cfg, self.opts

        def wrapped(c, x):
            p, kvc, w = x
            c, nkv = body(c, (p, kvc, w))
            return c, nkv

        if opts.scan_layers:
            return jax.lax.scan(wrapped, h, (params["layers"], cache, windows))
        new = []
        for i in range(cfg.n_layers):
            h, kv = wrapped(h, (_tree_index(params["layers"], i),
                                _tree_index(cache, i), windows[i]))
            new.append(kv)
        return h, jax.tree.map(lambda *xs: jnp.stack(xs), *new)

    def precompose(self, params, int8: bool = False):
        return precompose_tree(params, self.cfg.param, self.opts.dtype,
                               int8=int8)


# ============================================================= zamba2 hybrid

class HybridSSM:
    """zamba2: stacks of Mamba2 blocks with ONE shared attention+MLP
    block applied every ``attn_every`` positions (zamba's weight-sharing
    trick: a single parameter set, ``n_sites`` call sites, each with its
    own KV cache)."""

    def __init__(self, cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
        assert cfg.n_layers % cfg.attn_every == 0
        self.cfg = cfg
        self.opts = opts
        self.per = cfg.attn_every
        self.n_sites = cfg.n_layers // cfg.attn_every

    def init_params(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        layer_keys = jax.random.split(ks[0], cfg.n_layers)

        def init_block(k):
            kk = jax.random.split(k, 2)
            return {"ln": init_scale(cfg.d_model),
                    "mamba": ssm_mod.init_mamba(kk[0], cfg)}

        blocks = jax.vmap(init_block)(layer_keys)
        blocks = jax.tree.map(
            lambda a: a.reshape(self.n_sites, self.per, *a.shape[1:]), blocks
        )
        emb = jax.random.normal(ks[1], (cfg.vocab_size, cfg.d_model), jnp.float32)
        return {
            "embed": {"w": emb * (1.0 / cfg.d_model ** 0.5)},
            "blocks": blocks,
            "shared": {
                "ln1": init_scale(cfg.d_model),
                "attn": attn.init_attention(ks[2], cfg),
                "ln2": init_scale(cfg.d_model),
                "mlp": init_mlp(ks[3], cfg),
            },
            "final_norm": init_scale(cfg.d_model),
            "unembed": {"w": jax.random.normal(ks[4], (cfg.d_model, cfg.vocab_size),
                                               jnp.float32) * (1.0 / cfg.d_model ** 0.5)},
        }

    def _shared_block(self, params, h, cache_kv=None, pos=None, mode="train"):
        cfg, opts = self.cfg, self.opts
        sp = params["shared"]
        x = rms_norm(h, sp["ln1"], cfg.norm_eps)
        if mode == "train":
            y = attn.full_attention(sp["attn"], x, cfg, window=0,
                                    chunk=opts.attn_chunk, dtype=opts.dtype,
                                    use_pallas=opts.use_pallas)
            new_kv = None
        elif mode == "prefill":
            y, new_kv = attn.prefill_attention(sp["attn"], x, cfg, cache_kv,
                                               window=0, chunk=opts.attn_chunk,
                                               dtype=opts.dtype,
                                               use_pallas=opts.use_pallas)
        else:
            y, new_kv = attn.decode_attention(sp["attn"], x, cfg, cache_kv, pos,
                                              window=0, dtype=opts.dtype,
                                              use_pallas=opts.use_pallas)
        h = h + y
        x = rms_norm(h, sp["ln2"], cfg.norm_eps)
        h = h + mlp(sp["mlp"], x, cfg, opts.dtype, opts.use_pallas)
        return h, new_kv

    def hidden_states(self, params, tokens) -> jax.Array:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][tokens].astype(opts.dtype)
        h = constrain(h, "batch", "seq", None)

        def body(h, p, _):
            x = rms_norm(h, p["ln"], cfg.norm_eps)
            return h + ssm_mod.mamba_forward(p["mamba"], x, cfg,
                                             chunk=opts.ssm_chunk, dtype=opts.dtype,
                                             use_pallas=opts.use_pallas)

        def shared(h, sp_params):
            return self._shared_block(sp_params, h, mode="train")[0]

        shared_fn = jax.checkpoint(shared) if opts.remat else shared
        for s in range(self.n_sites):
            site = _tree_index(params["blocks"], s)
            h = iterate_layers(body, h, site, jnp.zeros((self.per,)),
                               self.per, opts.scan_layers, opts.remat)
            h = shared_fn(h, params)
        return rms_norm(h, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch) -> jax.Array:
        tokens = batch["tokens"]
        h = self.hidden_states(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        return chunked_ce_loss(h, params["unembed"]["w"].astype(self.opts.dtype),
                               targets, mask, self.opts.logit_chunk)

    def init_cache(self, batch: int, max_seq: int) -> Dict:
        cfg = self.cfg
        mc = ssm_mod.init_mamba_cache(cfg, batch, cfg.n_layers)
        mc = jax.tree.map(
            lambda a: a.reshape(self.n_sites, self.per, *a.shape[1:]), mc
        )
        kv = attn.init_kv_cache(cfg, batch, max_seq, self.n_sites, dtype=self.opts.dtype)
        return {"mamba": mc, "kv": kv}

    def prefill(self, params, tokens, cache) -> Tuple[Dict, jax.Array]:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][tokens].astype(opts.dtype)
        new_mamba, new_kv = [], []
        for s in range(self.n_sites):
            site = _tree_index(params["blocks"], s)
            site_states = []
            for l in range(self.per):
                p = _tree_index(site, l)
                x = rms_norm(h, p["ln"], cfg.norm_eps)
                y, (ssm_s, conv_s) = ssm_mod.mamba_forward(
                    p["mamba"], x, cfg, chunk=opts.ssm_chunk, dtype=opts.dtype,
                    return_state=True)
                h = h + y
                site_states.append({"ssm": ssm_s, "conv": conv_s})
            new_mamba.append(jax.tree.map(lambda *xs: jnp.stack(xs), *site_states))
            kvc = _tree_index(cache["kv"], s)
            h, kv = self._shared_block(params, h, (kvc["k"], kvc["v"]), mode="prefill")
            new_kv.append({"k": kv[0], "v": kv[1]})
        cache = {
            "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *new_mamba),
            "kv": jax.tree.map(lambda *xs: jnp.stack(xs), *new_kv),
        }
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                            params["unembed"]["w"].astype(jnp.float32))
        return cache, logits

    def decode_step(self, params, cache, token, pos) -> Tuple[jax.Array, Dict]:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][token].astype(opts.dtype)
        new_mamba, new_kv = [], []
        for s in range(self.n_sites):
            site = _tree_index(params["blocks"], s)
            site_states = []
            for l in range(self.per):
                p = _tree_index(site, l)
                mc = _tree_index(cache["mamba"], s)
                mcl = _tree_index(mc, l)
                x = rms_norm(h, p["ln"], cfg.norm_eps)
                y, (ssm_s, conv_s) = ssm_mod.mamba_decode_step(
                    p["mamba"], x, cfg, (mcl["ssm"], mcl["conv"]), dtype=opts.dtype)
                h = h + y
                site_states.append({"ssm": ssm_s, "conv": conv_s})
            new_mamba.append(jax.tree.map(lambda *xs: jnp.stack(xs), *site_states))
            kvc = _tree_index(cache["kv"], s)
            h, kv = self._shared_block(params, h, (kvc["k"], kvc["v"]), pos=pos,
                                       mode="decode")
            new_kv.append({"k": kv[0], "v": kv[1]})
        cache = {
            "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *new_mamba),
            "kv": jax.tree.map(lambda *xs: jnp.stack(xs), *new_kv),
        }
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bod,dv->bov", h.astype(jnp.float32),
                            params["unembed"]["w"].astype(jnp.float32))
        return logits[:, 0], cache

    def precompose(self, params, int8: bool = False):
        return precompose_tree(params, self.cfg.param, self.opts.dtype,
                               int8=int8)


# ====================================================== per-layer hybrid

class HybridLM:
    """Hybrid stack with a per-layer mixer pattern (granite-4.0-h).

    ``cfg.block_pattern`` repeats over the ``n_layers``: ``m`` is a
    Mamba-2 mixer, ``a`` a GQA attention mixer. Every layer, of either
    kind, has its own weights and its own MLP::

        h += residual_multiplier * mixer(rms(h))
        h += residual_multiplier * mlp(rms(h))

    after ``h = embedding_multiplier * embed[tokens]``; the logits are
    divided by ``logits_scaling``. Each kind's layers are stacked
    ``(periods, layers of that kind in a period, ...)``. Training scans
    over whole periods and, inside one, over each run of same-kind
    layers (``iterate_layers``, with DecoderLM's remat and
    ``use_pallas``). Mixers, MLPs and the unembedding run under the
    named scopes ``lm.mamba``, ``lm.attention``, ``lm.mlp`` and
    ``lm.unembed``."""

    KINDS = {"m": "mamba", "a": "attn"}

    def __init__(self, cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
        pat = cfg.block_pattern
        if set(pat) - set(self.KINDS) or cfg.n_layers % len(pat):
            raise ValueError(f"pattern {pat!r} over {cfg.n_layers} layers")
        self.cfg, self.opts = cfg, opts
        self.n_periods = cfg.n_layers // len(pat)
        self.per_period = {k: pat.count(k) for k in self.KINDS if k in pat}
        # [(kind, first index among that kind's layers, run length)]
        self.runs, seen = [], dict.fromkeys(self.KINDS, 0)
        for kind, run in itertools.groupby(pat):
            n = len(list(run))
            self.runs.append((kind, seen[kind], n))
            seen[kind] += n

    # ---------------- init
    def _init_layer(self, kind: str, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        mixer = (ssm_mod.init_mamba(ks[0], cfg) if kind == "m"
                 else attn.init_attention(ks[0], cfg))
        return {"ln1": init_scale(cfg.d_model), "mixer": mixer,
                "ln2": init_scale(cfg.d_model), "mlp": init_mlp(ks[1], cfg)}

    def init_params(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 2 + len(self.per_period))
        emb = jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
        p = {"embed": {"w": emb * (1.0 / cfg.d_model ** 0.5)},
             "final_norm": init_scale(cfg.d_model)}
        for k, (kind, n) in zip(ks[2:], self.per_period.items()):
            layers = jax.vmap(lambda kk, kind=kind: self._init_layer(kind, kk))(
                jax.random.split(k, self.n_periods * n))
            p[self.KINDS[kind]] = jax.tree.map(
                lambda a, n=n: a.reshape(self.n_periods, n, *a.shape[1:]), layers)
        if not cfg.tie_embeddings:
            unemb = jax.random.normal(ks[1], (cfg.d_model, cfg.vocab_size), jnp.float32)
            p["unembed"] = {"w": unemb * (1.0 / cfg.d_model ** 0.5)}
        return p

    # ---------------- one layer
    def _mixer(self, kind: str, p, x, mode="train", cache=None, pos=None):
        """The layer's mixer on normed ``x``; returns (y, new cache)."""
        cfg, opts = self.cfg, self.opts
        if kind == "m":
            with jax.named_scope("lm.mamba"):
                if mode == "train":
                    return ssm_mod.mamba_forward(
                        p, x, cfg, chunk=opts.ssm_chunk, dtype=opts.dtype,
                        use_pallas=opts.use_pallas), None
                if mode == "prefill":
                    y, (ssm_s, conv_s) = ssm_mod.mamba_forward(
                        p, x, cfg, chunk=opts.ssm_chunk, dtype=opts.dtype,
                        use_pallas=opts.use_pallas, return_state=True)
                else:
                    y, (ssm_s, conv_s) = ssm_mod.mamba_decode_step(
                        p, x, cfg, (cache["ssm"], cache["conv"]),
                        dtype=opts.dtype)
                return y, {"ssm": ssm_s, "conv": conv_s}
        with jax.named_scope("lm.attention"):
            if mode == "train":
                return attn.full_attention(
                    p, x, cfg, window=0, chunk=opts.attn_chunk,
                    dtype=opts.dtype, use_pallas=opts.use_pallas), None
            if mode == "prefill":
                y, (k, v) = attn.prefill_attention(
                    p, x, cfg, (cache["k"], cache["v"]), window=0,
                    chunk=opts.attn_chunk, dtype=opts.dtype,
                    use_pallas=opts.use_pallas)
            else:
                y, (k, v) = attn.decode_attention(
                    p, x, cfg, (cache["k"], cache["v"]), pos, window=0,
                    dtype=opts.dtype, use_pallas=opts.use_pallas)
            return y, {"k": k, "v": v}

    def _layer(self, kind: str, p, h, mode="train", cache=None, pos=None):
        cfg, opts = self.cfg, self.opts
        rm = cfg.residual_multiplier
        y, cache = self._mixer(kind, p["mixer"], rms_norm(h, p["ln1"], cfg.norm_eps),
                               mode, cache, pos)
        h = h + rm * y
        with jax.named_scope("lm.mlp"):
            h = h + rm * mlp(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps),
                             cfg, opts.dtype, opts.use_pallas)
        return constrain(h, "batch", "seq", None), cache

    def _embed(self, params, tokens):
        w = params["embed"]["w"][tokens]
        return (w * self.cfg.embedding_multiplier).astype(self.opts.dtype)

    def _unembed_w(self, params, dtype):
        if self.cfg.tie_embeddings:
            return params["embed"]["w"].astype(dtype).T
        return params["unembed"]["w"].astype(dtype)

    # ---------------- train forward
    def hidden_states(self, params, tokens) -> jax.Array:
        cfg, opts = self.cfg, self.opts
        h = constrain(self._embed(params, tokens), "batch", "seq", None)
        bodies = {k: (lambda c, p, _, k=k: self._layer(k, p, c)[0])
                  for k in self.per_period}

        def period(h, pp):
            for kind, start, n in self.runs:
                run = jax.tree.map(lambda a: a[start: start + n],
                                   pp[self.KINDS[kind]])
                h = iterate_layers(bodies[kind], h, run, jnp.zeros((n,)), n,
                                   opts.scan_layers, opts.remat)
            return h

        stacks = {self.KINDS[k]: params[self.KINDS[k]] for k in self.per_period}
        if opts.scan_layers:
            h, _ = jax.lax.scan(lambda c, pp: (period(c, pp), None), h, stacks)
        else:
            for i in range(self.n_periods):
                h = period(h, _tree_index(stacks, i))
        return rms_norm(h, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch) -> jax.Array:
        tokens = batch["tokens"]
        h = self.hidden_states(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        with jax.named_scope("lm.unembed"):
            return chunked_ce_loss(h, self._unembed_w(params, self.opts.dtype),
                                   targets, mask, self.opts.logit_chunk,
                                   1.0 / self.cfg.logits_scaling)

    # ---------------- serving (layers unrolled, as HybridSSM)
    def _layer_sites(self):
        """[(kind, period, index among the period's layers of that kind)]
        of every layer in stack order."""
        out = []
        for t in range(self.n_periods):
            for kind, start, n in self.runs:
                out.extend((kind, t, start + j) for j in range(n))
        return out

    def init_cache(self, batch: int, max_seq: int) -> Dict:
        cfg, out = self.cfg, {}
        for kind, n in self.per_period.items():
            sites = self.n_periods * n
            c = (ssm_mod.init_mamba_cache(cfg, batch, sites) if kind == "m"
                 else attn.init_kv_cache(cfg, batch, max_seq, sites,
                                         dtype=self.opts.dtype))
            out[self.KINDS[kind]] = jax.tree.map(
                lambda a, n=n: a.reshape(self.n_periods, n, *a.shape[1:]), c)
        return out

    def _serve(self, params, h, cache, mode, pos=None):
        new = {name: [] for name in cache}
        for kind, t, i in self._layer_sites():
            name = self.KINDS[kind]
            p = _tree_index(_tree_index(params[name], t), i)
            c = _tree_index(_tree_index(cache[name], t), i)
            h, c = self._layer(kind, p, h, mode, c, pos)
            new[name].append(c)
        cache = {}
        for name, cs in new.items():
            n = len(cs) // self.n_periods
            cache[name] = jax.tree.map(
                lambda *xs, n=n: jnp.stack(xs).reshape(
                    self.n_periods, n, *xs[0].shape), *cs)
        h = rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                            self._unembed_w(params, jnp.float32))
        return cache, logits / self.cfg.logits_scaling

    def prefill(self, params, tokens, cache) -> Tuple[Dict, jax.Array]:
        return self._serve(params, self._embed(params, tokens), cache, "prefill")

    def decode_step(self, params, cache, token, pos) -> Tuple[jax.Array, Dict]:
        cache, logits = self._serve(params, self._embed(params, token), cache,
                                    "decode", pos)
        return logits, cache

    def precompose(self, params, int8: bool = False):
        return precompose_tree(params, self.cfg.param, self.opts.dtype,
                               int8=int8)


# ================================================================ xLSTM stack

class XLSTMStack:
    """Alternating sLSTM / mLSTM blocks per ``cfg.block_pattern`` repeated
    over n_layers. Blocks are python-unrolled (the interleaved block types
    have different param structures; 12 small blocks keep the HLO tiny, so
    cost_analysis is exact without scan tricks)."""

    def __init__(self, cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
        self.cfg = cfg
        self.opts = opts
        pat = cfg.block_pattern or "m"
        reps = (cfg.n_layers + len(pat) - 1) // len(pat)
        self.pattern = (pat * reps)[: cfg.n_layers]

    def init_params(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, cfg.n_layers + 3)
        blocks = []
        for i, t in enumerate(self.pattern):
            sub = {"ln": init_scale(cfg.d_model)}
            if t == "s":
                sub["slstm"] = xlstm_mod.init_slstm(ks[i], cfg)
            else:
                sub["mlstm"] = xlstm_mod.init_mlstm(ks[i], cfg)
            blocks.append(sub)
        emb = jax.random.normal(ks[-2], (cfg.vocab_size, cfg.d_model), jnp.float32)
        return {
            "embed": {"w": emb * (1.0 / cfg.d_model ** 0.5)},
            "blocks": blocks,
            "final_norm": init_scale(cfg.d_model),
            "unembed": {"w": jax.random.normal(ks[-1], (cfg.d_model, cfg.vocab_size),
                                               jnp.float32) * (1.0 / cfg.d_model ** 0.5)},
        }

    def hidden_states(self, params, tokens) -> jax.Array:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][tokens].astype(opts.dtype)
        h = constrain(h, "batch", "seq", None)
        def block(h, p, t):
            x = rms_norm(h, p["ln"], cfg.norm_eps)
            if t == "s":
                return h + xlstm_mod.slstm_forward(p["slstm"], x, cfg,
                                                   dtype=opts.dtype,
                                                   use_pallas=opts.use_pallas)
            return h + xlstm_mod.mlstm_forward(p["mlstm"], x, cfg,
                                               chunk=opts.ssm_chunk,
                                               dtype=opts.dtype,
                                               use_pallas=opts.use_pallas)

        for p, t in zip(params["blocks"], self.pattern):
            fn = jax.checkpoint(block, static_argnums=(2,)) if opts.remat else block
            h = fn(h, p, t)
        return rms_norm(h, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch) -> jax.Array:
        tokens = batch["tokens"]
        h = self.hidden_states(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        return chunked_ce_loss(h, params["unembed"]["w"].astype(self.opts.dtype),
                               targets, mask, self.opts.logit_chunk)

    def init_cache(self, batch: int, max_seq: int) -> Dict:
        cfg = self.cfg
        states = []
        for t in self.pattern:
            if t == "s":
                states.append(xlstm_mod.init_slstm_state(cfg, batch))
            else:
                states.append(xlstm_mod.init_mlstm_state(cfg, batch))
        return {"states": states}

    def prefill(self, params, tokens, cache) -> Tuple[Dict, jax.Array]:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][tokens].astype(opts.dtype)
        new_states = []
        for p, t in zip(params["blocks"], self.pattern):
            x = rms_norm(h, p["ln"], cfg.norm_eps)
            if t == "s":
                y, st = xlstm_mod.slstm_forward(p["slstm"], x, cfg, dtype=opts.dtype,
                                                return_state=True)
            else:
                y, st = xlstm_mod.mlstm_forward(p["mlstm"], x, cfg,
                                                chunk=opts.ssm_chunk,
                                                dtype=opts.dtype, return_state=True)
            h = h + y
            new_states.append(st)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                            params["unembed"]["w"].astype(jnp.float32))
        return {"states": new_states}, logits

    def decode_step(self, params, cache, token, pos) -> Tuple[jax.Array, Dict]:
        cfg, opts = self.cfg, self.opts
        h = params["embed"]["w"][token].astype(opts.dtype)
        new_states = []
        for p, t, st in zip(params["blocks"], self.pattern, cache["states"]):
            x = rms_norm(h, p["ln"], cfg.norm_eps)
            if t == "s":
                y, st = xlstm_mod.slstm_decode_step(p["slstm"], x, cfg, st,
                                                    dtype=opts.dtype)
            else:
                y, st = xlstm_mod.mlstm_decode_step(p["mlstm"], x, cfg, st,
                                                    dtype=opts.dtype)
            h = h + y
            new_states.append(st)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bod,dv->bov", h.astype(jnp.float32),
                            params["unembed"]["w"].astype(jnp.float32))
        return logits[:, 0], {"states": new_states}

    def precompose(self, params, int8: bool = False):
        return precompose_tree(params, self.cfg.param, self.opts.dtype,
                               int8=int8)


# ================================================================ whisper

class EncDecLM:
    """whisper-small backbone: bidirectional encoder over (stub) frame
    embeddings + causal decoder with cross-attention. Sinusoidal absolute
    positions (adaptation: supports the assigned 32k decode shapes beyond
    whisper's 448-token learned table — noted in DESIGN.md)."""

    def __init__(self, cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
        self.cfg = cfg
        self.opts = opts

    def _init_enc_layer(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        return {
            "ln1": init_scale(cfg.d_model),
            "attn": attn.init_attention(ks[0], cfg),
            "ln2": init_scale(cfg.d_model),
            "mlp": init_mlp(ks[1], cfg),
        }

    def _init_dec_layer(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        return {
            "ln1": init_scale(cfg.d_model),
            "self_attn": attn.init_attention(ks[0], cfg),
            "ln_x": init_scale(cfg.d_model),
            "cross_attn": attn.init_attention(ks[1], cfg),
            "ln2": init_scale(cfg.d_model),
            "mlp": init_mlp(ks[2], cfg),
        }

    def init_params(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        enc = jax.vmap(self._init_enc_layer)(jax.random.split(ks[0], cfg.encoder_layers))
        dec = jax.vmap(self._init_dec_layer)(jax.random.split(ks[1], cfg.n_layers))
        emb = jax.random.normal(ks[2], (cfg.vocab_size, cfg.d_model), jnp.float32)
        return {
            "embed": {"w": emb * (1.0 / cfg.d_model ** 0.5)},
            "enc_layers": enc,
            "enc_norm": init_scale(cfg.d_model),
            "dec_layers": dec,
            "final_norm": init_scale(cfg.d_model),
            "unembed": {"w": jax.random.normal(ks[3], (cfg.d_model, cfg.vocab_size),
                                               jnp.float32) * (1.0 / cfg.d_model ** 0.5)},
        }

    def encode(self, params, frames) -> jax.Array:
        cfg, opts = self.cfg, self.opts
        B, S, _ = frames.shape
        h = frames.astype(opts.dtype) + sinusoidal_pos(jnp.arange(S), cfg.d_model
                                                       ).astype(opts.dtype)[None]
        h = constrain(h, "batch", "seq", None)

        def body(h, p, _):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            h = h + attn.full_attention(p["attn"], x, cfg, window=0,
                                        chunk=opts.attn_chunk, causal=False,
                                        use_rope=False, dtype=opts.dtype,
                                        use_pallas=opts.use_pallas)
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            return h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)

        h = iterate_layers(body, h, params["enc_layers"],
                           jnp.zeros((cfg.encoder_layers,)), cfg.encoder_layers,
                           opts.scan_layers, opts.remat)
        return rms_norm(h, params["enc_norm"], cfg.norm_eps)

    def _embed_dec(self, params, tokens, pos0=0):
        cfg, opts = self.cfg, self.opts
        S = tokens.shape[1]
        pos = jnp.arange(S) + pos0
        return (params["embed"]["w"][tokens].astype(opts.dtype)
                + sinusoidal_pos(pos, cfg.d_model).astype(opts.dtype)[None])

    def decoder_hidden(self, params, tokens, enc_out) -> jax.Array:
        cfg, opts = self.cfg, self.opts
        h = self._embed_dec(params, tokens)

        def body(h, p, _):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            h = h + attn.full_attention(p["self_attn"], x, cfg, window=0,
                                        chunk=opts.attn_chunk, causal=True,
                                        use_rope=False, dtype=opts.dtype,
                                        use_pallas=opts.use_pallas)
            x = rms_norm(h, p["ln_x"], cfg.norm_eps)
            h = h + attn.full_attention(p["cross_attn"], x, cfg, window=0,
                                        chunk=opts.attn_chunk, causal=False,
                                        use_rope=False, xkv=enc_out,
                                        dtype=opts.dtype, use_pallas=opts.use_pallas)
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            return h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)

        h = iterate_layers(body, h, params["dec_layers"],
                           jnp.zeros((cfg.n_layers,)), cfg.n_layers,
                           opts.scan_layers, opts.remat)
        return rms_norm(h, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch) -> jax.Array:
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        h = self.decoder_hidden(params, tokens[:, :-1], enc_out)
        targets = tokens[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        return chunked_ce_loss(h, params["unembed"]["w"].astype(self.opts.dtype),
                               targets, mask, self.opts.logit_chunk)

    def init_cache(self, batch: int, max_seq: int) -> Dict:
        cfg = self.cfg
        hd = cfg.resolved_head_dim()
        kv = attn.init_kv_cache(cfg, batch, max_seq, cfg.n_layers, dtype=self.opts.dtype)
        cross = {
            "k": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads, hd),
                           self.opts.dtype),
            "v": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, cfg.n_kv_heads, hd),
                           self.opts.dtype),
        }
        return {"self": kv, "cross": cross}

    def prefill(self, params, batch, cache) -> Tuple[Dict, jax.Array]:
        """Encode frames, precompute cross K/V, prefill decoder prompt."""
        cfg, opts = self.cfg, self.opts
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        h = self._embed_dec(params, tokens)

        def body(h, x_in):
            p, kvc = x_in
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            y, kv = attn.prefill_attention(p["self_attn"], x, cfg,
                                           (kvc["self"]["k"], kvc["self"]["v"]),
                                           window=0, chunk=opts.attn_chunk,
                                           use_rope=False, dtype=opts.dtype,
                                           use_pallas=opts.use_pallas)
            h = h + y
            ck, cv = attn.cross_kv(p["cross_attn"], enc_out, cfg, opts.dtype)
            x = rms_norm(h, p["ln_x"], cfg.norm_eps)
            h = h + attn.full_attention(p["cross_attn"], x, cfg, window=0,
                                        chunk=opts.attn_chunk, causal=False,
                                        use_rope=False, xkv=enc_out, dtype=opts.dtype,
                                        use_pallas=opts.use_pallas)
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            h = h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)
            return h, {"self": {"k": kv[0], "v": kv[1]}, "cross": {"k": ck, "v": cv}}

        def wrapped(c, px):
            return body(c, px)

        zipped = ({"self": cache["self"], "cross": cache["cross"]})
        per_layer = jax.tree.map(lambda a: a, zipped)
        if opts.scan_layers:
            h, new_cache = jax.lax.scan(
                wrapped, h, (params["dec_layers"], per_layer))
        else:
            outs = []
            for i in range(cfg.n_layers):
                h, nc = wrapped(h, (_tree_index(params["dec_layers"], i),
                                    _tree_index(per_layer, i)))
                outs.append(nc)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                            params["unembed"]["w"].astype(jnp.float32))
        return {"self": new_cache["self"], "cross": new_cache["cross"]}, logits

    def decode_step(self, params, cache, token, pos) -> Tuple[jax.Array, Dict]:
        cfg, opts = self.cfg, self.opts
        h = self._embed_dec(params, token, pos0=pos)

        def body(h, x_in):
            p, kvc = x_in
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            y, (ck, cv) = attn.decode_attention(
                p["self_attn"], x, cfg, (kvc["self"]["k"], kvc["self"]["v"]), pos,
                window=0, use_rope=False, dtype=opts.dtype,
                use_pallas=opts.use_pallas)
            h = h + y
            x = rms_norm(h, p["ln_x"], cfg.norm_eps)
            h = h + attn.cross_decode_attention(p["cross_attn"], x, cfg,
                                                (kvc["cross"]["k"], kvc["cross"]["v"]),
                                                opts.dtype, opts.use_pallas)
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            h = h + mlp(p["mlp"], x, cfg, opts.dtype, opts.use_pallas)
            return h, {"self": {"k": ck, "v": cv}, "cross": kvc["cross"]}

        per_layer = {"self": cache["self"], "cross": cache["cross"]}
        if opts.scan_layers:
            h, new_cache = jax.lax.scan(lambda c, px: body(c, px), h,
                                        (params["dec_layers"], per_layer))
        else:
            outs = []
            for i in range(cfg.n_layers):
                h, nc = body(h, (_tree_index(params["dec_layers"], i),
                                 _tree_index(per_layer, i)))
                outs.append(nc)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bod,dv->bov", h.astype(jnp.float32),
                            params["unembed"]["w"].astype(jnp.float32))
        return logits[:, 0], {"self": new_cache["self"], "cross": new_cache["cross"]}

    def precompose(self, params, int8: bool = False):
        return precompose_tree(params, self.cfg.param, self.opts.dtype,
                               int8=int8)


# ================================================================= factory

def build_model(cfg: ArchConfig, opts: ModelOptions = ModelOptions()):
    if cfg.is_encdec:
        return EncDecLM(cfg, opts)
    if cfg.attn_every:
        return HybridSSM(cfg, opts)
    if cfg.block_pattern and cfg.family == "hybrid":
        return HybridLM(cfg, opts)
    if cfg.block_pattern:
        return XLSTMStack(cfg, opts)
    return DecoderLM(cfg, opts)
