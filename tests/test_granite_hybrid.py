"""granite-4.0-h-micro, the per-layer hybrid of Mamba-2 and attention,
against the benchmark's plain reference (``bench/configs/
granite4_h_micro.py``, which imports nothing of the program) at the
registered config's ``reduced()`` widths, on seeded random weights.

Both sides compute in float32 at ``highest`` matmul precision, so what
is left between them is the order of float32 sums (the program's
chunked scan and query blocks against the reference's SSD listing and
plain softmax). Each tolerance is about a hundred times what that
leaves here (mixers 2e-8 to 8e-8 of the output's norm, gradients 1e-6
of a leaf's norm) and well below what the program's own bfloat16
activations give (gradients 0.02 to 0.07 of a leaf's norm).
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ParamCfg, get_arch
from repro.core import rank_policy
from repro.nn import attention as attn
from repro.nn import ssm as ssm_mod
from repro.nn.transformer import HybridLM, ModelOptions, build_model

ROOT = Path(__file__).resolve().parents[1]
ARCH = "granite-4.0-h-micro"


def _bench_module():
    path = ROOT / "bench" / "configs" / "granite4_h_micro.py"
    spec = importlib.util.spec_from_file_location("granite_bench_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module()


def tiny_spec(seq_len=19):
    """The benchmark's configuration at the registered ``reduced()``
    widths: its four layers, kinds and multipliers, ranks from the rank
    policy, Mamba-2 chunks of 8."""
    cfg = get_arch(ARCH).reduced()
    d_inner = cfg.ssm_expand * cfg.d_model
    heads = d_inner // cfg.ssm_head_dim
    kv = cfg.n_kv_heads * cfg.head_dim

    def r(m, n):
        return rank_policy.matrix_rank_for_gamma(m, n, REF.SPEC["gamma"])

    in_proj = 2 * d_inner + 2 * cfg.ssm_state + heads
    ff = r(cfg.d_model, cfg.d_ff)
    return dict(
        REF.SPEC, hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, intermediate_size=cfg.d_ff,
        vocab_size=cfg.vocab_size, mamba_d_state=cfg.ssm_state,
        mamba_d_head=cfg.ssm_head_dim, mamba_n_heads=heads,
        mamba_chunk_size=8, layers=cfg.n_layers,
        layer_types=["attention" if k == "a" else "mamba"
                     for k in cfg.block_pattern],
        min_dim_for_factorization=8, seq_len=seq_len, attn_chunk=8,
        logit_chunk=8,
        fedpara_ranks={"in_proj": r(cfg.d_model, in_proj),
                       "out_proj": r(d_inner, cfg.d_model), "gate": ff,
                       "up": ff, "down": r(cfg.d_ff, cfg.d_model),
                       "q": r(cfg.d_model, cfg.n_heads * cfg.head_dim),
                       "k": r(cfg.d_model, kv), "v": r(cfg.d_model, kv),
                       "o": r(cfg.n_heads * cfg.head_dim, cfg.d_model)})


def tiny_cfg(use_pallas=False):
    return get_arch(ARCH).reduced().with_(
        param=ParamCfg(gamma=0.1, min_dim_for_factorization=8,
                       use_pallas=use_pallas))


def _params(spec, seed=0):
    """Benchmark weights with every bias, skip and scale moved off its
    initial value, so that each one takes part in the comparison."""
    params = REF.init_params(jax.random.PRNGKey(seed), spec)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, x), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if any(s in name for s in ("scale", "'D'", "conv_b")):
            x = x + 0.3 * jax.random.normal(k, x.shape)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# -------------------------------------------------------------- config

def test_published_pattern_holds_both_kinds_at_nine_to_one():
    cfg = get_arch(ARCH)
    model = build_model(cfg)
    assert isinstance(model, HybridLM)
    pattern = cfg.block_pattern * model.n_periods
    assert pattern == "".join(
        "a" if t == "attention" else "m" for t in REF.SPEC["layer_types"])
    first = pattern[:10]                        # the benchmark's stage
    assert (first.count("m"), first.count("a")) == (9, 1)
    assert [i for i, k in enumerate(pattern) if k == "a"] == [5, 15, 25, 35]
    assert model.runs == [("m", 0, 5), ("a", 0, 1), ("m", 5, 4)]


def test_reduced_keeps_both_mixer_kinds():
    cfg = get_arch(ARCH).reduced()
    assert set(cfg.block_pattern) == {"m", "a"}
    assert cfg.n_layers % len(cfg.block_pattern) == 0
    assert (cfg.rope_style, cfg.attention_multiplier, cfg.ssm_conv_bias,
            cfg.tie_embeddings) == ("none", 1 / 64, True, True)
    # xLSTM's pattern already holds both of its kinds and is unchanged
    assert get_arch("xlstm-125m").reduced().block_pattern == "smmm"


def test_registered_widths_are_the_benchmark_files():
    cfg, spec = get_arch(ARCH), REF.SPEC
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size) == (spec["num_hidden_layers"], spec["hidden_size"],
                                spec["num_attention_heads"],
                                spec["num_key_value_heads"],
                                spec["intermediate_size"], spec["vocab_size"])
    assert (cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_conv, cfg.ssm_expand) == (
        spec["mamba_d_state"], spec["mamba_d_head"], spec["mamba_d_conv"],
        spec["mamba_expand"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps) == (
        spec["embedding_multiplier"], spec["residual_multiplier"],
        spec["attention_multiplier"], spec["logits_scaling"],
        spec["rms_norm_eps"])


# ------------------------------------------------------------- mixers

def test_mamba_mixer_matches_the_minimal_ssd():
    """Conv bias included; 21 positions pad the last chunk of 8."""
    spec = tiny_spec()
    cfg = tiny_cfg()
    p = jax.tree.map(lambda a: a[0, 0], _params(spec)["mamba"])["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 21, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        ours = ssm_mod.mamba_forward(p, x, cfg, chunk=8, dtype=jnp.float32)
        ref = REF.mamba_mixer(p, x, spec)
        no_bias = ssm_mod.mamba_forward({k: v for k, v in p.items()
                                         if k != "conv_b"}, x, cfg, chunk=8,
                                        dtype=jnp.float32)
    assert _rel(ours, ref) < 1e-5
    assert _rel(no_bias, ref) > 1e-2          # the bias takes part


def test_nope_attention_with_the_configured_scale():
    spec = tiny_spec()
    cfg = tiny_cfg()
    p = jax.tree.map(lambda a: a[0, 0], _params(spec)["attn"])["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 19, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        ours = attn.full_attention(p, x, cfg, window=0, chunk=8,
                                   dtype=jnp.float32)
        ref = REF.attention_mixer(p, x, spec, block=8)
        sqrt_scale = attn.full_attention(
            p, x, cfg.with_(attention_multiplier=0.0), window=0, chunk=8,
            dtype=jnp.float32)
        roped = attn.full_attention(p, x, cfg.with_(rope_style="full"),
                                    window=0, chunk=8, dtype=jnp.float32)
    assert attn.score_scale(cfg) == 1 / 64
    assert _rel(ours, ref) < 1e-5
    assert _rel(sqrt_scale, ref) > 1e-3 and _rel(roped, ref) > 1e-3


# ---------------------------------------------------------- the stack

@pytest.mark.parametrize("use_pallas", [False, True])
def test_stack_loss_and_gradients_match_the_reference(use_pallas):
    """The whole reduced stack through ``build_model``: loss and every
    leaf's gradient; with ``use_pallas`` every projection goes through
    the fused FedPara kernels (interpret mode here)."""
    spec = tiny_spec()
    params = _params(spec, seed=5)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, spec["seq_len"] + 1),
                                0, spec["vocab_size"])
    batch = {"tokens": tokens}
    model = build_model(tiny_cfg(use_pallas), ModelOptions(
        attn_chunk=8, ssm_chunk=8, logit_chunk=8, use_pallas=use_pallas,
        dtype=jnp.float32))
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        ref, ref_grads = jax.value_and_grad(
            lambda p: REF.reference_loss(p, batch, spec))(params)
    assert abs(float(loss) - float(ref)) / float(ref) < 1e-6
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(grads)[0]]
    gaps = [_rel(a, b) for a, b in zip(jax.tree.leaves(grads),
                                       jax.tree.leaves(ref_grads))]
    worst = max(zip(gaps, names))
    assert worst[0] < 1e-4, worst
