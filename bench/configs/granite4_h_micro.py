"""IBM Granite 4.0-H Micro: a hybrid of Mamba-2 and GQA attention layers.

Sizes are in ``granite4_h_micro.json``: every published width, cut in
depth to one whole period of ``layer_types`` (layers 0-9: five Mamba-2
layers, one attention layer, four Mamba-2 layers), a quarter of the
published 40. This module holds what the benchmark owns for
the configuration: weights and tokens drawn from the seed, the plain
reference loss, and the FLOP and kernel-call counts. Only
:func:`program_loss` touches the system under test.

Every projection (Mamba-2 in/out, q/k/v/o, MLP gate/up/down) is FedPara:
W = (X1 Y1^T) * (X2 Y2^T). The tied embedding and head, the norms and
the Mamba-2 dynamics (conv and its bias, A_log, D, dt_bias) stay dense,
as the paper keeps embeddings and small layers.

Departures from the published model, in the program and the reference
alike: the MLP keeps separate gate and up matrices where the published
``input_linear`` is one 2048 x 16384 matrix (this sets the FedPara
ranks); the in-projection's columns are laid out (x, z, B, C, dt) where
the published layout is (z, xBC, dt), which with random weights only
renames columns; weights are random.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

SPEC = json.load(open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "granite4_h_micro.json")))


def _dims(spec=SPEC) -> dict:
    d = spec["hidden_size"]
    d_inner = spec["mamba_expand"] * d
    heads = d_inner // spec["mamba_d_head"]
    if heads != spec["mamba_n_heads"] or spec["mamba_n_groups"] != 1:
        raise ValueError("the Mamba-2 heads do not tile its inner width")
    hd = d // spec["num_attention_heads"]
    return {"d": d, "d_inner": d_inner, "heads": heads,
            "head_dim": spec["mamba_d_head"], "state": spec["mamba_d_state"],
            "conv": spec["mamba_d_conv"], "chunk": spec["mamba_chunk_size"],
            "conv_dim": d_inner + 2 * spec["mamba_d_state"],
            "in_proj": 2 * d_inner + 2 * spec["mamba_d_state"] + heads,
            "q_heads": spec["num_attention_heads"],
            "kv_heads": spec["num_key_value_heads"], "hd": hd,
            "ff": spec["intermediate_size"], "vocab": spec["vocab_size"]}


def _kinds(spec=SPEC) -> list:
    """'mamba' or 'attention' of each layer this stage holds."""
    return spec["layer_types"][: spec["layers"]]


def _matrices(spec=SPEC) -> dict:
    """{kind: [(name, m, n, r)]} of every FedPara matrix of one layer."""
    k, ranks = _dims(spec), spec["fedpara_ranks"]
    mlp = [("w_gate", k["d"], k["ff"], ranks["gate"]),
           ("w_up", k["d"], k["ff"], ranks["up"]),
           ("w_down", k["ff"], k["d"], ranks["down"])]
    kv = k["kv_heads"] * k["hd"]
    return {
        "mamba": [("w_in", k["d"], k["in_proj"], ranks["in_proj"]),
                  ("w_out", k["d_inner"], k["d"], ranks["out_proj"])] + mlp,
        "attention": [("wq", k["d"], k["q_heads"] * k["hd"], ranks["q"]),
                      ("wk", k["d"], kv, ranks["k"]),
                      ("wv", k["d"], kv, ranks["v"]),
                      ("wo", k["q_heads"] * k["hd"], k["d"], ranks["o"])] + mlp,
    }


def _all_matrices(spec=SPEC) -> list:
    """[(kind, name, m, n, r)] of every FedPara matrix of the stage."""
    mats = _matrices(spec)
    return [(kind,) + m for kind in _kinds(spec) for m in mats[kind]]


# -------------------------------------------------------------- weights

def _factors(key, m, n, r) -> dict:
    # composed W at variance 1/m: each factor at (1/m)^(1/8) / r^(1/4)
    std = (1.0 / m) ** 0.125 / r ** 0.25
    ks = jax.random.split(key, 4)
    return {"x1": jax.random.normal(ks[0], (m, r)) * std,
            "y1": jax.random.normal(ks[1], (n, r)) * std,
            "x2": jax.random.normal(ks[2], (m, r)) * std,
            "y2": jax.random.normal(ks[3], (n, r)) * std}


def _layer(key, kind: str, spec) -> dict:
    k = _dims(spec)
    mats = _matrices(spec)[kind]
    keys = jax.random.split(key, len(mats) + 5)
    w = {name: _factors(kk, m, n, r)
         for kk, (name, m, n, r) in zip(keys, mats)}
    ones = {"scale": jnp.ones((k["d"],))}
    mlp = {n: w.pop(n) for n in ("w_gate", "w_up", "w_down")}
    if kind == "mamba":
        # Mamba-2's own initialisation: conv as PyTorch's Conv1d (uniform
        # over 1/sqrt(fan_in)), A = -uniform(1, 16), dt log-uniform in
        # [0.001, 0.1] through the inverse softplus of dt_bias
        bound = 1.0 / math.sqrt(k["conv"])
        dt = jnp.exp(jax.random.uniform(keys[-3], (k["heads"],),
                                        minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
        w.update({
            "conv_w": jax.random.uniform(keys[-1], (k["conv"], k["conv_dim"]),
                                         minval=-bound, maxval=bound),
            "conv_b": jax.random.uniform(keys[-2], (k["conv_dim"],),
                                         minval=-bound, maxval=bound),
            "A_log": jnp.log(jax.random.uniform(keys[-4], (k["heads"],),
                                                minval=1.0, maxval=16.0)),
            "D": jnp.ones((k["heads"],)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": {"scale": jnp.ones((k["d_inner"],))},
        })
    return {"ln1": ones, "mixer": w, "ln2": dict(ones), "mlp": mlp}


def init_params(key, spec=SPEC) -> dict:
    """Seeded float32 weights in the layout the program reads: each
    kind's layers stacked (1 period, layers of that kind, ...); the
    embedding at std 0.02; every norm scale 1."""
    kinds = _kinds(spec)
    keys = jax.random.split(key, len(kinds) + 1)
    params = {"embed": {"w": 0.02 * jax.random.normal(
                  keys[-1], (spec["vocab_size"], spec["hidden_size"]))},
              "final_norm": {"scale": jnp.ones((spec["hidden_size"],))}}
    for kind, stack in (("mamba", "mamba"), ("attention", "attn")):
        layers = [_layer(kk, kind, spec) for kk, t in zip(keys, kinds)
                  if t == kind]
        if layers:
            params[stack] = jax.tree.map(lambda *xs: jnp.stack(xs)[None],
                                         *layers)
    return params


def make_data(key, spec=SPEC) -> dict:
    """``clients * samples_per_client`` packed documents of ``seq_len + 1``
    token ids: a Zipf draw (P(rank k) ~ k^-s) over every id of the
    vocabulary, the ranks shuffled onto ids by the seed, drawn on the
    device in one program by inverting the cumulative distribution."""
    n = spec["clients"] * spec["samples_per_client"]
    vocab = spec["vocab_size"]

    def draw(key):
        k_perm, k_u = jax.random.split(key)
        ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
        cdf = jnp.cumsum(ranks ** -spec["zipf_exponent"])
        u = jax.random.uniform(k_u, (n, spec["seq_len"] + 1)) * cdf[-1]
        rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        return jax.random.permutation(k_perm, vocab)[rank].astype(jnp.int32)

    return {"tokens": jax.jit(draw)(key)}


def partition(n: int, clients: int, seed: int) -> list:
    """IID split of ``n`` sample indices into equal client shards."""
    idx = np.random.RandomState(seed).permutation(n)
    return [np.sort(p) for p in np.array_split(idx, clients)]


def program_loss(spec=SPEC):
    """The system under test's loss for these weights: the repository's
    registered ``granite-4.0-h-micro`` cut to this stage's layers, through
    ``build_model``, every projection through the fused FedPara kernels,
    bfloat16 activations, remat per layer."""
    from repro.configs import ParamCfg, get_arch
    from repro.nn.transformer import ModelOptions, build_model

    k = _dims(spec)
    cfg = get_arch("granite-4.0-h-micro").with_(
        n_layers=spec["layers"],
        block_pattern="".join("a" if t == "attention" else "m"
                              for t in _kinds(spec)),
        d_model=k["d"], n_heads=k["q_heads"], n_kv_heads=k["kv_heads"],
        head_dim=k["hd"], d_ff=k["ff"], vocab_size=k["vocab"],
        ssm_state=k["state"], ssm_expand=spec["mamba_expand"],
        ssm_conv=k["conv"], ssm_head_dim=k["head_dim"],
        ssm_conv_bias=spec["mamba_conv_bias"],
        attention_multiplier=spec["attention_multiplier"],
        embedding_multiplier=spec["embedding_multiplier"],
        residual_multiplier=spec["residual_multiplier"],
        logits_scaling=spec["logits_scaling"],
        norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_word_embeddings"],
        param=ParamCfg(gamma=spec["gamma"],
                       min_dim_for_factorization=spec["min_dim_for_factorization"],
                       use_pallas=spec["fused_kernels"]))
    model = build_model(cfg, ModelOptions(
        attn_chunk=spec["attn_chunk"], ssm_chunk=k["chunk"],
        logit_chunk=spec["logit_chunk"], use_pallas=spec["fused_kernels"],
        dtype=jnp.bfloat16))
    return model.loss


# ------------------------------------------------------------ reference

def _compose(f):
    return (f["x1"] @ f["y1"].T) * (f["x2"] @ f["y2"].T)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _segsum(x):
    """Stable segment sums: out[..., i, j] = sum(x[..., j+1 : i+1]) for
    j <= i, -inf above the diagonal (the paper's ``segsum``)."""
    t = x.shape[-1]
    x = jnp.repeat(x[..., None], t, axis=-1)                 # [..., i, j] = x_i
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    out = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd_minimal_discrete(X, A, B, C, block_len):
    """The minimal chunked SSD of arXiv:2405.21060 (Listing 1).

    X: (b, l, h, p); A: (b, l, h); B, C: (b, l, h, n); l a multiple of
    ``block_len``. Returns Y: (b, l, h, p)."""
    b, l, h, p = X.shape

    def chunks(t):
        return t.reshape(b, l // block_len, block_len, *t.shape[2:])

    X, A, B, C = chunks(X), chunks(A), chunks(B), chunks(C)
    A = jnp.moveaxis(A, -1, 1)                               # b h c l
    A_cumsum = jnp.cumsum(A, axis=-1)
    # 1. the output of each chunk from its own inputs (diagonal blocks)
    L = jnp.exp(_segsum(A))
    Y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, L, X)
    # 2. each chunk's final state
    decay_states = jnp.exp(A_cumsum[..., -1:] - A_cumsum)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X)
    # 3. the recurrence over chunk boundaries, from a zero state
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cumsum[..., -1], ((0, 0), (0, 0),
                                                              (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    # 4. each chunk's output from the state it starts in
    Y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cumsum))
    return (Y_diag + Y_off).reshape(b, l, h, p)


def mamba_mixer(p, x, spec=SPEC):
    """Mamba-2 mixer (arXiv:2405.21060) on normed ``x`` (b, l, d):
    in-projection to (x, z, B, C, dt); depthwise causal conv with bias
    and SiLU over xBC; dt = softplus(dt + dt_bias), A = -exp(A_log); the
    SSD over x*dt with decay A*dt (the sequence zero-padded to whole
    chunks) and the D skip; gated RMSNorm rms(y * SiLU(z)); out-projection."""
    k = _dims(spec)
    b, l, _ = x.shape
    di, n, h, hp = k["d_inner"], k["state"], k["heads"], k["head_dim"]
    zxbcdt = x @ _compose(p["w_in"])
    xs, z = zxbcdt[..., :di], zxbcdt[..., di: 2 * di]
    bc = zxbcdt[..., 2 * di: 2 * di + 2 * n]
    dt = zxbcdt[..., 2 * di + 2 * n:]
    xbc = jnp.concatenate([xs, bc], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k["conv"] - 1, 0), (0, 0)))
    conv = sum(padded[:, i: i + l] * p["conv_w"][i] for i in range(k["conv"]))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, B, C = xbc[..., :di], xbc[..., di: di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (b, l, h)
    A = -jnp.exp(p["A_log"])
    X = xs.reshape(b, l, h, hp)
    pad = -l % k["chunk"]

    def padl(t):
        return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))

    heads = (b, l, h, n)
    y = ssd_minimal_discrete(
        padl(X * dt[..., None]), padl(A * dt),
        padl(jnp.broadcast_to(B[:, :, None], heads)),
        padl(jnp.broadcast_to(C[:, :, None], heads)), k["chunk"])[:, :l]
    y = (y + X * p["D"][:, None]).reshape(b, l, di)
    y = _rms(y * jax.nn.silu(z), p["norm"]["scale"], spec["rms_norm_eps"])
    return y @ _compose(p["w_out"])


def attention_mixer(p, x, spec=SPEC, block: int = 1024):
    """Causal GQA softmax attention without positional encoding (NoPE),
    each KV head repeated over its query heads, scores scaled by
    ``attention_multiplier``; queries in blocks of ``block`` rows so that
    one block's scores are what is held."""
    k = _dims(spec)
    b, l, _ = x.shape
    hq, hk, hd = k["q_heads"], k["kv_heads"], k["hd"]
    q = (x @ _compose(p["wq"])).reshape(b, l, hq, hd)
    kk = jnp.repeat((x @ _compose(p["wk"])).reshape(b, l, hk, hd), hq // hk, 2)
    v = jnp.repeat((x @ _compose(p["wv"])).reshape(b, l, hk, hd), hq // hk, 2)
    rows = min(block, l)
    pad = -l % rows
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, -1, rows, hq, hd), 1, 0)

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kk) * spec["attention_multiplier"]
        causal = (start + jnp.arange(rows))[:, None] >= jnp.arange(l)[None]
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (qb, jnp.arange(qb.shape[0]) * rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, -1, hq * hd)[:, :l]
    return out @ _compose(p["wo"])


def _mlp(p, x):
    return (jax.nn.silu(x @ _compose(p["w_gate"]))
            * (x @ _compose(p["w_up"]))) @ _compose(p["w_down"])


def reference_loss(params, batch, spec=SPEC):
    """Mean next-token cross-entropy of the stage, written out plainly:
    embedding times ``embedding_multiplier``; per layer (recomputed in
    the backward pass, to fit) h += residual_multiplier * mixer(rms(h))
    and h += residual_multiplier * mlp(rms(h)); final RMSNorm; logits
    against the tied embedding divided by ``logits_scaling``, in blocks
    of positions. Computes in the dtype of ``params``."""
    tokens = batch["tokens"]
    eps, rm = spec["rms_norm_eps"], spec["residual_multiplier"]
    emb = params["embed"]["w"]
    h = emb[tokens[:, :-1]] * spec["embedding_multiplier"]
    seen = {"mamba": 0, "attention": 0}
    for kind in _kinds(spec):
        stack = params["mamba" if kind == "mamba" else "attn"]
        p = jax.tree.map(lambda a, i=seen[kind]: a[0, i], stack)
        seen[kind] += 1
        mixer = mamba_mixer if kind == "mamba" else attention_mixer

        @jax.checkpoint
        def layer(h, p, mixer=mixer):
            h = h + rm * mixer(p["mixer"], _rms(h, p["ln1"]["scale"], eps), spec)
            return h + rm * _mlp(p["mlp"], _rms(h, p["ln2"]["scale"], eps))

        h = layer(h, p)
    h = _rms(h, params["final_norm"]["scale"], eps)
    targets = tokens[:, 1:]
    rows = min(spec["logit_chunk"], h.shape[1])
    pad = -h.shape[1] % rows
    hb = jnp.moveaxis(jnp.pad(h, ((0, 0), (0, pad), (0, 0))).reshape(
        h.shape[0], -1, rows, h.shape[2]), 1, 0)
    tb = jnp.moveaxis(jnp.pad(targets, ((0, 0), (0, pad))).reshape(
        h.shape[0], -1, rows), 1, 0)
    valid = jnp.moveaxis((jnp.arange(h.shape[1] + pad) < h.shape[1]).reshape(
        1, -1, rows), 1, 0)

    @jax.checkpoint
    def nll(args):
        hi, ti, vi = args
        logp = jax.nn.log_softmax(hi @ emb.T / spec["logits_scaling"])
        picked = jnp.take_along_axis(logp, ti[..., None], axis=-1)[..., 0]
        return -(picked * vi).sum()

    return jax.lax.map(nll, (hb, tb, valid)).sum() / targets.size


# --------------------------------------------------------------- counts

def flops_per_sample(spec=SPEC) -> float:
    """Dense-equivalent forward + backward FLOPs of one packed document
    (``seq_len`` positions), times 3 for forward plus backward: every
    projection and the tied head at each position; causal attention's
    scores and values over the positions before it; the Mamba-2 SSD's
    causal intra-chunk products, chunk states and state outputs."""
    k = _dims(spec)
    s = spec["seq_len"]
    per_pos = sum(2 * m * n for _, _, m, n, _ in _all_matrices(spec))
    per_pos += 2 * k["d"] * k["vocab"]
    kinds = _kinds(spec)
    ctx = (s + 1) / 2                        # mean causal context
    per_pos += kinds.count("attention") * 2 * 2 * ctx * k["q_heads"] * k["hd"]
    q, hp = k["chunk"], k["heads"] * k["head_dim"]
    ssd = 2 * (q + 1) / 2 * (k["state"] + hp) + 2 * 2 * hp * k["state"]
    per_pos += kinds.count("mamba") * ssd
    return 3.0 * per_pos * s


def compose_flops_per_step(spec=SPEC) -> float:
    """Composing each FedPara weight once (two rank-r products and the
    Hadamard product) and its factor gradients once (the two Hadamard
    products and four rank-r products), per client per local step."""
    return float(sum(12 * m * n * r + 3 * m * n
                     for _, _, m, n, r in _all_matrices(spec)))


def kernel_calls(rows: int, clients: int, spec=SPEC) -> list:
    """The fused kernel calls of one local step of ``clients`` clients
    that run together, each client's batch of ``rows`` documents: per
    FedPara matrix of every layer one forward, one input-gradient and
    two factor-gradient calls over all the clients at once, each over
    ``rows * seq_len`` token rows. Operations and bytes are those of the
    call's unpadded shapes with the weight composed once per call (what
    the call needs, however the kernel tiles it); activations and their
    gradients at 2 bytes a value (bfloat16), factors and factor
    gradients at 4, each read or written once."""
    t = rows * spec["seq_len"]
    calls = {}
    for _, name, m, n, r in _all_matrices(spec):
        compose = 4 * m * n * r + m * n
        factors = 4 * 2 * (m + n) * r
        specs = {
            "fwd": (2 * t * m * n + compose, 2 * t * (m + n) + factors),
            "dx": (2 * t * m * n + compose, 2 * t * (n + m) + factors),
            "dfactors_x": (2 * t * m * n + compose + 2 * m * n + 4 * m * n * r,
                           2 * t * (m + n) + factors + 4 * 2 * m * r),
            "dfactors_y": (2 * t * m * n + compose + 2 * m * n + 4 * m * n * r,
                           2 * t * (m + n) + factors + 4 * 2 * n * r),
        }
        for kind, (flops, nbytes) in specs.items():
            key = (name, m, n, r, kind)
            if key not in calls:
                calls[key] = {"matrix": f"{name}.{m}x{n}", "kind": kind,
                              "count": 0, "flops": float(clients * flops),
                              "bytes": float(clients * nbytes)}
            calls[key]["count"] += 1
    return list(calls.values())
