"""The FedPara paper's CIFAR-10 VGG16.

Sizes are in ``vgg16_cifar10.json``. This module holds what the
benchmark owns for the configuration: weights and images drawn from the
seed, the plain reference loss, and the FLOP counts. Only
:func:`program_loss` touches the system under test.

VGG16 with GroupNorm; every conv but the first (3 input channels) is a
tensor FedPara kernel (Proposition 3):
W = (T1 x1 X1 x2 Y1) * (T2 x1 X2 x2 Y2), T of shape (R, R, 3, 3); the
three FC layers stay dense.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

SPEC = json.load(open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "vgg16_cifar10.json")))
K = 3   # kernel height and width


def _conv_shapes(spec=SPEC):
    """[(out_ch, in_ch, rank, spatial size)] of every conv, in order."""
    out, in_ch, size = [], spec["channels"], spec["image_size"]
    ranks = iter(spec["conv_ranks"])
    for item in spec["plan"]:
        if item == "M":
            size //= 2
            continue
        out.append((item, in_ch, next(ranks), size))
        in_ch = item
    return out


def _fc_dims(spec=SPEC):
    convs = _conv_shapes(spec)
    size = spec["image_size"] // 2 ** spec["plan"].count("M")
    return [convs[-1][0] * size * size] + spec["fc_dims"] + [spec["classes"]]


def init_params(key, spec=SPEC) -> dict:
    """Seeded float32 weights in the layout the program reads: He init
    for dense kernels; tensor-FedPara factors at
    sigma = (2/fan_in)^(1/12) / R^(1/3), so that the composed kernel
    matches He variance; GroupNorm scale 1, bias 0."""
    convs, dims = _conv_shapes(spec), _fc_dims(spec)
    keys = iter(jax.random.split(key, 6 * len(convs) + len(dims)))
    params = {"convs": [], "fcs": []}
    for out_ch, in_ch, r, _ in convs:
        fan_in = in_ch * K * K
        if r == 0:
            kernel = {"w": jax.random.normal(next(keys), (out_ch, in_ch, K, K))
                      * (2.0 / fan_in) ** 0.5}
        else:
            std = (2.0 / fan_in) ** (1.0 / 12.0) / r ** (1.0 / 3.0)
            shapes = {"t1": (r, r, K, K), "x1": (out_ch, r), "y1": (in_ch, r),
                      "t2": (r, r, K, K), "x2": (out_ch, r), "y2": (in_ch, r)}
            kernel = {name: jax.random.normal(next(keys), shape) * std
                      for name, shape in shapes.items()}
        params["convs"].append({
            "kernel": kernel,
            "gn": {"scale": jnp.ones((out_ch,)), "bias": jnp.zeros((out_ch,))}})
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        params["fcs"].append({
            "w": jax.random.normal(next(keys), (d_in, d_out)) * (2.0 / d_in) ** 0.5,
            "b": jnp.zeros((d_out,))})
    return params


def make_data(key, spec=SPEC) -> dict:
    """Synthetic CIFAR-10 stand-in drawn on the device in one program:
    each class is a template of four random low-frequency waves,
    normalized to peak 1; an image is its class template plus Gaussian
    noise of std 0.6; labels uniform."""
    n = spec["clients"] * spec["samples_per_client"]
    size, ch, classes = spec["image_size"], spec["channels"], spec["classes"]

    def draw(key):
        k_f, k_ph, k_amp, k_y, k_noise = jax.random.split(key, 5)
        yy, xx = jnp.meshgrid(jnp.arange(size), jnp.arange(size), indexing="ij")
        freq = jax.random.uniform(k_f, (classes, 4, 2), minval=0.5, maxval=3.0)
        phase = jax.random.uniform(k_ph, (classes, 4, ch), maxval=2 * jnp.pi)
        amp = jax.random.uniform(k_amp, (classes, 4), minval=0.5, maxval=1.0)
        arg = 2 * jnp.pi * (freq[..., 0, None, None] * xx
                            + freq[..., 1, None, None] * yy) / size
        wave = jnp.sin(arg)[..., None] + jnp.cos(phase)[:, :, None, None, :]
        templates = (amp[..., None, None, None] * wave).sum(axis=1)
        templates = templates / jnp.abs(templates).max(axis=(1, 2, 3),
                                                       keepdims=True)
        y = jax.random.randint(k_y, (n,), 0, classes)
        x = templates[y] + 0.6 * jax.random.normal(k_noise, (n, size, size, ch))
        return {"x": x, "y": y.astype(jnp.int32)}

    return jax.jit(draw)(key)


def program_loss(spec=SPEC):
    """The system under test's loss for these weights: the repository's
    VGG16 with tensor-FedPara convs."""
    from repro.configs.base import ParamCfg
    from repro.nn.vision import VGGConfig, vgg_loss

    cfg = VGGConfig(plan=tuple(spec["plan"]), classes=spec["classes"],
                    in_channels=spec["channels"],
                    image_size=spec["image_size"],
                    fc_dims=tuple(spec["fc_dims"]),
                    param=ParamCfg(gamma=spec["gamma"]),
                    gn_groups=spec["gn_groups"])

    def loss_fn(params, batch):
        return vgg_loss(params, cfg, batch)

    return loss_fn


# ------------------------------------------------------------ reference

def _kernel_hwio(kernel):
    if "w" in kernel:
        w = kernel["w"]
    else:
        w1 = jnp.einsum("oa,ib,abhw->oihw", kernel["x1"], kernel["y1"],
                        kernel["t1"])
        w2 = jnp.einsum("oa,ib,abhw->oihw", kernel["x2"], kernel["y2"],
                        kernel["t2"])
        w = w1 * w2
    return jnp.transpose(w, (2, 3, 1, 0))


def _group_norm(x, gn, groups):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + 1e-5)).reshape(n, h, w, c)
    return y * gn["scale"] + gn["bias"]


def reference_loss(params, batch, spec=SPEC):
    """Mean cross-entropy, written out plainly: compose each conv kernel,
    convolve (3x3, stride 1, same padding), GroupNorm, ReLU, 2x2 max
    pool at each "M"; then the dense head. Computes in the dtype of
    ``params``."""
    dtype = params["fcs"][0]["w"].dtype
    x = batch["x"].astype(dtype)
    convs = iter(params["convs"])
    for item in spec["plan"]:
        if item == "M":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        p = next(convs)
        x = jax.lax.conv_general_dilated(
            x, _kernel_hwio(p["kernel"]), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(_group_norm(x, p["gn"], spec["gn_groups"]))
    x = x.reshape(x.shape[0], -1)
    for i, fc in enumerate(params["fcs"]):
        x = x @ fc["w"] + fc["b"]
        if i < len(params["fcs"]) - 1:
            x = jax.nn.relu(x)
    logp = jax.nn.log_softmax(x)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=1).mean()


# --------------------------------------------------------------- counts

def forward_macs(spec=SPEC) -> int:
    """Multiply-accumulates of one image's forward pass: convs at their
    spatial size and the dense head."""
    macs = sum(size * size * o * i * K * K for o, i, _, size in _conv_shapes(spec))
    dims = _fc_dims(spec)
    return macs + sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def flops_per_sample(spec=SPEC) -> float:
    """Forward + backward conv and matmul FLOPs of one image: 2 FLOPs a
    MAC, times 3 for forward plus backward."""
    return 3.0 * 2 * forward_macs(spec)


def compose_flops_per_step(spec=SPEC) -> float:
    """Composing each tensor-FedPara kernel once per client per local
    step (per branch: T x X, then x Y, 2*O*R*R*9 + 2*O*I*R*9 FLOPs, and
    the Hadamard product), and its factor gradients once (twice the
    composition)."""
    total = 0
    for o, i, r, _ in _conv_shapes(spec):
        if r:
            total += 3 * (2 * (2 * o * r * r * K * K + 2 * o * i * r * K * K)
                          + o * i * K * K)
    return float(total)


def kernel_calls(rows: int, clients: int, spec=SPEC) -> list:
    """No fused FedPara matmul kernel runs in this model."""
    return []


def partition(n: int, clients: int, seed: int) -> list:
    """IID split of ``n`` sample indices into equal client shards."""
    idx = np.random.RandomState(seed).permutation(n)
    return [np.sort(p) for p in np.array_split(idx, clients)]
