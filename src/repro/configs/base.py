"""Config system: architecture, parameterization, mesh and run configs.

Every assigned architecture is an :class:`ArchConfig` in its own module
(``repro.configs.<id>``) registered under its public id. Shape suites
(train_4k / prefill_32k / decode_32k / long_500k) are global and pair
with every LM arch.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class ParamCfg:
    """Parameterization (the paper's technique) settings."""

    kind: str = "fedpara"          # original | lowrank | fedpara | fedpara_tanh | pfedpara
    gamma: float = 0.1             # paper's rank interpolation knob
    factorize_embeddings: bool = False  # paper keeps embeddings/last-FC dense
    min_dim_for_factorization: int = 128  # below this, 2R(m+n) >= mn anyway
    use_pallas: bool = False       # fused differentiable fedpara_matmul in
                                   # every dense() of this parameterization:
                                   # training never materializes W (custom
                                   # VJP, repro.kernels.fedpara_grad)
    gram_batch: int = 0            # serve decode: row counts <= this use the
                                   # Hadamard-Gram identity instead of the
                                   # tile kernel (repro.serve cost model
                                   # sets it; 0 = never, so training paths
                                   # are untouched)


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | encdec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads

    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25

    # attention pattern
    sliding_window: int = 0        # 0 = full attention
    local_global_period: int = 0   # gemma3: every Nth layer is global
    local_window: int = 0          # window used by the local layers
    qk_norm: bool = False
    rope_style: str = "full"       # full | half (chatglm 2d-RoPE) | none (NoPE)
    attention_multiplier: float = 0.0   # score scale; 0 -> 1/sqrt(head_dim)
    rope_base: float = 10000.0

    # hybrid / ssm
    ssm_state: int = 0             # mamba2 d_state
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_conv_bias: bool = False    # mamba2 depthwise conv with a bias
    attn_every: int = 0            # zamba2: shared attn+mlp block period
    block_pattern: str = ""        # per-layer kinds, repeated over the
                                   # stack: xlstm "smmm" (s/mLSTM); hybrid
                                   # family "mmmmmammmm" (mamba/attention)

    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0           # stub frontend frame count

    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # granite-4.0-h (muP-style): h0 = embedding_multiplier * embed,
    # h += residual_multiplier * block(h), logits = h @ unembed / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    act: str = "silu"

    # compute policy
    param: ParamCfg = field(default_factory=ParamCfg)
    dtype: str = "bfloat16"

    # capability flags for the shape suite
    subquadratic: bool = False     # may run long_500k
    is_encdec: bool = False

    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def with_(self, **kw) -> "ArchConfig":
        return replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """A smoke-test-sized config of the same family/feature set."""
        kw = dict(
            n_layers=max(2, min(4, self.n_layers)),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // max(1, self.n_heads))),
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            head_dim=16,
        )
        if self.n_experts:
            kw["n_experts"] = 4
            kw["experts_per_token"] = min(2, self.experts_per_token)
            kw["moe_capacity_factor"] = 4.0  # no drops -> exact decode tests
        if self.sliding_window:
            kw["sliding_window"] = 16
        if self.local_global_period:
            kw["local_global_period"] = 2
            kw["local_window"] = 16
        if self.attn_every:
            kw["attn_every"] = 2
            kw["n_layers"] = 4
        if self.block_pattern:
            # four layers that still hold every kind of the pattern
            kinds = [k for k in dict.fromkeys(self.block_pattern)
                     if k not in self.block_pattern[:4]]
            kw["block_pattern"] = (self.block_pattern[:4 - len(kinds)]
                                   + "".join(kinds))
            kw["n_layers"] = 4
        if self.encoder_layers:
            kw["encoder_layers"] = 2
            kw["encoder_seq"] = 16
        if self.ssm_state:
            kw["ssm_state"] = 16
            kw["ssm_head_dim"] = 16
        return replace(self, **kw)


@dataclass(frozen=True)
class ShapeCfg:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES: Dict[str, ShapeCfg] = {
    "train_4k": ShapeCfg("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCfg("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCfg("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCfg("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class MeshCfg:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axes


@dataclass(frozen=True)
class FedCfg:
    """Cross-pod federated (local-SGD) settings — the paper's FL protocol
    mapped onto the 'pod' mesh axis."""

    enabled: bool = False
    local_steps: int = 4           # K local optimizer steps per round
    sync: str = "factors"          # factors | full  (full = dense baseline)
    strategy: str = "fedavg"       # fedavg | fedprox | fedadam ...
    compression: str = "none"      # none | fp16 | int8 | powersgd
    engine: str = "batched"        # client-sim engine: sequential |
                                   # batched | streaming (fl mode)
    client_chunk: int = 16         # streaming engine: clients per
                                   # lax.scan step (the round's memory
                                   # high-water mark is O(chunk·model))
    gamma_tiers: Tuple[float, ...] = ()   # heterogeneous capacity tiers:
                                   # one rank-gamma per device tier;
                                   # () = uniform full-rank clients
    tier_assignment: str = "round_robin"  # client->tier rule:
                                   # round_robin | random | size
    state_store: str = "dict"      # per-client state residency: dict
                                   # (host, O(participants) Python
                                   # objects) | arena (device-resident
                                   # stacked rows, one gather/scatter
                                   # per round; see docs/fleet.md)
    data_stream: str = "eager"     # cohort batch materialization:
                                   # eager (full (C,S,B,...) host
                                   # stack) | chunked (streaming only:
                                   # per-scan-chunk host callback)
    defense: str = "none"          # upload screening/aggregation rule:
                                   # none | clip | trimmed (batched
                                   # only; see docs/robustness.md)
    fault_rate: float = 0.0        # chaos injection: per-client fault
                                   # probability per round (0 = off;
                                   # see repro.fl.faults.FaultPlan)


@dataclass(frozen=True)
class RunCfg:
    arch: ArchConfig
    shape: ShapeCfg
    mesh: MeshCfg = field(default_factory=MeshCfg)
    fed: FedCfg = field(default_factory=FedCfg)
    seed: int = 0
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    attn_chunk: int = 512          # query-chunk size for flash-style attention
    logit_chunk: int = 1024        # seq-chunk for the unembed+CE
    scan_layers: bool = True       # False => unrolled (dry-run cost accounting)
    remat: bool = True
    use_pallas: bool = False       # fused fedpara_matmul kernels (TPU path)
    sequence_parallel: bool = False


# ---------------------------------------------------------------- registry

_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (ensure modules imported)

    if name not in _REGISTRY:
        raise KeyError(f"unknown arch '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> Dict[str, ArchConfig]:
    import repro.configs  # noqa: F401

    return dict(_REGISTRY)


def asdict(cfg) -> dict:
    return dataclasses.asdict(cfg)
