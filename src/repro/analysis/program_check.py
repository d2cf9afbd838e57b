"""Compiled-program contract checks (fedlint Layer 2).

The AST rules (``repro.analysis.lint``) catch invariant violations the
source shows directly; this module checks the ones only the COMPILED
round programs show. Each engine's program is lowered on miniature
shapes and the jaxpr / post-compile HLO text is asserted on:

  * **donation** — ``donate_argnums`` actually produced input-output
    aliasing in the compiled HLO (an ``input_output_alias={...}``
    annotation). Donation silently degrades to copying when shapes or
    layouts stop matching; this catches it.
  * **wire dtype** — int8 / fp16 codec outputs cross the aggregation
    boundary at wire dtype: no ``convert_element_type`` widening to
    fp32 outside the fused Pallas dequant-accumulate kernel body (the
    in-VMEM per-tile convert is the design; a full-stack host-side
    widen is the regression).
  * **callbacks** — exactly the registered host callbacks appear in the
    program (``StreamingRound._fetch_chunk`` in chunked-data mode,
    none otherwise), and every callee is module/class-level (stable
    identity — the jaxpr-level mirror of lint rule FED005).
  * **retrace** — a second round at the same cohort shape compiles ZERO
    new XLA programs, for all four engines and both state stores, and
    for the async engine ACROSS VERSION BUMPS (arrival position / fold
    weight / ref coefficients are traced or host-side data, never
    program constants) — :class:`CompileCounter` hooks jax's dispatch
    logger.

Run locally::

    python -m repro.analysis.program_check           # full matrix
    python -m repro.analysis.program_check --fast    # skip retrace

All checks run on tiny synthetic shapes (seconds on CPU); CI runs them
as part of the blocking lint job.
"""
from __future__ import annotations

import logging
import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

# --------------------------------------------------------------- counter

_COMPILE_RX = re.compile(r"Finished XLA compilation of ([^\s]+) in")


class CompileCounter:
    """Counts XLA compilations by hooking ``jax._src.dispatch``'s DEBUG
    log ("Finished XLA compilation of <name> in <t> sec") — emitted for
    every fresh compile regardless of jax_log_compiles, so cache hits
    are exactly the calls that DON'T log. Handler and level are scoped
    to the one logger (not the 'jax' root, whose DEBUG cascade is
    enormous) and restored on exit."""

    def __init__(self):
        self.events: List[str] = []
        self._logger = logging.getLogger("jax._src.dispatch")
        self._handler = None
        self._prev_level = None

    def __enter__(self):
        counter = self

        class _H(logging.Handler):
            def emit(self, record):
                m = _COMPILE_RX.search(record.getMessage())
                if m:
                    counter.events.append(m.group(1))

        self._handler = _H(level=logging.DEBUG)
        self._prev_level = self._logger.level
        self._logger.addHandler(self._handler)
        self._logger.setLevel(logging.DEBUG)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self._handler)
        self._logger.setLevel(self._prev_level)
        return False

    @property
    def count(self) -> int:
        return len(self.events)


# ----------------------------------------------------------- jaxpr walks

def _eqn_subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr, *, skip: Sequence[str] = ()):
    """All equations of ``jaxpr`` and its sub-jaxprs, except the bodies
    of primitives named in ``skip`` (e.g. ``pallas_call``: converts in
    VMEM are the kernel's job, not a contract violation)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip:
            continue
        for sub in _eqn_subjaxprs(eqn):
            yield from iter_eqns(sub, skip=skip)


def widening_converts(jaxpr, src_dtypes=("int8", "float16"),
                      dst_dtype="float32") -> List[str]:
    """``convert_element_type`` eqns widening a wire dtype to fp32
    anywhere OUTSIDE a pallas_call body. Returns human-readable
    descriptions (empty = contract holds)."""
    out = []
    for eqn in iter_eqns(jaxpr, skip=("pallas_call",)):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        if (str(src.dtype) in src_dtypes
                and str(eqn.params.get("new_dtype")) == dst_dtype):
            out.append(f"convert {src.dtype}{list(src.shape)} -> "
                       f"{dst_dtype}")
    return out


def callback_callees(jaxpr) -> List[str]:
    """Qualified names of every host-callback callee in the program."""
    names = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in ("pure_callback", "io_callback",
                                      "debug_callback"):
            continue
        cb = eqn.params.get("callback")
        fn = getattr(cb, "callback_func", cb)
        fn = getattr(fn, "__func__", fn)   # unwrap bound methods
        names.append(getattr(fn, "__qualname__", repr(fn)))
    return sorted(names)


_ALIAS_RX = re.compile(r"input_output_alias=\{([^}]*)\}")


def hlo_aliases(compiled_text: str) -> List[str]:
    """Input-output alias entries in post-compile HLO text (one per
    donated buffer XLA actually aliased)."""
    out = []
    for m in _ALIAS_RX.finditer(compiled_text):
        body = m.group(1).strip()
        if body:
            out += [p.strip() for p in body.split("),") if p.strip()]
    return out


# -------------------------------------------------------- mini FL builds

N_CLIENTS = 8
_PER_CLIENT = 32          # samples per client; 32/batch16 = 2 full steps


def _mini_task(seed: int = 0):
    from repro.data import make_image_dataset

    n = N_CLIENTS * _PER_CLIENT
    ds = make_image_dataset(n, 4, size=8, channels=1, noise=0.3, seed=seed)
    data = {"x": ds["x"].reshape(n, -1), "y": ds["y"]}
    # equal-size partitions => the streaming engine's round-wide step
    # axis S is identical every round (shape-stable programs)
    perm = np.random.RandomState(seed).permutation(n)
    parts = [perm[i * _PER_CLIENT:(i + 1) * _PER_CLIENT]
             for i in range(N_CLIENTS)]
    return data, parts


def make_mini_server(engine: str, state_store: str = "dict", *,
                     data_stream: str = "eager", uplink_codec: str = "",
                     client_chunk: int = 4, participation: float = 1.0,
                     strategy: str = "fedavg", seed: int = 0,
                     defense: str = "none", fault_rate: float = 0.0,
                     **server_kw):
    """A tiny but real FLServer (8 clients, 64-16-4 fedpara MLP) whose
    round programs have every contract of the full-size ones.
    ``fault_rate > 0`` attaches a :class:`repro.fl.faults.FaultPlan`;
    extra ``server_kw`` forward to :class:`ServerConfig`."""
    from repro.configs.base import ParamCfg
    from repro.fl import ClientConfig, FLServer, ServerConfig, make_strategy
    from repro.fl.faults import FaultPlan
    from repro.nn import recurrent as rec

    data, parts = _mini_task(seed)
    cfg = rec.MLPConfig(in_dim=64, hidden=16, classes=4,
                        param=ParamCfg(kind="fedpara", gamma=0.3,
                                       min_dim_for_factorization=8))
    params = rec.init_mlp_model(jax.random.PRNGKey(seed), cfg)

    def loss_fn(p, b):
        return rec.mlp_loss(p, cfg, b)

    plan = FaultPlan(rate=fault_rate, seed=seed) if fault_rate > 0 else None
    return FLServer(
        loss_fn, params, data, parts, make_strategy(strategy),
        ClientConfig(lr=0.1, batch=16, epochs=1),
        ServerConfig(clients=N_CLIENTS, participation=participation,
                     rounds=3, engine=engine, client_chunk=client_chunk,
                     state_store=state_store, data_stream=data_stream,
                     uplink_codec=uplink_codec, seed=seed,
                     defense=defense, faults=plan, **server_kw))


def _spec(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def capture_program(target, attr: str = "_program"):
    """Spy-wrap a jitted program attribute: records the argument
    ShapeDtypeStructs of the next call BEFORE invoking it (the program
    may donate its inputs — shapes must be read first), then restores
    the original. Returns (original_jitted_fn, box); after one round
    ``box['avals']`` holds the call signature for AOT ``.lower()`` /
    ``.trace()``."""
    orig = getattr(target, attr)
    box: Dict[str, Any] = {}

    def spy(*args):
        box["avals"] = jax.tree.map(_spec, args)
        setattr(target, attr, orig)
        return orig(*args)

    setattr(target, attr, spy)
    return orig, box


# ---------------------------------------------------------------- checks

@dataclass
class CheckResult:
    name: str
    ok: bool
    detail: str = ""

    def render(self) -> str:
        return f"[{'PASS' if self.ok else 'FAIL'}] {self.name}: {self.detail}"


def _lower_engine_program(engine: str, state_store: str, *,
                          uplink_codec: str = "", data_stream: str = "eager",
                          strategy: str = "fedavg"):
    """Build a mini server, run one round through a spy, AOT-lower the
    engine's round program on the captured avals. Returns
    (server, jaxpr, compiled_hlo_text)."""
    srv = make_mini_server(engine, state_store, uplink_codec=uplink_codec,
                           data_stream=data_stream, strategy=strategy)
    target = srv._stream if engine == "streaming" else srv._engine
    prog, box = capture_program(target)
    srv.run_round()
    avals = box["avals"]
    jaxpr = prog.trace(*avals).jaxpr
    hlo = prog.lower(*avals).compile().as_text()
    return srv, jaxpr, hlo


def check_donation() -> List[CheckResult]:
    """Streaming round program (donate_argnums=(0, 1)) and the arena's
    scatter/bump programs (donate_argnums=(0,)) must show input-output
    aliasing in their compiled HLO."""
    out = []
    # scaffold gives the donated chunk-state tree real leaves (c_i / c);
    # with stateless fedavg there is nothing to donate and the check
    # would vacuously pass or fail
    _, _, hlo = _lower_engine_program("streaming", "dict",
                                      strategy="scaffold")
    aliases = hlo_aliases(hlo)
    out.append(CheckResult(
        "donation:streaming._round_program", bool(aliases),
        f"{len(aliases)} aliased buffer(s)" if aliases
        else "donate_argnums=(0, 1) produced no input_output_alias"))

    srv = make_mini_server("batched", "arena", strategy="scaffold")
    srv.run_round()   # materializes the arena and its jitted programs
    from repro.fl import arena as arena_mod
    state = srv.arena.state
    rows = jnp.arange(4, dtype=jnp.int32)
    for name in ("_scatter_rows", "_bump_rows"):
        fn = getattr(arena_mod, name)
        if name == "_scatter_rows":
            upd = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((4,) + x.shape[1:], x.dtype),
                state)
            mask = jax.ShapeDtypeStruct((4,), jnp.float32)
            lowered = fn.lower(jax.tree.map(_spec, state), rows, upd, mask)
        else:
            part = _spec(srv.arena.participation)
            lowered = fn.lower(part, rows, jax.ShapeDtypeStruct(
                (4,), jnp.float32))
        aliases = hlo_aliases(lowered.compile().as_text())
        out.append(CheckResult(
            f"donation:arena.{name}", bool(aliases),
            f"{len(aliases)} aliased buffer(s)" if aliases
            else "donate_argnums=(0,) produced no input_output_alias"))
    return out


def check_wire_dtype() -> List[CheckResult]:
    """Streaming aggregation must consume int8 / fp16 wire payloads at
    wire dtype: any fp32 widen outside the Pallas kernel body means the
    dense fp32 upload stack (which this engine exists to avoid) is
    back."""
    out = []
    for codec in ("int8", "fp16"):
        _, jaxpr, _ = _lower_engine_program("streaming", "dict",
                                            uplink_codec=codec)
        bad = widening_converts(jaxpr)
        out.append(CheckResult(
            f"wire-dtype:streaming:{codec}", not bad,
            "all converts inside the fused kernel" if not bad
            else "; ".join(bad[:4])))
    return out


def check_callbacks() -> List[CheckResult]:
    """Exactly the registered host callbacks appear: chunked-data
    streaming has the one ``_fetch_chunk`` pure_callback, eager-data
    programs have none."""
    out = []
    _, jaxpr, _ = _lower_engine_program("streaming", "dict",
                                        data_stream="chunked")
    names = callback_callees(jaxpr)
    expected = ["StreamingRound._fetch_chunk"]
    out.append(CheckResult(
        "callbacks:streaming:chunked", names == expected,
        f"found {names}" + ("" if names == expected
                            else f", expected {expected}")))
    for engine in ("streaming", "batched"):
        _, jaxpr, _ = _lower_engine_program(engine, "dict")
        names = callback_callees(jaxpr)
        out.append(CheckResult(
            f"callbacks:{engine}:eager", not names,
            "no host callbacks" if not names else f"unexpected: {names}"))
    return out


RETRACE_MATRIX: Tuple[Tuple[str, str], ...] = (
    ("sequential", "dict"),
    ("batched", "dict"),
    ("batched", "arena"),
    ("streaming", "dict"),
    ("streaming", "arena"),
    ("async", "dict"),
    ("async", "arena"),
)


def count_retrace(engine: str, state_store: str, *, warmup: int = 1,
                  measured: int = 2,
                  server_factory: Optional[Callable] = None) -> List[str]:
    """Compile events during rounds ``warmup+1 .. warmup+measured`` at a
    fixed cohort shape (should be empty: round 1 compiled everything)."""
    factory = server_factory or (
        lambda: make_mini_server(engine, state_store))
    srv = factory()
    for _ in range(warmup):
        srv.run_round()
    with CompileCounter() as cc:
        for _ in range(measured):
            srv.run_round()
    return cc.events


def check_retrace() -> List[CheckResult]:
    out = []
    for engine, store in RETRACE_MATRIX:
        events = count_retrace(engine, store)
        out.append(CheckResult(
            f"retrace:{engine}:{store}", not events,
            "0 recompiles in rounds 2-3" if not events
            else f"{len(events)} recompile(s): {sorted(set(events))}"))
    return out


def check_async_retrace() -> List[CheckResult]:
    """The version-bump contract (docs/async.md): a genuinely
    asynchronous regime — small buffer, lognormal stragglers, delta
    codec, so version bumps interleave with stale arrivals and
    mid-version re-dispatches — must compile zero new XLA programs
    after the warm-up versions. Arrival position, fold weight and the
    host-float ref coefficients are traced/eager data; only cohort
    SHAPES key the compiled programs."""
    events = count_retrace(
        "async", "dict", warmup=2, measured=2,
        server_factory=lambda: make_mini_server(
            "async", "dict", uplink_codec="delta|topk0.5|int8",
            buffer_k=4, straggler_sigma=1.0, staleness="poly:0.5"))
    return [CheckResult(
        "retrace:async:version-bumps", not events,
        "0 recompiles across version bumps 3-4" if not events
        else f"{len(events)} recompile(s): {sorted(set(events))}")]


def check_defense_retrace() -> List[CheckResult]:
    """Chaos knobs are DATA, not program constants: with faults drawn
    every round and the defense gate active, rounds 2-3 must still
    compile zero new XLA programs (the per-round fault arrays and the
    varying drawn-fault sets ride in as traced arguments)."""
    out = []
    for engine, defense in (("batched", "clip"), ("batched", "trimmed"),
                            ("streaming", "clip")):
        events = count_retrace(
            engine, "dict",
            server_factory=lambda e=engine, d=defense: make_mini_server(
                e, "dict", defense=d, fault_rate=0.4, uplink_codec="int8"))
        out.append(CheckResult(
            f"retrace:{engine}:defense={defense}+faults", not events,
            "0 recompiles in rounds 2-3" if not events
            else f"{len(events)} recompile(s): {sorted(set(events))}"))
    return out


# ---------------------------------------------------------------- serving

def _mini_serve_engine(mode: str, *, use_pallas: bool, users: int = 4,
                       batch: int = 4, seed: int = 0):
    """A tiny pFedPara ServeEngine (2-layer decoder, 4 resident users
    with random personal halves) whose decode program carries every
    contract of the full-size one."""
    import dataclasses

    from repro.configs import get_arch
    from repro.fl import comm
    from repro.nn.transformer import ModelOptions, build_model
    from repro.serve import ServeEngine

    cfg = get_arch("qwen3-8b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, param=dataclasses.replace(
        cfg.param, kind="pfedpara", min_dim_for_factorization=8, gamma=0.5))
    opts = ModelOptions(attn_chunk=8, ssm_chunk=8, logit_chunk=16,
                        dtype=jnp.float32)
    model = build_model(cfg, opts)
    params = model.init_params(jax.random.PRNGKey(seed))
    local_trees = {
        u: comm.split_pfedpara(
            model.init_params(jax.random.PRNGKey(seed + 1 + u)))[1]
        for u in range(users)}
    eng = ServeEngine(cfg, params, local_trees, mode=mode, batch=batch,
                      use_pallas=use_pallas, opts=opts)
    return eng, cfg


def check_serve_retrace() -> List[CheckResult]:
    """Decode must compile exactly once per engine config: 16 steps over
    2 DIFFERENT user cohorts reuse the first step's program (position
    and user-row indices are traced arguments, the KV cache is donated
    in place)."""
    out = []
    for mode in ("precompose", "fused"):
        eng, cfg = _mini_serve_engine(mode, use_pallas=False)
        cache = eng.init_cache(4, 24)
        tok = jnp.zeros((4, 1), jnp.int32)
        cohorts = ([0, 1, 2, 3], [3, 2, 1, 0])
        logits, cache = eng.decode_step(cache, tok, 0, user_ids=cohorts[0])
        with CompileCounter() as cc:
            for i in range(1, 16):
                logits, cache = eng.decode_step(
                    cache, tok, i, user_ids=cohorts[i % 2])
        out.append(CheckResult(
            f"serve-retrace:{mode}", not cc.events,
            "0 recompiles over 15 steps x 2 cohorts" if not cc.events
            else f"{len(cc.events)} recompile(s): {sorted(set(cc.events))}"))
    return out


def check_serve_wire_dtype() -> List[CheckResult]:
    """The int8 precomposed cache must reach the matmul at int8: any
    fp32 widen of an int8 array outside a pallas_call body means the
    cache is being dequantized in HBM — the full dense-fp32 weight
    stream the cache exists to avoid."""
    eng, cfg = _mini_serve_engine("precompose", use_pallas=True)
    cache = eng.init_cache(4, 24)
    rows = eng.arena.rows_for([0, 1, 2, 3])
    args = (eng.serve_params, eng.arena.tree, cache,
            jnp.zeros((4, 1), jnp.int32), jnp.int32(0), rows)
    jaxpr = eng._jit_decode.trace(*jax.tree.map(_spec, args)).jaxpr
    bad = widening_converts(jaxpr, src_dtypes=("int8",))
    return [CheckResult(
        "serve-wire-dtype:int8-cache", not bad,
        "int8 cache widened only inside pallas_call" if not bad
        else "; ".join(bad[:4]))]


def check_serve() -> List[CheckResult]:
    return check_serve_retrace() + check_serve_wire_dtype()


# ------------------------------------------------------------------- CLI

def run_all(fast: bool = False) -> List[CheckResult]:
    results = (check_donation() + check_wire_dtype() + check_callbacks()
               + check_serve())
    if not fast:
        results += (check_retrace() + check_defense_retrace()
                    + check_async_retrace())
    return results


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.program_check",
        description="fedlint Layer 2: compiled-program contract checks.")
    ap.add_argument("--fast", action="store_true",
                    help="skip the (slower) retrace matrix")
    args = ap.parse_args(argv)
    results = run_all(fast=args.fast)
    for r in results:
        print(r.render())
    bad = [r for r in results if not r.ok]
    print(f"program_check: {len(results) - len(bad)}/{len(results)} passed")
    return 1 if bad else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
