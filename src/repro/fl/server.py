"""FL server: sampling, straggler-aware aggregation, personalization.

Fault-tolerance / straggler model: per-round client latencies are drawn
from a lognormal compute + payload/bandwidth communication model; the
server over-samples by ``oversample`` and aggregates whoever arrives
before the deadline (quantile of expected latency). Clients that miss
the deadline are dropped from the round — a dropped pod costs a round
of its data, never a crash. ``staleness_mix`` is a legacy sync mixing
knob; true event-driven asynchrony is ``engine="async"`` below.

Execution engines (``ServerConfig.engine``):
  sequential  — reference implementation: a Python loop over arrived
                clients, one jitted step per local minibatch.
  batched     — ``repro.fl.batch_engine.ClientBatch``: all sampled
                clients' params/state are stacked along a leading
                client axis and the whole round (local epochs, payload
                selection, quantization, aggregation) runs as one
                jit-compiled vmap/shard_map program. Round memory is
                O(C · model).
  streaming   — ``repro.fl.stream_engine.StreamingRound``: one
                jit-compiled ``lax.scan`` over fixed-size client chunks
                (``ServerConfig.client_chunk``) threading a running
                fp32 weighted-sum accumulator; uploads stay in encoded
                wire form and are folded in by the fused
                dequant-accumulate Pallas kernel. Round memory is
                O(chunk · model + model) — participation becomes a
                time axis, so cohorts the stacked engine cannot hold
                (1024+ simulated clients on one host) stream through.
  async       — ``repro.fl.async_engine``: event-driven FedBuff-style
                buffered federation. A virtual clock drains an arrival
                queue (the same latency model the sync engines mask
                on); each upload folds into the streaming accumulator
                AT ARRIVAL, weighted by a staleness function ``s(tau)``
                (``ServerConfig.staleness``), and ``buffer_k`` folded
                arrivals trigger a version bump + re-broadcast. With
                ``buffer_k`` = participation target and every arrival
                landing before the next dispatch, it reproduces the
                streaming engine to fp32 tolerance with bitwise masks
                (see docs/async.md).

Masked-aggregation semantics: both engines derive the SAME boolean
arrived-mask over the sampled clients from host-side RNG draws
(``_select_round``): a client participates iff it survived random
dropout, beat the straggler deadline, and falls within the first
``n_target`` arrivals in simulated-latency order (earliest arrivals
win, not earliest sampling positions). The sequential engine
materializes the mask as the ``arrived`` list it loops over; the
batched engine keeps every sampled client in the stacked program and
multiplies the mask into the aggregation weights, so dropped clients
contribute exactly zero to the weighted tree-reduce and their
state/resident updates are discarded at unstack time. The mask is
bitwise identical between engines (it is recorded per round in
``history[i]["arrived_mask"]``), and the aggregated global params
match to fp32 tolerance.

Personalization modes:
  none      — vanilla FL (upload/download everything)
  pfedpara  — paper §2.3: only x1/y1 (the global halves) transferred;
              x2/y2 persist per client
  fedper    — Arivazhagan et al.: last layer stays local
  local     — FedPAQ-style local-only baseline (no aggregation)

Communication codecs (``ServerConfig.uplink_codec`` /
``downlink_codec``, specs like ``"delta|topk0.1|int8"`` — see
``repro.fl.codecs``): the downlink payload is encoded/decoded ONCE per
round host-side (the broadcast is identical for every client; delta
reference and server-side error feedback are broadcast state shared by
all clients, the standard sync-FL simulation assumption), and clients
train on the DECODED payload. Uplinks are encoded per client against
the round's decoded broadcast, with client-resident error-feedback
accumulators threaded through ``client_states["_ef_up"]``. The legacy
``uplink_quant`` / ``downlink_quant`` fields map to single-stage
quantizer codecs when no codec spec is given. ``CommLog`` charges the
codecs' exact ``wire_bytes``.

Heterogeneous capacity tiers (``ServerConfig.gamma_tiers`` /
``tier_assignment`` — see ``docs/hetero.md``): each client belongs to a
capacity tier with its own rank gamma; it receives, trains and uploads
only the leading tier-rank columns of every FedPara factor. The
sequential engine masks host-side per client; the batched/streaming
engines keep ONE compiled program by gathering per-client column masks
from a ``(T, ...)`` tier table instead of using ragged shapes. The
server aggregates rank-sliced uploads into the full-rank global factors
with per-column arrival-weighted averaging: columns beyond a client's
tier contribute zero WEIGHT (not zero value), and columns no arrived
client covers keep their current global value. Wire bytes are priced at
each tier's physically sliced payload shapes on both links, including
the straggler latency model. ``gamma_tiers=()`` (default) is exactly
the homogeneous path; a single tier at the model's own gamma reproduces
it to fp32 tolerance with bitwise-identical arrival masks.
"""
from __future__ import annotations

import functools
import heapq
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import parameterization as param_lib
from repro.core import rank_policy
from repro.data.loader import (DeviceDataset, client_epochs, data_bytes,
                               epoch_indices, gather_host)
from repro.fl import codecs, comm
from repro.fl import faults as faults_lib
from repro.fl.arrivals import arrival_events, arrival_mask, fold_crashes
from repro.fl.client import ClientConfig, init_client_state, local_update
from repro.fl.strategies import (
    Strategy, tree_broadcast, tree_hetero_wmean_stacked,
    tree_trimmed_wmean_stacked, tree_index, tree_mean, tree_stack,
    tree_wmean_stacked)
from repro.fl.trace import spawn_seeds

FEDPER_LOCAL_KEYS = ("head", "fc2", "b2")   # model-specific last layers


def _loss_stats(losses) -> tuple:
    """``(mean, nonfinite_count)`` over per-client round losses: the
    mean ignores non-finite entries (one NaN/Inf client must not poison
    the whole round's ``mean_loss``) and the count keeps fault rounds
    diagnosable. All-finite rounds reproduce the plain mean bitwise."""
    arr = np.asarray(losses).reshape(-1)
    if arr.size == 0:
        return float("nan"), 0
    fin = np.isfinite(arr)
    mean = float(arr[fin].mean()) if fin.any() else float("nan")
    return mean, int((~fin).sum())


def _to_plain(obj):
    """Recursively convert numpy scalars/arrays to plain Python so the
    checkpoint's msgpack ``extra`` blob can serialize history records."""
    if isinstance(obj, dict):
        return {k: _to_plain(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_plain(v) for v in obj]
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, (np.ndarray, jnp.ndarray)):
        return _to_plain(np.asarray(obj).tolist())
    return obj


# ``arrival_mask`` now lives in ``repro.fl.arrivals`` (one arrival-
# ordering code path shared with the async engine's event queue); it is
# re-imported above so existing ``from repro.fl.server import
# arrival_mask`` call sites keep working.
assert arrival_mask is not None


@dataclass
class ServerConfig:
    """Round/selection/wire/engine settings for :class:`FLServer`.

    Groups: fleet + participation (``clients``, ``participation``,
    ``rounds``, ``lr_decay``); personalization mode; wire codecs
    (``uplink_codec``/``downlink_codec`` specs — see docs/codecs.md —
    with the legacy ``*_quant`` single-stage fields as fallback);
    straggler/fault model (``oversample``, ``deadline_quantile``,
    ``straggler_sigma``, ``bandwidth_mbps``, ``dropout_prob``,
    ``staleness_mix``); execution engine (``engine``, ``client_chunk``
    — see docs/engines.md); fleet substrate (``state_store``,
    ``data_stream``, ``trace`` — see docs/fleet.md); heterogeneous
    capacity tiers (``gamma_tiers``, ``tier_assignment`` — see
    docs/hetero.md).
    """

    clients: int = 100
    participation: float = 0.16
    rounds: int = 20
    lr_decay: float = 0.992
    personalization: str = "none"      # none | pfedpara | fedper | local
    uplink_quant: str = "fp32"         # legacy: fp32 | fp16 | int8
    downlink_quant: str = "fp32"       # legacy: fp32 | fp16 | int8
    uplink_codec: str = ""             # codec spec, e.g. "delta|topk0.1|int8"
    downlink_codec: str = ""           # overrides *_quant when non-empty
    oversample: float = 0.0            # straggler over-sampling fraction
    deadline_quantile: float = 0.9
    straggler_sigma: float = 0.5       # lognormal sigma of compute time
    bandwidth_mbps: float = 10.0
    dropout_prob: float = 0.0          # random client failure per round
    staleness_mix: float = 0.0         # >0: async staleness-weighted mixing
    engine: str = "sequential"         # sequential | batched | streaming
                                       # | async (event-driven buffered
                                       # federation — docs/async.md)
    client_chunk: int = 16             # streaming/async: clients per scan step
    buffer_k: int = 0                  # async: folded arrivals per version
                                       # bump; 0 = the participation target
                                       # (K = cohort, the sync-parity limit)
    staleness: str = "constant"        # async staleness weight s(tau):
                                       # constant | poly[:a] | hinge[:b]
    max_staleness: int = -1            # async: drop arrivals staler than
                                       # this many versions; -1 = never
    state_store: str = "dict"          # dict | arena: host dicts (the
                                       # reference) or the device-resident
                                       # index-addressed fleet arena
                                       # (repro.fl.arena, docs/fleet.md)
    data_stream: str = "eager"         # eager | chunked: cohort batch
                                       # stack up front, or lazy per-chunk
                                       # host-callback materialization
                                       # (streaming engine only)
    trace: Optional[Any] = None        # repro.fl.trace.FleetTrace: O(cohort)
                                       # trace-driven sampling/availability;
                                       # None = legacy O(fleet) RNG path
    gamma_tiers: tuple = ()            # heterogeneous capacity tiers: one
                                       # rank-gamma per tier; () = uniform
                                       # full-rank clients (today's path)
    tier_assignment: str = "round_robin"   # round_robin | random | size
    defense: str = "none"              # upload screening + robust agg:
                                       # none | clip | trimmed (trimmed is
                                       # batched-only — docs/robustness.md)
    defense_z: float = 3.0             # validity-gate norm z-score bound
    defense_clip: float = 1.0          # clip: tau = clip * median norm
    defense_trim: float = 0.1          # trimmed: fraction cut per side
    faults: Optional[Any] = None       # repro.fl.faults.FaultPlan: chaos
                                       # injection; None = fault-free
    recover_frac: float = 0.5          # re-sample the round when more than
                                       # this fraction of participants
                                       # crashed or were gate-rejected ...
    recover_retries: int = 0           # ... up to this many retries
    seed: int = 0


class FLServer:
    """The federated-learning server/simulator (see module docstring).

    Args:
        loss_fn: ``loss_fn(params, batch) -> scalar`` traced inside each
            client's local step.
        global_params: initial global model pytree (FedPara factors are
            just leaves of this tree).
        data: dataset dict of arrays; clients index it via
            ``partitions``.
        partitions: per-client index arrays into ``data``.
        strategy: a ``repro.fl.strategies.Strategy``.
        client_cfg: local-SGD settings (lr, batch, epochs, ...).
        server_cfg: round/selection/codec/engine/tier settings.
        eval_fn: optional ``eval_fn(global_params) -> metric`` recorded
            per round in ``history[i]["eval"]``.
        mesh / mesh_axis: optional jax mesh for the batched/streaming
            engines' shard_map path.

    After ``run()``: ``global_params`` holds the trained model,
    ``history`` the per-round records (participants, ``arrived_mask``,
    mean loss, exact ``down_bytes``/``up_bytes``), ``comm_log`` the
    cumulative wire-byte totals, ``client_states``/``local_trees`` the
    per-client strategy state and personalization residents.
    """

    def __init__(
        self,
        loss_fn: Callable,
        global_params: Any,
        data: Dict[str, np.ndarray],
        partitions: List[np.ndarray],
        strategy: Strategy,
        client_cfg: ClientConfig,
        server_cfg: ServerConfig,
        eval_fn: Optional[Callable] = None,
        mesh: Optional[Any] = None,
        mesh_axis: str = "clients",
    ):
        self.loss_fn = loss_fn
        if mesh is not None:
            # replicated over the mesh from the start, as every round's
            # output is: the round programs then see one set of input
            # shardings and compile once
            from jax.sharding import NamedSharding, PartitionSpec

            global_params = jax.device_put(
                global_params, NamedSharding(mesh, PartitionSpec()))
        self.global_params = global_params
        self.data = data
        self.partitions = partitions
        self.strategy = strategy
        self.ccfg = client_cfg
        self.scfg = server_cfg
        self.eval_fn = eval_fn
        self.rng = np.random.RandomState(server_cfg.seed)
        self.round_idx = 0
        self.comm_log = comm.CommLog()
        self.server_state = (strategy.server_init(global_params)
                             if strategy.server_init else {})
        self.client_states: Dict[int, Dict] = {}
        self.local_trees: Dict[int, Any] = {}   # personalization residents
        self.history: List[Dict] = []
        self.uplink_codec = codecs.make_codec(
            server_cfg.uplink_codec or server_cfg.uplink_quant)
        self.downlink_codec = codecs.make_codec(
            server_cfg.downlink_codec or server_cfg.downlink_quant)
        self._down_ref: Any = None   # last decoded broadcast (delta ref)
        self._down_ef: Any = None    # server-side downlink error feedback
        self.tiers: Optional[rank_policy.TierSchedule] = None
        self.tier_of: Optional[np.ndarray] = None
        self._tier_cache: Optional[Dict] = None
        trace = server_cfg.trace
        if trace is not None and int(trace.clients) != int(server_cfg.clients):
            raise ValueError(
                f"trace.clients={trace.clients} != "
                f"ServerConfig.clients={server_cfg.clients}")
        if server_cfg.gamma_tiers:
            self.tiers = rank_policy.TierSchedule(
                tuple(float(g) for g in server_cfg.gamma_tiers),
                server_cfg.tier_assignment)
            if trace is not None and getattr(trace, "tier_mix", ()):
                # trace-hashed tiers: no O(fleet) assignment table
                if len(trace.tier_mix) != len(server_cfg.gamma_tiers):
                    raise ValueError(
                        "trace.tier_mix must pair one proportion with "
                        "each gamma tier")
            else:
                self.tier_of = self.tiers.assign(
                    server_cfg.clients,
                    sizes=[len(p) for p in partitions],
                    seed=server_cfg.seed)
        if server_cfg.state_store not in ("dict", "arena"):
            raise ValueError(
                f"unknown state_store {server_cfg.state_store!r} "
                "(expected dict | arena)")
        if (server_cfg.state_store == "arena"
                and server_cfg.engine == "sequential"):
            raise ValueError(
                "state_store='arena' requires the batched or streaming "
                "engine (the sequential reference keeps host dicts)")
        if server_cfg.data_stream not in ("eager", "chunked"):
            raise ValueError(
                f"unknown data_stream {server_cfg.data_stream!r} "
                "(expected eager | chunked)")
        if (server_cfg.data_stream == "chunked"
                and server_cfg.engine != "streaming"):
            raise ValueError(
                "data_stream='chunked' requires the streaming engine")
        if server_cfg.defense not in ("none", "clip", "trimmed"):
            raise ValueError(
                f"unknown defense {server_cfg.defense!r} "
                "(expected none | clip | trimmed)")
        if (server_cfg.defense == "trimmed"
                and server_cfg.engine != "batched"):
            raise ValueError(
                "defense='trimmed' requires the batched engine: the "
                "coordinate-wise trim needs every upload resident along "
                "the client axis (see docs/robustness.md); the streaming "
                "fold, the async event loop and the sequential reference "
                "use defense='clip'")
        if server_cfg.engine == "async":
            if server_cfg.staleness_mix > 0:
                raise ValueError(
                    "staleness_mix is the legacy sync mixing knob; the "
                    "async engine weights every arrival by its real "
                    "staleness s(tau) — use ServerConfig.staleness")
            if server_cfg.recover_retries > 0:
                raise ValueError(
                    "recover_retries (round-level cohort re-sampling) is "
                    "a synchronous-round notion; the async engine "
                    "recovers by dispatching fresh cohorts whenever the "
                    "arrival queue runs dry before buffer_k")
            if server_cfg.buffer_k < 0:
                raise ValueError("buffer_k must be >= 0")
        plan = server_cfg.faults
        if plan is not None and not isinstance(plan, faults_lib.FaultPlan):
            raise ValueError(
                "ServerConfig.faults must be a repro.fl.faults.FaultPlan")
        if server_cfg.recover_retries < 0:
            raise ValueError("recover_retries must be >= 0")
        self._stale_ref: Any = None   # previous decoded broadcast (what a
                                      # stale-replay fault re-uploads)
        self.arena = None   # created lazily at the first arena-mode round
        self._device_data = None   # uploaded lazily at the first stack
        self._mesh, self._mesh_axis = mesh, mesh_axis
        self._engine = None
        self._stream = None
        self._adispatch = None
        self._async = None            # async engine event-loop state
        self._staleness_fn = None
        self._client_versions: Dict[int, int] = {}   # dict-mode pinning
        if server_cfg.engine == "batched":
            from repro.fl.batch_engine import ClientBatch

            self._engine = ClientBatch(
                loss_fn=loss_fn, strategy=strategy, client_cfg=client_cfg,
                personalization=server_cfg.personalization,
                uplink_codec=self.uplink_codec,
                fedper_local_keys=FEDPER_LOCAL_KEYS,
                mesh=mesh, mesh_axis=mesh_axis,
                defense=server_cfg.defense,
                defense_z=server_cfg.defense_z,
                defense_clip=server_cfg.defense_clip,
                defense_trim=server_cfg.defense_trim,
                flip_bits=plan.flip_bits if plan is not None else 4)
        elif server_cfg.engine == "streaming":
            from repro.fl.stream_engine import StreamingRound

            self._stream = StreamingRound(
                loss_fn=loss_fn, strategy=strategy, client_cfg=client_cfg,
                personalization=server_cfg.personalization,
                uplink_codec=self.uplink_codec,
                fedper_local_keys=FEDPER_LOCAL_KEYS,
                chunk=max(1, int(server_cfg.client_chunk)),
                mesh=mesh, mesh_axis=mesh_axis,
                defense=server_cfg.defense,
                defense_z=server_cfg.defense_z,
                defense_clip=server_cfg.defense_clip,
                flip_bits=plan.flip_bits if plan is not None else 4)
        elif server_cfg.engine == "async":
            from repro.fl.async_engine import AsyncDispatch, make_staleness

            self._staleness_fn = make_staleness(server_cfg.staleness)
            self._adispatch = AsyncDispatch(
                loss_fn=loss_fn, strategy=strategy, client_cfg=client_cfg,
                personalization=server_cfg.personalization,
                uplink_codec=self.uplink_codec,
                fedper_local_keys=FEDPER_LOCAL_KEYS,
                chunk=max(1, int(server_cfg.client_chunk)),
                mesh=mesh, mesh_axis=mesh_axis,
                defense=server_cfg.defense,
                defense_z=server_cfg.defense_z,
                defense_clip=server_cfg.defense_clip,
                flip_bits=plan.flip_bits if plan is not None else 4)
        elif server_cfg.engine != "sequential":
            raise ValueError(
                f"unknown engine {server_cfg.engine!r} "
                "(expected sequential | batched | streaming | async)")

    @property
    def round_engine(self) -> Any:
        """The compiled engine running the rounds: a ``ClientBatch``,
        ``StreamingRound`` or ``AsyncDispatch``; ``None`` for the
        sequential reference."""
        return self._engine or self._stream or self._adispatch

    # ------------------------------------------------------------ payload
    def _download_payload(self, cid: int) -> Any:
        p = self.global_params
        mode = self.scfg.personalization
        if mode == "pfedpara":
            glob, _ = comm.split_pfedpara(p)
            return glob
        if mode == "fedper":
            return {k: v for k, v in p.items() if k not in FEDPER_LOCAL_KEYS}
        return p

    def _client_full_params(self, cid: int, download: Any) -> Any:
        """Client-side model assembly from the (decoded) downlink payload
        plus personalization residents. First-time participants take
        their resident half from the global init, so they too train on
        the decoded broadcast — not on uncompressed global params."""
        mode = self.scfg.personalization
        if mode == "none":
            return download
        resident = self.resident_of(cid)
        if mode == "pfedpara":
            if resident is None:
                resident = comm.split_pfedpara(self.global_params)[1]
            return comm.merge_pfedpara(download, resident)
        if mode == "fedper":
            if resident is None:
                resident = {k: v for k, v in self.global_params.items()
                            if k in FEDPER_LOCAL_KEYS}
            merged = dict(download)
            merged.update(resident)
            return merged
        if mode == "local":
            return resident if resident is not None else download
        return download

    def resident_of(self, cid: int) -> Any:
        """One client's personalization resident, wherever it lives:
        the arena row (``state_store='arena'``) or the ``local_trees``
        dict (``None`` if the client never participated — callers fall
        back to the global init, which is exactly what an arena row
        still holds before its first scatter)."""
        if self.arena is not None and self.arena.residents is not None:
            return self.arena.client_resident(cid)
        return self.local_trees.get(cid)

    def client_state_of(self, cid: int) -> Dict:
        """One client's strategy/EF state, wherever it lives: the arena
        row (``state_store='arena'``) or the ``client_states`` dict
        (``{}`` if the client never participated)."""
        if self.arena is not None:
            return self.arena.client_state(cid)
        return self.client_states.get(cid, {})

    def participation_counts(self) -> np.ndarray:
        """(clients,) per-client arrival counts. Arena mode reads the
        device-resident counter row (one masked ``.at[].add`` per
        round); dict mode tallies the recorded per-round cohorts."""
        if self.arena is not None:
            return self.arena.participation_counts()
        counts = np.zeros(self.scfg.clients, np.int64)
        for r in self.history:
            for cid, hit in zip(r.get("sampled", ()),
                                r.get("arrived_mask", ())):
                counts[cid] += int(hit)
        return counts

    def _split_upload(self, cid: int, trained: Any, into: Optional[Dict] = None):
        """Split a trained tree into (upload, resident); the resident
        lands in ``into`` (default ``self.local_trees`` — pass a pending
        dict to defer the writeback until the round commits)."""
        target = self.local_trees if into is None else into
        mode = self.scfg.personalization
        if mode == "pfedpara":
            glob, loc = comm.split_pfedpara(trained)
            target[cid] = loc
            return glob
        if mode == "fedper":
            target[cid] = {k: trained[k] for k in FEDPER_LOCAL_KEYS
                           if k in trained}
            return {k: v for k, v in trained.items() if k not in FEDPER_LOCAL_KEYS}
        if mode == "local":
            target[cid] = trained
            return None
        return trained

    def _apply_aggregated(self, new_global_part: Any, agg_target: Any):
        """Write the aggregated global slice back, with optional
        staleness-weighted async mixing. Shared by both engines."""
        scfg = self.scfg
        if scfg.staleness_mix > 0:
            a = scfg.staleness_mix
            new_global_part = jax.tree.map(
                lambda old, new: (1 - a) * old + a * new,
                agg_target, new_global_part)
        if scfg.personalization == "none":
            self.global_params = new_global_part
        elif scfg.personalization == "pfedpara":
            self.global_params = comm.merge_pfedpara(
                new_global_part, comm.split_pfedpara(self.global_params)[1])
        else:
            self.global_params = {**self.global_params, **new_global_part}

    # ------------------------------------------------ heterogeneous tiers
    def _tier_state(self, probe: Any) -> Dict:
        """Round-invariant tier tables, built once from the downlink
        payload structure (lazily, since the payload structure depends
        on the personalization mode):

          payload_masks  (T, ...)-leading rank-mask tree over the
                         payload structure (uploads + aggregation),
          full_masks     same over the full global-param structure
                         (client assembly + strategy state),
          down_bytes /   exact per-tier wire bytes, priced by each link's
          up_bytes       codec on the PHYSICALLY SLICED payload shapes —
                         the shape algebra of ``Codec.wire_bytes`` stays
                         exact, it just sees tier-rank column counts.
        """
        if self._tier_cache is None:
            gammas = self.tiers.gammas
            sliced = [param_lib.slice_factor_tree(probe, g) for g in gammas]
            self._tier_cache = {
                "payload_masks": param_lib.tier_rank_masks(probe, gammas),
                "full_masks": param_lib.tier_rank_masks(
                    self.global_params, gammas),
                "down_bytes": tuple(
                    self.downlink_codec.wire_bytes(s) for s in sliced),
                "up_bytes": tuple(
                    self.uplink_codec.wire_bytes(s) for s in sliced),
            }
        return self._tier_cache

    def tier_bytes(self) -> List[Dict]:
        """Public per-tier wire pricing (heterogeneous mode only).

        Returns one dict per tier, in ``gamma_tiers`` order:
        ``{"gamma", "up_bytes", "down_bytes", "clients"}`` — the exact
        per-round per-client wire bytes of the tier's sliced payload on
        each link, and how many clients the assignment mapped to it.
        Raises if ``gamma_tiers`` is unset or no round has run yet (the
        payload structure, hence the pricing, is known after the first
        round's broadcast).
        """
        if self.tiers is None:
            raise ValueError("tier_bytes() requires ServerConfig.gamma_tiers")
        if self._tier_cache is None:
            raise ValueError("tier_bytes() is available after the first "
                             "round (run_round() fixes the payload shapes)")
        tc = self._tier_cache
        if self.tier_of is not None:
            counts = [int((self.tier_of == t).sum())
                      for t in range(len(self.tiers.gammas))]
        else:   # trace-hashed tiers: expected counts, fleet never walked
            counts = [int(c) for c in self.scfg.trace.tier_counts()]
        return [{"gamma": g,
                 "up_bytes": tc["up_bytes"][t],
                 "down_bytes": tc["down_bytes"][t],
                 "clients": counts[t]}
                for t, g in enumerate(self.tiers.gammas)]

    def _cohort_tiers(self, cids) -> Optional[np.ndarray]:
        """Tier index per cohort client: the assignment table when one
        exists, otherwise the trace's O(cohort) id hash. ``None`` in
        homogeneous mode."""
        if self.tiers is None:
            return None
        cids = np.asarray(cids, np.int64)
        if self.tier_of is not None:
            return self.tier_of[cids].astype(np.int32)
        return self.scfg.trace.tiers_of(cids)

    def _round_bytes(self, sampled, mask, down_bytes: int, down_dec: Any,
                     up_mask=None) -> tuple:
        """Exact (down, up) wire bytes for the round's arrived clients.
        Homogeneous: participants × full payload bytes (as before).
        Heterogeneous: each arrived client is charged its TIER's sliced
        payload bytes on both links. ``up_mask`` (fault injection) lets
        crash-before-upload clients charge the downlink only — they
        received the broadcast, trained, and vanished."""
        if up_mask is None:
            up_mask = mask
        n_arrived = int(mask.sum())
        local = self.scfg.personalization == "local"
        if self.tiers is None:
            up = 0 if local else self.uplink_codec.wire_bytes(down_dec)
            return n_arrived * down_bytes, int(up_mask.sum()) * up
        tc = self._tier_cache
        down_tiers = self._cohort_tiers(
            np.asarray(sampled)[mask.astype(bool)])
        up_tiers = self._cohort_tiers(
            np.asarray(sampled)[up_mask.astype(bool)])
        down = sum(tc["down_bytes"][int(t)] for t in down_tiers)
        up = 0 if local else sum(tc["up_bytes"][int(t)] for t in up_tiers)
        return down, up

    # ------------------------------------------------------------- round
    def _simulate_latency(self, payload_bytes, n: int) -> np.ndarray:
        comp = self.rng.lognormal(mean=0.0, sigma=self.scfg.straggler_sigma, size=n)
        comm_s = 8.0 * payload_bytes / (self.scfg.bandwidth_mbps * 1e6)
        return comp + comm_s

    def _select_round(self, attempt: int = 0):
        """Host-side RNG for one round, shared verbatim by both engines:
        sample clients, simulate stragglers/dropout, derive the boolean
        arrived-mask over the sampled order (truncated to the first
        ``n_target`` ARRIVALS — earliest simulated latency first), and
        derive every sampled client's data seed. The mask — not a
        filtered list — is the round's participation record, so the two
        engines agree bitwise. Download latency is priced at the active
        downlink codec's wire bytes, not the raw fp32 tree.

        With a :class:`repro.fl.trace.FleetTrace` configured, sampling,
        availability and latency come from the trace's per-round
        generator at O(cohort) cost — ``dropout_prob`` defers to the
        trace's own dropout/diurnal model. Per-client data seeds are
        ``SeedSequence.spawn``-derived 64-bit values on BOTH paths
        (collision-free at fleet scale, unlike the legacy 2^30 draws).

        ``attempt > 0`` (round-level fault recovery) re-samples a
        replacement cohort from a fresh salted stream: the trace path
        salts its per-round generator, the legacy path switches to the
        stateless :func:`repro.fl.faults.recovery_rng` so retries never
        disturb the stateful ``self.rng`` sequence the clean rounds
        replay from.
        """
        scfg = self.scfg
        trace = scfg.trace
        n_target = max(1, int(round(scfg.participation * scfg.clients)))
        n_sample = max(n_target, int(round(n_target * (1 + scfg.oversample))))
        n_sample = min(n_sample, scfg.clients)
        rrng = (faults_lib.recovery_rng(scfg.seed, self.round_idx, attempt)
                if attempt and trace is None else None)
        if trace is not None:
            trng = trace.round_rng(self.round_idx, salt=attempt)
            sampled = trace.sample_cohort(trng, n_sample)
        elif rrng is not None:
            sampled = rrng.choice(scfg.clients, size=n_sample, replace=False)
        else:
            sampled = self.rng.choice(scfg.clients, size=n_sample,
                                      replace=False)
        lr = self.ccfg.lr * (scfg.lr_decay ** self.round_idx)

        probe_payload = self._download_payload(int(sampled[0]))
        if self.tiers is not None:
            # per-tier sliced broadcast: each sampled client's download
            # latency is priced at ITS tier's wire bytes
            tc = self._tier_state(probe_payload)
            payload_bytes = np.asarray(tc["down_bytes"])[
                self._cohort_tiers(sampled)]
        else:
            payload_bytes = self.downlink_codec.wire_bytes(probe_payload)
        if trace is not None:
            lat = trace.latency(trng, payload_bytes, len(sampled),
                                scfg.straggler_sigma, scfg.bandwidth_mbps)
            alive = (trng.random(len(sampled))
                     < trace.availability(sampled, self.round_idx))
        elif rrng is not None:
            lat = (rrng.lognormal(mean=0.0, sigma=scfg.straggler_sigma,
                                  size=len(sampled))
                   + 8.0 * np.asarray(payload_bytes, np.float64)
                   / (scfg.bandwidth_mbps * 1e6))
            alive = rrng.random(len(sampled)) >= scfg.dropout_prob
        else:
            lat = self._simulate_latency(payload_bytes, len(sampled))
            alive = self.rng.rand(len(sampled)) >= scfg.dropout_prob
        deadline = (np.quantile(lat, scfg.deadline_quantile)
                    if scfg.oversample else np.inf)
        ok = alive & (lat <= deadline)
        mask = arrival_mask(ok, lat, n_target)
        seeds = spawn_seeds(scfg.seed, self.round_idx, len(sampled))
        return sampled, mask, seeds, lr, probe_payload, lat

    def _quant_keys(self, n: int) -> jax.Array:
        """Per-client quantization keys: ``fold_in(key(round), i)`` for
        every cohort position — vectorized with one ``vmap`` dispatch
        (value-identical to the historical per-client fold_in loop,
        which cost O(cohort) dispatches per round)."""
        base = jax.random.PRNGKey(self.round_idx)
        return jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(n, dtype=jnp.uint32))

    def _encode_downlink(self, payload: Any):
        """One broadcast encode/decode per round (the downlink payload
        is identical for every sampled client). Returns the DECODED
        payload clients actually train on plus its exact per-client
        wire bytes; advances the server-side delta reference / error
        feedback. Identity codecs short-circuit so legacy runs are
        numerically untouched."""
        codec = self.downlink_codec
        if codec.is_identity:
            return payload, codec.wire_bytes(payload)
        if codec.has_delta and self._down_ref is None:
            self._down_ref = jax.tree.map(jnp.zeros_like, payload)
        if codec.has_ef and self._down_ef is None:
            self._down_ef = codec.ef_init(payload)
        key = jax.random.fold_in(jax.random.PRNGKey(self.round_idx),
                                 0x7FFFFFFF)   # distinct from client keys
        wire, self._down_ef = codec.encode(
            payload, ref=self._down_ref, ef=self._down_ef, key=key)
        decoded = codec.decode(wire, ref=self._down_ref)
        if codec.has_delta:
            self._down_ref = decoded   # clients cache the last broadcast
        return decoded, codec.wire_bytes(payload)

    def run_round(self) -> Dict:
        """Execute one federated round end-to-end (selection, broadcast
        encode, fault injection, the configured engine, defense gating,
        round-level recovery, bookkeeping) and return (and append to
        ``history``) its record dict.

        With ``ServerConfig.faults`` set, each attempt draws the round's
        deterministic fault schedule, folds crash-before-upload clients
        out of the effective arrival mask, and runs the engine WITHOUT
        committing state; when crashed + gate-rejected clients exceed
        ``recover_frac`` of the participants and retries remain, a
        replacement cohort is re-sampled from a salted stream and the
        attempt's results are discarded. Only the accepted attempt's
        writebacks, aggregation and wire charges commit.

        The round and its phases are recorded as ``jax.profiler`` spans
        (``fl.round``, ``fl.round.<phase>``; see docs/engines.md), which
        are inert while no profiler runs."""
        with TraceAnnotation("fl.round"):
            if self.scfg.engine == "async":
                return self._run_async_round()
            return self._run_sync_round()

    def _run_sync_round(self) -> Dict:
        """One round of the sequential, batched or streaming engine
        (:meth:`run_round`), its phases recorded as profiler spans."""
        scfg = self.scfg
        plan = scfg.faults
        with TraceAnnotation("fl.round.select"):
            sampled, mask, seeds, lr, probe, lat = self._select_round()
            if not mask.any():   # everyone failed: skip round (fault tolerance)
                self.round_idx += 1
                return {"round": self.round_idx, "participants": 0,
                        "skipped": True}
            down_dec, down_bytes = self._encode_downlink(probe)
        attempt = 0
        while True:
            fault = (plan.draw(self.round_idx, len(sampled), attempt)
                     if plan is not None else None)
            # crash-before-upload folds into the EFFECTIVE arrival mask
            # host-side: the client trained and vanished — no upload, no
            # state writeback, zero aggregation weight
            eff = fold_crashes(
                mask, fault["crash"] if fault is not None else None)
            if eff.any():
                if self._stream is not None:
                    runner = self._run_round_streaming
                elif self._engine is not None:
                    runner = self._run_round_batched
                else:
                    runner = self._run_round_sequential
                rec, commit, valid = runner(sampled, eff, seeds, lr,
                                            down_dec, down_bytes,
                                            sel_mask=mask, fault=fault)
            else:
                # every participant crashed before upload: a
                # downlink-only round, nothing arrives to aggregate
                valid = np.zeros(len(sampled), np.float32)
                rd, ru = self._round_bytes(sampled, mask, down_bytes,
                                           down_dec, up_mask=eff)
                rec = {"participants": int(mask.sum()),
                       "sampled": len(sampled),
                       "mean_loss": float("nan"), "nonfinite_losses": 0,
                       "down_bytes": rd, "up_bytes": ru, "lr": lr}

                def commit(rd=rd, ru=ru):
                    self.comm_log.log_round(rd, ru)
            participants = int(mask.sum())
            ok = (int(np.round(np.asarray(valid, np.float64)[
                np.asarray(eff, bool)].sum())) if eff.any() else 0)
            rejected = participants - ok
            if (fault is not None and attempt < scfg.recover_retries
                    and rejected > scfg.recover_frac * participants):
                nxt = self._select_round(attempt + 1)
                if nxt[1].any():
                    # discard the attempt (nothing committed) and rerun
                    # the round on the replacement cohort
                    attempt += 1
                    sampled, mask, seeds, lr, _, lat = nxt
                    continue
            break
        with TraceAnnotation("fl.round.commit"):
            commit()
        # virtual seconds the sync barrier costs: the round completes
        # when its LAST arrival lands (the async engine's benchmark
        # baseline — see benchmarks/fl_async.py)
        rec["round_latency"] = float(
            np.max(np.asarray(lat)[mask.astype(bool)]))
        rec["comm_gb"] = self.comm_log.total_gb
        self.round_idx += 1
        rec["round"] = self.round_idx
        rec["arrived_mask"] = mask.astype(int).tolist()
        rec["sampled"] = [int(c) for c in sampled]
        if plan is not None:
            rec["rejected"] = rejected
            rec["retries"] = attempt
            rec["fault_kinds"] = plan.kind_counts(fault, mask)
        if self.eval_fn is not None:
            rec["eval"] = self.eval_fn(self.global_params)
        self.history.append(rec)
        # next round's stale-replay faults re-upload THIS broadcast
        self._stale_ref = down_dec
        return rec

    def _ensure_ef(self, state: Dict, payload: Any) -> Dict:
        """Attach a zero uplink error-feedback accumulator (payload
        structure) to a client state that does not have one yet."""
        if self.uplink_codec.has_ef and "_ef_up" not in state:
            state = {**state, "_ef_up": self.uplink_codec.ef_init(payload)}
        return state

    # ------------------------------------------- sequential reference
    def _run_round_sequential(self, sampled, mask, seeds, lr, down_dec,
                              down_bytes, sel_mask=None, fault=None):
        """Reference round. ``mask`` is the EFFECTIVE arrival mask
        (crash faults removed); ``sel_mask`` the selection mask used for
        participant counts and downlink charges. Returns ``(rec, commit,
        valid)``: nothing is written back until ``commit()`` runs, so a
        recovery retry can discard the whole attempt."""
        scfg = self.scfg
        if sel_mask is None:
            sel_mask = mask
        up_codec = self.uplink_codec
        plan = scfg.faults
        quant_keys = self._quant_keys(len(sampled))
        hetero = self.tiers is not None
        tc = self._tier_state(down_dec) if hetero else None
        cohort_tiers = self._cohort_tiers(sampled) if hetero else None
        pend_states: Dict[int, Dict] = {}
        pend_locals: Dict[int, Any] = {}
        uploads, up_masks, weights, losses, up_pos = [], [], [], [], []
        for i, cid in enumerate(int(c) for c in sampled):
            if not mask[i]:
                continue
            tier = int(cohort_tiers[i]) if hetero else -1
            params = self._client_full_params(cid, down_dec)
            if hetero:
                # the client only receives (and trains) the leading
                # tier-rank factor columns of the broadcast
                params = param_lib.apply_rank_mask(
                    params, tree_index(tc["full_masks"], tier))
            state = self._prep_client_state(cid, params, down_dec, tier=tier)
            batches = client_epochs(self.data, self.partitions[cid],
                                    self.ccfg.batch, self.ccfg.epochs,
                                    seed=int(seeds[i]))
            trained, state, m = local_update(
                params, batches, self.loss_fn, self.ccfg, self.strategy,
                client_state=state, lr=lr)
            up = self._split_upload(cid, trained, into=pend_locals)
            if up is not None:
                ref = down_dec
                pmask = None
                if hetero:
                    pmask = tree_index(tc["payload_masks"], tier)
                    up = param_lib.apply_rank_mask(up, pmask)
                    ref = param_lib.apply_rank_mask(down_dec, pmask)
                    up_masks.append(pmask)
                if fault is not None:
                    # same per-client injection helpers the compiled
                    # engines vmap — identical inputs, bitwise-identical
                    # faulted uploads
                    sref = (self._stale_ref if self._stale_ref is not None
                            else down_dec)
                    if pmask is not None:
                        sref = param_lib.apply_rank_mask(sref, pmask)
                    up = faults_lib.poison_upload_one(
                        up, ref, sref,
                        jnp.float32(fault["nan"][i]),
                        jnp.float32(fault["poison"][i]),
                        jnp.float32(fault["byz"][i]),
                        jnp.float32(fault["stale"][i]))
                    if up_codec.is_identity:
                        new_ef = state.get("_ef_up")
                    else:
                        wire, new_ef = up_codec.encode(
                            up, ref=ref, ef=state.get("_ef_up"),
                            key=quant_keys[i])
                        wire = faults_lib.flip_wire_bits(
                            wire, jnp.float32(fault["flip"][i]),
                            jnp.asarray(fault["flip_keys"][i], jnp.uint32),
                            plan.flip_bits)
                        up = up_codec.decode(wire, ref=ref)
                else:
                    up, new_ef = up_codec.encode_decode(
                        up, ref=ref, ef=state.get("_ef_up"),
                        key=quant_keys[i])
                if new_ef is not None:
                    state = {**state, "_ef_up": new_ef}
                uploads.append(up)
                weights.append(float(len(self.partitions[cid])))
                up_pos.append(i)
            pend_states[cid] = state
            losses.append(m["loss"])

        # ---------------------------------------------------- aggregation
        valid = np.ones(len(sampled), np.float32)
        agg_state = None
        if uploads and scfg.personalization != "local":
            agg_target = (self.global_params if scfg.personalization == "none"
                          else self._download_payload(-1))
            if scfg.defense != "none":
                # same gate/clip primitives the batched program runs,
                # over the same statistics block (the arrived cohort)
                stacked = tree_stack(uploads)
                masks_st = tree_stack(up_masks) if hetero else None
                w = jnp.asarray(weights, jnp.float32)
                cand = jnp.ones(len(uploads), jnp.float32)
                dev = faults_lib.deviation_tree(stacked, down_dec, False)
                if hetero:
                    dev = param_lib.apply_rank_mask(dev, masks_st)
                norms, finite = faults_lib.upload_stats(dev)
                v = faults_lib.validity_gate(norms, finite, cand,
                                             scfg.defense_z)
                stacked = faults_lib.sanitize_stacked(stacked, v)
                w = w * v
                if scfg.defense == "clip":
                    s = faults_lib.clip_scales(norms, v, cand,
                                               scfg.defense_clip)
                    stacked = faults_lib.apply_clip_stacked(
                        stacked, down_dec, s)
                    if hetero:
                        stacked = param_lib.apply_rank_mask(stacked,
                                                            masks_st)
                valid[np.asarray(up_pos)] = np.asarray(v, np.float32)
                if hetero:
                    mean_w = tree_hetero_wmean_stacked(stacked, w, masks_st,
                                                       agg_target)
                else:
                    mean_w = tree_wmean_stacked(stacked, w)
                    wsum = w.sum()
                    # a fully-rejected round keeps the current global
                    # (zero accepted weight must not zero the model)
                    mean_w = jax.tree.map(
                        lambda mn, tgt: jnp.where(wsum > 0, mn,
                                                  tgt.astype(mn.dtype)),
                        mean_w, agg_target)
            elif hetero:
                mean_w = tree_hetero_wmean_stacked(
                    tree_stack(uploads), jnp.asarray(weights, jnp.float32),
                    tree_stack(up_masks), agg_target)
            else:
                mean_w = tree_mean(uploads, weights)
            new_global_part, new_server_state = self.strategy.server_update(
                self.server_state, agg_target, mean_w)
            agg_state = (new_global_part, new_server_state, agg_target)

        rd, ru = self._round_bytes(sampled, sel_mask, down_bytes, down_dec,
                                   up_mask=mask)
        mean_loss, nonfinite = _loss_stats(losses)

        def commit():
            self.client_states.update(pend_states)
            self.local_trees.update(pend_locals)
            if agg_state is not None:
                new_gp, new_ss, tgt = agg_state
                self.server_state = new_ss
                self._apply_aggregated(new_gp, tgt)
            self.comm_log.log_round(rd, ru)

        rec = {
            "participants": int(sel_mask.sum()),
            "sampled": len(sampled),
            "mean_loss": mean_loss,
            "nonfinite_losses": nonfinite,
            "down_bytes": rd,
            "up_bytes": ru,
            "lr": lr,
        }
        return rec, commit, valid

    def _prep_client_state(self, cid: int, params: Any, down_dec: Any,
                           tier: int = -1) -> Dict:
        """Round-start client state: stored state or strategy init, with
        the uplink EF accumulator (payload structure) attached and the
        SCAFFOLD server control variate broadcast in. Shared by all
        three engines. ``tier >= 0`` (heterogeneous mode) column-masks
        every payload/param-structured state tree to the client's tier
        rank, so masked factor columns see exactly-zero strategy signals
        and stay zero through local training."""
        state = self.client_states.get(cid)
        if state is None:
            state = init_client_state(self.strategy, params)
        if self.scfg.personalization != "local":
            state = self._ensure_ef(state, down_dec)
        if self.strategy.name == "scaffold" and "c" in state:
            c = (jax.tree.map(jnp.zeros_like, params)
                 if not self.server_state else self.server_state.get(
                     "c", jax.tree.map(jnp.zeros_like, params)))
            state = {**state, "c": c}
        if tier >= 0:
            tc = self._tier_cache
            fmask = tree_index(tc["full_masks"], tier)
            pmask = tree_index(tc["payload_masks"], tier)
            state = dict(state)
            for k in ("c", "c_i", "lambda_i"):
                if k in state:
                    state[k] = param_lib.apply_rank_mask(state[k], fmask)
            if "_ef_up" in state:
                state["_ef_up"] = param_lib.apply_rank_mask(
                    state["_ef_up"], pmask)
        return state

    # ------------------------------------------------- fleet arena
    def _ensure_arena(self):
        """Create the device-resident client arena on first use (its EF
        template needs the payload structure, which depends on the
        personalization mode — same laziness as ``_tier_cache``). Rows
        replicate the strategy-init state / global-init residents, so a
        never-sampled row equals what ``_prep_client_state`` would build
        at first participation."""
        if self.arena is not None or self.scfg.state_store != "arena":
            return
        from repro.fl.arena import ClientArena

        scfg = self.scfg
        tmpl = init_client_state(self.strategy, self.global_params)
        if scfg.personalization != "local" and self.uplink_codec.has_ef:
            tmpl = {**tmpl, "_ef_up": self.uplink_codec.ef_init(
                self._download_payload(-1))}
        mode = scfg.personalization
        if mode == "pfedpara":
            res = comm.split_pfedpara(self.global_params)[1]
        elif mode == "fedper":
            res = {k: v for k, v in self.global_params.items()
                   if k in FEDPER_LOCAL_KEYS}
        elif mode == "local":
            res = self.global_params
        else:
            res = None
        self.arena = ClientArena.create(scfg.clients, tmpl, res)
        self.arena.shard_rows(self._mesh, self._mesh_axis)

    def _stacked_state_fixups(self, state: Dict, n: int,
                              tiers: Optional[np.ndarray]) -> Dict:
        """Round-start fixups on arena-gathered stacked state — the
        vectorized mirror of ``_prep_client_state``: broadcast the
        SCAFFOLD server control variate into every row, column-mask
        state trees to each client's tier rank in heterogeneous mode."""
        if self.strategy.name == "scaffold" and "c" in state:
            c = (self.server_state or {}).get("c")
            if c is None:
                c = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype),
                                 state["c"])
            state = {**state, "c": tree_broadcast(c, n)}
        if tiers is not None:
            tc = self._tier_cache
            ti = jnp.asarray(tiers, jnp.int32)
            fmask = jax.tree.map(lambda m: jnp.take(m, ti, axis=0),
                                 tc["full_masks"])
            pmask = jax.tree.map(lambda m: jnp.take(m, ti, axis=0),
                                 tc["payload_masks"])
            state = dict(state)
            for k in ("c", "c_i", "lambda_i"):
                if k in state:
                    state[k] = param_lib.apply_rank_mask(state[k], fmask)
            if "_ef_up" in state:
                state["_ef_up"] = param_lib.apply_rank_mask(
                    state["_ef_up"], pmask)
        return state

    # ----------------------------------------------------- batch stacks
    def _device_dataset(self) -> Optional[DeviceDataset]:
        """The client dataset on the device, uploaded on first use
        (replicated over the mesh, if any) where it fits
        ``DeviceDataset.fits`` on the first device; ``None`` where it
        does not, and the host stacks each round's batches."""
        if self._device_data is None:
            device = (self._mesh.devices.flat[0] if self._mesh is not None
                      else jax.devices()[0])
            self._device_data = (DeviceDataset(self.data, self._mesh)
                                 if DeviceDataset.fits(self.data, device)
                                 else False)
        return self._device_data or None

    def _build_batches(self, cids, seeds, pad_clients=0):
        """The host's part of a round's ``(C + pad_clients, S, B, ...)``
        batch stack (``epoch_indices``). Returns ``(put, step_mask,
        gather, host_bytes)``: ``put()`` gives the stack on the device,
        gathered there from the resident dataset (``gather`` is
        ``"device"``, and the host built only the int32 index array) or
        copied from the stack the host built (``"host"``);
        ``host_bytes`` counts what the host built, the step mask and
        zero-row flag not counted."""
        ids, zero_rows, step_mask = epoch_indices(
            self.partitions, cids, self.ccfg.batch, self.ccfg.epochs,
            [int(s) for s in seeds], pad_clients=pad_clients)
        dev = self._device_dataset()
        if dev is not None:
            return (functools.partial(dev.gather, ids, zero_rows),
                    step_mask, "device", int(ids.nbytes))
        batches = gather_host(self.data, ids, zero_rows)
        return (functools.partial(jax.tree.map, jnp.asarray, batches),
                step_mask, "host", data_bytes(batches))

    def _train_tokens(self, step_mask, mask) -> int:
        """Target positions the round trained on, counted on the host
        from shapes: the real local steps (``step_mask``) of the clients
        whose uploads arrive (``mask``), times ``batch``, times the
        ``S`` of an ``(N, S + 1)`` ``tokens`` array; 0 for data that
        holds no token sequences."""
        tokens = self.data.get("tokens")
        if tokens is None or np.ndim(tokens) != 2:
            return 0
        arrived = np.asarray(mask) > 0
        steps = np.asarray(step_mask)[: len(arrived)][arrived].sum()
        return int(steps) * self.ccfg.batch * (np.shape(tokens)[1] - 1)

    # ------------------------------------------------ batched engine
    def _run_round_batched(self, sampled, mask, seeds, lr, down_dec,
                           down_bytes, sel_mask=None, fault=None):
        scfg = self.scfg
        if sel_mask is None:
            sel_mask = mask
        cids = [int(c) for c in sampled]
        C = len(cids)
        hetero = self.tiers is not None
        tc = self._tier_state(down_dec) if hetero else None
        tier_idx = self._cohort_tiers(cids) if hetero else None
        arena = scfg.state_store == "arena"

        with TraceAnnotation("fl.round.arena_gather"):
            if arena:
                # ONE vectorized gather for the whole cohort: state and
                # resident rows come off the device arena, params
                # assemble from the broadcast — no per-client Python loop
                self._ensure_arena()
                rows = self.arena.rows_for(cids)
                stacked_state, stacked_res = self.arena.gather(rows)
                stacked_state = self._stacked_state_fixups(stacked_state, C,
                                                           tier_idx)
                from repro.fl.batch_engine import assemble_client_params

                stacked_params = assemble_client_params(
                    down_dec, stacked_res, C, scfg.personalization,
                    FEDPER_LOCAL_KEYS)
                if hetero:
                    fmask = jax.tree.map(
                        lambda m: jnp.take(
                            m, jnp.asarray(tier_idx, jnp.int32), axis=0),
                        tc["full_masks"])
                    stacked_params = param_lib.apply_rank_mask(
                        stacked_params, fmask)
            else:
                full, states = [], []
                for pos, cid in enumerate(cids):
                    params = self._client_full_params(cid, down_dec)
                    tier = int(tier_idx[pos]) if hetero else -1
                    if hetero:
                        params = param_lib.apply_rank_mask(
                            params, tree_index(tc["full_masks"], tier))
                    full.append(params)
                    states.append(self._prep_client_state(
                        cid, params, down_dec, tier=tier))
                stacked_params = tree_stack(full)
                stacked_state = (tree_stack(states) if states and states[0]
                                 else {})

        with TraceAnnotation("fl.round.stack_batches"):
            put, step_mask, gather, batch_bytes = self._build_batches(
                cids, seeds)
        sizes = np.array([len(self.partitions[c]) for c in cids], np.float32)
        agg_target = (self.global_params if scfg.personalization == "none"
                      else self._download_payload(-1))
        with TraceAnnotation("fl.round.put_batches"):
            batches = put()

        with TraceAnnotation("fl.round.dispatch"):
            (new_p, new_state, upload, local, last_loss, n_steps, new_global,
             new_server_state, valid_dev) = self._engine.run(
                stacked_params, stacked_state, batches, step_mask,
                mask, sizes, lr, self._quant_keys(C),
                self.server_state, agg_target, down_dec,
                tier_idx=tier_idx,
                tier_masks=tc["payload_masks"] if hetero else None,
                fault=faults_lib.device_fault_args(fault),
                stale_ref=(None if fault is None else
                           (self._stale_ref if self._stale_ref is not None
                            else down_dec)))

        arrived = np.nonzero(mask)[0]
        with TraceAnnotation("fl.round.wait"):
            valid = np.asarray(valid_dev, np.float32)

        def commit():
            if arena:
                # ONE masked scatter writes the arrivals back;
                # non-arrived (and crashed) rows keep their previous
                # values bit-exactly
                self.arena.scatter(rows, new_state if new_state else {},
                                   local, mask)
            else:
                for pos in arrived:
                    cid = cids[pos]
                    if new_state:
                        self.client_states[cid] = tree_index(new_state, pos)
                    else:
                        self.client_states[cid] = {}
                    if local is not None:
                        self.local_trees[cid] = tree_index(local, pos)
            if upload is not None and scfg.personalization != "local":
                self.server_state = new_server_state
                self._apply_aggregated(new_global, agg_target)
            self.comm_log.log_round(rd, ru)

        losses = np.asarray(last_loss)[arrived]
        rd, ru = self._round_bytes(sampled, sel_mask, down_bytes, down_dec,
                                   up_mask=mask)
        mean_loss, nonfinite = _loss_stats(losses)

        rec = {
            "participants": int(sel_mask.sum()),
            "sampled": len(sampled),
            "mean_loss": mean_loss,
            "nonfinite_losses": nonfinite,
            "down_bytes": rd,
            "up_bytes": ru,
            "host_batch_bytes": batch_bytes,
            "batch_gather": gather,
            "train_tokens": self._train_tokens(step_mask, mask),
            "lr": lr,
        }
        return rec, commit, valid

    # ---------------------------------------------- streaming engine
    def _run_round_streaming(self, sampled, mask, seeds, lr, down_dec,
                             down_bytes, sel_mask=None, fault=None):
        """Chunked round: identical selection/bookkeeping contract as the
        batched engine, but clients are fed to the jitted scan program
        ``client_chunk`` at a time and the aggregate is a streamed fp32
        accumulator — no (C, model) tree is ever stacked."""
        from repro.data.loader import ChunkBatchSource, client_step_count
        from repro.fl.stream_engine import chunk_layout, from_chunks, to_chunks

        scfg = self.scfg
        if sel_mask is None:
            sel_mask = mask
        mode = scfg.personalization
        cids = [int(c) for c in sampled]
        C = len(cids)
        chunk, n_chunks, pad = chunk_layout(C, scfg.client_chunk)
        cids_pad = cids + cids[:1] * pad   # pad slots reuse client 0's
        # (small) state/resident trees; their batches are zeros below
        # (arena mode maps pad slots to the scratch row instead)
        hetero = self.tiers is not None
        tc = self._tier_state(down_dec) if hetero else None
        tier_pad = self._cohort_tiers(cids_pad) if hetero else None
        arena = scfg.state_store == "arena"

        with TraceAnnotation("fl.round.arena_gather"):
            if arena:
                # ONE vectorized cohort gather off the device arena (pad
                # slots address the scratch row); params assemble inside
                # the scan step from the broadcast + gathered residents
                self._ensure_arena()
                rows = self.arena.rows_for(cids, pad=pad)
                stacked_state, stacked_res = self.arena.gather(rows)
                stacked_state = self._stacked_state_fixups(
                    stacked_state, C + pad, tier_pad)
            else:
                states, residents = [], []
                for pos, cid in enumerate(cids_pad):
                    params = self._client_full_params(cid, down_dec)
                    states.append(self._prep_client_state(
                        cid, params, down_dec,
                        tier=int(tier_pad[pos]) if hetero else -1))
                    if mode == "pfedpara":
                        residents.append(comm.split_pfedpara(params)[1])
                    elif mode == "fedper":
                        residents.append({k: params[k]
                                          for k in FEDPER_LOCAL_KEYS
                                          if k in params})
                    elif mode == "local":
                        residents.append(params)
                stacked_state = (tree_stack(states) if states and states[0]
                                 else {})
                stacked_res = tree_stack(residents) if residents else None

        with TraceAnnotation("fl.round.stack_batches"):
            data_source = put = None
            if scfg.data_stream == "chunked":
                # lazy per-chunk data: the scan step's host callback
                # materializes one chunk's batches at a time — the
                # cohort's (C, S, B, ...) stack never exists on the host.
                # One round-wide step axis, as the eager stack has, so
                # every chunk shares a compiled program
                S = max(client_step_count(len(self.partitions[c]),
                                          self.ccfg.batch, self.ccfg.epochs)
                        for c in cids)
                data_source = ChunkBatchSource(
                    self.data, self.partitions, cids, self.ccfg.batch,
                    self.ccfg.epochs, [int(s) for s in seeds],
                    chunk=chunk, n_chunks=n_chunks, pad_steps=max(S, 1))
                step_mask = data_source.step_mask()
                batch_bytes = data_source.nbytes
                gather = "host"
            else:
                # pad slots are zero rows of the one stack (zero
                # batches, fully masked) — never concatenated in
                put, step_mask, gather, batch_bytes = self._build_batches(
                    cids, seeds, pad_clients=pad)
        with TraceAnnotation("fl.round.put_batches"):
            batches_xs = (None if put is None
                          else to_chunks(put(), n_chunks, chunk))
        mask_pad = np.zeros(C + pad, np.float32)
        mask_pad[:C] = mask
        sizes_pad = np.zeros(C + pad, np.float32)
        sizes_pad[:C] = [len(self.partitions[c]) for c in cids]
        agg_target = (self.global_params if mode == "none"
                      else self._download_payload(-1))

        fault_xs = None
        stale_ref = None
        if fault is not None:
            # pad slots are drawn-clean (byz scale 1, everything else 0)
            # so the injection math inside the scan is a no-op for them
            def _pad1(a, fill, dtype):
                out = np.full((C + pad,) + np.shape(a)[1:], fill, dtype)
                out[:C] = a
                return out
            fault_pad = {
                "nan": _pad1(fault["nan"], 0.0, np.float32),
                "poison": _pad1(fault["poison"], 0.0, np.float32),
                "byz": _pad1(fault["byz"], 1.0, np.float32),
                "stale": _pad1(fault["stale"], 0.0, np.float32),
                "flip": _pad1(fault["flip"], 0.0, np.float32),
                "flip_keys": _pad1(fault["flip_keys"], 0, np.uint32),
            }
            fault_xs = jax.tree.map(
                lambda a: to_chunks(a, n_chunks, chunk),
                faults_lib.device_fault_args(fault_pad))
            stale_ref = (self._stale_ref if self._stale_ref is not None
                         else down_dec)

        with TraceAnnotation("fl.round.dispatch"):
            (state_ys, local_ys, loss_ys, _steps, new_global,
             new_server_state, valid_ys) = self._stream.run(
                to_chunks(stacked_state, n_chunks, chunk),
                to_chunks(stacked_res, n_chunks, chunk)
                if stacked_res is not None else None,
                batches_xs,
                to_chunks(jnp.asarray(step_mask, jnp.float32), n_chunks,
                          chunk),
                to_chunks(jnp.asarray(mask_pad), n_chunks, chunk),
                to_chunks(jnp.asarray(sizes_pad), n_chunks, chunk),
                to_chunks(self._quant_keys(C + pad), n_chunks, chunk),
                lr, self.server_state, agg_target, down_dec,
                tier_xs=(to_chunks(jnp.asarray(tier_pad), n_chunks, chunk)
                         if hetero else None),
                tier_payload_masks=tc["payload_masks"] if hetero else None,
                tier_full_masks=tc["full_masks"] if hetero else None,
                data_source=data_source,
                fault_xs=fault_xs, stale_ref=stale_ref)

        new_state = from_chunks(state_ys) if state_ys else {}
        local = from_chunks(local_ys) if local_ys is not None else None
        arrived = np.nonzero(mask)[0]
        with TraceAnnotation("fl.round.wait"):
            valid = np.asarray(from_chunks(valid_ys), np.float32)[:C]

        def commit():
            if arena:
                # ONE masked scatter: arrivals land in their rows, the
                # pad slots all write the scratch row's unchanged value
                self.arena.scatter(rows, new_state, local, mask_pad)
            else:
                for pos in arrived:
                    cid = cids[pos]
                    self.client_states[cid] = (
                        tree_index(new_state, int(pos)) if new_state else {})
                    if local is not None:
                        self.local_trees[cid] = tree_index(local, int(pos))
            if mode != "local":
                self.server_state = new_server_state
                self._apply_aggregated(new_global, agg_target)
            self.comm_log.log_round(rd, ru)

        losses = np.asarray(from_chunks(loss_ys))[arrived]
        mean_loss, nonfinite = _loss_stats(losses)
        rd, ru = self._round_bytes(sampled, sel_mask, down_bytes, down_dec,
                                   up_mask=mask)

        rec = {
            "participants": int(sel_mask.sum()),
            "sampled": len(sampled),
            "chunks": n_chunks,
            "client_chunk": chunk,
            "mean_loss": mean_loss,
            "nonfinite_losses": nonfinite,
            "down_bytes": rd,
            "up_bytes": ru,
            "host_batch_bytes": batch_bytes,
            "batch_gather": gather,
            "train_tokens": self._train_tokens(step_mask, mask),
            "lr": lr,
        }
        return rec, commit, valid

    # ------------------------------------------------- async event loop
    def _ensure_async(self):
        """Lazily create the async event-loop state (docs/async.md)."""
        if self._async is None:
            from repro.fl.async_engine import AsyncState

            n_tiers = len(self.tiers.gammas) if self.tiers is not None else 1
            self._async = AsyncState(self.scfg.clients, n_tiers=n_tiers)

    def client_versions(self) -> np.ndarray:
        """(clients,) pinned broadcast version per client (-1 = never
        dispatched): the version whose decoded broadcast the client's
        current state (EF accumulator, strategy state, residents) was
        produced against. Arena mode reads the device-resident row;
        dict mode the host-side pinning map."""
        if self.arena is not None:
            return self.arena.client_versions()
        out = np.full(self.scfg.clients, -1, np.int64)
        for c, v in self._client_versions.items():
            out[int(c)] = int(v)
        return out

    def _async_dispatch(self) -> int:
        """One broadcast + training dispatch at the current version:
        sample a cohort (same host RNG / trace draws as the sync
        engines, salted by the dispatch index within the version),
        exclude clients still in flight, encode ONE downlink, run the
        jitted :class:`repro.fl.async_engine.AsyncDispatch` program,
        commit the trained state immediately (dispatch-atomic: the
        client HAS trained — only its upload is in flight), pin the
        cohort's broadcast version, and enqueue one arrival event per
        admitted client at ``clock + latency``. Returns the number of
        events enqueued (0 = nothing admitted / everyone crashed)."""
        from repro.fl import async_engine as async_lib
        from repro.fl.stream_engine import chunk_layout, from_chunks, to_chunks

        scfg = self.scfg
        st = self._async
        plan = scfg.faults
        mode = scfg.personalization
        attempt = st.n_dispatches
        st.n_dispatches += 1
        sampled, mask, seeds, _lr, probe, lat = self._select_round(attempt)
        # an in-flight client keeps training against its pinned version;
        # it is only re-admissible once its upload lands (or is dropped)
        mask = mask & ~st.in_flight[np.asarray(sampled, np.int64)]
        if not mask.any():
            return 0
        if st.window is None:
            # the version's first ADMITTING dispatch is its participation
            # record (the parity analogue of a sync round's sampled/mask)
            st.window = {"sampled": [int(c) for c in sampled],
                         "mask": [int(v) for v in mask.astype(int)]}
        version = self.round_idx
        did = st.total_dispatches
        st.total_dispatches += 1
        down_dec, down_bytes = self._encode_downlink(probe)
        fault = (plan.draw(version, len(sampled), attempt)
                 if plan is not None else None)
        # a crashed client trained and vanished: downlink is charged,
        # no state writeback, and NO arrival event is ever enqueued
        eff = fold_crashes(mask,
                           fault["crash"] if fault is not None else None)

        cids = [int(c) for c in sampled]
        C = len(cids)
        chunk, n_chunks, pad = chunk_layout(C, scfg.client_chunk)
        cids_pad = cids + cids[:1] * pad
        hetero = self.tiers is not None
        tc = self._tier_state(down_dec) if hetero else None
        tier_pad = self._cohort_tiers(cids_pad) if hetero else None
        arena = scfg.state_store == "arena"

        if arena:
            self._ensure_arena()
            rows = self.arena.rows_for(cids, pad=pad)
            stacked_state, stacked_res = self.arena.gather(rows)
            stacked_state = self._stacked_state_fixups(
                stacked_state, C + pad, tier_pad)
        else:
            states, residents = [], []
            for pos, cid in enumerate(cids_pad):
                params = self._client_full_params(cid, down_dec)
                states.append(self._prep_client_state(
                    cid, params, down_dec,
                    tier=int(tier_pad[pos]) if hetero else -1))
                if mode == "pfedpara":
                    residents.append(comm.split_pfedpara(params)[1])
                elif mode == "fedper":
                    residents.append({k: params[k] for k in FEDPER_LOCAL_KEYS
                                      if k in params})
                elif mode == "local":
                    residents.append(params)
            stacked_state = tree_stack(states) if states and states[0] else {}
            stacked_res = tree_stack(residents) if residents else None

        put, step_mask, _, _ = self._build_batches(cids, seeds,
                                                   pad_clients=pad)
        batches_xs = to_chunks(put(), n_chunks, chunk)
        eff_pad = np.zeros(C + pad, np.float32)
        eff_pad[:C] = eff
        sizes = np.asarray([len(self.partitions[c]) for c in cids],
                           np.float32)
        sizes_pad = np.zeros(C + pad, np.float32)
        sizes_pad[:C] = sizes

        fault_xs = None
        stale_ref = None
        if fault is not None:
            def _pad1(a, fill, dtype):
                out = np.full((C + pad,) + np.shape(a)[1:], fill, dtype)
                out[:C] = a
                return out
            fault_pad = {
                "nan": _pad1(fault["nan"], 0.0, np.float32),
                "poison": _pad1(fault["poison"], 0.0, np.float32),
                "byz": _pad1(fault["byz"], 1.0, np.float32),
                "stale": _pad1(fault["stale"], 0.0, np.float32),
                "flip": _pad1(fault["flip"], 0.0, np.float32),
                "flip_keys": _pad1(fault["flip_keys"], 0, np.uint32),
            }
            fault_xs = jax.tree.map(
                lambda a: to_chunks(a, n_chunks, chunk),
                faults_lib.device_fault_args(fault_pad))
            stale_ref = (self._stale_ref if self._stale_ref is not None
                         else down_dec)

        lr = self.ccfg.lr * (scfg.lr_decay ** version)
        (state_ys, local_ys, loss_ys, _steps, valid_ys, clip_ys,
         upload_ys) = self._adispatch.run(
            to_chunks(stacked_state, n_chunks, chunk),
            to_chunks(stacked_res, n_chunks, chunk)
            if stacked_res is not None else None,
            batches_xs,
            to_chunks(jnp.asarray(step_mask, jnp.float32), n_chunks, chunk),
            to_chunks(jnp.asarray(eff_pad), n_chunks, chunk),
            to_chunks(jnp.asarray(sizes_pad), n_chunks, chunk),
            to_chunks(self._quant_keys(C + pad), n_chunks, chunk),
            lr, down_dec,
            tier_xs=(to_chunks(jnp.asarray(tier_pad), n_chunks, chunk)
                     if hetero else None),
            tier_payload_masks=tc["payload_masks"] if hetero else None,
            tier_full_masks=tc["full_masks"] if hetero else None,
            fault_xs=fault_xs, stale_ref=stale_ref)

        new_state = from_chunks(state_ys) if state_ys else {}
        local = from_chunks(local_ys) if local_ys is not None else None

        # dispatch-atomic writeback: trained state/EF/residents commit
        # now, pinned to this version — the upload is what stays in
        # flight. A crashed client keeps its PREVIOUS row/pin.
        if arena:
            self.arena.scatter(rows, new_state, local, eff_pad)
            self.arena.pin_versions(rows, version, eff_pad)
        else:
            for pos in np.nonzero(eff)[0]:
                cid = cids[int(pos)]
                self.client_states[cid] = (
                    tree_index(new_state, int(pos)) if new_state else {})
                if local is not None:
                    self.local_trees[cid] = tree_index(local, int(pos))
                self._client_versions[cid] = version

        losses = np.asarray(from_chunks(loss_ys), np.float64)
        valid = np.asarray(from_chunks(valid_ys), np.float32)
        clips = np.asarray(from_chunks(clip_ys), np.float32)

        if mode != "local" and upload_ys is not None:
            st.wires[did] = from_chunks(upload_ys)
            st.refs[did] = down_dec
            if st.accs is None:
                st.accs = [jax.tree.map(
                    lambda x: jnp.zeros(jnp.shape(x), jnp.float32),
                    down_dec) for _ in range(st.n_tiers)]

        # downlink charged at dispatch time, uplink at each arrival
        rd, _ = self._round_bytes(sampled, mask, down_bytes, down_dec,
                                  up_mask=np.zeros(len(sampled), bool))
        st.down_bytes += int(rd)
        cohort_tiers = tier_pad[:C] if hetero else None
        if mode == "local":
            up_cost = np.zeros(C, np.int64)
        elif hetero:
            up_cost = np.asarray(tc["up_bytes"], np.int64)[cohort_tiers]
        else:
            up_cost = np.full(C, int(self.uplink_codec.wire_bytes(down_dec)),
                              np.int64)

        n_events = 0
        for t_abs, pos in arrival_events(eff, lat, t0=st.clock):
            ev = async_lib.ArrivalEvent(
                t=float(t_abs), seq=st.seq, cid=cids[pos], version=version,
                did=did, pos=int(pos),
                tier=int(cohort_tiers[pos]) if hetero else 0,
                weight=float(sizes[pos]), valid=float(valid[pos]),
                clip=float(clips[pos]), loss=float(losses[pos]),
                up_cost=int(up_cost[pos]))
            st.pending[ev.seq] = ev
            heapq.heappush(st.events, (ev.t, ev.seq))
            st.in_flight[ev.cid] = True
            st.seq += 1
            n_events += 1
        if mode != "local" and upload_ys is not None:
            if n_events:
                st.wire_left[did] = n_events
            else:
                # every admitted client crashed: nothing will ever
                # consume this dispatch's wires or pin its ref
                st.wires.pop(did, None)
                st.refs.pop(did, None)
        # the NEXT dispatch's stale-replay faults re-upload THIS broadcast
        self._stale_ref = down_dec
        return n_events

    def _async_step(self) -> bool:
        """Consume the earliest arrival: advance the virtual clock,
        charge its uplink bytes, record its staleness, and — unless it
        is past ``max_staleness`` — fold its wire row into the
        accumulator with weight ``s(tau) * n_samples * valid * clip``.
        Returns True iff the arrival counted toward the buffer."""
        from repro.fl import async_engine as async_lib

        scfg = self.scfg
        st = self._async
        t, seq = heapq.heappop(st.events)
        ev = st.pending.pop(seq)
        st.clock = max(st.clock, float(t))
        st.in_flight[ev.cid] = False
        tau = self.round_idx - ev.version
        st.up_bytes += int(ev.up_cost)
        st.stale_hist[tau] = st.stale_hist.get(tau, 0) + 1
        folded = False
        if scfg.max_staleness >= 0 and tau > scfg.max_staleness:
            st.dropped_stale += 1
        elif scfg.personalization == "local":
            # no uploads to aggregate: the arrival only paces the loop
            st.losses.append(ev.loss)
            st.buffer += 1
            folded = True
        else:
            s = float(self._staleness_fn(tau))
            base = s * ev.weight * ev.valid
            wf = base * ev.clip
            st.accs[ev.tier] = async_lib.fold_arrival(
                st.accs[ev.tier], st.wires[ev.did], ev.pos, wf)
            st.wtot[ev.tier] += base
            if self.uplink_codec.has_delta:
                # delta wires decode as linear + ref: the pinned
                # broadcast re-attaches at finalize with this weight
                st.refw[ev.tier][ev.did] = (
                    st.refw[ev.tier].get(ev.did, 0.0) + base)
            elif scfg.defense == "clip":
                # clipped non-delta upload: the clipped-away remainder
                # is (1-clip) of the client's pinned broadcast
                st.refw[ev.tier][ev.did] = (
                    st.refw[ev.tier].get(ev.did, 0.0)
                    + base * (1.0 - ev.clip))
            st.losses.append(ev.loss)
            st.buffer += 1
            folded = True
        st.release_wire(ev.did)
        return folded

    def _async_flush(self) -> Dict:
        """Buffer threshold reached: finalize the staleness-weighted
        mean, apply the strategy's server update, bump the global
        version, record the version's history row (staleness histogram
        + exact per-version wire bytes), and reset the buffer. Pending
        arrivals survive — they fold into future buffers at tau >= 1."""
        from repro.fl import async_engine as async_lib

        scfg = self.scfg
        st = self._async
        mode = scfg.personalization
        version = self.round_idx
        if mode != "local" and st.buffer > 0:
            agg_target = (self.global_params if mode == "none"
                          else self._download_payload(-1))
            hetero = self.tiers is not None
            mean = async_lib.finalize_buffer(
                st.accs, st.wtot, st.refw, st.refs,
                codec=self.uplink_codec, agg_target=agg_target,
                tier_payload_masks=(
                    self._tier_state(self._download_payload(-1))
                    ["payload_masks"] if hetero else None),
                defense=scfg.defense)
            new_global, self.server_state = self.strategy.server_update(
                self.server_state, agg_target, mean)
            self._apply_aggregated(new_global, agg_target)
        self.comm_log.log_round(st.down_bytes, st.up_bytes)
        mean_loss, nonfinite = _loss_stats(st.losses)
        window = st.window or {}
        rec = {
            "participants": int(np.sum(window.get("mask", [0]))),
            "mean_loss": mean_loss,
            "nonfinite_losses": nonfinite,
            "down_bytes": int(st.down_bytes),
            "up_bytes": int(st.up_bytes),
            "lr": float(self.ccfg.lr * (scfg.lr_decay ** version)),
            "version": int(version),
            "folded": int(st.buffer),
            "dispatches": int(st.n_dispatches),
            "virtual_time": float(st.clock),
            "round_latency": float(st.clock - st.flush_t0),
            "staleness_hist": {str(k): int(v)
                               for k, v in sorted(st.stale_hist.items())},
            "dropped_stale": int(st.dropped_stale),
            "in_flight": int(len(st.pending)),
        }
        rec["comm_gb"] = self.comm_log.total_gb
        st.flush_t0 = float(st.clock)
        self.round_idx += 1
        rec["round"] = self.round_idx
        rec["arrived_mask"] = [int(v) for v in window.get("mask", [])]
        rec["sampled"] = [int(c) for c in window.get("sampled", [])]
        if self.eval_fn is not None:
            rec["eval"] = self.eval_fn(self.global_params)
        self.history.append(rec)
        st.reset_buffer(None if mode == "local"
                        else self._download_payload(-1))
        st.prune_refs()
        return rec

    def _run_async_round(self) -> Dict:
        """One async 'round' = one buffer window: dispatch at the
        current version (re-admission broadcast), drain arrivals until
        ``buffer_k`` of them folded (dispatching fresh cohorts whenever
        the queue runs dry first), then flush. ``buffer_k=0`` defaults
        K to the sync participation target, which is what makes the
        instant-arrival regime a bitwise parity reference."""
        scfg = self.scfg
        self._ensure_async()
        st = self._async
        K = int(scfg.buffer_k) or max(
            1, int(round(scfg.participation * scfg.clients)))
        self._async_dispatch()
        dry = 0
        while st.buffer < K:
            if st.events:
                self._async_step()
                continue
            admitted = self._async_dispatch()
            if admitted == 0:
                dry += 1
                if not st.events:
                    break        # arrival stream exhausted: partial flush
                if dry >= 16:
                    break        # admission starved: flush what we have
            else:
                dry = 0
        if st.buffer == 0 and st.dropped_stale == 0 and st.window is None:
            # nothing admitted, nothing arrived: skip the round
            # (mirrors the sync engines' everyone-failed skip)
            st.n_dispatches = 0
            self.round_idx += 1
            return {"round": self.round_idx, "participants": 0,
                    "skipped": True}
        return self._async_flush()

    # --------------------------------------------------- crash / resume
    def _checkpoint_tree(self) -> Dict:
        """Every array-valued piece of server state, as one dict tree
        (client dicts keyed by stringified cid — the checkpoint's
        "/"-joined paths restore them without a target structure)."""
        tree: Dict[str, Any] = {"global_params": self.global_params,
                                "server_state": self.server_state}
        if self._down_ref is not None:
            tree["down_ref"] = self._down_ref
        if self._down_ef is not None:
            tree["down_ef"] = self._down_ef
        if self._stale_ref is not None:
            tree["stale_ref"] = self._stale_ref
        if self.client_states:
            tree["client_states"] = {str(c): s for c, s
                                     in self.client_states.items()}
        if self.local_trees:
            tree["local_trees"] = {str(c): t for c, t
                                   in self.local_trees.items()}
        if self.arena is not None:
            ar = {"state": self.arena.state,
                  "participation": self.arena.participation,
                  "versions": self.arena.versions}
            if self.arena.residents is not None:
                ar["residents"] = self.arena.residents
            tree["arena"] = ar
        if self._async is not None:
            # mid-buffer async state: the accumulator, every live
            # dispatch's stacked wires and pinned broadcast ref — the
            # array half of a bitwise event-loop resume (host half in
            # save_checkpoint's extra)
            st = self._async
            az: Dict[str, Any] = {}
            if st.accs is not None:
                az["acc"] = {str(t): a for t, a in enumerate(st.accs)}
            if st.wires:
                az["wires"] = {str(d): w for d, w in st.wires.items()}
            if st.refs:
                az["refs"] = {str(d): r for d, r in st.refs.items()}
            if az:
                tree["async"] = az
        return tree

    def save_checkpoint(self, manager) -> str:
        """Checkpoint the COMPLETE server state at a round boundary
        (arrays + host bookkeeping: round index, legacy RNG stream,
        wire-byte totals, history). A restore from the written step is
        bitwise: continuing reproduces an uninterrupted run exactly."""
        st = self.rng.get_state()
        extra = {
            "round_idx": int(self.round_idx),
            "rng": [st[0], [int(v) for v in st[1]], int(st[2]),
                    int(st[3]), float(st[4])],
            "comm": [int(self.comm_log.down_bytes),
                     int(self.comm_log.up_bytes),
                     int(self.comm_log.rounds)],
            "history": _to_plain(self.history),
        }
        if self._async is not None:
            ast = self._async
            extra["async"] = {
                "clock": float(ast.clock),
                "flush_t0": float(ast.flush_t0),
                "seq": int(ast.seq),
                "buffer": int(ast.buffer),
                "total_dispatches": int(ast.total_dispatches),
                "n_dispatches": int(ast.n_dispatches),
                "wtot": [float(w) for w in ast.wtot],
                "refw": [{str(d): float(w) for d, w in rw.items()}
                         for rw in ast.refw],
                "events": [ast.pending[seq].as_list()
                           for _, seq in sorted(ast.events)],
                "up_bytes": int(ast.up_bytes),
                "down_bytes": int(ast.down_bytes),
                "stale_hist": {str(k): int(v)
                               for k, v in ast.stale_hist.items()},
                "dropped_stale": int(ast.dropped_stale),
                "losses": [float(v) for v in ast.losses],
                "window": _to_plain(ast.window),
            }
        if self._client_versions:
            extra["client_versions"] = {str(c): int(v) for c, v
                                        in self._client_versions.items()}
        return manager.save(self.round_idx, self._checkpoint_tree(),
                            extra=extra)

    def restore_checkpoint(self, manager, step: Optional[int] = None) -> int:
        """Restore from ``manager`` (latest step by default) and return
        the restored round index. Structure-free: the checkpoint's
        "/"-joined paths rebuild the nested dict trees, so per-client
        state dicts restore without knowing which clients ever
        participated. Continuing the run reproduces the uninterrupted
        history bitwise (see docs/robustness.md)."""
        by_path, extra, step = manager.restore_items(step)
        root: Dict[str, Any] = {}
        for path, arr in by_path.items():
            parts = path.split("/")
            node = root
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(arr)
        self.global_params = root["global_params"]
        self.server_state = root.get("server_state", {})
        self._down_ref = root.get("down_ref")
        self._down_ef = root.get("down_ef")
        self._stale_ref = root.get("stale_ref")
        self.client_states = {int(c): s for c, s
                              in root.get("client_states", {}).items()}
        self.local_trees = {int(c): t for c, t
                            in root.get("local_trees", {}).items()}
        ar = root.get("arena")
        if ar is not None:
            self._ensure_arena()
            # fedavg-without-EF arenas have an EMPTY state dict — only
            # the sections that produced leaves exist in the checkpoint
            if "state" in ar:
                self.arena.state = ar["state"]
            self.arena.participation = ar["participation"]
            if "versions" in ar:
                self.arena.versions = ar["versions"]
            if "residents" in ar:
                self.arena.residents = ar["residents"]
        self.round_idx = int(extra["round_idx"])
        self._client_versions = {int(c): int(v) for c, v
                                 in extra.get("client_versions",
                                              {}).items()}
        ext_async = extra.get("async")
        if ext_async is not None:
            from repro.fl.async_engine import ArrivalEvent

            self._async = None
            self._ensure_async()
            ast = self._async
            ast.clock = float(ext_async["clock"])
            ast.flush_t0 = float(ext_async["flush_t0"])
            ast.seq = int(ext_async["seq"])
            ast.buffer = int(ext_async["buffer"])
            ast.total_dispatches = int(ext_async["total_dispatches"])
            ast.n_dispatches = int(ext_async["n_dispatches"])
            ast.wtot = [float(w) for w in ext_async["wtot"]]
            ast.refw = [{int(d): float(w) for d, w in rw.items()}
                        for rw in ext_async["refw"]]
            ast.up_bytes = int(ext_async["up_bytes"])
            ast.down_bytes = int(ext_async["down_bytes"])
            ast.stale_hist = {int(k): int(v) for k, v
                              in ext_async["stale_hist"].items()}
            ast.dropped_stale = int(ext_async["dropped_stale"])
            ast.losses = [float(v) for v in ext_async["losses"]]
            ast.window = ext_async["window"]
            evs = [ArrivalEvent.from_list(r) for r in ext_async["events"]]
            ast.pending = {ev.seq: ev for ev in evs}
            ast.events = [(ev.t, ev.seq) for ev in evs]
            heapq.heapify(ast.events)
            # in_flight and the wire refcounts are derived, not stored
            ast.in_flight = np.zeros(self.scfg.clients, bool)
            ast.wire_left = {}
            for ev in evs:
                ast.in_flight[ev.cid] = True
                ast.wire_left[ev.did] = ast.wire_left.get(ev.did, 0) + 1
            az = root.get("async", {})
            acc = az.get("acc")
            if acc is not None:
                ast.accs = [acc[str(t)] for t in range(ast.n_tiers)]
            ast.wires = {int(d): w for d, w in az.get("wires", {}).items()}
            ast.refs = {int(d): r for d, r in az.get("refs", {}).items()}
        r = extra["rng"]
        self.rng.set_state((r[0], np.asarray(r[1], np.uint32), int(r[2]),
                            int(r[3]), float(r[4])))
        (self.comm_log.down_bytes, self.comm_log.up_bytes,
         self.comm_log.rounds) = (int(v) for v in extra["comm"])
        self.history = list(extra["history"])
        return step

    def run(self, rounds: Optional[int] = None, log_every: int = 0,
            ckpt: Optional[Any] = None, ckpt_every: int = 1) -> List[Dict]:
        """Run ``rounds`` federated rounds (default:
        ``ServerConfig.rounds``) and return the full ``history`` list.

        With ``ckpt`` (a :class:`repro.checkpoint.CheckpointManager`),
        ``rounds`` is the TOTAL round target: a server restored via
        :meth:`restore_checkpoint` runs only the remaining rounds, and
        the full state checkpoints every ``ckpt_every`` completed
        rounds (plus at the end)."""
        target = rounds or self.scfg.rounds
        if ckpt is None:
            for r in range(target):
                rec = self.run_round()
                if log_every and (r % log_every == 0):
                    print(rec)
            return self.history
        while self.round_idx < target:
            rec = self.run_round()
            if log_every and ((self.round_idx - 1) % log_every == 0):
                print(rec)
            if (self.round_idx % ckpt_every == 0
                    or self.round_idx >= target):
                self.save_checkpoint(ckpt)
        ckpt.wait()
        return self.history

    # --------------------------------------------- personalization eval
    def personalized_eval(self, eval_fn: Optional[Callable] = None,
                          batch_eval_fn: Optional[Callable] = None) -> List[float]:
        """Evaluate each client's merged (global + resident local) model.

        ``eval_fn(params, cid)`` runs the sequential per-client sweep.
        ``batch_eval_fn(stacked_params, cids)`` replaces the sweep with
        one batched call over all clients' stacked params (see
        ``repro.fl.batch_engine.batched_personalized_eval``)."""
        if batch_eval_fn is not None:
            full = [self._client_full_params(cid, self._download_payload(cid))
                    for cid in range(self.scfg.clients)]
            scores = batch_eval_fn(tree_stack(full),
                                   np.arange(self.scfg.clients))
            return [float(s) for s in np.asarray(scores)]
        scores = []
        for cid in range(self.scfg.clients):
            params = self._client_full_params(cid, self._download_payload(cid))
            scores.append(float(eval_fn(params, cid)))
        return scores
