"""Streaming chunked FL rounds: O(chunk) client memory per round.

The batched engine (``repro.fl.batch_engine``) stacks every sampled
client's params, optimizer state and local batches into one ``(C, ...)``
tree — round memory grows linearly with participation, so simulated
cohorts die at a few hundred clients per host. This engine turns the
participation axis from a MEMORY axis into a TIME axis:

  1. ``jax.lax.scan`` over fixed-size client **chunks**
     (``ServerConfig.client_chunk``). Each scan step reuses the batched
     engine's chunk program (``chunk_round_program``: scan over local
     steps, vmap over the chunk's clients, payload selection, per-client
     uplink encoding) on ``chunk`` clients at a time, so the training
     working set — activations, per-step grads, the chunk's
     params/opt-state — peaks at O(chunk · model), never O(C · model).
  2. The carry threads a running **weighted-sum accumulator** plus a
     weight total instead of stacking uploads: uploads stay in the
     codec's encoded-for-aggregation form (``Codec.encode_for_agg`` —
     int8 ``{"q", "scale"}`` nodes, fp16/dense linear carriers) and are
     folded straight into an fp32 accumulator by the fused
     dequant-accumulate Pallas kernel (``repro.kernels.agg``): each
     wire byte is read once at its wire itemsize; the dense
     ``(C, model)`` fp32 upload stack of the batched engine never
     exists.
  3. Client params are ASSEMBLED inside each scan step from the round's
     single decoded broadcast (plus per-client personalization
     residents where the mode has them), so the program's inputs carry
     no ``(C, model)`` params tree at all — for vanilla FL the xs are
     just data batches, masks and RNG keys.
  4. The jitted round program donates the chunk-stacked state / batch
     buffers (``donate_argnums``), so XLA updates chunk params and
     opt-state in place instead of double-buffering them.
  5. On a ``("clients",)`` shard_map mesh the chunk's clients split
     across devices and aggregation goes two-level: each device reduces
     its shard with the fused kernel (partial sums), one ``psum``
     combines the fp32 partials (``sharded_tree_dequant_acc``).

Numerical contract: identical round selections (bitwise-equal arrival
masks — both engines derive them from ``FLServer._select_round``) give
global params, client states and residents matching the batched engine
to fp32 accumulation-order tolerance, for every personalization mode
and every codec (error-feedback accumulators thread through the chunk
state exactly as in the batched engine; the delta reference is a
constant the mean absorbs, added back by ``Codec.agg_finalize``).
Aggregation itself is chunk-size invariant: chunking only reassociates
the fp32 weighted sum.

Heterogeneous rank tiers (``ServerConfig.gamma_tiers``, docs/hetero.md)
keep the fused kernel's scalar per-client coefficients by running ONE
accumulator per tier: within a tier every client shares a column mask,
so the per-column weighting factors out of the contraction and is
applied once at finalize (num = Σ_t M_t ⊙ acc_t, den = Σ_t M_t·wtot_t,
uncovered columns fall back to the current global). Round memory gains
an O(T · model) term, T = number of tiers, and each chunk's wire tiles
are re-read once per tier (T× the homogeneous kernel's single-pass wire
traffic — the coefficients differ per tier, the data does not; a
multi-row-coefficient kernel variant would restore the single read).

This module is also the async engine's substrate
(``repro.fl.async_engine``, docs/async.md): ``AsyncDispatch`` is this
chunk-scan program with the aggregation carry removed (training +
encoding at dispatch time, the encoded wires returned as ys), and the
async server folds each wire row into the SAME fp32 accumulator via
the same fused kernel — at arrival time instead of inside the scan.
The finalize math here (per-tier num/den, agg_finalize ref add) is the
single-version special case of the async engine's version-pinned
``finalize_buffer``; keep the two in lockstep when changing either.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.parameterization import apply_rank_mask
from repro.fl import faults as faults_lib
from repro.fl.batch_engine import assemble_client_params, chunk_round_program
from repro.fl.client import ClientConfig
from repro.fl.codecs import Codec, make_codec
from repro.fl.strategies import Strategy
from repro.kernels import agg as agg_kernels


@dataclass
class StreamingRound:
    """The jitted streaming round program, configured once per server.

    ``run`` consumes chunk-stacked xs trees with leading
    ``(n_chunks, chunk, ...)`` axes and executes the whole round —
    local epochs, payload selection, uplink encoding, fused encoded-form
    aggregation, strategy server update — as one XLA program whose live
    set scales with ``chunk``. Recompiles only when the
    (n_chunks, chunk, S, B) shape signature changes.
    """

    loss_fn: Callable
    strategy: Strategy
    client_cfg: ClientConfig
    personalization: str = "none"
    uplink_codec: Optional[Codec] = None
    fedper_local_keys: Tuple[str, ...] = ()
    chunk: int = 16
    mesh: Optional[Mesh] = None
    mesh_axis: str = "clients"
    use_pallas_agg: bool = True
    # upload defenses (repro.fl.faults): "none" | "clip". The gate's
    # statistics block is the scan CHUNK (the cohort is never resident
    # here); "trimmed" needs every upload resident along the client
    # axis, so it is statically rejected — see docs/robustness.md.
    defense: str = "none"
    defense_z: float = 3.0
    defense_clip: float = 1.0
    flip_bits: int = 4

    def __post_init__(self):
        if self.defense not in ("none", "clip"):
            raise ValueError(
                f"streaming engine supports defense 'none' | 'clip', got "
                f"{self.defense!r} (coordinate-wise trimming needs all "
                "uploads resident along the client axis — use the batched "
                "engine; see docs/robustness.md)")
        if self.uplink_codec is None:
            self.uplink_codec = make_codec("")
        # The chunk-stacked client state and personalization residents
        # (positions 0-1) have the same shapes as the scan's ys, so
        # donating them lets XLA write each chunk's updated params /
        # opt-state over the incoming buffers instead of
        # double-buffering them. Batches are pure inputs (no
        # matching-shape output) — donating them would only warn.
        self._program = jax.jit(self._round_program,
                                donate_argnums=(0, 1))
        self._data_source = None      # lazy per-chunk batch provider
        self._data_shapes = None      # its pure_callback result struct
        # the last round's placed chunk inputs, as read back from the
        # arrays: name -> ShapeDtypeStruct carrying its sharding
        self.placement = {}

    # --------------------------------------------------- param assembly
    def _assemble(self, resident_chunk, down_payload, chunk: int):
        """Chunk params from the broadcast + per-client residents: the
        (chunk, model) tree exists only inside the scan step. ``chunk``
        is the actual chunk width (small cohorts clamp it below the
        configured size)."""
        return assemble_client_params(down_payload, resident_chunk, chunk,
                                      self.personalization,
                                      self.fedper_local_keys)

    def _fetch_chunk(self, chunk_idx):
        """Host callback: materialize one chunk's batches from the lazy
        source (``jax.pure_callback`` target — stable identity, so the
        jitted program is traced once per shape signature)."""
        return self._data_source.fetch(int(np.asarray(chunk_idx)))

    def chunk_sharding(self, chunk: int) -> Optional[NamedSharding]:
        """How the chunk-stacked ``(n_chunks, chunk, ...)`` inputs are
        placed: each chunk's clients split over the mesh axis, or
        ``None`` without a mesh or when ``chunk`` does not divide over
        it (the program then folds on one device, single-level)."""
        mesh, axis = self.mesh, self.mesh_axis
        if (mesh is None or axis not in mesh.axis_names
                or chunk % mesh.shape[axis]):
            return None
        return NamedSharding(mesh, P(None, axis))

    # ------------------------------------------------------- the program
    def _round_program(self, state_xs, resident_xs, batches_xs, step_mask_xs,
                       mask_xs, sizes_xs, quant_keys_xs, lr, server_state,
                       agg_target, down_payload, tier_xs, tier_payload_masks,
                       tier_full_masks, fault_xs=None, stale_ref=None):
        codec = self.uplink_codec
        mode = self.personalization
        mesh, axis = self.mesh, self.mesh_axis
        chunk = step_mask_xs.shape[1]   # actual width (≤ configured)
        two_level = self.chunk_sharding(chunk) is not None
        hetero = tier_payload_masks is not None
        n_tiers = (jax.tree.leaves(tier_payload_masks)[0].shape[0]
                   if hetero else 1)
        # clipping a non-delta codec re-centers each upload on the
        # broadcast: the fold keeps w·s as its weight and the leftover
        # w·(1-s)·broadcast rides in one scalar slack term per tier,
        # added back at finalize — the aggregate stays LINEAR, which is
        # the whole reason 'clip' streams and 'trimmed' cannot
        clip_slack = self.defense == "clip" and not codec.has_delta

        def chunk_step(carry, xs):
            if clip_slack:
                accs, wtots, slacks = carry
            else:
                accs, wtots = carry
                slacks = None
            (state_c, resident_c, batches_c, smask_c, mask_c, sizes_c,
             keys_c, tier_c, fault_c, chunk_i) = xs
            if batches_c is None:
                # lazy data: the chunk's batches materialize host-side
                # inside the scan step — the cohort-wide (C, S, B, ...)
                # stack never exists anywhere
                batches_c = jax.pure_callback(
                    self._fetch_chunk, self._data_shapes, chunk_i)
            params_c = self._assemble(resident_c, down_payload, chunk)
            col_masks = None
            if hetero:
                # mask assembled params to each client's tier slice (the
                # broadcast carries only the leading tier-rank columns)
                full_m = jax.tree.map(
                    lambda m: jnp.take(m, tier_c, axis=0), tier_full_masks)
                params_c = apply_rank_mask(params_c, full_m)
                col_masks = jax.tree.map(
                    lambda m: jnp.take(m, tier_c, axis=0),
                    tier_payload_masks)
            new_p, new_state, upload, local, last_loss, n_steps = \
                chunk_round_program(
                    params_c, state_c, batches_c, smask_c, keys_c,
                    down_payload,
                    loss_fn=self.loss_fn, client_cfg=self.client_cfg,
                    strategy_name=self.strategy.name, personalization=mode,
                    fedper_local_keys=self.fedper_local_keys,
                    uplink_codec=codec, lr=lr, mesh=mesh, axis=axis,
                    encoded_upload=True, col_masks=col_masks,
                    fault=fault_c, stale_ref=stale_ref,
                    flip_bits=self.flip_bits)
            valid_c = jnp.ones_like(mask_c)
            clip_s = None
            if upload is not None:
                w = mask_c * sizes_c
                if self.defense != "none":
                    # chunk-block screening on the linear-decoded wire:
                    # rejected clients fold in with zero WEIGHT and a
                    # sanitized (zeroed) wire so 0 * NaN never reaches
                    # the fp32 accumulator
                    lin = jax.vmap(
                        lambda u: faults_lib.linear_decode(codec, u))(upload)
                    dev = faults_lib.deviation_tree(lin, down_payload,
                                                    codec.has_delta)
                    if hetero:
                        dev = apply_rank_mask(dev, col_masks)
                    cand = (mask_c > 0).astype(jnp.float32)
                    norms, finite = faults_lib.upload_stats(dev)
                    valid_c = faults_lib.validity_gate(norms, finite, cand,
                                                       self.defense_z)
                    upload = faults_lib.sanitize_stacked(upload, valid_c)
                    w = w * valid_c
                    if self.defense == "clip":
                        clip_s = faults_lib.clip_scales(norms, valid_c,
                                                        cand,
                                                        self.defense_clip)
                # one fused accumulator per tier: within a tier every
                # client shares the same column mask, so the per-column
                # weighting factors out of the kernel contraction as
                # mask_t * (Σ_{c∈t} w_c · deq(wire_c))
                new_accs, new_wtots = [], []
                new_slacks = [] if clip_slack else None
                for t in range(n_tiers):
                    wt = (w * (tier_c == t).astype(w.dtype)) if hetero else w
                    # the per-client clip scale is scalar, so it folds
                    # straight into the kernel's fold weight
                    wf = wt * clip_s if clip_s is not None else wt
                    if two_level:
                        part = agg_kernels.sharded_tree_dequant_acc(
                            upload, wf, mesh, axis,
                            use_pallas=self.use_pallas_agg)
                        new_accs.append(jax.tree.map(jnp.add, accs[t], part))
                    else:
                        new_accs.append(agg_kernels.tree_dequant_acc(
                            accs[t], upload, wf,
                            use_pallas=self.use_pallas_agg))
                    new_wtots.append(wtots[t] + wt.sum())
                    if clip_slack:
                        new_slacks.append(
                            slacks[t] + (wt * (1.0 - clip_s)).sum())
                accs, wtots = tuple(new_accs), tuple(new_wtots)
                if clip_slack:
                    slacks = tuple(new_slacks)
            del new_p  # reassembled from the broadcast next round
            out_carry = ((accs, wtots, slacks) if clip_slack
                         else (accs, wtots))
            return out_carry, (new_state, local, last_loss, n_steps, valid_c)

        acc0 = tuple(
            jax.tree.map(lambda x: jnp.zeros(jnp.shape(x), jnp.float32),
                         down_payload) for _ in range(n_tiers))
        wtot0 = tuple(jnp.zeros((), jnp.float32) for _ in range(n_tiers))
        n_chunks = step_mask_xs.shape[0]
        xs = (state_xs, resident_xs, batches_xs, step_mask_xs, mask_xs,
              sizes_xs, quant_keys_xs, tier_xs, fault_xs,
              jnp.arange(n_chunks, dtype=jnp.int32))
        carry0 = ((acc0, wtot0,
                   tuple(jnp.zeros((), jnp.float32) for _ in range(n_tiers)))
                  if clip_slack else (acc0, wtot0))
        (carry_out,
         (state_ys, local_ys, loss_ys, steps_ys, valid_ys)) = jax.lax.scan(
            chunk_step, carry0, xs)
        if clip_slack:
            accs, wtots, slacks = carry_out
            # the clipped-away broadcast remainder: Σ_c w_c (1 - s_c)
            # per tier, re-attached as slack_t · broadcast so the mean
            # equals Σ w (down + s·(u - down)) / Σ w exactly as in the
            # dense engines (delta codecs need none — the reference is
            # outside the fold entirely)
            accs = tuple(
                jax.tree.map(
                    lambda a, d: a + slacks[t] * d.astype(jnp.float32),
                    accs[t], down_payload)
                for t in range(n_tiers))
        else:
            accs, wtots = carry_out

        if mode != "local":
            if hetero:
                masks_t = [jax.tree.map(lambda m: m[t], tier_payload_masks)
                           for t in range(n_tiers)]
                num = functools.reduce(
                    lambda a, b: jax.tree.map(jnp.add, a, b),
                    [jax.tree.map(lambda m, a: m * a, masks_t[t], accs[t])
                     for t in range(n_tiers)])
                den = functools.reduce(
                    lambda a, b: jax.tree.map(jnp.add, a, b),
                    [jax.tree.map(lambda m: m * wtots[t], masks_t[t])
                     for t in range(n_tiers)])
                mean = jax.tree.map(
                    lambda nm, d: nm / jnp.maximum(d, 1e-12), num, den)
                mean = codec.agg_finalize(mean, ref=down_payload)
                # columns no arrived client covers keep the global value
                mean = jax.tree.map(
                    lambda d, mn, tgt: jnp.where(d > 0, mn,
                                                 tgt.astype(mn.dtype)),
                    den, mean, agg_target)
            else:
                acc, wtot = accs[0], wtots[0]
                mean = jax.tree.map(lambda a: a / jnp.maximum(wtot, 1e-12),
                                    acc)
                mean = codec.agg_finalize(mean, ref=down_payload)
                if self.defense != "none":
                    # a fully-rejected round keeps the current global
                    # (zero accepted weight must not zero the model)
                    mean = jax.tree.map(
                        lambda mn, tgt: jnp.where(wtot > 0, mn,
                                                  tgt.astype(mn.dtype)),
                        mean, agg_target)
            new_global, new_server_state = self.strategy.server_update(
                server_state, agg_target, mean)
        else:
            new_global, new_server_state = agg_target, server_state
        return (state_ys, local_ys, loss_ys, steps_ys, new_global,
                new_server_state, valid_ys)

    def run(self, state_xs, resident_xs, batches_xs, step_mask_xs, mask_xs,
            sizes_xs, quant_keys_xs, lr, server_state, agg_target,
            down_payload, tier_xs=None, tier_payload_masks=None,
            tier_full_masks=None, data_source=None, fault_xs=None,
            stale_ref=None):
        """Execute one streaming round. The ``tier_*`` arguments switch
        on heterogeneous-rank mode: ``tier_xs`` is the chunked
        ``(n_chunks, chunk)`` int tier index, ``tier_payload_masks`` /
        ``tier_full_masks`` are ``(T, ...)``-leading rank-mask trees
        over the payload / full-param structures. All ``None`` (the
        default) runs the homogeneous single-accumulator program.

        ``data_source`` (a ``repro.data.loader.ChunkBatchSource``)
        switches on lazy per-chunk data: pass ``batches_xs=None`` and
        each scan step fetches its own chunk's batches through a host
        callback — the cohort-wide batch stack is never materialized,
        host data memory stays O(chunk).

        ``fault_xs`` (chaos injection): the per-client arrays of
        :func:`repro.fl.faults.device_fault_args` chunked to leading
        ``(n_chunks, chunk)`` axes; ``stale_ref`` is the previous
        decoded broadcast for stale-replay faults."""
        if data_source is not None:
            if batches_xs is not None:
                raise ValueError(
                    "pass batches_xs=None when a data_source is given")
            self._data_source = data_source
            self._data_shapes = data_source.chunk_struct()
        sharding = self.chunk_sharding(np.shape(step_mask_xs)[1])

        def place(tree, dtype=None):
            # on a mesh the chunk's client axis lands split over it, as
            # the program's shard_map reads it
            def one(x):
                x = x if dtype is None else x.astype(dtype)
                return (jnp.asarray(x) if sharding is None
                        else jax.device_put(x, sharding))

            return None if tree is None else jax.tree.map(one, tree)

        batches = place(batches_xs)
        step_mask = place(step_mask_xs, jnp.float32)
        mask = place(mask_xs, jnp.float32)
        placed = {"step_mask": step_mask, "mask": mask}
        if batches is not None:
            placed["batches"] = jax.tree.leaves(batches)[0]
        self.placement = {
            name: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            for name, x in placed.items()}
        return self._program(
            place(state_xs), place(resident_xs), batches, step_mask, mask,
            place(sizes_xs, jnp.float32),
            place(quant_keys_xs), jnp.asarray(lr, jnp.float32),
            server_state, agg_target, down_payload,
            place(tier_xs, jnp.int32),
            tier_payload_masks, tier_full_masks, place(fault_xs), stale_ref)


def chunk_layout(n_clients: int, chunk: int) -> Tuple[int, int, int]:
    """(chunk, n_chunks, pad): clients padded to a whole number of
    fixed-size chunks; pad entries ride along fully masked."""
    chunk = max(1, min(int(chunk), n_clients))
    n_chunks = -(-n_clients // chunk)
    return chunk, n_chunks, n_chunks * chunk - n_clients


def to_chunks(tree: Any, n_chunks: int, chunk: int) -> Any:
    """Reshape (C_pad, ...) stacked leaves to (n_chunks, chunk, ...)."""
    return jax.tree.map(
        lambda x: x.reshape((n_chunks, chunk) + x.shape[1:]), tree)


def from_chunks(tree: Any) -> Any:
    """Inverse of ``to_chunks``: flatten the two leading axes."""
    return jax.tree.map(
        lambda x: x.reshape((-1,) + x.shape[2:]), tree)
