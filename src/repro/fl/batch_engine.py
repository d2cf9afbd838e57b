"""Client-batched FL round engine: vmap/shard_map over a client axis.

The sequential reference in ``repro.fl.server`` runs each sampled
client's local epochs in a Python loop — round wall-clock scales
linearly with participation and every local step pays a dispatch.
This engine stacks the sampled clients' params / optimizer / strategy
state along a leading **client axis** and runs the whole round as ONE
jit-compiled program:

  1. ``lax.scan`` over local steps (per client), with a float step mask
     turning padded steps into no-ops (params carried through
     unchanged), so clients with different local-step counts share one
     fixed-shape program;
  2. ``jax.vmap`` over the client axis (single host), or
     ``shard_map`` over a named mesh axis (multi-device) with the vmap
     applied to each device's client shard;
  3. payload selection (none / pfedpara / fedper / local) as pure tree
     restructuring on the stacked tree;
  4. per-client uplink codec encode/decode (``repro.fl.codecs``: delta
     vs the round's decoded broadcast, top-k with client-stacked
     error-feedback accumulators riding in ``stacked_state["_ef_up"]``,
     low-rank delta truncation, int8/fp16 quantization) vmapped over
     the client axis with per-client RNG keys;
  5. masked weighted tree-reduce over the client axis (the
     arrived-mask replaces the sequential engine's ``arrived`` list)
     followed by the strategy's ``server_update``.

Numerical contract: with the same round selection (mask, seeds, keys)
the engine matches the sequential reference to fp32 tolerance; the
aggregation mask itself is bitwise identical because both engines
derive it from the same host-side RNG draws (``FLServer._select_round``).

Pallas interplay: when the model's loss runs the fused differentiable
fedpara_matmul (``ParamCfg(use_pallas=True)``), the client-axis
``jax.vmap`` here batches its custom-VJP forward/backward Pallas calls
through Pallas' batching rule — the mapped client axis folds into a
leading grid dimension, so each layer's compose+matmul (and each of its
three backward kernels) is ONE kernel launch for the whole client
batch, not C sequential launches.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.parameterization import apply_rank_mask
from repro.fl import comm
from repro.fl import faults as faults_lib
from repro.fl.codecs import Codec, make_codec
from repro.fl.client import ClientConfig, _step_math, strategy_post
from repro.fl.strategies import (
    Strategy, tree_hetero_wmean_stacked, tree_trimmed_wmean_stacked,
    tree_wmean_stacked, tree_zeros)


def _tree_where(cond, a, b):
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def batched_local_update(
    stacked_params: Any,
    stacked_state: Dict,
    batches: Dict[str, jax.Array],
    step_mask: jax.Array,
    loss_fn: Callable,
    cfg: ClientConfig,
    strategy_name: str,
    lr,
    mesh: Optional[Mesh] = None,
    axis: str = "clients",
):
    """Run every stacked client's local epochs at once.

    ``stacked_params`` / ``stacked_state`` leaves are ``(C, ...)``;
    ``batches`` leaves are ``(C, S, B, ...)``; ``step_mask`` is
    ``(C, S)`` float32. Returns ``(new_params, new_state, last_loss,
    n_steps)`` all stacked along the client axis. A masked step feeds a
    padding batch through the exact same step math and then discards
    the result, so real steps are bit-identical to the unmasked case.
    """

    def one_client(params0, state, cbatches, cmask):
        mu0 = tree_zeros(params0)

        def step(carry, xs):
            p, mu, last = carry
            b, m = xs
            new_p, new_mu, loss = _step_math(
                p, mu, b, params0, state, loss_fn, strategy_name,
                lr, cfg.momentum, cfg.weight_decay)
            on = m > 0
            p = _tree_where(on, new_p, p)
            mu = _tree_where(on, new_mu, mu)
            last = jnp.where(on, loss.astype(jnp.float32), last)
            return (p, mu, last), None

        (p, _, last), _ = jax.lax.scan(
            step, (params0, mu0, jnp.zeros((), jnp.float32)),
            (cbatches, cmask))
        n = cmask.sum()
        state = strategy_post(strategy_name, state, params0, p, n, lr)
        return p, state, last, n

    f = jax.vmap(one_client)
    if mesh is not None and axis in mesh.axis_names:
        C = step_mask.shape[0]
        ndev = mesh.shape[axis]
        pad = -C % ndev
        spec = P(axis)
        f_sharded = jax.shard_map(
            jax.vmap(one_client), mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, spec, spec),
            check_vma=False)
        if pad == 0:
            f = f_sharded
        else:
            # Keep the shard_map path for any C: pad the client batch
            # with masked dummies (client 0 replicated, step_mask 0 so
            # every padded step is a discarded no-op) and slice the
            # results back. The pad rows go through the identical step
            # math, so real clients stay bit-identical to the unpadded
            # run.
            def pad_tree(t):
                return jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])], 0),
                    t)

            def f(sp, ss, bt, sm):
                sm_p = jnp.concatenate(
                    [sm, jnp.zeros((pad,) + sm.shape[1:], sm.dtype)], 0)
                outs = f_sharded(pad_tree(sp), pad_tree(ss), pad_tree(bt),
                                 sm_p)
                return jax.tree.map(lambda x: x[:C], outs)
    return f(stacked_params, stacked_state, batches, step_mask)


def batched_personalized_eval(stacked_params: Any, eval_data: Dict,
                              metric_fn: Callable) -> jax.Array:
    """Batched replacement for the per-client eval sweep: vmap
    ``metric_fn(params, batch) -> scalar`` over the client axis.
    ``eval_data`` leaves are ``(C, n, ...)`` per-client eval batches."""
    return jax.vmap(metric_fn)(stacked_params, eval_data)


def assemble_client_params(down_payload: Any, residents: Any, n: int,
                           personalization: str,
                           fedper_local_keys: Tuple[str, ...] = ()):
    """Stacked ``(n, model)`` client params from the round's single
    decoded broadcast plus client-stacked personalization residents —
    the inverse of :func:`select_upload`, vectorized over the client
    axis. Shared by the streaming scan step (chunk assembly) and the
    arena server path (cohort assembly from gathered resident rows);
    with ``personalization="none"`` it is a pure broadcast and the
    residents argument is ignored."""
    from repro.fl.strategies import tree_broadcast

    if personalization == "none":
        return tree_broadcast(down_payload, n)
    if personalization == "pfedpara":
        return comm.merge_pfedpara(tree_broadcast(down_payload, n),
                                   residents)
    if personalization == "fedper":
        merged = dict(tree_broadcast(down_payload, n))
        merged.update(residents)
        return merged
    # "local": residents are the full per-client params
    return residents


def select_upload(stacked_params: Any, personalization: str,
                  fedper_local_keys: Tuple[str, ...] = ()):
    """(upload, local) stacked trees per personalization mode."""
    if personalization == "pfedpara":
        return comm.split_pfedpara(stacked_params)
    if personalization == "fedper":
        up = {k: v for k, v in stacked_params.items()
              if k not in fedper_local_keys}
        loc = {k: v for k, v in stacked_params.items()
               if k in fedper_local_keys}
        return up, loc
    if personalization == "local":
        return None, stacked_params
    return stacked_params, None


def chunk_round_program(
    stacked_params: Any,
    stacked_state: Dict,
    batches: Dict[str, jax.Array],
    step_mask: jax.Array,
    quant_keys: jax.Array,
    down_payload: Any,
    *,
    loss_fn: Callable,
    client_cfg: ClientConfig,
    strategy_name: str,
    personalization: str,
    fedper_local_keys: Tuple[str, ...],
    uplink_codec: Codec,
    lr,
    mesh: Optional[Mesh] = None,
    axis: str = "clients",
    encoded_upload: bool = False,
    col_masks: Any = None,
    fault: Any = None,
    stale_ref: Any = None,
    flip_bits: int = 4,
):
    """One chunk of clients: local epochs, payload selection, per-client
    uplink encoding. The shared core of the batched engine's round
    program (chunk = the whole sampled cohort) and of every streaming
    scan step (chunk = ``ServerConfig.client_chunk`` clients).

    With ``encoded_upload=False`` uploads come back DECODED (the batched
    engine weighted-means them densely). With ``encoded_upload=True``
    uploads stay in the codec's encoded-for-aggregation form
    (``Codec.encode_for_agg``: int8 ``{"q", "scale"}`` nodes / dense
    linear carriers, delta offset left to the aggregator) so the
    streaming accumulator can fold them in with the fused
    dequant-accumulate kernel without ever materializing the dense
    stack.

    ``col_masks`` (heterogeneous rank tiers): a client-stacked
    payload-structure tree of broadcastable 0/1 rank masks. When given,
    each client's upload is column-masked to its tier rank BEFORE the
    codec sees it, and the codec's delta reference becomes the
    equally-masked broadcast — exactly what a client that only ever
    received the leading tier-rank factor columns would transmit. With
    ``col_masks=None`` the homogeneous path below is byte-identical to
    before.

    ``fault`` (chaos injection, see ``repro.fl.faults``): the traced
    per-client arrays of :func:`repro.fl.faults.device_fault_args`.
    Stale-replay / byzantine-scaling / NaN-poison corruption hits the
    payload BEFORE the codec (exactly what a faulty client would
    transmit); bit-flips hit the ENCODED int8 wire between encode and
    decode. ``stale_ref`` is the server's previous decoded broadcast
    (the model a stale client replays). With ``fault=None`` the clean
    path below is byte-identical to before.

    Returns ``(new_params, new_state, upload, local, last_loss,
    n_steps)``, all stacked along the chunk's client axis.
    """
    new_p, new_state, last_loss, n_steps = batched_local_update(
        stacked_params, stacked_state, batches, step_mask,
        loss_fn, client_cfg, strategy_name, lr, mesh=mesh, axis=axis)

    upload, local = select_upload(new_p, personalization, fedper_local_keys)
    codec = uplink_codec
    if upload is not None and col_masks is not None:
        # tier-sliced uplink: zero columns stand in for absent ones
        # (they carry zero aggregation WEIGHT downstream, not zero value)
        upload = apply_rank_mask(upload, col_masks)
    if upload is not None and fault is not None:
        # pre-codec corruption: stale replay / byzantine deviation
        # scaling / NaN poisoning, per client (the wire then carries the
        # corrupted factors exactly as a faulty client would send them)
        sref = down_payload if stale_ref is None else stale_ref

        def poison_one(u, nan_on, pv, byz, st, m=None):
            r, s = down_payload, sref
            if m is not None:
                r = apply_rank_mask(r, m)
                s = apply_rank_mask(s, m)
            return faults_lib.poison_upload_one(u, r, s, nan_on, pv, byz, st)

        if col_masks is not None:
            upload = jax.vmap(poison_one)(
                upload, fault["nan"], fault["poison"], fault["byz"],
                fault["stale"], col_masks)
        else:
            upload = jax.vmap(
                lambda u, a, p, b, s: poison_one(u, a, p, b, s)
            )(upload, fault["nan"], fault["poison"], fault["byz"],
              fault["stale"])
    if upload is not None and not codec.is_identity:
        # per-client encode: delta against the round's decoded broadcast
        # (closure => broadcast under vmap), error feedback threaded
        # through the stacked client state
        if fault is None:
            enc = (codec.encode_for_agg if encoded_upload
                   else codec.encode_decode)
            if col_masks is not None:
                def enc_masked(u, m, e, k):
                    return enc(u, ref=apply_rank_mask(down_payload, m),
                               ef=e, key=k)

                if codec.has_ef:
                    upload, new_ef = jax.vmap(enc_masked)(
                        upload, col_masks, new_state["_ef_up"], quant_keys)
                    new_state = {**new_state, "_ef_up": new_ef}
                else:
                    upload, _ = jax.vmap(
                        lambda u, m, k: enc_masked(u, m, None, k)
                    )(upload, col_masks, quant_keys)
            elif codec.has_ef:
                upload, new_ef = jax.vmap(
                    lambda u, e, k: enc(u, ref=down_payload, ef=e, key=k)
                )(upload, new_state["_ef_up"], quant_keys)
                new_state = {**new_state, "_ef_up": new_ef}
            else:
                upload, _ = jax.vmap(
                    lambda u, k: enc(u, ref=down_payload, key=k)
                )(upload, quant_keys)
        else:
            # faulted path: the round trip is opened up so wire bit-flips
            # land on the ENCODED int8 payload, then the usual decode /
            # agg-form recovery runs on the corrupted wire. EF state is
            # taken from encode (client-side, before the wire corrupts).
            def enc_faulted(u, ref, e, k, fl, fk):
                wire, new_e = codec.encode(u, ref=ref, ef=e, key=k)
                wire = faults_lib.flip_wire_bits(wire, fl, fk, flip_bits)
                if encoded_upload:
                    if not codec.agg_linear:
                        wire = faults_lib.linear_decode(codec, wire)
                    return wire, new_e
                return codec.decode(wire, ref=ref), new_e

            fl, fk = fault["flip"], fault["flip_keys"]
            if col_masks is not None:
                def enc_fm(u, m, e, k, fl_, fk_):
                    return enc_faulted(u, apply_rank_mask(down_payload, m),
                                       e, k, fl_, fk_)

                if codec.has_ef:
                    upload, new_ef = jax.vmap(enc_fm)(
                        upload, col_masks, new_state["_ef_up"], quant_keys,
                        fl, fk)
                    new_state = {**new_state, "_ef_up": new_ef}
                else:
                    upload, _ = jax.vmap(
                        lambda u, m, k, fl_, fk_:
                            enc_fm(u, m, None, k, fl_, fk_)
                    )(upload, col_masks, quant_keys, fl, fk)
            elif codec.has_ef:
                upload, new_ef = jax.vmap(
                    lambda u, e, k, fl_, fk_:
                        enc_faulted(u, down_payload, e, k, fl_, fk_)
                )(upload, new_state["_ef_up"], quant_keys, fl, fk)
                new_state = {**new_state, "_ef_up": new_ef}
            else:
                upload, _ = jax.vmap(
                    lambda u, k, fl_, fk_:
                        enc_faulted(u, down_payload, None, k, fl_, fk_)
                )(upload, quant_keys, fl, fk)
    return new_p, new_state, upload, local, last_loss, n_steps


@dataclass
class ClientBatch:
    """The jit-compiled round program, configured once per server.

    ``run`` executes local updates, payload selection, per-client
    quantization, masked aggregation, and the strategy server update as
    a single XLA program. Recompiles only when the (C, S, B) shape
    signature changes.
    """

    loss_fn: Callable
    strategy: Strategy
    client_cfg: ClientConfig
    personalization: str = "none"
    uplink_codec: Optional[Codec] = None
    fedper_local_keys: Tuple[str, ...] = ()
    mesh: Optional[Mesh] = None
    mesh_axis: str = "clients"
    # upload defenses (repro.fl.faults): "none" | "clip" | "trimmed";
    # all static => baked into the one compiled program, no per-round
    # recompiles when fault draws change
    defense: str = "none"
    defense_z: float = 3.0
    defense_clip: float = 1.0
    defense_trim: float = 0.1
    flip_bits: int = 4

    def __post_init__(self):
        if self.uplink_codec is None:
            self.uplink_codec = make_codec("")
        self._program = jax.jit(self._round_program)

    # ------------------------------------------------------- the program
    def _round_program(self, stacked_params, stacked_state, batches,
                       step_mask, arrived_mask, sizes, lr, quant_keys,
                       server_state, agg_target, down_payload,
                       tier_idx, tier_masks, fault=None, stale_ref=None):
        col_masks = None
        if tier_masks is not None:
            # per-client rank masks gathered from the (T, ...) tier table
            col_masks = jax.tree.map(
                lambda m: jnp.take(m, tier_idx, axis=0), tier_masks)
        new_p, new_state, upload, local, last_loss, n_steps = \
            chunk_round_program(
                stacked_params, stacked_state, batches, step_mask,
                quant_keys, down_payload,
                loss_fn=self.loss_fn, client_cfg=self.client_cfg,
                strategy_name=self.strategy.name,
                personalization=self.personalization,
                fedper_local_keys=self.fedper_local_keys,
                uplink_codec=self.uplink_codec, lr=lr,
                mesh=self.mesh, axis=self.mesh_axis,
                col_masks=col_masks, fault=fault, stale_ref=stale_ref,
                flip_bits=self.flip_bits)

        valid = jnp.ones_like(arrived_mask)
        if upload is not None:
            w = arrived_mask * sizes
            if self.defense != "none":
                # compiled upload screening: finite + per-layer norm
                # z-score vs the cohort; rejected clients fold into the
                # arrival weighting as zero WEIGHT with sanitized (zero)
                # values so 0 * NaN never reaches the fp32 accumulators
                cand = (arrived_mask > 0).astype(jnp.float32)
                dev = faults_lib.deviation_tree(upload, down_payload, False)
                if col_masks is not None:
                    dev = apply_rank_mask(dev, col_masks)
                norms, finite = faults_lib.upload_stats(dev)
                valid = faults_lib.validity_gate(norms, finite, cand,
                                                 self.defense_z)
                upload = faults_lib.sanitize_stacked(upload, valid)
                w = w * valid
                if self.defense == "clip":
                    s = faults_lib.clip_scales(norms, valid, cand,
                                               self.defense_clip)
                    upload = faults_lib.apply_clip_stacked(
                        upload, down_payload, s)
                    if col_masks is not None:
                        # the clip re-centers on the full broadcast;
                        # re-mask so tier-absent columns stay zero-valued
                        upload = apply_rank_mask(upload, col_masks)
            if self.defense == "trimmed":
                # coordinate-wise trimmed mean: needs all uploads
                # resident along the client axis (batched engine only)
                mean_w = tree_trimmed_wmean_stacked(
                    upload, w, col_masks, agg_target, self.defense_trim)
            elif col_masks is not None:
                # per-column arrival weighting: a column only averages
                # over clients whose tier covers it; columns nobody
                # covers keep the current global value (agg_target)
                mean_w = tree_hetero_wmean_stacked(upload, w, col_masks,
                                                   agg_target)
            else:
                mean_w = tree_wmean_stacked(upload, w)
                if self.defense != "none":
                    # a fully-rejected round keeps the current global
                    # (zero accepted weight must not zero the model)
                    wsum = w.sum()
                    mean_w = jax.tree.map(
                        lambda mn, tgt: jnp.where(wsum > 0, mn,
                                                  tgt.astype(mn.dtype)),
                        mean_w, agg_target)
            new_global, new_server_state = self.strategy.server_update(
                server_state, agg_target, mean_w)
        else:
            new_global, new_server_state = agg_target, server_state
        return (new_p, new_state, upload, local, last_loss, n_steps,
                new_global, new_server_state, valid)

    def run(self, stacked_params, stacked_state, batches, step_mask,
            arrived_mask, sizes, lr, quant_keys, server_state, agg_target,
            down_payload, tier_idx=None, tier_masks=None, fault=None,
            stale_ref=None):
        """Execute one round. ``tier_idx`` (``(C,)`` int) and
        ``tier_masks`` (``(T, ...)``-leading payload-structure mask tree)
        switch on heterogeneous-rank aggregation; both ``None`` (the
        default) runs the homogeneous program unchanged. ``fault`` is a
        :func:`repro.fl.faults.device_fault_args` dict (or ``None``) and
        ``stale_ref`` the previous decoded broadcast for stale-replay
        injection."""
        return self._program(
            stacked_params, stacked_state,
            jax.tree.map(jnp.asarray, batches), jnp.asarray(step_mask),
            jnp.asarray(arrived_mask, jnp.float32),
            jnp.asarray(sizes, jnp.float32),
            jnp.asarray(lr, jnp.float32), quant_keys,
            server_state, agg_target, down_payload,
            None if tier_idx is None else jnp.asarray(tier_idx, jnp.int32),
            tier_masks, fault, stale_ref)
