"""Batch loaders: local epochs for FL clients + sharded global batches
for the pod trainer (deterministic, resumable — the checkpoint stores
the stream position so restarts continue mid-epoch).

A round's client batch stack is described by index first
(:func:`epoch_indices`), then gathered on the device from a resident
copy of the dataset (:class:`DeviceDataset`) or on the host
(:func:`gather_host`), bit for bit alike."""
from __future__ import annotations

import functools
import threading
import queue as queue_mod
from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _epoch_rng(seed: int) -> np.random.RandomState:
    """Shuffle RNG for one client's local epochs. Seeds below 2^32 keep
    the historical ``RandomState(seed)`` stream bit-exactly; the wider
    64-bit seeds the fleet path derives via ``SeedSequence.spawn``
    (``repro.fl.trace.spawn_seeds``) are folded through a SeedSequence
    into a full 128-bit ``RandomState`` key."""
    s = int(seed)
    if 0 <= s < 2 ** 32:
        return np.random.RandomState(s)
    return np.random.RandomState(np.random.SeedSequence(s).generate_state(4))


def client_step_ids(idx: np.ndarray, batch: int, epochs: int,
                    seed: int) -> np.ndarray:
    """One client's local-epoch order as an ``(steps, batch)`` array of
    the global sample ids in ``idx``: each epoch a fresh permutation
    (``_epoch_rng(seed)``) cut into full batches. A tiny client (fewer
    than ``batch`` samples) takes one batch an epoch, its permuted ids
    wrapped to ``batch`` (``np.resize``); an empty client, none."""
    idx = np.asarray(idx)
    n = len(idx)
    if n == 0:
        return np.zeros((0, batch), idx.dtype)
    rng = _epoch_rng(seed)
    per_epoch = n // batch if n >= batch else 1
    out = np.empty((epochs * per_epoch, batch), idx.dtype)
    for e in range(epochs):
        order = rng.permutation(n)
        if n >= batch:
            out[e * per_epoch:(e + 1) * per_epoch] = idx[
                order[: per_epoch * batch]].reshape(per_epoch, batch)
        else:
            out[e] = np.resize(idx[order], batch)
    return out


def client_epochs(data: Dict[str, np.ndarray], idx: np.ndarray, batch: int,
                  epochs: int, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Minibatch iterator over one client's local data for E epochs, in
    the order of :func:`client_step_ids`; a tiny client's batches keep
    their short length here (the sequential reference trains on them
    as they are)."""
    n = min(len(idx), batch)
    for sel in client_step_ids(idx, batch, epochs, seed):
        sel = sel[:n]
        yield {k: v[sel] for k, v in data.items()}


def client_step_count(n_samples: int, batch: int, epochs: int) -> int:
    """Number of local steps ``client_epochs`` yields for a client with
    ``n_samples`` points — computed from sizes alone, so chunked engines
    can fix a round-wide step axis without materializing any stream."""
    if n_samples <= 0:
        return 0
    per_epoch = n_samples // batch if n_samples >= batch else 1
    return per_epoch * epochs


def epoch_indices(
    partitions: Sequence[np.ndarray],
    cids: Sequence[int],
    batch: int,
    epochs: int,
    seeds: Sequence[int],
    pad_steps: Optional[int] = None,
    pad_clients: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The sampled clients' batch stack described by index: the one
    builder of every stack, on the host or on the device.

    Returns ``(ids, zero_rows, step_mask)``: ``ids`` an int32
    ``(C + pad_clients, S, B)`` array of global sample ids (row ``c``,
    step ``s`` is :func:`client_step_ids`'s step ``s % steps`` — a
    client with fewer than S steps repeats its own batches, masked
    out); ``zero_rows`` a bool ``(C + pad_clients,)`` flag on the rows
    whose batches are all zeros (empty clients and the
    ``pad_clients`` pad rows, whose ids are 0); ``step_mask`` a float32
    ``(C + pad_clients, S)`` array with 1.0 on real steps. S is the
    largest real step count, or ``pad_steps`` where given (it must
    cover every client's real step count)."""
    per_client = [client_step_ids(partitions[cid], batch, epochs, seed)
                  for cid, seed in zip(cids, seeds)]
    C = len(per_client)
    S = max(1, max((len(s) for s in per_client), default=0))
    if pad_steps is not None:
        if pad_steps < S:
            raise ValueError(
                f"pad_steps={pad_steps} below max real step count {S}")
        S = max(1, pad_steps)
    ids = np.zeros((C + pad_clients, S, batch), np.int32)
    zero_rows = np.ones(C + pad_clients, bool)
    step_mask = np.zeros((C + pad_clients, S), np.float32)
    for c, steps in enumerate(per_client):
        if len(steps):
            ids[c] = steps[np.arange(S) % len(steps)]
            zero_rows[c] = False
            step_mask[c, : len(steps)] = 1.0
    return ids, zero_rows, step_mask


def gather_host(data: Dict[str, np.ndarray], ids: np.ndarray,
                zero_rows: np.ndarray) -> Dict[str, np.ndarray]:
    """The ``(C, S, B, ...)`` batch stack of :func:`epoch_indices`'s
    ``ids`` built on the host, the flagged rows zeroed."""
    out = {k: v[ids] for k, v in data.items()}
    for v in out.values():
        v[zero_rows] = 0
    return out


def stack_client_epochs(
    data: Dict[str, np.ndarray],
    partitions: Sequence[np.ndarray],
    cids: Sequence[int],
    batch: int,
    epochs: int,
    seeds: Sequence[int],
    pad_steps: Optional[int] = None,
    pad_clients: int = 0,
) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Materialize every sampled client's ``client_epochs`` stream into one
    stacked batch tensor for the client-batched engine, on the host.

    Returns ``(batches, step_mask)`` where ``batches[k]`` has shape
    ``(C, S, B, ...)`` — C sampled clients, S = max local steps across the
    batch, B = batch size — and ``step_mask`` is a float32 ``(C, S)``
    array with 1.0 on real steps. Clients with fewer than S steps are
    right-padded by repeating their own batches (the pad steps are
    masked out, so the pad content only needs to be numerically tame).
    Short batches from tiny clients (fewer than ``batch`` samples) are
    filled by wrapping their indices; this is the one place the batched
    engine can diverge from the sequential reference, and only for
    clients whose whole dataset is smaller than one minibatch.
    ``pad_steps`` fixes the step axis S explicitly (must cover every
    client's real step count) so chunked callers keep one shape
    signature across chunks and rounds. ``pad_clients`` appends that
    many all-zero, fully-masked client rows. The rows are those of
    :func:`epoch_indices`, as :class:`DeviceDataset` gathers them on
    the device."""
    ids, zero_rows, step_mask = epoch_indices(
        partitions, cids, batch, epochs, seeds, pad_steps, pad_clients)
    return gather_host(data, ids, zero_rows), step_mask


def data_bytes(data: Dict[str, np.ndarray]) -> int:
    """Bytes of a client dataset, from its arrays' shapes and dtypes."""
    return sum(int(v.nbytes) for v in data.values())


def device_data_budget(device) -> Optional[int]:
    """Bytes of ``device``'s memory a resident client dataset may take:
    a quarter of the limit the device reports, leaving the rest to the
    round programs; ``None`` where it reports none (the CPU)."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    return None if limit is None else int(limit) // 4


@functools.partial(jax.jit, static_argnames=("row_shapes",))
def _gather_rows(arrays, ids, zero_rows, row_shapes):
    """Device half of :func:`gather_host`: rows ``ids`` of every array,
    the flagged client rows set to exact zeros, each sample reshaped to
    ``row_shapes[k]``."""
    out = {}
    for k, shape in row_shapes:
        rows = jnp.take(arrays[k], ids, axis=0, mode="clip")
        keep = ~zero_rows.reshape((-1,) + (1,) * (rows.ndim - 1))
        rows = jnp.where(keep, rows, jnp.zeros((), rows.dtype))
        out[k] = rows.reshape(ids.shape + shape)
    return out


class DeviceDataset:
    """A client dataset resident on the device, and the gather that
    builds a round's batch stack there from :func:`epoch_indices`: only
    the index array crosses from the host each round. Dtypes stay as
    given. Samples of more than one axis are kept flat (``(N, F)``), so
    the gather reads whole unpadded rows, and take their shape again in
    the gather's output. Under a mesh the copy is replicated over it.
    ``DeviceDataset.fits(data, device)`` says whether the dataset is
    within :func:`device_data_budget`."""

    def __init__(self, data: Dict[str, np.ndarray], mesh=None):
        sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(mesh, PartitionSpec())
        self.row_shapes = tuple((k, v.shape[1:]) for k, v in data.items())
        self.arrays = {
            k: jax.device_put(v.reshape(len(v), -1) if v.ndim > 2 else v,
                              sharding)
            for k, v in data.items()}

    @staticmethod
    def fits(data: Dict[str, np.ndarray], device) -> bool:
        """Whether ``data`` is within ``device``'s
        :func:`device_data_budget` (always, where it reports none)."""
        budget = device_data_budget(device)
        return budget is None or data_bytes(data) <= budget

    def gather(self, ids: np.ndarray,
               zero_rows: np.ndarray) -> Dict[str, jax.Array]:
        """The ``(C, S, B, ...)`` stack :func:`gather_host` would build,
        bit for bit, as device arrays."""
        return _gather_rows(self.arrays, jnp.asarray(ids),
                            jnp.asarray(zero_rows), self.row_shapes)


class ChunkBatchSource:
    """Lazy per-chunk stand-in for :func:`stack_client_epochs`.

    The streaming engine scans over fixed-size client chunks, but the
    eager path still materializes the WHOLE cohort's ``(C, S, B, ...)``
    batch stack on the host up front — the last O(cohort · data) host
    allocation in a streamed round. This source materializes one
    chunk at a time instead: the engine's scan step calls
    :meth:`fetch` through ``jax.pure_callback``, so host batch memory
    peaks at O(chunk · S · B), whatever the cohort size.

    Rows come from the same :func:`epoch_indices` builder as the eager
    stack, so chunk ``i`` of this source is bit-identical to rows
    ``[i*chunk, (i+1)*chunk)`` of ``stack_client_epochs`` with matching
    ``pad_steps`` / ``pad_clients`` — the eager/lazy parity tests hold
    the two together. Pad slots, all at the end, are encoded as client
    id ``-1`` (zero batches, zero mask).
    """

    def __init__(self, data: Dict[str, np.ndarray],
                 partitions: Sequence[np.ndarray], cids: Sequence[int],
                 batch: int, epochs: int, seeds: Sequence[int],
                 chunk: int, n_chunks: int, pad_steps: int):
        self.data = data
        self.partitions = partitions
        self.keys = list(data.keys())
        self.batch = int(batch)
        self.epochs = int(epochs)
        self.chunk = int(chunk)
        self.n_chunks = int(n_chunks)
        self.S = max(1, int(pad_steps))
        pad = self.chunk * self.n_chunks - len(cids)
        if pad < 0:
            raise ValueError("chunk * n_chunks smaller than the cohort")
        self.cids = [int(c) for c in cids] + [-1] * pad
        self.seeds = [int(s) for s in seeds] + [0] * pad

    def step_mask(self) -> np.ndarray:
        """The full cohort's ``(chunk * n_chunks, S)`` float32 step mask,
        from ``client_step_count`` alone — no batch data materialized."""
        m = np.zeros((len(self.cids), self.S), np.float32)
        for row, cid in enumerate(self.cids):
            if cid < 0:
                continue
            n = client_step_count(len(self.partitions[cid]), self.batch,
                                  self.epochs)
            m[row, : n] = 1.0
        return m

    @property
    def nbytes(self) -> int:
        """Bytes of the whole ``(chunk * n_chunks, S, B, ...)`` batch
        stack the fetches deliver, from shapes and dtypes alone: the
        ``nbytes`` of the eager ``stack_client_epochs`` stack it
        stands in for."""
        row = self.S * self.batch * sum(
            int(np.prod(self.data[k].shape[1:]))
            * np.dtype(self.data[k].dtype).itemsize for k in self.keys)
        return len(self.cids) * row

    def chunk_struct(self):
        """``jax.ShapeDtypeStruct`` tree of one fetched chunk — the
        ``pure_callback`` result signature."""
        return {k: jax.ShapeDtypeStruct(
            (self.chunk, self.S, self.batch) + self.data[k].shape[1:],
            self.data[k].dtype) for k in self.keys}

    def fetch(self, chunk_idx: int) -> Dict[str, np.ndarray]:
        """Materialize chunk ``chunk_idx``'s ``(chunk, S, B, ...)``
        batches (called from the scan step's host callback)."""
        lo = int(chunk_idx) * self.chunk
        real = [c for c in self.cids[lo: lo + self.chunk] if c >= 0]
        ids, zero_rows, _ = epoch_indices(
            self.partitions, real, self.batch, self.epochs,
            self.seeds[lo: lo + len(real)], pad_steps=self.S,
            pad_clients=self.chunk - len(real))
        return gather_host(self.data, ids, zero_rows)


@dataclass
class StreamState:
    epoch: int = 0
    step_in_epoch: int = 0


class ShardedBatcher:
    """Deterministic global-batch stream with resumable position and a
    background prefetch thread (overlaps host batch assembly with device
    compute — the CPU-side analogue of the input pipeline overlap used
    on real pods)."""

    def __init__(self, data: Dict[str, np.ndarray], global_batch: int,
                 seed: int = 0, prefetch: int = 2):
        self.data = data
        self.n = len(next(iter(data.values())))
        self.global_batch = global_batch
        self.seed = seed
        self.state = StreamState()
        self.prefetch = prefetch
        self._q: Optional[queue_mod.Queue] = None
        self._thread: Optional[threading.Thread] = None

    def _order(self, epoch: int) -> np.ndarray:
        return np.random.RandomState(self.seed + epoch).permutation(self.n)

    def next_batch(self) -> Dict[str, np.ndarray]:
        st = self.state
        order = self._order(st.epoch)
        per_epoch = self.n // self.global_batch
        if st.step_in_epoch >= per_epoch:
            st.epoch += 1
            st.step_in_epoch = 0
            order = self._order(st.epoch)
        lo = st.step_in_epoch * self.global_batch
        sel = order[lo: lo + self.global_batch]
        st.step_in_epoch += 1
        return {k: v[sel] for k, v in self.data.items()}

    # ---- background prefetch
    def start(self):
        self._q = queue_mod.Queue(maxsize=self.prefetch)
        self._stop = False

        def worker():
            while not self._stop:
                try:
                    self._q.put(self.next_batch(), timeout=0.5)
                except queue_mod.Full:
                    continue

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()
        return self

    def get(self) -> Dict[str, np.ndarray]:
        if self._q is None:
            return self.next_batch()
        return self._q.get()

    def stop(self):
        self._stop = True
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    # ---- checkpointable position
    def position(self) -> Dict[str, int]:
        return {"epoch": self.state.epoch, "step_in_epoch": self.state.step_in_epoch}

    def restore(self, pos: Dict[str, int]):
        self.state = StreamState(**pos)
