"""Batch loaders: local epochs for FL clients + sharded global batches
for the pod trainer (deterministic, resumable — the checkpoint stores
the stream position so restarts continue mid-epoch)."""
from __future__ import annotations

import threading
import queue as queue_mod
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

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


def client_epochs(data: Dict[str, np.ndarray], idx: np.ndarray, batch: int,
                  epochs: int, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Minibatch iterator over one client's local data for E epochs."""
    rng = _epoch_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(len(idx))
        for i in range(0, len(order) - batch + 1, batch):
            sel = idx[order[i: i + batch]]
            yield {k: v[sel] for k, v in data.items()}
        if 0 < len(order) < batch:  # tiny client: one short batch per epoch
            sel = idx[order]
            yield {k: v[sel] for k, v in data.items()}


def client_step_count(n_samples: int, batch: int, epochs: int) -> int:
    """Number of local steps ``client_epochs`` yields for a client with
    ``n_samples`` points — computed from sizes alone, so chunked engines
    can fix a round-wide step axis without materializing any stream."""
    if n_samples <= 0:
        return 0
    per_epoch = n_samples // batch if n_samples >= batch else 1
    return per_epoch * epochs


def _client_steps(data: Dict[str, np.ndarray], idx: np.ndarray, batch: int,
                  epochs: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """One client's materialized local-epoch minibatch list (empty for
    clients with no samples)."""
    return (list(client_epochs(data, idx, batch, epochs, seed))
            if len(idx) else [])


def _pad_batch(b: Dict[str, np.ndarray], batch: int,
               keys: Sequence[str]) -> Dict[str, np.ndarray]:
    """Wrap a tiny client's short batch up to the full batch size."""
    n = len(b[keys[0]])
    if n == batch:
        return b
    sel = np.resize(np.arange(n), batch)  # wrap tiny-client batches
    return {k: v[sel] for k, v in b.items()}


def _fill_row(out: Dict[str, np.ndarray], step_mask: np.ndarray, row: int,
              steps: List[Dict[str, np.ndarray]], S: int, batch: int,
              keys: Sequence[str]) -> None:
    """Write one client's steps into row ``row`` of the stacked output,
    right-padding by repeating its own batches. Shared by the eager
    stack (``stack_client_epochs``) and the lazy per-chunk source
    (:class:`ChunkBatchSource`) so the two are bit-identical."""
    if not steps:  # empty client: all-padding (zeros), mask stays 0
        return
    steps = [_pad_batch(b, batch, keys) for b in steps]
    step_mask[row, : len(steps)] = 1.0
    for s in range(S):
        b = steps[s] if s < len(steps) else steps[s % len(steps)]
        for k in keys:
            out[k][row, s] = b[k]


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
    stacked batch tensor for the client-batched engine.

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
    many all-zero, fully-masked client rows, pre-sized in the output
    allocation — the streaming engine's chunk padding — so callers
    never concatenate a second full-cohort copy."""
    per_client = [_client_steps(data, partitions[cid], batch, epochs, seed)
                  for cid, seed in zip(cids, seeds)]
    C = len(per_client)
    S = max(1, max(len(s) for s in per_client))
    if pad_steps is not None:
        if pad_steps < S:
            raise ValueError(
                f"pad_steps={pad_steps} below max real step count {S}")
        S = max(1, pad_steps)
    keys = list(data.keys())

    step_mask = np.zeros((C + pad_clients, S), np.float32)
    out = {k: np.zeros((C + pad_clients, S, batch) + data[k].shape[1:],
                       data[k].dtype) for k in keys}
    for c, steps in enumerate(per_client):
        _fill_row(out, step_mask, c, steps, S, batch, keys)
    return out, step_mask


class ChunkBatchSource:
    """Lazy per-chunk stand-in for :func:`stack_client_epochs`.

    The streaming engine scans over fixed-size client chunks, but the
    eager path still materializes the WHOLE cohort's ``(C, S, B, ...)``
    batch stack on the host up front — the last O(cohort · data) host
    allocation in a streamed round. This source materializes one
    chunk at a time instead: the engine's scan step calls
    :meth:`fetch` through ``jax.pure_callback``, so host batch memory
    peaks at O(chunk · S · B), whatever the cohort size.

    Rows are filled by the same ``_fill_row`` helper as the eager
    stack, so chunk ``i`` of this source is bit-identical to rows
    ``[i*chunk, (i+1)*chunk)`` of ``stack_client_epochs`` with matching
    ``pad_steps`` / ``pad_clients`` — the eager/lazy parity tests hold
    the two together. Pad slots are encoded as client id ``-1`` (zero
    batches, zero mask).
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
        import jax

        return {k: jax.ShapeDtypeStruct(
            (self.chunk, self.S, self.batch) + self.data[k].shape[1:],
            self.data[k].dtype) for k in self.keys}

    def fetch(self, chunk_idx: int) -> Dict[str, np.ndarray]:
        """Materialize chunk ``chunk_idx``'s ``(chunk, S, B, ...)``
        batches (called from the scan step's host callback)."""
        lo = int(chunk_idx) * self.chunk
        out = {k: np.zeros(
            (self.chunk, self.S, self.batch) + self.data[k].shape[1:],
            self.data[k].dtype) for k in self.keys}
        mask = np.zeros((self.chunk, self.S), np.float32)
        for j in range(self.chunk):
            cid = self.cids[lo + j]
            if cid < 0:
                continue
            steps = _client_steps(self.data, self.partitions[cid],
                                  self.batch, self.epochs,
                                  self.seeds[lo + j])
            _fill_row(out, mask, j, steps, self.S, self.batch, self.keys)
        return out


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
