"""Device-resident per-client state arena: gather-at-sample,
scatter-at-arrival.

The dict-based server keeps every client's strategy state (SCAFFOLD
``c_i``, FedDyn ``lambda_i``), codec error-feedback accumulators
(``"_ef_up"``) and personalization residents in host-side Python dicts
(``FLServer.client_states`` / ``local_trees``), and writes arrivals
back with a per-client ``tree_index`` loop — O(C) host objects and
O(cohort) Python-loop dispatches per round. :class:`ClientArena`
replaces both with **index-addressed stacked device arrays**:

  * every per-client tree lives once, stacked along a leading row axis
    of ``R = clients + 1`` rows (row ``clients`` is a scratch row that
    absorbs the streaming engine's pad-slot writebacks, so duplicate
    pad indices scatter the same value and stay deterministic);
  * round start is ONE vectorized ``jnp.take`` over the cohort's rows
    (:meth:`gather`), round end is ONE masked ``.at[rows].set``
    (:meth:`scatter`) — non-arrived clients keep their previous rows
    bit-exactly because the scatter writes ``where(mask, new, old)``;
  * the scatter donates the arena buffers (``donate_argnums``), so XLA
    updates the fleet state in place instead of double-buffering the
    O(C)-sized arrays;
  * on a ``("clients",)`` mesh the row axis is sharded across devices
    (:meth:`shard_rows`), putting each device in charge of a fleet
    shard.

Rows are initialized from a single template (strategy init state is
zeros / constants; residents start at the global init), which matches
the dict engines' lazy first-participation init exactly — a client's
row is bit-identical to what ``FLServer._prep_client_state`` would have
built the first time it was sampled. Participation counts ride along as
an int32 row vector bumped by the same arrival mask.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _gather_rows(tree: Any, rows: jax.Array) -> Any:
    from repro.fl.strategies import tree_take

    return tree_take(tree, rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(tree: Any, rows: jax.Array, new: Any,
                  mask: jax.Array) -> Any:
    def one(a, n):
        keep = (mask > 0).reshape((-1,) + (1,) * (n.ndim - 1))
        return a.at[rows].set(jnp.where(keep, n.astype(a.dtype), a[rows]))

    return jax.tree.map(one, tree, new)


@functools.partial(jax.jit, donate_argnums=(0,))
def _bump_rows(counts: jax.Array, rows: jax.Array,
               mask: jax.Array) -> jax.Array:
    return counts.at[rows].add((mask > 0).astype(counts.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def _pin_rows(versions: jax.Array, rows: jax.Array, mask: jax.Array,
              version: jax.Array) -> jax.Array:
    keep = mask > 0
    return versions.at[rows].set(
        jnp.where(keep, jnp.asarray(version, versions.dtype),
                  versions[rows]))


class ClientArena:
    """Stacked device-resident per-client state (see module docstring).

    Build with :meth:`create`; address with :meth:`rows_for` (appends
    the scratch row for streaming pad slots); move cohorts on and off
    with :meth:`gather` / :meth:`scatter`.
    """

    def __init__(self, n_clients: int, state: Any, residents: Any,
                 participation: jax.Array, versions: Any = None):
        self.n_clients = int(n_clients)
        self.scratch_row = int(n_clients)   # absorbs pad-slot scatters
        self.state = state                  # dict tree, leaves (R, ...)
        self.residents = residents          # tree or None, leaves (R, ...)
        self.participation = participation  # (R,) int32
        # broadcast-version pinning (async engine, docs/async.md): the
        # global version each row's state was produced against — the
        # row's EF accumulator / delta reference / strategy state are
        # KEYED by this version; -1 = never dispatched
        self.versions = (versions if versions is not None
                         else jnp.full(participation.shape, -1, jnp.int32))

    @classmethod
    def create(cls, n_clients: int, state_template: Any,
               resident_template: Any = None) -> "ClientArena":
        """Allocate ``n_clients + 1`` rows, every row a copy of the
        templates (strategy-init state / global-init residents): the
        vectorized equivalent of the dict engines' lazy per-client
        first-participation init."""
        rows = int(n_clients) + 1

        def stackify(tree):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(
                    jnp.asarray(x)[None], (rows,) + jnp.shape(x)) + 0,
                tree)

        return cls(n_clients,
                   stackify(state_template if state_template else {}),
                   stackify(resident_template)
                   if resident_template is not None else None,
                   jnp.zeros((rows,), jnp.int32))

    # ---------------------------------------------------------- addressing
    def rows_for(self, cids, pad: int = 0) -> jax.Array:
        """Row indices for a cohort, with ``pad`` trailing scratch-row
        slots (the streaming engine's chunk padding): every pad slot
        maps to the SAME scratch row, so the masked scatter writes it
        one identical value — duplicate-index order never matters."""
        rows = np.asarray(cids, np.int32)
        if pad:
            rows = np.concatenate(
                [rows, np.full(pad, self.scratch_row, np.int32)])
        return jnp.asarray(rows)

    # ------------------------------------------------------ gather/scatter
    def gather(self, rows: jax.Array) -> Tuple[Any, Any]:
        """One vectorized row gather: ``(state_chunk, resident_chunk)``
        stacked along the cohort axis (resident half ``None`` when the
        arena holds no residents)."""
        state = _gather_rows(self.state, rows)
        residents = (_gather_rows(self.residents, rows)
                     if self.residents is not None else None)
        return state, residents

    def scatter(self, rows: jax.Array, new_state: Any, new_residents: Any,
                arrived_mask) -> None:
        """One masked row scatter: arrived rows take the new values,
        everyone else (including the scratch row's pad slots) keeps the
        old row bit-exactly. Donates the arena buffers — the fleet
        arrays update in place. Also bumps the participation counters."""
        mask = jnp.asarray(arrived_mask, jnp.float32)
        if new_state:
            new_state = {k: v for k, v in new_state.items()
                         if k in self.state}
            self.state = {**self.state,
                          **_scatter_rows(
                              {k: self.state[k] for k in new_state},
                              rows, new_state, mask)}
        if new_residents is not None and self.residents is not None:
            self.residents = _scatter_rows(self.residents, rows,
                                           new_residents, mask)
        self.participation = _bump_rows(self.participation, rows, mask)

    def pin_versions(self, rows: jax.Array, version: int,
                     arrived_mask) -> None:
        """Record the broadcast version the masked rows' new state was
        trained against (one masked ``.at[].set`` — the async engine
        calls this alongside :meth:`scatter` at dispatch writeback)."""
        self.versions = _pin_rows(self.versions, rows,
                                  jnp.asarray(arrived_mask, jnp.float32),
                                  jnp.int32(int(version)))

    # ------------------------------------------------------------ sharding
    def shard_rows(self, mesh, axis: str = "clients") -> None:
        """Shard every arena leaf's row axis over ``mesh[axis]`` (no-op
        without a mesh). The scratch row makes ``clients + 1`` rows, and
        they must divide evenly over the axis: otherwise this raises
        rather than leave the whole fleet on one device."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if mesh is None or axis not in mesh.axis_names:
            return
        ndev = mesh.shape[axis]
        if (self.n_clients + 1) % ndev:
            raise ValueError(
                f"arena rows (clients + 1 = {self.n_clients + 1}) do not "
                f"divide over the {ndev} devices of mesh axis {axis!r}; "
                f"choose clients so that clients + 1 is a multiple of {ndev}")
        sharding = NamedSharding(mesh, P(axis))

        def put(tree):
            return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)

        self.state = put(self.state)
        if self.residents is not None:
            self.residents = put(self.residents)
        self.participation = jax.device_put(self.participation, sharding)
        self.versions = jax.device_put(self.versions, sharding)

    # ------------------------------------------------------------- readout
    def client_state(self, cid: int) -> Any:
        """One client's state row as host arrays (test/debug readout —
        the training path never unstacks rows)."""
        return jax.tree.map(lambda a: np.asarray(a[int(cid)]), self.state)

    def client_resident(self, cid: int) -> Any:
        """One client's personalization-resident row as host arrays
        (``None`` when the mode keeps no residents)."""
        if self.residents is None:
            return None
        return jax.tree.map(lambda a: np.asarray(a[int(cid)]),
                            self.residents)

    def participation_counts(self) -> np.ndarray:
        """(clients,) int array: rounds each client arrived in (the
        scratch row is excluded)."""
        return np.asarray(self.participation)[: self.n_clients]

    def client_versions(self) -> np.ndarray:
        """(clients,) int array: the pinned broadcast version of each
        row's state (-1 = never dispatched; scratch row excluded)."""
        return np.asarray(self.versions)[: self.n_clients]
