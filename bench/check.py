"""The output check: what the timed path produced against the plain
reference (``reference.py``), and the wire bytes against the
benchmark's own count.

The numbers, computed in every run; a cell's
``bench/limits/<cell>.json`` names the ones it compares and the limit
of each (the others are logged, for the readings in ``PERF.md``):

* ``cohort_mismatch``: rounds whose cohort differs from the reference's
  draw (exact, limit 0);
* ``wire_bytes_off``: largest gap in bytes, over every round of the run,
  between the program's charged up + down bytes and the count from the
  parameter tree's shapes and the codec (exact, limit 0);
* ``nonfinite_losses``: client losses that were not finite, over every
  round of the run (limit 0);
* ``loss_gap``, ``first_loss_gap``: relative gap between the program's
  and the reference's mean last local loss, the largest over the
  checked rounds and that of the first round;
* ``update_gap``: the first round's update of the global model (what
  the server's optimizer takes as its gradient), by the worst leaf: the
  gap between the program's norm and the reference's, over the larger
  of the reference's norm of that leaf and of the median leaf;
  ``update_gap_median``: the median of those per-leaf gaps;
* ``update_diff``, ``update_diff_median``: the same, but the norm of the
  difference between the program's update and the reference's. Both
  sides round their uploads to int8 with the same noise, so that noise
  cancels here where it dominates a norm;
* ``change_gap``, ``change_gap_median``, ``change_diff``,
  ``change_diff_median``: the same for the change of the global model
  over all the checked rounds.

Leaves whose first reference update is under a thousandth of the median
leaf's move by rounding alone; they are left out of the norm gaps.
"""
from __future__ import annotations

import jax
import numpy as np

EXCLUDE_BELOW = 1e-3


def wire_bytes_per_client(params, codec: str) -> tuple:
    """(up, down) wire bytes of one client and round: per-tensor int8
    values plus a 4-byte scale per tensor, or the identity codec at each
    leaf's own width; down is the identity broadcast."""
    leaves = jax.tree.leaves(params)
    sizes = [int(np.prod(np.shape(x))) or 1 for x in leaves]
    widths = [np.dtype(x.dtype).itemsize for x in leaves]
    plain = sum(s * w for s, w in zip(sizes, widths))
    if codec in ("", "fp32"):
        up = plain
    elif codec == "int8":
        up = sum(sizes) + 4 * len(leaves)
    else:
        raise ValueError(f"no byte count for uplink codec {codec!r}")
    return up, plain


def _leaf_norms(tree) -> list:
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def _delta(after, before):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64), after, before)


def leaf_gaps(program_delta, reference_delta, keep) -> list:
    """Per kept leaf: the gap between the program's norm and the
    reference's, over the larger of the reference's norm of that leaf
    and of the median leaf."""
    p, r = _leaf_norms(program_delta), _leaf_norms(reference_delta)
    med = float(np.median([r[i] for i in keep]))
    return [abs(p[i] - r[i]) / max(r[i], med) for i in keep]


def leaf_diffs(program_delta, reference_delta, keep) -> list:
    """Per kept leaf: the norm of the difference between the program's
    and the reference's, over the larger of the reference's norm of that
    leaf and of the median leaf."""
    d = _leaf_norms(_delta(program_delta, reference_delta))
    r = _leaf_norms(reference_delta)
    med = float(np.median([r[i] for i in keep]))
    return [d[i] / max(r[i], med) for i in keep]


def kept_leaves(reference_first) -> list:
    norms = _leaf_norms(reference_first)
    floor = EXCLUDE_BELOW * float(np.median(norms))
    return [i for i, n in enumerate(norms) if n >= floor]


def compare(params0, program_after: list, program_losses: list,
            program_cohorts: list, ref) -> dict:
    """The numbers that compare the program's checked rounds with the
    reference's. ``program_after`` holds the global model after each
    checked round."""
    first_ref = _delta(ref.params[0], params0)
    last_ref = _delta(ref.params[-1], params0)
    first, last = (_delta(program_after[0], params0),
                   _delta(program_after[-1], params0))
    keep = kept_leaves(first_ref)
    losses = [abs(a - b) / abs(b) for a, b in zip(program_losses, ref.losses)]
    out = {
        "cohort_mismatch": sum(a != b for a, b in zip(program_cohorts,
                                                      ref.cohorts)),
        "loss_gap": max(losses),
        "first_loss_gap": losses[0],
    }
    for name, gaps in (("update_gap", leaf_gaps(first, first_ref, keep)),
                       ("update_diff", leaf_diffs(first, first_ref, keep)),
                       ("change_gap", leaf_gaps(last, last_ref, keep)),
                       ("change_diff", leaf_diffs(last, last_ref, keep))):
        out[name] = max(gaps)
        out[f"{name}_median"] = float(np.median(gaps))
    return out


def leaf_table(params0, after: list, ref) -> dict:
    """Per-leaf norms behind the norm gaps and differences, for reading
    which leaf sets them: the reference's and the compared run's first
    update and change, and the norm of their difference, leaf by leaf in
    tree order."""
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params0)[0]]
    return {"leaves": names,
            "ref_update": _leaf_norms(_delta(ref.params[0], params0)),
            "update": _leaf_norms(_delta(after[0], params0)),
            "update_diff": _leaf_norms(_delta(after[0], ref.params[0])),
            "ref_change": _leaf_norms(_delta(ref.params[-1], params0)),
            "change": _leaf_norms(_delta(after[-1], params0)),
            "change_diff": _leaf_norms(_delta(after[-1], ref.params[-1]))}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for every limited number; a
    number that is missing or not finite fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out
