"""Readings that the output check's limits are set from, on the chip.

Not part of a benchmark run. In one process, for the cell's own sizes,
each against the plain reference at every precision of
``--precisions`` (the configuration's own, and others for the look):

* ``--seeds``: the program's checked rounds (the lower reading of every
  number);
* ``--control-seeds``: the control, the reference computed in bfloat16
  put in the program's place;
* ``--fault-seeds``: the program with a planted fault (``--faults``:
  ``half_batch``, each local step's loss over the first half of its
  batch; ``stale_state``, the round leaves the global model unchanged);
* ``--highest-seeds``: the program with every matmul at ``highest``.

    python3 bench/calibrate.py --workload lstm_shakespeare.c16 \\
        --seeds 1,2,3 --control-seeds 1,2,3 --faults half_batch --fault-seeds 1,2,3

Prints one JSON line of numbers per reading, and writes it with the
per-leaf norms to ``chiprun_out/calibrate_<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import manifest  # noqa: E402

OUT = harness.ROOT / "chiprun_out"


@contextlib.contextmanager
def planted(fault: str, cell):
    """Break the timed path underneath the harness for the block:
    ``stale_state``, every round leaves the global model unchanged;
    ``half_batch``, each local step takes its loss over the first half
    of its batch."""
    if fault == "stale_state":
        from repro.fl.server import FLServer

        original = FLServer._apply_aggregated
        FLServer._apply_aggregated = lambda self, new, target: None
        try:
            yield
        finally:
            FLServer._apply_aggregated = original
    elif fault == "half_batch":
        original = cell.config.program_loss

        def program_loss(spec):
            loss_fn = original(spec)

            def loss(params, batch):
                return loss_fn(params, {k: v[: v.shape[0] // 2]
                                        for k, v in batch.items()})
            return loss

        cell.config.program_loss = program_loss
        try:
            yield
        finally:
            cell.config.program_loss = original
    else:
        raise ValueError(f"no planted fault {fault!r}")


def readings(cell, devices, args, emit):
    import jax
    import jax.numpy as jnp

    import check
    import reference

    kinds = {"program": args.seeds, "control_bf16": args.control_seeds,
             "highest": args.highest_seeds,
             **{f"fault_{f}": args.fault_seeds for f in args.faults}}
    for seed in sorted(set().union(*kinds.values())):
        setup = harness.build(cell, seed, devices)
        setup.server = None
        refs, ref_s = {}, {}
        for p in args.precisions:
            t0 = time.perf_counter()
            refs[p] = harness.run_reference(setup, precision=p)
            ref_s[p] = time.perf_counter() - t0
        runs = {}
        if seed in args.seeds:
            runs["program"] = (setup.after, setup.losses, setup.cohorts)
        if seed in args.control_seeds:
            ctrl = harness.run_reference(setup, dtype=jnp.bfloat16,
                                         precision="default")
            runs["control_bf16"] = (ctrl.params, ctrl.losses, ctrl.cohorts)
        if seed in args.highest_seeds:
            with jax.default_matmul_precision("highest"):
                high = harness.build(cell, seed, devices)
            high.server = None
            runs["highest"] = (high.after, high.losses, high.cohorts)
        for fault in args.faults if seed in args.fault_seeds else ():
            with planted(fault, cell):
                bad = harness.build(cell, seed, devices)
            bad.server = None
            runs[f"fault_{fault}"] = (bad.after, bad.losses, bad.cohorts)
        for (kind, (after, losses, cohorts)), (p, ref), n in itertools.product(
                runs.items(), refs.items(), range(2, len(setup.after) + 1)):
            ref_n = reference.Rounds(ref.cohorts[:n], ref.losses[:n],
                                     ref.params[:n])
            numbers = check.compare(setup.params0, after[:n], losses[:n],
                                    cohorts[:n], ref_n)
            emit({"kind": kind, "seed": seed, "reference": p, "rounds": n,
                  "reference_s": ref_s[p], "losses": losses[:n],
                  "ref_losses": ref_n.losses, **numbers},
                 check.leaf_table(setup.params0, after[:n], ref_n))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--highest-seeds", default="")
    ap.add_argument("--precisions", default="default")
    args = ap.parse_args()

    def ints(s):
        return [int(x) for x in s.split(",") if x]

    for key in ("seeds", "control_seeds", "fault_seeds", "highest_seeds"):
        setattr(args, key, ints(getattr(args, key)))
    args.faults = [f for f in args.faults.split(",") if f]
    args.precisions = args.precisions.split(",")
    cell = manifest.resolve(harness.ROOT, args.workload)
    harness.import_program()
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    OUT.mkdir(exist_ok=True)
    log = open(OUT / f"calibrate_{cell.name}.jsonl", "a")

    def emit(numbers, leaves):
        line = {"cell": cell.name, **numbers}
        print(json.dumps(line), flush=True)
        log.write(json.dumps({**line, **leaves}) + "\n")
        log.flush()

    readings(cell, devices, args, emit)
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
