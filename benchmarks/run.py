# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import functools
import importlib
import sys
import traceback

# benchmark-module registry: (module under benchmarks/, --only match
# terms). A group runs when no --only filter is given or any term
# contains/equals the filter substring.
MODULES = (
    ("fl_round", ("fl_round_sequential", "fl_round_batched")),
    ("comm_codecs", ("comm", "comm_codecs")),
    ("fedpara_grad", ("grad", "kernel")),
    ("fl_streaming", ("stream",)),
    ("fl_hetero", ("hetero",)),
    ("fl_fleet_smoke", ("fleet",)),
    ("fl_faults", ("faults", "robust", "chaos")),
    ("fl_async", ("async", "fedbuff")),
    ("serve_decode", ("serve", "decode", "serve_decode")),
)


def _selected(only, terms):
    return only is None or any(only in t for t in terms)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = 0
    # fl_streaming's fleet sweep starts one device-using child per fleet
    # size; a device belongs to one process, so they run before this
    # process imports JAX (fl_streaming itself imports none at load)
    fleet = None
    if _selected(args.only, dict(MODULES)["fl_streaming"]):
        from benchmarks import fl_streaming

        try:
            fleet = fl_streaming.fleet_section()
        except Exception as e:
            failures += 1
            print(f"fl_fleet,0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)

    from benchmarks import roofline, tables

    def emit(group, rows_fn):
        nonlocal failures
        try:
            for name, us, derived in rows_fn():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:
            failures += 1
            print(f"{group},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)

    for fn in tables.ALL_TABLES:
        if args.only and args.only not in fn.__name__:
            continue
        emit(fn.__name__, fn)
    for modname, terms in MODULES:
        if not _selected(args.only, terms):
            continue
        rows_fn = importlib.import_module(f"benchmarks.{modname}").csv_rows
        if modname == "fl_streaming":
            rows_fn = functools.partial(rows_fn, fleet=fleet)
        emit(modname, rows_fn)
    if not args.skip_roofline:
        for name, us, derived in roofline.csv_rows():
            print(f"{name},{us:.1f},{derived}", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark groups failed")


if __name__ == "__main__":
    main()
