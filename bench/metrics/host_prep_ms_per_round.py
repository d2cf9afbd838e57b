"""Host milliseconds a round spends before its round program can run:
over the traced ``fl.round`` spans (``FLServer.run_round``), the mean
of the start of the round's first ``fl.round.dispatch`` span minus the
start of ``fl.round``. Selection, the arena gather, stacking the
cohort's batches and placing them on the device all fall in it. None
where the program records no such spans."""
import xplane


def compute(ctx):
    tr = ctx.trace
    host = xplane.in_window(tr.host, *tr.window)
    dispatches = [e.start for e in host if e.name == "fl.round.dispatch"]
    prep = []
    for r in (e for e in host if e.name == "fl.round"):
        inside = [t for t in dispatches if r.start <= t < r.end]
        if inside:
            prep.append(min(inside) - r.start)
    return sum(prep) / len(prep) * 1e-6 if prep else None
