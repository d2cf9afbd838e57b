"""Host milliseconds a round spends stacking its cohort's batches: the
summed duration of the ``fl.round.stack_batches`` spans in the traced
window over the number of traced ``fl.round`` spans (the program's
``stack_client_epochs``, or building its lazy chunk source). None where
the program records no such spans."""
import xplane


def compute(ctx):
    tr = ctx.trace
    host = xplane.in_window(tr.host, *tr.window)
    rounds = sum(e.name == "fl.round" for e in host)
    spans = [e.dur for e in host if e.name == "fl.round.stack_batches"]
    return sum(spans) / rounds * 1e-6 if rounds and spans else None
