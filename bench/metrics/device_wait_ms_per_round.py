"""Host milliseconds a round waits for its round program, as the host
sees it: the summed duration of the ``fl.round.wait`` spans (the
round's first read of the program's outputs) in the traced window over
the number of traced ``fl.round`` spans. None where the program records
no such spans."""
import xplane


def compute(ctx):
    tr = ctx.trace
    host = xplane.in_window(tr.host, *tr.window)
    rounds = sum(e.name == "fl.round" for e in host)
    spans = [e.dur for e in host if e.name == "fl.round.wait"]
    return sum(spans) / rounds * 1e-6 if rounds and spans else None
