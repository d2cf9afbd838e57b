"""Up plus down wire megabytes (1e6 bytes) per round in the window, as
the program charges them to its ``comm_log``; the output check holds
every round's charge equal to the benchmark's own count."""


def compute(ctx):
    w = ctx.window
    return w.wire_bytes / w.rounds / 1e6 if w.rounds else None
