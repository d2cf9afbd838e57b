"""Megabytes (1e6 bytes) of client batches the host moves to the device
a round: the mean of the program's ``host_batch_bytes`` count over the
window's round records, the byte size of the round's ``(clients, steps,
batch, ...)`` batch stack from its shapes and dtypes (the step mask not
counted). None where the records carry no such count."""


def compute(ctx):
    counts = [r["host_batch_bytes"] for r in ctx.window.records
              if "host_batch_bytes" in r]
    return sum(counts) / len(counts) / 1e6 if counts else None
