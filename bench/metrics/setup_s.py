"""Seconds from process start to the end of set-up: imports, the chip,
weights and data drawn from the seed, the program's server, and the
checked first rounds, which compile (or load from the persistent cache)
every program the window runs."""


def compute(ctx):
    return ctx.setup_s
