"""Seconds per federated round: the whole window over all the rounds it
completed (host clock, each round ending when its new global model is
ready on the device)."""


def compute(ctx):
    w = ctx.window
    return w.seconds / w.rounds if w.rounds else None
