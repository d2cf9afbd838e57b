"""Trained tokens a second a chip: the program's ``train_tokens`` count
(target positions of the real local steps of the clients whose uploads
arrived) summed over the traced rounds, over the traced rounds' time,
over the chips. None where the round records carry no such count."""


def compute(ctx):
    tr = ctx.trace
    records = ctx.window.records[: tr.rounds]
    counts = [r["train_tokens"] for r in records if "train_tokens" in r]
    if not counts or len(counts) < tr.rounds or tr.window_s <= 0:
        return None
    return sum(counts) / tr.window_s / len(ctx.devices)
