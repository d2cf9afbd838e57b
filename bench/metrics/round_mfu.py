"""Model FLOP/s utilization of the whole round: the local-training
FLOPs a round requires (the configuration's dense-equivalent forward +
backward over real samples, plus composing each FedPara weight and its
factor gradients once per client and local step; recomputation not
counted) over the traced rounds' time, over chips x bf16 peak, in
percent. Parameters are float32 at JAX's default precision, which runs
each matmul as one bf16 pass on the TPU, so the bf16 peak is the one."""


def compute(ctx):
    tr = ctx.trace
    if not tr.rounds or tr.window_s <= 0:
        return None
    per_round = tr.window_s / tr.rounds
    chips = len(ctx.devices)
    return 100.0 * ctx.flops_per_round() / per_round / (
        chips * ctx.peaks["bf16_flops"])
