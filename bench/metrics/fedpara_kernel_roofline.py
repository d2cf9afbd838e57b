"""Share of the fused FedPara kernels' roofline: the least time the
chip needs for every fused forward, input-gradient and factor-gradient
call of the traced rounds (each call the larger of its operations over
the bf16 peak and its bytes over HBM bandwidth, from its shapes), over
the summed device time of those calls' events on device 0, in percent.

The kernels' ops carry the names of the ``pallas_call`` wrappers
(``fedpara_matmul.<n>`` and so on), listed here."""
import sys

import xplane

KERNELS = ("fedpara_matmul", "fedpara_dx", "fedpara_dx_factors",
           "fedpara_dy_factors")


def compute(ctx):
    tr = ctx.trace
    cell = ctx.cell
    traffic, spec = cell.traffic, cell.spec
    lo, hi = tr.window
    events = xplane.in_window(
        xplane.matching(tr.devices.get(ctx.device0, []), KERNELS), lo, hi)
    if not events or not tr.rounds:
        return None
    chunk = min(traffic["client_chunk"], traffic["cohort"])
    per_device = chunk // traffic["devices"]
    n_chunks = -(-traffic["cohort"] // chunk)
    steps = ctx.steps_per_client()
    calls = cell.config.kernel_calls(spec["batch"], per_device, spec)
    if not calls:
        return None
    least = flops = nbytes = 0.0
    n_calls = 0
    for c in calls:
        n = c["count"] * steps * n_chunks * tr.rounds
        n_calls += n
        flops += n * c["flops"]
        nbytes += n * c["bytes"]
        least += n * max(c["flops"] / ctx.peaks["bf16_flops"],
                         c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    busy = sum(e.dur for e in events) * 1e-9
    bound = ("compute" if flops / ctx.peaks["bf16_flops"]
             > nbytes / ctx.peaks["hbm_bytes_per_s"] else "memory")
    print(f"fedpara_kernel_roofline: {len(events)} events, {n_calls} calls "
          f"counted, {busy} s, least {least} s, mostly {bound}-bound",
          file=sys.stderr)
    return 100.0 * least / busy
