"""Share of the traced window in which device 0 ran no operation:
1 - (union of its operation intervals) / window, in percent. Loops and
calls that contain other operations do not count as running, so the
gaps between the operations of a loop's body count as idle."""
import xplane


def compute(ctx):
    tr = ctx.trace
    ops = tr.devices.get(ctx.device0)
    if not ops or tr.window_s <= 0:
        return None
    lo, hi = tr.window
    busy = xplane.busy_ns(xplane.leaves(ops), lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo))
