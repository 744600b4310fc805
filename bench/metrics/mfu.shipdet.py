"""mfu.shipdet: the least time the chip needs for the multiply-adds of the
frames the window completed (2 operations each, at the int8 peak: every
layer runs int8), over the window's length times the chips used."""
import opcount


def read(ctx):
    c = ctx["counters"]
    if not c["frames"] or ctx["peaks"] is None:
        return None
    ops = 2 * opcount.shipdet_macs_per_frame(ctx["cell"].config) * c["frames"]
    t = ops / ctx["peaks"]["int8_ops"]
    return 100.0 * t / (ctx["seconds"] * ctx["cell"].chips)
