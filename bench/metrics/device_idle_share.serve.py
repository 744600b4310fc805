"""device_idle_share: the share of the traced window in which no operation
ran on the device: 1 - (union of the device's operation intervals / window),
averaged over the cell's chips (``trace_reduce.Summary``)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.chips or s.window_s <= 0:
        return None
    return 100.0 * s.idle_share()
