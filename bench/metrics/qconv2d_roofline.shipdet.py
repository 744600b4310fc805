"""qconv2d_roofline.shipdet: the least time of the window's qconv2d kernel
calls over their device time.  A call is matched to its layer by its
weight operand (kh, kw, cin, cout + 4 check columns); its least time is the
larger of the layer's int8 operations at the int8 peak and its bytes at the
memory bandwidth (``opcount.qconv2d`` at the layer's geometry for the
call's batch).  A Pallas call with no such weight operand is not one of
the network's convolutions and is not counted.  Every layer is
memory-bound at the cell's shapes (PERF.md)."""
import opcount
import trace_reduce


def layer_table(cfg):
    """(kh, kw, cin, cout + 4) -> (layer, input side)."""
    out, side = {}, cfg["tile"]
    for s in cfg["layers"]:
        out[(s["kh"], s["kw"], s["cin"], s["cout"] + 4)] = (s, side)
        side = opcount.conv_out(side, s["stride"])
    return out


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.chips or ctx["peaks"] is None:
        return None
    table = layer_table(ctx["cell"].config)
    least = spent = 0.0
    for c in s.chips:
        for e in trace_reduce.kernel_events(c, s.lo_ns, s.hi_ns):
            ops = trace_reduce.kernel_operands(e[0])
            w = next((d for t, d in ops if t == "s8" and d in table), None)
            if w is None:
                continue                  # not one of the network's convs
            n = ops[0][1][0]
            layer, side = table[w]
            work = opcount.qconv2d(n, side, side, layer["cin"],
                                   layer["cout"], layer["kh"], layer["kw"],
                                   layer["stride"])
            least += opcount.roofline_time_s(work, ctx["peaks"])[0]
            spent += e[2] / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
