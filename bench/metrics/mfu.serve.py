"""mfu.serve: the least time the chip needs for the model operations of the
window's released requests (every prompt token and every released token,
counted as the model needs them: no padding, no prefill recompute, no
masked cache positions; feed-forward int8 operations at the int8 peak, the
rest at the bf16 peak), over the window's length times the chips used."""
import opcount


def read(ctx):
    c, cfg = ctx["counters"], ctx["cell"].config
    if not c["released"] or ctx["peaks"] is None:
        return None
    t = 0.0
    for n_in, n_out in zip(c["prompt_lens"], c["released"]):
        t += opcount.least_time_s(
            opcount.smollm_request_ops(cfg, n_in, n_out), ctx["peaks"])
    return 100.0 * t / (ctx["seconds"] * ctx["cell"].chips)
