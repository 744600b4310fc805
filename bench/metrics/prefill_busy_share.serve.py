"""prefill_busy_share.serve: the prefill program's share of the device's
busy time in the traced window, averaged over chips: the union of operation
intervals inside executions of the engine's prefill program over all busy
time.  ``prefill_share.serve``'s definition, on the program's stable module
name ``jit_engine_prefill``.  A trace in which the engine's named programs
ran but no prefill reads 0; a program that does not name its programs
(none of ``jit_engine_*`` in the window) leaves the metric out of the
line."""
import sys

import trace_reduce

PREFILL = "jit_engine_prefill"
NAMED = (PREFILL, "jit_engine_decode_window", "jit_engine_decode_step")


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.chips:
        return None
    if not any(m in c.modules for c in s.chips for m in NAMED):
        print(f"prefill_busy_share.serve: none of {NAMED} in the traced "
              f"window; left out", file=sys.stderr)
        return None
    shares = [trace_reduce.busy_in(c, PREFILL, s.lo_ns, s.hi_ns) / c.busy_s
              for c in s.chips if c.busy_s > 0]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
