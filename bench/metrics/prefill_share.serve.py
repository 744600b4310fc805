"""prefill_share.serve: the prefill program's share of the device's busy
time in the traced window, averaged over chips: the union of operation
intervals inside the prefill program's executions over all busy time.
The program is found by its XLA module name: the engine jits prefill as an
unnamed lambda, so its module is ``jit__lambda``, and no other program of
the serving path is one.  Where no such module ran in the traced window
(as when the program names that jit), the metric is left out of the line,
never read as 0."""
import sys

import trace_reduce

PREFILL = "jit__lambda"


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.chips:
        return None
    if not any(PREFILL in c.modules for c in s.chips):
        print(f"prefill_share.serve: no module {PREFILL} in the traced "
              f"window; left out", file=sys.stderr)
        return None
    shares = [trace_reduce.busy_in(c, PREFILL, s.lo_ns, s.hi_ns) / c.busy_s
              for c in s.chips if c.busy_s > 0]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
