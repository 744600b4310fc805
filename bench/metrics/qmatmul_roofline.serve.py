"""qmatmul_roofline.serve: the least time of the window's qmatmul kernel
calls over their device time.  Each call's least time is the larger of its
int8 operations at the int8 peak and its bytes at the memory bandwidth
(``opcount.qmatmul`` at the call's own shapes, read from the trace: x
(M, K), w (K, N), and the (K, 4) check limbs where the call has them).
Every call at the serving shapes is memory-bound: its arithmetic intensity
(about 52 int8 operations a byte at decode's 32 rows, 130-380 at prefill's
128-1024) is below the chip's 480.  A Pallas call whose int8 operands do
not have that signature is not a qmatmul and is not counted."""
import sys

import opcount
import trace_reduce


def signature(operands):
    """(M, K, N, check) of a qmatmul call's int8 operands x (M, K), w (K, N)
    and, where it has them, the check limbs (K, 4); None for any other
    kernel."""
    s8 = [d for t, d in operands if t == "s8"]
    if len(s8) not in (2, 3) or any(len(d) != 2 for d in s8):
        return None
    (m, k), (k2, n) = s8[0], s8[1]
    if k2 != k or (len(s8) == 3 and s8[2] != (k, 4)):
        return None
    return m, k, n, len(s8) == 3


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.chips or ctx["peaks"] is None:
        return None
    least = spent = 0.0
    other = 0
    for c in s.chips:
        for e in trace_reduce.kernel_events(c, s.lo_ns, s.hi_ns):
            sig = signature(trace_reduce.kernel_operands(e[0]))
            if sig is None:
                other += 1
                continue
            m, k, n, check = sig
            least += opcount.roofline_time_s(
                opcount.qmatmul(m, k, n, checksum=check), ctx["peaks"])[0]
            spent += e[2] / 1e9
    if other:
        print(f"qmatmul_roofline.serve: {other} Pallas calls of another "
              f"signature not counted", file=sys.stderr)
    if spent <= 0:
        return None
    return 100.0 * least / spent
