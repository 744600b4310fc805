"""slot_wait_share.serve: of the time the window's released requests spent
from submit to release, the share they waited for a decode slot (submit to
the start of their prefill), summed over those requests, from the stage
stamps the engine takes on every request (``time.perf_counter()``).

The engine logs each release's stamps, process-wide and in release order
(``repro.obs.trace.RELEASED``: uid, prompt length, tokens released, then
the stamps).  The run's counters list the window's released requests in
the same order by prompt length and tokens released; the reader takes the
latest run of log entries that matches that list.  A program without the
log, or a list matched nowhere, leaves the metric out of the line."""
import sys

SUBMIT, PREFILL, RELEASE = 3, 4, 7      # columns of a log entry


def window_rows(log, prompt_lens, released):
    """The latest contiguous run of ``log`` entries whose (prompt length,
    tokens released) pairs are the window's, in order; None if none is."""
    want = list(zip(prompt_lens, released))
    keys = [(r[1], r[2]) for r in log]
    n = len(want)
    for i in range(len(keys) - n, -1, -1):
        if keys[i:i + n] == want:
            return log[i:i + n]
    return None


def read(ctx):
    c = ctx["counters"]
    if not c.get("released"):
        return None
    try:
        from repro.obs.trace import RELEASED
    except ImportError:
        return None
    rows = window_rows(list(RELEASED), c["prompt_lens"], c["released"])
    if rows is None:
        print("slot_wait_share.serve: the window's releases are not in the "
              "engine's stage-stamp log; left out", file=sys.stderr)
        return None
    total = sum(r[RELEASE] - r[SUBMIT] for r in rows)
    if total <= 0:
        return None
    return 100.0 * sum(r[PREFILL] - r[SUBMIT] for r in rows) / total
