"""protection_share.serve: the share of the traced window that the
protection layer holds the chip, averaged over chips.  Two parts:

  * device: busy time inside the engine's protection programs, the
    decode-state checksums of the pre-decode scrub, the post-mutation
    refresh and the snapshot (``jit_state_checksums``) and the in-serve
    weight verify (``jit_storage_verify``);
  * host: the device's idle gaps whose middle lies inside one of the
    engine's protection spans on the host (``engine/state_scrub``,
    ``engine/state_refresh``, ``engine/snapshot``, ``engine/storage_scrub``;
    ``trace_reduce`` names each gap by the host spans over its middle, so a
    gap counts whole or not at all).

The FFN's ABFT checks are fused into the decode and prefill programs and
are not in it.  A trace with neither protection program (a program that
does not name them) leaves the metric out of the line."""
import sys

import trace_reduce

PROGRAMS = ("jit_state_checksums", "jit_storage_verify")
SPANS = ("engine/state_scrub", "engine/state_refresh", "engine/snapshot",
         "engine/storage_scrub")


def in_protection_span(label: str) -> bool:
    """Does a gap's host label (spans outermost first, joined by " > ")
    name a protection span?"""
    return any(part in SPANS for part in label.split(" > "))


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.chips or s.window_s <= 0:
        return None
    if not any(m in c.modules for c in s.chips for m in PROGRAMS):
        print(f"protection_share.serve: none of {PROGRAMS} in the traced "
              f"window; left out", file=sys.stderr)
        return None
    device = sum(trace_reduce.busy_in(c, m, s.lo_ns, s.hi_ns)
                 for c in s.chips for m in PROGRAMS) / len(s.chips)
    host = sum(sec for label, sec in s.gaps if in_protection_span(label))
    return 100.0 * (device + host) / s.window_s
