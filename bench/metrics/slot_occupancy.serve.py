"""slot_occupancy.serve: mean share of the decode slots in use over the
window's decode steps: tokens decoded over steps times slots, from the
engines' own step and token counters read between pumps at the window's
open and close (summed over replicas)."""


def read(ctx):
    c = ctx["counters"]
    per_replica = ctx["counters"]["capacity"] / len(c["steps"])
    slot_steps = sum(s * per_replica for s in c["steps"])
    if slot_steps <= 0:
        return None
    return 100.0 * sum(c["tokens_out"]) / slot_steps
