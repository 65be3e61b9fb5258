"""round_ms: device busy time (union of op intervals) in the traced
window over the waves dispatched in it, averaged over the chips."""


def read(run):
    t = run.trace
    if t is None or not t.n_waves or t.busy_s <= 0:
        return None
    return t.busy_s / t.n_waves * 1e3
