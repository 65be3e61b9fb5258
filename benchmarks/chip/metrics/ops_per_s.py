"""ops_per_s: requests whose responses reached the host inside the
window, over the window's length (host clock)."""


def read(run):
    return run.ops_done / run.window_s if run.ops_done else None
