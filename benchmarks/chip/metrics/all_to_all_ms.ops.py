"""all_to_all_ms: device time of the all-to-all ops per wave, averaged
over the chips; nothing to read on one chip."""


def read(run):
    t = run.trace
    if t is None or t.all_to_all_s is None or not t.n_waves:
        return None
    return t.all_to_all_s / t.n_waves * 1e3
