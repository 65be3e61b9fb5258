"""round_roofline: HBM bytes the traced waves' ops require (opbytes.py),
at the chip's peak HBM rate, as a share of the device busy time."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or not run.bytes_in_window:
        return None
    least_s = run.bytes_in_window / (run.peak_hbm_bytes_per_s * t.n_devices)
    return least_s / t.busy_s * 100.0
