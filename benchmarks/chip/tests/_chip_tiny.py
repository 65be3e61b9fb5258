"""Tiny cells for the chip benchmark's CPU tests: the harness's own loop
(``run.run``) without its look for a chip, at sizes a test run holds."""
from __future__ import annotations

import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402
from harness import CellSpec  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9}


def tiny_spec(config: str, traffic: str, chips: int = 1, **traffic_kw):
    """A cell of BENCHMARK.json's own files, cut to a test's size: the
    widths and key distribution stay, the key count and wave shrink."""
    with open(os.path.join(CHIP, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CHIP, "traffic", traffic + ".json")) as f:
        trf = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg.update(n_keys=4096 * chips + 17, capacity=256,
               mesh=[1, chips])
    trf.update(wave_rows=256, distinct_waves=4, **traffic_kw)
    if trf["loop"] == "open":
        trf["rate_ops_per_s"] = 20000
    return CellSpec(f"tiny.{config}.{traffic}", chips, cfg, trf)


# the end-to-end readers a tiny run reports (each reads what its loop has)
METRICS = [{"name": "ops_per_s", "unit": "ops/s"},
           {"name": "p99_ms", "unit": "ms"},
           {"name": "setup_s", "unit": "s"}]


def run_tiny(spec, seed=2**31 + 11, seconds=0.5, control=None):
    import jax
    return bench_run.run(spec, seed, seconds, False,
                         jax.devices()[:spec.chips], PEAKS, METRICS,
                         time.perf_counter(), control)
