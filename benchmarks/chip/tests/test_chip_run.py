"""The chip benchmark's own loop, end to end on the CPU at a tiny size:
sound runs come out correct, the bfloat16 control does not.  The chip's
look for a TPU is skipped; everything after it runs as on the chip."""
from __future__ import annotations

import pytest

from _chip_tiny import run_tiny, tiny_spec


@pytest.mark.parametrize("config,traffic", [
    ("memcached16", "memcached_zipf"), ("memcached16", "memcached_open"),
    ("ycsb1kb", "ycsb_a")])
def test_tiny_cell_runs_correct(config, traffic):
    out = run_tiny(tiny_spec(config, traffic))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["setup_s"]["value"] > 0
    key = "p99_ms" if traffic.endswith("open") else "ops_per_s"
    assert m[key]["value"] > 0
    assert list(out)[-1] == "checks"


def test_mixed_ops_run_correct():
    """ADD and CAS lanes, which later traffic files may ask for."""
    spec = tiny_spec("memcached16", "memcached_zipf",
                     op_shares={"get": 6, "put": 2, "add": 1, "cas": 1})
    out = run_tiny(spec)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"answers_wrong", "final_rows_wrong"}


@pytest.mark.parametrize("traffic", ["memcached_zipf", "memcached_open"])
def test_bf16_control_is_not_correct(traffic):
    """The control: the program with a bfloat16 table, the nearest
    precision below the configuration's float32."""
    out = run_tiny(tiny_spec("memcached16", traffic), control="bf16")
    assert not out["correct"]
    assert out["checks"]["answers_wrong"]["value"] > 0
    assert out["checks"]["final_rows_wrong"]["value"] > 0
