"""Trace reduction of the chip benchmark, on a small trace with known
intervals, and the xplane reader on a trace recorded on the CPU."""
from __future__ import annotations

import json
import os

import pytest

import _chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import tracecut

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic() -> tracecut.RawTrace:
    with open(os.path.join(DATA, "synthetic_trace.json")) as f:
        d = json.load(f)
    return tracecut.RawTrace(
        host=[tuple(e) for e in d["host"]],
        devices={int(k): [tuple(e) for e in v]
                 for k, v in d["devices"].items()})


def test_reduce_known_intervals():
    r = tracecut.reduce(synthetic())
    ns = 1e-9
    assert r.window_s == pytest.approx(10000 * ns)
    assert r.n_waves == 2 and r.n_devices == 2
    # device 0: [1000,1100) + [1500,3000) + [6200,8000) + [10800,11000)
    # = 3600 ns; device 1: [1000,3000) + [7000,8000) = 3000 ns
    assert r.busy_s == pytest.approx(3300 * ns)
    assert r.all_to_all_s == pytest.approx(900 * ns)
    assert dict(r.device_ops) == pytest.approx(
        {"fusion.1": 1750 * ns, "fusion.2": 900 * ns,
         "all-to-all.3": 900 * ns, "copy.1": 50 * ns})
    # device 0's gaps: [3000,6200) mid 4600 in dispatch only,
    # [8000,10800) mid 9400 in wait inside drain, [1100,1500) in submit
    assert [lab for lab, _ in r.idle_gaps] == ["dispatch", "wait", "submit"]
    assert [g for _, g in r.idle_gaps] == pytest.approx(
        [3200 * ns, 2800 * ns, 400 * ns])
    # (submit 2000 + dispatch 6000 + drain 1500 - wait 3800
    #  - receive 800) / 2 waves
    assert tracecut.host_s_per_wave(r) == pytest.approx(2450 * ns)


def test_window_starts_when_every_device_records():
    """A device tracer that starts late: the window and its waves count
    from the first op of the last device to start recording."""
    raw = synthetic()
    raw.devices = {d: [e for e in evs if e[1] >= 6000]
                   for d, evs in raw.devices.items()}
    r = tracecut.reduce(raw)
    assert r.window_s == pytest.approx(4000e-9)       # [7000, 11000)
    assert r.n_waves == 0 and tracecut.host_s_per_wave(r) is None
    assert r.busy_s == pytest.approx((1000 + 200 + 1000) / 2 * 1e-9)


@pytest.mark.parametrize("name", ["all-to-all.3", "all_to_all.62"])
def test_all_to_all_op_counts(name):
    """The HLO text says ``all-to-all``; the TPU names its ops
    ``all_to_all.N``: both count in ``all_to_all_s``."""
    raw = synthetic()
    raw.devices = {d: [(name if n == "all-to-all.3" else n, s, t)
                       for n, s, t in evs]
                   for d, evs in raw.devices.items()}
    r = tracecut.reduce(raw)
    assert r.all_to_all_s == pytest.approx(900e-9)
    assert dict(r.device_ops)[name] == pytest.approx(900e-9)


def test_merge_unions_overlaps():
    import numpy as np
    iv = np.array([[5, 7], [1, 3], [2, 4], [7, 8], [10, 11]], float)
    assert tracecut.merge(iv).tolist() == [[1, 4], [5, 8], [10, 11]]


def test_reduce_needs_one_window_and_a_device():
    raw = synthetic()
    with pytest.raises(ValueError):
        tracecut.reduce(tracecut.RawTrace(raw.host[1:], raw.devices))
    with pytest.raises(ValueError):
        tracecut.reduce(tracecut.RawTrace(raw.host, {}))


def test_read_xplane_finds_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    raw = tracecut.read_xplane(str(tmp_path))
    names = [n for n, _, _ in raw.host]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3
    assert raw.devices == {}            # the CPU has no TPU plane


@pytest.mark.parametrize("text,want", [
    ("%broadcast_select_fusion = f32[1000000,4]{1,0:T(8,128)} fusion(f32[1]"
     " %a), kind=kLoop", "broadcast_select_fusion f32[1000000,4]"),
    ("%sort.11 = (s32[16385]{0:T(1024)S(1)}, s32[16385]{0}) sort(%x)",
     "sort.11"),
    ("%all-to-all.3 = f32[4,128]{1,0} all-to-all(f32[4,128]{1,0} %p)",
     "all-to-all.3 f32[4,128]"),
    ("%all_to_all.62 = f32[4,8192,250]{2,1,0:T(8,128)} all-to-all(f32[4,"
     "8192,250]{2,1,0:T(8,128)} %p)", "all_to_all.62 f32[4,8192,250]"),
    ("fusion.1", "fusion.1")])
def test_op_name_keeps_name_and_shape(text, want):
    assert tracecut.op_name(text) == want
