"""The reduction of the program's own spans and scopes (programcut.py), on
the synthetic trace's window with known program spans and scoped device
ops, and the reader on a streaming run traced on the CPU."""
from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

import _chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import programcut
import run as bench_run
import tracecut
from harness import Run
from test_chip_tracecut import DATA, synthetic

NS = 1e-9


def program() -> programcut.ProgramTrace:
    with open(os.path.join(DATA, "synthetic_program_trace.json")) as f:
        d = json.load(f)
    return programcut.ProgramTrace(
        spans=[tuple(e) for e in d["spans"]],
        devices={int(k): [tuple(e) for e in v]
                 for k, v in d["devices"].items()})


def reading() -> programcut.ProgramReading:
    raw = synthetic()
    return programcut.reduce(raw, program(), tracecut.reduce(raw).n_waves)


def test_device_time_by_scope():
    r = reading()
    # window [1000, 11000); per device, summed over both devices / 2:
    # serve = dev0 1000 + 1800 + 500, dev1 2000; put and commit = the two
    # fusion.1 of dev0 and dev1's; transmit = the all-to-alls; (none) =
    # the copy
    assert r.scope_s == pytest.approx({
        "trust.serve": 2650 * NS, "kv.put": 1750 * NS,
        "kv.commit": 1750 * NS, "kv.get": 900 * NS,
        "trust.transmit": 900 * NS, programcut.NONE: 50 * NS})
    assert r.n_devices == 2 and r.n_waves == 2


def test_program_spans_and_builds_in_the_window():
    r = reading()
    # the build at 500 lies before the window
    assert r.n_builds == 1
    assert r.span_s == pytest.approx({
        "trust.submit": 1600 * NS, "trust.bind": 600 * NS,
        "trust.route": 600 * NS, "trust.step": 1100 * NS,
        "trust.build": 500 * NS, "trust.launch": 300 * NS,
        "trust.consume": 2900 * NS, "trust.wait": 2300 * NS,
        "trust.callback": 400 * NS})


def test_per_wave_numbers():
    # two waves: submit 1600 ns, step 1100 ns, serve 2650 ns, commit
    # 1750 ns, channel (pack, transmit, respond) 900 ns per device
    assert programcut.per_wave_ms(reading()) == pytest.approx({
        "submit": 800e-6, "step": 550e-6, "serve": 1325e-6,
        "commit": 875e-6, "channel": 450e-6})


def test_one_chip_has_no_channel_and_no_waves_read_nothing():
    r = reading()
    one = copy.copy(r)
    one.n_devices = 1
    assert "channel" not in programcut.per_wave_ms(one)
    one.n_waves = 0
    assert programcut.per_wave_ms(one) == {}


def test_ops_and_gaps_carry_the_program_words():
    r = reading()
    assert dict(r.device_ops) == pytest.approx({
        "kv.commit: fusion.1": 1750 * NS, "kv.get: fusion.2": 900 * NS,
        "trust.transmit: all-to-all.3": 900 * NS,
        "(none): copy.1": 50 * NS})
    # device 0's gaps: [3000,6200) mid 4600 in dispatch and trust.build,
    # [8000,10800) mid 9400 in the drain's wait and wave 1's trust.wait,
    # [1100,1500) mid 1300 in submit and trust.bind
    assert [lab for lab, _ in r.idle_gaps] == [
        "dispatch/trust.build", "wait/trust.wait", "submit/trust.bind"]
    base = tracecut.reduce(synthetic())
    assert [g for _, g in r.idle_gaps] == [g for _, g in base.idle_gaps]


def test_stalls_name_the_spans_that_hold_them():
    out = programcut.stalls(synthetic(), program(), 3500 * NS)
    # the dispatches start at 2000 and 6000 ns: one gap of 4000 ns
    assert len(out) == 1 and out[0]["gap_ms"] == pytest.approx(4000e-6)
    assert [n for n, _ in out[0]["spans"]] == [
        "bench.dispatch", "bench.wait", "bench.submit", "trust.submit"]
    assert [t for _, t in out[0]["spans"]] == pytest.approx(
        [3000e-6, 1500e-6, 1000e-6, 800e-6])
    assert programcut.stalls(synthetic(), program(), 5000 * NS) == []


def test_existing_numbers_are_untouched():
    """Reading the program's part leaves the trace and every existing
    reader's number as they were."""
    with open(os.path.join(_chip_tiny.ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]

    def readings(tr):
        run = Run(setup_s=1.0, window_s=10000 * NS, ops_done=10,
                  latencies_s=None, bytes_in_window=1 << 20,
                  peak_hbm_bytes_per_s=819e9, trace=tr)
        return {n: bench_run.reader(n)(run) for n in names}
    raw = synthetic()
    before = tracecut.reduce(raw)
    want = readings(before)
    programcut.reduce(raw, program(), before.n_waves)
    after = tracecut.reduce(raw)
    assert after == before and readings(after) == want
    assert want["round_ms.ops"] == pytest.approx(3300 * NS / 2 * 1e3)


@pytest.mark.parametrize("path,want", [
    ("jit(fused)/trust.serve/kv.put/kv.commit/jit(_where)/select_n",
     "trust.serve/kv.put/kv.commit"),
    ("jit(fused)/trust.fuse/concatenate", "trust.fuse"),
    ("jit(fused)/add", "")])
def test_scope_path(path, want):
    assert programcut.scope_path(path) == want


def test_scopes_from_hlo_text():
    text = (
        '  %copy.6 = f32[8,4]{1,0} copy(f32[8,4]{1,0} %p)\n'
        '  ROOT %broadcast_select_fusion = f32[8,4]{1,0} fusion(%a), '
        'kind=kLoop, calls=%fc, metadata={op_name="jit(fused)/trust.serve/'
        'kv.put/kv.commit/select_n" stack_frame_id=3}\n'
        '  %sort.11 = (s32[16]{0}, s32[16]{0}) sort(%x), '
        'metadata={op_name="jit(fused)/trust.serve/sort"}\n')
    assert programcut.scopes_from_hlo(text) == {
        "broadcast_select_fusion": "trust.serve/kv.put/kv.commit",
        "sort.11": "trust.serve"}


def test_read_finds_the_program_spans_on_the_cpu(tmp_path):
    """A streaming KV run traced on the CPU: trust.* spans, each with the
    engine's wave id; the CPU has no TPU plane, so no device ops."""
    import jax
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore, TrustSession
    from repro.launch.streaming import StreamingDriver
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    ses = TrustSession()
    st = DelegatedKVStore(mesh, 64, 4, session=ses, name="kv", capacity=16)
    drv = StreamingDriver(ses)
    keys = np.arange(16, dtype=np.int32)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        drv.dispatch(outputs=[st.trust.op.get.then(keys)])
    drv.drain()
    jax.profiler.stop_trace()
    prog = programcut.read(str(tmp_path))
    assert prog.devices == {}
    by_name = {}
    for name, _s, _d, wave in prog.spans:
        by_name.setdefault(name, []).append(wave)
    assert by_name["trust.submit"] == [0, 1, 2]
    assert by_name["trust.step"] == [0, 1, 2]
    assert sorted(by_name["trust.consume"]) == [0, 1, 2]
    assert by_name["trust.build"] == [0]
