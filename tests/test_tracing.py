"""The runtime's own spans and scopes (core/tracing.py).

* host spans: a streaming KV run under the profiler writes trust.submit,
  trust.step and trust.consume with their children nested inside them,
  and every span of one wave carries the engine's id of that wave;
  trust.build marks exactly the rounds that made a new program;
* device scopes: the compiled solo and multiplexed rounds name their ops
  trust.serve and kv.commit (and, across four virtual devices, the
  channel's trust.pack / trust.transmit / trust.respond), and the scopes
  change nothing else: the optimized HLO without metadata is the same
  text with the scope helper turned into a no-op;
* the telemetry a round no longer rebuilds: ``last_exec`` is kept per
  compiled program, and StreamingDriver keeps no consumed wave.
"""
import contextlib
import gc
import json
import os
import re
import subprocess
import sys
import weakref

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import DelegatedKVStore, TrustSession, tracing
from repro.launch.streaming import AdmissionControl, StreamingDriver

ROWS, KEYS, WIDTH = 16, 64, 4


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _store(ses, name="kv", n_keys=KEYS):
    return DelegatedKVStore(_mesh1(), n_keys, WIDTH, session=ses, name=name,
                            capacity=ROWS)


def _wave(st, i):
    """One memcached-shaped wave: a GET lane and a PUT lane."""
    keys = (np.arange(ROWS, dtype=np.int32) * 3 + i) % KEYS
    mask = np.ones(ROWS, bool)
    st.trust.op.get.then(keys, where=mask)
    return st.trust.op.put.then(keys, np.full((ROWS, WIDTH), -i, np.float32),
                                where=mask)


def _traced_spans(path):
    """(name, start, end, wave) of every trust.* host span in a trace."""
    import glob
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    out = []
    for plane in ProfileData.from_file(found[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("trust."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                int(dict(e.stats)["wave"])))
    return out


@contextlib.contextmanager
def _profiled(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def streamed(tmp_path_factory):
    """Four waves through a depth-1 StreamingDriver, one warm wave before the
    trace: the spans, and the engine's ids of the traced waves."""
    path = tmp_path_factory.mktemp("trace")
    ses = TrustSession(donate_states=True)
    st = _store(ses)
    drv = StreamingDriver(ses, depth=1)
    drv.dispatch(outputs=[_wave(st, 0)], on_consume=lambda h: None)
    drv.drain()
    with _profiled(path):
        handles = [drv.dispatch(outputs=[_wave(st, i)],
                                on_consume=lambda h: None)
                   for i in range(1, 5)]
        drv.drain()
    return _traced_spans(path), [h.engine_wave for h in handles]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_every_span_of_a_wave_carries_its_id(streamed):
    spans, waves = streamed
    assert waves == [1, 2, 3, 4]
    for w in waves:
        names = [n for n, _s, _e, wave in spans if wave == w]
        assert names.count(tracing.SUBMIT) == 2          # a GET and a PUT
        assert names.count(tracing.STEP) == 1
        assert names.count(tracing.CONSUME) == 1
    assert {wave for *_x, wave in spans} == set(waves)


@pytest.mark.parametrize("parent,children", [
    (tracing.SUBMIT, (tracing.BIND, tracing.ROUTE)),
    (tracing.STEP, (tracing.LAUNCH,)),
    (tracing.CONSUME, (tracing.WAIT, tracing.CALLBACK))])
def test_children_nest_in_their_parent(streamed, parent, children):
    spans, waves = streamed
    for name in children:
        kids = [s for s in spans if s[0] == name]
        assert len(kids) >= len(waves), name
        for kid in kids:
            assert any(p[0] == parent and p[3] == kid[3] and _inside(kid, p)
                       for p in spans), (kid, parent)


def test_only_a_new_program_is_built(tmp_path):
    ses = TrustSession()
    st = _store(ses)
    with _profiled(tmp_path / "first"):
        _wave(st, 0)
        ses.step()
    with _profiled(tmp_path / "hit"):
        _wave(st, 1)
        ses.step()
    first = [s[0] for s in _traced_spans(tmp_path / "first")]
    hit = [s[0] for s in _traced_spans(tmp_path / "hit")]
    assert first.count(tracing.BUILD) == 1
    assert tracing.LAUNCH not in first
    assert tracing.BUILD not in hit and hit.count(tracing.LAUNCH) == 1


def _compiled_round(fused: bool = False) -> str:
    """Optimized HLO text of a memcached-shaped round (solo, or two stores
    fused into one multiplexed round)."""
    ses = TrustSession()
    st = _store(ses, "a")
    _wave(st, 1)
    if fused:
        other = _store(ses, "b")
        other.trust.op.get.then(np.arange(ROWS, dtype=np.int32))
    ses.step()
    assert ses.last_step_info["fused"] == ([["a", "b"]] if fused else [])
    raw, avals = ses.last_exec
    return jax.jit(raw).lower(*avals).compile().as_text()


def _without_metadata(text: str) -> str:
    """The module's instructions with their metadata (op names, source
    lines) stripped, and without the debug tables of source files."""
    lines = [ln for ln in text.splitlines()
             if re.match(r"^(HloModule|ENTRY|%|\}|\s+(%|ROOT))", ln)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.mark.parametrize("fused", [False, True])
def test_round_names_serve_and_commit(fused):
    names = set(re.findall(r'op_name="([^"]*)"', _compiled_round(fused)))
    paths = "\n".join(names)
    for scope in (tracing.FUSE, tracing.SERVE, tracing.RESPOND,
                  f"{tracing.KV_PUT}/{tracing.KV_COMMIT}", tracing.KV_GET):
        assert scope in paths, scope


@pytest.mark.parametrize("fused", [False, True])
def test_scopes_leave_the_program_unchanged(monkeypatch, fused):
    scoped = _compiled_round(fused)
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_round(fused)
    assert "kv.commit" in scoped and "kv.commit" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


CHILD = r"""
import json, re, sys
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import DelegatedKVStore, TrustSession
mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
ses = TrustSession()
st = DelegatedKVStore(mesh, 64, 4, session=ses, name="kv", capacity=16)
keys = np.arange(32, dtype=np.int32)
st.trust.op.get.then(keys)
st.trust.op.put.then(keys, np.ones((32, 4), np.float32))
ses.step()
raw, avals = ses.last_exec
text = jax.jit(raw).lower(*avals).compile().as_text()
print(json.dumps(sorted(set(re.findall(r'op_name="([^"]*)"', text)))))
"""


def test_channel_scopes_across_four_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    paths = "\n".join(json.loads(out.stdout.strip().splitlines()[-1]))
    for scope in (tracing.PACK, tracing.TRANSMIT, tracing.SERVE,
                  tracing.RESPOND, tracing.KV_COMMIT):
        assert scope in paths, scope
    assert re.search(r"trust\.transmit/[^\n]*all_to_all", paths), paths


def test_last_exec_is_kept_per_program():
    ses = TrustSession()
    st = _store(ses)
    _wave(st, 1)
    ses.step()
    first = ses.last_exec
    _wave(st, 2)
    ses.step()
    # a cache hit rebuilds nothing
    assert ses.last_exec[0] is first[0] and ses.last_exec[1] is first[1]
    st.trust.op.get.then(np.arange(2 * ROWS, dtype=np.int32) % KEYS)
    ses.step()
    assert ses.last_exec[1] is not first[1]   # a new program has its own
    state, dsts, _payloads = ses.last_exec[1]
    assert [d.shape for d in dsts] == [(2 * ROWS,)]
    assert state["table"].shape == (KEYS, WIDTH)


def test_consumed_handle_is_released():
    ses = TrustSession()
    st = _store(ses)
    drv = StreamingDriver(ses, depth=1)
    ref = weakref.ref(drv.dispatch(outputs=[_wave(st, 1)], rows=ROWS))
    drv.drain()
    gc.collect()
    assert ref() is None
    assert drv.stats()["waves"] == 1 and drv.stats()["rows"] == ROWS


def _old_stats(drv, handles):
    """``StreamingDriver.stats`` as it was computed from every consumed
    handle and the event log."""
    lat = [h.wave_latency_s for h in handles]
    overlapped = 0
    for kind, wid in drv.events:
        if kind != "consume":
            continue
        i = drv.events.index(("consume", wid))
        if any(k == "dispatch" and w > wid for k, w in drv.events[:i]):
            overlapped += 1
    return {"waves": len(handles), "rows": sum(h.rows for h in handles),
            "overlapped_waves": overlapped,
            "mean_wave_latency_s": sum(lat) / len(lat)}


@pytest.mark.parametrize("depth,budget", [(1, None), (0, None), (10, 16)])
def test_stats_match_the_event_log(depth, budget):
    ses = TrustSession()
    st = _store(ses)
    adm = AdmissionControl(budget) if budget else None
    drv = StreamingDriver(ses, depth=depth, admission=adm)
    handles = []
    for i in range(5):
        drv.admit(8)
        handles.append(drv.dispatch(outputs=[_wave(st, i)], rows=8))
    drv.drain()
    got, want = drv.stats(), _old_stats(drv, handles)
    for key, value in want.items():
        assert got[key] == pytest.approx(value), key
