"""Engine (multiplexed-round) test battery — executed as a SUBPROCESS with
8 simulated host devices (the main pytest process keeps a single device per
the dry-run protocol).

Coverage (ISSUE satellite: engine differential battery):

* a multiplexed ``session.step()`` round over >= 2 Trusts (a delegated KV
  store + a lock-analog-backed store) is bit-identical to sequential
  per-Trust ``apply`` calls, across shared / shared+shortcut / dedicated
  modes and both ``pack_impl``s;
* one engine step lowers to exactly ONE request ``all_to_all`` plus one
  response transpose (jaxpr inspection of the fused program);
* per-trust stats ({name: {rounds, residual, demand_max}}) and the defer
  drain engine through the multiplexed path (tuple-of-states drain).

Ordering note (DESIGN.md §8): the engine lays the fused batch out
trust-major, so each trust's serve order still equals its own batch order —
EXCEPT under the local shortcut, where the set of self-addressed rows
depends on the row->client layout.  Shortcut and drain checks therefore use
per-round distinct keys (order-free), mirroring the §4 testing strategy.

Prints one JSON dict of named check results; tests/test_engine.py asserts.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import json
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from _jaxpr import count_primitive

RESULTS = {}


def check(name):
    def deco(fn):
        try:
            fn()
            RESULTS[name] = {"ok": True}
        except Exception as e:                                # noqa: BLE001
            RESULTS[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}",
                             "trace": traceback.format_exc()[-1500:]}
        return fn
    return deco


N_KEYS = 67          # prime: exercises owner-shard padding
VW = 2
R = 48               # rows per batch (fits R distinct keys in N_KEYS)
N_ROUNDS = 8


def mesh2x4():
    return Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))


def gen_trace(seed, n_rounds=N_ROUNDS, distinct=False):
    """Per-trust random op trace.  ``distinct=True`` draws each round's keys
    without replacement so results are independent of intra-round serve
    order (required for shortcut/drain layouts, see module docstring)."""
    rng = np.random.default_rng(seed)
    init = rng.integers(1, 8, (N_KEYS, VW)).astype(np.float32)
    rounds = []
    for _ in range(n_rounds):
        op = ["get", "put", "add", "cas"][int(rng.integers(0, 4))]
        if distinct:
            keys = rng.choice(N_KEYS, R, replace=False).astype(np.int32)
        else:
            keys = rng.integers(0, N_KEYS, R).astype(np.int32)
        vals = rng.integers(0, 8, (R, VW)).astype(np.float32)
        expect = rng.integers(0, 8, (R, VW)).astype(np.float32)
        rounds.append((op, keys, vals, expect))
    return init, rounds


def _payload(store, op, keys, vals, expect):
    p = {"key": jnp.asarray(keys, jnp.int32)}
    if op in ("put", "add", "cas"):
        p["value"] = jnp.asarray(vals)
    if op == "cas":
        p["expect"] = jnp.asarray(expect)
    return p


def _normalize(op, resp):
    if op == "cas":
        return (np.asarray(resp["flag"]), np.asarray(resp["value"]))
    return np.asarray(resp["value"])


def drive_sequential(stores, traces):
    """Per-Trust apply calls, one solo channel round per (store, round)."""
    outs = [[] for _ in stores]
    for rnd in range(len(traces[0][1])):
        for i, (st, (init, rounds)) in enumerate(zip(stores, traces)):
            op, keys, vals, expect = rounds[rnd]
            resp = st.trust.apply(op, st.route(jnp.asarray(keys)),
                                  _payload(st, op, keys, vals, expect))
            outs[i].append(_normalize(op, resp))
    return outs


def drive_fused(stores, traces, session):
    """Same trace, ONE multiplexed engine round per trace round."""
    outs = [[] for _ in stores]
    for rnd in range(len(traces[0][1])):
        futs = []
        for st, (init, rounds) in zip(stores, traces):
            op, keys, vals, expect = rounds[rnd]
            futs.append((op, st.trust.submit(
                op, st.route(jnp.asarray(keys)),
                _payload(st, op, keys, vals, expect))))
        session.step()
        names = [n for grp in session.last_step_info["fused"] for n in grp]
        assert all(st.trust.name in names for st in stores), \
            f"step did not fuse: {session.last_step_info}"
        for i, (op, fut) in enumerate(futs):
            outs[i].append(_normalize(op, fut.result()))
    return outs


def make_pair(mode_kw, **extra):
    """Two Trusts sharing one channel signature: a delegated KV store plus a
    lock-analog (FetchRMWStore) inner table.  The lock analogs hard-disable
    the local shortcut, so the shortcut combo pairs two KV stores instead
    (signatures must match for the engine to fuse)."""
    from repro.core import DelegatedKVStore, FetchRMWStore, TrustSession
    session = TrustSession()
    mesh = mesh2x4()
    kw = dict(capacity=R)
    kw.update(mode_kw)
    kw.update(extra)
    shortcut = kw.get("local_shortcut", True) \
        and kw.get("mode", "shared") != "dedicated"
    lkw = {k: v for k, v in kw.items() if k != "local_shortcut"}

    def build(ses):
        kv = DelegatedKVStore(mesh, N_KEYS, VW, name="kv", session=ses, **kw)
        if shortcut:
            other = DelegatedKVStore(mesh, N_KEYS, VW, name="kv2",
                                     session=ses, **kw)
        else:
            other = FetchRMWStore(mesh, N_KEYS, VW, session=ses, **lkw).store
        return kv, other

    fused_stores = build(session)
    # reference stores in their own session (solo applies never fuse)
    seq_stores = build(TrustSession())
    return session, fused_stores, seq_stores


def run_pair(mode_kw, seeds, distinct=False, **extra):
    session, fused_stores, seq_stores = make_pair(mode_kw, **extra)
    traces = [gen_trace(s, distinct=distinct) for s in seeds]
    for st_f, st_s, (init, _r) in zip(fused_stores, seq_stores, traces):
        st_f.prefill(init)
        st_s.prefill(init)
    want = drive_sequential(seq_stores, traces)
    got = drive_fused(fused_stores, traces, session)
    for i, (g_rounds, w_rounds) in enumerate(zip(got, want)):
        for rnd, (g, w) in enumerate(zip(g_rounds, w_rounds)):
            if isinstance(g, tuple):
                assert np.array_equal(g[0], w[0]), \
                    f"store {i} round {rnd}: cas flags differ"
                assert np.array_equal(g[1], w[1]), \
                    f"store {i} round {rnd}: cas old values differ"
            else:
                assert np.array_equal(g, w), \
                    f"store {i} round {rnd}: responses differ"
    for i, (st_f, st_s) in enumerate(zip(fused_stores, seq_stores)):
        assert np.array_equal(st_f.dump(), st_s.dump()), \
            f"store {i}: final tables differ"
    return session


# ---------------------------------------------------------------------------
@check("mux_shared_matches_sequential")
def _shared():
    """Conflict-heavy trace: in shared mode without the shortcut the fused
    trust-major layout preserves each trust's serve order exactly."""
    run_pair({"local_shortcut": False, "overflow": "drop"}, seeds=(10, 11))


@check("mux_shared_shortcut_matches_sequential")
def _shared_shortcut():
    run_pair({"local_shortcut": True, "overflow": "drop"}, seeds=(12, 13),
             distinct=True)


@check("mux_dedicated_matches_sequential")
def _dedicated():
    ses = run_pair({"mode": "dedicated", "n_dedicated": 3,
                    "overflow": "drop"}, seeds=(14, 15))
    stats = ses.last_stats()
    assert set(stats) >= {"kv", "rmw-lock"}, stats


@check("mux_pallas_matches_sequential")
def _pallas():
    run_pair({"local_shortcut": False, "overflow": "drop",
              "pack_impl": "pallas"}, seeds=(16, 17))


@check("mux_single_all_to_all")
def _jaxpr():
    """One engine step over 2 trusts lowers to EXACTLY one request
    all_to_all plus one response transpose (2 total)."""
    ses = run_pair({"local_shortcut": False, "overflow": "drop"},
                   seeds=(18, 19))
    fn, args = ses.last_exec
    n = count_primitive(jax.make_jaxpr(fn)(*args), "all_to_all")
    assert n == 2, f"expected 1 request all_to_all + 1 response " \
                   f"transpose, found {n} all_to_all eqns"


@check("mux_per_trust_stats")
def _stats():
    ses = run_pair({"local_shortcut": False, "overflow": "drop"},
                   seeds=(20, 21))
    stats = ses.last_stats()
    assert set(stats) == {"kv", "rmw-lock"}, stats
    for name, d in stats.items():
        assert set(d) == {"rounds", "residual", "demand_max",
                          "resp_bytes_saved", "rows_combined",
                          "req_bytes_saved", "impl_fallback"}, d
        assert d["rounds"] == 1 and d["residual"] == 0, (name, d)
        assert d["demand_max"] >= 1, (name, d)
        # ref serve on f32 tables: no trace-time impl downgrade fired
        assert d["impl_fallback"] == 0, (name, d)
        # both stores GET+ADD in this round: only the flag plane elides,
        # and the fused round reports the shared per-round saving
        assert d["resp_bytes_saved"] >= 0, (name, d)
        # combine off (the default): the stats keys are still present,
        # zero-filled, so consumers never KeyError
        assert d["rows_combined"] == 0 and d["req_bytes_saved"] == 0, \
            (name, d)


@check("mux_defer_drain_matches_sequential")
def _defer():
    """Multi-state drain: capacity=2 + defer through the MULTIPLEXED round
    drains to the same result as solo defer rounds (distinct keys per
    round: the inter-round interleaving is order-free, DESIGN.md §4/§8)."""
    ses = run_pair({"local_shortcut": False, "overflow": "defer",
                    "max_rounds": 16}, seeds=(22, 23), distinct=True,
                   capacity=2)
    stats = ses.last_stats()
    for name, d in stats.items():
        assert d["residual"] == 0, (name, d)
        assert d["rounds"] >= 1, (name, d)


@check("mux_capacity_planner_adapts")
def _planner():
    """Auto-capacity multiplexed rounds consult the EMA planner: after the
    first observed round the planned capacity tracks realized demand
    (quantized pow2), not the static 2x-mean rule."""
    from repro.core import DelegatedKVStore, TrustSession
    from repro.core import meshctx
    session = TrustSession()
    mesh = mesh2x4()
    with meshctx.use_session(session):
        a = DelegatedKVStore(mesh, N_KEYS, VW, local_shortcut=False,
                             name="a")
        b = DelegatedKVStore(mesh, N_KEYS, VW, local_shortcut=False,
                             name="b")
    init = np.ones((N_KEYS, VW), np.float32)
    a.prefill(init)
    b.prefill(init)
    rng = np.random.default_rng(0)
    caps = []
    for _ in range(4):
        keys = rng.integers(0, N_KEYS, R).astype(np.int32)
        vals = np.ones((R, VW), np.float32)
        a.add_then(jnp.asarray(keys), jnp.asarray(vals))
        b.add_then(jnp.asarray(keys), jnp.asarray(vals))
        session.step()
        sig = ("mux", session._mux_signature(a.trust))
        caps.append(session.planner.plan(sig, fallback=-1))
    assert caps[0] == -1 or caps[0] > 0   # first plan may predate history
    assert caps[-1] > 0, caps             # EMA engaged after observations
    assert caps[-1] & (caps[-1] - 1) == 0, f"not pow2-quantized: {caps}"
    ema = session.planner.ema(("mux", session._mux_signature(a.trust)))
    assert ema is not None and ema > 0


if __name__ == "__main__":
    print(json.dumps(RESULTS))
