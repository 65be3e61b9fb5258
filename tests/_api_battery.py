"""Typed-API differential battery — executed as a SUBPROCESS with 8
simulated host devices (the main pytest process stays single-device per the
dry-run protocol).

The acceptance bar for the typed delegation API (DESIGN.md §10): replaying
one ≥1k-op mixed GET/PUT/ADD/CAS trace through the TYPED op handles
(``trust.op.get.then(keys)`` — schema-routed, submit-validated) must be
bit-identical to the legacy STRINGLY path (``trust.submit("get", dst,
{"key": ...})`` with hand-built dst/payload) — every response batch and the
final table — across shared / shared+shortcut / dedicated modes ×
pack_impl {ref, pallas} × serve_impl {ref, pallas, masked}.  Additionally,
a solo typed round must lower to the same jaxpr collective count as the
legacy round (they share ONE compiled program — the schema-identity cache
key — so this is checked both by cache hits and by counting all_to_all
eqns), and a typed multiplexed engine step keeps the §8 guarantee of
exactly 1 request all_to_all + 1 response transpose.

Prints one JSON dict of named check results; tests/test_api_battery.py
asserts on them.
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


N_KEYS = 37          # prime: exercises owner-shard padding
VW = 2               # value width
R = 64               # rows per op batch
N_ROUNDS = 4         # 4 rounds x 4 ops x 64 rows = 1024 ops >= the floor
N_HOT = 5            # conflict-heavy key space


def mesh2x4():
    return Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))


def gen_trace(seed):
    """Per round one batch per op, keys squeezed onto N_HOT hot keys so
    every conflict-resolution path (last-writer, priors, CAS winners) is
    exercised.  Integer-valued float payloads keep adds bit-exact."""
    rng = np.random.default_rng(seed)
    init = rng.integers(0, 8, (N_KEYS, VW)).astype(np.float32)
    rounds = []
    for _ in range(N_ROUNDS):
        batches = {}
        for op in ("get", "put", "add", "cas"):
            keys = rng.integers(0, N_HOT, R).astype(np.int32)
            vals = rng.integers(0, 8, (R, VW)).astype(np.float32)
            expect = rng.integers(0, 8, (R, VW)).astype(np.float32)
            batches[op] = (keys, vals, expect)
        rounds.append(batches)
    return init, rounds


def drive_legacy(st, rounds):
    """The stringly path: hand-built dst (store router) + payload dicts
    through the ``submit`` shim — the pre-refactor API, byte for byte."""
    outs = []
    for batches in rounds:
        gk, _gv, _ge = batches["get"]
        pk, pv, _pe = batches["put"]
        ak, av, _ae = batches["add"]
        ck, cv, ce = batches["cas"]
        fg = st.trust.submit("get", st.route(jnp.asarray(gk)),
                             st._payload(jnp.asarray(gk)))
        st.trust.submit("put", st.route(jnp.asarray(pk)),
                        st._payload(jnp.asarray(pk), jnp.asarray(pv)))
        fa = st.trust.submit("add", st.route(jnp.asarray(ak)),
                             st._payload(jnp.asarray(ak), jnp.asarray(av)))
        fc = st.trust.submit("cas", st.route(jnp.asarray(ck)),
                             st._payload(jnp.asarray(ck), jnp.asarray(cv),
                                         jnp.asarray(ce)))
        st.flush()
        outs.append({"get": np.asarray(fg.result()["value"]),
                     "add": np.asarray(fa.result()["value"]),
                     "cas": (np.asarray(fc.result()["flag"]),
                             np.asarray(fc.result()["value"]))})
    return outs, st.dump()


def drive_typed(st, rounds):
    """The typed path: generated op handles, schema-routed and validated."""
    op = st.trust.op
    outs = []
    for batches in rounds:
        gk, _gv, _ge = batches["get"]
        pk, pv, _pe = batches["put"]
        ak, av, _ae = batches["add"]
        ck, cv, ce = batches["cas"]
        fg = op.get.then(jnp.asarray(gk))
        op.put.then(jnp.asarray(pk), jnp.asarray(pv))
        fa = op.add.then(jnp.asarray(ak), jnp.asarray(av))
        fc = op.cas.then(jnp.asarray(ck), value=jnp.asarray(cv),
                         expect=jnp.asarray(ce))
        st.flush()
        outs.append({"get": np.asarray(fg.result()["value"]),
                     "add": np.asarray(fa.result()["value"]),
                     "cas": (np.asarray(fc.result()["flag"]),
                             np.asarray(fc.result()["value"]))})
    return outs, st.dump()


def make_store(mode_kw, pack_impl, serve_impl):
    from repro.core import DelegatedKVStore
    return DelegatedKVStore(mesh2x4(), N_KEYS, VW, capacity=R,
                            pack_impl=pack_impl, serve_impl=serve_impl,
                            **mode_kw)


def run_differential(mode_kw, seed, what):
    """Typed bit-identical to legacy for every pack × serve combination.

    The legacy baseline runs once per mode with (ref, ref); legacy
    responses are impl-invariant (tests/_diff_battery.py pins all impls to
    the sequential oracle), so each typed run compares against it."""
    init, rounds = gen_trace(seed)
    base = make_store(mode_kw, "ref", "ref")
    base.prefill(init)
    want, want_table = drive_legacy(base, rounds)
    for pack in ("ref", "pallas"):
        for serve in ("ref", "pallas", "masked"):
            st = make_store(mode_kw, pack, serve)
            st.prefill(init)
            got, got_table = drive_typed(st, rounds)
            tag = f"{what}/pack={pack}/serve={serve}"
            for i, (g, w) in enumerate(zip(got, want)):
                assert np.array_equal(g["get"], w["get"]), f"{tag} r{i}: get"
                assert np.array_equal(g["add"], w["add"]), f"{tag} r{i}: add"
                assert np.array_equal(g["cas"][0], w["cas"][0]), \
                    f"{tag} r{i}: cas flags"
                assert np.array_equal(g["cas"][1], w["cas"][1]), \
                    f"{tag} r{i}: cas old"
            assert np.array_equal(got_table, want_table), f"{tag}: table"


@check("typed_matches_stringly_shared")
def _shared():
    run_differential({"local_shortcut": False}, seed=60, what="shared")


@check("typed_matches_stringly_shortcut")
def _shortcut():
    run_differential({"local_shortcut": True}, seed=61, what="shortcut")


@check("typed_matches_stringly_dedicated")
def _dedicated():
    run_differential({"mode": "dedicated", "n_dedicated": 3}, seed=62,
                     what="dedicated")


# ---------------------------------------------------------------------------
# Program identity + collective counts (acceptance criterion)
# ---------------------------------------------------------------------------

def count_all_to_all(fn, args) -> int:
    return count_primitive(jax.make_jaxpr(fn)(*args), "all_to_all")


@check("typed_solo_same_collectives_as_legacy")
def _solo_collectives():
    """A solo typed round shares the legacy round's compiled program (the
    schema-identity cache key) and lowers to the same jaxpr collective
    count."""
    from repro.core import DelegatedKVStore
    init = np.zeros((N_KEYS, VW), np.float32)
    keys = np.arange(16, dtype=np.int32)
    st = DelegatedKVStore(mesh2x4(), N_KEYS, VW, capacity=R,
                          local_shortcut=False)
    st.prefill(init)
    eng = st.session
    st.trust.apply("get", st.route(jnp.asarray(keys)),
                   st._payload(jnp.asarray(keys)))
    n_legacy = count_all_to_all(*eng.last_exec)
    n_cache = len(eng._cache)
    st.trust.op.get(jnp.asarray(keys))
    assert len(eng._cache) == n_cache, "typed round missed the program cache"
    n_typed = count_all_to_all(*eng.last_exec)
    assert n_typed == n_legacy, \
        f"typed round lowers {n_typed} all_to_all vs legacy {n_legacy}"


@check("typed_mux_one_request_one_response")
def _mux_collectives():
    """A typed multiplexed engine step keeps the §8 lowering: EXACTLY one
    request all_to_all + one response transpose."""
    from repro.core import DelegatedKVStore, TrustSession
    ses = TrustSession()
    kw = dict(capacity=R, local_shortcut=False, overflow="drop", session=ses)
    a = DelegatedKVStore(mesh2x4(), N_KEYS, VW, name="a", **kw)
    b = DelegatedKVStore(mesh2x4(), N_KEYS, VW, name="b", **kw)
    keys = jnp.arange(16, dtype=jnp.int32)
    ones = jnp.ones((16, VW), jnp.float32)
    fa = a.trust.op.add.then(keys, ones)
    fb = b.trust.op.add.then(keys, ones)
    ses.step()
    assert fa.ready() and fb.ready()
    assert ses.last_step_info["fused"] == [["a", "b"]], ses.last_step_info
    n = count_all_to_all(*ses.last_exec)
    assert n == 2, f"expected 1 request all_to_all + 1 response " \
                   f"transpose, found {n}"


if __name__ == "__main__":
    print(json.dumps(RESULTS))
