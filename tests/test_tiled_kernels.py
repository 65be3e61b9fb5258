"""Tiled serve/pack kernel batteries (DESIGN.md §12).

The tiled kernels replace the retired dense single-block Pallas layer; the
invariant is BIT-IDENTITY to the shared-grouping lax reference across every
adversarial Grouping segment layout the tiling has to survive:

  * one segment spanning every row (the carry chains through all tiles)
  * all-distinct keys (every segment is a singleton; no carry ever fires)
  * fully-dropped tiles (whole row tiles of invalid rows)
  * non-power-of-two R landing mid-tile (padding rows behind real ones)

plus the structural claims the refactor makes: multi-block grids actually
engage for R > block size, and no (N, N) / (N, K) dense intermediate
appears anywhere in the lowered jaxpr.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # seeded fallback sweep below covers the gap
    HAVE_HYPOTHESIS = False

from repro.core import Received, make_grouping, make_kv_ops, serve_optable
from repro.core.channel import ChannelConfig, collect_impl_events
from repro.kernels.delegation_serve import num_row_tiles, row_block
from repro.kernels import ops as kops

from _jaxpr import iter_eqns


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _kv_round(n_rows, op_col, keys, vals, expect, valid, table):
    rows = {"op": jnp.asarray(op_col, jnp.int16),
            "key": jnp.asarray(keys, jnp.int32),
            "value": jnp.asarray(vals, jnp.float32),
            "expect": jnp.asarray(expect, jnp.float32)}
    received = Received(rows, jnp.asarray(valid),
                        jnp.zeros((n_rows,), jnp.int32))
    return received, {"table": jnp.asarray(table, jnp.float32)}


def _serve_all(received, state, ops, cfgs):
    out = {}
    for impl, cfg in cfgs:
        serve = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl=impl,
                              cfg=cfg)
        new_state, resp = jax.jit(serve)(state, received)
        out[impl, None if cfg is None else cfg.serve_block_rows] = (
            np.asarray(new_state["table"]), np.asarray(resp["value"]),
            np.asarray(resp["flag"]))
    return out


def _assert_identical(out, ref_key):
    ref = out[ref_key]
    for key, got in out.items():
        if key == ref_key:
            continue
        for a, b, what in zip(ref, got, ("table", "value", "flag")):
            assert np.array_equal(a, b), f"{ref_key} vs {key}: {what} differs"


def _small_cfg(br=128, bk=128):
    return ChannelConfig(axis="model", serve_block_rows=br,
                         serve_block_keys=bk)


# ---------------------------------------------------------------------------
# adversarial segment layouts (ref vs tiled pallas, forced multi-tile)
# ---------------------------------------------------------------------------

def _adversarial_case(layout, n_rows, n_keys, vw, seed):
    rng = np.random.default_rng(seed)
    op_col = rng.integers(0, 4, n_rows).astype(np.int16)
    if layout == "single_segment":
        # every row the same (op, key): ONE segment spans all row tiles and
        # the ADD carry must chain through every boundary
        op_col = np.full(n_rows, 2, np.int16)
        keys = np.zeros(n_rows, np.int32)
        valid = np.ones(n_rows, bool)
    elif layout == "all_distinct":
        # all-distinct (op, key) pairs: every segment is a singleton, the
        # carry never fires, and every one-hot column is unique
        assert n_rows <= 4 * n_keys
        pairs = rng.permutation(4 * n_keys)[:n_rows]
        op_col = (pairs // n_keys).astype(np.int16)
        keys = (pairs % n_keys).astype(np.int32)
        valid = np.ones(n_rows, bool)
    elif layout == "dropped_tiles":
        # whole row tiles of invalid rows: the grouping sorts them to the
        # tail, where the kernels must treat them as sentinels
        keys = rng.integers(0, n_keys, n_rows).astype(np.int32)
        valid = np.zeros(n_rows, bool)
        valid[: max(1, n_rows // 4)] = True
        rng.shuffle(valid)
    else:
        raise AssertionError(layout)
    vals = rng.integers(0, 8, (n_rows, vw)).astype(np.float32)
    table = rng.integers(0, 8, (n_keys, vw)).astype(np.float32)
    expect = np.where(rng.random(n_rows)[:, None] < 0.5, table[keys],
                      rng.integers(0, 8, (n_rows, vw))).astype(np.float32)
    return _kv_round(n_rows, op_col, keys, vals, expect, valid, table)


@pytest.mark.parametrize("layout",
                         ["single_segment", "all_distinct", "dropped_tiles"])
@pytest.mark.parametrize("seed", [0, 1])
def test_adversarial_layouts_bit_identical(layout, seed):
    # 640 rows at br=128 -> 5 row tiles; 384 keys at bk=128 -> 3 key tiles
    received, state = _adversarial_case(layout, 640, 384, 2, seed)
    ops = make_kv_ops(1, 2)
    out = _serve_all(received, state, ops,
                     [("ref", None), ("masked", None),
                      ("pallas", _small_cfg())])
    _assert_identical(out, ("ref", None))


@pytest.mark.parametrize("n_rows", [129, 255, 257, 500, 777])
def test_non_power_of_two_rows_bit_identical(n_rows):
    """R landing mid-tile: the pad rows (sentinel key, lane -1, sid -1)
    share the last tile with real rows and must stay inert."""
    rng = np.random.default_rng(n_rows)
    n_keys, vw = 96, 2
    op_col = rng.integers(0, 4, n_rows).astype(np.int16)
    keys = rng.integers(0, n_keys, n_rows).astype(np.int32)
    vals = rng.integers(0, 8, (n_rows, vw)).astype(np.float32)
    table = rng.integers(0, 8, (n_keys, vw)).astype(np.float32)
    expect = np.where(rng.random(n_rows)[:, None] < 0.5, table[keys],
                      rng.integers(0, 8, (n_rows, vw))).astype(np.float32)
    valid = rng.random(n_rows) < 0.9
    received, state = _kv_round(n_rows, op_col, keys, vals, expect, valid,
                                table)
    ops = make_kv_ops(1, vw)
    out = _serve_all(received, state, ops,
                     [("ref", None), ("pallas", _small_cfg())])
    _assert_identical(out, ("ref", None))


def _random_layout_case(n_rows, n_hot, seed):
    """Random op mixes over a hot key set (deep segments at small n_hot)
    at arbitrary R, ref vs tiled pallas."""
    rng = np.random.default_rng(seed)
    n_keys, vw = 48, 2
    op_col = rng.integers(0, 4, n_rows).astype(np.int16)
    keys = rng.integers(0, min(n_hot, n_keys), n_rows).astype(np.int32)
    vals = rng.integers(0, 8, (n_rows, vw)).astype(np.float32)
    table = rng.integers(0, 8, (n_keys, vw)).astype(np.float32)
    expect = np.where(rng.random(n_rows)[:, None] < 0.5, table[keys],
                      rng.integers(0, 8, (n_rows, vw))).astype(np.float32)
    valid = rng.random(n_rows) < 0.85
    received, state = _kv_round(n_rows, op_col, keys, vals, expect, valid,
                                table)
    ops = make_kv_ops(1, vw)
    out = _serve_all(received, state, ops,
                     [("ref", None), ("pallas", _small_cfg())])
    _assert_identical(out, ("ref", None))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 400), st.integers(1, 12),
           st.integers(0, 2 ** 31 - 1))
    def test_property_random_layouts(n_rows, n_hot, seed):
        _random_layout_case(n_rows, n_hot, seed)
else:
    @pytest.mark.parametrize("n_rows,n_hot,seed",
                             [(1, 1, 0), (7, 2, 1), (130, 1, 2),
                              (255, 12, 3), (400, 3, 4), (333, 5, 5)])
    def test_property_random_layouts_seeded(n_rows, n_hot, seed):
        _random_layout_case(n_rows, n_hot, seed)


def test_r65k_serve_sweep_bit_identical():
    """The scaling point the refactor exists for: a 65k-row fused round —
    unrunnable under the dense (N, N) kernel — served bit-identically by
    ref, masked, and the tiled pallas path."""
    n_rows, n_keys, vw = 65536, 128, 2
    rng = np.random.default_rng(7)
    op_col = rng.integers(0, 4, n_rows).astype(np.int16)
    keys = rng.integers(0, n_keys, n_rows).astype(np.int32)
    vals = rng.integers(0, 8, (n_rows, vw)).astype(np.float32)
    table = rng.integers(0, 8, (n_keys, vw)).astype(np.float32)
    expect = np.where(rng.random(n_rows)[:, None] < 0.5, table[keys],
                      rng.integers(0, 8, (n_rows, vw))).astype(np.float32)
    valid = rng.random(n_rows) < 0.95
    received, state = _kv_round(n_rows, op_col, keys, vals, expect, valid,
                                table)
    ops = make_kv_ops(1, vw)
    out = _serve_all(received, state, ops,
                     [("ref", None), ("masked", None), ("pallas", None)])
    _assert_identical(out, ("ref", None))


# ---------------------------------------------------------------------------
# Grouping tile contract
# ---------------------------------------------------------------------------

def test_tile_meta_invariants():
    gid = np.concatenate([np.full(200, 3), np.full(100, 7), np.full(84, 9)])
    g = make_grouping(jnp.asarray(gid, jnp.int32))
    meta = g.tile_meta(block_rows=128)
    assert meta.block_rows == row_block(384, 128) == 128
    assert meta.n_tiles == num_row_tiles(384, 128) == 3
    sid = np.asarray(g.seg_start)
    tiles = sid.reshape(3, 128)
    assert np.array_equal(np.asarray(meta.first_sid), tiles[:, 0])
    assert np.array_equal(np.asarray(meta.last_sid), tiles[:, -1])
    cont = np.asarray(meta.cont)
    assert not cont[0], "tile 0 never continues a previous segment"
    # segment [0, 200) spans the 128 boundary; [200, 300) spans 256
    assert cont[1] and cont[2]
    # padded tail (R not a tile multiple) carries sid -1, breaking cont
    meta_small = g.tile_meta(block_rows=256)
    assert meta_small.n_tiles == 2
    assert np.asarray(meta_small.cont)[1]


def test_tile_meta_distinct_keys_never_continue():
    g = make_grouping(jnp.arange(512, dtype=jnp.int32))
    cont = np.asarray(g.tile_meta(block_rows=128).cont)
    assert not cont.any(), "singleton segments must never set cont"


# ---------------------------------------------------------------------------
# structural claims: tiled grids engage, no dense intermediates
# ---------------------------------------------------------------------------

def _avals(jaxpr):
    return {tuple(v.aval.shape) for eqn in iter_eqns(jaxpr)
            for v in list(eqn.invars) + list(eqn.outvars)
            if hasattr(getattr(v, "aval", None), "shape")}


def test_no_dense_intermediates_and_grid_engages():
    """N=1024 rows over K=256 keys at (br=256, bk=128): every pallas_call
    must run a true multi-block grid, and no (N, N) / (N, K) / (K, N)
    aval may appear anywhere in the jaxpr — the retired dense kernel's
    one-hots and same-segment masks are structurally gone."""
    from repro.kernels.delegation_serve import delegation_serve
    n, k, w = 1024, 256, 2
    args = (jnp.zeros((k, w), jnp.float32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n, w), jnp.float32),
            jnp.zeros((n, w), jnp.float32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((num_row_tiles(n, 256),), jnp.int32))
    jx = jax.make_jaxpr(
        lambda *a: delegation_serve(*a, br=256, bk=128, interpret=True))(
        *args)
    grids = [eqn.params["grid_mapping"].grid for eqn in iter_eqns(jx)
             if eqn.primitive.name == "pallas_call"]
    assert len(grids) == 4, f"expected 4 tiled pallas_calls, saw {grids}"
    for grid in grids:
        assert len(grid) == 2 and all(d > 1 for d in grid), \
            f"tiled grid must engage for R > block size, got grid={grid}"
    acc = _avals(jx)
    # forbidden: any aval coupling the FULL row batch to the full row batch
    # or the full key space — (N, N) masks, (N, K)/(K, N) one-hots.  Block-
    # granularity (br, br)/(br, bk) masks and (N, W) row payloads survive.
    dense = [sh for sh in acc if len(sh) >= 2
             and max(sh[-2], sh[-1]) >= n and min(sh[-2], sh[-1]) >= k]
    assert not dense, f"dense (row x row/key) intermediates found: {dense}"


def test_pack_slot_tiling_bit_identical_odd_sizes():
    """Slot-tiled pack vs the lax reference at ragged R / T*C not a tile
    multiple — including capacity overflow (pos >= capacity drops)."""
    for r, t, c, seed in ((97, 3, 5, 0), (400, 7, 33, 1), (1111, 5, 11, 2),
                          (256, 2, 300, 3)):
        rng = np.random.default_rng(seed)
        dst = jnp.asarray(
            np.where(rng.random(r) < 0.9, rng.integers(0, t, r), -1)
            .astype(np.int32))
        payload = jnp.asarray(rng.integers(0, 100, (r, 3)).astype(np.int32))
        ref = kops.delegation_pack(dst, payload, t, c, impl="ref")
        got = kops.delegation_pack(dst, payload, t, c, impl="pallas",
                                   br=128, bs=128)
        for a, b, what in zip(ref, got, ("slots", "counts", "request_slot")):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                f"pack r={r} t={t} c={c}: {what} differs"


# ---------------------------------------------------------------------------
# strict_impl / impl-event reporting (the fallback is no longer silent)
# ---------------------------------------------------------------------------

def _int_table_round():
    n_rows, n_keys, vw = 16, 8, 2
    rng = np.random.default_rng(0)
    received, _ = _kv_round(
        n_rows, rng.integers(0, 4, n_rows).astype(np.int16),
        rng.integers(0, n_keys, n_rows).astype(np.int32),
        rng.integers(0, 8, (n_rows, vw)).astype(np.float32),
        rng.integers(0, 8, (n_rows, vw)).astype(np.float32),
        np.ones(n_rows, bool), np.zeros((n_keys, vw), np.float32))
    state = {"table": jnp.zeros((n_keys, vw), jnp.int32)}
    rows = dict(received.rows)
    rows["value"] = rows["value"].astype(jnp.int32)
    rows["expect"] = rows["expect"].astype(jnp.int32)
    return Received(rows, received.valid, received.client), state


def test_non_f32_fallback_reports_impl_event():
    ops = make_kv_ops(1, 2, dtype=jnp.int32)
    received, state = _int_table_round()
    serve = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl="pallas")
    with collect_impl_events() as events:
        jax.jit(serve)(state, received)
    assert len(events) == 1 and "fell back" in events[0], events


def test_strict_impl_raises_on_fallback():
    ops = make_kv_ops(1, 2, dtype=jnp.int32)
    received, state = _int_table_round()
    cfg = ChannelConfig(axis="model", strict_impl=True)
    serve = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl="pallas",
                          cfg=cfg)
    with pytest.raises(TypeError, match="strict_impl"):
        jax.jit(serve)(state, received)


def test_f32_pallas_reports_no_event():
    received, state = _adversarial_case("all_distinct", 64, 96, 2, 0)
    ops = make_kv_ops(1, 2)
    serve = serve_optable(ops, active_ids=(0, 1, 2, 3), serve_impl="pallas")
    with collect_impl_events() as events:
        jax.jit(serve)(state, received)
    assert events == []
