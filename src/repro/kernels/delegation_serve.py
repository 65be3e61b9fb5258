"""Delegation-serve Pallas kernels — the trustee's serve phase, tiled.

The MXU sibling of ``delegation_pack``: applies a whole grouped KV op-mix
(GET / PUT / ADD / CAS lanes) to the entrusted table in one fused pass over
the received rows, pre-sorted by the channel's shared grouping pass
(channel.Grouping, DESIGN.md §9/§12).

Unlike the retired single-block kernel (grid=(1,), dense (N, K) one-hots
and an (N, N) same-segment mask — O(N²) work and VMEM that capped the row
batch at a few thousand), the serve is now FOUR small multi-block grid
kernels composed by one jitted wrapper.  No (N, N) or (K, N) intermediate
ever materializes: every mask/one-hot lives at BLOCK granularity —
(br, br) same-segment masks and (bk, br) / (br, bk) key one-hots — and
the table streams through key-partitioned (bk, W) tiles:

  phase snapshots   T0 --PUT--> T1 --ADD--> T2 --CAS--> T3

  1. ``_scatter_last`` (PUT, then CAS commit): grid (key tiles, row tiles)
     with rows INNERMOST.  Each step picks the block-local last-OK row per
     segment ((br, br) masked sum) and overwrites its table line via a
     (bk, br) one-hot matmul; later row tiles overwrite earlier
     ones, so the sequential row walk realizes global last-writer-wins
     exactly (sorted segments keep request order inside a tile and across
     tiles).
  2. ``_scatter_add`` (ADD totals): same grid; deltas accumulate into the
     key tile via the masked (bk, br) one-hot matmul.
  3. ``_gather`` (all read lanes + ADD priors): grid (row tiles, key
     tiles) with KEYS innermost.  Each row block computes its ADD
     exclusive-prefix priors block-locally ((br, br) strict-lower same-
     segment matmul) plus a CARRY — a VMEM running delta-sum for the one
     segment that can straddle a row-tile boundary, keyed by the
     Grouping's per-tile metadata (``cont``: does tile t continue tile
     t-1's last segment?), read from SMEM.  The key-tile walk then
     accumulates the GET
     (from T0), ADD-base (from T1) and CAS-current (from T2) gathers.
  4. CAS compare (``cur == expect``) runs as plain jnp between the calls
     (exact — no kernel needed), and the commit reuses ``_scatter_last``.

Op-phase order matches the masked reference serve exactly: GET reads the
round-entry table, PUT commits before ADD reads, CAS compares against the
post-ADD table.  Bit-identical to the grouped lax path on integer-exact
payloads (both are exact: every gather one-hot matmul has a single nonzero
term, winners write whole rows, and f32 addition is commutative so
prior-then-base equals base-then-prior bit-for-bit); general floats agree
within the accumulation orders the round-batch semantics already leave
unspecified (§4).

Output-block discipline (the TPU rule that shapes the grids): an output
block may only be revisited on CONSECUTIVE grid steps, so gathers (output
indexed by row tile) iterate keys innermost while scatters (output indexed
by key tile) iterate rows innermost — hence separate pallas_calls per
phase, with the table snapshots threaded between them by XLA.  The cost is
three extra table copies (T1/T2/T3) vs the old in-place update; the win is
row batches bounded by HBM, not by one VMEM-resident (N, N) mask.

Layout discipline (what the chip's Mosaic compiler accepts): a per-row
vector never changes orientation inside a kernel — Mosaic cannot lower a
(br,) -> (br, 1) relayout — so the wrapper hands each kernel every
per-row vector as a (1, N) row where it broadcasts along lanes and as an
(N, 1) column where it broadcasts along sublanes.  Matmuls that move
table or payload values run at ``Precision.HIGHEST``: a one-hot gather of
an f32 value is exact only without a bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_block(n: int, br: int) -> int:
    """Effective row-block size for an N-row batch: clamped so small
    batches run one lane-aligned tile, never below the 128-lane minimum.
    Grouping.tile_meta applies the SAME rule — the channel and the kernel
    must agree on the tiling for the per-tile carry metadata to line up."""
    return max(128, min(br, -(-n // 128) * 128))


def key_block(k: int, bk: int) -> int:
    """Effective key-block size for a K-line table (same clamp rule)."""
    return max(128, min(bk, -(-k // 128) * 128))


def num_row_tiles(n: int, br: int) -> int:
    b = row_block(n, br)
    return -(-n // b)


_HI = jax.lax.Precision.HIGHEST
_f = lambda b: b.astype(jnp.float32)


def _scatter_last_kernel(tin_ref, keys_ref, sid_ref, sidc_ref, ok_ref, okc_ref,
                         value_ref, out_ref, *, br: int, bk: int):
    """One (key tile, row tile) step of last-writer-wins commit.

    ``ok`` flags the candidate rows (one lane per call, so same key <=>
    same segment and each key has at most one block-local winner); the
    block-local winner is the last OK row of its segment, and later row
    tiles overwrite earlier ones — global last-writer without any
    cross-tile state.  Per-row inputs come as (1, br) rows; ``sidc``/
    ``okc`` are the same vectors as (br, 1) columns."""
    kt, rt = pl.program_id(0), pl.program_id(1)

    @pl.when(rt == 0)
    def _init():
        out_ref[...] = tin_ref[...]

    ok = ok_ref[...] > 0                                    # (1, br)
    # later[j, i]: row j comes after row i in the same segment
    later = ((jax.lax.broadcasted_iota(jnp.int32, (br, br), 0) >
              jax.lax.broadcasted_iota(jnp.int32, (br, br), 1)) &
             (sidc_ref[...] == sid_ref[...]))
    later_ok = jnp.sum(_f(later) * _f(okc_ref[...] > 0), axis=0,
                       keepdims=True)                       # (1, br)
    win = ok & (later_ok == 0.0)
    kh = keys_ref[...] - kt * bk                            # tile-local key
    oh_w = _f((jax.lax.broadcasted_iota(jnp.int32, (bk, br), 0) == kh)
              & win)                                        # (bk, br)
    wrote = jnp.sum(oh_w, axis=1, keepdims=True)            # (bk, 1) 0/1
    out_ref[...] = out_ref[...] * (1.0 - wrote) + \
        jnp.dot(oh_w, value_ref[...], precision=_HI,
                preferred_element_type=jnp.float32)


def _scatter_add_kernel(tin_ref, keys_ref, lane_ref, value_ref, out_ref, *,
                        br: int, bk: int):
    """One (key tile, row tile) step of the ADD total scatter."""
    kt, rt = pl.program_id(0), pl.program_id(1)

    @pl.when(rt == 0)
    def _init():
        out_ref[...] = tin_ref[...]

    kh = keys_ref[...] - kt * bk                            # (1, br)
    oh = _f((jax.lax.broadcasted_iota(jnp.int32, (bk, br), 0) == kh)
            & (lane_ref[...] == 2))                         # (bk, br)
    out_ref[...] += jnp.dot(oh, value_ref[...], precision=_HI,
                            preferred_element_type=jnp.float32)


def _gather_kernel(meta_ref, t0_ref, t1_ref, t2_ref, keys_ref, lane_ref,
                   sidc_ref, sid_ref, value_ref, resp_ref, carry_ref, *,
                   br: int, bk: int):
    """One (row tile, key tile) step of the response gather.

    At the first key step of each row tile the block computes its ADD
    priors: block-local strict-lower same-segment prefix plus the carried
    delta sum of the segment straddling the tile boundary.  ``meta_ref``
    (SMEM, (3, n_row_tiles)) holds each tile's ``cont`` flag (from
    Grouping.tile_meta: does this tile's leading run continue the previous
    tile's trailing segment?) and its first and last segment ids; the
    carry scratch persists across the whole grid because row tiles advance
    outermost.  Per-row inputs come as (br, 1) columns, except ``sid``,
    which the same-segment mask also needs as a (1, br) row."""
    rt, kt = pl.program_id(0), pl.program_id(1)
    keys = keys_ref[...]                                    # (br, 1)
    lane = lane_ref[...]
    m_get, m_add, m_cas = lane == 0, lane == 2, lane == 3

    @pl.when(kt == 0)
    def _prior():
        sidc = sidc_ref[...]                                # (br, 1)
        delta = value_ref[...] * _f(m_add)                  # (br, W)
        earlier = ((jax.lax.broadcasted_iota(jnp.int32, (br, br), 0) >
                    jax.lax.broadcasted_iota(jnp.int32, (br, br), 1)) &
                   (sidc == sid_ref[...]))
        prior = jnp.dot(_f(earlier), delta, precision=_HI,
                        preferred_element_type=jnp.float32)
        cont = meta_ref[0, rt] > 0
        sid_first, sid_last = meta_ref[1, rt], meta_ref[2, rt]
        # select, don't multiply: the scratch is UNINITIALIZED before the
        # first carrying tile (NaN/garbage), and 0 * NaN is NaN
        carry = jnp.where(cont, carry_ref[...], 0.0)        # (1, W)
        # sorted segment ids are monotone, so rows continuing the previous
        # tile's segment are exactly the leading sid_first run
        from_carry = _f((sidc == sid_first) & cont)         # (br, 1)
        resp_ref[...] = (prior + from_carry * carry) * _f(m_add)
        # roll the carry forward: the trailing segment's in-tile delta sum,
        # plus the old carry when ONE segment spans the whole tile
        in_last = _f(sidc == sid_last)
        carry_ref[...] = jnp.sum(delta * in_last, axis=0, keepdims=True) + \
            jnp.where((sid_last == sid_first) & cont, 1.0, 0.0) * carry

    kh = keys - kt * bk                                     # tile-local key
    oh = kh == jax.lax.broadcasted_iota(jnp.int32, (br, bk), 1)
    gather = lambda m, t_ref: jnp.dot(_f(oh & m), t_ref[...], precision=_HI,
                                      preferred_element_type=jnp.float32)
    resp_ref[...] += (gather(m_get, t0_ref) + gather(m_add, t1_ref) +
                      gather(m_cas, t2_ref))


def _scatter_last(table, keys, sid, sidc, ok, okc, value, *, br, bk,
                  interpret):
    kp, wp = table.shape
    np_ = value.shape[0]
    n_kt, n_rt = kp // bk, np_ // br
    row = pl.BlockSpec((1, br), lambda kt, rt: (0, rt))
    col = pl.BlockSpec((br, 1), lambda kt, rt: (rt, 0))
    return pl.pallas_call(
        functools.partial(_scatter_last_kernel, br=br, bk=bk),
        grid=(n_kt, n_rt),
        in_specs=[
            pl.BlockSpec((bk, wp), lambda kt, rt: (kt, 0)),
            row, row, col, row, col,
            pl.BlockSpec((br, wp), lambda kt, rt: (rt, 0)),
        ],
        out_specs=pl.BlockSpec((bk, wp), lambda kt, rt: (kt, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, wp), jnp.float32),
        interpret=interpret,
    )(table, keys, sid, sidc, ok, okc, value)


def _scatter_add(table, keys, lane, value, *, br, bk, interpret):
    kp, wp = table.shape
    np_ = value.shape[0]
    n_kt, n_rt = kp // bk, np_ // br
    row = pl.BlockSpec((1, br), lambda kt, rt: (0, rt))
    return pl.pallas_call(
        functools.partial(_scatter_add_kernel, br=br, bk=bk),
        grid=(n_kt, n_rt),
        in_specs=[
            pl.BlockSpec((bk, wp), lambda kt, rt: (kt, 0)),
            row, row,
            pl.BlockSpec((br, wp), lambda kt, rt: (rt, 0)),
        ],
        out_specs=pl.BlockSpec((bk, wp), lambda kt, rt: (kt, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, wp), jnp.float32),
        interpret=interpret,
    )(table, keys, lane, value)


def _gather(meta, t0, t1, t2, keys, lane, sidc, sid, value, *, br, bk,
            interpret):
    kp, wp = t0.shape
    np_ = value.shape[0]
    n_kt, n_rt = kp // bk, np_ // br
    tbl = pl.BlockSpec((bk, wp), lambda rt, kt: (kt, 0))
    col = pl.BlockSpec((br, 1), lambda rt, kt: (rt, 0))
    return pl.pallas_call(
        functools.partial(_gather_kernel, br=br, bk=bk),
        grid=(n_rt, n_kt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            tbl, tbl, tbl, col, col, col,
            pl.BlockSpec((1, br), lambda rt, kt: (0, rt)),
            pl.BlockSpec((br, wp), lambda rt, kt: (rt, 0)),
        ],
        out_specs=pl.BlockSpec((br, wp), lambda rt, kt: (rt, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, wp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, wp), jnp.float32)],
        interpret=interpret,
    )(meta, t0, t1, t2, keys, lane, sidc, sid, value)


@functools.partial(jax.jit, static_argnames=("br", "bk", "interpret"))
def delegation_serve(table: jax.Array, keys: jax.Array, lane: jax.Array,
                     value: jax.Array, expect: jax.Array, sid: jax.Array,
                     cont: jax.Array, *, br: int = 256, bk: int = 512,
                     interpret: bool = True):
    """Apply a grouped GET/PUT/ADD/CAS row batch to ``table`` tile by tile.

    All row inputs are in SORTED (grouping) coordinates:
      table    (K, W) f32      the entrusted table shard
      keys     (N,)  int32     local table index; >= K marks inactive rows
      lane     (N,)  int32     0 GET | 1 PUT | 2 ADD | 3 CAS | -1 inactive
      value    (N, W) f32      PUT/CAS new rows, ADD deltas
      expect   (N, W) f32      CAS compare rows
      sid      (N,)  int32     segment id, monotone over sorted rows (same
                               value <=> same (op, key) segment — the
                               Grouping's ``seg_start`` works verbatim)
      cont     (n_row_tiles,)  per-tile carry metadata from
                               ``Grouping.tile_meta(block_rows=br)``:
                               tile t's first row continues tile t-1's
                               trailing segment (False for tile 0)

    ``br``/``bk`` are the row/key block sizes (multiples of 128; clamped
    for small inputs by ``row_block``/``key_block``).  The wrapper pads N
    to the tile multiple with inactive rows (lane -1, sid -1, sentinel
    key) and K/W to lane-aligned tile multiples, then slices back.

    Returns (new_table (K, W) f32, resp_value (N, W) f32, flag (N,) f32):
    resp_value carries GET/ADD old values and CAS current values (zeros
    for PUT/inactive rows), flag the CAS compare results.
    """
    k, w = table.shape
    n = keys.shape[0]
    br = row_block(n, br)
    bk = key_block(k, bk)
    np_, kp = -(-n // br) * br, -(-k // bk) * bk
    wp = -(-w // 128) * 128
    n_rt = np_ // br
    assert cont.shape[0] == n_rt, (
        f"cont carries {cont.shape[0]} row tiles but N={n} at br={br} "
        f"tiles into {n_rt} — build it with Grouping.tile_meta(block_rows="
        f"{br}) so the channel and kernel agree on the tiling")
    rpad = np_ - n
    t0 = jnp.pad(table.astype(jnp.float32), ((0, kp - k), (0, wp - w)))
    # inactive keys (>= the UNPADDED k) are remapped to the padded size kp,
    # which lies outside every key tile — sentinel rows match nothing even
    # when the caller's table was padded
    keys_p = jnp.pad(jnp.where(keys >= k, kp, keys), (0, rpad),
                     constant_values=kp)
    lane_p = jnp.pad(lane, (0, rpad), constant_values=-1)
    sid_p = jnp.pad(sid, (0, rpad), constant_values=-1)
    value_p = jnp.pad(value.astype(jnp.float32), ((0, rpad), (0, wp - w)))
    expect_p = jnp.pad(expect.astype(jnp.float32), ((0, rpad), (0, wp - w)))
    # each kernel takes a per-row vector in the orientation it consumes:
    # (1, N) rows broadcast along lanes, (N, 1) columns along sublanes
    row = lambda x: x.reshape(1, np_)
    col = lambda x: x.reshape(np_, 1)
    kw = dict(br=br, bk=bk, interpret=interpret)
    # per-row-tile scalars for the gather's carry, read from SMEM
    tiles = sid_p.reshape(n_rt, br)
    meta = jnp.stack([cont.astype(jnp.int32), tiles[:, 0], tiles[:, -1]])

    # PUT: every lane-1 row is a candidate; the last per segment commits
    put = (lane_p == 1).astype(jnp.int32)
    t1 = _scatter_last(t0, row(keys_p), row(sid_p), col(sid_p), row(put),
                       col(put), value_p, **kw)
    # ADD totals
    t2 = _scatter_add(t1, row(keys_p), row(lane_p), value_p, **kw)
    # responses: GET from T0, ADD base (from T1) + priors, CAS cur from T2
    resp = _gather(meta, t0, t1, t2, col(keys_p), col(lane_p), col(sid_p),
                   row(sid_p), value_p, **kw)
    # CAS compare is a plain elementwise reduce — exact outside the kernel
    ok_cas = (lane_p == 3) & jnp.all(resp == expect_p, axis=-1)
    cas = ok_cas.astype(jnp.int32)
    t3 = _scatter_last(t2, row(keys_p), row(sid_p), col(sid_p), row(cas),
                       col(cas), value_p, **kw)
    return t3[:k, :w], resp[:n, :w], ok_cas[:n].astype(jnp.float32)
