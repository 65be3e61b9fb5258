"""Delegation-pack Pallas kernel — the channel's client-side pack phase.

Bins R requests into per-trustee capacity-limited slots (paper §5.1/§5.3).
The CPU implementation is pointer-chasing per request; the TPU adaptation
reformulates binning as two MXU matmuls per tile (DESIGN.md §2 "hardware
adaptation"):

  1. position-in-group: the (T, R) destination one-hot times an upper-
     triangular ones matrix gives each request its running rank within its
     destination group (prefix count), offset by a per-trustee counter
     carried in VMEM scratch across row tiles.
  2. scatter: the (slots, R) slot one-hot matmul against the payload tile
     accumulates rows directly into the slot buffer — a scatter expressed
     as dense MXU work, which beats per-row dynamic stores on a systolic
     machine.

The grid is (slot tiles, row tiles) with rows INNERMOST: each slot tile of
the output walks every row tile consecutively (the TPU's only safe
output-revisit pattern), accumulating a BLOCK-LOCAL (bs, br) slot one-hot
— the dense (br, T*C) one-hot of the old single-slot-block kernel is
retired, so the slot buffer can grow past VMEM (DESIGN.md §12).  The
running per-trustee counters recompute identically on every slot-tile
pass (the prefix matmul is cheap); ``request_slot`` is only written on
the first pass, with later passes redirected to a sliced-off dump block.

Per-request values stay (1, br) lane rows throughout (Mosaic cannot lower
a (br,) -> (br, 1) relayout), and the payload scatter runs at
``Precision.HIGHEST`` so f32 planes move exactly.

Outputs match ``ref.delegation_pack`` bit-for-bit (FIFO within destination).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(dst_ref, payload_ref, slots_ref, counts_ref, reqslot_ref,
                 running_ref, *, n_trustees: int, capacity: int, br: int,
                 bs: int, n_rt: int, n_st: int):
    """One (slot tile, row tile) step.  Every per-request quantity stays a
    (1, br) lane row and the one-hots are built TRANSPOSED ((T, br) and
    (bs, br)), so no row vector is ever relaid out into a column — Mosaic
    cannot lower that reshape.  ``n_trustees`` is the one-hot height,
    padded by the wrapper to a sublane multiple (rows past the real T are
    never matched)."""
    st, rt = pl.program_id(0), pl.program_id(1)
    t, c = n_trustees, capacity
    hi = jax.lax.Precision.HIGHEST

    @pl.when(rt == 0)
    def _init():
        slots_ref[...] = jnp.zeros_like(slots_ref)
        running_ref[...] = jnp.zeros_like(running_ref)

    dst = dst_ref[...]                                      # (1, br) int32
    active = dst >= 0
    dst_c = jnp.where(active, dst, 0)
    oh = ((jax.lax.broadcasted_iota(jnp.int32, (t, br), 0) == dst_c)
          & active).astype(jnp.float32)                     # (T, br)

    # 1) prefix count within tile via upper-triangular matmul (MXU):
    #    prefix[d, i] = #{j <= i : dst_j == d}
    triu = (jax.lax.broadcasted_iota(jnp.int32, (br, br), 0) <=
            jax.lax.broadcasted_iota(jnp.int32, (br, br), 1)).astype(jnp.float32)
    prefix = jnp.dot(oh, triu, precision=hi,
                     preferred_element_type=jnp.float32)    # (T, br)
    base = running_ref[...]                                 # (T, 1) f32 counts
    pos = jnp.sum(oh * (prefix - 1.0 + base), axis=0,
                  keepdims=True).astype(jnp.int32)          # (1, br)
    running_ref[...] = base + jnp.sum(oh, axis=1, keepdims=True)

    ok = active & (pos < c)
    slot_idx = dst_c * c + jnp.minimum(pos, c - 1)          # (1, br) global
    # identical on every slot-tile pass; passes past the first write the
    # dump block (see the index map in the wrapper)
    reqslot_ref[...] = jnp.where(ok, slot_idx, -1)

    # 2) scatter rows into THIS slot tile via the (bs, br) slot one-hot
    sh = slot_idx - st * bs                                 # tile-local slot
    slot_oh = ((jax.lax.broadcasted_iota(jnp.int32, (bs, br), 0) == sh)
               & ok).astype(jnp.float32)
    slots_ref[...] += jnp.dot(slot_oh, payload_ref[...], precision=hi,
                              preferred_element_type=jnp.float32
                              ).astype(slots_ref.dtype)

    @pl.when((st == n_st - 1) & (rt == n_rt - 1))
    def _done():
        counts_ref[...] = jnp.minimum(running_ref[...],
                                      float(c)).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("n_trustees", "capacity", "br", "bs",
                                    "interpret"))
def delegation_pack(dst: jax.Array, payload: jax.Array, *, n_trustees: int,
                    capacity: int, br: int = 256, bs: int = 512,
                    interpret: bool = True):
    """dst: (R,) int32 in [-1, T); payload: (R, W).  Any R works: ragged
    request counts are padded to a tile multiple with inactive rows
    (dst = -1, zero payload) and the padding is sliced back off the
    request_slot output; the T*C slot buffer likewise pads to a multiple
    of the ``bs`` slot tile (rows never target the padding — slot ids are
    < T*C by construction).
    Returns (slots (T*C, W) f32, counts (T,) i32, request_slot (R,) i32)."""
    r, w = payload.shape
    t, c = n_trustees, capacity
    # shrink the tiles for small inputs but keep them lane-aligned: a
    # ragged block like (1, 97) would not lower on real TPU hardware
    br = min(br, -(-r // 128) * 128)
    bs = min(bs, -(-(t * c) // 128) * 128)
    wp = -(-w // 128) * 128
    pad = (-r) % br
    if pad:
        dst = jnp.concatenate([dst, jnp.full((pad,), -1, dst.dtype)])
    if pad or wp != w:
        payload = jnp.pad(payload, ((0, pad), (0, wp - w)))
    rp = r + pad
    sp = -(-(t * c) // bs) * bs
    n_rt, n_st = rp // br, sp // bs
    tp = -(-t // 8) * 8                  # one-hot height: a sublane multiple

    slots, counts, request_slot = pl.pallas_call(
        functools.partial(_pack_kernel, n_trustees=tp, capacity=c, br=br,
                          bs=bs, n_rt=n_rt, n_st=n_st),
        grid=(n_st, n_rt),
        in_specs=[
            pl.BlockSpec((1, br), lambda st, rt: (0, rt)),
            pl.BlockSpec((br, wp), lambda st, rt: (rt, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bs, wp), lambda st, rt: (st, 0)),
            pl.BlockSpec((tp, 1), lambda st, rt: (0, 0)),
            # request_slot is recomputed identically per slot tile; only the
            # st == 0 pass lands in the real rows, the rest hit an extra
            # dump block sliced off below (consecutive revisits only)
            pl.BlockSpec((1, br),
                         lambda st, rt: (0, jnp.where(st == 0, rt, n_rt))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, wp), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, (n_rt + 1) * br), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tp, 1), jnp.float32)],
        interpret=interpret,
    )(dst.reshape(1, rp), payload)
    return (slots[:t * c, :w], counts[:t, 0],
            request_slot.reshape((n_rt + 1) * br)[:r])
