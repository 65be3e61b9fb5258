"""The Trust<T> delegation channel, adapted to TPU SPMD.

Paper mapping (see DESIGN.md §2):

  * request slot  -> fixed-capacity buffer ``(T, C, *payload)`` per device,
                     one row block per (client, trustee) pair, moved by ONE
                     ``all_to_all`` over the trustee mesh axis.
  * count header  -> ``counts[t]`` = number of valid requests for trustee t
                     (the paper's request counter; the ready bit is subsumed
                     by SPMD collective synchronization).
  * two-part slot -> ``capacity`` (primary block, sized for mean load) plus an
                     ``overflow`` policy: "second_round" ships the excess in a
                     second, narrower all_to_all; "drop" discards (MoE-style
                     capacity factor); "defer" returns the unsent mask to the
                     caller (paper: wait for slot availability) — served to
                     completion by ``delegate_drain``'s bounded retry rounds.
  * FIFO per pair -> pack is a stable sort by destination, so requests from
                     one client to one trustee are served in issue order.

All functions here are *per-shard* code: they must run inside a ``shard_map``
whose mesh contains ``axis``.  ``Trust`` (trust.py) provides that wrapper.
Payloads are pytrees of ``(R, ...)`` arrays — the "captured environment" rows.
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import tracing

Pytree = Any


# ---------------------------------------------------------------------------
# Implementation-event side channel (satellite of DESIGN.md §12)
#
# Some impl decisions are STATIC (trace-time): e.g. the KV serve provider
# can only route serve_impl="pallas" through the kernel for f32 tables and
# silently served via lax otherwise.  Such decisions happen while the round
# traces, so they cannot ride a traced array — they ride this stack of
# collector lists instead.  ``delegate``/``delegate_async`` (and the engine
# around its jit boundary) open a collector around the serve; providers call
# ``report_impl_event`` at the decision point.  Nested collectors all
# receive the event (the engine's sits outside the channel's).
# ---------------------------------------------------------------------------

_impl_event_sinks: List[List[str]] = []


def report_impl_event(event: str) -> None:
    """Record a trace-time implementation fallback (no-op outside any
    collector).  ``event`` is a short human-readable reason string."""
    for sink in _impl_event_sinks:
        sink.append(event)


@contextlib.contextmanager
def collect_impl_events():
    """Collect ``report_impl_event`` calls made while the body runs (i.e.
    while the round traces — jit-cached re-executions re-use the decision
    made at trace time, so the collected events are the truth for every
    execution of that program)."""
    events: List[str] = []
    _impl_event_sinks.append(events)
    try:
        yield events
    finally:
        _impl_event_sinks.remove(events)


@dataclass(frozen=True)
class ChannelConfig:
    axis: str = "model"            # trustee mesh axis (or tuple of axes)
    capacity: int = 0              # primary rows per (client, trustee) pair
    overflow: str = "drop"         # "drop" | "second_round" | "defer"
    overflow_capacity: int = 0     # rows per pair in the overflow round
    local_shortcut: bool = False   # apply self-addressed requests inline (§5.2.1)
    pack_impl: str = "ref"         # "ref" (lax sort) | "pallas" (MXU pack kernel)
    mode: str = "shared"           # "shared" | "dedicated" (paper's two runtimes)
    n_clients: int = 0             # dedicated only: client devices on the axis
    max_rounds: int = 1            # defer only: drain-engine round bound (§5.1)
    wire_fmt: str = "tree"         # "tree" (one collective per payload leaf)
    #                                | "planes" (ONE fused all_to_all per
    #                                block: leaves encode into a single f32
    #                                plane matrix, validity mask rides as an
    #                                extra column — the multiplexed-engine
    #                                wire format, bit-identical to "tree")
    n_lanes: int = 1               # slot sub-lanes per destination slot: the
    #                                multiplexed engine gives each Trust its
    #                                own ``capacity`` rows inside every
    #                                (client, trustee) block, so ``dst`` then
    #                                carries VIRTUAL bins dst*n_lanes + lane
    #                                and each lane keeps solo pack semantics
    serve_impl: str = "ref"        # trustee serve path: "ref" (shared-
    #                                grouping lax segment primitives) |
    #                                "pallas" (fused MXU serve kernel over
    #                                the same grouping) | "masked" (the
    #                                legacy per-op full-buffer passes, kept
    #                                as the differential reference)
    elide_resp: Tuple[str, ...] = ()   # response fields statically zero for
    #                                every op in the round — dropped from the
    #                                response transpose and re-inflated as
    #                                zeros client-side (paper: zero-size PUT
    #                                responses save response bytes)
    elide_lanes: Tuple[int, ...] = ()  # multiplexed rounds: lanes (trusts)
    #                                whose every response field is elided
    #                                (e.g. a PUT-only trust) — their slot
    #                                rows are dropped from the response
    #                                transpose ("planes" wire format only)
    serve_block_rows: int = 256    # tiled serve kernel: rows per grid tile
    serve_block_keys: int = 512    # tiled serve kernel: table lines per tile
    pack_block_rows: int = 256     # tiled pack kernel: rows per grid tile
    pack_block_slots: int = 512    # tiled pack kernel: slot lines per tile
    #                                (all multiples of 128; clamped for
    #                                small inputs — DESIGN.md §12 tuning)
    strict_impl: bool = False      # raise instead of silently falling back
    #                                when the requested serve_impl cannot
    #                                engage (e.g. "pallas" on a non-f32
    #                                table); False reports the fallback via
    #                                ChannelInfo.impl_fallback / last_stats
    combine_impl: str = "off"      # client-side request combining before
    #                                pack (DESIGN.md §13): "off" ships every
    #                                request row; "ref" groups local rows by
    #                                (op, key), ships ONE wire row per
    #                                segment, and reconstructs full per-
    #                                request responses after unpack —
    #                                bit-identical by construction for the
    #                                per-op archetypes (dedupe/sum/last)

    def total_capacity(self) -> int:
        if self.overflow == "second_round":
            return self.capacity + self.overflow_capacity
        return self.capacity

    def fuse_sig(self) -> Tuple:
        """Channel-compatibility signature: the config fields two Trusts
        must agree on to share one multiplexed engine round (DESIGN.md §8).
        Capacity is included deliberately — an explicit slot budget is a
        SEMANTIC choice (what drops/defers), so differently provisioned
        trusts never fuse.  Declared here (next to the fields) rather than
        as an ad-hoc tuple inside the engine so config growth cannot
        silently fall out of the fuse step."""
        return (self.axis, self.overflow, self.local_shortcut,
                self.pack_impl, self.serve_impl, self.mode, self.n_clients,
                self.max_rounds, self.capacity, self.overflow_capacity,
                self.serve_block_rows, self.serve_block_keys,
                self.pack_block_rows, self.pack_block_slots,
                self.strict_impl, self.combine_impl)

    def n_slots(self, n_trustees: int) -> int:
        """Destination slots per device in the all_to_all block layout.

        Shared mode exchanges one block per trustee.  Dedicated mode keeps the
        collective over the FULL axis (clients + trustees): trustee t lives at
        device slot ``n_clients + t``, client slots carry zero-count blocks, so
        the symmetric all_to_all degenerates into the asymmetric
        client->trustee send (and its transpose routes responses back by
        client id)."""
        if self.mode == "dedicated":
            return n_trustees + self.n_clients
        return n_trustees


class Packed(NamedTuple):
    """Client-side packed request slots (pre-transmission)."""
    slots: Pytree          # leaves (T*C, ...) — primary block
    counts: jax.Array      # (T,) int32 — count header per pair
    slots2: Optional[Pytree]   # overflow block leaves (T*C2, ...) or None
    counts2: Optional[jax.Array]
    request_slot: jax.Array    # (R,) int32: row id in [0, T*C + T*C2) or -1
    dropped: jax.Array         # (R,) bool: not sent this step (drop/defer)


class Received(NamedTuple):
    """Trustee-side received requests (post-transmission)."""
    rows: Pytree           # leaves (T*C [+T*C2], ...) — flattened request rows
    valid: jax.Array       # (N,) bool
    client: jax.Array      # (N,) int32 — originating client (response routing)
    grouping: Any = None   # Optional[Grouping] — the per-round shared
    #                        grouping pass (computed once by serve_optable
    #                        when the active ops declare ``group_key``)


class TileMeta(NamedTuple):
    """Per-row-tile segment metadata for the TILED serve consumers.

    The tiled Pallas serve walks the sorted rows in ``block_rows`` tiles;
    segments may straddle tile boundaries, so each tile needs to know
    whether its leading run continues the previous tile's trailing segment
    (the ADD prefix-prior carry).  ``Grouping.tile_meta`` derives this once
    from the sorted segment ids — the lax path needs none of it (its scans
    are global), which is exactly the contract: one grouped representation,
    two consumers (DESIGN.md §12)."""
    block_rows: int        # static: effective row tile size (the kernel's
    #                        clamp rule applied — multiples of 128)
    n_tiles: int           # static: row tiles covering the padded batch
    first_sid: jax.Array   # (n_tiles,) int32 — segment id of each tile's
    #                        first row (-1 for all-padding tiles)
    last_sid: jax.Array    # (n_tiles,) int32 — segment id of the last row
    cont: jax.Array        # (n_tiles,) bool — tile t's first row continues
    #                        tile t-1's trailing segment (False for t = 0)


class Grouping(NamedTuple):
    """ONE stable sort of the received rows by (op, group key) per round.

    Every per-row array except ``order``/``inv`` lives in SORTED coordinates
    (index i refers to the i-th row of the sorted order).  Rows of one
    (op, key) segment are contiguous and keep request order — (client, slot)
    order, the serve order the channel guarantees — so last-writer-wins is
    "last row of the segment", fetch-and-add priors are segment-exclusive
    prefix sums, and CAS winners are "last matching row of the segment".
    Computed once by ``serve_optable`` and shared by every op in the round,
    replacing the per-op argsort + searchsorted (ADD) and scatter-max (PUT/
    CAS last-writer) passes."""
    order: jax.Array       # (N,) int32 — sorted position -> original row
    inv: jax.Array         # (N,) int32 — original row -> sorted position
    gid_sorted: jax.Array  # (N,) int32 — combined (op, key) group id of
    #                        sorted row i; inactive rows sort last under a
    #                        sentinel id
    seg_start: jax.Array   # (N,) int32 — first sorted position of row i's
    #                        segment
    seg_end: jax.Array     # (N,) int32 — one past the last position
    rank: jax.Array        # (N,) int32 — rank of sorted row i within its
    #                        segment (position - seg_start)
    seg_end_row: jax.Array = None  # (N,) int32 — seg_end in REQUEST
    #                        coordinates (seg_end[inv]): row i is its
    #                        segment's last writer iff
    #                        inv[i] == seg_end_row[i] - 1 — the one shared
    #                        gather that lets PUT commit winners without
    #                        sorting any payload rows

    def tile_meta(self, block_rows: int = 256) -> TileMeta:
        """Per-tile segment boundaries/carry metadata for a tiled consumer.

        ``seg_start`` doubles as the segment id (monotone over sorted rows,
        equal exactly within one segment), so tiling it answers every
        cross-tile question the kernels ask.  Padding rows (up to the tile
        multiple) carry sid -1, matching the kernel wrapper's padding —
        build the meta with the SAME ``block_rows`` handed to the kernel."""
        from ..kernels.delegation_serve import row_block
        n = int(self.seg_start.shape[0])
        br = row_block(n, block_rows)
        n_tiles = -(-n // br)
        sid = self.seg_start.astype(jnp.int32)
        pad = n_tiles * br - n
        if pad:
            sid = jnp.concatenate(
                [sid, jnp.full((pad,), -1, jnp.int32)])
        tiles = sid.reshape(n_tiles, br)
        first, last = tiles[:, 0], tiles[:, -1]
        cont = jnp.concatenate(
            [jnp.zeros((1,), bool), first[1:] == last[:-1]])
        return TileMeta(br, n_tiles, first, last, cont)


def make_grouping(gid: jax.Array, n_bins: int = 0,
                  gid2: Optional[jax.Array] = None) -> Grouping:
    """Build the shared grouping from a per-row group id (sentinel = max).

    ONE stable sort per round (`lax.sort` carries the ids and the
    permutation together) is the only superlinear work.  Segment
    boundaries come from a histogram over the (small) id space when
    ``n_bins`` is given and modest — `seg_start = offsets[gid]`,
    `seg_end = offsets[gid + 1]` after an exclusive bin cumsum — and from
    O(N) scans over the sorted ids otherwise.

    ``gid2`` adds a SECONDARY sort key: rows group by the pair
    ``(gid, gid2)`` without packing both into one int32 (the client-side
    combine pass groups by (destination, span) x an unbounded key column,
    where a packed id could overflow).  The pair path always takes the
    O(N)-scan boundary route (``n_bins`` is ignored)."""
    n = gid.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    if gid2 is not None:
        gid_sorted, gid2_sorted, order = lax.sort(
            (gid, gid2.astype(jnp.int32), pos), num_keys=2, is_stable=True)
        inv = jnp.zeros((n,), jnp.int32).at[order].set(pos)
        changed = (gid_sorted[1:] != gid_sorted[:-1]) \
            | (gid2_sorted[1:] != gid2_sorted[:-1])
        is_start = jnp.concatenate([jnp.ones((1,), bool), changed])
        is_end = jnp.concatenate([changed, jnp.ones((1,), bool)])
        seg_start = lax.cummax(jnp.where(is_start, pos, 0))
        seg_end = lax.cummin(jnp.where(is_end, pos + 1, n), reverse=True)
        return Grouping(order.astype(jnp.int32), inv, gid_sorted,
                        seg_start, seg_end, pos - seg_start,
                        jnp.take(seg_end, inv))
    gid_sorted, order = lax.sort((gid, pos), num_keys=1, is_stable=True)
    inv = jnp.zeros((n,), jnp.int32).at[order].set(pos)
    if 0 < n_bins <= 4 * n:
        hist = jnp.zeros((n_bins + 1,), jnp.int32).at[gid].add(
            1, mode="drop")
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(hist)])
        seg_start = offsets[gid_sorted]
        seg_end = offsets[gid_sorted + 1]
    else:
        changed = gid_sorted[1:] != gid_sorted[:-1]
        is_start = jnp.concatenate([jnp.ones((1,), bool), changed])
        is_end = jnp.concatenate([changed, jnp.ones((1,), bool)])
        seg_start = lax.cummax(jnp.where(is_start, pos, 0))
        seg_end = lax.cummin(jnp.where(is_end, pos + 1, n), reverse=True)
    return Grouping(order.astype(jnp.int32), inv, gid_sorted,
                    seg_start, seg_end, pos - seg_start,
                    jnp.take(seg_end, inv))


def _group_positions(dst: jax.Array, n_trustees: int):
    """Stable grouping of requests by destination.

    Returns (order, key_sorted, pos_sorted, group_sizes):
      order       (R,) permutation grouping requests by trustee, FIFO inside
      key_sorted  (R,) destination of order[i] (n_trustees == inactive)
      pos_sorted  (R,) rank of the request within its destination group
      group_sizes (T,) demand per trustee (pre-capacity — used for load stats)
    """
    r = dst.shape[0]
    key = jnp.where(dst < 0, n_trustees, dst).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    key_sorted = key[order]
    # start offset of each group via binary search on the sorted keys
    starts = jnp.searchsorted(key_sorted, jnp.arange(n_trustees + 1, dtype=jnp.int32))
    pos_sorted = jnp.arange(r, dtype=jnp.int32) - starts[key_sorted]
    group_sizes = (starts[1:] - starts[:-1]).astype(jnp.int32)
    return order, key_sorted, pos_sorted, group_sizes


def _scatter_rows(payload: Pytree, order: jax.Array, row_ids: jax.Array,
                  valid: jax.Array, n_rows: int) -> Pytree:
    """Scatter payload rows (in sorted order) into a slot buffer; invalid rows
    are dropped (out-of-bounds index + mode='drop')."""
    idx = jnp.where(valid, row_ids, n_rows)

    def scat(leaf):
        sorted_leaf = jnp.take(leaf, order, axis=0)
        out = jnp.zeros((n_rows,) + leaf.shape[1:], leaf.dtype)
        return out.at[idx].set(sorted_leaf, mode="drop")

    return jax.tree.map(scat, payload)


def _encode_planes(payload: Pytree, r: int):
    """Flatten a payload pytree into one (R, W) float32 plane matrix for the
    Pallas pack kernel.  Integer leaves are split into hi/lo 16-bit planes
    (each exact in f32 — the MXU scatter matmul moves them losslessly);
    float leaves are upcast to f32 (exact for f32/bf16/f16 inputs)."""
    from ..kernels import ops as kops
    leaves, treedef = jax.tree.flatten(payload)
    planes, decs, col = [], [], 0
    for leaf in leaves:
        mat = leaf.reshape(r, -1)
        w = mat.shape[1]
        if jnp.issubdtype(leaf.dtype, jnp.integer) and leaf.dtype.itemsize <= 2:
            # <= 16-bit ints fit one f32 plane exactly (|v| < 2^16 << 2^24);
            # the engine's op/trust id lanes ride this narrow path
            planes.append(mat.astype(jnp.float32))
            decs.append(("smallint", col, w, leaf.dtype, leaf.shape))
            col += w
        elif jnp.issubdtype(leaf.dtype, jnp.integer) or leaf.dtype == jnp.bool_:
            hi, lo = kops.int_split_f32(mat)
            planes.extend([hi, lo])
            decs.append(("int", col, w, leaf.dtype, leaf.shape))
            col += 2 * w
        else:
            assert leaf.dtype.itemsize <= 4, \
                f"f32 planes cannot carry {leaf.dtype} exactly"
            planes.append(mat.astype(jnp.float32))
            decs.append(("float", col, w, leaf.dtype, leaf.shape))
            col += w
    return jnp.concatenate(planes, 1), treedef, decs


def _decode_planes(slots: jax.Array, treedef, decs, n_rows: int) -> Pytree:
    from ..kernels import ops as kops
    out = []
    for kind, c0, w, dt, shp in decs:
        if kind == "int":
            block = kops.int_join_f32(slots[:, c0:c0 + w],
                                      slots[:, c0 + w:c0 + 2 * w], dt)
        else:
            # "smallint" f32 planes hold exact integers; astype truncates
            # back losslessly, same as the plain float path
            block = slots[:, c0:c0 + w].astype(dt)
        out.append(block.reshape((n_rows,) + shp[1:]))
    return jax.tree.unflatten(treedef, out)


def _pack_with_kernel(dst: jax.Array, payload: Pytree, n_trustees: int,
                      cfg: ChannelConfig) -> Tuple[Packed, jax.Array]:
    """``pack`` via the MXU delegation_pack kernel (cfg.pack_impl="pallas").

    Bit-identical to the lax path: slot assignment, counts, request_slot and
    dropped all match, and payload values round-trip exactly (one-hot matmul
    scatter places each row once; integers ride the split-plane encoding).
    The second_round block reruns the kernel on the rows the primary block
    rejected, preserving FIFO within each destination."""
    from ..kernels import ops as kops
    c1 = cfg.capacity
    assert c1 > 0, "channel capacity must be positive"
    r = dst.shape[0]
    interp = jax.default_backend() != "tpu"
    planes, treedef, decs = _encode_planes(payload, r)
    s1, counts1, req1 = kops.delegation_pack_planes(
        dst, planes, n_trustees, c1, interpret=interp,
        br=cfg.pack_block_rows, bs=cfg.pack_block_slots)
    slots1 = _decode_planes(s1, treedef, decs, n_trustees * c1)
    active = dst >= 0
    group_sizes = jnp.zeros((n_trustees,), jnp.int32).at[
        jnp.where(active, dst, n_trustees)].add(1, mode="drop")

    slots2 = counts2 = None
    request_slot = req1
    if cfg.overflow == "second_round" and cfg.overflow_capacity > 0:
        c2 = cfg.overflow_capacity
        dst2 = jnp.where(req1 >= 0, -1, dst)
        s2, counts2, req2 = kops.delegation_pack_planes(
            dst2, planes, n_trustees, c2, interpret=interp,
            br=cfg.pack_block_rows, bs=cfg.pack_block_slots)
        slots2 = _decode_planes(s2, treedef, decs, n_trustees * c2)
        request_slot = jnp.where(req2 >= 0, n_trustees * c1 + req2, req1)
    dropped = (request_slot < 0) & active
    return Packed(slots1, counts1, slots2, counts2,
                  request_slot, dropped), group_sizes


def pack(dst: jax.Array, payload: Pytree, n_trustees: int,
         cfg: ChannelConfig) -> Tuple[Packed, jax.Array]:
    """Client-side: bin requests into per-trustee slots with capacity.

    dst: (R,) int32 trustee id per request; -1 marks inactive rows.
    Returns (Packed, group_sizes) — group_sizes is pre-capacity demand.
    ``cfg.pack_impl`` selects the implementation: "ref" is the lax stable-sort
    path; "pallas" routes through the MXU pack kernel, bit-identically.
    """
    if cfg.pack_impl == "pallas":
        return _pack_with_kernel(dst, payload, n_trustees, cfg)
    c1 = cfg.capacity
    assert c1 > 0, "channel capacity must be positive"
    r = dst.shape[0]
    order, key_sorted, pos_sorted, group_sizes = _group_positions(dst, n_trustees)

    active_sorted = key_sorted < n_trustees
    in1 = active_sorted & (pos_sorted < c1)
    rows1 = key_sorted * c1 + jnp.minimum(pos_sorted, c1 - 1)
    slots1 = _scatter_rows(payload, order, rows1, in1, n_trustees * c1)
    counts1 = jnp.minimum(group_sizes, c1)

    slots2 = counts2 = None
    in2 = jnp.zeros_like(in1)
    slot_of_sorted = jnp.where(in1, rows1, -1)
    if cfg.overflow == "second_round" and cfg.overflow_capacity > 0:
        c2 = cfg.overflow_capacity
        pos2 = pos_sorted - c1
        in2 = active_sorted & (pos2 >= 0) & (pos2 < c2)
        rows2 = key_sorted * c2 + jnp.clip(pos2, 0, c2 - 1)
        slots2 = _scatter_rows(payload, order, rows2, in2, n_trustees * c2)
        counts2 = jnp.clip(group_sizes - c1, 0, c2)
        slot_of_sorted = jnp.where(in2, n_trustees * c1 + rows2, slot_of_sorted)

    # invert the sort: request_slot[order[i]] = slot_of_sorted[i]
    request_slot = jnp.zeros((r,), jnp.int32).at[order].set(slot_of_sorted)
    sent_sorted = in1 | in2
    dropped = jnp.ones((r,), bool).at[order].set(~sent_sorted)
    dropped = dropped & (dst >= 0)

    return Packed(slots1, counts1, slots2, counts2, request_slot, dropped), group_sizes


def _a2a(x: jax.Array, axis: str, n: int) -> jax.Array:
    """all_to_all over the trustee axis on a leading-(T,)-shaped array."""
    if n == 1:
        return x
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)


def _transmit_planes(packed: Packed, t: int, cfg: ChannelConfig) -> Received:
    """``transmit`` with ``wire_fmt="planes"``: ONE all_to_all per block.

    The payload pytree is flattened into a single f32 plane matrix (the same
    exact encoding the Pallas pack kernel uses: floats upcast, integers split
    into hi/lo 16-bit planes) and the per-slot validity mask — derived from
    the count header — rides as one extra column.  The whole request move is
    therefore a single collective instead of one per payload leaf plus a
    counts header, which is what lets a multiplexed engine round lower to
    exactly one request ``all_to_all``.  Bit-identical to the tree format.

    ``t`` counts VIRTUAL bins (device slots x ``cfg.n_lanes``); the
    collective still splits over the ``t_send`` device slots, moving each
    device's ``n_lanes * c`` lane rows as one block."""
    t_send = t // cfg.n_lanes

    def send_block(slots, counts, c):
        planes, treedef, decs = _encode_planes(slots, t * c)
        validcol = (jnp.arange(c)[None, :] < counts[:, None]) \
            .reshape(t * c, 1).astype(jnp.float32)
        planes = jnp.concatenate([planes, validcol], 1)
        planes = _a2a(planes.reshape(t_send, (t // t_send) * c, -1),
                      cfg.axis, t_send).reshape(t * c, -1)
        rows = _decode_planes(planes[:, :-1], treedef, decs, t * c)
        valid = planes[:, -1] > 0.5
        client = jnp.repeat(jnp.arange(t_send, dtype=jnp.int32),
                            (t // t_send) * c)
        return rows, valid, client

    rows, valid, client = send_block(packed.slots, packed.counts, cfg.capacity)
    if packed.slots2 is not None:
        rows2, valid2, client2 = send_block(packed.slots2, packed.counts2,
                                            cfg.overflow_capacity)
        rows = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), rows, rows2)
        valid = jnp.concatenate([valid, valid2])
        client = jnp.concatenate([client, client2])
    return Received(rows, valid, client)


def transmit(packed: Packed, n_trustees: int, cfg: ChannelConfig) -> Received:
    """Move request slots to their trustees (the delegation message).

    ``n_trustees`` counts destination BINS: device slots times
    ``cfg.n_lanes`` (the engine's per-trust slot lanes; 1 for solo rounds).
    """
    t, c1 = n_trustees, cfg.capacity
    if cfg.wire_fmt == "planes":
        return _transmit_planes(packed, t, cfg)
    t_send = t // cfg.n_lanes
    lanes = t // t_send

    def send_block(slots, counts, c):
        rows = jax.tree.map(
            lambda l: _a2a(l.reshape((t_send, lanes * c) + l.shape[1:]),
                           cfg.axis, t_send)
                        .reshape((t * c,) + l.shape[1:]),
            slots)
        cnt = _a2a(counts.reshape(t_send, lanes), cfg.axis, t_send).reshape(t)
        valid = (jnp.arange(c)[None, :] < cnt[:, None]).reshape(-1)
        client = jnp.repeat(jnp.arange(t_send, dtype=jnp.int32), lanes * c)
        return rows, valid, client

    rows, valid, client = send_block(packed.slots, packed.counts, c1)
    if packed.slots2 is not None:
        c2 = cfg.overflow_capacity
        rows2, valid2, client2 = send_block(packed.slots2, packed.counts2, c2)
        rows = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), rows, rows2)
        valid = jnp.concatenate([valid, valid2])
        client = jnp.concatenate([client, client2])
    return Received(rows, valid, client)


def respond(responses: Pytree, n_trustees: int, cfg: ChannelConfig) -> Pytree:
    """Move response rows back to clients (matching response slot).
    ``n_trustees`` counts bins (device slots x ``cfg.n_lanes``)."""
    t, c1 = n_trustees, cfg.capacity
    n1 = t * c1
    t_send = t // cfg.n_lanes
    lanes = t // t_send

    if cfg.wire_fmt == "planes":
        # one fused response transpose per block (see _transmit_planes);
        # lanes whose trust writes no response (cfg.elide_lanes) are sliced
        # out of the transpose and re-inflated as zeros — their slot rows
        # never ride the wire
        keep = tuple(l for l in range(lanes) if l not in cfg.elide_lanes)

        def back_planes(block, c):
            planes, treedef, decs = _encode_planes(block, t * c)
            wp = planes.shape[1]
            if len(keep) < lanes:
                if keep:
                    sub = planes.reshape(t_send, lanes, c, wp)[
                        :, jnp.asarray(keep)]
                    moved = _a2a(sub.reshape(t_send, len(keep) * c, wp),
                                 cfg.axis, t_send)
                    full = jnp.zeros((t_send, lanes, c, wp), planes.dtype) \
                        .at[:, jnp.asarray(keep)].set(
                            moved.reshape(t_send, len(keep), c, wp))
                else:
                    full = jnp.zeros((t_send, lanes, c, wp), planes.dtype)
                planes = full.reshape(t * c, wp)
            else:
                planes = _a2a(planes.reshape(t_send, lanes * c, wp),
                              cfg.axis, t_send).reshape(t * c, wp)
            return _decode_planes(planes, treedef, decs, t * c)

        if cfg.overflow == "second_round" and cfg.overflow_capacity > 0:
            c2 = cfg.overflow_capacity
            p1 = jax.tree.map(lambda l: l[:n1], responses)
            p2 = jax.tree.map(lambda l: l[n1:], responses)
            return jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                                back_planes(p1, c1), back_planes(p2, c2))
        return back_planes(responses, c1)

    def back(leaf_block, c):
        return _a2a(leaf_block.reshape((t_send, lanes * c)
                                       + leaf_block.shape[1:]),
                    cfg.axis, t_send) \
                 .reshape((t * c,) + leaf_block.shape[1:])

    if cfg.overflow == "second_round" and cfg.overflow_capacity > 0:
        c2 = cfg.overflow_capacity
        out = jax.tree.map(
            lambda l: jnp.concatenate([back(l[:n1], c1), back(l[n1:], c2)], 0),
            responses)
    else:
        out = jax.tree.map(lambda l: back(l, c1), responses)
    return out


def unpack(responses_at_client: Pytree, request_slot: jax.Array) -> Pytree:
    """Client-side: responses back into original request order.
    Rows for unsent requests (slot == -1) come back as zeros."""
    def take(leaf):
        safe = jnp.where(request_slot >= 0, request_slot, 0)
        rows = jnp.take(leaf, safe, axis=0)
        mask_shape = (request_slot.shape[0],) + (1,) * (leaf.ndim - 1)
        return jnp.where((request_slot >= 0).reshape(mask_shape), rows,
                         jnp.zeros_like(rows))
    return jax.tree.map(take, responses_at_client)


# ---------------------------------------------------------------------------
# Full synchronous round trip == paper's apply()
# ---------------------------------------------------------------------------

ServeFn = Callable[[Pytree, Received], Tuple[Pytree, Pytree]]
# (state_shard, received) -> (new_state_shard, response_rows)


class ChannelInfo(NamedTuple):
    group_sizes: jax.Array   # (T,) pre-capacity demand from this client
    dropped: jax.Array       # (R,) bool — not transmitted (residual after drain)
    n_rows: int              # static: channel rows per device per round
    rounds: Any = 1          # channel rounds executed (int32 after a drain)
    residual: Any = 0        # GLOBAL unsent-row count (psum; int32 after drain)
    resp_bytes_saved: int = 0  # static: response-transpose bytes per shard
    #                            NOT moved this round thanks to response-
    #                            plane / lane elision (cfg.elide_resp /
    #                            cfg.elide_lanes)
    impl_fallback: int = 0     # static: trace-time implementation
    #                            fallbacks during the serve (e.g. the
    #                            requested "pallas" serve routed through
    #                            lax for a non-f32 table); > 0 means the
    #                            round did NOT run the impl the config
    #                            asked for (cfg.strict_impl raises instead)
    rows_combined: Any = 0     # GLOBAL request rows NOT transmitted this
    #                            round because the combine pass collapsed
    #                            them into a segment representative (psum;
    #                            int32 when cfg.combine_impl != "off")
    req_bytes_saved: Any = 0   # request-wire bytes those rows would have
    #                            occupied (rows_combined x static bytes/row
    #                            of the round's request payload)


def _resp_bytes_per_row(leaf, wire_fmt: str) -> int:
    """Wire bytes one response row of this leaf occupies."""
    shape = tuple(leaf.shape)
    trailing = 1
    for d in shape[1:]:
        trailing *= int(d)
    if wire_fmt != "planes":
        return trailing * jnp.dtype(leaf.dtype).itemsize
    dt = jnp.dtype(leaf.dtype)
    if (jnp.issubdtype(dt, jnp.integer) and dt.itemsize > 2) or dt == bool:
        return 2 * trailing * 4        # hi/lo 16-bit plane split
    return trailing * 4                # one f32 plane


def resp_elision_bytes(resp_like: Pytree, cfg: "ChannelConfig",
                       n_rows: int) -> int:
    """Static response-transpose bytes per shard saved by elision: whole
    planes for fields no op writes, plus the elided lanes' rows of the
    remaining fields (multiplexed rounds)."""
    if not isinstance(resp_like, dict) or n_rows <= 0:
        return 0
    saved = 0
    kept_bpr = 0
    for name, leaf in resp_like.items():
        bpr = _resp_bytes_per_row(leaf, cfg.wire_fmt)
        if name in cfg.elide_resp:
            saved += n_rows * bpr
        else:
            kept_bpr += bpr
    if cfg.elide_lanes and cfg.n_lanes > 1 and cfg.wire_fmt == "planes":
        saved += (n_rows // cfg.n_lanes) * len(cfg.elide_lanes) * kept_bpr
    return saved


def _elide_split(resp_rows: Pytree, cfg: "ChannelConfig"):
    """Split response rows into (kept, elided) by ``cfg.elide_resp``.
    Elision only applies to flat-dict response trees (the store shape)."""
    if not cfg.elide_resp or not isinstance(resp_rows, dict):
        return resp_rows, {}
    kept = {k: v for k, v in resp_rows.items() if k not in cfg.elide_resp}
    elided = {k: v for k, v in resp_rows.items() if k in cfg.elide_resp}
    return kept, elided


def _respond_unpack(resp_rows: Pytree, request_slot: jax.Array, n_bins: int,
                    cfg: "ChannelConfig", local_resp: Optional[Pytree] = None,
                    local_mask: Optional[jax.Array] = None) -> Pytree:
    """respond -> unpack -> merge-local, with statically-elided response
    fields dropped from the transpose and re-inflated as zeros client-side.
    A round whose every response field is elided (e.g. PUT-only) pays NO
    response transpose at all — the paper's zero-size-response note."""
    r = request_slot.shape[0]
    kept, elided = _elide_split(resp_rows, cfg)
    if not elided:
        out = unpack(respond(resp_rows, n_bins, cfg), request_slot)
        if local_resp is not None:
            out = _merge_local(out, local_resp, local_mask)
        return out
    out = {}
    if kept:
        out = unpack(respond(kept, n_bins, cfg), request_slot)
        if local_resp is not None:
            out = _merge_local(out, {k: local_resp[k] for k in kept},
                               local_mask)
    zeros = {k: jnp.zeros((r,) + tuple(v.shape[1:]), v.dtype)
             for k, v in elided.items()}
    return {**out, **zeros}


def _merge_local(responses: Pytree, local_resp: Pytree, local_mask: jax.Array) -> Pytree:
    def sel(chan, loc):
        m = local_mask.reshape((-1,) + (1,) * (chan.ndim - 1))
        return jnp.where(m, loc, chan)
    return jax.tree.map(sel, responses, local_resp)


def _my_trustee_id(axis) -> jax.Array:
    try:
        return lax.axis_index(axis)
    except NameError:
        return jnp.int32(0)


def _flat_axis_index(axis) -> jax.Array:
    """Flattened device index along ``axis`` (row-major over tuple axes),
    matching how a leading dim sharded with ``P(axis)`` is laid out."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    try:
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * lax.psum(1, a) + lax.axis_index(a)
        return idx
    except NameError:
        return jnp.int32(0)


def _to_device_slots(dst: jax.Array, n_trustees: int,
                     cfg: ChannelConfig) -> jax.Array:
    """Dedicated mode: translate trustee ids [0, T) to device slots on the
    axis and mask any request originating on a trustee shard (requests may
    only come from client shards — the paper's reserved-core contract).
    With ``n_lanes > 1`` dst carries virtual bins trustee*L + lane; the
    translation shifts by ``n_clients`` whole device slots (L bins)."""
    if cfg.mode != "dedicated":
        return dst
    assert cfg.n_clients > 0, "dedicated mode needs n_clients > 0"
    from .routing import trustee_device_slot
    is_client = _flat_axis_index(cfg.axis) < cfg.n_clients
    dst = jnp.where(is_client, dst, -1)
    if cfg.n_lanes > 1:
        return jnp.where(dst >= 0,
                         dst + cfg.n_clients * cfg.n_lanes, -1) \
            .astype(jnp.int32)
    return trustee_device_slot(dst, cfg.n_clients)


def _split_local(dst: jax.Array, payload: Pytree, axis, n_lanes: int = 1):
    """Local-trustee shortcut (§5.2.1): requests addressed to self skip the
    channel; they are appended to the trustee's serve batch directly, so one
    serve call processes channel + local rows in a single deterministic pass
    (op-table order), exactly as if the trustee fiber handled them.  With
    lanes, ``dst`` holds virtual bins — self-addressed means the DEVICE slot
    (dst // n_lanes) is mine, whichever lane the row rides."""
    my_id = _my_trustee_id(axis)
    local_mask = (dst // n_lanes) == my_id
    remote_dst = jnp.where(local_mask, -1, dst)
    local_recv = Received(rows=payload, valid=local_mask,
                          client=jnp.full(dst.shape, my_id, jnp.int32))
    return remote_dst, local_recv, local_mask


def _concat_received(a: Received, b: Received) -> Received:
    return Received(
        rows=jax.tree.map(lambda x, y: jnp.concatenate([x, y], 0), a.rows, b.rows),
        valid=jnp.concatenate([a.valid, b.valid]),
        client=jnp.concatenate([a.client, b.client]))


# ---------------------------------------------------------------------------
# Client-side request combining (DESIGN.md §13)
#
# On hot-key traces many rows of one shard address the SAME (op, key); each
# currently rides its own request row through the all_to_all.  The combine
# pass runs between the local-shortcut split and ``pack``: it groups the
# remaining remote rows by (destination, op span, key) — reusing the
# ``make_grouping`` sort machinery — deactivates every non-representative
# row (dst = -1, so pack never assigns it a slot and the planner's demand
# telemetry shrinks with it), and reconstructs the full per-request
# responses after unpack.  Three archetypes cover the KV mix:
#
#   dedupe  (GET)  one row per distinct key rides the wire; the response
#                  fans back to every requester (all read the same
#                  round-entry value).
#   sum     (ADD)  the segment-FIRST row carries the segment's summed
#                  delta; each request's prior rebuilds as the combined
#                  prior + the segment-local exclusive prefix of the
#                  original deltas (exact for integer payloads within the
#                  16-bit-plane encoding bound — and for the table, exact
#                  always: addition is the same sum either way).
#   last    (PUT)  only the segment-LAST row (the locally final write)
#                  rides; last-writer-wins across clients is unchanged
#                  because serve order is (client, slot) and each client
#                  still contributes its final value in its own slot block.
#
# Ops whose outcome depends on each individual request (CAS: each expect can
# match or not) declare no combine and pass through untouched — every
# non-combinable row forms its own singleton segment.
# ---------------------------------------------------------------------------

_COMBINE_KINDS = ("dedupe", "sum", "last")
_C_DEDUPE, _C_SUM, _C_LAST = 0, 1, 2


class CombineSpan(NamedTuple):
    """Static combine plan for ONE batch span of the fused round (built by
    the engine's program builders; row membership rides a per-row int32
    span column, -1 = never combined).  Lane names are post-rename wire
    lane names (the multiplexed engine may namespace fields per trust)."""
    kind: str                # "dedupe" | "sum" | "last"
    key_lane: str            # wire lane whose value identifies the segment
    sum_lane: Optional[str] = None   # "sum": wire lane carrying the delta
    resp_tid: Optional[int] = None   # response subtree (tuple index) for a
    #                                  non-merged multiplexed round; None =
    #                                  the single/merged response dict
    resp_field: str = "value"        # "sum": response field rebuilt as
    #                                  combined prior + local excl. prefix


class CombineCtx:
    """Per-round reconstruction context ``RequestCombiner.pre`` hands to
    ``post`` (plain object on purpose: it must never be flattened as a
    pytree — it only flows within one trace)."""
    __slots__ = ("rep_row", "prefixes", "combined")

    def __init__(self, rep_row, prefixes, combined):
        self.rep_row = rep_row      # (R,) int32 request-coord representative
        self.prefixes = prefixes    # ((tid|None, field, (R, ...) array), ...)
        self.combined = combined    # (R,) bool — deactivated (not shipped)


class RequestCombiner:
    """The combine pass: ``pre`` before ``pack``, ``post`` after unpack.

    Segments never straddle destinations or spans (both are part of the
    grouping key), and a segment is atomic under capacity pressure: only
    its ONE representative can be dropped/deferred, so ``post`` expands the
    representative's dropped bit back over the segment and the drain
    engine retries whole segments."""

    def __init__(self, spans: Tuple[CombineSpan, ...]):
        assert spans, "RequestCombiner needs at least one CombineSpan"
        for sp in spans:
            assert sp.kind in _COMBINE_KINDS, sp.kind
            assert sp.kind != "sum" or sp.sum_lane is not None
        self.spans = tuple(spans)

    def pre(self, dst: jax.Array, rows: Pytree, span_col: jax.Array):
        """(dst, rows, span_col) -> (dst', rows', CombineCtx).  ``dst`` may
        already hold virtual bins / -1 for local-shortcut rows; only active
        rows of a declared span combine."""
        n = dst.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        s = len(self.spans)
        span_col = jnp.where(dst >= 0, span_col, -1)
        comb = span_col >= 0
        # primary key (dst, span) small; secondary key the op's combine key
        # (unbounded — ride it as make_grouping's second sort key rather
        # than packing into one id).  Non-combinable rows share primary -1
        # with a unique secondary -> singleton segments.
        k1 = jnp.where(comb, dst * s + span_col, -1).astype(jnp.int32)
        key_col = jnp.zeros((n,), jnp.int32)
        for sid, sp in enumerate(self.spans):
            key_col = jnp.where(span_col == sid,
                                rows[sp.key_lane].astype(jnp.int32), key_col)
        k2 = jnp.where(comb, key_col, pos)
        g = make_grouping(k1, gid2=k2)
        seg_start_row = jnp.take(g.seg_start, g.inv)   # sorted pos of seg head
        is_first = g.inv == seg_start_row
        is_last = g.inv == g.seg_end_row - 1
        kinds = jnp.asarray([_COMBINE_KINDS.index(sp.kind)
                             for sp in self.spans], jnp.int32)
        kind_col = jnp.take(kinds, jnp.clip(span_col, 0, s - 1))
        keep_last = kind_col == _C_LAST
        is_rep = jnp.where(comb,
                           jnp.where(keep_last, is_last, is_first), True)
        new_dst = jnp.where(comb & ~is_rep, -1, dst)

        new_rows = dict(rows)
        prefixes = []
        for sid, sp in enumerate(self.spans):
            if sp.kind != "sum":
                continue
            m = comb & (span_col == sid)
            leaf = rows[sp.sum_lane]
            mm = m.reshape((-1,) + (1,) * (leaf.ndim - 1))
            delta = jnp.where(mm, leaf, jnp.zeros_like(leaf))
            d_s = jnp.take(delta, g.order, axis=0)
            incl = jnp.cumsum(d_s, axis=0)
            excl = incl - d_s
            seg_base = jnp.take(excl, g.seg_start, axis=0)
            prefix = jnp.take(excl - seg_base, g.inv, axis=0)
            total_s = jnp.take(incl, jnp.clip(g.seg_end - 1, 0, n - 1),
                               axis=0) - seg_base
            total = jnp.take(total_s, g.inv, axis=0)
            # the representative (segment-first) ships the summed delta;
            # every other row of the segment is deactivated anyway
            new_rows[sp.sum_lane] = jnp.where(mm & is_rep.reshape(mm.shape),
                                              total, new_rows[sp.sum_lane])
            prefixes.append((sp.resp_tid, sp.resp_field,
                             jnp.where(mm, prefix, jnp.zeros_like(prefix))))

        rep_sorted = jnp.where(keep_last, g.seg_end_row - 1, seg_start_row)
        rep_row = jnp.where(comb, jnp.take(g.order, rep_sorted), pos)
        return new_dst, new_rows, CombineCtx(rep_row, tuple(prefixes),
                                             comb & ~is_rep)

    def post(self, responses: Pytree, dropped: jax.Array, ctx: CombineCtx):
        """Fan the representative responses back over their segments, add
        the sum archetype's exclusive-prefix priors, and expand the
        representative's dropped bit over the whole segment.  Returns
        (responses', dropped')."""
        rep = ctx.rep_row
        dropped2 = jnp.take(dropped, rep)
        out = jax.tree.map(lambda l: jnp.take(l, rep, axis=0), responses)
        served = ~dropped2
        for tid, field, pref in ctx.prefixes:
            mm = served.reshape((-1,) + (1,) * (pref.ndim - 1))
            pref = jnp.where(mm, pref, jnp.zeros_like(pref))
            if tid is None:
                out = {**out, field: out[field] + pref}
            else:
                sub = {**out[tid], field: out[tid][field] + pref}
                out = tuple(sub if i == tid else o
                            for i, o in enumerate(out))
        return out, dropped2


def as_combine_decl(c) -> Tuple[str, str, str, str]:
    """Normalize an op's combine declaration (an ``opspec.Combine`` or the
    "dedupe"/"sum"/"last" string shorthand) into a plain
    ``(kind, key_field, sum_field, resp_field)`` tuple so the engine
    builders never import the typed layer."""
    if isinstance(c, str):
        kind, key, field, resp = c, "key", "value", "value"
    else:
        kind, key, field, resp = c.kind, c.key, c.field, c.resp
    if kind not in _COMBINE_KINDS:
        raise ValueError(f"unknown combine kind {kind!r}; "
                         f"expected one of {_COMBINE_KINDS}")
    return kind, key, field, resp


def _req_bytes_per_row(rows: Pytree, wire_fmt: str) -> int:
    """Static request-wire bytes one row of this payload tree occupies
    (the per-leaf rule is the response one — same encoding both ways)."""
    return sum(_resp_bytes_per_row(l, wire_fmt)
               for l in jax.tree.leaves(rows))


def delegate(state: Pytree, dst: jax.Array, payload: Pytree, serve_fn: ServeFn,
             n_trustees: int, cfg: ChannelConfig,
             combine: Optional[RequestCombiner] = None,
             combine_span: Optional[jax.Array] = None
             ) -> Tuple[Pytree, Pytree, ChannelInfo]:
    """Synchronous delegation: pack -> transmit -> serve -> respond -> unpack.

    Must run inside shard_map over ``cfg.axis``.  Returns
    (new_state_shard, responses_in_request_order, info).

    In dedicated mode (``cfg.mode == "dedicated"``) ``dst`` still holds
    trustee ids in [0, n_trustees); they are translated to device slots past
    the ``cfg.n_clients`` client shards, requests originating on trustee
    shards are masked off, and the local shortcut is disabled (a client is
    never its own trustee).

    With ``cfg.n_lanes > 1`` (the multiplexed engine), ``dst`` holds virtual
    bins ``trustee * n_lanes + lane``: every (client, trustee) block carries
    one ``capacity`` sub-block per lane, so each lane (Trust) keeps exactly
    its solo pack/capacity/FIFO semantics inside the shared message.

    ``combine``/``combine_span`` (with ``cfg.combine_impl != "off"``)
    engage the client-side combine pass (DESIGN.md §13) between the
    shortcut split and ``pack``: local-shortcut rows are served
    individually (they never ride the wire), remote rows collapse to one
    row per (destination, span, key) segment, and responses/dropped bits
    reconstruct after unpack.  ``pack``'s demand telemetry — and hence the
    CapacityPlanner's EMA — therefore observes POST-combine demand.
    """
    r = dst.shape[0]
    n_slots = cfg.n_slots(n_trustees)
    n_bins = n_slots * cfg.n_lanes
    # device scopes (tracing.py): the client's side up to the wire is
    # trust.pack (the shortcut split and the combine pass with it)
    with tracing.scope(tracing.PACK):
        dst = _to_device_slots(dst, n_trustees, cfg)
        local_recv = local_mask = None
        if cfg.local_shortcut and cfg.mode != "dedicated":
            dst, local_recv, local_mask = _split_local(dst, payload, cfg.axis,
                                                       cfg.n_lanes)
    if local_recv is not None and n_slots == 1:
        with collect_impl_events() as impl_events, \
                tracing.scope(tracing.SERVE):
            new_state, local_resp = serve_fn(state, local_recv)
        info = ChannelInfo(jnp.zeros((n_bins,), jnp.int32),
                           jnp.zeros((r,), bool), 0,
                           impl_fallback=len(impl_events))
        return new_state, local_resp, info

    with tracing.scope(tracing.PACK):
        cctx = None
        if combine is not None and combine_span is not None \
                and cfg.combine_impl != "off":
            # combine AFTER the shortcut split (only wire rows collapse; the
            # serve still sees shortcut rows individually, appended last, in
            # exactly the combine-off order) and BEFORE pack (group_sizes —
            # the planner's demand — count combined rows).  local_recv
            # captured the pre-combine payload, so shortcut rows serve their
            # original deltas.
            dst, payload, cctx = combine.pre(dst, payload, combine_span)
        packed, group_sizes = pack(dst, payload, n_bins, cfg)
    with tracing.scope(tracing.TRANSMIT):
        received = transmit(packed, n_bins, cfg)
    n_chan = received.valid.shape[0]
    with collect_impl_events() as impl_events, tracing.scope(tracing.SERVE):
        if local_recv is not None:
            received = _concat_received(received, local_recv)
        new_state, resp_rows = serve_fn(state, received)
    with tracing.scope(tracing.RESPOND):
        local_resp = None
        if local_recv is not None:
            local_resp = jax.tree.map(lambda l: l[n_chan:], resp_rows)
            resp_rows = jax.tree.map(lambda l: l[:n_chan], resp_rows)
        responses = _respond_unpack(resp_rows, packed.request_slot, n_bins,
                                    cfg, local_resp, local_mask)
        dropped = packed.dropped
        rows_combined = req_bytes_saved = 0
        if cctx is not None:
            responses, dropped = combine.post(responses, dropped, cctx)
            rows_combined = lax.psum(
                jnp.sum(cctx.combined, dtype=jnp.int32), cfg.axis)
        req_bytes_saved = rows_combined * _req_bytes_per_row(payload,
                                                             cfg.wire_fmt)
    n_rows = n_bins * cfg.total_capacity()
    info = ChannelInfo(group_sizes, dropped, n_rows,
                       resp_bytes_saved=resp_elision_bytes(
                           resp_rows, cfg, n_rows),
                       impl_fallback=len(impl_events),
                       rows_combined=rows_combined,
                       req_bytes_saved=req_bytes_saved)
    return new_state, responses, info


def delegate_drain(state: Pytree, dst: jax.Array, payload: Pytree,
                   serve_fn: ServeFn, n_trustees: int, cfg: ChannelConfig,
                   max_rounds: Optional[int] = None,
                   combine: Optional[RequestCombiner] = None,
                   combine_span: Optional[jax.Array] = None
                   ) -> Tuple[Pytree, Pytree, ChannelInfo]:
    """Multi-round drain for ``overflow="defer"`` (paper §5.1: the two-part
    slot's third outcome, *wait for slot availability*, as bounded SPMD
    retry rounds — the lock-free-style bounded-retry translation).

    Round 1 is a full ``delegate`` (local shortcut included).  Rows the
    primary block rejected stay marked in the deferred mask; a
    ``lax.while_loop`` then re-packs and re-transmits only those rows until
    every device's batch drains or ``max_rounds`` is reached.  The loop
    condition is a ``psum``-reduced global residual count, so every shard
    executes the same number of collective rounds (no divergence).  Responses
    from each round merge back into original request order; FIFO per
    (client, trustee) pair holds across rounds (each round serves the next
    ``capacity`` rows of a pair, in issue order).

    Returns (new_state, responses, info) where ``info.rounds`` is the number
    of channel rounds executed, ``info.residual`` the global count of rows
    still unserved (> 0 only when ``max_rounds`` was too small — those rows
    keep zero responses and stay set in ``info.dropped``).
    """
    assert cfg.overflow == "defer", \
        f"delegate_drain requires overflow='defer', got {cfg.overflow!r}"
    if max_rounds is None:
        max_rounds = cfg.max_rounds
    assert max_rounds >= 1

    state, responses, info = delegate(state, dst, payload, serve_fn,
                                      n_trustees, cfg,
                                      combine=combine,
                                      combine_span=combine_span)
    remaining = info.dropped
    total = lax.psum(jnp.sum(remaining, dtype=jnp.int32), cfg.axis)
    if max_rounds == 1:
        return state, responses, info._replace(rounds=jnp.int32(1),
                                               residual=total)
    # rounds >= 2 carry only deferred REMOTE rows; self-addressed rows were
    # fully served inline in round 1 (the shortcut path has no capacity), so
    # the shortcut split is disabled for the retry rounds
    cfg_retry = dataclasses.replace(cfg, local_shortcut=False)
    combined0 = jnp.asarray(info.rows_combined, jnp.int32)
    saved0 = jnp.asarray(info.req_bytes_saved, jnp.int32)

    def cond(carry):
        _state, _resp, _rem, rounds, total, _comb, _saved = carry
        return (total > 0) & (rounds < max_rounds)

    def body(carry):
        state, responses, remaining, rounds, _total, comb, saved = carry
        dst_r = jnp.where(remaining, dst, -1)
        # deferred segments stay atomic (only a segment's representative
        # can be deferred, and post marks its whole segment remaining), so
        # re-combining the retried rows re-forms the same segments
        state, resp_r, info_r = delegate(state, dst_r, payload, serve_fn,
                                         n_trustees, cfg_retry,
                                         combine=combine,
                                         combine_span=combine_span)
        sent = remaining & ~info_r.dropped
        responses = jax.tree.map(
            lambda acc, new: jnp.where(
                sent.reshape((-1,) + (1,) * (new.ndim - 1)), new, acc),
            responses, resp_r)
        remaining = info_r.dropped
        total = lax.psum(jnp.sum(remaining, dtype=jnp.int32), cfg.axis)
        comb = comb + jnp.asarray(info_r.rows_combined, jnp.int32)
        saved = saved + jnp.asarray(info_r.req_bytes_saved, jnp.int32)
        return state, responses, remaining, rounds + 1, total, comb, saved

    (state, responses, remaining, rounds, total, combined,
     saved) = lax.while_loop(
        cond, body, (state, responses, remaining, jnp.int32(1), total,
                     combined0, saved0))
    return state, responses, ChannelInfo(info.group_sizes, remaining,
                                         info.n_rows, rounds, total,
                                         info.resp_bytes_saved,
                                         info.impl_fallback,
                                         combined, saved)


class DelegationFuture(NamedTuple):
    """apply_then(): response transmission + unpack deferred (§4.2).

    The serve already happened; calling ``wait()`` later gives XLA's
    latency-hiding scheduler room to overlap the response collective with
    whatever the client computes in between (the fiber analog)."""
    resp_rows: Pytree
    request_slot: jax.Array
    n_trustees: int
    cfg: ChannelConfig
    local_resp: Optional[Pytree] = None
    local_mask: Optional[jax.Array] = None
    combiner: Optional[RequestCombiner] = None
    combine_ctx: Optional[CombineCtx] = None
    dropped: Optional[jax.Array] = None

    def wait(self) -> Pytree:
        if self.n_trustees == 1 and self.cfg.local_shortcut:
            return self.local_resp
        out = _respond_unpack(self.resp_rows, self.request_slot,
                              self.n_trustees, self.cfg,
                              self.local_resp, self.local_mask)
        if self.combine_ctx is not None:
            out, _dropped = self.combiner.post(out, self.dropped,
                                               self.combine_ctx)
        return out


def delegate_async(state: Pytree, dst: jax.Array, payload: Pytree,
                   serve_fn: ServeFn, n_trustees: int, cfg: ChannelConfig,
                   combine: Optional[RequestCombiner] = None,
                   combine_span: Optional[jax.Array] = None
                   ) -> Tuple[Pytree, DelegationFuture, ChannelInfo]:
    """apply_then(): returns immediately after the serve phase."""
    r = dst.shape[0]
    n_slots = cfg.n_slots(n_trustees)
    n_bins = n_slots * cfg.n_lanes
    dst = _to_device_slots(dst, n_trustees, cfg)
    local_recv = local_mask = local_resp = None
    if cfg.local_shortcut and cfg.mode != "dedicated":
        dst, local_recv, local_mask = _split_local(dst, payload, cfg.axis,
                                                   cfg.n_lanes)
        if n_slots == 1:
            with collect_impl_events() as impl_events:
                new_state, local_resp = serve_fn(state, local_recv)
            fut = DelegationFuture(None, None, 1, cfg, local_resp, local_mask)
            info = ChannelInfo(jnp.zeros((n_bins,), jnp.int32),
                               jnp.zeros((r,), bool), 0,
                               impl_fallback=len(impl_events))
            return new_state, fut, info

    cctx = None
    if combine is not None and combine_span is not None \
            and cfg.combine_impl != "off":
        dst, payload, cctx = combine.pre(dst, payload, combine_span)

    packed, group_sizes = pack(dst, payload, n_bins, cfg)
    received = transmit(packed, n_bins, cfg)
    n_chan = received.valid.shape[0]
    if local_recv is not None:
        received = _concat_received(received, local_recv)
    with collect_impl_events() as impl_events:
        new_state, resp_rows = serve_fn(state, received)
    if local_recv is not None:
        local_resp = jax.tree.map(lambda l: l[n_chan:], resp_rows)
        resp_rows = jax.tree.map(lambda l: l[:n_chan], resp_rows)
    dropped = packed.dropped
    rows_combined = req_bytes_saved = 0
    if cctx is not None:
        dropped = jnp.take(dropped, cctx.rep_row)
        rows_combined = lax.psum(
            jnp.sum(cctx.combined, dtype=jnp.int32), cfg.axis)
        req_bytes_saved = rows_combined * _req_bytes_per_row(payload,
                                                             cfg.wire_fmt)
    fut = DelegationFuture(resp_rows, packed.request_slot, n_bins, cfg,
                           local_resp, local_mask,
                           combiner=combine if cctx is not None else None,
                           combine_ctx=cctx, dropped=packed.dropped)
    n_rows = n_bins * cfg.total_capacity()
    info = ChannelInfo(group_sizes, dropped, n_rows,
                       resp_bytes_saved=resp_elision_bytes(
                           resp_rows, cfg, n_rows),
                       impl_fallback=len(impl_events),
                       rows_combined=rows_combined,
                       req_bytes_saved=req_bytes_saved)
    return new_state, fut, info


# ---------------------------------------------------------------------------
# Op table — the SPMD "vtable" for delegated closures (DESIGN.md §2)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DelegatedOp:
    """A registered, vectorized operation a trustee can apply.

    ``apply(state, rows, valid, client) -> (new_state, response_rows)`` must be
    pure, vectorized over rows, and a no-op on rows where ``valid`` is False.
    This is the compile-time analog of the paper's closure fat pointer; the
    payload rows are the captured environment (pass-by-value enforced).

    Ops may additionally join the SHARED GROUPING serve path (DESIGN.md §9):

    * ``group_key(state, rows) -> (keys, n_groups)`` declares the per-row
      group key (e.g. the local table index) and its static bound; the
      serve then computes ONE stable (op, key) sort per round and shares it
      with every op via ``Received.grouping``.
    * ``fused`` points several ops at ONE fused-serve provider (an object
      with ``serve(ops, ids, state, received, impl)``): when every active
      op shares the provider, the whole op-mix applies in a single pass
      over the grouped rows — the KV table's provider implements the mix
      as lax segment primitives (``serve_impl="ref"``) or the fused Pallas
      serve kernel (``"pallas"``), sharing the sort, the gathers and the
      response assembly across ops.
    * ``apply_grouped`` optionally gives a standalone op a 5-arg
      ``(state, rows, valid, client, grouping)`` segment-primitive
      implementation, used when no shared provider covers the round.
    * ``kernel_lane`` in {"get","put","add","cas"} names the op's lane
      inside the fused kernel.
    * ``resp_fields`` names the response fields the op actually writes
      (``None`` = all); fields no active op writes are statically elided
      from the response transpose.

    ``apply`` itself stays the pre-grouping 4-arg masked implementation —
    ``serve_impl="masked"`` (the differential reference) and ops outside
    the grouped path run it unchanged.

    A DelegatedOp is the COMPILED ARTIFACT of an ``opspec.OpSpec``
    (``TrustSchema.delegated_ops`` builds the table and ``spec`` points
    back at the declaration); hand-constructing one remains supported for
    schema-less trusts (DESIGN.md §10)."""
    name: str
    apply: Callable
    group_key: Optional[Callable] = None
    kernel_lane: Optional[str] = None
    resp_fields: Optional[Tuple[str, ...]] = None
    apply_grouped: Optional[Callable] = None
    fused: Any = None
    spec: Any = None
    combine: Any = None   # opspec.Combine (or "dedupe"/"sum"/"last"
    #                       shorthand) declaring the op's client-side
    #                       request-combining archetype; None = never
    #                       combined (e.g. CAS — each request's outcome
    #                       depends on its own expect value)


def check_response_structs(named_resps) -> None:
    """Every op fused into one serve table must produce the SAME response
    structure — the round's response buffer is one tree with each row
    carrying its own op's response.  A mismatch used to surface as an
    opaque ``jax.tree.map`` structure error deep inside the accumulator;
    raise up front naming both ops and their structures instead (the serve
    analog of ``check_payload_fields``)."""
    first = None
    for label, resp in named_resps:
        leaves, treedef = jax.tree.flatten(resp)
        sig = (str(treedef), tuple((tuple(jnp.asarray(l).shape[1:]),
                                    str(jnp.asarray(l).dtype))
                                   for l in leaves))
        if first is None:
            first = (label, sig)
        elif first[1] != sig:
            l0, s0 = first
            raise ValueError(
                f"ops fused into one serve table must agree on the response "
                f"structure: op {l0!r} responds with {s0[0]} "
                f"(trailing shapes/dtypes {list(s0[1])}) but op {label!r} "
                f"responds with {sig[0]} (trailing shapes/dtypes "
                f"{list(sig[1])}); give the ops matching resp trees or "
                f"serve them from separate Trusts")


def _serve_grouping(ops, ids, state, received: Received) -> Optional[Grouping]:
    """The SHARED grouping pass: one stable sort by (op, group key) for the
    whole round.  Returns None when no active op declares ``group_key``."""
    grouped = [i for i in ids if ops[i].group_key is not None]
    if not grouped:
        return None
    rows, valid = received.rows, received.valid
    multi = len(ids) > 1
    op_col = rows["op"] if multi else None
    keys, spans = {}, []
    shared = {}   # ops sharing one group_key fn (the KV table's) share keys
    for i in grouped:
        fn = ops[i].group_key
        if fn not in shared:
            k, span = fn(state, rows)
            shared[fn] = (k.astype(jnp.int32), int(span))
        keys[i], span = shared[fn]
        spans.append(span)
    span = max(max(spans), 1)
    # combined id: (op rank, key) for grouped ops, (op rank, 0) for plain
    # ops, sentinel for inactive rows — inactive sorts last, each op's rows
    # stay contiguous and in request order (stable sort)
    sentinel = len(ids) * span
    gid = jnp.full(valid.shape, sentinel, jnp.int32)
    for rank_i, i in enumerate(ids):
        m = valid & (op_col == i) if multi else valid
        key_i = jnp.clip(keys[i], 0, span - 1) if i in keys else 0
        gid = jnp.where(m, rank_i * span + key_i, gid)
    return make_grouping(gid, sentinel)


def _apply_op(op: DelegatedOp, state, rows, m, client, grouping):
    """Dispatch: ``apply_grouped`` (5-arg) when a grouping is at hand and
    the op provides one, the legacy 4-arg masked ``apply`` otherwise."""
    if grouping is not None and op.apply_grouped is not None:
        return op.apply_grouped(state, rows, m, client, grouping)
    return op.apply(state, rows, m, client)


def _serve_optable_masked(ops: Tuple[DelegatedOp, ...],
                          ids: Tuple[int, ...]) -> ServeFn:
    """The pre-grouping serve: one masked full-buffer pass per op.  Kept as
    ``serve_impl="masked"`` — the differential reference the shared-grouping
    and Pallas paths must match bit-for-bit."""
    def serve(state, received: Received):
        rows = received.rows
        # the op lane may be omitted from the wire when the round carries a
        # single op (it would be a constant column)
        op_ids = rows.get("op") if hasattr(rows, "get") else rows["op"]
        out_resp = None
        first = None
        for i in ids:
            m = received.valid & (op_ids == i) if len(ids) > 1 else received.valid
            state, resp = _apply_op(ops[i], state, rows, m, received.client,
                                    None)
            if out_resp is None:
                first = (ops[i].name, resp)
                out_resp = jax.tree.map(jnp.zeros_like, resp)
            else:
                check_response_structs([first, (ops[i].name, resp)])
            out_resp = jax.tree.map(
                lambda acc, r: jnp.where(
                    m.reshape((-1,) + (1,) * (r.ndim - 1)), r, acc),
                out_resp, resp)
        return state, out_resp
    return serve


def serve_optable(ops: Tuple[DelegatedOp, ...],
                  active_ids: Optional[Tuple[int, ...]] = None,
                  serve_impl: str = "ref",
                  cfg: Optional["ChannelConfig"] = None) -> ServeFn:
    """Multi-op serve: payload rows carry an 'op' column selecting the op.
    When the caller statically knows which ops appear in the batch (Trust
    does), ``active_ids`` skips the rest at trace time.  ``cfg`` (when
    given) hands the fused provider the kernel tiling knobs
    (``serve_block_rows``/``serve_block_keys``) and the ``strict_impl``
    fallback policy.

    ``serve_impl`` selects the trustee hot path (DESIGN.md §9):

    * ``"ref"``    — ONE shared grouping pass (stable (op, key) sort +
                     segment boundaries) per round, exposed via
                     ``Received.grouping``; when every active op shares a
                     fused provider (``DelegatedOp.fused`` — the KV table
                     does), the WHOLE op-mix applies in one lax pass of
                     segment primitives.  Other ops apply per-op
                     (``apply_grouped`` if declared, masked otherwise).
    * ``"pallas"`` — same grouping, but the provider routes the mix
                     through the fused MXU serve kernel in one pass over
                     the sorted rows.
    * ``"masked"`` — the legacy per-op full-buffer passes (differential
                     reference only).

    All three are bit-identical on integer-exact payloads; "ref"/"pallas"
    reorder float accumulation only within what the round-batch semantics
    already leave unspecified (§4)."""
    ids = tuple(range(len(ops))) if active_ids is None else tuple(active_ids)
    if serve_impl == "masked":
        return _serve_optable_masked(ops, ids)
    assert serve_impl in ("ref", "pallas"), \
        f"unknown serve_impl {serve_impl!r} (want ref|pallas|masked)"
    # one shared fused-serve provider across every active op -> the whole
    # op-mix applies in a single pass over the grouped rows
    fused = ops[ids[0]].fused
    if fused is None or any(ops[i].fused is not fused for i in ids):
        fused = None

    def serve(state, received: Received):
        rows = received.rows
        grouping = _serve_grouping(ops, ids, state, received)
        received = received._replace(grouping=grouping)
        if fused is not None and grouping is not None:
            return fused.serve(ops, ids, state, received, serve_impl, cfg)
        op_ids = rows.get("op") if hasattr(rows, "get") else rows["op"]
        out_resp = None
        first = None
        for i in ids:
            m = received.valid & (op_ids == i) if len(ids) > 1 else received.valid
            state, resp = _apply_op(ops[i], state, rows, m, received.client,
                                    grouping)
            if out_resp is None:
                first = (ops[i].name, resp)
                out_resp = jax.tree.map(jnp.zeros_like, resp)
            else:
                check_response_structs([first, (ops[i].name, resp)])
            out_resp = jax.tree.map(
                lambda acc, r: jnp.where(
                    m.reshape((-1,) + (1,) * (r.ndim - 1)), r, acc),
                out_resp, resp)
        return state, out_resp
    return serve


def serve_multiplex(tables: Sequence[Tuple[Tuple[DelegatedOp, ...],
                                           Tuple[int, ...]]],
                    renames: Sequence[dict],
                    merge_resp: bool = False,
                    serve_impl: str = "ref",
                    cfg: Optional["ChannelConfig"] = None) -> ServeFn:
    """Merged serve table for one MULTIPLEXED round over several Trusts.

    ``state`` is a tuple of per-trust state pytrees; request rows carry a
    ``"trust"`` lane next to the ``"op"`` lane, and each trust's payload
    fields live in the shared lane named by ``renames[tid][field]`` (fields
    whose dtype/shape agree across trusts share one wire lane — the row sets
    are disjoint so sharing is free; mismatched fields get per-trust lanes).
    One deterministic pass dispatches per (trust, op): trust ``tid`` serves
    the rows where ``rows["trust"] == tid`` through its own op table, with
    its own state threaded — so intra-trust semantics are exactly those of a
    solo round, and cross-trust order is (registration, op-table) order.

    The response is a tuple of per-trust response trees (rows not belonging
    to a trust stay zero in that trust's tree) — or, with ``merge_resp``
    (legal whenever every trust's response structure matches), ONE tree with
    each row carrying its own trust's response: the row sets are disjoint,
    so merging halves the response-transpose bytes per extra trust."""
    serves = tuple(serve_optable(ops, active, serve_impl=serve_impl,
                                 cfg=cfg)
                   for ops, active in tables)

    def serve(states, received: Received):
        trust_col = received.rows["trust"]
        new_states, resps = [], []
        for tid, serve_t in enumerate(serves):
            rows_t = {}
            if "op" in received.rows:
                rows_t["op"] = received.rows["op"]
            for field, lane in renames[tid].items():
                rows_t[field] = received.rows[lane]
            recv_t = Received(rows_t,
                              received.valid & (trust_col == tid),
                              received.client)
            s, r = serve_t(states[tid], recv_t)
            new_states.append(s)
            resps.append(r)
        if merge_resp:
            out = resps[0]
            for tid in range(1, len(resps)):
                m = trust_col == tid
                out = jax.tree.map(
                    lambda acc, r, mm=m: jnp.where(
                        mm.reshape((-1,) + (1,) * (r.ndim - 1)), r, acc),
                    out, resps[tid])
            return tuple(new_states), out
        return tuple(new_states), tuple(resps)
    return serve


def serve_multiplex_strided(tables: Sequence[Tuple[Tuple[DelegatedOp, ...],
                                                   Tuple[int, ...]]],
                            renames: Sequence[dict], n_lanes: int,
                            t_send: int, c1: int, c2: int,
                            serve_impl: str = "ref",
                            cfg: Optional["ChannelConfig"] = None) -> ServeFn:
    """``serve_multiplex`` for the LANE slot layout (``cfg.n_lanes > 1``).

    With per-trust lanes the received buffer is block-structured: for each
    of the ``t_send`` client blocks, lane ``tid`` owns a STATIC ``c1`` slice
    of the primary block (and ``c2`` of the overflow block), followed by an
    optional local-shortcut tail of whole request rows.  Each trust's serve
    therefore gathers only its own ``t_send * (c1 + c2)`` channel rows plus
    the shared tail — total serve work stays LINEAR in the number of trusts
    (the masked ``serve_multiplex`` pays a full-buffer pass per trust).

    Requires every trust's response structure to match (the caller falls
    back to the masked variant otherwise): per-trust responses reassemble
    into one merged buffer by restacking the lane slices, so the response
    transpose moves each row's bytes exactly once."""
    serves = tuple(serve_optable(ops, active, serve_impl=serve_impl,
                                 cfg=cfg)
                   for ops, active in tables)
    n1, n2 = t_send * n_lanes * c1, t_send * n_lanes * c2

    def serve(states, received: Received):
        rows, valid, client = received.rows, received.valid, received.client
        n_local = valid.shape[0] - n1 - n2
        assert n_local >= 0, \
            "strided multiplex serve called with a non-lane row layout"

        def sub(leaf, tid):
            parts = [leaf[:n1]
                     .reshape((t_send, n_lanes, c1) + leaf.shape[1:])[:, tid]
                     .reshape((t_send * c1,) + leaf.shape[1:])]
            if n2:
                parts.append(
                    leaf[n1:n1 + n2]
                    .reshape((t_send, n_lanes, c2) + leaf.shape[1:])[:, tid]
                    .reshape((t_send * c2,) + leaf.shape[1:]))
            if n_local:
                parts.append(leaf[n1 + n2:])
            return jnp.concatenate(parts, 0) if len(parts) > 1 else parts[0]

        # the trust lane is only on the wire when a local-shortcut tail
        # exists (lane membership is the slot LAYOUT for channel rows)
        trust_col = rows.get("trust")
        assert trust_col is not None or not n_local, \
            "local-shortcut tail needs the trust lane on the wire"
        new_states, resps = [], []
        for tid, serve_t in enumerate(serves):
            rows_t = {}
            if "op" in rows:
                rows_t["op"] = sub(rows["op"], tid)
            for field, lane in renames[tid].items():
                rows_t[field] = sub(rows[lane], tid)
            valid_t = sub(valid, tid)
            if trust_col is not None:
                # channel rows in lane tid always carry trust == tid; the
                # mask only bites on the shared local-shortcut tail
                valid_t = valid_t & (sub(trust_col, tid) == tid)
            recv_t = Received(rows_t, valid_t, sub(client, tid))
            s, r = serve_t(states[tid], recv_t)
            new_states.append(s)
            resps.append(r)

        # reassemble one full response buffer from the per-trust sub-batches
        lm = trust_col[n1 + n2:] if n_local else None

        def join(*leaves):
            shp = leaves[0].shape[1:]
            parts = [jnp.stack(
                [l[:t_send * c1].reshape((t_send, c1) + shp) for l in leaves],
                1).reshape((n1,) + shp)]
            if n2:
                o1 = t_send * c1
                parts.append(jnp.stack(
                    [l[o1:o1 + t_send * c2].reshape((t_send, c2) + shp)
                     for l in leaves], 1).reshape((n2,) + shp))
            if n_local:
                oL = t_send * (c1 + c2)
                tail = leaves[0][oL:]
                for tid in range(1, n_lanes):
                    m = (lm == tid).reshape((-1,) + (1,) * (tail.ndim - 1))
                    tail = jnp.where(m, leaves[tid][oL:], tail)
                parts.append(tail)
            return jnp.concatenate(parts, 0) if len(parts) > 1 else parts[0]

        resp = jax.tree.map(join, *resps)
        return tuple(new_states), resp
    return serve
