"""DelegatedKVStore — the paper's key-value store (§6.3) as a Trust.

State: a direct-indexed table of fixed-width values, range/mod-partitioned
over trustees (the paper pre-fills a known key space and benchmarks GET/PUT
over it; memcached's hash power is fixed likewise).  Ops:

  GET(key)                 -> value            (read request, large response)
  PUT(key, value)          -> ()               (write request, no response —
                                                the paper notes zero-size PUT
                                                responses save response bytes)
  ADD(key, delta)          -> old value        (fetch-and-add, Fig 6)
  CAS(key, expect, value)  -> success flag

Within one channel round, multiple writers to one key are resolved
last-writer-wins *in request order* (client id, slot order) — matching the
paper's per-pair FIFO plus a deterministic inter-client order (the Rust
runtime serves slots in client order; we reproduce that exactly).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .channel import DelegatedOp, Received
from .opspec import Combine, Field, OpSpec, TrustSchema
from .trust import Trust, TrusteeGroup
from . import routing, tracing

Pytree = Any


def _mask(x, m):
    return jnp.where(m.reshape((-1,) + (1,) * (x.ndim - 1)), x, jnp.zeros_like(x))


def _ordered_last_writer(table: jax.Array, idx: jax.Array, rows: jax.Array,
                         m: jax.Array) -> jax.Array:
    """Pre-grouping last-writer-wins scatter (masked reference serve only):
    scatter each request's sequence number, keep the max, gather the winner.
    The grouped ops replace this with a segment-last scatter."""
    safe_idx = jnp.where(m, idx, table.shape[0])
    seq = jnp.arange(1, idx.shape[0] + 1, dtype=jnp.int32)
    winner = jnp.zeros((table.shape[0] + 1,), jnp.int32).at[safe_idx].max(
        jnp.where(m, seq, 0), mode="drop")[: table.shape[0]]
    has_write = winner > 0
    win_rows = rows[jnp.clip(winner - 1, 0, None)]
    return jnp.where(has_write[:, None] if table.ndim > 1 else has_write,
                     win_rows, table)


class KVTableServe:
    """Fused grouped serve for the KV op-mix (DESIGN.md §9).

    One provider object is shared by all four ops of one table
    (``DelegatedOp.fused``); whenever a round's active ops all belong to
    it, ``serve_optable`` hands the WHOLE mix here and the round applies in
    a single pass over the channel's shared (op, key) grouping:

      * ONE stable sort per round (``Received.grouping``) instead of
        ADD's private argsort + searchsorted and PUT/CAS's scatter-max of
        sequence numbers;
      * last-writer-wins = "the segment's last row" (one compare in
        request coordinates — winners have unique keys, a plain scatter
        commits them);
      * fetch-and-add priors = segment-exclusive prefix sums over the
        sorted deltas;
      * CAS keeps round-snapshot-at-phase-entry semantics and commits the
        last MATCHING row per segment (running max of matching positions);
      * op-phase order matches the masked reference exactly (GET reads the
        round-entry table, PUT before ADD before CAS) and the response
        planes assemble once (the per-op row sets are disjoint).

    ``impl="pallas"`` routes the same grouped mix through the tiled MXU
    serve kernels (``kernels/delegation_serve``) — gathers, segment
    primitives and scatters as one-hot matmuls over (block_rows,
    block_keys) tiles.  When the table is not f32 it falls back to the lax
    pass bit-identically, reporting the downgrade through the channel's
    impl-event side channel (and raising under
    ``ChannelConfig.strict_impl``)."""

    def __init__(self, n_trustees: int, value_width: int, dtype):
        self.n_trustees = n_trustees
        self.value_width = value_width
        self.dtype = dtype

    def local_idx(self, rows):
        return (rows["key"] // self.n_trustees).astype(jnp.int32)

    def group_key(self, state, rows):
        return self.local_idx(rows), state["table"].shape[0]

    def _lane_masks(self, ops, ids, received):
        multi = len(ids) > 1
        op_col = received.rows["op"] if multi else None
        lanes = {}
        for i in ids:
            m = received.valid & (op_col == i) if multi else received.valid
            lanes[ops[i].kernel_lane] = m
        return lanes

    def serve(self, ops, ids, state, received, impl: str, cfg=None):
        """Entry point used by ``channel.serve_optable``.  ``cfg`` (a
        ``ChannelConfig``, optional for direct callers) supplies the serve
        kernel's tile sizes and the ``strict_impl`` fallback policy."""
        if impl == "pallas":
            return self.serve_kernel(ops, ids, state, received, cfg)
        return self.serve_lax(ops, ids, state, received)

    def serve_lax(self, ops, ids, state, received):
        rows, g = received.rows, received.grouping
        table = state["table"]
        n_local = table.shape[0]
        n = received.valid.shape[0]
        lanes = self._lane_masks(ops, ids, received)
        idx = self.local_idx(rows)
        value = rows.get("value")
        pos = jnp.arange(n, dtype=jnp.int32)

        def commit(table, win):
            """Scatter each winning row's value straight into the table.
            Winners have unique keys (one per segment); each loser gets
            its own out-of-range target, so the indices stay unique and
            the losers drop.  The session donates the table, so the
            scatter writes the rows in place and costs the wave's rows,
            not the table's: a winner array the table's length, a gather
            of a row for every table row and a select over the whole
            table were 88-98% of the round on a TPU v5e."""
            with tracing.scope(tracing.KV_COMMIT):
                tgt = jnp.where(win, idx, n_local + pos)
                return table.at[tgt].set(value, mode="drop",
                                         unique_indices=True)

        resp_value = jnp.zeros((n, self.value_width), table.dtype)
        # GET — reads the round-entry table
        if "get" in lanes:
            with tracing.scope(tracing.KV_GET):
                m = lanes["get"]
                resp_value = resp_value + _mask(table[jnp.where(m, idx, 0)],
                                                m)
        # PUT — segment-last rows commit (request coords: one compare)
        if "put" in lanes:
            with tracing.scope(tracing.KV_PUT):
                m = lanes["put"]
                table = commit(table, m & (g.inv == g.seg_end_row - 1))
        # ADD — prior = segment-exclusive prefix sum of the sorted deltas
        if "add" in lanes:
            with tracing.scope(tracing.KV_ADD):
                m = lanes["add"]
                delta = _mask(value, m)
                delta_s = jnp.take(delta, g.order, axis=0)
                excl = jnp.cumsum(delta_s, axis=0) - delta_s
                prior = jnp.take(excl - excl[g.seg_start], g.inv, axis=0)
                base = table[jnp.where(m, idx, 0)]
                resp_value = resp_value + _mask(base + prior, m)
                with tracing.scope(tracing.KV_COMMIT):
                    table = table.at[jnp.where(m, idx, n_local)].add(
                        delta, mode="drop")
        # CAS — compare against the post-ADD table; the LAST matching row
        # of each segment commits (running max of matching positions, read
        # at the segment end, aliases no earlier segment: positions grow
        # globally)
        if "cas" in lanes:
            with tracing.scope(tracing.KV_CAS):
                m = lanes["cas"]
                cur = table[jnp.where(m, idx, 0)]
                ok = m & jnp.all(cur == rows["expect"], axis=-1)
                ok_s = jnp.take(ok, g.order)
                run = jax.lax.cummax(jnp.where(ok_s, pos, -1))
                write_s = (pos == run[jnp.clip(g.seg_end - 1, 0, n - 1)]) \
                    & ok_s
                table = commit(table, jnp.take(write_s, g.inv))
                resp_value = resp_value + _mask(cur, m)
                flag = ok.astype(jnp.int32)
        else:
            flag = jnp.zeros((n,), jnp.int32)
        return {**state, "table": table}, \
               {"value": resp_value, "flag": flag}

    def serve_kernel(self, ops, ids, state, received, cfg=None):
        """The same grouped mix as tiled Pallas passes — the MXU sibling
        of ``delegation_pack`` (bit-identical on integer-exact payloads).
        Tile sizes come from ``cfg`` (``serve_block_rows`` /
        ``serve_block_keys``); the row-tile carry metadata comes from the
        shared grouping (``Grouping.tile_meta``)."""
        from ..kernels import ops as kops
        from . import channel as _channel
        table = state["table"]
        if table.dtype != jnp.float32:
            # static (trace-time) decision: the MXU serve path is f32-only.
            # Report it through the impl-event side channel so ChannelInfo /
            # engine stats can surface the silent downgrade, and hard-fail
            # when the caller demanded the pallas path.
            event = (f"serve_kernel: table dtype {table.dtype} is not "
                     f"float32; fell back to serve_lax")
            _channel.report_impl_event(event)
            if cfg is not None and cfg.strict_impl:
                raise TypeError(
                    event + " (ChannelConfig.strict_impl=True forbids the "
                    "silent lax fallback; use serve_impl='ref' or an f32 "
                    "table)")
            return self.serve_lax(ops, ids, state, received)
        rows, g = received.rows, received.grouping
        n_local, w = table.shape
        n = received.valid.shape[0]
        lanes = self._lane_masks(ops, ids, received)
        lane_ids = ("get", "put", "add", "cas")
        lane = jnp.full((n,), -1, jnp.int32)
        for name, m in lanes.items():
            lane = jnp.where(m, lane_ids.index(name), lane)
        keys = jnp.where(lane >= 0,
                         jnp.clip(self.local_idx(rows), 0, n_local - 1),
                         n_local)
        value = rows.get("value")
        if value is None:
            value = jnp.zeros((n, w), table.dtype)
        expect = rows.get("expect")
        if expect is None:
            expect = jnp.zeros((n, w), table.dtype)
        srt = lambda x: jnp.take(x, g.order, axis=0)
        interp = jax.default_backend() != "tpu"
        br = cfg.serve_block_rows if cfg is not None else 256
        bk = cfg.serve_block_keys if cfg is not None else 512
        meta = g.tile_meta(block_rows=br)
        # one kernel serves every lane and writes the table back: all of it
        # reads as the commit
        with tracing.scope(tracing.KV_COMMIT):
            new_table, val_s, flag_s = kops.delegation_serve(
                table, srt(keys), srt(lane), srt(value.astype(jnp.float32)),
                srt(expect.astype(jnp.float32)), g.seg_start, meta.cont,
                br=meta.block_rows, bk=bk, interpret=interp)
        unsrt = lambda x: jnp.take(x, g.inv, axis=0)
        return {**state, "table": new_table.astype(table.dtype)}, \
               {"value": unsrt(val_s).astype(table.dtype),
                "flag": unsrt(flag_s).astype(jnp.int32)}


def kv_reshard(host_state: Dict[str, np.ndarray], old_t: int,
               new_t: int) -> Dict[str, np.ndarray]:
    """Re-layout an owner-major KV table for a different trustee count
    (the failover path: ``TrustSchema.reshard``).

    The table stores keys owner-major: trustee ``i`` holds keys
    ``{k : k % old_t == i}`` at local index ``k // old_t``.  Reconstruct
    key order, pad to a multiple of ``new_t`` (the extra rows are phantom
    keys past the key space — zero, never routed to), and re-lay out
    owner-major for ``new_t``."""
    table = np.asarray(host_state["table"])
    n_old = table.shape[0]
    assert n_old % old_t == 0, (n_old, old_t)
    n_local = n_old // old_t
    key_order = np.zeros_like(table)
    for i in range(old_t):
        key_order[np.arange(i, n_old, old_t)] = \
            table[i * n_local:(i + 1) * n_local]
    n_new = ((n_old + new_t - 1) // new_t) * new_t
    if n_new != n_old:
        key_order = np.concatenate(
            [key_order,
             np.zeros((n_new - n_old,) + table.shape[1:], table.dtype)], 0)
    nl2 = n_new // new_t
    out = np.zeros((n_new,) + table.shape[1:], table.dtype)
    for i in range(new_t):
        out[i * nl2:(i + 1) * nl2] = key_order[np.arange(i, n_new, new_t)]
    return {**{k: np.asarray(v) for k, v in host_state.items()},
            "table": out}


def make_kv_schema(n_trustees: int, value_width: int,
                   dtype=jnp.float32) -> TrustSchema:
    """The paper's KV store (§6.3) as a declarative ``TrustSchema``.

    Everything ``entrust`` needs derives from here (DESIGN.md §10): the
    payload/response Fields (typed, validated at handle-call time), the
    response struct (``resp_like``), the per-op ``writes`` elision
    metadata, and the mod-router key→owner rule — so callers of the typed
    handles pass keys, never shard ids.  Local key index =
    key // n_trustees (mod router).

    Each op's ``serve`` is the pre-grouping masked implementation — the
    ``serve_impl="masked"`` differential reference, byte-for-byte the old
    serve.  All four ops share ONE ``KVTableServe`` provider (``fused``),
    so grouped rounds (``serve_impl="ref"|"pallas"``) apply the whole mix
    in a single pass over the channel's shared (op, key) grouping."""

    fused = KVTableServe(n_trustees, value_width, dtype)
    local_idx = fused.local_idx

    def get(state, rows, m, client):
        idx = jnp.where(m, local_idx(rows), 0)
        vals = state["table"][idx]
        return state, {"value": _mask(vals, m),
                       "flag": jnp.zeros(m.shape, jnp.int32)}

    def put(state, rows, m, client):
        idx = local_idx(rows)
        table = _ordered_last_writer(state["table"], idx, rows["value"], m)
        return {**state, "table": table}, \
               {"value": jnp.zeros(m.shape + (value_width,), dtype),
                "flag": jnp.zeros(m.shape, jnp.int32)}

    def add(state, rows, m, client):
        # per-op sort + segmented exclusive prefix sum (O(R log R) per op)
        n_local = state["table"].shape[0]
        idx = jnp.where(m, local_idx(rows), n_local)
        delta = _mask(rows["value"], m)
        order = jnp.argsort(idx, stable=True)
        idx_s = idx[order]
        delta_s = delta[order]
        incl = jnp.cumsum(delta_s, axis=0)
        excl = incl - delta_s
        seg_start = jnp.searchsorted(idx_s, idx_s, side="left")
        prior_s = excl - excl[seg_start]
        prior = jnp.zeros_like(delta).at[order].set(prior_s)
        base = state["table"][jnp.where(m, idx, 0)]
        old = _mask(base + prior, m)
        table = state["table"].at[idx].add(delta, mode="drop")
        return {**state, "table": table}, \
               {"value": old, "flag": jnp.zeros(m.shape, jnp.int32)}

    def cas(state, rows, m, client):
        idx = jnp.where(m, local_idx(rows), 0)
        cur = state["table"][idx]
        ok = m & jnp.all(cur == rows["expect"], axis=-1)
        table = _ordered_last_writer(state["table"], local_idx(rows),
                                     rows["value"], ok)
        return {**state, "table": table}, \
               {"value": _mask(cur, m), "flag": ok.astype(jnp.int32)}

    key_f = Field("key", (), jnp.int32)
    value_f = Field("value", (value_width,), dtype)
    expect_f = Field("expect", (value_width,), dtype)
    resp = (Field("value", (value_width,), dtype), Field("flag", (), jnp.int32))
    kw = dict(response=resp, group_key=fused.group_key, fused=fused)
    return TrustSchema(
        "kv",
        # Combine archetypes (DESIGN.md §13): GET dedupes (every duplicate
        # reads the same round-entry table), ADD ships one summed delta and
        # rebuilds per-request priors client-side, PUT ships only the
        # segment-last writer (same global winner).  CAS declares NO
        # combine: each expect can individually match or miss.
        ops=[OpSpec("get", payload=(key_f,), writes=("value",),
                    serve=get, kernel_lane="get",
                    combine=Combine("dedupe"), **kw),
             OpSpec("put", payload=(key_f, value_f), writes=(),
                    serve=put, kernel_lane="put",
                    combine=Combine("last"), **kw),
             OpSpec("add", payload=(key_f, value_f), writes=("value",),
                    serve=add, kernel_lane="add",
                    combine=Combine("sum"), **kw),
             OpSpec("cas", payload=(key_f, value_f, expect_f),
                    writes=("value", "flag"),
                    serve=cas, kernel_lane="cas", **kw)],
        state={"table": Field("table", (value_width,), dtype)},
        route=lambda payload, t: routing.mod_router(payload["key"], t),
        reshard=kv_reshard)


def make_kv_ops(n_trustees: int, value_width: int,
                dtype=jnp.float32) -> Tuple[DelegatedOp, ...]:
    """Back-compat: the compiled op table of ``make_kv_schema`` (each
    ``DelegatedOp`` is the compiled artifact of one ``OpSpec``)."""
    return make_kv_schema(n_trustees, value_width, dtype).delegated_ops()


class DelegatedKVStore:
    """High-level store facade used by the KV-store / memcached benchmarks.

    ``mode="shared"`` (default) entrusts the table to every device; in
    ``mode="dedicated"`` the last ``n_dedicated`` device slots of the mesh
    hold the table and serve the remaining client devices (the paper's
    reserved trustee cores).  The public GET/PUT/ADD/CAS API is identical in
    both modes."""

    def __init__(self, mesh: Mesh, n_keys: int, value_width: int = 4,
                 axis: Any = None, dtype=jnp.float32,
                 capacity: Optional[int] = None,
                 overflow: str = "second_round", overflow_capacity: int = 0,
                 local_shortcut: bool = True, mode: str = "shared",
                 n_dedicated: int = 0, max_rounds: int = 1,
                 pack_impl: str = "ref", serve_impl: str = "ref",
                 name: Optional[str] = None,
                 plan_capacity: bool = False, session=None,
                 strict_impl: bool = False,
                 serve_blocks: Any = (256, 512),
                 pack_blocks: Any = (256, 512),
                 combine: str = "off"):
        axis = axis if axis is not None else tuple(mesh.axis_names)
        group = TrusteeGroup(mesh, axis, mode=mode, n_dedicated=n_dedicated)
        t = group.n_trustees
        self.group = group
        self.mode = mode
        self.n_keys = n_keys
        self.n_keys_padded = ((n_keys + t - 1) // t) * t
        self.value_width = value_width
        table = jnp.zeros((self.n_keys_padded, value_width), dtype)
        # the factory lets session.re_entrust rebuild the op table for a
        # different trustee count (KVTableServe bakes n_trustees into its
        # serve closures); the schema's reshard= rule re-lays the table out
        schema_factory = lambda t_: make_kv_schema(t_, value_width, dtype)
        self.schema = schema_factory(t)
        # entrusting registers the trust with the (ambient or given)
        # TrustSession, so session.step() can fuse this store's pending
        # batches with every other registered Trust's into one round;
        # the op table, resp_like and elision metadata derive from the
        # schema, and self.trust.op carries the typed handles
        self.trust = group.entrust(
            {"table": table}, schema=self.schema,
            capacity=capacity, overflow=overflow,
            overflow_capacity=overflow_capacity,
            local_shortcut=local_shortcut, max_rounds=max_rounds,
            pack_impl=pack_impl, serve_impl=serve_impl, name=name,
            plan_capacity=plan_capacity, session=session,
            strict_impl=strict_impl, serve_blocks=serve_blocks,
            pack_blocks=pack_blocks, combine=combine,
            schema_factory=schema_factory)
        self.t = t
        self.dtype = dtype
        self.trust._on_rebuild.append(self._on_trust_rebuild)

    def _on_trust_rebuild(self, trust: Trust) -> None:
        """Failover hook: ``session.re_entrust`` rebound the trust onto a
        new trustee group — refresh the facade's cached layout (trustee
        count, schema, padded key-space size) so route/prefill/dump keep
        working against the survivors' layout."""
        self.group = trust.group
        self.mode = trust.group.mode
        self.t = trust.n_trustees
        self.schema = trust.schema
        self.n_keys_padded = int(
            jax.tree.leaves(trust.trustee_state())[0].shape[0])

    @property
    def session(self):
        """The TrustSession this store's trust is registered with."""
        return self.trust.session

    # -- routing ---------------------------------------------------------
    def route(self, keys: jax.Array) -> jax.Array:
        """Key → trustee (the schema's router).  Only needed by callers of
        the stringly ``trust.apply``/``submit`` shims; the typed handles
        route internally."""
        return routing.mod_router(keys, self.t)

    def _payload(self, keys, value=None, expect=None):
        """Back-compat payload builder for the stringly shims (the typed
        handles bind and validate arguments through the schema instead)."""
        p = {"key": keys.astype(jnp.int32)}
        if value is not None:
            p["value"] = value.astype(self.dtype)
        if expect is not None:
            p["expect"] = expect.astype(self.dtype)
        return p

    # -- sync API (typed handles: routed + validated) -----------------------
    def get(self, keys):
        return self.trust.op.get(keys)["value"]

    def put(self, keys, values):
        self.trust.op.put(keys, values)

    def add(self, keys, deltas):
        return self.trust.op.add(keys, deltas)["value"]

    def cas(self, keys, expect, values):
        r = self.trust.op.cas(keys, value=values, expect=expect)
        return r["flag"], r["value"]

    # -- async API (apply_then) ---------------------------------------------
    def get_then(self, keys, then=None):
        return self.trust.op.get.then(keys, then=then)

    def put_then(self, keys, values, then=None):
        return self.trust.op.put.then(keys, values, then=then)

    def add_then(self, keys, deltas, then=None):
        return self.trust.op.add.then(keys, deltas, then=then)

    def cas_then(self, keys, expect, values, then=None):
        return self.trust.op.cas.then(keys, value=values, expect=expect,
                                      then=then)

    def flush(self):
        self.trust.flush()

    # -- bulk load (bench setup) ---------------------------------------------
    def prefill(self, values: np.ndarray) -> None:
        """Directly install table contents (pre-fill before timed runs)."""
        padded = np.zeros((self.n_keys_padded, self.value_width),
                          dtype=np.dtype(self.dtype.dtype)
                          if hasattr(self.dtype, "dtype") else self.dtype)
        padded[: values.shape[0]] = values
        # owner-major layout: trustee t holds keys {k : k % T == t} at k // T
        t = self.t
        owner_major = np.concatenate(
            [padded[np.arange(i, self.n_keys_padded, t)] for i in range(t)], 0)
        state = self.trust.state()
        pad_rows = state["table"].shape[0] - self.n_keys_padded
        if pad_rows:
            # dedicated mode: client shards hold no state — zero region ahead
            # of the trustee-owned rows (the layout entrust installed)
            owner_major = np.concatenate(
                [np.zeros((pad_rows, self.value_width), owner_major.dtype),
                 owner_major], 0)
        new_table = jax.device_put(owner_major.astype(padded.dtype),
                                   state["table"].sharding)
        self.trust.set_state({**state, "table": new_table})

    def dump(self) -> np.ndarray:
        """Gather table to host in key order (tests only)."""
        t = self.t
        owner_major = np.asarray(self.trust.trustee_state()["table"])
        n_local = self.n_keys_padded // t
        out = np.zeros_like(owner_major)
        for i in range(t):
            out[np.arange(i, self.n_keys_padded, t)] = \
                owner_major[i * n_local:(i + 1) * n_local]
        return out[: self.n_keys]

    def client_region(self) -> np.ndarray:
        """Dedicated mode: the physical table rows living on client shards
        (must stay zero — state lives only on trustee shards).  Tests only."""
        full = np.asarray(self.trust.state()["table"])
        n_trustee_rows = self.trust.trustee_state()["table"].shape[0]
        return full[: full.shape[0] - n_trustee_rows]
