"""Trust — the user-facing handle to entrusted state (paper §3, §4).

``entrust`` places a pytree of state under the care of trustees laid out along
one or more mesh axes.  The state is then *only* reachable through the
delegation channel.  The TYPED path (DESIGN.md §10) entrusts against a
declarative ``TrustSchema`` (opspec.py) and uses the generated op handles —
callers pass keys and row batches; routing, validation, response structure
and elision metadata all derive from the schema:

    group = TrusteeGroup(mesh, axis=("data", "model"))     # every chip serves
    ded   = TrusteeGroup(mesh, axis=("data", "model"),     # reserved trustee
                         mode="dedicated", n_dedicated=2)  # cores serve rest
    trust = group.entrust(table, schema=kv_schema)
    vals  = trust.op.get(keys)                             # sync apply()
    fut   = trust.op.put.then(keys, values)                # apply_then()
    trust.flush()                                          # one fused program

The stringly path is kept as a thin shim over the same machinery —
``trust.apply("get", dst, {"key": k})`` / ``trust.submit(...)`` — validated
through the schema when one exists, and required for schema-less trusts
built from raw ``DelegatedOp`` tables.  Both paths produce bit-identical
programs (they share the engine's compiled-program cache entry).

Differences from the Rust original (DESIGN.md §2): closures are entries in a
static op table; requests are rows of serializable values (the paper imposes
the same value-only restriction via serde); synchronization is the SPMD
program itself.  Batching of many requests per message (paper §5.3) falls out
of ``submit``/``flush`` fusing all queued requests into one channel round.

Execution lives in the session's ``DelegationEngine`` (engine.py, DESIGN.md
§8): a Trust is a thin handle that enqueues batches; ``apply``/``flush``
take the solo fast path (one per-trust program, bit-identical to the
pre-engine runtime), while ``session.step()`` fuses the pending batches of
EVERY registered Trust into one multiplexed channel round.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .channel import ChannelConfig, DelegatedOp
from .opspec import OpNamespace, TrustSchema
from . import tracing

Pytree = Any


def _axes_tuple(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


@dataclass
class TrusteeGroup:
    """A set of trustees: the devices along ``axis`` of ``mesh``.

    Two runtime modes, matching the paper's evaluation:

    * ``mode="shared"`` (default): every device along ``axis`` is both client
      and trustee.  With ``axis`` covering every mesh axis, every chip serves;
      with a subset (e.g. just ``"model"``), state is replicated over the
      remaining axes and must only be mutated in ways that keep replicas
      coherent (read-only serve, or disjoint per-replica state such as
      batch-sharded KV pages).
    * ``mode="dedicated"``: the LAST ``n_dedicated`` device slots along the
      flattened ``axis`` are reserved trustee cores serving the remaining
      ``n_clients`` client cores.  Entrusted state lives only on trustee
      shards; requests originate only on client shards.  ``axis`` must cover
      the whole mesh (the reserved-core split is a partition of all chips).
    """
    mesh: Mesh
    axis: Any = "model"
    mode: str = "shared"
    n_dedicated: int = 0

    def __post_init__(self):
        if self.mode not in ("shared", "dedicated"):
            raise ValueError(f"unknown trustee mode {self.mode!r}")
        if self.mode == "dedicated":
            if self.axes != tuple(self.mesh.axis_names):
                raise ValueError(
                    "dedicated mode partitions the whole mesh: axis must be "
                    f"{tuple(self.mesh.axis_names)}, got {self.axes}")
            if not (0 < self.n_dedicated < self.axis_size):
                raise ValueError(
                    f"n_dedicated must be in (0, {self.axis_size}), "
                    f"got {self.n_dedicated}")

    @property
    def axes(self) -> Tuple[str, ...]:
        return _axes_tuple(self.axis)

    @property
    def axis_size(self) -> int:
        n = 1
        for a in self.axes:
            n *= int(self.mesh.shape[a])
        return n

    @property
    def n_trustees(self) -> int:
        if self.mode == "dedicated":
            return self.n_dedicated
        return self.axis_size

    @property
    def n_clients(self) -> int:
        """Devices that originate requests (== axis_size in shared mode)."""
        if self.mode == "dedicated":
            return self.axis_size - self.n_dedicated
        return self.axis_size

    def entrust(self, state: Pytree, ops: Optional[Sequence[DelegatedOp]] = None,
                resp_like: Optional[Pytree] = None,
                state_specs: Optional[Pytree] = None,
                capacity: Optional[int] = None, overflow: str = "second_round",
                overflow_capacity: int = 0, local_shortcut: bool = True,
                max_rounds: int = 1, pack_impl: str = "ref",
                serve_impl: str = "ref",
                name: Optional[str] = None, plan_capacity: bool = False,
                session=None, schema: Optional[TrustSchema] = None,
                strict_impl: bool = False,
                serve_blocks: Any = (256, 512),
                pack_blocks: Any = (256, 512),
                combine: str = "off",
                schema_factory: Optional[Callable[[int], TrustSchema]] = None
                ) -> "Trust":
        """Move ``state`` under trustee ownership and return the Trust handle.

        The TYPED form passes ``schema=`` (a ``TrustSchema``, DESIGN.md
        §10): the op table, ``resp_like``, per-op elision metadata and the
        routing rule all derive from it, the state pytree is validated
        against the state schema, and the returned Trust carries generated
        op handles (``trust.op.get(keys)``).  The legacy form passes
        ``ops=`` (raw ``DelegatedOp``s) plus a hand-built ``resp_like``;
        it remains fully supported but skips submit-time validation.

        state leaves must have a leading dim divisible by n_trustees (the
        owner shard dim) unless ``state_specs`` overrides the layout.  In
        dedicated mode the default layout pads each leaf with a zero client
        region so the physical array shards over the whole axis while the
        logical state occupies only the trustee shards; ``Trust.trustee_state``
        strips the padding back off.

        ``capacity``: rows per (client, trustee) pair in the primary block.
        ``None`` (or 0, the legacy spelling) auto-sizes per batch; any
        explicit positive value — including 1 — is honored as-is.
        ``max_rounds`` bounds the defer drain engine (``overflow="defer"``
        with ``max_rounds > 1`` re-transmits deferred rows until the batch
        drains).  ``pack_impl`` selects the channel pack implementation
        ("ref" lax sort | "pallas" MXU kernel); ``serve_impl`` the trustee
        serve path ("ref" shared-grouping segment primitives | "pallas"
        fused MXU serve kernel | "masked" legacy per-op passes,
        DESIGN.md §9).

        ``name`` labels the trust in the session engine's per-trust stats;
        ``plan_capacity`` lets the engine's EMA planner auto-size the solo
        primary block from observed demand (auto capacity only);
        ``session`` pins a specific ``TrustSession`` (default: the ambient
        one from ``meshctx.current_session()``) — entrusting REGISTERS the
        Trust with that session, so ``session.step()`` can fuse its pending
        batches with every other registered Trust's into one multiplexed
        channel round.

        ``serve_blocks``/``pack_blocks`` are the (row, key|slot) tile sizes
        of the tiled Pallas kernels (multiples of 128; clamped for small
        inputs — DESIGN.md §12), or the string ``"auto"`` to pick them from
        the roofline model (``rooflines.select_serve_blocks`` /
        ``select_pack_blocks``) for this trust's state shape.
        ``strict_impl=True`` turns the serve kernel's silent lax fallback
        (non-f32 tables) into a TypeError.  ``combine`` ("off" | "ref")
        engages the client-side request-combining pass for ops that declare
        a combine archetype (DESIGN.md §13).  All of these are part of the
        fuse signature: trusts configured differently never share a
        compiled round program.
        """
        if combine not in ("off", "ref"):
            raise ValueError(
                f"combine must be 'off' or 'ref', got {combine!r}")
        if schema is None and schema_factory is not None:
            # failover-aware trusts entrust via a factory (n_trustees ->
            # TrustSchema) so session.re_entrust can rebuild the op table
            # for a different trustee count (serve closures bake T in)
            schema = schema_factory(self.n_trustees)
        if schema is not None:
            if ops is not None or resp_like is not None:
                raise ValueError(
                    "entrust takes EITHER schema= (typed, derives ops and "
                    "resp_like) OR ops=/resp_like= (legacy), not both")
            schema.validate_state(state)
            ops = schema.delegated_ops()
            resp_like = schema.resp_like()
        elif ops is None or resp_like is None:
            raise ValueError(
                "entrust needs a schema= (typed path) or both ops= and "
                "resp_like= (legacy path)")
        if serve_blocks == "auto" or pack_blocks == "auto":
            # Autotuned block sizes (DESIGN.md §12): size the kernel tiles
            # from the roofline model for this trust's state shape and a
            # nominal wire-row count (n_clients x capacity when capacity is
            # pinned; 4096 rows under auto capacity).
            from ..launch.rooflines import (select_pack_blocks,
                                            select_serve_blocks)
            leaf = jnp.asarray(jax.tree.leaves(state)[0])
            n_local = max(1, int(leaf.shape[0]) // self.n_trustees)
            width = 1
            for d in leaf.shape[1:]:
                width *= int(d)
            nominal = self.n_clients * capacity if capacity else 4096
            if serve_blocks == "auto":
                serve_blocks = select_serve_blocks(
                    nominal, n_local, max(1, width),
                    dtype_bytes=jnp.dtype(leaf.dtype).itemsize)
            if pack_blocks == "auto":
                pack_blocks = select_pack_blocks(
                    nominal, nominal, max(1, width),
                    dtype_bytes=jnp.dtype(leaf.dtype).itemsize)
        if state_specs is None:
            state_specs = jax.tree.map(lambda _: P(self.axes), state)
        if self.mode == "dedicated":
            def pad_client_region(x):
                x = jnp.asarray(x)
                assert x.shape[0] % self.n_trustees == 0, \
                    f"leading dim {x.shape[0]} not divisible by " \
                    f"{self.n_trustees} trustees"
                rows_per = x.shape[0] // self.n_trustees
                z = jnp.zeros((self.n_clients * rows_per,) + x.shape[1:],
                              x.dtype)
                return jnp.concatenate([z, x], 0)
            state = jax.tree.map(pad_client_region, state)
            local_shortcut = False   # a client is never its own trustee
        sharded = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, s)),
            state, state_specs)
        # capacity sentinel: None/0 -> 0 (auto-sized per batch in _cfg_for);
        # an explicit capacity — including 1 — is stored verbatim
        cfg = ChannelConfig(axis=self.axis if len(self.axes) > 1 else self.axes[0],
                            capacity=0 if not capacity else capacity,
                            overflow=overflow,
                            overflow_capacity=overflow_capacity,
                            local_shortcut=local_shortcut,
                            pack_impl=pack_impl,
                            serve_impl=serve_impl,
                            mode=self.mode,
                            n_clients=self.n_clients if self.mode == "dedicated"
                            else 0,
                            max_rounds=max_rounds,
                            serve_block_rows=serve_blocks[0],
                            serve_block_keys=serve_blocks[1],
                            pack_block_rows=pack_blocks[0],
                            pack_block_slots=pack_blocks[1],
                            strict_impl=strict_impl,
                            combine_impl=combine)
        return Trust(self, sharded, tuple(ops), resp_like, state_specs, cfg,
                     name=name, plan_capacity=plan_capacity, session=session,
                     schema=schema, schema_factory=schema_factory)


@dataclass
class TrustFuture:
    """Host-level future for ``submit`` (apply_then analog).

    ``trust``/``op`` name the submission so an early ``result()`` read
    raises a message that says WHICH queued batch is unserved (matching
    the ``last_drain_stats`` RuntimeError contract)."""
    _result: Optional[Pytree] = None
    _then: Optional[Callable[[Pytree], None]] = None
    trust: str = ""
    op: str = ""

    def ready(self) -> bool:
        return self._result is not None

    def result(self) -> Pytree:
        if self._result is None:
            raise RuntimeError(
                f"result of op {self.op!r} on trust {self.trust!r} is not "
                f"ready: the submitted batch has not been served — flush() "
                f"the trust (or run session.step()) first")
        return self._result

    def _fulfil(self, value: Pytree) -> None:
        self._result = value
        if self._then is not None:
            self._then(value)


class Trust:
    """Reference to entrusted state.  Clone freely (it is just a handle).

    A schema'd Trust exposes the TYPED surface as ``trust.op`` — one
    generated handle per OpSpec (``trust.op.get(keys)`` /
    ``trust.op.get.then(keys)``), each validating its arguments and
    routing through the schema before anything queues.  ``apply`` and
    ``submit`` remain as stringly shims over the same machinery.

    Execution is owned by the session ``DelegationEngine`` the Trust
    registers with at construction: ``apply``/``flush`` run the solo fast
    path through it, ``submit`` enqueues for either ``flush`` (solo) or
    ``session.step()`` (one multiplexed round over all registered Trusts)."""

    def __init__(self, group: TrusteeGroup, state: Pytree,
                 ops: Tuple[DelegatedOp, ...], resp_like: Pytree,
                 state_specs: Pytree, cfg: ChannelConfig,
                 name: Optional[str] = None, plan_capacity: bool = False,
                 session=None, schema: Optional[TrustSchema] = None,
                 schema_factory: Optional[Callable] = None):
        self.group = group
        self._state = state
        self.ops = ops
        self.op_index = {o.name: i for i, o in enumerate(ops)}
        self.resp_like = resp_like
        self.state_specs = state_specs
        self.cfg = cfg
        self.schema = schema
        self.schema_factory = schema_factory
        # failover hooks: session.re_entrust fires these after rebinding the
        # trust onto a new trustee group (facades refresh cached layout here)
        self._on_rebuild: List[Callable] = []
        self.op = OpNamespace(self, schema) if schema is not None else None
        self.plan_capacity = plan_capacity
        self._pending: List[Tuple[int, jax.Array, Pytree, TrustFuture]] = []
        self._last_stats = None
        if session is None:
            from . import meshctx
            session = meshctx.current_session()
        self.session = session
        self.token = session.register(self)
        self.name = name if name else f"trust{self.token}"

    # -- introspection ------------------------------------------------------
    @property
    def n_trustees(self) -> int:
        return self.group.n_trustees

    def state(self) -> Pytree:
        """Debug/checkpoint access to the raw sharded state."""
        return self._state

    def set_state(self, state: Pytree) -> None:
        self._state = state

    def trustee_state(self) -> Pytree:
        """Logical state: strips the zero client region in dedicated mode."""
        if self.group.mode != "dedicated":
            return self._state
        t, c = self.group.n_trustees, self.group.n_clients

        def strip(x):
            rows_per = x.shape[0] // (t + c)
            return x[c * rows_per:]
        return jax.tree.map(strip, self._state)

    # -- resilience (DESIGN.md §14) ------------------------------------------
    def install_trustee_state(self, logical_state: Pytree) -> None:
        """Install a LOGICAL (host or device) state pytree as the entrusted
        state: re-pad the zero client region in dedicated mode and
        device_put every leaf against the CURRENT group mesh's shardings —
        the elastic half of checkpoint restore (the snapshot stores logical
        owner-major state, the mesh it lands on may differ)."""
        g = self.group

        def pad(x):
            x = jnp.asarray(x)
            assert x.shape[0] % g.n_trustees == 0, \
                f"leading dim {x.shape[0]} not divisible by " \
                f"{g.n_trustees} trustees"
            rows_per = x.shape[0] // g.n_trustees
            z = jnp.zeros((g.n_clients * rows_per,) + x.shape[1:], x.dtype)
            return jnp.concatenate([z, x], 0)

        if g.mode == "dedicated":
            logical_state = jax.tree.map(pad, logical_state)
        self._state = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x),
                                        NamedSharding(g.mesh, s)),
            logical_state, self.state_specs)

    def rebind(self, group: TrusteeGroup,
               schema: Optional[TrustSchema] = None,
               logical_state: Optional[Pytree] = None) -> None:
        """Re-home this trust onto a new trustee group (failover path,
        called by ``session.re_entrust``): swap group and (optionally)
        schema, recompute the derived op table / handles / config fields,
        reset the cached fuse signature so the engine recompiles, install
        the given logical state, and fire the ``_on_rebuild`` hooks."""
        self.group = group
        if schema is not None:
            self.schema = schema
            self.ops = tuple(schema.delegated_ops())
            self.op_index = {o.name: i for i, o in enumerate(self.ops)}
            self.resp_like = schema.resp_like()
            self.op = OpNamespace(self, schema)
        self.cfg = dataclasses.replace(
            self.cfg,
            axis=group.axis if len(group.axes) > 1 else group.axes[0],
            mode=group.mode,
            n_clients=group.n_clients if group.mode == "dedicated" else 0,
            local_shortcut=(False if group.mode == "dedicated"
                            else self.cfg.local_shortcut))
        # state_specs are PartitionSpecs (mesh-independent) — keep them
        self._mux_sig = None
        self._last_stats = None
        if logical_state is not None:
            self.install_trustee_state(logical_state)
        for cb in self._on_rebuild:
            cb(self)

    # -- core API ------------------------------------------------------------
    # The typed handles (``trust.op.<name>``) and the stringly shims below
    # both funnel into ``_apply_validated``/``_submit_validated``; for a
    # schema'd trust every entry point validates against the OpSpec FIRST,
    # so a bad batch raises before anything is queued (queued batches stay
    # untouched and no channel round runs).

    def _apply_validated(self, op_id: int, dst: jax.Array, payload: Pytree,
                         capacity: Optional[int] = None) -> Pytree:
        self.flush()
        resp = self.session.run_solo(self, [(op_id, dst, payload)], capacity)
        return resp[0]

    def _submit_validated(self, op_id: int, dst: jax.Array, payload: Pytree,
                          then: Optional[Callable] = None) -> TrustFuture:
        fut = TrustFuture(_then=then, trust=self.name,
                          op=self.ops[op_id].name)
        self._pending.append((op_id, dst, payload, fut))
        self.session.notify(self)
        return fut

    def _shim(self, op: str, payload: Pytree,
              wave: int) -> Tuple[int, Pytree]:
        """The stringly entry points' validation step: an unknown op name
        raises ``KeyError`` on both the schema'd and schema-less paths
        (the pre-schema behavior); schema'd trusts additionally validate
        and coerce the payload dict against the OpSpec (``SchemaError``)."""
        if self.schema is not None:
            with tracing.span(tracing.BIND, wave):
                payload = self.schema.bind_payload(op, payload)
        elif op not in self.op_index:
            raise KeyError(
                f"trust {self.name!r} has no op {op!r} "
                f"(ops: {[o.name for o in self.ops]})")
        return self.op_index[op], payload

    def apply(self, op: str, dst: jax.Array, payload: Pytree,
              capacity: Optional[int] = None) -> Pytree:
        """Synchronous delegation (paper apply()): blocks for the response.
        Stringly shim over the typed path — prefer ``trust.op.<name>(...)``
        on schema'd trusts (same program, routed and validated)."""
        wave = self.session.span_wave()
        with tracing.span(tracing.SUBMIT, wave):
            op_id, payload = self._shim(op, payload, wave)
        return self._apply_validated(op_id, dst, payload, capacity)

    def submit(self, op: str, dst: jax.Array, payload: Pytree,
               then: Optional[Callable] = None) -> TrustFuture:
        """apply_then(): queue the request batch; executed at flush() or at
        the next ``session.step()``.  All queued batches ride ONE channel
        round (request batching, §5.3) — across every registered Trust when
        the round runs through the session engine.  Stringly shim — prefer
        ``trust.op.<name>.then(...)`` on schema'd trusts."""
        wave = self.session.span_wave()
        with tracing.span(tracing.SUBMIT, wave):
            op_id, payload = self._shim(op, payload, wave)
            return self._submit_validated(op_id, dst, payload, then)

    def flush(self, capacity: Optional[int] = None) -> None:
        """Run this trust's queued batches as ONE solo channel round."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        self.session.unnotify(self)
        try:
            resps = self.session.run_solo(
                self, [(o, d, p) for (o, d, p, _) in pending], capacity)
        except Exception:
            # a build error (e.g. the payload-widening mismatch) must not
            # discard the queued batches: restore them so the caller can
            # drop the offending submit and flush again
            self._pending = pending + self._pending
            self.session.notify(self)
            raise
        for (_, _, _, fut), resp in zip(pending, resps):
            fut._fulfil(resp)

    # -- execution -----------------------------------------------------------
    def _auto_capacity(self, r_total: int) -> int:
        # mean load per (client, trustee) pair with 2x headroom, min 4 rows —
        # the "primary block sized for the common case" rule (§5.3.1).
        # Dedicated mode concentrates all requests on the client shards, so
        # the per-client share divides by n_clients, not the whole mesh.
        n_origins = (self.group.n_clients if self.group.mode == "dedicated"
                     else max(1, self.group.mesh.size))
        per_client = max(1, r_total // n_origins)
        mean = max(1, per_client // self.n_trustees)
        return max(4, 2 * mean)

    def _cfg_for(self, r_total: int, capacity: Optional[int]) -> ChannelConfig:
        # ``None`` means "use the entrusted config" (whose 0 means auto);
        # any explicit positive capacity — including 1 — wins verbatim
        if capacity is None:
            capacity = self.cfg.capacity
        cap = capacity if capacity > 0 else self._auto_capacity(r_total)
        over = cap if self.cfg.overflow == "second_round" else 0
        return dataclasses.replace(
            self.cfg, capacity=cap,
            overflow_capacity=self.cfg.overflow_capacity or over)

    def fuse_signature(self) -> Tuple:
        """Channel-compatibility signature for the engine's fuse step:
        trustee-group identity plus ``ChannelConfig.fuse_sig()``.  Trusts
        with equal signatures may share one multiplexed round (DESIGN.md
        §8); the engine caches the tuple on the Trust."""
        g = self.group
        return (g.mesh, g.axes, g.mode, g.n_dedicated) + self.cfg.fuse_sig()

    def batch_signature(self, op_ids, sizes, payloads) -> Tuple:
        """Compiled-program cache-key component for a set of queued
        batches.  A schema'd trust keys on SCHEMA IDENTITY — submit-time
        validation pins every payload aval to the declared Fields, so
        (schema, op ids, sizes) determines the program and the per-leaf
        aval hashing the stringly path pays is skipped.  Schema-less
        trusts keep the aval tuple."""
        if self.schema is not None:
            # the schema object itself (identity-hashed) — it outlives the
            # cache entry because the trust holds it and dead trusts prune
            # their entries
            return (self.schema, tuple(op_ids), tuple(sizes))
        from .engine import _payload_sig
        return (tuple(op_ids), tuple(sizes),
                tuple(_payload_sig(p) for p in payloads))

    def last_drain_stats(self) -> Dict[str, int]:
        """Telemetry from the most recent channel execution: rounds used and
        the global residual row count (rows still unserved — nonzero only
        when ``overflow="defer"`` ran out of ``max_rounds``).  Per-trust
        stats for multiplexed rounds — including demand telemetry — come
        from ``session.last_stats()``."""
        if getattr(self, "_last_stats", None) is None:
            raise RuntimeError(
                f"no delegation round has executed yet for trust "
                f"{self.name!r}: apply/flush it (or run session.step()) "
                f"before reading drain stats")
        # engine._as_int also resolves the lazy (array, index) entries a
        # multiplexed round stores (per-trust slices stay on device)
        from .engine import _as_int
        rounds, residual = self._last_stats
        return {"rounds": _as_int(rounds), "residual": _as_int(residual)}


# ---------------------------------------------------------------------------
# Convenience: entrust with the current mesh context
# ---------------------------------------------------------------------------

def local_trustees(axis=None, mode: Optional[str] = None,
                   n_dedicated: Optional[int] = None) -> TrusteeGroup:
    """TrusteeGroup over the ambient mesh.

    With no arguments, ``mode``/``n_dedicated`` default to the session-wide
    delegation mode (meshctx.set_delegation_mode, set by launch drivers from
    their --delegation-mode flag).  An EXPLICIT ``axis`` requests the shared
    sub-axis pattern (state replicated over the remaining axes) and is
    incompatible with dedicated mode, which always partitions the whole
    mesh — asking for both raises instead of silently ignoring the axis."""
    from . import meshctx
    mesh = meshctx.current_mesh()
    d_mode, d_n = meshctx.delegation_mode()
    if mode is None:
        # the session default applies only to whole-mesh groups; an explicit
        # sub-axis group keeps shared semantics
        mode = d_mode if axis is None else "shared"
    n_dedicated = d_n if n_dedicated is None else n_dedicated
    if mode == "dedicated":
        if axis is not None and _axes_tuple(axis) != tuple(mesh.axis_names):
            raise ValueError(
                f"dedicated mode partitions the whole mesh "
                f"{tuple(mesh.axis_names)}; it cannot honor axis={axis!r}")
        return TrusteeGroup(mesh, tuple(mesh.axis_names), mode="dedicated",
                            n_dedicated=n_dedicated)
    return TrusteeGroup(mesh, "model" if axis is None else axis)
