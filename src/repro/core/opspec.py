"""Declarative delegation schemas — the typed layer over the channel.

The paper's Trust<T> is *type*-safe as well as memory-safe: in Rust the
type system makes entrusted state unreachable except through statically
checked operations.  Our SPMD reproduction had the memory-safety half
(state only reachable via the channel) but a stringly-typed API — every
delegated object hand-built ``resp_like`` pytrees, hand-declared
``resp_fields`` elision metadata, and hand-wired routing around
``trust.apply("get", dst, {"key": k})``.  This module is the missing type
layer (DESIGN.md §10), in the spirit of Bestow/Atomic's language-level
isolation constructs and the region/lock type systems that enforce
protected-access discipline:

* ``Field(name, row_shape, dtype)`` — one payload or response column.
* ``OpSpec(name, payload=[...], response=[...], writes=[...], serve=fn)``
  — one delegated operation, declaratively: what rows it consumes, what
  struct it answers with, which response fields it actually writes (the
  elision metadata), and the serve closure.
* ``TrustSchema(name, state, ops, route=)`` — the full delegated object:
  an op table plus a state schema and a key→owner routing rule.

From a schema, ``entrust`` derives everything that used to be hand-written
— ``resp_like``, per-op ``resp_fields``, wire plane widths, and the
payload/response consistency checks (raised at SCHEMA BUILD time, before
any channel round exists) — and ``Trust`` grows **typed op handles**:

    t = group.entrust(state, schema=kv_schema)
    vals = t.op.get(keys)                  # routed: dst = schema.route
    fut  = t.op.put.then(keys, values)     # apply_then, same round fusing

Handles validate every argument against the spec at call time (wrong
dtype kind, wrong trailing shape, missing or unknown fields raise naming
the op and the field, with expected vs got — before anything is queued),
compute ``dst`` through the schema's router so callers pass keys rather
than shard ids, and then enter exactly the same submit/flush machinery as
the legacy stringly path — bit-identical programs, now reached safely.

``DelegatedOp`` (channel.py) remains the runtime vtable entry, but it is
now the COMPILED ARTIFACT of an OpSpec (``TrustSchema.delegated_ops``)
rather than the user-facing type.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import tracing

Pytree = Any


class SchemaError(ValueError):
    """A payload/response value does not match its declared Field.

    Raised at schema build time (inconsistent declarations) or at
    submit/handle-call time (bad argument) — always BEFORE any channel
    round runs, naming the op and field with expected vs got."""


def _dtype_kind(dt) -> str:
    dt = jnp.dtype(dt)
    if dt == jnp.bool_ or jnp.issubdtype(dt, jnp.integer):
        return "integer"
    if jnp.issubdtype(dt, jnp.floating):
        return "floating"
    return dt.kind


@dataclass(frozen=True)
class Field:
    """One named row column: ``row_shape`` is the per-row trailing shape
    (``()`` for scalars), ``dtype`` the wire dtype.  Values bound to the
    field are coerced with ``astype`` when the dtype KIND matches (int→int,
    float→float — the same implicit casts the legacy facades performed);
    a kind mismatch or a trailing-shape mismatch raises ``SchemaError``."""
    name: str
    row_shape: Tuple[int, ...] = ()
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "row_shape", tuple(self.row_shape))
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))

    def like(self) -> jax.Array:
        """One-row zeros template (the resp_like leaf shape)."""
        return jnp.zeros((1,) + self.row_shape, self.dtype)

    def zeros(self, n: int) -> jax.Array:
        return jnp.zeros((n,) + self.row_shape, self.dtype)

    def plane_width(self) -> int:
        """f32 wire planes one row of this field occupies under the
        ``"planes"`` wire format (channel._encode_planes): ≤16-bit ints
        and floats ride one plane per element, wider ints/bools split
        into exact hi/lo 16-bit plane pairs."""
        w = 1
        for d in self.row_shape:
            w *= int(d)
        dt = self.dtype
        if (jnp.issubdtype(dt, jnp.integer) and dt.itemsize > 2) \
                or dt == jnp.bool_:
            return 2 * w
        return w

    def bind(self, value, op: str) -> jax.Array:
        """Validate + coerce one batch of rows for this field.  The
        leading dim is the batch; everything else must match the spec."""
        x = jnp.asarray(value)
        if x.ndim != 1 + len(self.row_shape) \
                or tuple(x.shape[1:]) != self.row_shape:
            raise SchemaError(
                f"op {op!r}: payload field {self.name!r} expects row shape "
                f"{list(self.row_shape)} (a (R,{', '.join(map(str, self.row_shape))}"
                f") batch), got array of shape {list(x.shape)}")
        if x.dtype != self.dtype:
            if _dtype_kind(x.dtype) != _dtype_kind(self.dtype):
                raise SchemaError(
                    f"op {op!r}: payload field {self.name!r} expects dtype "
                    f"{self.dtype} (kind {_dtype_kind(self.dtype)}), got "
                    f"{x.dtype} (kind {_dtype_kind(x.dtype)}); cast "
                    f"explicitly if the conversion is intended")
            x = x.astype(self.dtype)
        return x


@dataclass(frozen=True)
class ListField(Field):
    """A bounded list-valued column: one row carries up to ``max_len``
    elements, padded with ``pad`` — the declaration for ops that answer
    with variable-length collections (a sequence's page chain, a top-K
    slate).  On the wire it is exactly a ``Field`` with row shape
    ``(max_len,)``; the subclass carries the padding contract so facades
    and tests can recover the logical lists without re-stating it.

        pages = ListField("pages", max_len=8, dtype=jnp.int32)
        pages.counts(resp["pages"])   # per-row logical lengths
        pages.trim(resp["pages"][i])  # one row without the padding
    """
    max_len: int = 1
    pad: int = -1

    def __post_init__(self):
        if not self.row_shape:
            object.__setattr__(self, "row_shape", (int(self.max_len),))
        super().__post_init__()
        if self.row_shape != (self.max_len,):
            raise SchemaError(
                f"list field {self.name!r}: row_shape {list(self.row_shape)} "
                f"conflicts with max_len={self.max_len}; declare max_len "
                f"only (row_shape derives as (max_len,))")

    def counts(self, rows) -> jax.Array:
        """Logical length of each row's list: elements != ``pad``.  Valid
        because serves pack lists left-aligned (pad only as a suffix)."""
        return (jnp.asarray(rows) != self.pad).sum(axis=-1)

    def trim(self, row):
        """One row's list without the padding (host-side, numpy)."""
        import numpy as np
        r = np.asarray(row)
        return r[r != self.pad]


@dataclass(frozen=True)
class Combine:
    """Client-side request-combining declaration for one op (DESIGN.md
    §13).  When the channel runs with ``combine_impl="ref"``, rows of this
    op that share a ``key`` value on one client shard collapse into ONE
    wire row before the request all_to_all:

    * ``kind="dedupe"`` — any row represents the segment (all read the
      same round-entry value); the response fans back to every requester.
    * ``kind="sum"`` — the representative ships the segment's summed
      ``field``; each request's ``resp`` response rebuilds as the combined
      response plus the segment-local exclusive prefix of the original
      deltas (exact for integer payloads within the 16-bit-plane bound).
    * ``kind="last"`` — only the segment-LAST row (the locally final
      write) ships; inter-client last-writer-wins is unchanged because
      serve order is (client, slot).

    Ops whose outcome depends on each individual request (CAS) declare no
    combine (``OpSpec(combine=None)``, the default) and pass through."""
    kind: str                 # "dedupe" | "sum" | "last"
    key: str = "key"          # payload field identifying the segment
    field: str = "value"      # "sum": payload field holding the delta
    resp: str = "value"       # "sum": response field carrying the prior

    KINDS = ("dedupe", "sum", "last")

    def __post_init__(self):
        if self.kind not in self.KINDS:
            raise SchemaError(
                f"Combine kind {self.kind!r} is not one of {self.KINDS}")


@dataclass(frozen=True, eq=False)
class OpSpec:
    """Declarative spec of one delegated operation.

    ``payload`` — the Fields a caller must supply, in handle-argument
    order; ``response`` — the full response struct this op answers with
    (every op of one schema must agree, checked at schema build);
    ``writes`` — the subset of response field NAMES the op actually
    writes (``()`` = pure write op like PUT, the zero-size-response case;
    ``None`` = undeclared, opting the round out of response elision).
    ``serve`` is the masked reference implementation
    (``(state, rows, valid, client) -> (state, resp_rows)``); ``fused``/
    ``group_key``/``kernel_lane``/``apply_grouped`` pass through to the
    compiled ``DelegatedOp`` (DESIGN.md §9).  Identity-hashed: two specs
    are the same op only if they are the same object."""
    name: str
    payload: Tuple[Field, ...] = ()
    response: Tuple[Field, ...] = ()
    writes: Optional[Tuple[str, ...]] = None
    serve: Optional[Callable] = None
    group_key: Optional[Callable] = None
    kernel_lane: Optional[str] = None
    apply_grouped: Optional[Callable] = None
    fused: Any = None
    combine: Optional[Combine] = None   # client-side request combining
    #                                     (a Combine, or the "dedupe"/
    #                                     "sum"/"last" string shorthand);
    #                                     None = never combined

    # keyword names the generated handles take for themselves — a payload
    # field with one of these names could never be passed by keyword (its
    # value would be consumed as the mask/callback), so reject at build
    RESERVED = ("where", "then", "capacity")

    def __post_init__(self):
        object.__setattr__(self, "payload", tuple(self.payload))
        object.__setattr__(self, "response", tuple(self.response))
        reserved = [f.name for f in self.payload if f.name in self.RESERVED]
        if reserved:
            raise SchemaError(
                f"op {self.name!r}: payload field name(s) {reserved} are "
                f"reserved for handle keywords {list(self.RESERVED)}; "
                f"rename the field(s)")
        if self.writes is not None:
            object.__setattr__(self, "writes", tuple(self.writes))
            resp_names = {f.name for f in self.response}
            unknown = [w for w in self.writes if w not in resp_names]
            if unknown:
                raise SchemaError(
                    f"op {self.name!r}: writes names {unknown} not among "
                    f"its response fields {sorted(resp_names)}")
        if self.combine is not None:
            c = self.combine
            if isinstance(c, str):
                c = Combine(c)
                object.__setattr__(self, "combine", c)
            pay = {f.name for f in self.payload}
            if c.key not in pay:
                raise SchemaError(
                    f"op {self.name!r}: combine key {c.key!r} is not a "
                    f"payload field (fields: {sorted(pay)})")
            if c.kind == "sum":
                if c.field not in pay:
                    raise SchemaError(
                        f"op {self.name!r}: combine sum field {c.field!r} "
                        f"is not a payload field (fields: {sorted(pay)})")
                resp_names = {f.name for f in self.response}
                if c.resp not in resp_names:
                    raise SchemaError(
                        f"op {self.name!r}: combine resp field {c.resp!r} "
                        f"is not a response field "
                        f"(fields: {sorted(resp_names)})")

    @property
    def payload_names(self) -> Tuple[str, ...]:
        return tuple(f.name for f in self.payload)

    def bind(self, args: Sequence, kwargs: Dict[str, Any]) -> Dict[str, jax.Array]:
        """Bind positional/keyword arguments to payload fields (positional
        follow declaration order), validating each — the submit-time type
        check.  Raises ``SchemaError`` before anything touches a queue."""
        fields = {f.name: f for f in self.payload}
        if len(args) > len(self.payload):
            raise SchemaError(
                f"op {self.name!r} takes {len(self.payload)} payload "
                f"argument(s) {list(fields)}, got {len(args)} positional")
        bound: Dict[str, Any] = {}
        for f, a in zip(self.payload, args):
            bound[f.name] = a
        for k, v in kwargs.items():
            if k not in fields:
                if k in self.RESERVED:
                    # a handle keyword leaked into a payload bind — most
                    # likely then= on a sync call; point at the right API
                    raise SchemaError(
                        f"op {self.name!r}: {k!r} is a handle keyword, not "
                        f"a payload field" + (
                            " — use handle.then(..., then=cb) for the "
                            "async callback" if k == "then" else ""))
                raise SchemaError(
                    f"op {self.name!r} has no payload field {k!r} "
                    f"(fields: {list(fields)})")
            if k in bound:
                raise SchemaError(
                    f"op {self.name!r}: payload field {k!r} given both "
                    f"positionally and by keyword")
            bound[k] = v
        missing = [n for n in fields if n not in bound]
        if missing:
            raise SchemaError(
                f"op {self.name!r}: missing payload field(s) {missing} "
                f"(expected {list(fields)})")
        return {n: fields[n].bind(v, self.name) for n, v in bound.items()}


def _check_consistent(kind: str, per_op: Sequence[Tuple[str, Field]]) -> Dict[str, Field]:
    """Fields sharing a name across ops must agree on row shape and dtype
    — the schema-build-time form of ``engine.check_payload_fields`` /
    ``channel.check_response_structs``."""
    seen: Dict[str, Tuple[str, Field]] = {}
    for op_name, f in per_op:
        if f.name not in seen:
            seen[f.name] = (op_name, f)
            continue
        first_op, first = seen[f.name]
        if (first.row_shape, first.dtype) != (f.row_shape, f.dtype):
            raise SchemaError(
                f"{kind} field {f.name!r} is declared as {first.dtype}"
                f"{list(first.row_shape)} by op {first_op!r} but as "
                f"{f.dtype}{list(f.row_shape)} by op {op_name!r}; ops of "
                f"one schema must agree on shared {kind} fields")
    return {n: f for n, (_op, f) in seen.items()}


class TrustSchema:
    """A delegated object's full contract: op table + state schema +
    routing rule.  Everything ``entrust`` used to be handed piecemeal
    (``ops=``, ``resp_like=``, per-op ``resp_fields``) derives from here,
    and the engine keys compiled programs on schema IDENTITY — sound
    because handle/submit validation pins every payload aval to the
    declared Fields before a batch can enter a queue.

    ``route(payload, n_trustees) -> dst`` computes the destination
    trustee for each row from the (validated) payload — callers of typed
    handles pass keys, never shard ids.  ``state`` optionally names the
    state leaves (documentation + ``validate_state``)."""

    def __init__(self, name: str, ops: Sequence[OpSpec],
                 state: Optional[Dict[str, Field]] = None,
                 route: Optional[Callable] = None,
                 reshard: Optional[Callable] = None):
        self.name = name
        self.ops = tuple(ops)
        # reshard(host_state, old_t, new_t) -> host_state re-laid-out for a
        # different trustee count; enables failover onto a shrunk mesh
        self.reshard = reshard
        if not self.ops:
            raise SchemaError(f"schema {name!r} declares no ops")
        names = [o.name for o in self.ops]
        if len(set(names)) != len(names):
            raise SchemaError(f"schema {name!r}: duplicate op names {names}")
        self.state = dict(state) if state else None
        self.route = route
        self.op_index = {o.name: i for i, o in enumerate(self.ops)}
        # build-time consistency: shared payload fields and the (single)
        # response struct are validated here, not deep inside a traced
        # round — this subsumes the runtime widening/struct guards for
        # schema'd trusts
        self.payload_fields = _check_consistent(
            "payload", [(o.name, f) for o in self.ops for f in o.payload])
        self.response_fields = _check_consistent(
            "response", [(o.name, f) for o in self.ops for f in o.response])
        responding = [o for o in self.ops if o.response]
        for o in responding:
            if {f.name for f in o.response} != set(self.response_fields):
                raise SchemaError(
                    f"schema {name!r}: op {o.name!r} responds with "
                    f"{sorted(f.name for f in o.response)} but the schema's "
                    f"response struct is {sorted(self.response_fields)}; "
                    f"every responding op must produce the same struct "
                    f"(declare the full struct and use writes= for the "
                    f"subset actually written)")
        self._delegated = None

    def fingerprint(self) -> str:
        """Stable identity for checkpoint manifests: hashes the contract a
        restore must match (op names + payload/response field layouts +
        state schema), NOT python object identity — two sessions that build
        the same schema from the same factory fingerprint identically."""
        import hashlib
        parts = [self.name]
        for o in self.ops:
            parts.append(f"op:{o.name}")
            for kind, fields in (("p", o.payload), ("r", o.response)):
                for f in fields:
                    parts.append(
                        f"{kind}:{f.name}:{f.dtype}:{f.row_shape}")
            parts.append(f"w:{sorted(o.writes or ())}")
        if self.state is not None:
            for n in sorted(self.state):
                f = self.state[n]
                parts.append(f"s:{n}:{f.dtype}:{f.row_shape}")
        return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]

    # -- derivations ---------------------------------------------------------
    def resp_like(self) -> Dict[str, jax.Array]:
        """The hand-written ``resp_like`` pytree, derived: one one-row
        zeros leaf per response field, in declaration order."""
        return {f.name: f.like() for f in self._response_order()}

    def _response_order(self) -> Tuple[Field, ...]:
        for o in self.ops:
            if o.response:
                return o.response
        return ()

    def delegated_ops(self):
        """Compile the specs into the runtime op table.  ``DelegatedOp``
        is the compiled artifact: serve closure + grouping hooks +
        ``resp_fields`` (from ``writes``) — cached, one table per schema."""
        if self._delegated is None:
            from .channel import DelegatedOp
            self._delegated = tuple(
                DelegatedOp(o.name, o.serve, group_key=o.group_key,
                            kernel_lane=o.kernel_lane,
                            resp_fields=o.writes,
                            apply_grouped=o.apply_grouped, fused=o.fused,
                            spec=o, combine=o.combine)
                for o in self.ops)
        return self._delegated

    def payload_plane_width(self, op: Optional[str] = None) -> int:
        """Wire planes one request row occupies under ``wire_fmt="planes"``
        (excluding the engine's op/trust id lanes and the validity
        column).  With ``op`` given, only that op's fields count; without,
        the union a fused all-op round ships."""
        fields = (self.ops[self.op_index[op]].payload if op
                  else self.payload_fields.values())
        return sum(f.plane_width() for f in fields)

    def response_plane_width(self) -> int:
        return sum(f.plane_width() for f in self.response_fields.values())

    def validate_state(self, state: Pytree) -> None:
        """Check an entrusted state pytree against the state schema
        (leaf names, trailing shapes, dtypes).  Leading dims are the
        owner-shard dim and stay unconstrained."""
        if self.state is None:
            return
        if not isinstance(state, dict) or set(state) != set(self.state):
            got = sorted(state) if isinstance(state, dict) else type(state)
            raise SchemaError(
                f"schema {self.name!r} state expects leaves "
                f"{sorted(self.state)}, got {got}")
        for n, f in self.state.items():
            leaf = jnp.asarray(state[n])
            if tuple(leaf.shape[1:]) != f.row_shape or leaf.dtype != f.dtype:
                raise SchemaError(
                    f"schema {self.name!r} state leaf {n!r} expects "
                    f"{f.dtype}[R, {', '.join(map(str, f.row_shape))}], got "
                    f"{leaf.dtype}{list(leaf.shape)}")

    # -- submit-time validation (the typed path AND the legacy shims) -------
    def bind_payload(self, op: str, payload: Dict[str, Any]) -> Dict[str, jax.Array]:
        """Validate a payload DICT for ``op`` (the ``apply``/``submit``
        shim path): same checks as handle-call binding.  An unknown op
        name raises ``KeyError``, matching the schema-less shim (and the
        pre-schema ``op_index[op]`` behavior); only payload problems are
        ``SchemaError``s."""
        if op not in self.op_index:
            raise KeyError(
                f"schema {self.name!r} has no op {op!r} "
                f"(ops: {[o.name for o in self.ops]})")
        return self.ops[self.op_index[op]].bind((), dict(payload))

    def dst_for(self, payload: Dict[str, jax.Array], n_trustees: int,
                where=None) -> jax.Array:
        """Destination trustee per row via the schema router; ``where``
        (bool mask) deactivates rows (dst = -1) without touching keys."""
        if self.route is None:
            raise SchemaError(
                f"schema {self.name!r} declares no route= rule; pass dst "
                f"explicitly via Trust.apply/submit")
        dst = self.route(payload, n_trustees).astype(jnp.int32)
        if where is not None:
            dst = jnp.where(jnp.asarray(where, bool), dst, -1)
        return dst

    def __repr__(self):
        return (f"TrustSchema({self.name!r}, ops={[o.name for o in self.ops]}, "
                f"route={'yes' if self.route else 'no'})")


# ---------------------------------------------------------------------------
# Typed op handles (attached to Trust as ``t.op``)
# ---------------------------------------------------------------------------

class OpHandle:
    """Callable handle for one op of a schema'd Trust.

    ``handle(*rows, where=mask)`` — synchronous apply: validates the
    arguments against the OpSpec, routes them (``dst`` comes from the
    schema, masked by ``where``), and runs the solo round.  Returns the
    response dict.  ``handle.then(*rows, where=, then=)`` — apply_then:
    same validation and routing, but the batch queues for the next
    ``flush()`` / ``session.step()`` and a ``TrustFuture`` comes back."""

    __slots__ = ("_trust", "_spec", "_op_id")

    def __init__(self, trust, spec: OpSpec, op_id: int):
        self._trust = trust
        self._spec = spec
        self._op_id = op_id

    @property
    def spec(self) -> OpSpec:
        return self._spec

    def _bind(self, args, kwargs, where, wave: int):
        with tracing.span(tracing.BIND, wave):
            payload = self._spec.bind(args, kwargs)
        with tracing.span(tracing.ROUTE, wave):
            dst = self._trust.schema.dst_for(payload, self._trust.n_trustees,
                                             where)
        return dst, payload

    def __call__(self, *args, where=None, capacity=None, **kwargs) -> Pytree:
        wave = self._trust.session.span_wave()
        with tracing.span(tracing.SUBMIT, wave):
            dst, payload = self._bind(args, kwargs, where, wave)
        return self._trust._apply_validated(self._op_id, dst, payload,
                                            capacity)

    def then(self, *args, where=None, then=None, **kwargs):
        wave = self._trust.session.span_wave()
        with tracing.span(tracing.SUBMIT, wave):
            dst, payload = self._bind(args, kwargs, where, wave)
            return self._trust._submit_validated(self._op_id, dst, payload,
                                                 then)

    def __repr__(self):
        return (f"<op {self._trust.name}.{self._spec.name}"
                f"({', '.join(self._spec.payload_names)})>")


class OpNamespace:
    """``trust.op`` — one generated ``OpHandle`` attribute per OpSpec
    (``trust.op.get``, ``trust.op.put``, …; ``trust.op["get"]`` for
    non-identifier names)."""

    def __init__(self, trust, schema: TrustSchema):
        self._handles = {
            spec.name: OpHandle(trust, spec, i)
            for i, spec in enumerate(schema.ops)}
        for name, h in self._handles.items():
            if name.isidentifier() and not hasattr(type(self), name):
                setattr(self, name, h)

    def __getitem__(self, name: str) -> OpHandle:
        return self._handles[name]

    def __getattr__(self, name: str) -> OpHandle:
        try:
            return self.__dict__["_handles"][name]
        except KeyError:
            raise AttributeError(
                f"no op {name!r} (ops: {sorted(self.__dict__['_handles'])})"
            ) from None

    def __iter__(self):
        return iter(self._handles.values())

    def __repr__(self):
        return f"<ops {sorted(self._handles)}>"
