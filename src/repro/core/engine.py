"""DelegationEngine — one multiplexed channel round for ALL Trusts.

The paper's throughput comes from batching many requests per message (§5.3)
and sizing the primary slot block for the mean load (§5.3.1).  Before this
module, the runtime executed one SPMD program — and one ``all_to_all`` pair —
*per Trust per step*: a serve step touching the KV table, the token ledger,
and a lock store paid three channel rounds where the hardware could do one.
"Bestow and Atomic" (Castegren et al.) makes the same observation for
delegation generally: grouping delegated objects behind a shared message
lane is what lets delegation scale past a single object.

The engine (exposed as the ambient ``TrustSession`` via
``meshctx.current_session()``) owns execution for every registered Trust:

  * ``step()`` collects the pending ``submit`` batches of ALL dirty Trusts,
    tags each row with a trust-id lane next to the op-id lane, and runs them
    through a single fused ``shard_map`` program — one pack, one request
    ``all_to_all`` (the "planes" wire format fuses payload leaves + validity
    into one matrix), one trustee serve pass over a merged op table
    dispatching per (trust, op) with each trust's state threaded separately,
    and one response transpose.  Each Trust gets its new state and per-batch
    responses back in request order.
  * the compiled-program cache lives here, keyed on the multiplexed batch
    signature (trust tokens x ``Trust.batch_signature`` x capacity, where
    the batch signature is SCHEMA IDENTITY + op ids + sizes for schema'd
    trusts — submit-time validation pins the payload avals — and the
    per-leaf aval tuple otherwise) — it replaces the per-Trust
    ``_exec_cache``.
  * a ``CapacityPlanner`` turns the per-trustee demand telemetry the channel
    always computed (``group_sizes`` from ``_group_positions``, previously
    discarded) into an EMA that auto-sizes ``capacity``/``overflow_capacity``
    for the NEXT round, replacing the static 2x-mean heuristic for
    engine-planned rounds; drain/defer stats are reported per trust via
    ``last_stats()`` as ``{trust_name: {rounds, residual, demand_max}}``.

Solo rounds (``Trust.apply`` / ``Trust.flush``) keep the pre-engine fast
path bit-for-bit: the same per-trust program (tree wire format, no trust
lane), just built and cached here.  See DESIGN.md §8.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from . import channel as ch
from . import tracing

Pytree = Any


# ---------------------------------------------------------------------------
# Fused-batch payload widening (and its mismatch guard)
# ---------------------------------------------------------------------------

def check_payload_fields(named_batches) -> Dict[str, Tuple[str, Tuple]]:
    """Validate the zero-fill widening of a fused batch.

    ``named_batches`` is a sequence of ``(label, payload_dict)``.  When two
    queued ops share a payload field name, the fuse step zero-fills the op
    that lacks it using the first op's leaf as the ``like`` template — which
    silently corrupts the round if the two ops disagree on the field's dtype
    or trailing shape.  Detect that and raise a clear error naming the field
    and both ops.  Returns ``{field: (first_label, (dtype, trailing_shape))}``
    so callers can reuse the (now verified) like templates."""
    seen: Dict[str, Tuple[str, Tuple]] = {}
    for label, payload in named_batches:
        for name in sorted(payload.keys()):
            leaf = jnp.asarray(payload[name])
            sig = (leaf.dtype, tuple(leaf.shape[1:]))
            if name not in seen:
                seen[name] = (label, sig)
            elif seen[name][1] != sig:
                l0, s0 = seen[name]
                raise ValueError(
                    f"fused-batch payload field {name!r} is declared as "
                    f"{s0[0]}{list(s0[1])} by op {l0!r} but as "
                    f"{sig[0]}{list(sig[1])} by op {label!r}; ops fused into "
                    f"one channel round must agree on the dtype and trailing "
                    f"shape of shared payload fields (rename one of the "
                    f"fields or flush between the two submissions)")
    return seen


def _payload_sig(payload: Pytree):
    leaves, treedef = jax.tree.flatten(payload)
    return (treedef, tuple((tuple(jnp.asarray(l).shape),
                            str(jnp.asarray(l).dtype)) for l in leaves))


def _elidable_fields(ops, active_ids, resp_like) -> Tuple[str, ...]:
    """Response fields statically untouched by EVERY active op this round
    (``DelegatedOp.resp_fields``) — dropped from the response transpose.
    An op without a declaration opts the whole round out."""
    if not isinstance(resp_like, dict):
        return ()
    written = set()
    for i in active_ids:
        rf = ops[i].resp_fields
        if rf is None:
            return ()
        written |= set(rf)
    return tuple(sorted(set(resp_like.keys()) - written))


# ---------------------------------------------------------------------------
# Capacity planner (paper §5.3.1, adaptive)
# ---------------------------------------------------------------------------

class CapacityPlanner:
    """EMA-based primary-block sizing.

    The paper sizes the request slot for the mean load (§5.3.1); the seed
    runtime hard-coded that as "2x the mean of THIS batch".  The planner
    instead observes the realized max per-(client, trustee) pair demand of
    each executed round — telemetry the pack phase always computed and
    discarded — and plans the next round's ``capacity`` as
    ``headroom * EMA``, quantized to powers of two so the number of distinct
    compiled programs stays bounded.  Observations are kept as device values
    and only resolved at ``plan()`` time, so the round that produced them is
    never host-synced on the hot path."""

    def __init__(self, alpha: float = 0.5, headroom: float = 1.5,
                 min_capacity: int = 4):
        self.alpha = alpha
        self.headroom = headroom
        self.min_capacity = min_capacity
        self._ema: Dict[Any, float] = {}
        self._staged: Dict[Any, Any] = {}

    def observe(self, sig, demand_max) -> None:
        self._staged[sig] = demand_max

    def prune(self, live_sigs) -> None:
        """Evict EMA/staged entries whose signature no live trust can
        produce again.  Signatures embed the trust token (solo) or the full
        fuse signature (mux), so a session that churns trusts — entrust,
        serve, drop, repeat — would otherwise accumulate one EMA float and
        possibly one staged DEVICE ARRAY per dead signature forever.  The
        engine calls this from ``_prune`` whenever trusts die."""
        live = set(live_sigs)
        for d in (self._ema, self._staged):
            for sig in [s for s in d if s not in live]:
                del d[sig]

    def _resolve(self, sig) -> None:
        staged = self._staged.pop(sig, None)
        if staged is None:
            return
        d = float(np.asarray(jax.device_get(staged)).reshape(-1)[0])
        prev = self._ema.get(sig)
        self._ema[sig] = d if prev is None else \
            self.alpha * d + (1.0 - self.alpha) * prev

    def ema(self, sig) -> Optional[float]:
        self._resolve(sig)
        return self._ema.get(sig)

    def plan(self, sig, fallback: int) -> int:
        """Planned primary capacity, or ``fallback`` with no history yet."""
        ema = self.ema(sig)
        if ema is None or ema <= 0:
            return fallback
        need = max(1, int(math.ceil(self.headroom * ema)))
        return max(self.min_capacity, 1 << (need - 1).bit_length())


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

def _avals(args: Pytree) -> Pytree:
    """Shape/dtype stand-ins of a round's arguments (``last_exec``): taken
    before the first call, since donation invalidates the state buffers,
    and never the arrays themselves, which would keep a round's states and
    payloads alive between steps."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.asarray(x).shape, jnp.asarray(x).dtype), args)


def _as_int(x) -> int:
    """Host-resolve a stat entry: a scalar-ish array, or ``(array, idx)``
    kept lazy so the hot path never slices a sharded array eagerly."""
    if isinstance(x, tuple):
        arr, idx = x
        return int(np.asarray(jax.device_get(arr)).reshape(-1)[idx])
    return int(np.asarray(jax.device_get(x)).reshape(-1)[0])


class DelegationEngine:
    """Session-wide execution engine for delegation rounds (``TrustSession``).

    Trusts register here at ``entrust`` time (weakly — dropping every handle
    to a Trust retires it and its cached programs).  ``submit`` marks a trust
    dirty; ``step()`` flushes ALL dirty trusts, fusing channel-compatible
    ones (same mesh/axes/mode/overflow/shortcut/pack_impl) into one
    multiplexed round and flushing the rest solo.  ``apply``/``flush`` on a
    single Trust always take the solo fast path."""

    def __init__(self, planner: Optional[CapacityPlanner] = None,
                 donate_states: bool = False):
        self._trusts: Dict[int, Any] = {}
        self._next_token = 0
        self._dirty: List[int] = []
        # key -> (jitted program, raw fn, resp_bytes_saved, its avals)
        self._cache: Dict[Any, Tuple[Callable, Callable, int, Any]] = {}
        self.planner = planner if planner is not None else CapacityPlanner()
        # donate the state buffers into each round's jitted program: the old
        # state is dead the moment the round commits (``trust._state`` is
        # replaced with the program output), so XLA may serve in place
        # instead of allocating a fresh state per round.  Opt-in (streaming
        # driver sessions) because donation invalidates the PREVIOUS state
        # array — callers that keep ``trust.state()`` references across
        # rounds (checkpoint diffing, the test batteries' oracles) must stay
        # on undonated sessions.  Request/response buffers are NOT donated:
        # requests are caller-owned (benchmarks replay one trace through
        # several drivers) and responses outlive the round by design.
        self.donate_states = donate_states
        # dispatched channel rounds (solo + mux) over the session lifetime —
        # cheap host-side telemetry for the streaming driver's occupancy math
        self.rounds_dispatched = 0
        self._last_step_stats: Dict[str, Dict[str, Any]] = {}
        # trace-time impl downgrade events (e.g. the f32-only serve kernel
        # falling back to lax) per compiled program — captured once when the
        # program traces, reported in every step's stats thereafter
        self._impl_events: Dict[Any, Tuple[str, ...]] = {}
        self._stats_owner: Dict[str, int] = {}
        self.last_step_info: Dict[str, Any] = {"fused": [], "solo": []}
        # (unjitted fused fn, aval-shaped args) of the last round's program
        # — jaxpr inspection in tests; fixed per program, so kept in _cache
        self.last_exec = None
        # -- resilience (DESIGN.md §14) ---------------------------------
        # monotonic wave id per step() dispatch: failure schedules key on
        # it, snapshot manifests record it, replays get FRESH ids
        self.wave_counter = 0
        self._current_wave = -1
        self._stepping = False      # in step(): spans read _current_wave
        self.injector = None            # EngineFailureInjector, if installed
        self.dead_shards: set = set()
        self.recovery = {"restores": 0, "replayed_rounds": 0,
                         "recovery_ms": 0.0}
        self._replaying = False
        self._last_snapshot: Optional[Tuple[str, int]] = None

    def _jit(self, fn) -> Callable:
        """jit a round program, donating the leading states argument when
        the session opts in (argument 0 is the state pytree in both the
        solo and mux builders)."""
        return jax.jit(fn, donate_argnums=(0,) if self.donate_states else ())

    # -- registry -----------------------------------------------------------
    def register(self, trust) -> int:
        token = self._next_token
        self._next_token += 1
        self._trusts[token] = weakref.ref(trust)
        return token

    def trusts(self) -> List[Any]:
        """Live registered trusts, in registration order."""
        out = []
        for tok in sorted(self._trusts):
            t = self._trusts[tok]()
            if t is not None:
                out.append(t)
        return out

    def _prune(self) -> None:
        dead = [tok for tok, ref in self._trusts.items() if ref() is None]
        for tok in dead:
            del self._trusts[tok]
        if dead:
            gone = set(dead)
            self._cache = {k: v for k, v in self._cache.items()
                           if not gone & set(k[1])}
            self._impl_events = {k: v for k, v in self._impl_events.items()
                                 if not gone & set(k[1])}
            self._dirty = [tok for tok in self._dirty if tok not in gone]
            # planner entries are keyed by ("solo", token) / ("mux", fuse
            # signature) — both outlive their trusts unless evicted here
            # (a session churning trusts would leak one EMA entry, and
            # possibly a staged device array, per dead signature)
            live_sigs = set()
            for t in self.trusts():
                live_sigs.add(("solo", t.token))
                live_sigs.add(("mux", self._mux_signature(t)))
            self.planner.prune(live_sigs)
            live_toks = {t.token for t in self.trusts()}
            self._stats_owner = {n: tok for n, tok in
                                 self._stats_owner.items()
                                 if tok in live_toks}

    def notify(self, trust) -> None:
        """A trust has pending submissions (called by ``Trust.submit``)."""
        if trust.token not in self._dirty:
            self._dirty.append(trust.token)

    def unnotify(self, trust) -> None:
        if trust.token in self._dirty:
            self._dirty.remove(trust.token)

    # -- telemetry ----------------------------------------------------------
    def last_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-trust stats of the most recent engine round(s):
        ``{trust_name: {rounds, residual, demand_max, resp_bytes_saved}}``.
        ``resp_bytes_saved`` counts response-transpose bytes per shard per
        round statically elided (zero-response fields / PUT-only lanes);
        for a fused round every member reports the round's total.

        After any recovery (``restore``/``re_entrust``) the dict carries a
        ``"recovery"`` entry with session-lifetime counters: ``restores``,
        ``replayed_rounds`` (rounds dispatched inside ``replaying()``), and
        ``recovery_ms`` (host wall time spent restoring/rebinding)."""
        out = {name: {k: _as_int(v) for k, v in d.items()}
               for name, d in self._last_step_stats.items()}
        if self.recovery["restores"]:
            out["recovery"] = {
                "restores": int(self.recovery["restores"]),
                "replayed_rounds": int(self.recovery["replayed_rounds"]),
                "recovery_ms": float(self.recovery["recovery_ms"])}
        return out

    # -- step: one multiplexed round for everything pending -----------------
    def _mux_signature(self, trust):
        # the fuse signature is DECLARED by the trust/config layer
        # (Trust.fuse_signature -> ChannelConfig.fuse_sig) rather than
        # assembled ad hoc here; capacity/overflow_capacity are part of it
        # because an explicit slot budget is a SEMANTIC choice (what
        # drops/defers), so trusts provisioned differently never fuse —
        # each lane must keep its solo capacity behavior bit-for-bit
        sig = getattr(trust, "_mux_sig", None)
        if sig is None:
            sig = trust.fuse_signature()
            trust._mux_sig = sig
        return sig

    def span_wave(self) -> int:
        """The wave id a host span carries: the wave ``step()`` is running,
        or outside it (a synchronous apply) the id the next step takes."""
        return self._current_wave if self._stepping else self.wave_counter

    def step(self, sync: bool = True) -> Optional[Dict[str, Dict[str, int]]]:
        """Flush every pending batch in as few channel rounds as possible.

        Channel-compatible trusts fuse into ONE multiplexed round; the rest
        flush solo.  Returns ``last_stats()``, UNLESS ``sync=False``:
        resolving the stats host-reads the round's telemetry outputs, which
        blocks the caller until the round has finished executing — exactly
        the barrier a dispatch-ahead driver (launch/streaming.py) must not
        pay.  ``sync=False`` dispatches the round asynchronously and
        returns ``None``; call ``last_stats()`` later (after consuming the
        responses) for the same numbers."""
        with tracing.span(tracing.STEP, self.wave_counter):
            self._stepping = True
            try:
                self._step()
            finally:
                self._stepping = False
        return self.last_stats() if sync else None

    def _step(self) -> None:
        self._prune()
        pending_trusts = []
        for tok in list(self._dirty):
            ref = self._trusts.get(tok)
            t = ref() if ref is not None else None
            if t is not None and t._pending:
                pending_trusts.append(t)
        if pending_trusts:
            # one wave id per non-empty step; probed BEFORE the queues are
            # dequeued so a pre-dispatch kill leaves them intact + notified
            self._current_wave = self.wave_counter
            self.wave_counter += 1
            if self.injector is not None:
                hit = self.injector.before_dispatch(self._current_wave)
                if hit is not None:
                    self._raise_failure(hit, self._current_wave,
                                        pending_trusts)
        self._dirty.clear()
        self._last_step_stats = {}
        self.last_step_info = {"fused": [], "solo": []}
        groups: Dict[Any, List[Any]] = {}
        for t in pending_trusts:
            groups.setdefault(self._mux_signature(t), []).append(t)
        remaining = [t for members in groups.values() for t in members]
        try:
            for members in groups.values():
                if len(members) == 1:
                    self.last_step_info["solo"].append(members[0].name)
                    members[0].flush()
                else:
                    self.last_step_info["fused"].append(
                        [t.name for t in members])
                    self._run_mux(members)
                for t in members:
                    remaining.remove(t)
        except Exception:
            # one group failing must not strand the others' pending batches
            # (the failed group restores its own queue and re-notifies)
            for t in remaining:
                if t._pending:
                    self.notify(t)
            raise

    # -- solo fast path (the pre-engine per-Trust program) ------------------
    def run_solo(self, trust, batches, capacity: Optional[int] = None):
        """Run ``batches`` of one trust through its own channel round.

        Bit-identical to the pre-engine ``Trust._run``: same program, same
        ordering, tree wire format — plus demand telemetry feeding the
        planner.  Returns the per-batch responses in request order."""
        sizes = [b[1].shape[0] for b in batches]
        r_total = sum(sizes)
        cfg = trust._cfg_for(r_total, capacity)
        sig = ("solo", trust.token)
        if (capacity is None and trust.cfg.capacity == 0
                and trust.plan_capacity):
            cap = self.planner.plan(sig, cfg.capacity)
            over = cap if trust.cfg.overflow == "second_round" else 0
            cfg = dataclasses.replace(
                cfg, capacity=cap,
                overflow_capacity=trust.cfg.overflow_capacity or over)
        # cache key: schema'd trusts key on SCHEMA IDENTITY (validation
        # pinned the payload avals at submit), stringly trusts on the
        # per-leaf aval tuple (trust.batch_signature)
        # the fuse signature carries every semantic knob of the compiled
        # program (impl choices, tile sizes, strict_impl, ...) — two configs
        # differing only in e.g. serve_block_rows must not share a program
        key = ("solo", (trust.token,),
               trust.batch_signature([b[0] for b in batches], sizes,
                                     [b[2] for b in batches]),
               cfg.capacity, cfg.overflow_capacity, cfg.fuse_sig())
        args = (trust._state, [b[1] for b in batches],
                [b[2] for b in batches])
        miss = key not in self._cache
        with tracing.span(tracing.BUILD if miss else tracing.LAUNCH,
                          self.span_wave()):
            if miss:
                fn, saved = _build_solo(trust, batches, cfg)
                self._cache[key] = (self._jit(fn), fn, saved, _avals(args))
            jitted, raw, saved, avals = self._cache[key]
            self.last_exec = (raw, avals)
            # impl events fire at trace time (first call per cache entry):
            # pin them to the program so later cache-hit steps still report
            # them
            with ch.collect_impl_events() as impl_events:
                (new_state, resps, rounds, residual, demand,
                 combined, req_saved) = jitted(*args)
        if impl_events:
            self._impl_events[key] = tuple(impl_events)
        # post-dispatch failure injection (drop/tear): fires BEFORE the
        # state commits, so recovery = restore snapshot + replay, uniformly
        self._maybe_tear([trust])
        trust._state = new_state
        trust._last_stats = (rounds, residual)
        self.planner.observe(sig, demand)
        self.rounds_dispatched += 1
        if self._replaying:
            self.recovery["replayed_rounds"] += 1
        # rows_combined/req_bytes_saved are zero-filled constants when the
        # trust ran no combine-eligible ops, so consumers (serve.py's
        # per-trust stats print) can always read them
        self._last_step_stats[self._stats_key(trust)] = {
            "rounds": rounds, "residual": residual, "demand_max": demand,
            "resp_bytes_saved": saved,
            "rows_combined": combined, "req_bytes_saved": req_saved,
            "impl_fallback": len(self._impl_events.get(key, ()))}
        return list(resps)

    # -- the multiplexed round ----------------------------------------------
    def _mux_cfg(self, trusts, r_totals) -> ch.ChannelConfig:
        """One channel config for the fused round.  ``capacity`` is PER
        LANE (each trust's own slot budget inside a (client, trustee)
        block): the trusts' shared explicit capacity (capacity is part of
        the fuse signature, so it is identical across the group), or — for
        auto-capacity trusts — the planner's EMA-sized block, falling back
        to the static per-trust mean rule before any history exists."""
        base = trusts[0].cfg
        explicit = [t.cfg.capacity for t in trusts if t.cfg.capacity > 0]
        fallback = max(t._auto_capacity(rt)
                       for t, rt in zip(trusts, r_totals))
        cap = max(explicit) if explicit else 0
        if any(t.cfg.capacity == 0 for t in trusts):
            planned = self.planner.plan(
                ("mux", self._mux_signature(trusts[0])), fallback)
            cap = max(cap, planned)
        over = 0
        if base.overflow == "second_round":
            over = max((t.cfg.overflow_capacity for t in trusts),
                       default=0) or cap
        return dataclasses.replace(base, capacity=cap,
                                   overflow_capacity=over,
                                   wire_fmt="planes")

    def _stats_key(self, trust) -> str:
        """Stats-dict key: the trust name, token-suffixed when a DIFFERENT
        live trust already claimed that name — so e.g. two 'rmw-lock'
        stores in one session never overwrite each other's stats."""
        name = trust.name
        owner = self._stats_owner.get(name)
        if owner is None or owner == trust.token:
            self._stats_owner[name] = trust.token
            return name
        return f"{name}#{trust.token}"

    def _run_mux(self, trusts) -> None:
        entries = []
        for t in trusts:
            pending, t._pending = t._pending, []
            entries.append((t, pending))
        try:
            batches = [[(o, d, p) for (o, d, p, _f) in pend]
                       for _t, pend in entries]
            sizes = [[b[1].shape[0] for b in tb] for tb in batches]
            cfg = self._mux_cfg(trusts, [sum(s) for s in sizes])
            key = ("mux", tuple(t.token for t in trusts),
                   tuple(t.batch_signature([b[0] for b in tb], sz,
                                           [b[2] for b in tb])
                         for t, tb, sz in zip(trusts, batches, sizes)),
                   cfg.capacity, cfg.overflow_capacity, cfg.fuse_sig())
            states = tuple(t._state for t in trusts)
            dsts = [[b[1] for b in tb] for tb in batches]
            payloads = [[b[2] for b in tb] for tb in batches]
            miss = key not in self._cache
            with tracing.span(tracing.BUILD if miss else tracing.LAUNCH,
                              self.span_wave()):
                if miss:
                    fn, saved = _build_mux(trusts, batches, cfg)
                    self._cache[key] = (self._jit(fn), fn, saved,
                                        _avals((states, dsts, payloads)))
                jitted, raw, saved, avals = self._cache[key]
                self.last_exec = (raw, avals)
                with ch.collect_impl_events() as impl_events:
                    (new_states, resps, rounds, residual_pt, demand_pt,
                     demand_merged, combined, req_saved) = \
                        jitted(states, dsts, payloads)
            if impl_events:
                self._impl_events[key] = tuple(impl_events)
            # post-dispatch failure injection (drop/tear) BEFORE any state
            # commits — the except below restores every member's queue
            self._maybe_tear(trusts)
        except Exception:
            # a build/dispatch error must not discard the queued batches:
            # restore every member's queue (state is untouched) so callers
            # can drop the offending submit and step again
            for t, pend in entries:
                t._pending = pend + t._pending
                self.notify(t)
            raise
        self.rounds_dispatched += 1
        if self._replaying:
            self.recovery["replayed_rounds"] += 1
        self.planner.observe(("mux", self._mux_signature(trusts[0])),
                             demand_merged)
        # per-batch responses were sliced INSIDE the program; stats stay
        # lazily indexed — no eager host-side ops on sharded arrays here
        for i, (t, pend) in enumerate(entries):
            t._state = new_states[i]
            t._last_stats = (rounds, (residual_pt, i))
            self._last_step_stats[self._stats_key(t)] = {
                "rounds": rounds, "residual": (residual_pt, i),
                "demand_max": (demand_pt, i),
                # round-level response-transpose bytes elided (shared by
                # every member of the fused round); rows_combined /
                # req_bytes_saved are likewise round totals, zero-filled
                # constants for rounds with no combine-eligible ops
                "resp_bytes_saved": saved,
                "rows_combined": combined, "req_bytes_saved": req_saved,
                "impl_fallback": len(self._impl_events.get(key, ()))}
            for (_o, _d, _p, fut), resp in zip(pend, resps[i]):
                fut._fulfil(resp)

    # -- resilience: snapshot / restore / failover (DESIGN.md §14) ----------
    def install_injector(self, injector) -> None:
        """Install an ``EngineFailureInjector`` (runtime/fault_tolerance):
        its schedule is probed per wave at dispatch (kill) and between
        dispatch and state-commit (drop/tear)."""
        self.injector = injector

    def _raise_failure(self, hit, wave_id: int, trusts) -> None:
        from ..runtime.fault_tolerance import TrusteeFailure
        kind, shard = hit
        if kind == "kill" and shard is not None:
            self.dead_shards.add(int(shard))
        snap = self._last_snapshot[1] if self._last_snapshot else None
        raise TrusteeFailure(
            f"trustee failure ({kind}) on shard {shard} at wave {wave_id}"
            f" (last snapshot: {'none' if snap is None else snap})",
            kind=kind, trusts=tuple(t.name for t in trusts),
            wave_id=wave_id, shard=shard, last_snapshot_step=snap)

    def _maybe_tear(self, trusts) -> None:
        if self.injector is None:
            return
        hit = self.injector.after_dispatch(self._current_wave)
        if hit is not None:
            self._raise_failure(hit, self._current_wave, trusts)

    @contextlib.contextmanager
    def replaying(self):
        """Mark the enclosed rounds as recovery replays: they increment
        ``recovery["replayed_rounds"]`` instead of counting as new work."""
        prev, self._replaying = self._replaying, True
        try:
            yield
        finally:
            self._replaying = prev

    def quiesced(self) -> bool:
        """True when no trust has pending submissions (the only states a
        snapshot may capture — between engine rounds the trustee's linear
        op history has no in-flight prefix)."""
        return not self._dirty and all(
            not t._pending for t in self.trusts())

    def checkpoint(self, directory: str, step: Optional[int] = None) -> int:
        """Snapshot every registered Trust's LOGICAL entrusted state into
        one atomic, crc-checked checkpoint (checkpoint/checkpoint.py).

        Requires a quiesced session: the trustee serializes all ops, so
        "state between engine rounds" IS the consistent cut — there is no
        speculative work to lose and nothing in flight to fence.  The
        manifest carries each trust's schema fingerprint, fuse signature
        and trustee-group layout so ``restore`` can validate compatibility
        and re-shard across a trustee-count change.  Returns the step
        (default: the current wave counter)."""
        from ..checkpoint import checkpoint as ckpt
        self._prune()
        trusts = self.trusts()
        busy = sorted(t.name for t in trusts if t._pending)
        if busy:
            raise RuntimeError(
                f"session.checkpoint requires a quiesced session (snapshots "
                f"are taken between engine rounds); trusts with pending "
                f"submissions: {busy} — flush/step/drain first")
        names = [t.name for t in trusts]
        if len(set(names)) != len(names):
            raise ValueError(
                f"session.checkpoint needs unique trust names (the name is "
                f"the manifest key), got {sorted(names)}")
        if step is None:
            step = self.wave_counter
        tree, meta = {}, {}
        for t in trusts:
            tree[t.name] = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), t.trustee_state())
            g = t.group
            meta[t.name] = {
                "schema": (t.schema.fingerprint()
                           if t.schema is not None else None),
                "fuse_sig": repr(t.cfg.fuse_sig()),
                "n_trustees": g.n_trustees, "mode": g.mode,
                "axes": list(g.axes), "n_dedicated": g.n_dedicated,
                "mesh_shape": list(g.mesh.devices.shape)}
        ckpt.save(directory, step, tree,
                  extra={"kind": "trust_session", "wave": self.wave_counter,
                         "trusts": meta})
        self._last_snapshot = (directory, step)
        return step

    def restore(self, directory: str, step: Optional[int] = None) -> int:
        """Restore every registered Trust's entrusted state from a session
        snapshot, matching by trust NAME, validating the schema fingerprint,
        and ``device_put``-ing against the CURRENT mesh's shardings (the
        snapshot stores logical owner-major state, so the mesh shape may
        have changed).  A trustee-count change re-lays the state out via
        the schema's ``reshard=`` rule.  Unacknowledged pending submissions
        are dropped — recovery replays them from the snapshot wave.
        Returns the restored step."""
        from ..checkpoint import checkpoint as ckpt
        t0 = time.perf_counter()
        self._prune()
        trusts = {t.name: t for t in self.trusts()}
        tree_like = {name: jax.tree.map(lambda _: 0, t.trustee_state())
                     for name, t in trusts.items()}
        try:
            tree, got_step, extra = ckpt.restore(directory, tree_like, step)
        except KeyError as e:
            raise ValueError(
                f"checkpoint under {directory} has no state for trust "
                f"leaf {e.args[0]!r}: the live session and the snapshot "
                f"disagree on registered trusts") from None
        meta = (extra or {}).get("trusts", {})
        for name, t in trusts.items():
            m = meta.get(name, {})
            want = t.schema.fingerprint() if t.schema is not None else None
            if m and m.get("schema") != want:
                raise ValueError(
                    f"trust {name!r}: schema fingerprint mismatch "
                    f"(checkpoint {m.get('schema')}, live {want}) — "
                    f"refusing to restore incompatible state")
            host = tree[name]
            old_t = int(m.get("n_trustees", t.n_trustees))
            if old_t != t.n_trustees:
                if t.schema is None or t.schema.reshard is None:
                    raise ValueError(
                        f"trust {name!r}: checkpoint holds {old_t}-trustee "
                        f"state but the live group has {t.n_trustees} "
                        f"trustees and the schema declares no reshard= rule")
                host = t.schema.reshard(
                    jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                 host), old_t, t.n_trustees)
            t.install_trustee_state(host)
            t._pending = []
            self.unnotify(t)
        self._last_snapshot = (directory, got_step)
        self.recovery["restores"] += 1
        self.recovery["recovery_ms"] += (time.perf_counter() - t0) * 1e3
        return got_step

    def re_entrust(self, failed_shards, survivors=None,
                   ckpt_dir: Optional[str] = None,
                   step: Optional[int] = None, plan=None) -> None:
        """Failover: rebuild every live trust's trustee group EXCLUDING the
        dead shards, re-shard its state onto the survivors, and invalidate
        the stale compiled programs.

        ``failed_shards`` are flat device-slot indices into each group's
        mesh; ``survivors`` overrides the survivor device list (default:
        every mesh device not named in ``failed_shards``).  The shrunk mesh
        shape comes from ``plan`` (an ``ElasticPlan``; default the
        delegation ladder — 1-D trustee rings shrinking one shard at a
        time).  State comes from ``ckpt_dir`` (the last snapshot — the
        normal recovery path: the dead shard's DRAM is gone) or, when
        ``ckpt_dir`` is None, live from the current state (administrative
        re-shard, e.g. draining a shard ahead of maintenance).  Pending
        submissions are dropped: the driver replays from the snapshot.
        Callers replay inside ``session.replaying()`` so the rounds land
        in ``recovery["replayed_rounds"]``."""
        from .trust import TrusteeGroup
        from .meshctx import survivors_mesh
        from ..checkpoint import checkpoint as ckpt
        t0 = time.perf_counter()
        self._prune()
        trusts = self.trusts()
        if not trusts:
            return
        failed = {int(s) for s in failed_shards}
        self.dead_shards |= failed
        if ckpt_dir is None:
            host_states = {t.name: jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), t.trustee_state())
                for t in trusts}
            metas = {t.name: {"n_trustees": t.n_trustees} for t in trusts}
        else:
            tree_like = {t.name: jax.tree.map(lambda _: 0, t.trustee_state())
                         for t in trusts}
            host_states, got_step, extra = ckpt.restore(
                ckpt_dir, tree_like, step)
            metas = (extra or {}).get("trusts", {})
            self._last_snapshot = (ckpt_dir, got_step)
        new_meshes: Dict[int, Mesh] = {}

        def shrunk_mesh(old_mesh: Mesh) -> Mesh:
            key = id(old_mesh)
            if key not in new_meshes:
                new_meshes[key] = survivors_mesh(old_mesh, failed,
                                                 survivors, plan)
            return new_meshes[key]

        for t in trusts:
            g = t.group
            mesh = shrunk_mesh(g.mesh)
            n_ded = g.n_dedicated
            if g.mode == "dedicated":
                axis_size = 1
                for a in g.axes:
                    axis_size *= int(mesh.shape[a])
                n_ded = max(1, min(g.n_dedicated, axis_size - 1))
            new_group = TrusteeGroup(mesh, g.axis, mode=g.mode,
                                     n_dedicated=n_ded)
            new_t = new_group.n_trustees
            old_t = int(metas.get(t.name, {}).get("n_trustees",
                                                  t.n_trustees))
            host = host_states[t.name]
            schema = t.schema
            if new_t != old_t:
                if schema is None or schema.reshard is None:
                    raise ValueError(
                        f"trust {t.name!r}: cannot re-entrust from {old_t} "
                        f"to {new_t} trustees — the schema declares no "
                        f"reshard= rule")
                host = schema.reshard(
                    jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                 host), old_t, new_t)
                if t.schema_factory is not None:
                    # serve closures may bake the trustee count in (e.g.
                    # the KV table's local_idx): rebuild the schema for it
                    schema = t.schema_factory(new_t)
            t._pending = []
            self.unnotify(t)
            t.rebind(new_group, schema=schema, logical_state=host)
        # every compiled program whose member set touches a rebound trust
        # carries the OLD fuse signature / schema identity — evict them
        toks = {t.token for t in trusts}
        self._cache = {k: v for k, v in self._cache.items()
                       if not toks & set(k[1])}
        self._impl_events = {k: v for k, v in self._impl_events.items()
                             if not toks & set(k[1])}
        live_sigs = set()
        for t in self.trusts():
            live_sigs.add(("solo", t.token))
            live_sigs.add(("mux", self._mux_signature(t)))
        self.planner.prune(live_sigs)
        self.recovery["restores"] += 1
        self.recovery["recovery_ms"] += (time.perf_counter() - t0) * 1e3


# ``TrustSession`` is the user-facing name (the paper-side concept: one
# session, many entrusted objects, one message lane); ``DelegationEngine``
# the implementation-side one.  Same class.
TrustSession = DelegationEngine


# ---------------------------------------------------------------------------
# Program builders
# ---------------------------------------------------------------------------

def _demand_from_group_sizes(info: ch.ChannelInfo, axes_all) -> jax.Array:
    """Max per-(client, trustee) pair demand over the whole mesh — the
    §5.3.1 telemetry (``group_sizes``) the pack phase always computed."""
    demand = lax.pmax(jnp.max(info.group_sizes), axes_all)
    return jnp.reshape(demand.astype(jnp.int32), (1,))


def _build_solo(trust, batches, cfg: ch.ChannelConfig):
    """The per-Trust program (the pre-engine ``Trust._build_exec``), plus
    demand telemetry: fuse the queued batches into one delegation round.
    Returns ``(fused_fn, resp_bytes_saved)`` — the second element is the
    static response-transpose bytes the round's elision plan avoids."""
    mesh = trust.group.mesh
    ops = trust.ops
    resp_like = trust.resp_like
    n_trustees = trust.n_trustees
    op_ids = [b[0] for b in batches]
    check_payload_fields(
        [(ops[oid].name, p) for (oid, _d, p) in batches])
    active = tuple(sorted(set(op_ids)))
    # response-plane elision: fields no active op writes stay off the wire
    # (replace cfg BEFORE building the serve — the fused serve reads the
    # tile/strict knobs off the cfg it is handed)
    cfg = dataclasses.replace(
        cfg, elide_resp=_elidable_fields(ops, active, resp_like))
    serve = ch.serve_optable(ops, active_ids=active,
                             serve_impl=cfg.serve_impl, cfg=cfg)
    # request combining (DESIGN.md §13): one CombineSpan per active op that
    # declares an archetype; rows of undeclared ops ride span -1 (never
    # combined).  Span membership is static per batch, so the span column
    # is built host-side below and never ships on the wire.
    combiner = None
    span_of_op: Dict[int, int] = {}
    if cfg.combine_impl != "off":
        span_list = []
        for oid in active:
            if ops[oid].combine is None:
                continue
            kind, ckey, cfield, cresp = ch.as_combine_decl(ops[oid].combine)
            span_of_op[oid] = len(span_list)
            span_list.append(ch.CombineSpan(
                kind, key_lane=ckey,
                sum_lane=cfield if kind == "sum" else None,
                resp_tid=None, resp_field=cresp))
        if span_list:
            combiner = ch.RequestCombiner(tuple(span_list))
    # Request batches are sharded over the whole mesh.  Shared mode: every
    # device is a client and originates its own slice.  Dedicated mode: the
    # fused batch is repacked so all real rows land on the leading n_clients
    # shards and trustee shards see only dst=-1 padding — requests originate
    # on client shards only.
    req_spec = P(tuple(mesh.axis_names))
    axes_all = tuple(mesh.axis_names)
    dedicated = trust.group.mode == "dedicated"
    n_cli = trust.group.n_clients
    n_dev = trust.group.axis_size
    state_specs = trust.state_specs
    batch_sizes = [b[1].shape[0] for b in batches]

    single_op = len(set(op_ids)) == 1

    def fuse(dsts, payloads):
        # concat batches, tag each row with its op id; a single-op round
        # skips the lane (it would be a constant column on the wire)
        dst = jnp.concatenate(dsts, 0)
        rows = {} if single_op else {"op": jnp.concatenate(
            [jnp.full((d.shape[0],), oid, jnp.int16)
             for oid, d in zip(op_ids, dsts)], 0)}
        names = set()
        for p in payloads:
            names |= set(p.keys())
        for name in sorted(names):
            parts = []
            for p, d in zip(payloads, dsts):
                if name in p:
                    parts.append(p[name])
                else:
                    like = next(pp[name] for pp in payloads if name in pp)
                    parts.append(jnp.zeros((d.shape[0],) + like.shape[1:],
                                           like.dtype))
            rows[name] = jnp.concatenate(parts, 0)

        span_col = None
        if combiner is not None:
            span_col = jnp.concatenate(
                [jnp.full((d.shape[0],), span_of_op.get(oid, -1), jnp.int32)
                 for oid, d in zip(op_ids, dsts)], 0)

        r_total = dst.shape[0]
        # pad the fused batch so each ORIGIN shard gets an equal slice:
        # dedicated mode packs all R rows onto the leading n_clients shards
        # (trustee shards hold only inactive padding); shared mode pads
        # ragged batches up to a multiple of the mesh size
        n_origins = n_cli if dedicated else max(1, mesh.size)
        r_dev = -(-r_total // n_origins)
        pad = (n_dev if dedicated else mesh.size) * r_dev - r_total
        if pad:
            dst = jnp.concatenate(
                [dst, jnp.full((pad,), -1, dst.dtype)], 0)
            rows = jax.tree.map(
                lambda l: jnp.concatenate(
                    [l, jnp.zeros((pad,) + l.shape[1:], l.dtype)], 0),
                rows)
            if span_col is not None:
                span_col = jnp.concatenate(
                    [span_col, jnp.full((pad,), -1, jnp.int32)], 0)
        return dst, rows, span_col

    def fused(state, dsts, payloads):
        with tracing.scope(tracing.FUSE):
            dst, rows, span_col = fuse(dsts, payloads)
        # any defer config routes through the drain engine so the
        # rounds/residual telemetry is truthful even at max_rounds=1
        drain = cfg.overflow == "defer"

        def shard_fn(state_shard, dst_l, rows_l, *extra):
            ckw = dict(combine=combiner, combine_span=extra[0]) \
                if combiner is not None else {}
            if drain:
                new_state, resp, info = ch.delegate_drain(
                    state_shard, dst_l, rows_l, serve, n_trustees, cfg,
                    **ckw)
                rounds, residual = info.rounds, info.residual
            else:
                new_state, resp, info = ch.delegate(
                    state_shard, dst_l, rows_l, serve, n_trustees, cfg,
                    **ckw)
                rounds, residual = jnp.int32(1), jnp.int32(0)
            demand = _demand_from_group_sizes(info, axes_all)
            combined = jnp.reshape(
                jnp.asarray(info.rows_combined, jnp.int32), (1,))
            req_saved = jnp.reshape(
                jnp.asarray(info.req_bytes_saved, jnp.int32), (1,))
            # identical on every shard (the drain loop count is psum-
            # synchronized, combine stats are psum totals), so P(None)
            # replication below is sound
            return (new_state, resp, jnp.reshape(rounds, (1,)),
                    jnp.reshape(residual, (1,)), demand, combined,
                    req_saved)

        in_specs = (state_specs, req_spec,
                    jax.tree.map(lambda _: req_spec, rows)) \
            + ((req_spec,) if combiner is not None else ())
        out_specs = (state_specs,
                     jax.tree.map(lambda _: req_spec, resp_like),
                     P(None), P(None), P(None), P(None), P(None))
        f = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_rep=False)
        args = (state, dst, rows) + \
            ((span_col,) if combiner is not None else ())
        (new_state, resp, rounds, residual, demand,
         combined, req_saved) = f(*args)
        # split the fused responses back per batch INSIDE the program (host-
        # side slicing of sharded arrays would pay one dispatch per leaf)
        resps, off = [], 0
        with tracing.scope(tracing.RESPOND):
            for n in batch_sizes:
                resps.append(jax.tree.map(lambda l, o=off, m=n: l[o:o + m],
                                          resp))
                off += n
        return (new_state, tuple(resps), rounds, residual, demand,
                combined, req_saved)

    n_rows = cfg.n_slots(n_trustees) * cfg.n_lanes * cfg.total_capacity()
    saved = 0 if (cfg.n_slots(n_trustees) == 1 and cfg.local_shortcut) \
        else ch.resp_elision_bytes(resp_like, cfg, n_rows)
    return fused, saved


def _build_mux(trusts, batches, cfg: ch.ChannelConfig) -> Callable:
    """ONE multiplexed program for several trusts' queued batches.

    Layout: rows concatenate in (trust, batch) order with ``"trust"`` and
    ``"op"`` id lanes; payload fields whose dtype/trailing shape agree
    across trusts share a wire lane (row sets are disjoint), mismatched
    fields get per-trust lanes (``field@tid``).  One pack, one request
    all_to_all (planes wire), one merged serve pass, one response
    transpose; per-trust states thread independently through the serve, so
    each trust's semantics are exactly its solo semantics over the engine's
    row layout (DESIGN.md §8 ordering note)."""
    group = trusts[0].group
    mesh = group.mesh
    n_trusts = len(trusts)
    n_trustees = group.n_trustees
    dedicated = group.mode == "dedicated"
    n_cli = group.n_clients
    n_dev = group.axis_size
    req_spec = P(tuple(mesh.axis_names))
    axes_all = tuple(mesh.axis_names)

    # field plan: intra-trust mismatches are errors (zero-fill widening
    # would corrupt); cross-trust mismatches get namespaced lanes
    per_trust_fields: List[Dict[str, Tuple]] = []
    for t, tb in zip(trusts, batches):
        seen = check_payload_fields(
            [(f"{t.name}.{t.ops[oid].name}", p) for (oid, _d, p) in tb])
        per_trust_fields.append({name: sig for name, (_l, sig)
                                 in seen.items()})
    lane_of: List[Dict[str, str]] = [dict() for _ in range(n_trusts)]
    for name in sorted(set().union(*[set(f) for f in per_trust_fields])):
        sigs = {tid: f[name] for tid, f in enumerate(per_trust_fields)
                if name in f}
        shared = len(set(sigs.values())) == 1
        for tid in sigs:
            lane_of[tid][name] = name if shared else f"{name}@{tid}"

    # one merged response tree when every trust's response structure agrees
    # (the row sets are disjoint, so one tree carries them all and the
    # response transpose moves each row's bytes once); otherwise a tuple of
    # per-trust trees
    def resp_sig(t):
        leaves, treedef = jax.tree.flatten(t.resp_like)
        return (treedef, tuple((tuple(jnp.asarray(l).shape[1:]),
                                str(jnp.asarray(l).dtype)) for l in leaves))
    merged_resp = len({resp_sig(t) for t in trusts}) == 1

    # LANE slot layout (the fused round's core): each trust owns a static
    # ``capacity`` sub-block of every (client, trustee) slot block, so pack
    # bins by virtual destination dst*n_trusts + tid, each trust keeps its
    # solo capacity/FIFO/drop semantics, and the strided serve touches each
    # received row exactly once (work linear in n_trusts).  Falls back to
    # the masked full-pass serve when response structures differ (no
    # restacking possible) or the channel degenerates to local-only.
    t_send = cfg.n_slots(n_trustees)
    strided = merged_resp and not (t_send == 1 and cfg.local_shortcut)
    if strided:
        cfg = dataclasses.replace(cfg, n_lanes=n_trusts)
    c2 = cfg.overflow_capacity \
        if cfg.overflow == "second_round" and cfg.overflow_capacity > 0 else 0

    tables = tuple((t.ops, tuple(sorted({oid for (oid, _d, _p) in tb})))
                   for t, tb in zip(trusts, batches))

    # response elision plan: fields NO trust's active ops write drop from
    # the response transpose entirely; with the lane layout, lanes whose
    # trust writes nothing (e.g. PUT-only) drop their slot rows per lane
    elidable_pt = [_elidable_fields(ops_t, active, t.resp_like)
                   for t, (ops_t, active) in zip(trusts, tables)]
    if merged_resp and isinstance(trusts[0].resp_like, dict):
        all_fields = set(trusts[0].resp_like.keys())
        common = set.intersection(*[set(e) for e in elidable_pt])
        lanes_off = tuple(tid for tid, e in enumerate(elidable_pt)
                          if set(e) == all_fields)
        if len(lanes_off) == n_trusts:
            common, lanes_off = all_fields, ()   # nothing responds at all
        elif not strided:
            lanes_off = ()                       # masked layout has no lanes
        cfg = dataclasses.replace(cfg, elide_resp=tuple(sorted(common)),
                                  elide_lanes=lanes_off)

    if strided:
        serve = ch.serve_multiplex_strided(
            tables, tuple(lane_of), n_lanes=n_trusts, t_send=t_send,
            c1=cfg.capacity, c2=c2, serve_impl=cfg.serve_impl, cfg=cfg)
    else:
        serve = ch.serve_multiplex(tables, tuple(lane_of),
                                   merge_resp=merged_resp,
                                   serve_impl=cfg.serve_impl, cfg=cfg)
    # request combining (DESIGN.md §13): one CombineSpan per (trust, op)
    # that declares an archetype, on the POST-rename wire lanes; the sum
    # archetype's prior rebuilds into the merged response dict (resp_tid
    # None) or this trust's subtree of the per-trust response tuple
    combiner = None
    span_of: Dict[Tuple[int, int], int] = {}
    if cfg.combine_impl != "off":
        span_list = []
        for tid, (t, (ops_t, active)) in enumerate(zip(trusts, tables)):
            for oid in active:
                if ops_t[oid].combine is None:
                    continue
                kind, ckey, cfield, cresp = \
                    ch.as_combine_decl(ops_t[oid].combine)
                span_of[(tid, oid)] = len(span_list)
                span_list.append(ch.CombineSpan(
                    kind, key_lane=lane_of[tid][ckey],
                    sum_lane=lane_of[tid][cfield] if kind == "sum" else None,
                    resp_tid=None if merged_resp else tid,
                    resp_field=cresp))
        if span_list:
            combiner = ch.RequestCombiner(tuple(span_list))

    state_specs = tuple(t.state_specs for t in trusts)
    resp_specs = jax.tree.map(lambda _: req_spec, trusts[0].resp_like) \
        if merged_resp else \
        tuple(jax.tree.map(lambda _: req_spec, t.resp_like) for t in trusts)
    # static row offsets per (trust, batch) in the fused trust-major layout
    spans: List[List[Tuple[int, int]]] = []
    off = 0
    for tb in batches:
        spans.append([])
        for b in tb:
            n = b[1].shape[0]
            spans[-1].append((off, n))
            off += n

    # wire-lane economy: the op lane ships only when some trust dispatches
    # more than one op this round; the trust lane ships only when the serve
    # actually reads it (masked layout, or a local-shortcut tail in the
    # strided layout) — otherwise lane membership IS the slot layout and
    # the column stays off the wire (stats get it as a separate shard arg)
    need_op = any(len(active) > 1 for _ops, active in tables)
    need_trust_on_wire = (not strided) or cfg.local_shortcut

    def fuse(dsts, payloads):
        flat = []   # (tid, oid, dst, payload) in (trust, batch) order
        for tid, (tb_d, tb_p, tb) in enumerate(zip(dsts, payloads, batches)):
            for (oid, _d0, _p0), d, p in zip(tb, tb_d, tb_p):
                flat.append((tid, oid, d, p))
        dst = jnp.concatenate([d for _t, _o, d, _p in flat], 0)
        tid_col = jnp.concatenate(
            [jnp.full((d.shape[0],), tid, jnp.int16)
             for tid, _o, d, _p in flat], 0)
        rows = {}
        if need_op:
            rows["op"] = jnp.concatenate(
                [jnp.full((d.shape[0],), oid, jnp.int16)
                 for _t, oid, d, _p in flat], 0)
        if need_trust_on_wire:
            rows["trust"] = tid_col
        # like templates per lane (verified consistent above)
        lane_like: Dict[str, jax.Array] = {}
        for tid, _oid, _d, p in flat:
            for fname, leaf in p.items():
                lane_like.setdefault(lane_of[tid][fname], jnp.asarray(leaf))
        for lane in sorted(lane_like):
            parts = []
            for tid, _oid, d, p in flat:
                rev = {ln: f for f, ln in lane_of[tid].items()}
                fname = rev.get(lane)
                if fname is not None and fname in p:
                    parts.append(p[fname])
                else:
                    like = lane_like[lane]
                    parts.append(jnp.zeros((d.shape[0],) + like.shape[1:],
                                           like.dtype))
            rows[lane] = jnp.concatenate(parts, 0)

        if strided:
            # virtual bins: lane tid of trustee d is bin d*n_trusts + tid
            dst = jnp.where(dst >= 0,
                            dst * n_trusts + tid_col.astype(jnp.int32), -1)

        span_col = None
        if combiner is not None:
            span_col = jnp.concatenate(
                [jnp.full((d.shape[0],), span_of.get((tid, oid), -1),
                          jnp.int32)
                 for tid, oid, d, _p in flat], 0)

        r_total = dst.shape[0]
        n_origins = n_cli if dedicated else max(1, mesh.size)
        r_dev = -(-r_total // n_origins)
        pad = (n_dev if dedicated else mesh.size) * r_dev - r_total
        if pad:
            dst = jnp.concatenate(
                [dst, jnp.full((pad,), -1, dst.dtype)], 0)
            tid_col = jnp.concatenate(
                [tid_col, jnp.zeros((pad,), tid_col.dtype)], 0)
            rows = jax.tree.map(
                lambda l: jnp.concatenate(
                    [l, jnp.zeros((pad,) + l.shape[1:], l.dtype)], 0),
                rows)
            if span_col is not None:
                span_col = jnp.concatenate(
                    [span_col, jnp.full((pad,), -1, jnp.int32)], 0)
        return dst, rows, tid_col, span_col

    def fused(states, dsts, payloads):
        with tracing.scope(tracing.FUSE):
            dst, rows, tid_col, span_col = fuse(dsts, payloads)
        drain = cfg.overflow == "defer"

        def shard_fn(states_l, dst_l, rows_l, tid_l, *extra):
            ckw = dict(combine=combiner, combine_span=extra[0]) \
                if combiner is not None else {}
            if drain:
                new_states, resp, info = ch.delegate_drain(
                    states_l, dst_l, rows_l, serve, n_trustees, cfg, **ckw)
                rounds = info.rounds
            else:
                new_states, resp, info = ch.delegate(
                    states_l, dst_l, rows_l, serve, n_trustees, cfg, **ckw)
                rounds = jnp.int32(1)
            tid32 = tid_l.astype(jnp.int32)
            # per-trust residual (rows left unserved on any shard)
            res_pt = jnp.zeros((n_trusts + 1,), jnp.int32).at[
                jnp.where(info.dropped, tid32, n_trusts)].add(1)[:-1]
            res_pt = lax.psum(res_pt, axes_all)
            if strided:
                # group_sizes is per virtual bin (device slot x lane): the
                # §5.3.1 telemetry, now per trust for free
                gs = info.group_sizes.reshape(-1, n_trusts)
                demand_pt = lax.pmax(jnp.max(gs, axis=0), axes_all)
            else:
                # masked layout: per-trust max pair demand via scatter-add
                # (post-shortcut, pre-capacity)
                act = dst_l >= 0
                if cfg.local_shortcut and not dedicated:
                    act = act & (dst_l != ch._my_trustee_id(cfg.axis))
                idx = jnp.where(act,
                                tid32 * n_trustees
                                + jnp.clip(dst_l, 0, n_trustees - 1),
                                n_trusts * n_trustees)
                pair = jnp.zeros((n_trusts * n_trustees + 1,), jnp.int32) \
                    .at[idx].add(1)[:-1].reshape(n_trusts, n_trustees)
                demand_pt = lax.pmax(jnp.max(pair, axis=1), axes_all)
            demand_merged = _demand_from_group_sizes(info, axes_all)
            combined = jnp.reshape(
                jnp.asarray(info.rows_combined, jnp.int32), (1,))
            req_saved = jnp.reshape(
                jnp.asarray(info.req_bytes_saved, jnp.int32), (1,))
            return (new_states, resp, jnp.reshape(rounds, (1,)),
                    res_pt, demand_pt, demand_merged, combined, req_saved)

        in_specs = (state_specs, req_spec,
                    jax.tree.map(lambda _: req_spec, rows), req_spec) \
            + ((req_spec,) if combiner is not None else ())
        out_specs = (state_specs, resp_specs,
                     P(None), P(None), P(None), P(None), P(None), P(None))
        f = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_rep=False)
        args = (states, dst, rows, tid_col) + \
            ((span_col,) if combiner is not None else ())
        (new_states, resp, rounds, res_pt, demand_pt, demand_merged,
         combined, req_saved) = f(*args)
        # slice every (trust, batch) span back out INSIDE the program (host-
        # side slicing of sharded arrays would pay one dispatch per leaf)
        out_resps = []
        with tracing.scope(tracing.RESPOND):
            for tid, tb_spans in enumerate(spans):
                src = resp if merged_resp else resp[tid]
                out_resps.append(tuple(
                    jax.tree.map(lambda l, o=o, m=m: l[o:o + m], src)
                    for (o, m) in tb_spans))
        return (new_states, tuple(out_resps), rounds, res_pt,
                demand_pt, demand_merged, combined, req_saved)

    n_rows = cfg.n_slots(n_trustees) * cfg.n_lanes * cfg.total_capacity()
    saved = 0 if (t_send == 1 and cfg.local_shortcut) \
        else ch.resp_elision_bytes(trusts[0].resp_like, cfg, n_rows)
    return fused, saved
