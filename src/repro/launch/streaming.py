"""Continuous serving driver — double-buffered engine rounds (DESIGN.md §11).

Everything below the engine is bulk-synchronous: callers enqueue, one
blocking ``session.step()`` runs one fused round, responses come back, the
next wave starts.  The paper's headline numbers (5-9x on memcached, §7) are
about *sustained serving under live traffic*, where the client side packs
the NEXT wave while the trustees serve the current one.  This module is
that loop:

  * **dispatch-ahead** — ``StreamingDriver.dispatch()`` runs
    ``session.step(sync=False)`` (an asynchronous engine round: JAX's
    async dispatch returns as soon as the program is enqueued) and parks a
    ``WaveHandle``; ``jax.block_until_ready`` is paid only when the wave's
    responses are CONSUMED, up to ``depth`` waves later.  In between, the
    host packs and dispatches the following waves — wave k+1's program
    chains on wave k's state output inside the runtime, so ordering (and
    bit-identity with a lockstep run) is preserved by dataflow, not by
    host barriers.
  * **admission control** — ``AdmissionControl`` is a host-side row-token
    bucket bounding the rows in flight across all unconsumed waves (the
    streaming analog of the ``launch/serve.py`` token ledger: what has
    been admitted but not yet served).  ``admit()`` consumes oldest waves
    until the bucket has room, so a burst cannot queue unboundedly ahead
    of the trustees — latency is bounded by ``depth`` waves instead.
  * **adaptive wave sizing** — ``wave_budget()`` turns the
    ``CapacityPlanner`` demand EMA (max per-(client, trustee, lane) pair
    rows, §5.3.1 telemetry) into a target row count for the next wave:
    ``headroom * EMA * n_pairs`` keeps the hot pair's expected demand at
    the planned primary-block size.  The EMA is refreshed only at
    pipeline-QUIESCE points (a consume that leaves nothing in flight):
    the planner's staged demand scalar always belongs to the newest
    dispatched round, so resolving it any earlier would host-sync on an
    in-flight program — the exact stall ``step(sync=False)`` exists to
    avoid.  (Same reason streaming stores should use static ``capacity``:
    auto-capacity trusts make the ENGINE consult ``planner.plan()`` at
    pack time.)

Ordering/consistency: overlapped waves commit in dispatch order (state
chains through the jitted programs); responses of wave k reflect exactly
the waves ≤ k.  The §4 drain-round caveat carries over unchanged — a
``defer`` trust's wave may internally run several drain rounds, but they
stay inside that wave's program.  See DESIGN.md §11.

Sessions used for streaming may opt into state-buffer donation
(``TrustSession(donate_states=True)``): each round's state input is dead
as soon as the round commits, so XLA may reuse the buffer instead of
allocating a fresh state per wave.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..core import tracing

Pytree = Any


@dataclass
class WaveHandle:
    """One dispatched engine round and the bookkeeping to consume it.
    ``wave_id`` counts StreamingDriver's waves; ``engine_wave`` is the engine's
    id of the round (-1 when the dispatch ran none), which the engine's
    host spans carry too."""
    wave_id: int
    outputs: Any = None              # pytree of arrays / TrustFutures
    rows: int = 0
    rids: Tuple[int, ...] = ()
    on_consume: Optional[Callable[["WaveHandle"], None]] = None
    dispatched_at: float = 0.0
    consumed_at: float = -1.0
    users: Optional[Dict[Any, int]] = None   # per-user row breakdown
    engine_wave: int = -1

    @property
    def wave_latency_s(self) -> float:
        return self.consumed_at - self.dispatched_at


class AdmissionControl:
    """Row-token bucket over the waves in flight.

    ``max_inflight_rows`` bounds the admitted-but-unserved backlog; a
    request wave is admitted only while the bucket has room, and a consumed
    wave returns its rows.  With ``depth``-bounded pipelining this is the
    knob that trades throughput (deeper backlog keeps the trustees busy)
    against tail latency (every admitted row waits behind the rows ahead
    of it) — the §7 serving trade-off the streaming benchmark reports.

    ``per_user_rows`` adds OPTIONAL per-user token buckets under the
    global one: a wave carrying a ``users`` breakdown ({user_id: rows})
    is admitted only if the global bucket AND every named user's bucket
    have room — one hot user saturates their own budget, not the
    service (the multi-tenant fairness knob of ROADMAP item 1).  The
    check is atomic: a wave refused on any bucket consumes nothing."""

    def __init__(self, max_inflight_rows: int,
                 per_user_rows: Optional[int] = None):
        if max_inflight_rows <= 0:
            raise ValueError(
                f"max_inflight_rows must be positive, got {max_inflight_rows}")
        if per_user_rows is not None and per_user_rows <= 0:
            raise ValueError(
                f"per_user_rows must be positive, got {per_user_rows}")
        self.max_inflight_rows = max_inflight_rows
        self.per_user_rows = per_user_rows
        self.inflight_rows = 0
        self.admitted = 0
        self.refused = 0
        self.user_inflight: Dict[Any, int] = {}
        self.user_refused: Dict[Any, int] = {}

    def try_admit(self, rows: int,
                  users: Optional[Dict[Any, int]] = None) -> bool:
        if self.inflight_rows + rows > self.max_inflight_rows:
            self.refused += 1
            return False
        if self.per_user_rows is not None and users:
            over = [u for u, r in users.items()
                    if self.user_inflight.get(u, 0) + r > self.per_user_rows]
            if over:
                self.refused += 1
                for u in over:
                    self.user_refused[u] = self.user_refused.get(u, 0) + 1
                return False
        self.inflight_rows += rows
        self.admitted += rows
        if users:
            for u, r in users.items():
                self.user_inflight[u] = self.user_inflight.get(u, 0) + r
        return True

    def release(self, rows: int,
                users: Optional[Dict[Any, int]] = None) -> None:
        self.inflight_rows -= rows
        assert self.inflight_rows >= 0, "released more rows than admitted"
        if users:
            for u, r in users.items():
                self.user_inflight[u] = self.user_inflight.get(u, 0) - r
                assert self.user_inflight[u] >= 0, \
                    f"released more rows than admitted for user {u!r}"


class StreamingDriver:
    """Double-buffered driver over one ``TrustSession``.

    ``depth`` is the number of dispatched-but-unconsumed waves allowed to
    remain in flight after ``dispatch()`` returns: ``0`` degenerates to the
    lockstep loop (dispatch, block, return), ``1`` is classic double
    buffering (the host packs wave k+1 while wave k serves), larger values
    queue deeper.  The caller's loop is::

        driver = StreamingDriver(session, depth=1,
                                 admission=AdmissionControl(4096))
        for wave in waves:
            driver.admit(rows)                  # blocks via consume()
            futs = [trust.op.add.then(...), ...]   # pack (enqueue)
            driver.dispatch(outputs=futs, rows=rows, rids=rids)
        driver.drain()

    Every consumed wave is stamped with a wall-clock ``consumed_at``;
    per-request latency is ``consumed_at - arrival`` of each rid riding
    the wave (the load generator owns the arrival clock).  ``events``
    records ``("dispatch", k)`` / ``("consume", k)`` in host order so
    tests can assert overlap actually happened (wave k+1 dispatched before
    wave k consumed).  A consumed wave's handle is not kept: ``stats()``
    reads running counts."""

    def __init__(self, session, depth: int = 1,
                 admission: Optional[AdmissionControl] = None,
                 headroom: float = 1.5, min_wave: int = 64,
                 max_wave: int = 65536):
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        self.session = session
        self.depth = depth
        self.admission = admission
        self.headroom = headroom
        self.min_wave = min_wave
        self.max_wave = max_wave
        self._inflight: deque = deque()
        self._next_wave = 0
        self.events: List[Tuple[str, int]] = []
        # running counts over the consumed waves, for stats()
        self._n_consumed = 0
        self._rows_consumed = 0
        self._n_overlapped = 0
        self._latency_sum_s = 0.0
        self._ema_cache: Dict[Any, float] = {}

    # -- pipeline core ------------------------------------------------------
    def dispatch(self, outputs: Any = None, rows: int = 0,
                 rids: Tuple[int, ...] = (),
                 on_consume: Optional[Callable] = None,
                 users: Optional[Dict[Any, int]] = None) -> WaveHandle:
        """Run ONE asynchronous engine round over everything pending on the
        session and park its handle.  Blocks only to keep the pipeline at
        ``depth`` in-flight waves (consuming oldest-first)."""
        h = WaveHandle(wave_id=self._next_wave, outputs=outputs, rows=rows,
                       rids=tuple(rids), on_consume=on_consume,
                       dispatched_at=time.perf_counter(), users=users)
        self._next_wave += 1
        before = self.session.wave_counter
        self.session.step(sync=False)
        if self.session.wave_counter > before:
            h.engine_wave = before
        self._inflight.append(h)
        self.events.append(("dispatch", h.wave_id))
        while len(self._inflight) > self.depth:
            self._consume_oldest()
        return h

    def admit(self, rows: int,
              users: Optional[Dict[Any, int]] = None) -> None:
        """Reserve ``rows`` admission tokens (and per-user tokens when a
        ``users`` breakdown is given), consuming in-flight waves
        oldest-first until the buckets have room.  No-op without admission
        control.  Raises if ``rows`` can never fit."""
        if self.admission is None:
            return
        if rows > self.admission.max_inflight_rows:
            raise ValueError(
                f"wave of {rows} rows exceeds the admission budget "
                f"{self.admission.max_inflight_rows} outright")
        pu = self.admission.per_user_rows
        if pu is not None and users:
            worst = max(users.values())
            if worst > pu:
                raise ValueError(
                    f"a user's {worst} rows exceed the per-user budget "
                    f"{pu} outright")
        while not self.admission.try_admit(rows, users):
            if not self._inflight:
                raise AssertionError(
                    "admission bucket too small for already-released rows")
            self._consume_oldest()

    def _consume_oldest(self) -> WaveHandle:
        h = self._inflight.popleft()
        with tracing.span(tracing.CONSUME, h.engine_wave):
            if h.outputs is not None:
                with tracing.span(tracing.WAIT, h.engine_wave):
                    jax.block_until_ready(_concrete(h.outputs))
            h.consumed_at = time.perf_counter()
            self.events.append(("consume", h.wave_id))
            # it overlapped if a later wave was dispatched before it was
            # consumed
            overlapped = self._next_wave > h.wave_id + 1
            if self.admission is not None:
                self.admission.release(h.rows, h.users)
            # refresh the EMA cache for wave_budget() only at QUIESCE
            # points: planner.observe() overwrites the staged demand scalar
            # at every dispatch, so with waves still in flight the staged
            # value belongs to an unfinished round and resolving it would
            # host-sync on it — the stall this driver exists to avoid
            if not self._inflight:
                for sig in list(self.session.planner._staged):
                    self._ema_cache[sig] = self.session.planner.ema(sig)
            if h.on_consume is not None:
                with tracing.span(tracing.CALLBACK, h.engine_wave):
                    h.on_consume(h)
        self._n_consumed += 1
        self._rows_consumed += h.rows
        self._n_overlapped += overlapped
        self._latency_sum_s += h.wave_latency_s
        return h

    def drain(self) -> List[WaveHandle]:
        """Consume every wave still in flight (end of stream); returns
        the waves this call consumed."""
        done = []
        while self._inflight:
            done.append(self._consume_oldest())
        return done

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    # -- resilience (DESIGN.md §14) -----------------------------------------
    def quiesce(self) -> None:
        """Bring the pipeline to a quiesce point: consume every in-flight
        wave AND flush anything still queued on the session.  After this,
        no wave is in flight and no trust has pending submissions — the
        only states a snapshot may capture (an in-flight wave's state
        transition is not yet observable, so checkpointing mid-flight
        would tear the acknowledged-op history)."""
        self.drain()
        if not self.session.quiesced():
            self.session.step()
            self.drain()

    def checkpoint(self, directory: str, step: Optional[int] = None) -> int:
        """Quiesce the pipeline, then snapshot the session
        (``TrustSession.checkpoint``) — the ONLY correct way to checkpoint
        a depth>0 streaming session.  Returns the snapshot step."""
        self.quiesce()
        return self.session.checkpoint(directory, step=step)

    def recover(self, failure, ckpt_dir: str, survivors=None,
                plan=None) -> int:
        """Standard failover sequence for a ``TrusteeFailure`` raised out
        of ``dispatch()``: discard the torn in-flight waves (their state
        never committed), re-entrust onto the survivors when a shard died,
        otherwise restore the last snapshot in place.  Returns the snapshot
        step to replay from; the caller re-submits every wave after it
        inside ``session.replaying()``."""
        # the torn waves' futures will never be fulfilled: drop the handles
        # without blocking on them (their programs may never have run)
        self._inflight.clear()
        if self.admission is not None:
            self.admission.inflight_rows = 0
            self.admission.user_inflight.clear()
        if getattr(failure, "kind", "kill") == "kill":
            self.session.re_entrust(
                [failure.shard] if failure.shard is not None else [],
                survivors=survivors, ckpt_dir=ckpt_dir, plan=plan)
        else:
            self.session.restore(ckpt_dir)
        snap = self.session._last_snapshot
        return snap[1] if snap is not None else 0

    # -- adaptive wave sizing ----------------------------------------------
    def wave_budget(self, trusts, fallback: Optional[int] = None) -> int:
        """Target row count for the next wave, from the planner demand EMA.

        The EMA tracks the max per-(client, trustee, lane) pair rows of
        recent waves; a wave of ``headroom * EMA * n_pairs`` rows keeps
        the expected hot-pair demand at the planned primary-block size
        (§5.3.1), so admitted waves neither drown the hot trustee nor
        under-fill the round.  Uses only telemetry cached at pipeline
        quiesce points (see ``_consume_oldest``); before any such point
        returns ``fallback`` (or ``max_wave``)."""
        trusts = [getattr(t, "trust", t) for t in trusts]
        if len(trusts) > 1:
            sig = ("mux", self.session._mux_signature(trusts[0]))
        else:
            sig = ("solo", trusts[0].token)
        ema = self._ema_cache.get(sig)
        if ema is None or ema <= 0:
            return fallback if fallback is not None else self.max_wave
        g = trusts[0].group
        n_pairs = g.n_clients * g.n_trustees * max(1, len(trusts))
        target = int(self.headroom * ema * n_pairs)
        return max(self.min_wave, min(self.max_wave, target))

    # -- telemetry ----------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Host-side pipeline telemetry over the consumed waves."""
        n = self._n_consumed
        out = {"waves": n,
               "rows": self._rows_consumed,
               "depth": self.depth,
               "overlapped_waves": self._n_overlapped,
               "mean_wave_latency_s": self._latency_sum_s / n if n else 0.0}
        if self.admission is not None:
            out["admitted_rows"] = self.admission.admitted
            out["admission_refusals"] = self.admission.refused
            if self.admission.user_refused:
                out["user_refusals"] = dict(self.admission.user_refused)
        return out


def _concrete(outputs):
    """Resolve TrustFutures inside an outputs pytree to their result trees
    (futures are fulfilled at dispatch; their leaves may still be
    computing — that is what block_until_ready is for)."""
    from ..core.trust import TrustFuture

    def leaf(x):
        return x.result() if isinstance(x, TrustFuture) else x
    if isinstance(outputs, (list, tuple)):
        return type(outputs)(leaf(x) for x in outputs)
    return leaf(outputs)
