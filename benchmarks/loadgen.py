"""Closed/open-loop load generator over the streaming serve driver.

Drives a delegated KV store through ``launch.streaming.StreamingDriver``
under live traffic and reports HONEST per-request tail latency — the
numbers ``latency.py`` used to fake (trial means divided by load):

  * **closed loop** — a fixed population of ``load`` outstanding requests
    per wave; a request's latency runs from the moment its wave is packed
    to the moment the wave's responses are consumed.  Throughput here is
    the saturation number (the generator never idles).
  * **open loop** — requests arrive on their own clock (exponential gaps
    at ``--rate`` req/s; ``burst`` modulates the rate 4x up/down in
    phases) regardless of service progress; latency runs from ARRIVAL to
    consumption, so queueing delay under overload is visible instead of
    hidden — the throughput-vs-latency framing of "On the Cost of
    Concurrency in Transactional Memory" (PAPERS.md).

Each (dist, load) trace is pregenerated once and replayed through every
driver mode, so ``lockstep`` (the pre-streaming serving loop: one
BLOCKING ``session.step()`` per wave, which resolves the per-trust stats
— a device_get sync — before the next wave may pack) and ``pipelined``
(``StreamingDriver`` depth ``--depth``: dispatch-ahead, block at
consumption) serve identical request streams at equal offered load.
The driver mode rides in the ``pack_impl`` CSV column so
``check_bench.py --impl pipelined --normalize-impl lockstep`` gates the
within-run ratio rather than machine-bound absolute numbers.

Stores use a STATIC channel capacity: the planner's EMA plan() resolves
device telemetry on the host and would stall the pipeline at pack time
(see launch/streaming.py); ``overflow=second_round`` keeps every request
served regardless of skew.

Both loops repeat ``--repeats`` times per mode, INTERLEAVED across
modes, and report each mode's best repeat (latency percentiles from that
same repeat): ambient load on a shared box drifts over the tens of
seconds one mode takes, and back-to-back single runs can flip the
within-run ratio the CI gate watches.

What each loop shows: the CLOSED loop measures saturation throughput,
where dispatch-ahead wins even on one core (typically 1.05-1.15x here)
— not by overlapping compute (work conservation forbids that on a
single core) but by eliminating the per-wave wakeup bubble: lockstep
sleeps inside its blocking step, so every wave boundary idles the core
for a scheduler wakeup before the host can pack again, while the
pipelined consume returns on already-finished work without sleeping.
The OPEN loop at the default ``--rate-frac`` (comfortably below
capacity) has BOTH modes at line rate — throughput parity by
construction — and makes the latency trade visible instead: pipelined
requests carry ~``depth`` waves of extra queueing (p99 ~1.3x here).
Near lockstep's capacity the story inverts hard (lockstep's effective
open-loop service rate is well below its closed-loop rate, so it falls
behind offered rates pipelined absorbs easily), but that window is
machine-sensitive, so CI gates the stable regimes: the closed-loop
throughput win and the open-loop p99 bound.

Columns: ``us_per_req`` = wall-clock per served request (1/throughput,
feeds the BENCH ops/s trajectory); ``p50_us``/``p99_us`` = per-request
latency percentiles; ``served_frac`` = served/offered (open loop drops a
trailing partial wave); ``dup_factor`` = mean per-wave requests per
distinct key — the combining headroom of the offered trace (DESIGN.md
§13).

``--users N`` splits the offered load across N tenants (per-user Zipf:
user u's share ∝ 1/(u+1)^1.1, so user 0 is the hot tenant), threads the
per-wave ``{user: rows}`` breakdown through the driver's admission
ledger — the pipelined mode runs with ``per_user_rows`` buckets, so the
hot user throttles against their OWN budget — and reports per-user p99
rows (``experiment=<arrival>_users``, top users by traffic) next to the
aggregate ones.

``--chaos N`` adds an ``experiment=chaos`` open-loop lane per mode: a
trustee shard is killed N waves into the timed run, the store recovers
onto the survivors from the last quiesce-point snapshot (every
``--chaos-snap-every`` waves), and every unsnapshotted wave replays.  A
request whose response was never delivered keeps its ORIGINAL arrival
time, so the recovery stall — re-entrust, restore, replay, and the
recompile for the shrunk mesh — lands in p99 instead of being laundered
by a post-recovery restart of the clock (DESIGN.md §14).
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dist", default="zipf", choices=["uniform", "zipf"])
    ap.add_argument("--objects", type=int, default=4096)
    ap.add_argument("--loads", default="512,2048",
                    help="wave sizes (outstanding requests per wave)")
    ap.add_argument("--reqs", type=int, default=16384,
                    help="requests per (load, mode, arrival) run")
    ap.add_argument("--modes", default="lockstep,pipelined")
    ap.add_argument("--depth", type=int, default=2,
                    help="in-flight waves for the pipelined driver")
    ap.add_argument("--arrivals", default="closed,open",
                    help="comma list of closed|open|burst")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop offered req/s (0 = --rate-frac x the "
                         "measured closed-loop lockstep throughput)")
    ap.add_argument("--rate-frac", type=float, default=0.75,
                    help="auto-rate headroom: fraction of the closed-loop "
                         "lockstep saturation throughput offered to BOTH "
                         "modes in open loop (well inside pipelined "
                         "capacity, so a mode that falls behind does so on "
                         "its own merits, not because the offered rate "
                         "already exceeded the machine)")
    ap.add_argument("--write-frac", type=float, default=0.1,
                    help="fraction of ADD waves (rest are GETs)")
    ap.add_argument("--warmup", type=int, default=3,
                    help="untimed compile/warmup waves per run")
    ap.add_argument("--repeats", type=int, default=3,
                    help="repeats per (arrival, mode), INTERLEAVED across "
                         "modes (lockstep, pipelined, lockstep, ...) with "
                         "best-of reporting — ambient load on a shared box "
                         "drifts over the ~tens of seconds one mode takes, "
                         "and back-to-back single runs can flip the "
                         "within-run ratio the CI gate watches")
    ap.add_argument("--users", type=int, default=0,
                    help="split traffic across this many tenants (Zipf "
                         "shares), admit through per-user buckets in the "
                         "pipelined mode, and report per-user p99 rows "
                         "(0 = off)")
    ap.add_argument("--chaos", type=int, default=0,
                    help="kill a trustee shard this many waves into each "
                         "run and recover onto the survivors (0 = off); "
                         "adds experiment=chaos rows whose p50/p99 include "
                         "the recovery stall (needs >= 2 devices)")
    ap.add_argument("--chaos-snap-every", type=int, default=8,
                    help="snapshot cadence (waves) for the chaos lane")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore, TrustSession
    from repro.core.routing import sample_keys
    from repro.launch.streaming import (AdmissionControl, StreamingDriver,
                                        WaveHandle, _concrete)
    from repro.runtime import EngineFailureInjector, TrusteeFailure
    from benchmarks.common import Csv

    class LockstepLoop:
        """The pre-streaming serving loop, driver-shaped for replay: one
        blocking ``session.step()`` per wave (its return value resolves the
        per-trust stats — a device_get the caller pays BEFORE packing the
        next wave), then the wave's responses.  This is the baseline the
        streaming driver replaces; a depth-0 ``StreamingDriver`` already
        runs ``step(sync=False)`` and would understate the pipelining win
        by eliding the very sync the driver exists to remove."""

        def __init__(self, ses):
            self.ses = ses

        def admit(self, rows, users=None):
            pass

        def dispatch(self, outputs=None, rows=0, on_consume=None,
                     users=None):
            h = WaveHandle(wave_id=0, outputs=outputs, rows=rows,
                           dispatched_at=time.perf_counter())
            self.ses.step()
            if outputs is not None:
                jax.block_until_ready(_concrete(outputs))
            h.consumed_at = time.perf_counter()
            if on_consume is not None:
                on_consume(h)

        def drain(self):
            pass

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    csv = Csv(["experiment", "setting", "pack_impl", "us_per_req",
               "p50_us", "p99_us", "served_frac", "dup_factor"])
    csv.print_header()

    modes = [m for m in args.modes.split(",") if m]
    arrivals = [a for a in args.arrivals.split(",") if a]
    depth = {"pipelined": max(1, args.depth)}

    def gen_trace(load, seed):
        """(op, keys, vals) per wave — identical across driver modes."""
        rng = np.random.default_rng(seed)
        n_waves = args.reqs // load
        waves = []
        for _ in range(n_waves):
            op = "add" if rng.random() < args.write_frac else "get"
            keys = jnp.asarray(sample_keys(rng, args.objects, load,
                                           args.dist))
            vals = jnp.ones((load, 1), jnp.float32) if op == "add" else None
            waves.append((op, keys, vals))
        return waves

    def gen_users(load, n_waves, seed):
        """Per-wave tenant ids, Zipf-shared across ``--users`` tenants
        (identical across driver modes, like the key trace)."""
        if not args.users:
            return None
        rng = np.random.default_rng(seed + 1)
        p = 1.0 / np.arange(1, args.users + 1) ** 1.1
        p /= p.sum()
        return [rng.choice(args.users, size=load, p=p)
                for _ in range(n_waves)]

    def wave_users(uw):
        if uw is None:
            return None
        ids, counts = np.unique(uw, return_counts=True)
        return {int(u): int(c) for u, c in zip(ids, counts)}

    def trace_dup(waves):
        """Mean per-wave requests per distinct key (each wave is one op,
        so distinct keys = distinct (op, key) pairs)."""
        rs = [k.shape[0] / max(1, len(np.unique(np.asarray(k))))
              for _op, k, _v in waves]
        return round(float(np.mean(rs)), 2)

    def build(load, mode):
        ses = TrustSession(donate_states=True)
        # static capacity: the EMA planner's plan() host-syncs staged
        # telemetry and would stall dispatch-ahead (launch/streaming.py)
        cap = 2 * max(1, -(-load // n_dev))
        st = DelegatedKVStore(mesh, args.objects, 1, session=ses, name="kv",
                              capacity=cap, overflow="second_round",
                              local_shortcut=False)
        st.prefill(np.zeros((args.objects, 1), np.float32))
        if mode == "lockstep":
            return st, LockstepLoop(ses)
        # per-user buckets: a single wave may be all one tenant (<= load
        # rows), so the bucket must admit at least one full wave; depth
        # waves of one tenant then exhaust their budget and throttle
        per_user = load * depth[mode] if args.users else None
        drv = StreamingDriver(
            ses, depth=depth[mode],
            admission=AdmissionControl(load * (depth[mode] + 1),
                                       per_user_rows=per_user))
        return st, drv

    def pack(st, op, keys, vals):
        return st.add_then(keys, vals) if op == "add" else st.get_then(keys)

    def warm(st, drv, load):
        """Untimed warmup covering BOTH op programs — a first-occurrence
        ADD wave mid-run would otherwise put its compile in the p99."""
        keys = jnp.zeros((load,), jnp.int32)
        vals = jnp.ones((load, 1), jnp.float32)
        for _ in range(max(1, args.warmup)):
            for op in ("get", "add"):
                drv.admit(load)
                drv.dispatch(outputs=pack(st, op, keys, vals), rows=load)
        drv.drain()

    def run_closed(load, mode, waves, uwaves=None):
        st, drv = build(load, mode)
        warm(st, drv, load)
        lat = []                           # (per-request latency s, count)
        ulat = {}                          # user -> [(latency s, count)]

        t0 = time.perf_counter()
        for w, (op, keys, vals) in enumerate(waves):
            users = wave_users(uwaves[w]) if uwaves is not None else None

            def consumed(h, users=users):
                wl = h.consumed_at - h.dispatched_at
                lat.append((wl, h.rows))
                for u, c in (users or {}).items():
                    ulat.setdefault(u, []).append((wl, c))

            drv.admit(load, users)
            drv.dispatch(outputs=pack(st, op, keys, vals), rows=load,
                         on_consume=consumed, users=users)
        drv.drain()
        wall = time.perf_counter() - t0
        return wall, lat, len(waves) * load, len(waves) * load, ulat

    def gen_arrivals(n, rate, burst, seed):
        """Arrival offsets (s from run start) at ``rate`` req/s; burst
        alternates 4x/0.25x rate in 8 phases (same mean rate)."""
        rng = np.random.default_rng(seed)
        gaps = rng.exponential(1.0 / rate, n)
        if burst:
            phase = (np.arange(n) * 8 // n) % 2
            gaps = gaps * np.where(phase == 0, 0.25, 4.0)
        return np.cumsum(gaps)

    def run_open(load, mode, waves, rate, burst, uwaves=None):
        st, drv = build(load, mode)
        warm(st, drv, load)
        n = len(waves) * load              # whole waves only
        arr = gen_arrivals(n, rate, burst, seed=99)
        lat = []
        ulat = {}

        t0 = time.perf_counter()
        for w, (op, keys, vals) in enumerate(waves):
            last = arr[(w + 1) * load - 1]  # wave departs when full
            wait = last - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            users = wave_users(uwaves[w]) if uwaves is not None else None
            drv.admit(load, users)
            wave_arr = arr[w * load:(w + 1) * load]
            wave_uw = uwaves[w] if uwaves is not None else None

            def consumed(h, wave_arr=wave_arr, wave_uw=wave_uw):
                done = h.consumed_at - t0
                lat.extend((done - a, 1) for a in wave_arr)
                if wave_uw is not None:
                    for a, u in zip(wave_arr, wave_uw):
                        ulat.setdefault(int(u), []).append((done - a, 1))

            drv.dispatch(outputs=pack(st, op, keys, vals), rows=load,
                         on_consume=consumed, users=users)
        drv.drain()
        wall = time.perf_counter() - t0
        return wall, lat, n, args.reqs, ulat

    def run_chaos_open(load, mode, waves, rate):
        """Open-loop run with a mid-trace trustee kill: snapshot every
        ``--chaos-snap-every`` waves at pipeline quiesce points, recover
        onto the survivors, replay the unsnapshotted suffix in order.
        State rolls back to the snapshot, so already-delivered waves
        re-commit their writes but record no second latency; a wave whose
        response never reached the generator keeps its ORIGINAL arrival
        time — the whole recovery stall lands in its latency."""
        st, drv = build(load, mode)
        ses = getattr(drv, "session", None) or drv.ses
        warm(st, drv, load)
        n = len(waves) * load
        arr = gen_arrivals(n, rate, burst=False, seed=99)
        lat = []
        ckdir = tempfile.mkdtemp(prefix="loadgen_chaos_")
        ses.install_injector(EngineFailureInjector(
            schedule={ses.wave_counter + max(1, args.chaos):
                      ("kill", len(jax.devices()) - 1)}))

        def snapshot():
            if hasattr(drv, "checkpoint"):
                drv.checkpoint(ckdir)     # quiesces the pipeline first
            else:
                ses.checkpoint(ckdir)     # lockstep quiesces every wave

        snapshot()
        since_snap = []         # (op, keys, vals, on_consume) since snap
        recovered = False
        t0 = time.perf_counter()
        for w, (op, keys, vals) in enumerate(waves):
            wave_arr = arr[w * load:(w + 1) * load]
            acked = [False]

            def consumed(h, wave_arr=wave_arr, acked=acked):
                if acked[0]:
                    return      # replay of an already-delivered wave
                acked[0] = True
                done = h.consumed_at - t0
                lat.extend((done - a, 1) for a in wave_arr)

            entry = (op, keys, vals, consumed)
            last = arr[(w + 1) * load - 1]
            wait = last - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            try:
                drv.admit(load)
                drv.dispatch(outputs=pack(st, op, keys, vals), rows=load,
                             on_consume=consumed)
            except TrusteeFailure as e:
                recovered = True
                if hasattr(drv, "recover"):
                    drv.recover(e, ckdir)
                else:
                    ses.re_entrust([e.shard], ckpt_dir=ckdir)
                with ses.replaying():
                    for rop, rkeys, rvals, rcb in since_snap + [entry]:
                        drv.admit(load)
                        drv.dispatch(outputs=pack(st, rop, rkeys, rvals),
                                     rows=load, on_consume=rcb)
                    drv.drain()
            since_snap.append(entry)
            if (w + 1) % args.chaos_snap_every == 0:
                snapshot()
                since_snap = []
        drv.drain()
        wall = time.perf_counter() - t0
        shutil.rmtree(ckdir, ignore_errors=True)
        if not recovered:
            raise SystemExit(f"--chaos {args.chaos}: kill never fired "
                             f"(only {len(waves)} waves at load {load})")
        return wall, lat, n, args.reqs, ses.last_stats().get("recovery", {})

    def report(experiment, setting, mode, wall, lat, served, offered, dup):
        per_req = np.repeat([l for l, _c in lat], [c for _l, c in lat])
        csv.add(experiment, setting, mode,
                round(wall / served * 1e6, 2),
                round(float(np.percentile(per_req, 50)) * 1e6, 1),
                round(float(np.percentile(per_req, 99)) * 1e6, 1),
                round(served / offered, 3), dup)
        return served / wall

    def report_users(experiment, setting, mode, ulat, served, dup):
        """Per-tenant latency rows (top tenants by traffic).  us_per_req
        here is the tenant's MEAN latency — per-tenant wall share is not
        well-defined when tenants interleave inside one wave."""
        by_rows = sorted(ulat.items(),
                         key=lambda kv: -sum(c for _l, c in kv[1]))
        for u, entries in by_rows[:8]:
            per_req = np.repeat([l for l, _c in entries],
                                [c for _l, c in entries])
            csv.add(f"{experiment}_users", f"{setting}/u{u}", mode,
                    round(float(np.mean(per_req)) * 1e6, 2),
                    round(float(np.percentile(per_req, 50)) * 1e6, 1),
                    round(float(np.percentile(per_req, 99)) * 1e6, 1),
                    round(len(per_req) / served, 3), dup)

    for load in [int(x) for x in args.loads.split(",")]:
        waves = gen_trace(load, seed=7)
        uwaves = gen_users(load, len(waves), seed=7)
        dup = trace_dup(waves)
        closed_tput = {}
        if "closed" in arrivals:
            best = {}
            for _rep in range(max(1, args.repeats)):
                for mode in modes:
                    run = run_closed(load, mode, waves, uwaves)
                    if mode not in best or run[0] < best[mode][0]:
                        best[mode] = run
            for mode in modes:
                wall, lat, served, offered, ulat = best[mode]
                closed_tput[mode] = report(
                    "closed", f"{args.dist}/load{load}", mode,
                    wall, lat, served, offered, dup)
                if ulat:
                    report_users("closed", f"{args.dist}/load{load}", mode,
                                 ulat, served, dup)
        for arrival in arrivals:
            if arrival == "closed":
                continue
            rate = args.rate or args.rate_frac * closed_tput.get("lockstep", 0)
            if rate <= 0:
                raise SystemExit("--rate required when closed mode not run")
            best = {}
            for _rep in range(max(1, args.repeats)):
                for mode in modes:
                    run = run_open(load, mode, waves, rate,
                                   burst=(arrival == "burst"), uwaves=uwaves)
                    if mode not in best or run[0] < best[mode][0]:
                        best[mode] = run
            for mode in modes:
                wall, lat, served, offered, ulat = best[mode]
                report(arrival, f"{args.dist}/load{load}_{arrival}", mode,
                       wall, lat, served, offered, dup)
                if ulat:
                    report_users(arrival, f"{args.dist}/load{load}_{arrival}",
                                 mode, ulat, served, dup)
        if args.chaos:
            if len(jax.devices()) < 2:
                raise SystemExit("--chaos needs >= 2 devices (set "
                                 "XLA_FLAGS=--xla_force_host_platform_"
                                 "device_count=8)")
            rate = args.rate or args.rate_frac * closed_tput.get("lockstep", 0)
            if rate <= 0:
                raise SystemExit("--chaos needs --rate or a closed-loop run")
            # one run per mode: the deterministic recovery stall dwarfs
            # ambient drift, so best-of-repeats would only launder it
            for mode in modes:
                wall, lat, served, offered, rec = run_chaos_open(
                    load, mode, waves, rate)
                report("chaos", f"{args.dist}/load{load}_chaos", mode,
                       wall, lat, served, offered, dup)
                print(f"# chaos {mode}: restores {rec.get('restores', 0)}, "
                      f"replayed_rounds {rec.get('replayed_rounds', 0)}, "
                      f"recovery_ms {rec.get('recovery_ms', 0.0):.1f}",
                      file=sys.stderr)

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
