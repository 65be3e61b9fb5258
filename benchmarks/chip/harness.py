"""One run of one cell: set-up, the measured window, the drain, the
comparison with the plain reference, and (``--trace 1``) the trace.

The window drives the program's normal path at its defaults:
``DelegatedKVStore`` typed handles (``trust.op.<op>.then``, which
``get_then``/``put_then`` wrap, with ``where=`` marking inactive rows),
entrusted through a ``TrustSession(donate_states=True)`` and fed by a
``StreamingDriver`` at its default depth.  Requests go in from host
memory and responses count as done once they are on the host.

Closed loop: the next wave is submitted as soon as the driver returns.
Open loop: requests arrive on a Poisson schedule; whenever the driver
returns, the next wave takes every request that has arrived, up to
``wave_rows`` per client, the rest of its rows inactive.  When nothing
has arrived the harness drains the driver, so no response waits for
traffic, and sleeps until the next arrival.
"""
from __future__ import annotations

import gc
import resource
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

import opbytes
import traffic as tf
from kvref import KVReference

N_WARM = 3                       # full waves before the window
TRACE_SECONDS = 4.0              # longest traced window
TRACE_LEAD_S = 1.0               # traced waves before the traced window
DRAIN_LIMIT_S = 60.0             # open loop: how long the drain may take
KEEP_RESPONSE_BYTES = 256 << 20  # responses kept on the host for the check
FINAL_ROWS_BYTES = 256 << 20     # rows read back for the final-table check
READBACK_BUCKET = 1 << 16
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class CellSpec:
    name: str
    chips: int
    config: Dict
    traffic: Dict


@dataclass
class WaveRecord:
    ranges: np.ndarray           # (clients, 2) client-local request ranges
    rows: int                    # active requests
    required_bytes: int
    in_window: bool
    ops: List[str]               # op of each batch, in submission order
    arrived: Optional[range] = None   # open loop: arrival indices served
    t_dispatch: float = 0.0
    t_done: float = -1.0         # responses on the host
    got: Optional[List[Dict]] = None  # received fields, for kept waves


@dataclass
class Run:
    """What a metric reader reads."""
    setup_s: float
    window_s: float
    ops_done: int                        # requests answered in the window
    latencies_s: Optional[np.ndarray]    # open loop: one per request
    bytes_in_window: int                 # what the window's ops require
    peak_hbm_bytes_per_s: float
    trace: Optional[object] = None       # a tracecut.TraceReading


class Receipt:
    """The wave's outputs as handed to the driver: blocking on it blocks
    on every response array, inside the harness's ``wait`` span."""

    def __init__(self, futures):
        self.futures = futures

    def block_until_ready(self):
        import jax
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready([f.result() for f in self.futures])
        return self


RECEIVED_FIELDS = {"get": ("value",), "put": ("flag",), "add": ("value",),
                   "cas": ("value", "flag")}


def initial_table(data: tf.TableData, n_keys: int, n_trustees: int,
                  like, dtype):
    """The store's logical table, made on the device in one jitted call:
    the closed form of ``TableData.initial`` laid out owner-major, as the
    store keeps it (key k on trustee k % T at local row k // T; rows past
    the key space are zero), sharded as ``like``."""
    import jax
    import jax.numpy as jnp
    mask = jnp.uint32((1 << tf.VALUE_BITS) - 1)
    n_pad, width = like.shape

    # the seed's terms are arguments, so one compiled program serves
    # every seed
    def make(a, b, c, d):
        pos = jax.lax.iota(jnp.uint32, n_pad)
        n_local = n_pad // n_trustees
        key = (pos % n_local) * n_trustees + pos // n_local
        k = ((key * a + b) & mask).astype(jnp.float32)
        col = jax.lax.iota(jnp.uint32, width)
        cc = ((col * c + d) & mask).astype(jnp.float32)
        rows = jnp.where((key < n_keys)[:, None], k[:, None] + cc[None, :],
                         0.0)
        return rows.astype(dtype)
    return jax.jit(make, out_shardings=like.sharding)(
        *np.array(data.terms, np.uint32))


class Cell:
    """The store, its traffic and everything a run of one cell keeps."""

    def __init__(self, spec: CellSpec, seed: int, devices, control=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import DelegatedKVStore, TrustSession
        cfg, trf = spec.config, spec.traffic
        self.spec = spec
        self.seed = seed
        self.n_keys = int(cfg["n_keys"])
        self.width = int(cfg["value_width"])
        self.mode = cfg.get("mode", "shared")
        self.n_clients = spec.chips if self.mode == "shared" \
            else spec.chips - int(cfg["n_dedicated"])
        self.wave_rows = int(trf["wave_rows"])
        t = time.perf_counter()
        self.streams = [tf.ClientStream(
            np.random.default_rng([seed, 1, c]), self.n_keys,
            trf["op_shares"], trf["keys"], self.wave_rows,
            int(trf["distinct_waves"])) for c in range(self.n_clients)]
        slack = max(max(s.lane.values()) for s in self.streams)
        self.data = tf.make_table_data(seed, self.width, slack)
        log(f"traffic made in {time.perf_counter() - t:.3f} s: lanes "
            f"{self.streams[0].lane} per client, {self.n_clients} clients")
        self.check_capacity(int(cfg["capacity"]))
        self.dtype_bytes = np.dtype(cfg["dtype"]).itemsize
        dtype = jnp.bfloat16 if control == "bf16" else jnp.dtype(cfg["dtype"])
        mesh = Mesh(np.array(devices).reshape(cfg["mesh"]),
                    ("data", "model"))
        t = time.perf_counter()
        n_pad = -(-self.n_keys // self.n_clients) * self.n_clients
        table_bytes = n_pad * self.width * np.dtype(dtype).itemsize
        limit = (devices[0].memory_stats() or {}).get("bytes_limit")
        # the store makes its zero table on the default device before it
        # shards it: where that table would crowd one chip, make it on the
        # host
        host = jax.devices("cpu")[0] if limit and table_bytes > limit // 2 \
            else None
        with jax.default_device(host):
            self.store = DelegatedKVStore(
                mesh, self.n_keys, self.width, dtype=dtype,
                capacity=int(cfg["capacity"]), mode=self.mode,
                n_dedicated=int(cfg.get("n_dedicated", 0)),
                session=TrustSession(donate_states=True), name="kv")
        t1 = time.perf_counter()
        trust = self.store.trust
        table = initial_table(self.data, self.n_keys, self.store.t,
                              trust.trustee_state()["table"], dtype)
        trust.install_trustee_state({"table": table})
        jax.block_until_ready(trust.state())
        log(f"store built in {t1 - t:.3f} s, initial table made on the "
            f"device and installed in {time.perf_counter() - t1:.3f} s")
        self.ref = KVReference(
            self.n_keys, self.data, self.store.t,
            owner_last=self.mode == "shared",
            with_add="add" in self.streams[0].ops)
        self.waves: List[WaveRecord] = []        # in dispatch order
        self.unsent = 0     # open loop: arrivals never sent by the drain's end

    # -- traffic -----------------------------------------------------------
    def check_capacity(self, capacity: int) -> None:
        """Every full wave of the traffic sends at most ``capacity`` rows
        to each other chip's trustee, so nothing overflows into a second
        round (the configuration's guarantee of request order)."""
        if self.n_clients == 1 and self.mode == "shared":
            return
        t = self.n_clients if self.mode == "shared" else \
            self.spec.chips - self.n_clients
        worst = 0
        for c, s in enumerate(self.streams):
            for k in range(int(self.spec.traffic["distinct_waves"])):
                keys = np.concatenate(
                    [ln.keys[ln.mask] for ln in
                     s.lanes(k * self.wave_rows, (k + 1) * self.wave_rows)])
                cnt = np.bincount(keys % t, minlength=t)
                if self.mode == "shared":
                    cnt[c] = 0
                worst = max(worst, int(cnt.max()))
        log(f"most rows of one wave from one client to one trustee: {worst}"
            f" (capacity {capacity})")
        if worst > capacity:
            raise ValueError(f"capacity {capacity} is below the traffic's "
                             f"{worst} rows per (client, trustee) pair")

    def payload(self, client: int, lane: tf.Lane) -> Dict:
        """The lane as the reference reads it; ``rows`` are put-pool rows."""
        start = (lane.first + client * (tf.VALUE_POOL_ROWS
                                        // self.n_clients)) \
            % tf.VALUE_POOL_ROWS
        d = {"client": client, "op": lane.op, "keys": lane.keys,
             "mask": lane.mask}
        size = len(lane.keys)
        if lane.op in ("put", "cas"):
            d["rows"] = np.arange(start, start + size)
        if lane.op == "add":
            d["delta"] = self.data.add_pool[start:start + size]
        if lane.op == "cas":
            # even rows expect the key's initial row, odd rows a pool row
            exp = self.data.put_pool[(start + 1) % tf.VALUE_POOL_ROWS:][:size]
            exp = exp.copy()
            even = (lane.first + np.arange(size)) % 2 == 0
            act = even & lane.mask
            exp[act] = self.data.initial(lane.keys[act])
            d["expect"] = exp
        return d

    def wave_lanes(self, ranges: np.ndarray) -> List[Dict]:
        return [self.payload(c, ln)
                for c, (s, e) in enumerate(ranges)
                for ln in self.streams[c].lanes(int(s), int(e))]

    def submit(self, lanes: List[Dict]) -> List:
        op = self.store.trust.op
        pool = self.data.put_pool
        futs = []
        for d in lanes:
            k, m = d["keys"], d["mask"]
            if d["op"] == "get":
                f = op.get.then(k, where=m)
            elif d["op"] == "put":
                r = d["rows"]
                f = op.put.then(k, pool[r[0]:r[0] + len(r)], where=m)
            elif d["op"] == "add":
                f = op.add.then(k, d["delta"], where=m)
            else:
                r = d["rows"]
                f = op.cas.then(k, value=pool[r[0]:r[0] + len(r)],
                                expect=d["expect"], where=m)
            futs.append(f)
        return futs

    # -- the window ----------------------------------------------------------
    def run(self, seconds: float, t_start: float, trace_dir: Optional[str],
            rng_keep: np.random.Generator) -> Dict:
        import jax
        from repro.launch.streaming import StreamingDriver
        ann = jax.profiler.TraceAnnotation
        trf = self.spec.traffic
        open_loop = trf["loop"] == "open"
        drv = StreamingDriver(self.store.session)
        keep_cap = max(1, KEEP_RESPONSE_BYTES // max(1, self.response_bytes()))
        kept: List[WaveRecord] = []
        n_received = [0]

        def on_consume(rec: WaveRecord):
            # the client receives every response; a seeded reservoir of
            # waves keeps theirs for the comparison after the window
            def cb(h):
                with ann("bench.receive"):
                    receipt = h.outputs[0]
                    got = [{f: np.asarray(fut.result()[f])
                            for f in RECEIVED_FIELDS[op]}
                           for op, fut in zip(rec.ops, receipt.futures)]
                    rec.t_done = time.perf_counter()
                    receipt.futures = None
                    n_received[0] += 1
                    if len(kept) < keep_cap:
                        kept.append(rec)
                        rec.got = got
                    else:
                        j = int(rng_keep.integers(0, n_received[0]))
                        if j < keep_cap:
                            kept[j].got = None
                            kept[j] = rec
                            rec.got = got
            return cb

        def send(ranges: np.ndarray, in_window: bool,
                 arrived: Optional[range] = None) -> WaveRecord:
            lanes = self.wave_lanes(ranges)
            rows = {}
            for d in lanes:
                rows[d["op"]] = rows.get(d["op"], 0) + int(d["mask"].sum())
            rec = WaveRecord(ranges, sum(rows.values()), opbytes.wave_bytes(
                rows, self.width, self.dtype_bytes), in_window,
                [d["op"] for d in lanes], arrived)
            self.waves.append(rec)
            with ann("bench.submit"):
                futs = self.submit(lanes)
            rec.t_dispatch = time.perf_counter()
            with ann("bench.dispatch"):
                drv.dispatch(outputs=[Receipt(futs)], rows=rec.rows,
                             on_consume=on_consume(rec))
            return rec

        w = self.wave_rows
        full = lambda k: np.tile([k * w, (k + 1) * w], (self.n_clients, 1))
        for k in range(N_WARM):
            send(full(k), False)
        drv.drain()
        compiles: List[float] = []

        def on_event(name, *_a, **_k):
            if name in COMPILE_EVENTS:
                compiles.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(on_event)
        arrivals = None
        if open_loop:
            arrivals = tf.poisson_arrivals(
                np.random.default_rng([self.seed, 3]),
                float(trf["rate_ops_per_s"]), seconds)
        gc.collect()
        gc.freeze()
        k = N_WARM
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            # the device tracer starts recording some time after
            # start_trace returns: lead in with full waves, then drain
            lead_end = time.perf_counter() + TRACE_LEAD_S
            while time.perf_counter() < lead_end:
                send(full(k), False)
                k += 1
            drv.drain()
        base = k * w
        lateness: List[float] = []
        window = ann("bench.window")
        gc_pauses: List[float] = []
        gc_start = [0.0]

        def on_gc(phase, _info):
            if phase == "start":
                gc_start[0] = time.perf_counter()
            else:
                gc_pauses.append(time.perf_counter() - gc_start[0])
        gc.callbacks.append(on_gc)
        usage0 = resource.getrusage(resource.RUSAGE_SELF)
        window.__enter__()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        if not open_loop:
            while time.perf_counter() < t_end:
                send(full(k), True)
                k += 1
            with ann("bench.drain"):
                drv.drain()
            unsent = 0
        else:
            c = self.n_clients
            n_sched = len(arrivals)
            nxt = 0
            while nxt < n_sched:
                now = time.perf_counter()
                if now > t_end + DRAIN_LIMIT_S:
                    break
                here = int(np.searchsorted(arrivals, now - t0, "right"))
                if here > nxt:
                    end = min(here, nxt + w * c)
                    # arrival j is global request base * c + j
                    send(tf.client_ranges(base * c + nxt, base * c + end, c),
                         True, range(nxt, end))
                    nxt = end
                    continue
                if drv.inflight:
                    with ann("bench.drain"):
                        drv.drain()
                    continue
                with ann("bench.idle"):
                    due = t0 + float(arrivals[nxt])
                    time.sleep(max(0.0, due - time.perf_counter()))
                    lateness.append(time.perf_counter() - due)
            with ann("bench.drain"):
                drv.drain()
            unsent = n_sched - nxt
        t_last = time.perf_counter()
        usage1 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.remove(on_gc)
        window.__exit__(None, None, None)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        gc.unfreeze()
        jax.monitoring.unregister_event_duration_listener(on_event)
        n_compiles = sum(t0 <= t <= t_last for t in compiles)
        log(f"compiles inside the window: {n_compiles}")
        if lateness:
            log(f"generator lateness: mean {np.mean(lateness) * 1e3:.3f} ms,"
                f" max {np.max(lateness) * 1e3:.3f} ms over {len(lateness)} "
                f"sleeps")
        else:
            log("generator lateness: 0 (the loop never waited for arrivals)")
        win = [r for r in self.waves if r.in_window]
        self.log_steadiness(win, t0, t_last, usage0, usage1, gc_pauses)
        self.unsent = unsent
        out = {"t_end": t_end, "setup_s": t0 - t_start, "waves": win}
        if open_loop:
            lat = np.empty(n_sched)
            for r in win:
                a = r.arrived
                lat[a.start:a.stop] = r.t_done - (t0 + arrivals[a.start:a.stop])
            if unsent:
                lat[n_sched - unsent:] = t_last - (
                    t0 + arrivals[n_sched - unsent:])
            out["latencies"] = lat
        log(f"window: {len(win)} waves, {sum(r.rows for r in win)} requests,"
            f" {len(kept)} waves' responses kept for the check")
        return out

    @staticmethod
    def log_steadiness(win, t0, t_last, u0, u1, gc_pauses) -> None:
        """What the host did in the window besides serving: the longest
        gaps between wave dispatches, garbage collection, CPU time against
        wall time, page faults and involuntary context switches."""
        t = np.array([t0] + [r.t_dispatch for r in win])
        gaps = np.diff(t)
        top = np.argsort(gaps)[::-1][:3]
        log("longest gaps between dispatches: " + ", ".join(
            f"{gaps[i] * 1e3:.1f} ms at {t[i] - t0:.2f} s" for i in top)
            + f" (median {np.median(gaps) * 1e3:.2f} ms)" if len(gaps)
            else "no wave in the window")
        cpu = (u1.ru_utime - u0.ru_utime) + (u1.ru_stime - u0.ru_stime)
        log(f"host in the window: {t_last - t0:.3f} s wall, {cpu:.3f} s CPU,"
            f" {u1.ru_minflt - u0.ru_minflt} minor and "
            f"{u1.ru_majflt - u0.ru_majflt} major page faults, "
            f"{u1.ru_nivcsw - u0.ru_nivcsw} involuntary context switches,"
            f" {len(gc_pauses)} collections taking "
            f"{sum(gc_pauses) * 1e3:.1f} ms (longest "
            f"{max(gc_pauses, default=0) * 1e3:.1f} ms)")

    def response_bytes(self) -> int:
        """Host bytes of one wave's received responses."""
        n = 0
        for s in self.streams:
            for op, size in s.lane.items():
                per = {"get": self.width * self.dtype_bytes, "put": 4,
                       "add": self.width * self.dtype_bytes,
                       "cas": self.width * self.dtype_bytes + 4}[op]
                n += size * per
        return n

    # -- the comparison ------------------------------------------------------
    def compare(self, rng: np.random.Generator) -> Dict[str, Dict]:
        """Replay every wave through the reference, compare the responses
        of the waves kept, then read back a sample of the keys the run
        touched from every trustee."""
        wrong = {op: 0 for op in self.streams[0].ops}
        compared = {op: 0 for op in wrong}
        touched = np.zeros(self.n_keys, bool)
        missing = self.unsent
        last_put: List[np.ndarray] = []
        for r in self.waves:
            lanes = self.wave_lanes(r.ranges)
            got = r.got
            if r.t_done < 0:
                missing += r.rows
            want = self.ref.wave(lanes, answer=got is not None)
            for d in lanes:
                touched[d["keys"][d["mask"]]] = True
            last_put = [d["keys"][d["mask"]] for d in lanes
                        if d["op"] in ("put", "cas", "add")]
            if got is None:
                continue
            for d, g, x in zip(lanes, got, want):
                bad = np.zeros(len(d["keys"]), bool)
                for f, v in g.items():
                    e = x[f]
                    if v.shape != e.shape:
                        bad[:] = True
                        continue
                    bad |= (v.astype(e.dtype) != e).reshape(
                        len(bad), -1).any(axis=1)
                wrong[d["op"]] += int(bad.sum())
                compared[d["op"]] += len(bad)
        keys = np.nonzero(touched)[0]
        budget = max(1, FINAL_ROWS_BYTES // (self.width * 4))
        if len(keys) > budget:
            keys = np.union1d(rng.choice(keys, budget, replace=False),
                              np.concatenate(last_put or [[]]).astype(
                                  np.int64))
        final_wrong = int((self.read_back(keys) != self.ref.rows(keys))
                          .any(axis=1).sum())
        log("compared: " + ", ".join(
            f"{n} {op} rows ({wrong[op]} wrong)" for op, n in compared.items())
            + f", {missing} requests unanswered, {len(keys)} final rows of "
            f"{int(touched.sum())} touched keys")
        # answers: every op's response and acknowledgement compared, and
        # every request that got none
        return {"answers_wrong": {"value": sum(wrong.values()) + missing,
                                  "limit": 0},
                "final_rows_wrong": {"value": final_wrong, "limit": 0}}

    def read_back(self, keys: np.ndarray) -> np.ndarray:
        """Rows of ``keys`` as the trustees hold them: the table is
        owner-major (key k on trustee k % T at local row k // T), read
        shard by shard on the device that holds it."""
        import jax
        import jax.numpy as jnp
        table = self.store.trust.state()["table"]
        logical = self.store.trust.trustee_state()["table"]
        pad = table.shape[0] - logical.shape[0]
        t = self.store.t
        n_local = logical.shape[0] // t
        pos = (keys % t) * n_local + keys // t + pad
        out = np.zeros((len(keys), self.width), np.float32)
        take = jax.jit(lambda x, i: jnp.take(x, i, axis=0))
        for shard in table.addressable_shards:
            lo = shard.index[0].start or 0
            hi = shard.index[0].stop or table.shape[0]
            sel = np.nonzero((pos >= lo) & (pos < hi))[0]
            for a in range(0, len(sel), READBACK_BUCKET):
                part = sel[a:a + READBACK_BUCKET]
                idx = np.zeros(READBACK_BUCKET, np.int32)
                idx[:len(part)] = pos[part] - lo
                rows = take(shard.data, jax.device_put(idx, shard.device))
                out[part] = np.asarray(rows)[:len(part)]
        return out
