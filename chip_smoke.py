"""Smoke run of the delegation runtime on a TPU.

Drives the main path once: a ``DelegatedKVStore`` at the store's default
width (``value_width=4``, f32) over 1,000,000 keys, entrusted through a
``TrustSession(donate_states=True)`` and fed by a depth-1
``StreamingDriver``, 16 waves of 16,384 Zipf-keyed requests.  Even waves
are the memcached lane (5% PUT, the rest GET); odd waves mix GET, PUT, ADD
and CAS.  Every response and the final table are compared with
``SequentialKVReference``; the capacity is sized so that no row overflows
into a second round, where that oracle is exact.

    python chip_smoke.py                # one chip: Pallas pack + serve, then ref
    python chip_smoke.py --four-chips   # 1x4 mesh: shared + combine, 4->3
                                        # failover replay, dedicated mode

The last line of standard output is ``{"ok": true, "device": {...}}``, and
only when every phase matched.  Without a TPU the script exits nonzero
before running anything.  Timings and memory it prints come from one run:
smoke output, not measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N_KEYS = 1_000_000          # the repo's largest full-lane KV table
WIDTH = 4                   # DelegatedKVStore's default value width
WAVE = 16_384               # requests per wave
N_WAVES = 16
WRITE_PCT = 5               # the memcached lane's write share


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def wave_mix(i: int, wave: int) -> dict:
    """Rows per op of wave ``i``, in submission (and serve-phase) order."""
    if i % 2 == 0:
        n_put = wave * WRITE_PCT // 100
        return {"get": wave - n_put, "put": n_put}
    n_get, n_put, n_add = wave // 2, wave // 8, wave // 4
    return {"get": n_get, "put": n_put, "add": n_add,
            "cas": wave - n_get - n_put - n_add}


def make_trace(seed: int, n_keys: int, wave: int, n_waves: int):
    """The initial table, each wave's op batches, the oracle's responses
    per wave and its final table.  Values are small integers in f32, so
    ADD sums are exact in any order.  A wave's ops apply in the serve's
    phase order: GET reads the wave-entry table, PUT commits before ADD,
    CAS compares against the post-ADD table."""
    from repro.core import SequentialKVReference
    from repro.core.routing import sample_keys
    rng = np.random.default_rng(seed)
    ints = lambda *shape: rng.integers(0, 8, shape).astype(np.float32)
    init = ints(n_keys, WIDTH)
    ref = SequentialKVReference(n_keys, WIDTH)
    ref.prefill(init)
    waves, expected = [], []
    for i in range(n_waves):
        batch, want = {}, {}
        for op, n in wave_mix(i, wave).items():
            keys = sample_keys(rng, n_keys, n, "zipf").astype(np.int32)
            vals = ints(n, WIDTH)
            if op == "get":
                batch[op] = (keys,)
                want[op] = ref.get(keys)
            elif op == "put":
                batch[op] = (keys, vals)
                ref.put(keys, vals)
            elif op == "add":
                batch[op] = (keys, vals)
                want[op] = ref.add(keys, vals)
            else:
                # half the expects hit the live value: both outcomes occur
                expect = np.where(rng.random((n, 1)) < 0.5, ref.table[keys],
                                  ints(n, WIDTH))
                batch[op] = (keys, expect, vals)
                want[op] = ref.cas(keys, expect, vals)
        waves.append(batch)
        expected.append(want)
    return init, waves, expected, ref.dump()


def submit(store, batch: dict) -> dict:
    import jax.numpy as jnp
    futs = {}
    for op, arrs in batch.items():
        a = [jnp.asarray(x) for x in arrs]
        if op == "get":
            futs[op] = store.get_then(a[0])
        elif op == "put":
            futs[op] = store.put_then(a[0], a[1])
        elif op == "add":
            futs[op] = store.add_then(a[0], a[1])
        else:
            futs[op] = store.cas_then(a[0], a[1], a[2])
    return futs


def round_program_text(session) -> str:
    """Compiled text of the engine's most recent round program."""
    raw, avals = session.last_exec
    jitted = next(j for j, fn, _ in session._cache.values() if fn is raw)
    return jitted.lower(*avals).compile().as_text()


def check_round(session, what: str, kernels: bool) -> None:
    """The round just run took no impl fallback and, for the Pallas
    configuration, its compiled program holds the kernels."""
    fb = {n: d["impl_fallback"] for n, d in session.last_stats().items()
          if n != "recovery"}
    check(bool(fb) and not any(fb.values()),
          f"{what}: impl fallback in last_stats: {fb}")
    line = f"[smoke] {what}: impl_fallback={fb}"
    if kernels:
        n = round_program_text(session).count("tpu_custom_call")
        check(n > 0, f"{what}: no tpu_custom_call in the round program")
        line += f", tpu_custom_call x{n} in the compiled round"
    print(line, flush=True)


def run_trace(store, waves, what: str, kernels: bool,
              checkpoint_at: int = -1, ckpt_dir: str = ""):
    """Drive ``waves`` through a depth-1 StreamingDriver.  The first wave
    of each mix runs alone (its compile) and has its round checked; the
    rest stream.  Returns the per-wave futures."""
    from repro.launch.streaming import AdmissionControl, StreamingDriver
    session = store.session
    drv = StreamingDriver(session, depth=1,
                          admission=AdmissionControl(2 * WAVE))
    futs = []

    def dispatch(batch):
        rows = sum(len(a[0]) for a in batch.values())
        drv.admit(rows)
        f = submit(store, batch)
        drv.dispatch(outputs=list(f.values()), rows=rows)
        futs.append(f)

    n_warm = min(2, len(waves))
    t0 = time.perf_counter()
    for i in range(n_warm):
        dispatch(waves[i])
        drv.drain()
        check_round(session, f"{what} wave {i}", kernels)
    t1 = time.perf_counter()
    for i in range(n_warm, len(waves)):
        if i == checkpoint_at:
            drv.checkpoint(ckpt_dir)
        dispatch(waves[i])
    drv.drain()
    t2 = time.perf_counter()
    steady = (t2 - t1) / max(1, len(waves) - n_warm)
    print(f"[smoke] {what}: first {n_warm} waves (compile + run) "
          f"{t1 - t0:.3f} s, then {steady * 1e3:.3f} ms per wave over "
          f"{len(waves) - n_warm} streamed waves", flush=True)
    return futs


def compare(store, futs, expected, final, what: str) -> None:
    n = 0
    for i, (f, want) in enumerate(zip(futs, expected)):
        for op, w in want.items():
            r = f[op].result()
            if op == "cas":
                check(np.array_equal(np.asarray(r["flag"]), w[0]),
                      f"{what}: wave {i} CAS flags differ from the oracle")
                w = w[1]
            check(np.array_equal(np.asarray(r["value"]), w),
                  f"{what}: wave {i} {op.upper()} values differ from the "
                  f"oracle")
            n += len(w)
    check(np.array_equal(store.dump(), final),
          f"{what}: final table differs from the oracle")
    print(f"[smoke] {what}: {len(futs)} waves, {n} checked responses and "
          f"the final table match SequentialKVReference", flush=True)


def state_devices(store, what: str, want: int) -> None:
    n = len(store.trust.state()["table"].sharding.device_set)
    print(f"[smoke] {what}: table sharding.device_set holds {n} devices",
          flush=True)
    check(n == want, f"{what}: state on {n} devices, expected {want}")


def one_chip(seed: int, device) -> None:
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore, TrustSession
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    init, waves, expected, final = make_trace(seed, N_KEYS, WAVE, N_WAVES)
    for impl in ("pallas", "ref"):
        kw = (dict(pack_impl="pallas", serve_impl="pallas", strict_impl=True)
              if impl == "pallas" else {})
        store = DelegatedKVStore(mesh, N_KEYS, WIDTH,
                                 session=TrustSession(donate_states=True),
                                 name="kv", capacity=WAVE,
                                 local_shortcut=False, **kw)
        store.prefill(init)
        what = f"1 chip, {impl}"
        futs = run_trace(store, waves, what, kernels=impl == "pallas")
        compare(store, futs, expected, final, what)
        del store, futs
    stats = device.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)


def four_chips(seed: int, devices) -> None:
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore, TrustSession
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    mesh = Mesh(np.array(devices[:4]).reshape(1, 4), ("data", "model"))
    init, waves, expected, final = make_trace(seed, N_KEYS, WAVE, N_WAVES)
    half = N_WAVES // 2
    # WAVE // 2 rows per (client, trustee) pair cover every client's share
    # on 4, 3 (after the failover) and 2 (dedicated) clients
    kw = dict(capacity=WAVE // 2, local_shortcut=False, pack_impl="pallas",
              serve_impl="pallas", strict_impl=True)

    with tempfile.TemporaryDirectory() as ckpt:
        store = DelegatedKVStore(mesh, N_KEYS, WIDTH,
                                 session=TrustSession(donate_states=True),
                                 name="kv", combine="ref", **kw)
        store.prefill(init)
        state_devices(store, "shared 1x4", 4)
        futs = run_trace(store, waves, "shared 1x4", kernels=True,
                         checkpoint_at=half, ckpt_dir=ckpt)
        compare(store, futs, expected, final, "shared 1x4")
        # shard 3 is lost after the mid-trace checkpoint: re-entrust the
        # snapshot onto the three survivors and replay the waves after it
        store.session.re_entrust(failed_shards=[3], ckpt_dir=ckpt)
        state_devices(store, "re-entrusted 4->3", 3)
        with store.session.replaying():
            futs = run_trace(store, waves[half:], "replay on 3 survivors",
                             kernels=True)
        compare(store, futs, expected[half:], final, "replay on 3 survivors")
        del store, futs

    store = DelegatedKVStore(mesh, N_KEYS, WIDTH,
                             session=TrustSession(donate_states=True),
                             name="kv", mode="dedicated", n_dedicated=2, **kw)
    store.prefill(init)
    state_devices(store, "dedicated 2+2", 4)
    futs = run_trace(store, waves, "dedicated 2+2", kernels=True)
    compare(store, futs, expected, final, "dedicated 2+2")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the delegated KV store on a TPU.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the initial table and the request trace")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip path on a 1x4 mesh: "
                         "shared mode with combining, a 4->3 failover "
                         "replay, and dedicated mode")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime import enable_compile_cache
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[smoke] device_kind={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache} ({warm} entries at start)", flush=True)
    try:
        if args.four_chips:
            four_chips(args.seed, devices)
        else:
            one_chip(args.seed, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
