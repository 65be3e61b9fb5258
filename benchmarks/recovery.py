"""Recovery benchmark: quiesce-point snapshot overhead and time-to-recover.

Two experiments feed the ``BENCH_recovery.json`` trajectory (DESIGN.md
§14):

  * **ckpt_overhead** — identical engine-round traces served with
    snapshots off and with ``TrustSession.checkpoint`` every
    ``--snap-every`` waves.  The gated metric is the WITHIN-RUN on/off
    rounds-per-second ratio (absolute round time is machine-bound): the
    snapshot path device_gets every registered state and writes the
    crc-checked atomic checkpoint, and that cost must stay a bounded
    fraction of the serving it protects.
  * **recover** — a trustee shard is killed mid-trace; the row records
    the wall time from the ``TrusteeFailure`` to the last replayed wave
    acked on the survivors (re-entrust + elastic restore onto the shrunk
    mesh + recompile + replay).  Absolute and machine-bound, so it is
    reported but not gated; the companion ``per_replayed_round`` row
    amortizes it over the replay set.

Rows print in run.py's ``us_per_round`` summarize schema.
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
    ap.add_argument("--objects", type=int, default=4096)
    ap.add_argument("--vw", type=int, default=1)
    ap.add_argument("--load", type=int, default=512,
                    help="requests per wave")
    ap.add_argument("--waves", type=int, default=24)
    ap.add_argument("--snap-every", type=int, default=4)
    ap.add_argument("--kill-wave", type=int, default=10,
                    help="timed wave at which the injected kill fires")
    ap.add_argument("--write-frac", type=float, default=0.5)
    ap.add_argument("--iters", type=int, default=3,
                    help="best-of repeats for the overhead experiment "
                         "(recover runs once: its recompile dominates and "
                         "repeats would just re-pay it)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore, TrustSession
    from repro.core.routing import sample_keys
    from repro.runtime import EngineFailureInjector, TrusteeFailure
    from benchmarks.common import Csv

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    csv = Csv(["experiment", "setting", "pack_impl", "us_per_round",
               "served_frac"])
    csv.print_header()

    rng = np.random.default_rng(11)
    waves = []
    for _ in range(args.waves):
        op = "add" if rng.random() < args.write_frac else "get"
        keys = jnp.asarray(sample_keys(rng, args.objects, args.load, "zipf"))
        vals = (jnp.ones((args.load, args.vw), jnp.float32)
                if op == "add" else None)
        waves.append((op, keys, vals))

    def build():
        ses = TrustSession(donate_states=True)
        cap = 2 * max(1, -(-args.load // n_dev))
        st = DelegatedKVStore(mesh, args.objects, args.vw, session=ses,
                              name="kv", capacity=cap,
                              overflow="second_round", local_shortcut=False)
        st.prefill(np.zeros((args.objects, args.vw), np.float32))
        return st, ses

    def serve(st, ses, op, keys, vals):
        fut = st.add_then(keys, vals) if op == "add" else st.get_then(keys)
        ses.step()
        jax.block_until_ready(list(fut.result().values()))

    def warm(st, ses):
        k = jnp.zeros((args.load,), jnp.int32)
        v = jnp.ones((args.load, args.vw), jnp.float32)
        serve(st, ses, "get", k, None)
        serve(st, ses, "add", k, v)

    # -- ckpt_overhead: rounds/s with snapshots off vs on -------------------
    def run_rounds(snap_every):
        st, ses = build()
        warm(st, ses)
        ckdir = tempfile.mkdtemp(prefix="recovery_bench_")
        best = float("inf")
        try:
            for _ in range(max(1, args.iters)):
                t0 = time.perf_counter()
                for w, (op, keys, vals) in enumerate(waves):
                    serve(st, ses, op, keys, vals)
                    # the blocking serve left the session quiesced — the
                    # only state a snapshot may capture
                    if snap_every and (w + 1) % snap_every == 0:
                        ses.checkpoint(ckdir)
                best = min(best, time.perf_counter() - t0)
        finally:
            shutil.rmtree(ckdir, ignore_errors=True)
        return best / len(waves)

    off = run_rounds(0)
    on = run_rounds(args.snap_every)
    csv.add("ckpt_overhead", f"snap{args.snap_every}", "off",
            round(off * 1e6, 2), 1.0)
    csv.add("ckpt_overhead", f"snap{args.snap_every}", "on",
            round(on * 1e6, 2), 1.0)

    # -- recover: kill -> re-entrust -> replay ------------------------------
    if n_dev < 2:
        print("# recover experiment skipped: needs >= 2 devices",
              file=sys.stderr)
        if args.out:
            csv.dump(args.out)
        return

    def run_recover():
        st, ses = build()
        warm(st, ses)
        ckdir = tempfile.mkdtemp(prefix="recovery_bench_")
        ses.install_injector(EngineFailureInjector(
            schedule={ses.wave_counter + args.kill_wave:
                      ("kill", n_dev - 1)}))
        ses.checkpoint(ckdir)
        since_snap = []
        recover_s = replayed = None
        try:
            w = 0
            while w < len(waves):
                op, keys, vals = waves[w]
                try:
                    serve(st, ses, op, keys, vals)
                except TrusteeFailure as e:
                    t0 = time.perf_counter()
                    ses.re_entrust([e.shard], ckpt_dir=ckdir)
                    with ses.replaying():
                        for rop, rkeys, rvals in since_snap + [(op, keys,
                                                                vals)]:
                            serve(st, ses, rop, rkeys, rvals)
                    recover_s = time.perf_counter() - t0
                    replayed = len(since_snap) + 1
                since_snap.append((op, keys, vals))
                w += 1
                if w % args.snap_every == 0:
                    ses.checkpoint(ckdir)
                    since_snap = []
        finally:
            shutil.rmtree(ckdir, ignore_errors=True)
        if recover_s is None:
            raise SystemExit(f"--kill-wave {args.kill_wave}: kill never "
                             f"fired (only {len(waves)} waves)")
        return recover_s, replayed

    rec_s, replayed = run_recover()
    csv.add("recover", f"kill_w{args.kill_wave}_snap{args.snap_every}", "",
            round(rec_s * 1e6, 2), 1.0)
    csv.add("recover", "per_replayed_round", "",
            round(rec_s / replayed * 1e6, 2), 1.0)

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
