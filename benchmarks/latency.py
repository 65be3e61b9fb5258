"""Latency vs offered load — paper Fig. 7, with honest labels.

Measures the SERVICE ROUND latency of delegation vs the lock analog as the
request batch size (offered load) grows, at 64 objects (uniform) and 1e6
objects (zipf α=1) as in the paper.  Every request in a bulk round waits
for the whole round, so the per-request latency distribution at one load
IS the round-time distribution: ``round_us_p50``/``round_us_p99`` are
percentiles over individually-timed rounds (after untimed warmup), and
``wall_us_per_req`` is the amortized wall share (1/throughput) — NOT a
latency.  (The previous version of this file divided a p99 over ~15 trial
MEANS by the load and called it per-request p99; see git history.)

Latency(load) behavior to reproduce: locks are fast at low load but
collapse (convoy rounds) as load concentrates; delegation has a higher
floor (the channel round) but stays flat until trustee capacity saturates.

Stores run on the session/typed API (``session.step()`` rounds through the
DelegationEngine — the same path the streaming driver and the engine
battery exercise); per-request streaming tail latency under open/closed
arrivals lives in ``benchmarks/loadgen.py``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dist", default="uniform", choices=["uniform", "zipf"])
    ap.add_argument("--objects", type=int, default=0)   # 0 -> paper default
    ap.add_argument("--loads", default="64,128,256,512,1024,2048,4096,8192")
    ap.add_argument("--trials", type=int, default=15)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (DelegatedKVStore, FetchRMWStore, TrustSession,
                            conflict_ranks)
    from repro.core.routing import sample_keys
    from benchmarks.common import Csv, block

    n_obj = args.objects or (64 if args.dist == "uniform" else 1_000_000)
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    rng = np.random.default_rng(2)

    csv = Csv(["fig", "dist", "n_objects", "load_req_per_round", "solution",
               "round_us_p50", "round_us_p99", "wall_us_per_req",
               "throughput_mops"])
    csv.print_header()

    def timed_rounds(once, trials):
        """Individually time ``trials`` rounds after untimed warmup."""
        for _ in range(args.warmup):
            once()
        times = []
        for _ in range(trials):
            t0 = time.perf_counter()
            once()
            times.append(time.perf_counter() - t0)
        return np.array(times)

    def emit(solution, load, times, scale=1.0):
        times = times * scale
        csv.add("fig7", args.dist, n_obj, load, solution,
                round(np.percentile(times, 50) * 1e6, 1),
                round(np.percentile(times, 99) * 1e6, 1),
                round(times.mean() / load * 1e6, 2),
                round(load / times.mean() / 1e6, 3))

    for load in [int(x) for x in args.loads.split(",")]:
        keys_np = sample_keys(rng, n_obj, load, args.dist)
        keys = jnp.asarray(keys_np)
        ones = jnp.ones((load, 1), jnp.float32)

        ses = TrustSession()
        st = DelegatedKVStore(mesh, n_obj, 1, session=ses, name="kv",
                              capacity=2 * max(1, -(-load // n_dev)),
                              overflow="second_round", local_shortcut=False)
        st.prefill(np.zeros((n_obj, 1), np.float32))

        def trust_round():
            fut = st.add_then(keys, ones)
            ses.step()
            block(fut.result()["value"])

        emit("trust", load, timed_rounds(trust_round, args.trials))

        ranks, n_rounds = conflict_ranks(keys_np, n_dev)
        n_rounds_c = min(n_rounds, 32)
        lock = FetchRMWStore(mesh, n_obj, 1, session=TrustSession())
        lock.prefill(np.zeros((n_obj, 1), np.float32))
        rk = np.minimum(ranks, n_rounds_c - 1)

        def mutex_round():
            lock.rmw(keys, lambda v, p: v + 1.0, rk, n_rounds_c)
            block(lock.store.trust.state()["table"])

        # zipf convoys need n_rounds serialization rounds; only the first
        # n_rounds_c are executed, the rest are linearly extrapolated
        emit("mutex", load,
             timed_rounds(mutex_round, max(3, args.trials // 3)),
             scale=n_rounds / n_rounds_c)

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
