"""Fetch-and-add microbenchmark — paper Fig. 6a (uniform) / 6b (zipfian).

Clients repeatedly fetch-and-add a counter chosen from a set of N objects.
Competitors (TPU translations, DESIGN.md §2):

  trust      — synchronous delegation (one channel round per batch)
  async      — apply_then batching: 4 submitted batches ride one fused round
               (the paper's multiple-outstanding-requests client)
  mcs/mutex  — FetchRMW lock analog: fetch rows, RMW client-side, write back,
               one serialization round per conflicting writer (lock convoy)
  atomic     — scatter-add combine (hardware fetch-and-add instruction
               analog; commutative ops only)

Outputs MOPS (wall, CPU-simulated mesh) plus modeled v5e throughput from the
actual bytes each algorithm moves.  The reproduction claims are *relational*:
delegation flat vs. object count; locks collapse under congestion; parity
when uncongested (paper Fig. 6).
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dist", default="uniform", choices=["uniform", "zipf"])
    ap.add_argument("--objects", default="1,2,4,8,16,64,256,1024,8192")
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--mode", default="shared",
                    choices=["shared", "dedicated"],
                    help="trustee runtime: every core serves (shared) or a "
                         "reserved tail of cores serves the rest (dedicated)")
    ap.add_argument("--n-dedicated", type=int, default=0,
                    help="dedicated trustee cores (default: half the mesh)")
    from benchmarks.common import add_channel_args
    add_channel_args(ap)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (AtomicAddStore, DelegatedKVStore, FetchRMWStore,
                            conflict_ranks)
    from repro.core.routing import sample_keys
    from benchmarks.common import (Csv, V5E, bench, block, channel_kwargs,
                                   trustee_mode_kwargs)

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    mode_kw = trustee_mode_kwargs(args.mode, args.n_dedicated, n_dev)
    chan_kw = channel_kwargs(args, mode_kw)
    R = args.requests
    rng = np.random.default_rng(0)
    csv = Csv(["fig", "dist", "mode", "pack_impl", "n_objects", "solution",
               "mops_wall", "rounds", "bytes_per_op", "mops_v5e_model"])
    csv.print_header()

    for n_obj in [int(x) for x in args.objects.split(",")]:
        keys_np = sample_keys(rng, n_obj, R, args.dist)
        keys = jnp.asarray(keys_np)
        ones = jnp.ones((R, 1), jnp.float32)

        # --- delegation (sync) --------------------------------------------
        st = DelegatedKVStore(mesh, n_obj, 1, capacity=0, **chan_kw)
        st.prefill(np.zeros((n_obj, 1), np.float32))
        dt = bench(lambda: block(st.add(keys, ones)), iters=args.iters)
        # bytes/op over the channel: key+delta request + old-value response
        req_b, resp_b = 4 + 4, 4
        v5e = R / max((R * (req_b + resp_b)) / V5E["ici_bw"], 1e-9) / 1e6
        csv.add("fig6", args.dist, args.mode, args.pack_impl, n_obj,
                "trust", round(R / dt / 1e6, 3),
                1, req_b + resp_b, round(v5e, 1))

        # --- delegation (async, 4 outstanding batches fused) ---------------
        st2 = DelegatedKVStore(mesh, n_obj, 1, capacity=0, **chan_kw)
        st2.prefill(np.zeros((n_obj, 1), np.float32))
        q = R // 4

        def async_round():
            for i in range(4):
                st2.trust.op.add.then(keys[i * q:(i + 1) * q], ones[:q])
            st2.flush()
            block(st2.trust.state()["table"])

        dt = bench(async_round, iters=args.iters)
        csv.add("fig6", args.dist, args.mode, args.pack_impl, n_obj,
                "async", round(R / dt / 1e6, 3),
                1, req_b + resp_b, round(v5e, 1))

        # --- lock analog (fetch + serialize on conflicts) -------------------
        ranks, n_rounds = conflict_ranks(keys_np, n_dev)
        # cap rounds so single-object zipf cases terminate (the paper also
        # reports lock runs timing out under extreme congestion)
        capped = min(n_rounds, 64)
        lock = FetchRMWStore(mesh, n_obj, 1, pack_impl=args.pack_impl,
                             **mode_kw)
        lock.prefill(np.zeros((n_obj, 1), np.float32))
        ranks_j = np.minimum(ranks, capped - 1)

        def lock_round():
            lock.rmw(keys, lambda v, p: v + 1.0, ranks_j, capped)
            block(lock.store.trust.state()["table"])

        dt = bench(lock_round, iters=max(1, args.iters - 2))
        dt_scaled = dt * (n_rounds / capped)     # charge the uncapped convoy
        # lock bytes/op: value row travels both ways, per serialization round
        lock_bytes = 2 * 4 * n_rounds / max(1, n_rounds)
        v5e_lock = R / max(
            (R * 2 * 4) / V5E["ici_bw"] * n_rounds, 1e-9) / 1e6
        csv.add("fig6", args.dist, args.mode, args.pack_impl, n_obj,
                "mcs", round(R / dt_scaled / 1e6, 3),
                n_rounds, 8, round(v5e_lock, 1))

        # --- atomic scatter-add ---------------------------------------------
        at = AtomicAddStore(mesh, n_obj, 1, pack_impl=args.pack_impl,
                            **mode_kw)
        at.prefill(np.zeros((n_obj, 1), np.float32))
        dt = bench(lambda: block(at.add(keys, ones)), iters=args.iters)
        csv.add("fig6", args.dist, args.mode, args.pack_impl, n_obj,
                "atomic", round(R / dt / 1e6, 3),
                1, 8, round(v5e, 1))

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
