"""Concurrent key-value store — paper Fig. 8 (table-size sweep) and Fig. 9
(write-percentage sweep).

Server model: batched GET/PUT requests against a table of W-byte values.
  trust     — DelegatedKVStore (shards entrusted; paper §6.3 Trust16/24)
  rwlock    — sharded readers-writer lock analog: GETs are one parallel
              fetch round; PUTs serialize per conflicting key (dashmap /
              sharded-HashMap competitors)
  mutex     — every op (GET and PUT) serializes per conflicting key

5% writes, uniform + zipf, value 16 B (matches the paper's 8 B key / 16 B
value setup).
"""
from __future__ import annotations

import argparse

import numpy as np


def _pad_writes(wkeys_np, wvals, ranks, n_rounds, mult):
    """Pad a variable-length write subset to a multiple of the device count;
    padded rows get rank == n_rounds (never active -> dst -1)."""
    import numpy as _np
    import jax.numpy as _jnp
    n = len(wkeys_np)
    pad = (-n) % mult
    if pad == 0:
        return _jnp.asarray(wkeys_np), wvals[:n], _np.asarray(ranks), n_rounds
    wk = _np.concatenate([wkeys_np, _np.zeros(pad, wkeys_np.dtype)])
    rk = _np.concatenate([_np.asarray(ranks), _np.full(pad, n_rounds)])
    wv = _jnp.concatenate([wvals[:n], _jnp.zeros((pad,) + wvals.shape[1:],
                                                 wvals.dtype)], 0)
    return _jnp.asarray(wk), wv, rk, n_rounds


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fig", default="8", choices=["8", "9"])
    ap.add_argument("--dist", default="uniform", choices=["uniform", "zipf"])
    ap.add_argument("--tables", default="10,100,1000,10000,100000,1000000")
    ap.add_argument("--writes", default="5")
    ap.add_argument("--requests", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=4)
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
    from repro.core import DelegatedKVStore, FetchRMWStore, conflict_ranks
    from repro.core.routing import sample_keys
    from benchmarks.common import (Csv, V5E, bench, block, channel_kwargs,
                                   trustee_mode_kwargs)

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    mode_kw = trustee_mode_kwargs(args.mode, args.n_dedicated, n_dev)
    chan_kw = channel_kwargs(args, mode_kw)
    R = args.requests
    W = 4                      # 4 x f32 = 16-byte values
    rng = np.random.default_rng(1)

    if args.fig == "8":
        tables = [int(x) for x in args.tables.split(",")]
        writes = [int(args.writes)]
    else:
        tables = [int(args.tables.split(",")[0])]
        writes = [0, 5, 10, 25, 50, 100]

    csv = Csv(["fig", "dist", "mode", "pack_impl", "n_keys", "write_pct",
               "solution", "mops_wall", "write_rounds", "mops_v5e_model"])
    csv.print_header()

    for n_keys in tables:
        for wr in writes:
            keys_np = sample_keys(rng, n_keys, R, args.dist)
            is_write = rng.random(R) < wr / 100.0
            keys = jnp.asarray(keys_np)
            gk = jnp.where(jnp.asarray(~is_write), keys, -1)
            pk = jnp.where(jnp.asarray(is_write), keys, -1)
            vals = jnp.ones((R, W), jnp.float32)

            # --- delegated store (async GET + PUT fused in one round) ------
            st = DelegatedKVStore(mesh, n_keys, W, capacity=0, **chan_kw)
            st.prefill(np.zeros((n_keys, W), np.float32))

            get_mask = gk >= 0
            put_mask = pk >= 0

            def trust_round():
                # typed handles: routed by the schema, masked via where=
                st.trust.op.get.then(keys, where=get_mask)
                st.trust.op.put.then(keys, vals, where=put_mask)
                st.flush()
                block(st.trust.state()["table"])

            dt = bench(trust_round, iters=args.iters)
            # channel bytes: GET req 4 + resp 16; PUT req 20 + resp 0
            b_op = (1 - wr / 100) * 20 + (wr / 100) * 20
            v5e = R / max(R * b_op / V5E["ici_bw"], 1e-9) / 1e6
            csv.add(f"fig{args.fig}", args.dist, args.mode, args.pack_impl,
                    n_keys, wr, "trust",
                    round(R / dt / 1e6, 3), 0, round(v5e, 1))

            # --- rw-lock analog --------------------------------------------
            wranks, wrounds = conflict_ranks(keys_np[is_write], n_dev)
            wrounds = min(wrounds, 32)
            lock = FetchRMWStore(mesh, n_keys, W, rw_lock=True,
                                 pack_impl=args.pack_impl, **mode_kw)
            lock.prefill(np.zeros((n_keys, W), np.float32))
            if is_write.any():
                wkeys, wvals_p, wr_ranks, _ = _pad_writes(
                    keys_np[is_write], vals, np.minimum(wranks, wrounds - 1),
                    wrounds, n_dev)
            else:
                wkeys = wr_ranks = None
                wvals_p = vals[:0]

            def rw_round():
                out = lock.get(gk)           # reads: one parallel round
                if wkeys is not None:
                    lock.put(wkeys, wvals_p, wr_ranks, wrounds)
                block(lock.store.trust.state()["table"])

            dt = bench(rw_round, iters=max(1, args.iters - 2))
            rounds = 1 + (wrounds if is_write.any() else 0)
            v5e_l = R / max(
                (R * (1 - wr / 100) * 2 * W * 4
                 + R * (wr / 100) * 4 * W * 4 * max(1, wrounds))
                / V5E["ici_bw"], 1e-9) / 1e6
            csv.add(f"fig{args.fig}", args.dist, args.mode, args.pack_impl,
                    n_keys, wr, "rwlock",
                    round(R / dt / 1e6, 3), wrounds, round(v5e_l, 1))

            # --- mutex analog (everything serializes) -----------------------
            ranks, rounds = conflict_ranks(keys_np, n_dev)
            rounds_c = min(rounds, 32)
            mtx = FetchRMWStore(mesh, n_keys, W,
                                pack_impl=args.pack_impl, **mode_kw)
            mtx.prefill(np.zeros((n_keys, W), np.float32))
            rk = np.minimum(ranks, rounds_c - 1)

            def mutex_round():
                mtx.rmw(keys, lambda v, p: p, rk, rounds_c, payload=vals)
                block(mtx.store.trust.state()["table"])

            dt = bench(mutex_round, iters=max(1, args.iters - 2))
            dt_scaled = dt * (rounds / rounds_c)
            v5e_m = R / max(R * 4 * W * 4 * rounds / V5E["ici_bw"],
                            1e-9) / 1e6
            csv.add(f"fig{args.fig}", args.dist, args.mode, args.pack_impl,
                    n_keys, wr, "mutex",
                    round(R / dt_scaled / 1e6, 3), rounds, round(v5e_m, 1))

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
