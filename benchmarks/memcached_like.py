"""Memcached-shaped end-to-end service — paper Fig. 10/11 (§7).

A worker pipeline per round: parse request batch (stub) -> route -> issue
asynchronous delegation (apply_then, §7: "rather than sequentially process
each incoming request") -> order responses -> transmit (stub).  Stock
memcached analog: per-item locking backend (FetchRMW), synchronous.

Sweeps table size at 1/5/10% writes like Figs. 10-11.
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
    ap.add_argument("--dist", default="uniform", choices=["uniform", "zipf"])
    ap.add_argument("--tables", default="100,10000,1000000")
    ap.add_argument("--writes", default="1,5,10")
    ap.add_argument("--requests", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore, FetchRMWStore, conflict_ranks
    from repro.core.routing import sample_keys
    from benchmarks.common import Csv, bench, block

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    R = args.requests
    W = 8                                    # 32-byte values
    rng = np.random.default_rng(3)
    csv = Csv(["fig", "dist", "n_keys", "write_pct", "solution",
               "mops_wall"])
    csv.print_header()

    for n_keys in [int(x) for x in args.tables.split(",")]:
        for wr in [int(x) for x in args.writes.split(",")]:
            keys_np = sample_keys(rng, n_keys, R, args.dist)
            is_write = rng.random(R) < wr / 100.0
            keys = jnp.asarray(keys_np)
            vals = jnp.ones((R, W), jnp.float32)

            # ---- delegated memcached -------------------------------------
            st = DelegatedKVStore(mesh, n_keys, W, capacity=0)
            st.prefill(np.zeros((n_keys, W), np.float32))
            get_mask = jnp.asarray(~is_write)
            put_mask = jnp.asarray(is_write)
            order = np.argsort(rng.random(R))    # response-reorder stub

            def delegated_round():
                # state machine: parse (noop) -> async typed delegate per
                # op kind (schema-routed, masked via where=)
                futs = [st.trust.op.get.then(keys, where=get_mask),
                        st.trust.op.put.then(keys, vals, where=put_mask)]
                st.flush()                       # one fused channel round
                # order responses for the socket (paper §7 ordering step)
                resp = futs[0].result()["value"][jnp.asarray(order)]
                block(resp)

            dt = bench(delegated_round, iters=args.iters)
            csv.add("fig10/11", args.dist, n_keys, wr, "trust-memcached",
                    round(R / dt / 1e6, 3))

            # ---- stock analog (locking backend) ---------------------------
            wkeys_np = keys_np[is_write]
            ranks, rounds = conflict_ranks(wkeys_np, n_dev)
            rounds_c = max(1, min(rounds, 16))
            lock = FetchRMWStore(mesh, n_keys, W, rw_lock=True)
            lock.prefill(np.zeros((n_keys, W), np.float32))
            gk = jnp.where(jnp.asarray(~is_write), keys, -1)
            if is_write.any():
                wkeys, wvals_p, rk, _ = _pad_writes(
                    wkeys_np, vals, np.minimum(ranks, rounds_c - 1),
                    rounds_c, n_dev)
            else:
                wkeys = rk = None
                wvals_p = vals[:0]

            def stock_round():
                out = lock.get(gk)
                if wkeys is not None:
                    lock.put(wkeys, wvals_p, rk, rounds_c)
                block(lock.store.trust.state()["table"])

            dt = bench(stock_round, iters=max(1, args.iters - 2))
            dt = dt * (max(rounds, 1) / rounds_c)
            csv.add("fig10/11", args.dist, n_keys, wr, "stock-memcached",
                    round(R / dt / 1e6, 3))

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
