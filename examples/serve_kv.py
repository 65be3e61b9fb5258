"""The paper's key-value store service (§6.3) end to end.

A batched GET/PUT server over a delegated table, with the async
(apply_then) pipeline of the memcached port (§7): parse -> route -> delegate
-> order responses -> reply.  Compares against the lock-analog backend under
a zipfian (hot-key) workload — the paper's headline scenario.

Run:  PYTHONPATH=src python examples/serve_kv.py [--requests 4096]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import (DelegatedKVStore, FetchRMWStore, conflict_ranks,
                        current_session)
from repro.core.routing import sample_keys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-keys", type=int, default=100_000)
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--write-pct", type=int, default=5)
    args = ap.parse_args()

    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(1, len(devs)), ("data", "model"))
    rng = np.random.default_rng(0)
    W = 4

    store = DelegatedKVStore(mesh, args.n_keys, W)
    store.prefill(rng.normal(size=(args.n_keys, W)).astype(np.float32))
    lock = FetchRMWStore(mesh, args.n_keys, W, rw_lock=True)
    lock.prefill(rng.normal(size=(args.n_keys, W)).astype(np.float32))

    def service_round(st, keys_np, is_write, backend):
        keys = jnp.asarray(keys_np)
        vals = jnp.ones((len(keys_np), W), jnp.float32)
        if backend == "trust":
            # typed handles (DESIGN.md §10): the schema routes the keys and
            # validates the rows; where= deactivates the other op's subset
            g = st.trust.op.get.then(keys, where=jnp.asarray(~is_write))
            st.trust.op.put.then(keys, vals, where=jnp.asarray(is_write))
            # session API: step() flushes EVERY registered trust's pending
            # batches — with more entrusted objects in flight they would all
            # ride this one multiplexed channel round (DESIGN.md §8)
            current_session().step()
            return g.result()["value"]
        gk = jnp.where(jnp.asarray(~is_write), keys, -1)
        out = st.get(gk)
        wk = keys_np[is_write]
        if len(wk):
            ranks, n = conflict_ranks(wk, len(devs))
            st.put(jnp.asarray(wk), vals[: len(wk)], ranks, min(n, 16))
        return out

    for backend, st in (("trust", store), ("rw-lock", lock)):
        # warmup/compile
        keys_np = sample_keys(rng, args.n_keys, args.requests, "zipf")
        is_write = rng.random(args.requests) < args.write_pct / 100
        jax.block_until_ready(service_round(st, keys_np, is_write, backend))
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            out = service_round(st, keys_np, is_write, backend)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        total = args.rounds * args.requests
        print(f"{backend:8s}: {total/dt/1e3:8.1f} kops "
              f"({dt/args.rounds*1e3:.1f} ms/round, zipf hot-key, "
              f"{args.write_pct}% writes)")


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
