"""Continuous-batching GQA decode over the delegated page table.

End-to-end wiring of DESIGN.md §15: a ``PagedDecodeDriver`` runs a
stream of requests through the Trust-owned page table — every wave is
ONE fused engine round (free + alloc + append + lookup) — and the two
model callbacks do real attention math against a real paged KV pool:

  on_prefill  writes the prompt's KV into the pages the alloc returned
  on_decode   runs one ``paged_decode_attention`` step per sequence,
              consuming the block-sparse page list the same round served

Prints tokens/s, page-table ops/s, tail latency and the conservation
audit (zero leaked pages).

Run:  PYTHONPATH=src python examples/paged_decode.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.core import DelegatedPageTable
from repro.launch.paged_serve import DecodeRequest, PagedDecodeDriver
from repro.launch.streaming import AdmissionControl
from repro.models import attention as att


def make_cfg():
    return ModelConfig(name="paged-demo", family="dense", n_layers=1,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=256)


def run_decode(mesh: Mesh, n_requests: int = 24, n_pages: int = 64,
               max_seqs: int = 16, page_size: int = 4, max_pages: int = 8,
               seed: int = 0, verbose: bool = False):
    cfg = make_cfg()
    rng = np.random.default_rng(seed)
    params = att.init_attention(jax.random.PRNGKey(seed), cfg, jnp.float32)
    pool = att.init_paged_kv_pool(cfg, n_pages, page_size, jnp.float32)
    pt = DelegatedPageTable(mesh, n_pages, max_seqs=max_seqs,
                            page_size=page_size, max_pages=max_pages,
                            capacity=128)
    max_total = max_pages * page_size
    # one fixed token-embedding stream per (seq slot, position): prefill
    # replays after an eviction re-derive identical KV from these
    xs = jnp.asarray(rng.normal(size=(max_seqs, max_total, cfg.d_model)),
                     jnp.float32)
    state = {"pool": pool, "ys": [], "kv_writes": 0}
    step = jax.jit(lambda x, pos, pool, tbl: att.paged_decode_attention(
        params, x, pos, pool, tbl, cfg))

    def write_kv(seqs, positions, chains):
        x = xs[jnp.asarray(seqs), jnp.asarray(positions)]
        y, state["pool"] = step(x, jnp.asarray(positions, jnp.int32),
                                state["pool"], jnp.asarray(chains, jnp.int32))
        state["kv_writes"] += len(seqs)
        return y

    def on_prefill(seqs, lengths, chains):
        # ragged prompt lengths: step position-by-position (toy-sized)
        for t in range(int(np.max(lengths))):
            live = lengths > t
            if not live.any():
                break
            write_kv(seqs[live], np.full(int(live.sum()), t, np.int32),
                     chains[live])

    def on_decode(seqs, positions, chains):
        state["ys"].append(np.asarray(
            write_kv(seqs, positions, chains)).sum())

    drv = PagedDecodeDriver(pt, depth=2,
                            admission=AdmissionControl(512,
                                                       per_user_rows=256),
                            on_prefill=on_prefill, on_decode=on_decode,
                            max_active=max_seqs)
    reqs = [DecodeRequest(rid=i,
                          prompt_len=int(rng.integers(2, max_total // 2)),
                          gen_len=int(rng.integers(4, max_total // 2)),
                          user=f"u{i % 4}")
            for i in range(n_requests)]
    t0 = time.perf_counter()
    stats = drv.run(reqs)
    wall = time.perf_counter() - t0
    stats["wall_s"] = wall
    stats["tokens_per_s"] = stats["tokens"] / wall if wall else 0.0
    stats["pt_ops_per_s"] = stats["pt_rows"] / wall if wall else 0.0
    stats["kv_writes"] = state["kv_writes"]
    stats["y_checksum"] = float(np.sum(state["ys"])) if state["ys"] else 0.0
    stats["audit"] = pt.audit()
    if verbose:
        print(f"requests      {stats['completed']}/{n_requests} completed, "
              f"{stats['failed']} failed, {stats['restarts']} restarts")
        print(f"decode        {stats['tokens']} tokens in {wall:.2f}s "
              f"({stats['tokens_per_s']:.1f} tok/s)")
        print(f"page table    {stats['pt_rows']} op rows "
              f"({stats['pt_ops_per_s']:.1f} rows/s), "
              f"p50 {stats['p50_ms']:.1f}ms  p99 {stats['p99_ms']:.1f}ms")
        print(f"kv pool       {stats['kv_writes']} writes, "
              f"y checksum {stats['y_checksum']:+.4f}")
        a = stats["audit"]
        print(f"audit         consistent={a['consistent']} "
              f"leaked={a['leaked']} allocated={a['allocated']} "
              f"evictions={a['evictions']}")
    return stats


def main():
    mesh = Mesh(np.array(jax.devices()).reshape(1, -1), ("data", "model"))
    stats = run_decode(mesh, verbose=True)
    a = stats["audit"]
    ok = (a["consistent"] and a["leaked"] == 0 and a["allocated"] == 0
          and stats["failed"] == 0)
    print("\nzero leaked pages, every request served:", bool(ok))
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
