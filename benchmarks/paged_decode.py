"""Continuous-batching decode throughput over the delegated page table.

Two lanes over the SAME request trace (prompt/gen lengths, admission
heuristic, eviction semantics):

  * ``pack_impl=delegated`` — the real thing: ``PagedDecodeDriver``
    waves, each ONE fused engine round (free + alloc + append + lookup)
    through the Trust-owned ``DelegatedPageTable``.
  * ``pack_impl=host`` — the lock-free-because-single-threaded baseline:
    the same continuous-batching loop driving the ``SequentialPageTable``
    oracle directly on the host, no delegation rounds.

Columns: ``tokens_per_s`` (decode steps served per wall second — the
serving headline), ``pt_ops_per_s`` (page-table op rows per second),
``p50_us``/``p99_us`` (per-request latency, arrival to retirement).
Absolute numbers are machine-bound; CI gates the within-run
delegated/host ratio (``check_bench.py --normalize-impl host
--metric tokens_per_s``).
"""
from __future__ import annotations

import argparse
import time
from collections import deque

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--pages", type=int, default=128)
    ap.add_argument("--max-seqs", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--max-pages", type=int, default=8)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=2,
                    help="best-of repeats per lane, interleaved")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from jax.sharding import Mesh
    from repro.core import DelegatedPageTable, SequentialPageTable
    from repro.launch.paged_serve import DecodeRequest, PagedDecodeDriver
    from repro.launch.streaming import AdmissionControl
    from benchmarks.common import Csv

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    ps, mp = args.page_size, args.max_pages
    max_total = mp * ps

    def gen_requests(seed):
        rng = np.random.default_rng(seed)
        return [(int(rng.integers(2, max_total // 2)),
                 int(rng.integers(4, max_total // 2)), f"u{i % 4}")
                for i in range(args.requests)]

    def pages_for(tokens):
        return -(-max(tokens, 1) // ps)

    def run_delegated(trace):
        pt = DelegatedPageTable(mesh, args.pages, max_seqs=args.max_seqs,
                                page_size=ps, max_pages=mp,
                                capacity=4 * args.max_seqs)
        drv = PagedDecodeDriver(
            pt, depth=args.depth,
            admission=AdmissionControl(16 * args.max_seqs,
                                       per_user_rows=8 * args.max_seqs),
            max_active=args.max_seqs)
        reqs = [DecodeRequest(rid=i, prompt_len=p, gen_len=g, user=u)
                for i, (p, g, u) in enumerate(trace)]
        t0 = time.perf_counter()
        stats = drv.run(reqs)
        wall = time.perf_counter() - t0
        aud = pt.audit()
        assert aud["consistent"] and aud["leaked"] == 0, aud
        assert stats["completed"] == len(reqs), stats
        return wall, stats["tokens"], stats["pt_rows"], \
            [r.done_at - r.arrived for r in drv.finished if r.done_at >= 0]

    def run_host(trace):
        """The same continuous-batching loop against the sequential oracle
        (same trustee count, so per-owner capacity and eviction pressure
        match the delegated lane)."""
        t = n_dev
        pt = SequentialPageTable(args.pages, args.max_seqs, ps, mp, t)
        queue = deque()
        t0 = time.perf_counter()
        for i, (p, g, u) in enumerate(trace):
            queue.append([i, p, g, p + g, -1, 0, time.perf_counter()])
        active, free_seqs = {}, list(range(args.max_seqs - 1, -1, -1))
        owner_est, est, lat = {}, 0, []
        tokens = rows = 0

        def local_cap(o):
            return max(0, (args.pages - o + t - 1) // t)

        while queue or active:
            progressed = 0
            while queue and free_seqs and len(active) < args.max_seqs:
                req = queue[0]
                need = pages_for(req[3])
                if est + need > args.pages:
                    break
                pick = None
                for j in range(len(free_seqs) - 1, -1, -1):
                    o = free_seqs[j] % t
                    if owner_est.get(o, 0) + need <= local_cap(o):
                        pick = free_seqs.pop(j)
                        break
                if pick is None:
                    break
                queue.popleft()
                req[4] = pick
                est += need
                owner_est[pick % t] = owner_est.get(pick % t, 0) + need
                active[pick] = req
                pt.alloc(np.array([pick], np.int32),
                         np.array([pages_for(req[1])], np.int32))
                rows += 1
                progressed += 1
            decoding = sorted(active)
            if decoding:
                seqs = np.array(decoding, np.int32)
                poss = np.array([active[s][1] + active[s][5]
                                 for s in decoding], np.int32)
                pt.append(seqs, poss)
                pt.lookup(seqs)
                rows += 2 * len(decoding)
                tokens += len(decoding)
                progressed += len(decoding)
                for s in decoding:
                    req = active[s]
                    req[5] += 1
                    if req[5] >= req[2]:
                        del active[s]
                        pt.free(np.array([s], np.int32))
                        rows += 1
                        need = pages_for(req[3])
                        est -= need
                        o = s % t
                        owner_est[o] = max(0, owner_est.get(o, 0) - need)
                        free_seqs.append(s)
                        lat.append(time.perf_counter() - req[6])
            if not progressed:
                break
        assert not queue and not active, "host loop wedged"
        assert int(pt.used.sum()) == 0, "host lane leaked pages"
        return time.perf_counter() - t0, tokens, rows, lat

    csv = Csv(["experiment", "setting", "pack_impl", "tokens_per_s",
               "pt_ops_per_s", "p50_us", "p99_us"])
    csv.print_header()
    setting = (f"r{args.requests}_p{args.pages}x{ps}_mp{mp}"
               f"_s{args.max_seqs}")
    trace = gen_requests(seed=13)
    best = {}
    for _rep in range(max(1, args.repeats)):
        for impl, fn in (("delegated", run_delegated), ("host", run_host)):
            run = fn(trace)
            if impl not in best or run[0] < best[impl][0]:
                best[impl] = run
    for impl in ("delegated", "host"):
        wall, tokens, rows, lat = best[impl]
        csv.add("paged_decode", setting, impl,
                round(tokens / wall, 1), round(rows / wall, 1),
                round(float(np.percentile(lat, 50)) * 1e6, 1),
                round(float(np.percentile(lat, 99)) * 1e6, 1))
    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
