"""Uninterpreted kernel sweeps on a TPU.

Runs the tiled delegation serve/pack Pallas kernels UNINTERPRETED (real
Mosaic lowering) over row-batch and block-size sweeps, printing us/round
and achieved bytes/s next to the closed-form roofline
(repro.launch.rooflines.delegation_serve_roofline).

On a host without a TPU there is nothing honest to measure — interpret-
mode wall-clock is Python, not kernel, time — so the script fails (exit 2)
there.  The CPU tests cover semantics (interpret-mode bit-identity,
tests/test_tiled_kernels.py) and compilation for a described chip
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rs", default="8192,32768,131072,524288",
                    help="row-batch sweep (comma-separated)")
    ap.add_argument("--keys", type=int, default=65536,
                    help="table lines per trustee shard")
    ap.add_argument("--width", type=int, default=4, help="value width")
    ap.add_argument("--blocks", default="256x512,512x512,512x1024",
                    help="BRxBK (serve) / BRxBS (pack) block sweep")
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"kernel_sweep: backend is {jax.default_backend()!r}, not "
              f"tpu — uninterpreted Pallas needs the chip", file=sys.stderr)
        return 2

    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.core.channel import make_grouping
    from repro.launch.rooflines import delegation_serve_roofline
    from benchmarks.common import bench, block

    rs = [int(x) for x in args.rs.split(",") if x]
    blocks = [tuple(int(v) for v in b.split("x"))
              for b in args.blocks.split(",") if b]
    k, w = args.keys, args.width
    print("kernel,rows,keys,width,br,bk_or_bs,us_per_round,model_us,"
          "bottleneck")
    for r in rs:
        rng = np.random.default_rng(3)
        table = jnp.asarray(rng.integers(0, 8, (k, w)).astype(np.float32))
        lane_np = rng.integers(0, 4, r).astype(np.int32)
        keys_np = rng.integers(0, k, r).astype(np.int32)
        g = make_grouping(jnp.asarray(lane_np * k + keys_np, jnp.int32))
        srt = lambda x: jnp.take(jnp.asarray(x), g.order, axis=0)
        keys_s, lane_s = srt(keys_np), srt(lane_np)
        value_s = srt(rng.integers(0, 8, (r, w)).astype(np.float32))
        expect_s = srt(rng.integers(0, 8, (r, w)).astype(np.float32))
        dst = jnp.asarray(rng.integers(0, 8, r).astype(np.int32))
        payload = jnp.asarray(rng.integers(0, 8, (r, w)).astype(np.float32))
        for br, bkbs in blocks:
            meta = g.tile_meta(block_rows=br)
            model = delegation_serve_roofline(r, k, w, br=br, bk=bkbs)

            def serve_round():
                block(kops.delegation_serve(
                    table, keys_s, lane_s, value_s, expect_s, g.seg_start,
                    meta.cont, br=meta.block_rows, bk=bkbs,
                    interpret=False))

            dt = bench(serve_round, iters=args.iters)
            model_us = max(model["compute_s"], model["memory_s"]) * 1e6
            print(f"serve,{r},{k},{w},{br},{bkbs},{dt*1e6:.1f},"
                  f"{model_us:.1f},{model['bottleneck']}")

            def pack_round():
                block(kops.delegation_pack(
                    dst, payload, 8, max(1, r // 8), impl="pallas",
                    interpret=False, br=br, bs=bkbs))

            dt = bench(pack_round, iters=args.iters)
            print(f"pack,{r},{k},{w},{br},{bkbs},{dt*1e6:.1f},,")
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
