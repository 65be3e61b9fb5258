"""Benchmark harness — one function per paper table/figure.

Each benchmark runs in a child process of its own, in a reduced-size mode
so the suite completes on the CPU; pass --full for the paper-scale sweeps.
On the CPU a child sees 8 simulated devices (``run_in_subprocess`` forces
the host device count); on a TPU host it sees the real chips.  A chip
belongs to one process at a time, so this parent process does not import
JAX until every child has exited (the roofline table at the end is the
first thing here that touches it).
Prints ``name,us_per_call,derived`` CSV summary lines at the end.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

# allow "python benchmarks/run.py" from the repo root (script dir is on
# sys.path then, but the benchmarks package itself is not)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import artifact_path, run_in_subprocess

REDUCED = {
    "fetch_add_uniform": ("benchmarks.fetch_add",
                          ["--dist", "uniform", "--objects", "1,8,64,1024",
                           "--requests", "1024", "--iters", "3"]),
    "fetch_add_zipf": ("benchmarks.fetch_add",
                       ["--dist", "zipf", "--objects", "8,64,1024",
                        "--requests", "1024", "--iters", "3"]),
    "latency_uniform": ("benchmarks.latency",
                        ["--dist", "uniform", "--loads", "128,1024,4096",
                         "--trials", "5"]),
    "kv_store_fig8": ("benchmarks.kv_store",
                      ["--fig", "8", "--dist", "zipf",
                       "--tables", "100,10000", "--requests", "2048",
                       "--iters", "2"]),
    "kv_store_fig9": ("benchmarks.kv_store",
                      ["--fig", "9", "--dist", "uniform", "--tables", "1000",
                       "--writes", "5", "--requests", "2048", "--iters", "2"]),
    "memcached": ("benchmarks.memcached_like",
                  ["--dist", "zipf", "--tables", "10000", "--writes", "5",
                   "--requests", "2048", "--iters", "2"]),
    "channel_micro": ("benchmarks.channel_micro", ["--requests", "1024"]),
    "streaming": ("benchmarks.loadgen",
                  ["--dist", "zipf", "--objects", "4096", "--loads", "512",
                   "--reqs", "8192", "--arrivals", "closed,open"]),
    "recovery": ("benchmarks.recovery",
                 ["--objects", "2048", "--load", "256", "--waves", "16",
                  "--iters", "2"]),
    "paged_decode": ("benchmarks.paged_decode",
                     ["--requests", "32", "--max-seqs", "32",
                      "--repeats", "1"]),
}

FULL = {
    "fetch_add_uniform": ("benchmarks.fetch_add", ["--dist", "uniform"]),
    "fetch_add_zipf": ("benchmarks.fetch_add", ["--dist", "zipf"]),
    "latency_uniform": ("benchmarks.latency", ["--dist", "uniform"]),
    "latency_zipf": ("benchmarks.latency", ["--dist", "zipf"]),
    "kv_store_fig8_uniform": ("benchmarks.kv_store",
                              ["--fig", "8", "--dist", "uniform"]),
    "kv_store_fig8_zipf": ("benchmarks.kv_store",
                           ["--fig", "8", "--dist", "zipf"]),
    "kv_store_fig9_uniform": ("benchmarks.kv_store",
                              ["--fig", "9", "--dist", "uniform",
                               "--tables", "1000"]),
    "kv_store_fig9_zipf": ("benchmarks.kv_store",
                           ["--fig", "9", "--dist", "zipf",
                            "--tables", "1000000"]),
    "memcached_uniform": ("benchmarks.memcached_like",
                          ["--dist", "uniform"]),
    "memcached_zipf": ("benchmarks.memcached_like", ["--dist", "zipf"]),
    "channel_micro": ("benchmarks.channel_micro", []),
    "streaming": ("benchmarks.loadgen",
                  ["--dist", "zipf", "--objects", "65536",
                   "--loads", "512,2048", "--reqs", "32768",
                   "--arrivals", "closed,open,burst"]),
    "recovery": ("benchmarks.recovery",
                 ["--objects", "65536", "--load", "1024", "--waves", "32"]),
    "paged_decode": ("benchmarks.paged_decode",
                     ["--requests", "96", "--pages", "256",
                      "--max-seqs", "64", "--repeats", "2"]),
}


def summarize(name: str, stdout: str):
    """Extract (key, us_per_call, derived, fields) rows from a benchmark's
    CSV output.  ``fields`` keeps the parsed CSV row for the --json
    trajectory (benchmark x mode x pack_impl)."""
    lines = [l for l in stdout.strip().splitlines() if "," in l]
    if len(lines) < 2:
        return []
    header = lines[0].split(",")
    out = []
    for line in lines[1:]:
        parts = line.split(",")
        if len(parts) != len(header):
            continue
        row = dict(zip(header, parts))
        if "mops_wall" in row:
            mops = float(row["mops_wall"])
            us = 1.0 / mops if mops > 0 else float("inf")
            key = "/".join(str(row.get(k, "")) for k in
                           ("dist", "mode", "pack_impl", "n_objects",
                            "n_keys", "write_pct", "solution") if row.get(k))
            out.append((f"{name}:{key}", round(us, 3),
                        f"mops={row['mops_wall']}", row))
        elif "wall_us_per_req" in row:
            out.append((f"{name}:{row['dist']}/load{row['load_req_per_round']}"
                        f"/{row['solution']}",
                        float(row["wall_us_per_req"]),
                        f"round_p99={row['round_us_p99']}us", row))
        elif "us_per_req" in row:
            # streaming loadgen: us_per_req is wall share (1/throughput),
            # p50/p99 are honest per-request latency percentiles; the
            # driver mode (lockstep/pipelined) rides in pack_impl
            out.append((f"{name}:{row['experiment']}/{row['setting']}"
                        f"/{row['pack_impl']}",
                        float(row["us_per_req"]),
                        f"p50={row['p50_us']}us p99={row['p99_us']}us", row))
        elif "tokens_per_s" in row:
            # paged decode: throughput-first rows; us_per_call derives as
            # 1/tokens_per_s so the ops/s trajectory stays comparable
            tps = float(row["tokens_per_s"])
            out.append((f"{name}:{row['experiment']}/{row['setting']}"
                        f"/{row['pack_impl']}",
                        round(1e6 / tps, 3) if tps > 0 else float("inf"),
                        f"pt_ops={row['pt_ops_per_s']}/s "
                        f"p99={row['p99_us']}us", row))
        elif "us_per_round" in row:
            key = f"{name}:{row['experiment']}/{row['setting']}"
            if row.get("pack_impl"):
                key += f"/{row['pack_impl']}"
            out.append((key, float(row["us_per_round"]),
                        f"served={row['served_frac']}", row))
    return out


# benchmarks that understand the shared/dedicated trustee-mode switch
MODE_AWARE = ("benchmarks.fetch_add", "benchmarks.kv_store")
# benchmarks that understand --pack-impl / the overflow switches
PACK_AWARE = ("benchmarks.fetch_add", "benchmarks.kv_store",
              "benchmarks.channel_micro")
OVERFLOW_AWARE = ("benchmarks.fetch_add", "benchmarks.kv_store")
# benchmarks that understand --serve-impl (channel_micro's serve_hotpath
# experiment enumerates every serve impl itself)
SERVE_AWARE = ("benchmarks.fetch_add", "benchmarks.kv_store")


def write_bench_json(tag: str, args, summary) -> str:
    """Emit the perf-trajectory artifact: ops/s per benchmark row
    (benchmark x mode x pack_impl), for cross-PR baseline comparison.

    The artifact ACCUMULATES: each run appends one timestamped entry to
    ``entries`` instead of overwriting, so checked-in BENCH_*.json files
    carry the ops/s trajectory across PRs (the newest entry is last).
    Legacy single-run files ({"rows": ...}) are migrated in place."""
    import datetime
    import json
    rows = []
    for name, us, derived, fields in summary:
        failed = not us or us != us or us == float("inf")
        rows.append({"name": name,
                     # strict JSON: null, never NaN/Infinity, for failed rows
                     "us_per_call": None if failed else us,
                     "ops_per_s": 0.0 if failed else round(1e6 / us, 1),
                     "derived": derived,
                     "mode": fields.get("mode", args.mode),
                     # serve_hotpath rows carry the SERVE impl here (the
                     # benchmark's impl column is shared)
                     "pack_impl": fields.get("pack_impl", ""),
                     # engine_multi rows carry fused vs per_trust settings so
                     # the trajectory tracks the multiplexed-round speedup
                     "experiment": fields.get("experiment", ""),
                     "setting": fields.get("setting", "")})
        # streaming rows carry per-request latency percentiles so the
        # trajectory can gate tails (check_bench --metric p99_us), not just
        # throughput; paged-decode rows carry their native throughput pair
        # (check_bench --metric tokens_per_s)
        for k in ("p50_us", "p99_us", "tokens_per_s", "pt_ops_per_s"):
            if fields.get(k):
                rows[-1][k] = float(fields[k])
    entry = {"timestamp": datetime.datetime.now(datetime.timezone.utc)
             .strftime("%Y-%m-%dT%H:%M:%SZ"),
             "mode": args.mode, "full": bool(args.full), "rows": rows}
    path = artifact_path(f"BENCH_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    entries = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            entries = prev.get("entries", [prev] if "rows" in prev else [])
        except (json.JSONDecodeError, OSError):
            entries = []
    entries.append(entry)
    with open(path, "w") as f:
        json.dump({"tag": tag, "entries": entries}, f, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--mode", default="shared",
                    choices=["shared", "dedicated"],
                    help="trustee runtime for the mode-aware benchmarks "
                         "(fetch-add, kv-store); dedicated reserves trustee "
                         "cores and restricts the run to those benchmarks")
    ap.add_argument("--n-dedicated", type=int, default=0,
                    help="dedicated trustee cores (default: half the mesh)")
    ap.add_argument("--pack-impl", default="",
                    choices=["", "ref", "pallas", "both"],
                    help="forwarded to the pack-aware benchmarks "
                         "(channel_micro also accepts 'both')")
    ap.add_argument("--overflow", default="",
                    choices=["", "second_round", "drop", "defer"],
                    help="forwarded to the overflow-aware benchmarks; defer "
                         "engages the drain engine")
    ap.add_argument("--serve-impl", default="",
                    choices=["", "ref", "pallas", "masked"],
                    help="trustee serve path, forwarded to the serve-aware "
                         "benchmarks (kv-store, fetch-add)")
    ap.add_argument("--experiment", default="",
                    help="forwarded to channel_micro: run only the named "
                         "experiment (CI bench-smoke: serve_hotpath)")
    ap.add_argument("--json", action="store_true",
                    help="also write the ops/s trajectory to "
                         "benchmarks/artifacts/BENCH_<tag>.json")
    ap.add_argument("--tag", default="local",
                    help="tag for the --json artifact filename")
    args = ap.parse_args()
    table = FULL if args.full else REDUCED

    summary = []
    for name, (module, margs) in table.items():
        if args.only and args.only not in name:
            continue
        if args.mode == "dedicated" and module not in MODE_AWARE:
            continue
        if module in MODE_AWARE and args.mode != "shared":
            margs = margs + ["--mode", args.mode]
            if args.n_dedicated:
                margs = margs + ["--n-dedicated", str(args.n_dedicated)]
        if args.pack_impl and module in PACK_AWARE:
            impl = args.pack_impl
            if impl == "both" and module != "benchmarks.channel_micro":
                impl = "ref"
            margs = margs + ["--pack-impl", impl]
        if args.overflow and module in OVERFLOW_AWARE:
            margs = margs + ["--overflow", args.overflow]
        if args.serve_impl and module in SERVE_AWARE:
            margs = margs + ["--serve-impl", args.serve_impl]
        if args.experiment and module == "benchmarks.channel_micro":
            margs = margs + ["--experiment", args.experiment]
        print(f"=== {name} ({module}) ===", flush=True)
        try:
            out = run_in_subprocess(module, margs, devices=8, timeout=2400)
            print(out, flush=True)
            summary.extend(summarize(name, out))
        except Exception as e:                               # noqa: BLE001
            print(f"{name} FAILED: {e}", flush=True)
            summary.append((name, float("nan"),
                            f"FAILED {type(e).__name__}", {}))

    print("\n=== summary: name,us_per_call,derived ===", flush=True)
    for name, us, derived, _fields in summary:
        print(f"{name},{us},{derived}", flush=True)

    if args.json:
        path = write_bench_json(args.tag, args, summary)
        print(f"\nwrote perf trajectory to {path}", flush=True)

    failed = [n for n, us, _d, _f in summary if us != us]
    if failed:
        # exit nonzero so CI never uploads a green-but-garbage baseline
        print(f"\nFAILED benchmarks: {', '.join(failed)}", flush=True)
        sys.exit(1)

    # roofline table from dry-run artifacts, if present
    print("\n=== roofline (from dry-run artifacts) ===", flush=True)
    try:
        from benchmarks import roofline
        roofline.main(["--fmt", "csv"])
    except Exception as e:                                   # noqa: BLE001
        print(f"roofline unavailable: {e}", flush=True)


if __name__ == "__main__":
    main()
