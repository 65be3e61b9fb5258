"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload memcached16.zipf --seed 7 \\
        --seconds 51 --trace 0

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for; without a TPU, or with fewer chips, or with a device that
``peaks.json`` does not list, it exits nonzero before any result.  The
last line of standard output is the result object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, ``breakdown`` (traced
runs) and, last, ``checks``: each number compared with the reference,
beside its limit.  The same numbers end standard error.

Everything a cell is made of is data found by name: its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``)
and one reader per metric (``metrics/<metric>.py``, a ``read(run)`` that
returns the number or ``None`` where it finds nothing to read).

``--control bf16`` runs the program with a bfloat16 table, the control
that the comparison must fail; the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import tracecut  # noqa: E402
from harness import CellSpec, log  # noqa: E402


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(bench: Dict, workload: str) -> CellSpec:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return CellSpec(workload, int(w["chips"]),
                    load_json(os.path.join(ROOT, conf["file"])),
                    load_json(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json")))


def reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(devices) -> Dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def run(spec: CellSpec, seed: int, seconds: float, trace: bool, devices,
        peaks: Dict, metrics: List[Dict], t_start: float,
        control: Optional[str] = None) -> Dict:
    """Set up, measure, compare; return the result object.  Takes the
    devices as found by the caller, so a test can drive it on the CPU."""
    cell = harness.Cell(spec, seed, devices, control)
    window = min(seconds, harness.TRACE_SECONDS) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if trace else None
    try:
        w = cell.run(window, t_start, trace_dir,
                     np.random.default_rng([seed, 5]))
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        log(f"peak_bytes_in_use={peak}")
        reading = tracecut.reduce(tracecut.read_xplane(trace_dir)) \
            if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.perf_counter()
    checks = cell.compare(np.random.default_rng([seed, 7]))
    log(f"reference check took {time.perf_counter() - t:.3f} s")
    done = [r for r in w["waves"] if 0 <= r.t_done <= w["t_end"]]
    r = harness.Run(
        setup_s=w["setup_s"], window_s=window,
        ops_done=sum(x.rows for x in done),
        latencies_s=w.get("latencies"),
        bytes_in_window=sum(x.required_bytes for x in w["waves"]),
        peak_hbm_bytes_per_s=float(peaks["hbm_bytes_per_s"]),
        trace=reading)
    units = {m["name"]: m["unit"] for m in metrics}
    values = {}
    for m in metrics:
        v = reader(m["name"])(r)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted = (len(w["latencies"]) if w.get("latencies") is not None
                 else sum(x.rows for x in w["waves"]))
    out = {"correct": correct, "attempted": attempted,
           "failed": int(sum(c["value"] for c in checks.values())),
           "metrics": values,
           "device": {**device_info(devices), "memory_peak_bytes": peak}}
    if reading is not None:
        out["device"].update(busy_s=reading.busy_s,
                             window_s=reading.window_s)
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in reading.device_ops],
            "idle_gaps": [[n, s] for n, s in reading.idle_gaps]}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bf16",), default=None)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = resolve(bench, args.workload)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = [m for m in bench[group] if applies(m, args.workload)]

    import jax
    devices = jax.devices()
    info = device_info(devices)
    log(f"platform={info['platform']} device_kind={info['kind']} "
        f"device_count={info['count']}")
    if info["platform"] != "tpu":
        log("JAX found no TPU: this benchmark runs only on the chip")
        return 2
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if info["kind"] not in peaks:
        log(f"no peaks for device kind {info['kind']!r} in peaks.json")
        return 2
    if len(devices) < spec.chips:
        log(f"the cell asks for {spec.chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    devices = devices[:spec.chips]
    harness.keep_freed_host_memory()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache {cache}: {n_cached} entries at start")
    out = run(spec, args.seed, args.seconds, bool(args.trace), devices,
              peaks[info["kind"]], metrics, T_START, args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
