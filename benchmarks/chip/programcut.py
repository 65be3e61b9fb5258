"""The program's own spans and scopes in a profiler trace.

The runtime writes host spans named ``trust.*`` (``jax.profiler``
``TraceAnnotation``s, each with a ``wave`` argument) and names the ops of
its round with device scopes (``jax.named_scope``: ``trust.fuse``,
``trust.pack``, ``trust.transmit``, ``trust.serve``, ``trust.respond``,
``kv.get``/``put``/``add``/``cas`` and ``kv.commit``); see
``src/repro/core/tracing.py``.  ``tracecut`` reads only the harness's own
``bench.*`` spans and names device ops by their HLO names.  This module
reads the rest, beside it, from the same ``.xplane.pb``:

* ``read`` returns the program's host spans and, for each device op, its
  scope path.  The TPU's op events name an op by its HLO instruction and
  carry none of its metadata, so the path comes from the compiled round's
  HLO text (``scopes_from_hlo``: instruction name to ``op_name``; a
  fusion carries the metadata of its root).  An op under no scope of the
  program, such as a copy the compiler added, counts as ``(none)``.
* ``reduce`` adds up, inside the window ``tracecut`` uses, the device time
  under each scope (an op counts toward every scope on its path, so
  ``trust.serve`` holds ``kv.commit``), the program's span time by name,
  the count of ``trust.build`` spans, the costliest ops named with their
  innermost scope (``kv.commit: broadcast_select_fusion f32[..]``), and
  the longest idle gaps labelled with the harness span and the innermost
  program span over their middle (``dispatch/trust.launch``).
* ``per_wave_ms`` turns a reading into the per-wave numbers (submit, step,
  serve, commit and channel time).

``python3 benchmarks/chip/programcut.py --config memcached16 --traffic
memcached_zipf --chips 1 --seed 7 --seconds 4`` runs one traced window of
a cell through the harness on the chip, with XLA dumping the round's
optimized HLO (and the persistent compilation cache off, so the round
compiles and is dumped), and prints both readings as one JSON line;
``--stall-ms`` lists the host stalls (gaps between dispatches) above that
length with the spans that hold them.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

import tracecut

PREFIXES = ("trust.", "kv.")
NONE = "(none)"
BUILD = "trust.build"
ROUND_MODULES = "jit_fused.*"             # the engine's round programs
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"')

Span = Tuple[str, float, float, int]      # (name, start_ns, dur_ns, wave)
Op = Tuple[str, float, float, str]        # (name, start_ns, dur_ns, scope path)


def scope_path(op_name: str) -> str:
    """The program's scopes on an ``op_name`` path, outermost first,
    joined by ``/``; ``""`` when none."""
    return "/".join(p for p in op_name.split("/") if p.startswith(PREFIXES))


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> scope path, from an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = scope_path(m.group(2))
    return out


@dataclass
class ProgramTrace:
    spans: List[Span]                    # the program's host spans
    devices: Dict[int, List[Op]]         # device id -> ops with scopes


def read(path: str, hlo_text: str = "") -> ProgramTrace:
    """The program's spans and scoped device ops from a ``.xplane.pb`` (or
    the newest under a ``jax.profiler.trace`` directory); ``hlo_text``, the
    compiled round's HLO, names each op's scopes."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    by_hlo = scopes_from_hlo(hlo_text)
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        m = tracecut.DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != tracecut.OPS_LINE:
                    continue
                for e in line.events:
                    name = tracecut.op_name(e.name)
                    ops.append((name, e.start_ns, e.duration_ns,
                                by_hlo.get(name.split(" ")[0], "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("trust."):
                        wave = dict(e.stats).get("wave", -1)
                        spans.append((e.name, e.start_ns, e.duration_ns,
                                      int(wave)))
    return ProgramTrace(spans, devices)


def window(raw: tracecut.RawTrace) -> Tuple[float, float]:
    """``tracecut.reduce``'s window in ns: the harness's ``window`` span,
    from the moment every device records."""
    win = [(s, s + d) for n, s, d in raw.host if n == tracecut.WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {tracecut.WINDOW} span, "
                         f"found {len(win)}")
    lo, hi = win[0]
    firsts = [min(s for _, s, _ in evs) for evs in raw.devices.values()
              if evs]
    if firsts:
        lo = min(max(lo, max(firsts)), hi)
    return lo, hi


@dataclass
class ProgramReading:
    n_waves: int
    n_devices: int
    scope_s: Dict[str, float]            # device s under each scope, per device
    span_s: Dict[str, float]             # the program's span s by name
    n_builds: int                        # trust.build spans in the window
    device_ops: List[Tuple[str, float]]  # "scope: op", s per device
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def _innermost(spans: List[Span], t: np.ndarray) -> List[str]:
    """For each time, the name of the latest-starting span around it."""
    out = []
    for x in t:
        best, start = "", -np.inf
        for n, s, d, _w in spans:
            if s <= x < s + d and s > start:
                best, start = n, s
        out.append(best)
    return out


def reduce(raw: tracecut.RawTrace, prog: ProgramTrace, n_waves: int,
           top: int = 10) -> ProgramReading:
    """The program's numbers over ``tracecut``'s window; ``n_waves`` is
    ``tracecut.reduce(raw).n_waves``."""
    lo, hi = window(raw)
    spans: Dict[str, float] = {}
    n_builds = 0
    for n, s, d, _w in prog.spans:
        if lo <= s < hi:
            spans[n] = spans.get(n, 0.0) + d * 1e-9
            n_builds += n == BUILD
    scope_s: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    for dev in sorted(prog.devices):
        for name, s, d, path in prog.devices[dev]:
            if not lo <= s < hi:
                continue
            parts = path.split("/") if path else [NONE]
            for p in set(parts):
                scope_s[p] = scope_s.get(p, 0.0) + d * 1e-9
            label = f"{parts[-1]}: {name}"
            ops[label] = ops.get(label, 0.0) + d * 1e-9
    n_dev = max(1, len(prog.devices))
    scope_s = {k: v / n_dev for k, v in scope_s.items()}
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if raw.devices:
        first = raw.devices[min(raw.devices)]
        iv = np.array([(s, s + d) for _, s, d in first]).reshape(-1, 2)
        b = tracecut.clip(tracecut.merge(iv), lo, hi)
        edges = np.concatenate([[lo], b.reshape(-1), [hi]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        longest = edges[np.argsort(edges[:, 0] - edges[:, 1],
                                   kind="stable")[:top]]
        harness = tracecut._label_gaps(longest, raw.host)
        inner = _innermost(prog.spans, longest.mean(axis=1))
        gaps = [(f"{h}/{p}" if p else h, float(g[1] - g[0]) * 1e-9)
                for h, p, g in zip(harness, inner, longest)]
    return ProgramReading(
        n_waves=n_waves, n_devices=n_dev, scope_s=scope_s, span_s=spans,
        n_builds=n_builds, device_ops=[(n, t / n_dev) for n, t in ranked],
        idle_gaps=gaps)


def per_wave_ms(r: ProgramReading) -> Dict[str, float]:
    """Per-wave milliseconds: ``submit`` and ``step`` (host spans),
    ``serve``, ``commit`` and ``channel`` (pack, transmit and respond:
    device time per chip).  A number with nothing to read is left out."""
    if not r.n_waves:
        return {}
    out = {}
    for key, name in (("submit", "trust.submit"), ("step", "trust.step")):
        if name in r.span_s:
            out[key] = r.span_s[name] / r.n_waves * 1e3
    for key, name in (("serve", "trust.serve"), ("commit", "kv.commit")):
        if name in r.scope_s:
            out[key] = r.scope_s[name] / r.n_waves * 1e3
    chan = [r.scope_s[n] for n in ("trust.pack", "trust.transmit",
                                   "trust.respond") if n in r.scope_s]
    if chan and r.n_devices > 1:
        out["channel"] = sum(chan) / r.n_waves * 1e3
    return out


def stalls(raw: tracecut.RawTrace, prog: ProgramTrace,
           min_s: float) -> List[Dict]:
    """Host stalls: gaps between successive ``bench.dispatch`` starts of at
    least ``min_s``, each with the longest harness and program spans that
    overlap it (name and overlap in ms)."""
    starts = np.sort([s for n, s, _ in raw.host
                      if n == tracecut.SPAN + "dispatch"])
    host = [(n, s, d) for n, s, d in raw.host if n != tracecut.WINDOW] \
        + [(n, s, d) for n, s, d, _w in prog.spans]
    out = []
    for a, b in zip(starts[:-1], starts[1:]):
        if (b - a) * 1e-9 < min_s:
            continue
        over = sorted(((min(b, s + d) - max(a, s), n) for n, s, d in host
                       if s < b and s + d > a), reverse=True)
        seen, top = set(), []
        for t, n in over:
            if n not in seen and len(top) < 4:
                seen.add(n)
                top.append([n, float(t) * 1e-6])
        out.append({"at_s": float(a - starts[0]) * 1e-9,
                    "gap_ms": float(b - a) * 1e-6, "spans": top})
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import tempfile
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--stall-ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    trace_dir = tempfile.mkdtemp(prefix="programcut-")
    hlo_dir = tempfile.mkdtemp(prefix="programcut-hlo-")
    # before JAX starts: dump the rounds' optimized HLO, where the scopes
    # are
    os.environ["XLA_FLAGS"] = " ".join([
        os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={hlo_dir}",
        f"--xla_dump_hlo_module_re={ROUND_MODULES}"]).strip()
    import jax
    import harness
    jax.config.update("jax_enable_compilation_cache", False)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        harness.log(f"needs {args.chips} TPU chips, found {devices}")
        return 2
    devices = devices[:args.chips]
    sys.path.insert(0, os.path.join(root, "src"))

    def load(*parts):
        with open(os.path.join(here, *parts)) as f:
            return json.load(f)
    spec = harness.CellSpec(f"{args.config}.{args.traffic}", args.chips,
                            load("configs", args.config + ".json"),
                            load("traffic", args.traffic + ".json"))
    cell = harness.Cell(spec, args.seed, devices)
    try:
        w = cell.run(args.seconds, t_start, trace_dir,
                     np.random.default_rng([args.seed, 5]))
        raw = tracecut.read_xplane(trace_dir)
        hlo = []
        for p in sorted(glob.glob(os.path.join(
                hlo_dir, "*after_optimizations.txt"))):
            with open(p) as f:
                hlo.append(f.read())
        prog = read(trace_dir, "\n".join(hlo))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        shutil.rmtree(hlo_dir, ignore_errors=True)
    base = tracecut.reduce(raw)
    r = reduce(raw, prog, base.n_waves)
    checks = cell.compare(np.random.default_rng([args.seed, 7]))
    print(json.dumps({
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "setup_s": w["setup_s"], "window_s": base.window_s,
        "n_waves": base.n_waves, "busy_s": base.busy_s,
        "host_ms": (tracecut.host_s_per_wave(base) or 0) * 1e3,
        "round_ms": base.busy_s / max(1, base.n_waves) * 1e3,
        "idle_share": (1 - base.busy_s / base.window_s) * 100,
        "all_to_all_ms": (base.all_to_all_s or 0) / max(1, base.n_waves)
        * 1e3,
        "bench_span_s": base.span_s, "round_modules": len(hlo),
        "per_wave_ms": per_wave_ms(r), "scope_s": r.scope_s,
        "span_s": r.span_s, "n_builds": r.n_builds,
        "device_ops": r.device_ops, "idle_gaps": r.idle_gaps,
        "stalls": stalls(raw, prog, args.stall_ms * 1e-3),
        "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
