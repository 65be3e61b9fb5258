"""Reduction of a profiler trace to the benchmark's per-layer numbers.

The traced run wraps its window in host spans that the harness writes
with ``jax.profiler.TraceAnnotation`` (names below).  The device side is
read from the device planes' op line.  ``read_xplane`` turns the
profiler's ``.xplane.pb`` into plain event lists; ``reduce`` computes
everything from those lists, so it can be checked on a small trace kept
as data (``tests/data/``).

* window: the harness's ``window`` span, from the moment every device's
  ops are recorded (the device tracer starts some time after the trace
  does).  Waves are the ``dispatch`` spans that start in it.
* busy: the union of the device's op intervals inside the window.
* idle gaps: the complement of busy inside the window, on the first
  device, each labelled with the harness span the host was in at the
  gap's middle (the innermost one, by ``SPAN_PRIORITY``).
* all-to-all: the summed duration of ops whose name holds ``all-to-all``
  (HLO text) or ``all_to_all`` (the TPU's op names, ``all_to_all.62``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

SPAN = "bench."
WINDOW = SPAN + "window"
# innermost first: the label a gap gets when several spans cover it
SPAN_PRIORITY = ("wait", "receive", "submit", "idle", "dispatch", "drain")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ALL_TO_ALL = re.compile(r"all[-_]to[-_]all")

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
# a device op event is named by its HLO text: "%name = f32[..]{layout} op("
_HLO = re.compile(r"^%?([^\s=]+) = (\w+\[[^\]]*\])?")


def op_name(text: str) -> str:
    """An op's instruction name and result shape, from its HLO text."""
    m = _HLO.match(text)
    if not m:
        return text[:96]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


@dataclass
class RawTrace:
    host: List[Event]                    # the harness's spans
    devices: Dict[int, List[Event]]      # device id -> op events


@dataclass
class TraceReading:
    window_s: float
    n_waves: int
    n_devices: int
    busy_s: float                        # mean over devices
    span_s: Dict[str, float]             # summed host span time by name
    device_ops: List[Tuple[str, float]]  # name, s per device, most first
    all_to_all_s: Optional[float]        # per device; None when none ran
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def read_xplane(path: str) -> RawTrace:
    """Plain event lists from a profiler ``.xplane.pb`` (or the newest one
    under a ``jax.profiler.trace`` directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(SPAN))
    return RawTrace(host, devices)


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of (start, end) rows, as sorted disjoint rows."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.nonzero(new)[0]
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], 1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _label_gaps(gaps: np.ndarray, host: List[Event]) -> List[str]:
    mid = gaps.mean(axis=1)
    labels = np.full(len(gaps), "none", dtype=object)
    done = np.zeros(len(gaps), bool)
    for name in SPAN_PRIORITY:
        iv = np.array([(s, s + d) for n, s, d in host if n == SPAN + name])
        if not len(iv):
            continue
        iv = iv[np.argsort(iv[:, 0])]
        j = np.searchsorted(iv[:, 0], mid, side="right") - 1
        inside = (j >= 0) & (mid < iv[np.maximum(j, 0), 1]) & ~done
        labels[inside] = name
        done |= inside
    return list(labels)


def reduce(raw: RawTrace, top: int = 10) -> TraceReading:
    win = [(s, s + d) for n, s, d in raw.host if n == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
    lo, hi = win[0]
    if not raw.devices:
        raise ValueError("the trace holds no TPU device plane")
    # the window counts from the moment every device's ops are recorded
    firsts = [min(s for _, s, _ in evs) for evs in raw.devices.values()
              if evs]
    if firsts:
        lo = min(max(lo, max(firsts)), hi)
    spans: Dict[str, float] = {}
    n_waves = 0
    for n, s, d in raw.host:
        if n == WINDOW or not lo <= s < hi:
            continue
        key = n[len(SPAN):]
        spans[key] = spans.get(key, 0.0) + d * 1e-9
        n_waves += key == "dispatch"
    busy, ops, a2a = [], {}, 0.0
    first_busy = None
    for dev in sorted(raw.devices):
        evs = raw.devices[dev]
        iv = np.array([(s, s + d) for _, s, d in evs]).reshape(-1, 2)
        b = clip(merge(iv), lo, hi)
        busy.append(float((b[:, 1] - b[:, 0]).sum()) * 1e-9)
        if first_busy is None:
            first_busy = b
        for name, s, d in evs:
            if lo <= s < hi:
                ops[name] = ops.get(name, 0.0) + d * 1e-9
                if ALL_TO_ALL.search(name):
                    a2a += d * 1e-9
    n_dev = len(raw.devices)
    edges = np.concatenate([[lo], first_busy.reshape(-1), [hi]])
    gaps = edges.reshape(-1, 2)
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:top]]
    labels = _label_gaps(longest, raw.host)
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return TraceReading(
        window_s=(hi - lo) * 1e-9, n_waves=n_waves, n_devices=n_dev,
        busy_s=float(np.mean(busy)), span_s=spans,
        device_ops=[(n, t / n_dev) for n, t in ranked],
        all_to_all_s=(a2a / n_dev) if a2a > 0 else None,
        idle_gaps=[(lab, float(g[1] - g[0]) * 1e-9)
                   for lab, g in zip(labels, longest)])


def host_s_per_wave(tr: TraceReading) -> Optional[float]:
    """Host time per wave in the harness's calls into the program, less
    the time blocked on the device (``wait``) and the copy of responses
    to the host (``receive``)."""
    if not tr.n_waves:
        return None
    s = tr.span_s
    calls = s.get("submit", 0.0) + s.get("dispatch", 0.0) \
        + s.get("drain", 0.0)
    return (calls - s.get("wait", 0.0) - s.get("receive", 0.0)) \
        / tr.n_waves
