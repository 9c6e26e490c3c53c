"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``load`` reads the newest ``*.xplane.pb`` under a directory into plain
event records; ``reduce`` turns the records of one or more traced slices
into these numbers, summed over the slices:

- ``window_s``: the traced window, the host span named ``bench.window``
  (or the extent of the device events where there is none);
- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices;
- ``programs``: device seconds and launch count of each compiled program
  (XLA module), by name without its launch suffix;
- ``ops``: device seconds of each operation name, summed (loops and
  conditionals left out: their time is that of the ops inside them);
- ``idle_gaps``: the device's idle time inside the window, attributed to
  the benchmark's own host span (``bench.*``) that covers most of each gap.

Records are ``(plane, line, name, start_ns, dur_ns)`` tuples, so a small
recorded trace can be kept as JSON and reduced in a test.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:"
HOST_SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTROL_FLOW = ("%while", "%conditional", "%call")


def load(trace_dir: str) -> list[tuple]:
    """Event records of the newest trace under ``trace_dir``."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(HOST_SPAN_PREFIX):
                    out.append((plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def op_name(name: str, width: int = 120) -> str:
    """An HLO op's trace name without layouts, cut to ``width``."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def program_name(name: str) -> str:
    """``jit_serve_step(12)`` -> ``jit_serve_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def _slice(events: list[tuple]) -> dict:
    """Window, busy time, idle gaps, op and program times of one slice."""
    devices = sorted({p for p, ln, *_ in events
                      if p.startswith(DEVICE_PREFIX)
                      and ln in (OPS_LINE, MODULES_LINE)})
    host = [(n, s, s + d) for p, _, n, s, d in events
            if not p.startswith(DEVICE_PREFIX)]
    ops_of = {dev: [(s, s + d, n) for p, ln, n, s, d in events
                    if p == dev and ln == OPS_LINE and d > 0]
              for dev in devices}
    windows = [(s, e) for n, s, e in host if n == "bench.window"]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        spans = [(s, e) for iv in ops_of.values() for s, e, _ in iv]
        lo = min((s for s, _ in spans), default=0)
        hi = max((e for _, e in spans), default=0)

    busy, gaps = [], {}
    spans_host = [(n, s, e) for n, s, e in host if n != "bench.window"]
    for dev in devices:
        iv = _clip(_union([(s, e) for s, e, _ in ops_of[dev]]), lo, hi)
        busy.append(sum(b - a for a, b in iv))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, cover = "host (no bench span)", 0
            for n, s, e in spans_host:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = n, c
            gaps[best] = gaps.get(best, 0) + (b - a) / len(devices)
    ops: dict[str, float] = {}
    for dev in devices:
        for s, e, n in ops_of[dev]:
            if n.startswith(CONTROL_FLOW):     # holds the ops it runs
                continue
            n = op_name(n)
            ops[n] = ops.get(n, 0.0) + (e - s) / len(devices)
    programs: dict[str, list] = {}
    for p, ln, n, s, d in events:
        if p in devices and ln == MODULES_LINE:
            acc = programs.setdefault(program_name(n), [0, 0])
            acc[0] += d / len(devices)
            acc[1] += 1
    return {"devices": devices, "window": max(hi - lo, 0),
            "busy": sum(busy) / max(len(devices), 1), "gaps": gaps,
            "ops": ops, "programs": programs}


def _top(acc: dict, top: int) -> list:
    return sorted(([n, t * 1e-9] for n, t in acc.items()),
                  key=lambda x: -x[1])[:top]


def reduce(*slices: list[tuple], top: int = 10) -> dict:
    """The numbers of one or more traced slices (each the events of one
    profiler session), summed over the slices."""
    parts = [_slice(ev) for ev in slices]
    window_s = sum(p["window"] for p in parts) * 1e-9
    busy_s = sum(p["busy"] for p in parts) * 1e-9
    gaps: dict[str, float] = {}
    ops: dict[str, float] = {}
    programs: dict[str, dict] = {}
    for p in parts:
        for n, t in p["gaps"].items():
            gaps[n] = gaps.get(n, 0.0) + t
        for n, t in p["ops"].items():
            ops[n] = ops.get(n, 0.0) + t
        for n, (t, c) in p["programs"].items():
            acc = programs.setdefault(n, {"seconds": 0.0, "count": 0})
            acc["seconds"] += t * 1e-9
            acc["count"] += c
    return {
        "devices": sorted({d for p in parts for d in p["devices"]}),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s else None,
        "programs": programs,
        "ops": _top(ops, top),
        "idle_gaps": _top(gaps, top),
    }


def program(red: dict, part: str) -> dict | None:
    """Device seconds and launches of the programs whose name holds
    ``part`` (``serve_step`` finds ``jit_serve_step``)."""
    hits = [p for n, p in red["programs"].items() if part in n]
    if not hits:
        return None
    return {"seconds": sum(p["seconds"] for p in hits),
            "count": sum(p["count"] for p in hits)}
