"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

    busy       union of the op intervals on each TPU's "XLA Ops" line,
               inside the window, averaged over the chips traced
    ops        device time by HLO instruction name (numeric suffix
               dropped; loops, which contain other ops, left out), chip 0
    gaps       the longest idle stretches of chip 0 inside the window, each
               put down to the harness's host span (`bench.*`) that
               overlaps it most, or to "host.other"

The window is the host span `bench.window`, which the harness writes with
`jax.profiler.TraceAnnotation` around the measured loop; host spans and
device ops share the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
# ops that contain other ops on the same line: their time is their body's
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"[.:]\d+$")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(name: str) -> str:
    """The HLO instruction's name without its numeric suffix:
    `%paged_decode.8 = bf16[...] custom-call(...)` -> `paged_decode`,
    `fusion.123` -> `fusion`."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def read_planes(path: str) -> tuple[dict, list]:
    """({device plane name: [(name, start_ns, end_ns)]}, host spans
    [(name, start_ns, end_ns)]) from one xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    return devices, spans


def reduce_events(devices: dict, spans: list, top: int = 10) -> dict:
    """The reduction proper, on plain lists (testable without a file)."""
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    if not devices:
        raise ValueError("trace holds no TPU op line")
    busy = []
    for name in sorted(devices):
        merged = _merge(_clip([(s, e) for _, s, e in devices[name]], lo, hi))
        busy.append(sum(e - s for s, e in merged))
    first = devices[sorted(devices)[0]]
    ops: dict[str, int] = {}
    for n, s, e in first:
        c = _clip([(s, e)], lo, hi)
        name = op_name(n)
        if c and name not in CONTAINERS:
            ops[name] = ops.get(name, 0) + c[0][1] - c[0][0]
    merged = _merge(_clip([(s, e) for _, s, e in first], lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host.other", 0
        for n, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > cover:
                best, cover = n, ov
        gaps.append([best, (e - s) * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "ops_s": {n: t * 1e-9 for n, t in ops.items()},
        "gaps": gaps,
        "chips": len(busy),
    }


def reduce_trace(log_dir: str, top: int = 10) -> dict:
    devices, spans = read_planes(find_xplane(log_dir))
    return reduce_events(devices, spans, top=top)
