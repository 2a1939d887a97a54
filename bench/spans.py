"""Reduce the program's own records of a window: the engine's phase spans
in a profiler trace, and its requests' lifecycle stamps.

The engine writes a `repro.<phase>` span (`jax.profiler.TraceAnnotation`,
tagged `step=<id>`) around each host phase of a step, on the thread that
drives `Engine.stream()`; the harness writes its `bench.*` spans on the
same thread.  From one `.xplane.pb`, clipped to the host span
`bench.window`:

    steps         the steps whose spans all lie inside the window
    self_ms       per phase, its spans' self time (duration less that of
                  the spans nested in it) per step, over those steps
    host_step_ms  per such step, the summed self time of every engine span
                  except `repro.wait_tokens` (the host blocked on the
                  device)
    idle_s        chip 0's idle time in the window (1 - union of its
                  "XLA Ops" intervals)
    idle_by_span  that idle time put down to the innermost `repro.*` span
                  covering it; the rest to `engine.other` (inside
                  `bench.stream_next`, outside every engine span) or
                  `host.other`.  The parts sum to `idle_s`.
    gaps          the longest idle stretches, each with the label that
                  covers most of it and its start in the window

`queue_wait_ms` reads the stamps `Scheduler` sets on each `Request`.
"""
from __future__ import annotations

from bench import trace

ENGINE_PREFIX = "repro."
WAIT = "repro.wait_tokens"
ENGINE_OTHER = "engine.other"
HOST_OTHER = "host.other"
STREAM = "bench.stream_next"


def read_spans(path: str) -> tuple[list, list]:
    """(chip 0's ops [(start_ns, end_ns)], host spans [(name, start_ns,
    end_ns, step or None)]) of the `repro.*` and `bench.*` spans in one
    xplane file."""
    from jax.profiler import ProfileData
    chips: dict[str, list] = {}
    spans: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    chips[plane.name] = [
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((ENGINE_PREFIX,
                                           trace.SPAN_PREFIX)):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      dict(ev.stats).get("step")))
    if not chips:
        raise ValueError("trace holds no TPU op line")
    return chips[min(chips)], spans


def _self_times(spans: list) -> list[float]:
    """Self time (ns) of each span of a properly nested list, in its
    order: its duration less that of its direct children."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    own = [float(s[2] - s[1]) for s in spans]
    stack: list[int] = []
    for i in order:
        while stack and spans[stack[-1]][2] <= spans[i][1]:
            stack.pop()
        if stack:
            own[stack[-1]] -= spans[i][2] - spans[i][1]
        stack.append(i)
    return own


def _labels(spans: list, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """[lo, hi) cut into stretches, each labelled with the innermost
    engine span covering it, else `engine.other` inside a
    `bench.stream_next`, else `host.other`."""
    edges = sorted({lo, hi} | {t for s in spans for t in s[1:3]
                               if lo < t < hi})
    events = sorted(spans, key=lambda s: s[1])
    active: list = []
    out, k = [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(events) and events[k][1] <= a:
            active.append(events[k])
            k += 1
        active = [s for s in active if s[2] > a]
        engine = [s for s in active if s[0].startswith(ENGINE_PREFIX)]
        if engine:
            label = max(engine, key=lambda s: (s[1], -s[2]))[0]
        elif any(s[0] == STREAM for s in active):
            label = ENGINE_OTHER
        else:
            label = HOST_OTHER
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def reduce_events(ops: list, spans: list, top: int = 10) -> dict:
    """The reduction proper, on plain lists (testable without a file)."""
    win = [(s, e) for n, s, e, _ in spans if n == trace.WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {trace.WINDOW_SPAN} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    engine = [s for s in spans if s[0].startswith(ENGINE_PREFIX)]

    outside = {step for _, s, e, step in engine if s < lo or e > hi}
    phase_ns: dict[str, float] = {}
    steps: dict[int, float] = {}
    for (name, _, _, step), own in zip(engine, _self_times(engine)):
        if step in outside:
            continue
        phase_ns[name] = phase_ns.get(name, 0.0) + own
        steps[step] = steps.get(step, 0.0) + (own if name != WAIT else 0.0)
    n = max(len(steps), 1)

    busy = trace._merge(trace._clip(ops, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    labels = _labels([s for s in spans if s[0] != trace.WINDOW_SPAN],
                     lo, hi)
    idle_by: dict[str, float] = {}
    gaps = []
    k = 0
    for s, e in idle:
        while k < len(labels) and labels[k][1] <= s:
            k += 1
        cover: dict[str, int] = {}
        j = k
        while j < len(labels) and labels[j][0] < e:
            a, b, label = labels[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[label] = cover.get(label, 0) + ov
            j += 1
        for label, ov in cover.items():
            idle_by[label] = idle_by.get(label, 0.0) + ov * 1e-9
        gaps.append([max(cover, key=cover.get) if cover else HOST_OTHER,
                     (e - s) * 1e-9, (s - lo) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(e - s for s, e in idle) * 1e-9,
        "idle_by_span": idle_by,
        "steps": len(steps),
        "self_ms": {k: ns * 1e-6 / n for k, ns in phase_ns.items()},
        "host_step_ms": [steps[k] * 1e-6 for k in sorted(steps)],
        "spans": len(engine),
        "gaps": gaps[:top],
    }


def reduce_trace(log_dir: str, top: int = 10) -> dict:
    ops, spans = read_spans(trace.find_xplane(log_dir))
    return reduce_events(ops, spans, top=top)


def queue_wait_ms(reqs, t_w0: float, t_w1: float) -> list[float]:
    """Admission wait of each request due in [t_w0, t_w1) (the TTFT
    sample), from its stamps: `admitted_at - submitted_at`; one still
    waiting at the close counts its wait so far.  `reqs` are
    (due time, Request) pairs on the stamps' clock.  Empty where the
    program sets no stamps."""
    out = []
    for due, req in reqs:
        sub = getattr(req, "submitted_at", None)
        if sub is None or not t_w0 <= due < t_w1:
            continue
        adm = req.admitted_at
        end = adm if adm is not None and adm < t_w1 else t_w1
        out.append((end - sub) * 1e3)
    return out
