#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<traffic>.json`).  One run, one process:

 1. finds the TPU (no CPU fallback) and turns on the compile cache at
    `<checkout>/.jax_cache`;
 2. makes the weights on the device from the seed, in one jitted call;
 3. builds the program's `Engine` and launches every executable the cell's
    traffic can reach (`bench.system.warm_up`);
 4. drives the traffic open loop through `Engine.submit` / `Engine.stream`
    until it is steady (the ramp), then measures for `--seconds`;
 5. frees the program's state and compares what the window served with the
    plain reference (`bench.check`);
 6. prints the result: end-to-end metrics with `--trace 0`, per-layer
    metrics with `--trace 1` (the window traced by the profiler).

Set-up is everything before the window: weights, engine, executables, the
warm-up and the ramp.  The compared numbers, each with its limit, are the
last lines on standard error and the last key of the result line.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
BENCH = CHECKOUT / "bench"
CACHE_DIR = CHECKOUT / ".jax_cache"

log = logging.getLogger("bench")


def load_cell(name: str, bench_json: pathlib.Path, root: pathlib.Path) \
        -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, the workload's entry, its configuration, its
    traffic mix), each file found by the names the entry gives."""
    bench = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    path = root / "configs" / f"{cell['config']}.json"
    if not path.is_file():
        raise SystemExit(f"configuration {cell['config']!r}: no file {path}")
    from bench import traffic
    return (bench, cell, json.loads(path.read_text()),
            traffic.load_mix(cell["traffic"], root / "traffic"))


def _reader(metric: str):
    """The per-layer metric's reader: `bench/metrics/<name>.py`, else the
    file of the part of the name before its first dot (`step_mfu.serve`
    reads with `step_mfu.py`)."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"per-layer metric {metric!r}: no reader in "
                     f"{BENCH / 'metrics'}")


def _metrics_for(bench: dict, cell: str, key: str) -> list[dict]:
    return [m for m in bench[key]
            if cell in m.get("workloads", [cell])]


def percentile(values, q: float) -> float | None:
    import numpy as np
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, float), q))


class CompileCounter:
    """Backend compiles and persistent-cache loads JAX reports (`n`), and
    traces and lowerings to MLIR, which need no compile (`traces`)."""

    def __init__(self):
        import jax
        self.n = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
        elif name in ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.traces += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.n += 1


class GcPauses:
    """Seconds each collection of the Python garbage collector took."""

    def __init__(self):
        self.t0 = None
        self.pauses: list[tuple[int, float]] = []  # (generation, seconds)
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self.t0))


class OpenLoop:
    """Submits each arrival when it is due, between yields of
    `Engine.stream()`; sleeps when the engine is idle.  Every request is
    timed from when it was due.  Every half second it notes the KV pool's
    occupancy (`pages`), and it keeps the longest wait for one yield of
    the stream (`longest_yield`: seconds, host clock at its end)."""

    def __init__(self, eng, arrivals, system, clock=time.perf_counter):
        import jax
        self.jax = jax
        self.eng = eng
        self.sys = system
        self.clock = clock
        self.pending = deque(arrivals)
        self.t0 = None  # schedule start (host clock)
        self.reqs = {}  # req_id -> dict(arrival, req, due, submit, times)
        self.done = 0
        self.it = None
        self.pages: list[tuple[float, dict]] = []
        self.pages_next = 0.0
        self.longest_yield = (0.0, 0.0)

    def span(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def _submit_due(self, now):
        while self.pending and self.t0 + self.pending[0].due_s <= now:
            a = self.pending.popleft()
            req = self.sys.new_request(a.prompt, a.max_new_tokens,
                                       a.temperature, a.top_p, a.stream)
            self.eng.submit(req)
            self.reqs[req.req_id] = {
                "arrival": a, "req": req, "due": self.t0 + a.due_s,
                "submit": self.clock(), "times": [], "tokens": []}

    def run(self, stop) -> None:
        """Drive until `stop(now)` holds (checked between tokens)."""
        if self.t0 is None:
            self.t0 = self.clock()
        while True:
            now = self.clock()
            if stop(now):
                return
            with self.span("bench.submit"):
                self._submit_due(now)
            if self.it is None and not self.eng.sched.has_work:
                nxt = (self.t0 + self.pending[0].due_s if self.pending
                       else now + 0.01)
                with self.span("bench.wait_for_arrival"):
                    time.sleep(max(0.0, min(nxt - now, 0.01)))
                continue
            if self.it is None:
                self.it = self.eng.stream(max_steps=1 << 62)
            with self.span("bench.stream_next"):
                try:
                    rid, tok = next(self.it)
                except StopIteration:
                    self.it = None
                    continue
            t = self.clock()
            if t - now > self.longest_yield[0]:
                self.longest_yield = (t - now, t)
            if t >= self.pages_next:
                self.pages.append((t, self.eng.alloc.stats()))
                self.pages_next = t + 0.5
            r = self.reqs.get(rid)
            if r is None:  # submitted by an earlier loop on this engine
                continue
            r["times"].append(self.clock())
            r["tokens"].append(tok)
            if len(r["times"]) == r["arrival"].max_new_tokens:
                self.done += 1

    def close(self) -> None:
        if self.it is not None:
            self.it.close()
            self.it = None


def _device_info(jax):
    devs = jax.devices()
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def _snapshot(eng) -> dict:
    return {"prefilled_tokens": eng.prefilled_tokens,
            "launched_token_slots": eng.launched_token_slots,
            "executables": len(eng.executables)}


def _within(t: float, lo: float, hi: float) -> bool:
    return lo <= t < hi


def _window_work(loop: OpenLoop, t_w0: float, t_w1: float,
                 computed_at_start: dict) -> dict:
    """Token events of the window: prompt positions computed, decode rows
    with their context, sampled rows."""
    prompts, decode_ctx, sampled = [], [], 0
    for rid, r in loop.reqs.items():
        req, plen = r["req"], len(r["arrival"].prompt)
        start = max(computed_at_start.get(rid, 0), req.num_cached_tokens)
        end = min(req.num_computed_tokens, plen)
        if end > start:
            prompts.append((start, end))
        for j, t in enumerate(r["times"]):
            if _within(t, t_w0, t_w1):
                sampled += 1
                if j >= 1:
                    decode_ctx.append(plen + j)
    return {"prompts": prompts, "decode_ctx": decode_ctx,
            "sampled_rows": sampled}


def end_to_end(loop: OpenLoop, t_w0: float, t_w1: float) -> dict:
    """ttft_p95_ms, itl_p95_ms, output_tok_s over the window, plus the
    counts behind them.  `due` counts the requests due in the window (the
    TTFT sample); `attempted` every request the window worked on: due in
    it, or due before it and given a token in it (a backlog's requests are
    all due at its start); `failed` those of them that the engine finished
    short.  That is judged by the tokens the engine generated, not by those
    the loop has read: the stream yields a finished step's tokens one by
    one, so the window can close before a finished request's last token is
    read."""
    ttft, itl, tokens = [], [], 0
    due = attempted = failed = 0
    for r in loop.reqs.values():
        times = r["times"]
        in_window = [j for j, t in enumerate(times) if _within(t, t_w0, t_w1)]
        if _within(r["due"], t_w0, t_w1):
            due += 1
            first = times[0] if times else t_w1
            ttft.append((min(first, t_w1) - r["due"]) * 1e3)
        if _within(r["due"], t_w0, t_w1) or (r["due"] < t_w0 and in_window):
            attempted += 1
            if loop.sys.is_finished(r["req"]) and \
                    r["req"].num_generated < r["arrival"].max_new_tokens:
                failed += 1
        for j in in_window:
            tokens += 1
            if j:
                itl.append((times[j] - times[j - 1]) * 1e3)
    return {"ttft_ms": ttft, "itl_ms": itl, "tokens": tokens, "due": due,
            "attempted": attempted, "failed": failed}


def generator_lag_ms(loop: OpenLoop, t_w0: float, t_w1: float) -> list:
    return [(r["submit"] - r["due"]) * 1e3 for r in loop.reqs.values()
            if _within(r["due"], t_w0, t_w1)]


def _log_host(loop: OpenLoop, traces: int, gcs: list, t_w0: float,
              t_w1: float) -> None:
    """What the host did in the window besides serving: traces and
    lowerings, garbage collections, the longest wait for one token, and
    the KV pool's occupancy."""
    wait, at = loop.longest_yield
    log.info("window host: %d traces or lowerings, %d gc collections "
             "(longest %.1f ms), longest wait for a token %.1f ms at "
             "%.2f s into the window", traces, len(gcs),
             1e3 * max((s for _, s in gcs), default=0.0), 1e3 * wait,
             at - t_w0 if at else 0.0)
    pool = [st for t, st in loop.pages if t_w0 <= t < t_w1]
    if pool:
        live = [st["referenced_pages"] for st in pool]
        log.info("KV pool in the window: %d pages; live sequences hold "
                 "%.0f on average, %d at most; the prefix cache holds %d "
                 "at the close, %d evicted", loop.eng.num_pages,
                 sum(live) / len(live), max(live),
                 pool[-1]["evictable_pages"], pool[-1]["evictions"])


def build_engine(spec: dict, seed: int, patch=None):
    """The program's engine for this configuration, with the weights made
    on the device from the seed in one jitted call, warmed up.  `patch` is
    called with the engine before its warm-up (the tests break it)."""
    import jax
    from bench import system, weights
    from bench.configs import reference
    cfg = system.model_config(spec)
    params = jax.jit(lambda base: reference(spec).program_params(
        cfg, weights.stacked_weights(spec, base)))(weights.seed_key(seed))
    system.check_params(cfg, params)
    eng = system.make_engine(cfg, params, spec["engine"])
    if patch is not None:
        patch(eng)
    system.warm_up(eng, spec["engine"], spec["vocab_size"], seed)
    return eng


def main(argv=None, *, require_tpu: bool = True, patch=None,
         emit: bool = True, bench_json: pathlib.Path | None = None,
         root: pathlib.Path = BENCH) -> dict:
    """One run.  `require_tpu=False`, `patch` (called with the engine
    before warm-up), `bench_json` and `root` (where configs/, traffic/ and
    limits/ are) are for the tests, which drive the rest of a run on the
    CPU at a small size; `emit=False` prints nothing.  Returns the result line's
    object, with the served (arrival, tokens) pairs under "served"."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(message)s")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(CHECKOUT))
    bench, cell, spec, mix = load_cell(
        args.workload, bench_json or CHECKOUT / "BENCHMARK.json", root)

    import jax
    devs, device = _device_info(jax)
    log.info("device: platform=%s kind=%s count=%d", device["platform"],
             device["kind"], device["count"])
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell["chips"]):
        raise SystemExit(f"needs {cell['chips']} TPU chip(s); JAX found "
                         f"{device['count']} {device['platform']} device(s)")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import check, system, traffic
    from bench.configs import reference
    log.info("compile cache: %s", system.enable_compile_cache())
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if require_tpu and device["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         f"bench/peaks.json")
    compiles = CompileCounter()
    gc_pauses = GcPauses()

    # --- set-up: weights, engine, warm-up ---------------------------------
    eng = build_engine(spec, args.seed, patch)
    log.info("warm-up: %d executables, %.1fs since start",
             len(eng.executables), time.monotonic() - T_START)

    arrivals = traffic.schedule(mix, args.seed, spec["vocab_size"])
    loop = OpenLoop(eng, arrivals, system)
    if mix["arrival"]["process"] == "backlog":
        goal = int(mix["ramp_done"])
        loop.run(lambda now: loop.done >= goal)
    else:
        ramp = float(mix["ramp_s"])
        loop.run(lambda now: now >= loop.t0 + ramp)

    # --- the window -------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    before = _snapshot(eng)
    computed = {rid: r["req"].num_computed_tokens
                for rid, r in loop.reqs.items()}
    compiles_before, traces_before = compiles.n, compiles.traces
    gc_before = len(gc_pauses.pauses)
    loop.longest_yield = (0.0, 0.0)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_w0 = time.perf_counter()
    setup_s = time.monotonic() - T_START
    window = loop.span("bench.window") if trace_dir \
        else contextlib.nullcontext()
    with window:
        loop.run(lambda now: now >= t_w0 + args.seconds)
        if trace_dir:
            jax.block_until_ready(eng.cache)
    t_w1 = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    after = _snapshot(eng)
    window_compiles = compiles.n - compiles_before + (
        after["executables"] - before["executables"])
    loop.close()
    e2e = end_to_end(loop, t_w0, t_w1)
    lag = generator_lag_ms(loop, t_w0, t_w1) or [0.0]
    log.info("window: %.3fs, %d requests due, %d worked on, %d tokens, "
             "%d compiles", t_w1 - t_w0, e2e["due"], e2e["attempted"],
             e2e["tokens"], window_compiles)
    log.info("generator lateness: p95 %.3f ms, max %.3f ms over %d "
             "submissions", percentile(lag, 95), max(lag), len(lag))
    _log_host(loop, compiles.traces - traces_before,
              gc_pauses.pauses[gc_before:], t_w0, t_w1)
    peak = devs[0].memory_stats().get("peak_bytes_in_use") \
        if devs[0].platform == "tpu" else None
    device["memory_peak_bytes"] = peak

    record = {
        "dims": reference(spec).dims(spec),
        "peaks": peaks.get(device["kind"]),
        "window_s": t_w1 - t_w0,
        "counters": {k: after[k] - before[k]
                     for k in ("prefilled_tokens", "launched_token_slots")},
        **_window_work(loop, t_w0, t_w1, computed),
        "trace": None,
    }
    if trace_dir:
        from bench import trace
        record["trace"] = trace.reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]

    # --- correctness, after the program's state is freed ------------------
    served = [(r["arrival"], r["tokens"])
              for r in loop.reqs.values()
              if r["times"] and _within(r["times"][-1], t_w0, t_w1)
              and len(r["times"]) == r["arrival"].max_new_tokens]
    del eng, loop
    gc.collect()
    limits = check.load_limits(args.workload, root / "limits")
    t_ref = time.monotonic()
    checks = check.compare(spec, args.seed, served, limits)
    log.info("reference: %.1fs", time.monotonic() - t_ref)
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    correct = check.all_within(checks)

    metrics = {}
    if args.trace:
        for m in _metrics_for(bench, args.workload, "per_layer"):
            value = _reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "ttft_p95_ms": percentile(e2e["ttft_ms"], 95),
            "itl_p95_ms": percentile(e2e["itl_ms"], 95),
            "output_tok_s": e2e["tokens"] / (t_w1 - t_w0),
            "setup_s": setup_s,
        }
        for m in _metrics_for(bench, args.workload, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": device}
    if record["trace"]:
        tr = record["trace"]
        result["breakdown"] = {
            "device_ops": sorted(([n, t] for n, t in tr["ops_s"].items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": tr["gaps"][:10]}
    result["checks"] = checks
    if emit:
        for name, c in checks.items():
            print(f"check {name}: {c['value']} limit {c['limit']}",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return dict(result, served=served)


if __name__ == "__main__":
    main()
