"""Paper Fig. 9 analog: end-to-end serving latency vs number of generated
tokens, measured through the real engine (continuous batching + static-shape
executables) on this host with a reduced model, plus the projected TPU
per-token latency from the roofline terms of the full-size decode cell."""
from __future__ import annotations

import os
import tempfile
import time

import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core.attention import heuristics
from repro.models import model as M
from repro.serving.engine import Engine
from repro.serving.request import make_requests


def run_padding_waste(emit, cfg=None, params=None):
    """`padding-waste` scenario: the same mixed prefill+decode trace
    (staggered arrivals, chunked long prompts, steady decodes) through the
    packed (unified token stream) and padded (per-kind [B, S] buckets)
    engines.  Reports launched token slots (the FLOPs proxy: every slot
    runs the full per-token model FLOPs, padding included), the padding
    waste each path carries over the scheduled work, and the
    `compile_events` counts — the two quantities the unified launch
    exists to shrink."""
    if cfg is None:
        cfg = reduced(ARCHS["smollm-135m"]).replace(dtype="float32")
        params = M.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(11)
    first = [list(rng.integers(1, cfg.vocab_size, size=n))
             for n in (40, 9, 33)]
    late = [list(rng.integers(1, cfg.vocab_size, size=n))
            for n in (25, 6, 30)]
    results = {}
    for packed in (False, True):
        eng = Engine(cfg, params, max_seqs=4, num_pages=256,
                     max_model_len=256, packed_attention=packed,
                     enable_chunked_prefill=True, max_prefill_tokens=48)
        reqs = make_requests([list(p) for p in first], max_new_tokens=12)
        for r in reqs:
            eng.add_request(r)
        for _ in range(6):
            eng.step()  # long prompts chunk while shorts decode
        late_reqs = make_requests([list(p) for p in late],
                                  max_new_tokens=12)
        for r in late_reqs:  # land mid-decode: mixed steps
            eng.add_request(r)
        t0 = time.perf_counter()
        step_times = []
        while eng.sched.has_work:
            ts = time.perf_counter()
            eng.step()
            step_times.append(time.perf_counter() - ts)
        useful = (eng.prefilled_tokens
                  + sum(len(r.output) for r in reqs + late_reqs))
        results[packed] = {
            "slots": eng.launched_token_slots,
            "useful": useful,
            "compiles": len(eng.compile_events),
            "wall": time.perf_counter() - t0,
            "steps": len(step_times),
            "step_p50": float(np.percentile(step_times, 50)),
            "step_p95": float(np.percentile(step_times, 95)),
        }
    for packed, tag in ((False, "padded"), (True, "packed")):
        r = results[packed]
        emit(f"padding_waste/token_slots/{tag}", r["slots"],
             f"token rows launched ({r['useful']} useful); "
             f"FLOPs proxy: slots x per-token model FLOPs")
        emit(f"padding_waste/waste_pct/{tag}",
             100.0 * (r["slots"] - r["useful"]) / r["slots"],
             "launched slots that were padding")
        emit(f"padding_waste/compile_events/{tag}", r["compiles"],
             "distinct captured executables over the trace")
        emit(f"padding_waste/step_ms_p50/{tag}", r["step_p50"] * 1e3,
             f"median step wall-clock over {r['steps']} drain steps")
        emit(f"padding_waste/step_ms_p95/{tag}", r["step_p95"] * 1e3,
             "p95 step wall-clock (includes capture-step spikes)")
        emit(f"padding_waste/tokens_per_step/{tag}",
             r["useful"] / r["steps"],
             "useful tokens processed per drain step")
    emit("padding_waste/slot_reduction",
         results[False]["slots"] / results[True]["slots"],
         "padded / packed launched token rows (>1: packing saves FLOPs)")
    emit("padding_waste/compile_reduction",
         results[False]["compiles"] / results[True]["compiles"],
         "padded / packed captured executables")
    return results


def run_telemetry_overhead(emit, cfg=None, params=None, repeats=5):
    """`telemetry-overhead` scenario: the padding-waste mixed trace with
    the observability plane fully enabled (metrics + tracing + latency
    grid + sampled launch-timing barriers + a LIVE MetricsServer scrape
    thread + an armed RefitDaemon on the engine hook) vs disabled.  The
    observability layer must be effectively free: the acceptance guard
    is < 5% per-step overhead.

    Measurement discipline: each arm gets its OWN engine — the jitted
    executable caches hang off `functools.partial` wrappers created per
    engine, so sharing one would let the second arm ride the first arm's
    captures — with its own warmup drain.  Measured drains then
    INTERLEAVE the arms (disabled, enabled, disabled, ...) so slow host
    drift hits both equally.  Both arms replay the SAME deterministic
    trace, so step i is the same work in both; the guard compares the
    per-step-index noise floor (min over `repeats` drains, summed) —
    drain totals or plain medians are too noisy for a stable <5% verdict
    on a busy host, a min-floor over identical work is not."""
    if cfg is None:
        cfg = reduced(ARCHS["smollm-135m"]).replace(dtype="float32")
        params = M.init(cfg, jax.random.key(0))
    import tempfile as _tempfile

    from repro.obs import MetricsServer, RefitDaemon, Telemetry
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (40, 9, 33, 25, 6, 30)]

    def drive(eng):
        reqs = make_requests([list(p) for p in prompts], max_new_tokens=12)
        for r in reqs:
            eng.add_request(r)
        step_times = []
        while eng.sched.has_work:
            t1 = time.perf_counter()
            eng.step()
            step_times.append(time.perf_counter() - t1)
        return step_times

    with _tempfile.TemporaryDirectory() as d:
        tel = Telemetry()
        # the enabled arm carries the LIVE plane: a scrape-server thread
        # on an ephemeral port and a refit daemon evaluated from the
        # engine's on_step hook every step (min_new is set beyond the
        # trace so the trigger is watched but never fires — the cost
        # under guard is the watch, not an actual refit)
        server = MetricsServer(tel, snapshot_dir=None).start()
        daemon = RefitDaemon(tel, out_dir=d, min_new=10 ** 9)
        try:
            engines = {}
            for enabled in (False, True):
                engines[enabled] = Engine(
                    cfg, params, max_seqs=4, num_pages=256,
                    max_model_len=256, enable_chunked_prefill=True,
                    max_prefill_tokens=48,
                    telemetry=tel if enabled else None,
                    refit=daemon if enabled else None)
                drive(engines[enabled])  # warmup: capture executables
            drains = {False: [], True: []}
            for _ in range(repeats):
                for enabled in (False, True):
                    drains[enabled].append(drive(engines[enabled]))
        finally:
            server.stop()
    # per-step-index noise floor: min over repeats, then sum the schedule
    floor = {k: sum(min(ts) for ts in zip(*v)) for k, v in drains.items()}
    nsteps = min(len(d) for v in drains.values() for d in v)
    overhead = floor[True] / floor[False] - 1.0
    emit("telemetry_overhead/wall_s/disabled", floor[False],
         f"per-step-index min over {repeats} interleaved warmed drains, "
         f"summed ({nsteps} steps)")
    emit("telemetry_overhead/wall_s/enabled", floor[True],
         "same trace with metrics + tracing + latency grid + live "
         "scrape server + armed refit daemon on")
    emit("telemetry_overhead/overhead_pct", 100.0 * overhead,
         "enabled / disabled noise-floor ratio - 1 (guard: < 5%)")
    return {"disabled": floor[False], "enabled": floor[True],
            "overhead": overhead, "refits": daemon.refits}


def run_live_obs(emit, cfg=None, params=None):
    """`live-obs` scenario: the full observability plane active around a
    serving run — /metrics scraped over a real socket MID-RUN and parsed
    against the exposition grammar, the flight recorder breached once by
    a deliberately impossible SLO (exactly one bounded dump, then the
    latch holds), and the online refit daemon hot-swapping the heuristic
    trees between steps.  The differential guard: the instrumented run
    must emit token-for-token the same outputs as a bare engine — the
    whole plane observes and re-routes dispatch, it never touches the
    math."""
    if cfg is None:
        cfg = reduced(ARCHS["smollm-135m"]).replace(dtype="float32")
        params = M.init(cfg, jax.random.key(0))
    import json as _json
    import tempfile as _tempfile
    from urllib.request import urlopen

    from repro.obs import (
        FlightRecorder, MetricsServer, RefitDaemon, Telemetry,
    )
    from repro.obs.metrics import parse_prometheus
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (40, 9, 33, 25, 6, 30)]

    def drive(eng, scrape_at=None, url=None):
        reqs = make_requests([list(p) for p in prompts], max_new_tokens=16)
        for r in reqs:
            eng.add_request(r)
        steps, families = 0, None
        while eng.sched.has_work:
            eng.step()
            steps += 1
            if scrape_at is not None and steps == scrape_at:
                with urlopen(url, timeout=10.0) as resp:
                    assert resp.status == 200
                    families = parse_prometheus(
                        resp.read().decode("utf-8"))
        return [r.output for r in reqs], steps, families

    heuristics.reset()  # both arms must START from the default trees
    baseline, _, _ = drive(Engine(cfg, params, max_seqs=4, num_pages=256,
                                  max_model_len=256,
                                  enable_chunked_prefill=True,
                                  max_prefill_tokens=48))

    with _tempfile.TemporaryDirectory() as d:
        tel = Telemetry(trace_ring=True, launch_timing_interval=1)
        server = MetricsServer(tel, snapshot_dir=d).start()
        # 1ns SLO: breaches on the first eligible window -> exactly one
        # dump, then the latch holds until p95 recovers (it can't)
        flight = FlightRecorder(tel, slo_p95_s=1e-9, dump_dir=d,
                                window=16, min_steps=4)
        daemon = RefitDaemon(tel, out_dir=d, min_new=4)
        eng = Engine(cfg, params, max_seqs=4, num_pages=256,
                     max_model_len=256, enable_chunked_prefill=True,
                     max_prefill_tokens=48, telemetry=tel, refit=daemon)
        outputs, steps, families = drive(eng, scrape_at=5,
                                         url=server.url())
        with urlopen(server.url("/snapshot"), timeout=10.0) as resp:
            snap = _json.loads(resp.read().decode("utf-8"))
        server.stop()
        heuristics.reset()
        dump_files = [os.path.basename(p) + "*" for p in flight.dumps]
        res = {
            "outputs": outputs,
            "baseline": baseline,
            "steps": steps,
            "families": len(families),
            "snapshot_metrics": len(snap["metrics"]),
            "dumps": len(flight.dumps),
            "dump_paths": dump_files,
            "refits": daemon.refits,
            "swaps": daemon.swaps,
            "swap_steps": list(daemon.swap_steps),
        }
    emit("live_obs/scrape_families", res["families"],
         f"metric families parsed from a mid-run /metrics scrape "
         f"(step 5 of {res['steps']}, real socket)")
    emit("live_obs/flight_dumps", res["dumps"],
         f"SLO-breach auto-dumps (1ns SLO; latch held): "
         f"{', '.join(res['dump_paths'])}")
    emit("live_obs/refit_swaps", res["swaps"],
         f"heuristics hot-swaps at steps {res['swap_steps']} "
         f"({res['refits']} refits)")
    return res


def run_fused_sampling(emit, cfg=None, params=None):
    """`fused-sampling` scenario: the same mixed trace through (a) the
    fused single-dispatch packed engine, (b) the retained two-dispatch
    packed baseline (`fused_sampling=False`), and (c) the fused engine
    driven by the async double-buffered `stream()` loop.  Reports step
    p50/p95, the sample-phase time (the separate host-side sampling
    dispatch + [S, V] logits transfer the fusion removes), and device
    dispatches per step; asserts token identity between the arms."""
    if cfg is None:
        cfg = reduced(ARCHS["smollm-135m"]).replace(dtype="float32")
        params = M.init(cfg, jax.random.key(0))
    from repro.obs import Telemetry
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (40, 9, 33, 25, 6, 30)]

    def build(fused):
        return Engine(cfg, params, max_seqs=4, num_pages=256,
                      max_model_len=256, fused_sampling=fused,
                      enable_chunked_prefill=True, max_prefill_tokens=48,
                      telemetry=Telemetry())

    def requests():
        return make_requests([list(p) for p in prompts], max_new_tokens=16)

    def phase_sum(eng, phase):
        h = eng.telemetry.metrics.families().get("repro_step_phase_seconds")
        entry = h.get(phase=phase) if h is not None else None
        return entry["sum"] if entry else 0.0

    results = {}
    for tag, fused in (("fused", True), ("two_dispatch", False)):
        eng = build(fused)
        reqs_warm = requests()
        for r in reqs_warm:
            eng.add_request(r)
        while eng.sched.has_work:  # warmup: capture executables
            eng.step()
        base_sample = phase_sum(eng, "sample")
        base_calls = dict(eng.device_calls)
        reqs = requests()
        for r in reqs:
            eng.add_request(r)
        step_times = []
        while eng.sched.has_work:
            t0 = time.perf_counter()
            eng.step()
            step_times.append(time.perf_counter() - t0)
        calls = {k: eng.device_calls[k] - base_calls.get(k, 0)
                 for k in eng.device_calls}
        results[tag] = {
            "outputs": [r.output for r in reqs],
            "steps": len(step_times),
            "step_p50": float(np.percentile(step_times, 50)),
            "step_p95": float(np.percentile(step_times, 95)),
            "sample_s": phase_sum(eng, "sample") - base_sample,
            "device_calls": sum(calls.values()),
            "sample_calls": calls.get("sample", 0),
        }

    # async stream arm: same fused executables, double-buffered drive
    eng = build(True)
    reqs_warm = requests()
    for r in reqs_warm:
        eng.add_request(r)
    while eng.sched.has_work:
        eng.step()
    reqs = requests()
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    n_tokens = sum(1 for _ in eng.stream())
    stream_wall = time.perf_counter() - t0
    results["stream"] = {
        "outputs": [r.output for r in reqs],
        "wall": stream_wall,
        "tokens": n_tokens,
        "overlap_s": phase_sum(eng, "overlap"),
    }

    for tag in ("fused", "two_dispatch"):
        r = results[tag]
        emit(f"fused_sampling/step_ms_p50/{tag}", r["step_p50"] * 1e3,
             f"median step wall-clock over {r['steps']} warmed drain steps")
        emit(f"fused_sampling/step_ms_p95/{tag}", r["step_p95"] * 1e3,
             "p95 step wall-clock")
        emit(f"fused_sampling/sample_phase_ms/{tag}", r["sample_s"] * 1e3,
             "host sample-phase time over the drain (token transfer for "
             "fused; [S,V] logits + sampling dispatch for two_dispatch)")
        emit(f"fused_sampling/device_calls_per_step/{tag}",
             r["device_calls"] / r["steps"],
             f"device dispatches / steps ({r['sample_calls']} sampling "
             f"dispatches)")
    emit("fused_sampling/stream_tokens_per_s",
         results["stream"]["tokens"] / results["stream"]["wall"],
         f"async double-buffered stream() drain "
         f"({results['stream']['tokens']} tokens)")
    emit("fused_sampling/stream_overlap_ms",
         results["stream"]["overlap_s"] * 1e3,
         "host work overlapped with in-flight device steps")
    return results


def run_spec_decode(emit, cfg=None, params=None):
    """`spec-decode` scenario: a repetitive-text trace (cyclic prompts —
    the template/code-like traffic n-gram lookup exists for) through the
    packed engine with and without speculative decoding.  Reports the
    draft accept rate, accepted/emitted tokens per step, step counts and
    device dispatches per step; the guards are the PR's acceptance
    criteria — token-for-token identity with the non-speculative path,
    accepted tokens/step > 1.0, and a steady step still exactly ONE
    device dispatch (verify + accept + bonus sampling are fused into the
    unified launch)."""
    if cfg is None:
        cfg = reduced(ARCHS["smollm-135m"]).replace(dtype="float32")
        params = M.init(cfg, jax.random.key(0))
    cycle = [5, 9, 17, 3]
    rng = np.random.default_rng(11)
    prompts = [cycle * 6, (cycle * 5)[:18], cycle * 4,
               list(rng.integers(1, cfg.vocab_size, size=9))]

    def drive(eng):
        reqs = make_requests([list(p) for p in prompts], max_new_tokens=24)
        for r in reqs:
            eng.add_request(r)
        t0 = time.perf_counter()
        steps = 0
        while eng.sched.has_work:
            eng.step()
            steps += 1
        return {
            "outputs": [r.output for r in reqs],
            "steps": steps,
            "wall": time.perf_counter() - t0,
            "tokens": sum(len(r.output) for r in reqs),
            "device_calls": sum(eng.device_calls.values()),
        }

    results = {}
    for tag, spec in (("baseline", False), ("spec", True)):
        eng = Engine(cfg, params, max_seqs=4, num_pages=256,
                     max_model_len=256, speculative=spec, draft_k=4)
        drive(eng)  # warmup: capture executables (incl. spec buckets)
        eng.device_calls.clear()
        warm_stats = dict(eng.spec_stats)
        results[tag] = drive(eng)
        results[tag]["engine"] = eng
    spec_eng = results["spec"]["engine"]
    # measured drive only: the warmup drain's counters would double-count
    st = {k: spec_eng.spec_stats[k] - warm_stats[k]
          for k in spec_eng.spec_stats}
    for tag in ("baseline", "spec"):
        r = results[tag]
        emit(f"spec_decode/steps/{tag}", r["steps"],
             f"drain steps for {r['tokens']} output tokens")
        emit(f"spec_decode/tokens_per_step/{tag}",
             r["tokens"] / r["steps"],
             "output tokens delivered per engine step")
        emit(f"spec_decode/dispatches_per_step/{tag}",
             r["device_calls"] / r["steps"],
             "device dispatches / steps (guard: exactly 1.0)")
    emit("spec_decode/accept_rate",
         st["accepted"] / max(st["proposed"], 1),
         f"drafts verified == target ({st['accepted']}/{st['proposed']} "
         f"over {st['steps']} speculative steps)")
    emit("spec_decode/accepted_tokens_per_step",
         st["accepted"] / results["spec"]["steps"],
         "accepted draft tokens per engine step (guard: > 1.0 on this "
         "repetitive trace)")
    emit("spec_decode/step_reduction",
         results["baseline"]["steps"] / results["spec"]["steps"],
         "baseline / speculative drain steps on the same trace")
    return {"baseline": results["baseline"], "spec": results["spec"],
            "stats": st}


def run_tp_scaling(emit):
    """`tp-scaling` scenario: the mesh executor's scaling contract.  A
    child process (this file, `--scenario _tp-child`) is re-exec'd with
    four forced CPU host devices and drives the SAME mixed
    chunked+cached+preemption trace through engines at tp=1, tp=2 and
    tp=4.  Records device dispatches per step and padding waste per tp;
    the guards are structural, not wall-clock: every tp must keep the
    steady step at exactly 1.0 dispatches/step (a shard_map-wrapped jit
    is still one launch) and produce token-for-token identical outputs
    (replicated params + head-parallel attention + tiled all_gather
    split no contraction, so per-device math is bitwise the
    single-device math)."""
    import json
    import subprocess
    import sys

    env = dict(os.environ)
    # force 4 host devices in the child only; strip any pre-existing
    # device-count flag so `make test-mesh`-style environments compose
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"])
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                 if p and p != src])
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--scenario", "_tp-child"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"tp-scaling child failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("TPCHILD:"))
    res = json.loads(line[len("TPCHILD:"):])
    for tp in sorted(res, key=int):
        r = res[tp]
        emit(f"tp_scaling/dispatches_per_step/tp{tp}",
             r["dispatches_per_step"],
             f"total device dispatches / {r['steps']} steps "
             f"(guard: exactly 1.0 — shard_map jit is one launch)")
        emit(f"tp_scaling/waste_pct/tp{tp}", r["waste_pct"],
             f"launched slots that were padding "
             f"({r['slots']} slots, {r['useful']} useful)")
        emit(f"tp_scaling/steps/tp{tp}", r["steps"],
             "drain steps over the mixed trace (identical across tp)")
        emit(f"tp_scaling/wall_s/tp{tp}", r["wall"],
             f"drain wall-clock on {r['num_devices']} forced CPU host "
             f"device(s) — structural scenario, not a speed claim")
    return res


def run_tp_child():
    """Child half of `tp-scaling` (hidden `_tp-child` scenario): runs
    under XLA_FLAGS=--xla_force_host_platform_device_count=4 and prints
    one TPCHILD: JSON line for the parent to parse."""
    import json

    # reduced smollm has 2 q / 1 kv heads — not tp=4 divisible; override
    # to an 8q/4kv geometry (same d_model/head_dim) like test_mesh_serving
    cfg = reduced(ARCHS["smollm-135m"]).replace(
        dtype="float32", num_q_heads=8, num_kv_heads=4)
    params = M.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (58, 50)]
    out = {}
    for tp in (1, 2, 4):
        eng = Engine(cfg, params, max_seqs=2, num_pages=8,
                     max_model_len=128, enable_chunked_prefill=True,
                     enable_prefix_caching=True, max_prefill_tokens=16,
                     tp=tp)
        reqs = make_requests([list(p) for p in prompts], max_new_tokens=8)
        for r in reqs:
            eng.add_request(r)
        preempted = 0
        t0 = time.perf_counter()
        steps = 0
        while eng.sched.has_work:
            preempted += eng.step()["preempted"]
            steps += 1
        wall = time.perf_counter() - t0
        useful = eng.prefilled_tokens + sum(len(r.output) for r in reqs)
        out[str(tp)] = {
            "steps": steps,
            "dispatches_per_step": sum(eng.device_calls.values()) / steps,
            "device_calls": {k: int(v)
                             for k, v in eng.device_calls.items()},
            "slots": eng.launched_token_slots,
            "useful": useful,
            "waste_pct": 100.0 * (eng.launched_token_slots - useful)
            / eng.launched_token_slots,
            "preempted": preempted,
            "wall": wall,
            "num_devices": eng.alloc.mesh_stats(tp)["num_devices"],
            "outputs": [[int(t) for t in r.output] for r in reqs],
        }
    print("TPCHILD:" + json.dumps(out))


def run(emit):
    cfg = reduced(ARCHS["smollm-135m"]).replace(dtype="float32")
    params = M.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompt = list(rng.integers(1, cfg.vocab_size, size=50))

    run_padding_waste(emit, cfg, params)

    for out_tokens in (8, 32, 128):
        eng = Engine(cfg, params, max_seqs=4, num_pages=128,
                     max_model_len=512)
        # warmup: capture the executables (the CUDA-graph-record analog)
        warm = make_requests([prompt], max_new_tokens=out_tokens)
        eng.generate(warm)
        t0 = time.perf_counter()
        reqs = make_requests([prompt], max_new_tokens=out_tokens)
        eng.generate(reqs)
        dt = time.perf_counter() - t0
        emit(f"fig9/e2e_latency/out{out_tokens}", dt * 1e6,
             f"prompt=50 batch=1 compiles={len(eng.compile_events)}")
        emit(f"fig9/per_token/out{out_tokens}", dt / out_tokens * 1e6,
             "amortized decode latency on this host")

    # batched throughput (continuous batching with mixed lengths)
    eng = Engine(cfg, params, max_seqs=8, num_pages=256, max_model_len=512)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (50, 20, 70, 35, 50, 10, 60, 25)]
    warm = make_requests(prompts, max_new_tokens=4)
    eng.generate(warm)
    reqs = make_requests(prompts, max_new_tokens=32)
    t0 = time.perf_counter()
    eng.generate(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.output) for r in reqs)
    emit("fig9/batched_tokens_per_s", total / dt,
         f"8 concurrent requests, {total} tokens")

    # chunked prefill: a long prompt arriving mid-decode monopolizes a step
    # under monolithic prefill (the inter-token-latency spike chunking
    # exists to remove) — compare the worst per-step wall-clock while
    # short requests keep decoding
    long_prompt = list(rng.integers(1, cfg.vocab_size, size=192))
    shorts = [list(rng.integers(1, cfg.vocab_size, size=8))
              for _ in range(3)]

    def run_mixed(eng):
        sreqs = make_requests([list(p) for p in shorts], max_new_tokens=24)
        for r in sreqs:
            eng.add_request(r)
        for _ in range(4):
            eng.step()  # shorts reach steady-state decode
        [lr] = make_requests([list(long_prompt)], max_new_tokens=4)
        eng.add_request(lr)
        step_times = []
        while eng.sched.has_work:
            t0 = time.perf_counter()
            eng.step()
            step_times.append(time.perf_counter() - t0)
        return step_times

    spike = {}
    for chunked in (False, True):
        eng = Engine(cfg, params, max_seqs=4, num_pages=256,
                     max_model_len=512, enable_chunked_prefill=chunked,
                     max_prefill_tokens=32 if chunked else 8192)
        run_mixed(eng)                    # warmup: capture executables
        times = run_mixed(eng)            # measured
        tag = "chunked" if chunked else "monolithic"
        spike[chunked] = max(times)
        emit(f"chunked_prefill/max_step_ms/{tag}", max(times) * 1e3,
             f"worst step while a 192-token prompt lands mid-decode "
             f"({len(times)} steps)")
    emit("chunked_prefill/itl_spike_ratio", spike[False] / spike[True],
         "monolithic worst-step / chunked worst-step (budget=32)")

    # shared-prefix workload: chat/agent traffic with a common system prompt
    # — the automatic-prefix-caching scenario (cache hit rate + prefill
    # savings + wall-clock, cache off vs on)
    shared = list(rng.integers(1, cfg.vocab_size, size=96))
    sp_prompts = [shared + list(rng.integers(1, cfg.vocab_size, size=n))
                  for n in (12, 30, 7, 22, 15, 9, 26, 18)]
    times = {}
    for cache_on in (False, True):
        eng = Engine(cfg, params, max_seqs=4, num_pages=256,
                     max_model_len=512, enable_prefix_caching=cache_on)
        # two warm rounds: the first populates the cache, the second runs
        # all-hits and captures the cached-prefill executables
        for _ in range(2 if cache_on else 1):
            warm = make_requests([list(p) for p in sp_prompts],
                                 max_new_tokens=2)
            eng.generate(warm)
        # snapshot counters so the warm rounds (deliberately cold cache)
        # don't dilute the measured run's hit rate / savings
        warm_stats = eng.prefix_cache.stats() if cache_on else {}
        warm_prefilled = eng.prefilled_tokens
        warm_cached = eng.cached_prefill_tokens
        reqs = make_requests([list(p) for p in sp_prompts], max_new_tokens=16)
        t0 = time.perf_counter()
        eng.generate(reqs)
        times[cache_on] = time.perf_counter() - t0
        if cache_on:
            stats = eng.prefix_cache.stats()
            hits = stats["cache_hits"] - warm_stats["cache_hits"]
            misses = stats["cache_misses"] - warm_stats["cache_misses"]
            new_toks = eng.prefilled_tokens - warm_prefilled
            cached_toks = eng.cached_prefill_tokens - warm_cached
            emit("prefix_cache/hit_rate",
                 100.0 * hits / max(hits + misses, 1),
                 f"% of admissions with a cached prefix "
                 f"({hits + misses} lookups, measured run only)")
            emit("prefix_cache/prefill_savings",
                 100.0 * cached_toks / max(new_toks + cached_toks, 1),
                 f"% prompt tokens skipped "
                 f"({cached_toks}/{new_toks + cached_toks})")
    emit("prefix_cache/e2e_speedup", times[False] / times[True],
         f"shared-prefix batch wall-clock, cache off {times[False]:.3f}s "
         f"vs on {times[True]:.3f}s")

    # autotuned vs default kernel dispatch: fit trees on this arch's
    # geometry, then serve the same mixed workload with the tuned tree
    # installed vs the shipped default heuristics.  The cost-model speedup
    # is the tuned tree's predicted gain over the best fixed config (the
    # paper's Fig. 8 quantity); the engine run verifies the dispatch loop
    # end-to-end (per-config captures stay bounded, variants switch by
    # batch shape) — on this CPU host the xla decode path is
    # variant-agnostic, so wall-clock parity is expected, not a speedup.
    at_prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                  for n in (60, 10, 45, 25)]
    # the 'default' arm must actually be default: shield the comparison
    # from an operator's $REPRO_ATTN_HEURISTICS (engine init would
    # re-install it after heuristics.reset() and compare tuned-vs-tuned)
    env_tree = os.environ.pop("REPRO_ATTN_HEURISTICS", None)
    try:
        with tempfile.TemporaryDirectory() as d:
            tree_path = os.path.join(d, "tree.json")
            rep = tune_and_export_arch(cfg, tree_path)
            at_times, captures = {}, {}
            for tuned in (False, True):
                if tuned:
                    heuristics.load(tree_path)
                else:
                    heuristics.reset()
                try:
                    eng = Engine(cfg, params, max_seqs=4, num_pages=256,
                                 max_model_len=512)
                    warm = make_requests([list(p) for p in at_prompts],
                                         max_new_tokens=4)
                    eng.generate(warm)
                    reqs = make_requests([list(p) for p in at_prompts],
                                         max_new_tokens=24)
                    t0 = time.perf_counter()
                    eng.generate(reqs)
                    at_times[tuned] = time.perf_counter() - t0
                    captures[tuned] = len(eng.compile_events)
                finally:
                    heuristics.reset()
    finally:
        if env_tree is not None:
            os.environ["REPRO_ATTN_HEURISTICS"] = env_tree
    emit("autotune/costmodel_speedup", rep["tuned_vs_untuned_speedup"],
         "tuned tree vs best fixed config (cost model, decode grid)")
    emit("autotune/costmodel_prefill_speedup",
         rep["prefill"]["tuned_vs_untuned_speedup"],
         "prefill tree vs best fixed config (cost model)")
    emit("autotune/e2e_ratio", at_times[False] / at_times[True],
         f"default {at_times[False]:.3f}s vs tuned {at_times[True]:.3f}s "
         f"wall-clock; captures default={captures[False]} "
         f"tuned={captures[True]}")


def tune_and_export_arch(cfg, path_json: str) -> dict:
    from repro.autotune.tune import tune_and_export
    return tune_and_export(
        path_json, num_q_heads=cfg.num_q_heads,
        num_kv_heads=max(cfg.num_kv_heads, 1),
        head_dim=cfg.resolved_head_dim, page_size=cfg.page_size,
    )


if __name__ == "__main__":
    # standalone smoke entry (`make bench-smoke`): the CPU-cheap scenarios
    # (CSV to stdout + machine-readable BENCH_e2e.json) in well under two
    # minutes.  `smoke` = padding-waste + fused-sampling + live-obs
    # (mid-run scrape / flight-recorder latch / refit hot-swap token
    # differential) + spec-decode (accept rate / one-dispatch / token
    # identity guards) + the telemetry-overhead guard.
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="smoke",
                    choices=["smoke", "padding-waste", "fused-sampling",
                             "telemetry-overhead", "live-obs", "spec-decode",
                             "tp-scaling", "_tp-child", "all"])
    ap.add_argument("--json-out", default="BENCH_e2e.json", metavar="PATH",
                    help="machine-readable results ('' disables)")
    args = ap.parse_args()
    if args.scenario == "_tp-child":
        # hidden: the forced-4-device half of tp-scaling (no CSV/JSON)
        run_tp_child()
        raise SystemExit(0)
    print("name,value,derived")
    rows: dict[str, dict] = {}

    def _emit(name, value, derived=""):
        print(f"{name},{value:.4f},{derived}")
        rows[name] = {"value": float(value), "note": derived}

    if args.scenario in ("smoke", "padding-waste", "all"):
        res = run_padding_waste(_emit)
        assert res[True]["slots"] < res[False]["slots"], \
            "packed step launched MORE token rows than padded"
        assert res[True]["compiles"] <= res[False]["compiles"], \
            "packed step compiled MORE executables than padded"
    if args.scenario in ("smoke", "fused-sampling", "all"):
        fs = run_fused_sampling(_emit)
        assert fs["fused"]["outputs"] == fs["two_dispatch"]["outputs"], \
            "fused sampling diverged from the two-dispatch baseline"
        assert fs["fused"]["outputs"] == fs["stream"]["outputs"], \
            "async stream diverged from the synchronous fused engine"
        assert fs["fused"]["sample_calls"] == 0 and \
            fs["fused"]["device_calls"] == fs["fused"]["steps"], (
            "fused packed step must be exactly one device dispatch: "
            f"{fs['fused']}")
        # the sample-phase span is the step's device-wait sync point
        # (untimed launches return immediately; the host blocks when it
        # pulls the result), so on this CPU host it is dominated by model
        # compute and fused-vs-two-dispatch wall parity is expected — the
        # structural reduction (no [S, V] transfer, no second dispatch)
        # is the device_calls assert above.  Slack guard only: a real
        # regression (e.g. re-materializing logits host-side) would blow
        # well past 1.5x.
        assert fs["fused"]["sample_s"] < 1.5 * fs["two_dispatch"]["sample_s"], (
            "fused sample/host phase regressed: "
            f"{fs['fused']['sample_s']:.4f}s vs "
            f"{fs['two_dispatch']['sample_s']:.4f}s two-dispatch")
    if args.scenario in ("tp-scaling", "all"):
        # deliberately not in smoke: spawns a 4-device child process
        tp_res = run_tp_scaling(_emit)
        for tp, r in sorted(tp_res.items(), key=lambda kv: int(kv[0])):
            assert r["dispatches_per_step"] == 1.0, (
                f"tp={tp} broke the one-dispatch steady step: "
                f"{r['device_calls']} over {r['steps']} steps")
            assert r["outputs"] == tp_res["1"]["outputs"], (
                f"tp={tp} outputs diverged from tp=1 on the mixed trace")
            assert r["steps"] == tp_res["1"]["steps"], (
                f"tp={tp} took {r['steps']} steps vs "
                f"{tp_res['1']['steps']} at tp=1")
        assert tp_res["1"]["preempted"] > 0, \
            "tp-scaling trace no longer exercises preemption"
    if args.scenario in ("smoke", "live-obs", "all"):
        lo = run_live_obs(_emit)
        assert lo["outputs"] == lo["baseline"], (
            "live observability plane changed emitted tokens — the "
            "refit hot-swap must only re-route dispatch")
        assert lo["dumps"] == 1, (
            f"flight recorder under a breached SLO must dump exactly "
            f"once (latch), got {lo['dumps']}")
        assert lo["swaps"] >= 1, \
            "online refit daemon never hot-swapped on the live grid"
        assert lo["families"] >= 10, (
            f"mid-run /metrics scrape parsed only {lo['families']} "
            f"families")
    if args.scenario in ("smoke", "spec-decode", "all"):
        sd = run_spec_decode(_emit)
        assert sd["spec"]["outputs"] == sd["baseline"]["outputs"], \
            "speculative decoding changed emitted tokens"
        for tag in ("baseline", "spec"):
            r = sd[tag]
            assert r["device_calls"] == r["steps"], (
                f"{tag} broke the one-dispatch steady step: "
                f"{r['device_calls']} dispatches over {r['steps']} steps")
        st = sd["stats"]
        assert st["accepted"] / sd["spec"]["steps"] > 1.0, (
            f"accepted tokens/step {st['accepted']}/{sd['spec']['steps']} "
            f"did not beat 1.0 on the repetitive trace")
        assert sd["spec"]["steps"] < sd["baseline"]["steps"], \
            "speculation saved no steps on the repetitive trace"
    if args.scenario in ("smoke", "telemetry-overhead", "all"):
        tel_res = run_telemetry_overhead(_emit)
        assert tel_res["overhead"] < 0.05, (
            f"telemetry overhead {tel_res['overhead']:.1%} breaches the "
            f"5% acceptance guard")
    if args.scenario == "all":
        run(_emit)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"bench": "e2e_latency",
                       "scenario": args.scenario,
                       "results": rows}, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json_out} ({len(rows)} metrics)")
