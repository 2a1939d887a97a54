"""End-to-end serving driver: batched requests through the continuous-
batching engine with paged KV cache, preemption under page pressure, and
autotuned kernel heuristics (the paper's full system, Fig. 2).

    PYTHONPATH=src python examples/serve_paged.py [--arch smollm-135m]
                                                  [--backend xla|pallas]
"""
import argparse
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
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=sorted(ARCHS))
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--prefix-caching", action="store_true",
                    help="content-addressed KV page reuse across requests")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend a common N-token system prompt to every "
                         "request (the prefix-cache hot path)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="split long prompts into budget-sized chunks "
                         "across steps (flat inter-token latency)")
    ap.add_argument("--max-prefill-tokens", type=int, default=None,
                    metavar="N", help="per-step token budget (default: "
                    "the tuned tree's roofline suggestion or 32 when "
                    "--chunked-prefill, else 8192)")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel mesh size: the unified step "
                         "runs under shard_map with KV pools sharded on "
                         "the head axis (docs/serving.md); on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first")
    ap.add_argument("--padded", action="store_true",
                    help="use the padded per-kind step (decode / prefill "
                         "/ cached-prefill executables) instead of the "
                         "default unified token-packed launch")
    ap.add_argument("--no-fused-sampling", action="store_true",
                    help="sample host-side from transferred logits "
                         "(two dispatches/step) instead of in-graph "
                         "(one fused dispatch/step; docs/serving.md)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: per-request n-gram drafts "
                         "verified in the one packed launch, exact page "
                         "rollback on rejection (docs/serving.md); "
                         "outputs are token-identical to the plain path")
    ap.add_argument("--draft-k", type=int, default=4, metavar="K",
                    help="max draft tokens proposed per request per step "
                         "(adaptive: shrinks/regrows with the accept-rate "
                         "EMA; default 4)")
    ap.add_argument("--stream", action="store_true",
                    help="drive via submit() + run(): async double-"
                         "buffered loop, tokens printed as they land")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy); "
                         "seeded per-request RNG streams make outputs "
                         "batch-composition independent")
    ap.add_argument("--heuristics", default=None, metavar="TREE.json",
                    help="autotune-exported decision trees (from "
                         "examples/autotune_attn.py); default: run a "
                         "quick cost-model tune inline. "
                         "$REPRO_ATTN_HEURISTICS works too.")
    ap.add_argument("--metrics-dir", default=None, metavar="DIR",
                    help="enable telemetry and write DIR/metrics.prom "
                         "(Prometheus text), DIR/metrics.jsonl (snapshot) "
                         "and DIR/latency_grid.json (the refit input for "
                         "examples/autotune_attn.py --refit-from)")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="enable telemetry and write a Chrome/Perfetto "
                         "trace (load at https://ui.perfetto.dev)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="P",
                    help="enable telemetry and serve live /metrics "
                         "(Prometheus text), /snapshot and /trace on "
                         "127.0.0.1:P while the engine runs (0 = pick an "
                         "ephemeral port, printed at startup); periodic "
                         "JSONL snapshots rotate into --metrics-dir")
    ap.add_argument("--snapshot-interval", type=float, default=30.0,
                    metavar="SEC", help="periodic snapshot cadence for "
                         "the --metrics-port server (default 30s)")
    ap.add_argument("--slo-p95", type=float, default=None, metavar="SEC",
                    help="enable the flight recorder: when rolling p95 "
                         "step latency breaches SEC, auto-dump the trace "
                         "ring + a metrics snapshot into --metrics-dir "
                         "(or cwd)")
    ap.add_argument("--refit-every", type=int, default=None, metavar="N",
                    help="enable the online refit daemon: after N new "
                         "warm launch observations per (phase, profile) "
                         "bucket, refit the heuristics from the live "
                         "latency grid and hot-swap the trees between "
                         "steps (artifacts land in --metrics-dir or cwd)")
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    cfg = reduced(ARCHS[args.arch]).replace(dtype="float32")
    params = M.init(cfg, jax.random.key(0))

    if args.heuristics:
        heuristics.load(args.heuristics)
        print(f"heuristics installed from {args.heuristics}")
    elif heuristics.maybe_load_env():
        print(f"heuristics installed from $REPRO_ATTN_HEURISTICS "
              f"({heuristics.loaded_path()})")
    else:
        # offline autotune -> decision-tree heuristics (paper §5 workflow)
        from repro.autotune.tune import tune_and_export
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "tree.json")
            rep = tune_and_export(path, num_q_heads=cfg.num_q_heads,
                                  num_kv_heads=cfg.num_kv_heads,
                                  head_dim=cfg.resolved_head_dim,
                                  page_size=cfg.page_size)
            heuristics.load(path)
        print(f"heuristics installed (tuned-vs-fixed speedup "
              f"{rep['tuned_vs_untuned_speedup']:.2f}x)")

    if args.max_prefill_tokens is not None:
        budget = args.max_prefill_tokens
    elif args.chunked_prefill:
        # chunk-size autotuner: the tuned tree ships a roofline-derived
        # per-step budget; fall back to the demo-scale constant
        budget = heuristics.suggested_max_prefill_tokens() or 32
    else:
        budget = 8192
    tel = server = daemon = flight = None
    need_tel = (args.metrics_dir or args.trace_out
                or args.metrics_port is not None
                or args.slo_p95 is not None or args.refit_every is not None)
    if need_tel:
        from repro.obs import FlightRecorder, MetricsServer, RefitDaemon, \
            Telemetry
        obs_dir = args.metrics_dir or "."
        # ring mode: the flight recorder wants the LAST N steps at the
        # breach, not the first N of the run
        tel = Telemetry(trace_ring=args.slo_p95 is not None,
                        launch_timing_interval=1 if args.refit_every
                        else 8)
        if args.metrics_port is not None:
            server = MetricsServer(
                tel, port=args.metrics_port,
                snapshot_dir=args.metrics_dir,
                snapshot_interval_s=args.snapshot_interval,
                arch=args.arch).start()
            print(f"live metrics: curl {server.url()}")
        if args.slo_p95 is not None:
            flight = FlightRecorder(tel, slo_p95_s=args.slo_p95,
                                    dump_dir=obs_dir)
        if args.refit_every is not None:
            daemon = RefitDaemon(tel, out_dir=obs_dir,
                                 min_new=args.refit_every)
    eng = Engine(cfg, params, max_seqs=4, num_pages=96, max_model_len=256,
                 backend=args.backend,
                 packed_attention=not args.padded,
                 enable_prefix_caching=args.prefix_caching,
                 enable_chunked_prefill=args.chunked_prefill,
                 max_prefill_tokens=budget,
                 fused_sampling=not args.no_fused_sampling,
                 speculative=args.speculative,
                 draft_k=args.draft_k,
                 telemetry=tel,
                 refit=daemon,
                 tp=args.tp)
    rng = np.random.default_rng(0)
    shared = list(rng.integers(1, cfg.vocab_size, size=args.shared_prefix))
    prompts = [shared + list(rng.integers(1, cfg.vocab_size,
                                          size=int(rng.integers(5, 60))))
               for _ in range(args.requests)]
    reqs = make_requests(prompts, max_new_tokens=args.max_new_tokens,
                         temperature=args.temperature)
    t0 = time.perf_counter()
    steps = 0
    partial_chunks = 0
    try:
        _drive_and_report(args, eng, reqs, tel, daemon, budget, t0)
    finally:
        # flush observability artifacts even on Ctrl-C / crash: a
        # truncated run's grid and trace are exactly what you want to
        # refit or debug from
        steps = eng.step_idx
        if tel is not None and args.metrics_dir:
            os.makedirs(args.metrics_dir, exist_ok=True)
            tel.export_prometheus(
                os.path.join(args.metrics_dir, "metrics.prom"))
            tel.write_snapshot(
                os.path.join(args.metrics_dir, "metrics.jsonl"),
                arch=args.arch, steps=steps)
            grid_path = os.path.join(args.metrics_dir, "latency_grid.json")
            tel.export_latency_grid(grid_path)
            print(f"metrics -> {args.metrics_dir}/ "
                  f"(refit: python examples/autotune_attn.py "
                  f"--refit-from {grid_path})")
        if tel is not None and args.trace_out:
            tel.export_trace(args.trace_out)
            print(f"trace -> {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")
        if server is not None:
            server.stop()
        if daemon is not None:
            daemon.stop()
        heuristics.reset()


def _drive_and_report(args, eng, reqs, tel, daemon, budget, t0):
    steps = 0
    partial_chunks = 0
    if args.stream:
        # async double-buffered drive loop: host packs step N+1 while
        # the device runs step N (docs/serving.md)
        for r in reqs:
            eng.submit(r)

        def on_finish(req):
            print(f"req {req.req_id:3d}: {len(req.output)} tokens "
                  f"(first {req.output[:4]}...)")

        res = eng.run(on_finish=on_finish)
        steps = res["steps"]
    else:
        for r in reqs:
            eng.add_request(r)
        while eng.sched.has_work:
            stats = eng.step()
            partial_chunks += stats["partial_prefills"]
            if steps % 10 == 0:
                disp = ",".join(f"{ph}:{d['variant']}"
                                for ph, d in stats["dispatch"].items())
                print(f"step {steps:3d}: prefill={stats['prefill']} "
                      f"decode={stats['decode']} "
                      f"preempted={stats['preempted']} "
                      f"free_pages={eng.alloc.free_pages} [{disp}]")
            steps += 1
    dt = time.perf_counter() - t0
    total = sum(len(r.output) for r in reqs)
    print(f"\n{args.requests} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s on this host)")
    kind = ("padded per-kind buckets" if args.padded
            else "unified token-packed buckets")
    print(f"graph captures: {len(eng.compile_events)} "
          f"({kind}, one per bucket x kernel-config); "
          f"{eng.launched_token_slots} token rows launched")
    counts = ", ".join(f"{ph}/{var}={n}" for (ph, var), n
                       in sorted(eng.dispatch_counts.items()))
    print(f"kernel dispatch: {counts}")
    calls = ", ".join(f"{k}={n}" for k, n in sorted(eng.device_calls.items()))
    mode = ("fused in-graph sampling"
            if not (args.no_fused_sampling or args.padded)
            else "host-side sampling")
    print(f"device calls: {calls} ({mode})")
    if args.chunked_prefill:
        print(f"chunked prefill: budget={budget} tokens/step, "
              f"{partial_chunks} partial chunks scheduled")
    if args.speculative:
        st = eng.spec_stats
        rate = st["accepted"] / st["proposed"] if st["proposed"] else 0.0
        k = eng.drafter.controller.k if eng.drafter is not None else 0
        print(f"speculative decoding: {st['proposed']} drafted, "
              f"{st['accepted']} accepted ({rate:.1%}), "
              f"{st['emitted']} emitted over {st['steps']} spec steps "
              f"(adaptive k now {k})")
    if eng.prefix_cache is not None:
        st = eng.prefix_cache.stats()
        print(f"prefix cache: {st['cache_hits']} hits / "
              f"{st['cache_misses']} misses, "
              f"{eng.cached_prefill_tokens} prompt tokens reused, "
              f"{st['cache_evictions']} evictions")
    if tel is not None:
        s = tel.summary()
        print(f"telemetry: ttft p50={s['ttft_p50']:.4f}s "
              f"p95={s['ttft_p95']:.4f}s, itl p50={s['itl_p50']:.4f}s, "
              f"step p50={s['step_p50']:.4f}s, "
              f"padding waste={s['padding_waste']:.1%}")
        if tel.flight is not None:
            n = len(tel.flight.dumps)
            where = f" (last: {tel.flight.dumps[-1]}*)" if n else ""
            print(f"flight recorder: rolling p95="
                  f"{tel.flight.rolling_p95() or 0:.4f}s vs SLO "
                  f"{tel.flight.slo_p95_s:.4f}s, {n} dump(s){where}")
    if daemon is not None:
        rep = daemon.report()
        print(f"online refit: {rep['refits']} refit(s), "
              f"{rep['swaps']} hot-swap(s) at steps {rep['swap_steps']}"
              + (f", tree: {rep['last_path']}" if rep['last_path'] else ""))


if __name__ == "__main__":
    main()
