"""Production serving launcher: continuous-batching engine fed by an
open-loop synthetic request stream (arrival-rate driven), reporting
latency/throughput statistics.

    python -m repro.launch.serve --arch qwen2.5-3b --backend pallas
    python -m repro.launch.serve --arch smollm-135m --reduced --rate 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.configs.registry import reduced
from repro.models import model as M
from repro.serving.engine import Engine
from repro.serving.request import Request, State
from repro.utils.compile_cache import enable_compile_cache


def serve(eng: Engine, requests: list[Request], *, rate: float) -> dict:
    """Submit `requests` to `eng` in order, `rate` arrivals per engine
    step, and step the engine until every request has finished.

    Returns {"seconds": wall time of the loop, "steps": engine steps,
    "tokens": output tokens, "latency": per-request submit-to-finish
    seconds in submission order}."""
    pending = list(requests)
    t_submit: dict[int, float] = {}
    t_done: dict[int, float] = {}
    arrivals = 0.0
    steps = 0
    t0 = time.perf_counter()
    while pending or eng.sched.has_work:
        arrivals += rate
        while pending and arrivals >= 1.0:
            r = pending.pop(0)
            t_submit[r.req_id] = time.perf_counter()
            eng.add_request(r)
            arrivals -= 1.0
        eng.step()
        steps += 1
        now = time.perf_counter()
        for r in requests:
            if r.state is State.FINISHED and r.req_id not in t_done:
                t_done[r.req_id] = now
    return {
        "seconds": time.perf_counter() - t0,
        "steps": steps,
        "tokens": sum(len(r.output) for r in requests),
        "latency": [t_done[r.req_id] - t_submit[r.req_id]
                    for r in requests],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="arrivals per engine step")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=256)
    ap.add_argument("--max-model-len", type=int, default=512)
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    params = M.init(cfg, jax.random.key(0))
    eng = Engine(cfg, params, max_seqs=args.max_seqs,
                 num_pages=args.num_pages,
                 max_model_len=args.max_model_len, backend=args.backend)

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                         size=int(rng.integers(8, 80)))),
                max_new_tokens=args.max_new_tokens)
        for _ in range(args.num_requests)
    ]
    res = serve(eng, reqs, rate=args.rate)
    lat = np.asarray(res["latency"])
    dt = res["seconds"]
    print(f"served {len(reqs)} requests / {res['tokens']} tokens "
          f"in {dt:.2f}s ({res['tokens'] / dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind})")
    print(f"latency p50={np.percentile(lat, 50):.3f}s "
          f"p95={np.percentile(lat, 95):.3f}s max={lat.max():.3f}s")
    print(f"graph captures: {eng.compile_events} "
          f"({eng.compile_seconds:.1f}s compiling)")


if __name__ == "__main__":
    main()
