#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU.

Serves Qwen2.5-3B at its published widths (all 36 layers, bf16, weights
drawn from a seed) through `repro.launch.serve.serve`, the loop
`python -m repro.launch.serve` runs: the packed unified `Engine` step with
fused sampling, prefix caching and chunked prefill.  It serves the same
requests with the Pallas kernels and then with the XLA attention path and
checks that

  * every request finishes with its full token count on both backends;
  * the Pallas run dispatched both the GQA and the segmented decode
    kernel, and every Pallas executable holds a `tpu_custom_call` (no
    interpret mode, no fallback to the XLA path);
  * one packed step that mixes decode rows and chunk rows gives the same
    logits on both backends, within `BF16_LOGIT_TOL`;
  * `paged_attention_unified` in float32 matches the plain reference in
    `kernels/paged_attention/ref.py` within `F32_KERNEL_TOL`.

    python chip_smoke.py        # one chip
    python chip_smoke.py --tp   # a four-chip host: tp=1 against tp=2 only

Any failed check exits non-zero.  Only a run on a TPU in which every check
passed prints the last line, one JSON object naming the device.  There is
no CPU fallback.
"""
from __future__ import annotations

import argparse
import collections
import functools
import gc
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.distributed.sharding import make_mesh  # noqa: E402
from repro.kernels.paged_attention import ops, ref  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.executor import SingleDeviceExecutor  # noqa: E402
from repro.serving.request import Request, State  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen2.5-3b"
SEED = 0

# 8 slots, a 2048-page pool (32k tokens, 1.2 GB of bf16 KV at this width;
# the step does not donate its cache, so two pools are live), 512-token
# chunks.
SERVE_ENGINE = dict(max_seqs=8, num_pages=2048, max_model_len=2048,
                    enable_prefix_caching=True, enable_chunked_prefill=True,
                    max_prefill_tokens=512)
NUM_REQUESTS = 8
NEW_TOKENS = 32
SHARED_PREFIX = 256
ARRIVALS_PER_STEP = 1.0

# Pallas and XLA logits of one packed step, from the same cache and batch,
# as max |difference| over max |XLA logit|.  Both paths keep activations in
# bf16 (unit roundoff 2**-9) and differ in how attention accumulates; 36
# layers of independent roundings grow the relative error to about
# sqrt(36) * 2**-9 ~ 0.012, and 2**-5 leaves room for that.  A wrong page,
# mask or segment merge moves attention outputs by O(1).
BF16_LOGIT_TOL = 2.0 ** -5
# float32 kernel against the float32 reference (matmuls at HIGHEST):
# |got - want| <= tol * (1 + |want|).  A kernel that rounds its matmul
# inputs to bf16 errs by about 1e-2 here.
F32_KERNEL_TOL = 1e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def smoke_requests(vocab: int) -> list[Request]:
    """Prompts of 64-1024 tokens; every second one starts with the same
    256-token prefix.  Greedy, with pinned sampling streams."""
    rng = np.random.default_rng(SEED)
    shared = rng.integers(1, vocab, size=SHARED_PREFIX).tolist()
    reqs = []
    for i in range(NUM_REQUESTS):
        if i % 2:
            tail = int(rng.integers(64, 1025 - SHARED_PREFIX))
            prompt = shared + rng.integers(1, vocab, size=tail).tolist()
        else:
            prompt = rng.integers(1, vocab,
                                  size=int(rng.integers(64, 1025))).tolist()
        reqs.append(Request(prompt=prompt, max_new_tokens=NEW_TOKENS,
                            seed=i))
    return reqs


def serve_phase(cfg, params, backend: str, engine_kw=SERVE_ENGINE,
                requests=None, rate=ARRIVALS_PER_STEP):
    eng = Engine(cfg, params, backend=backend, **engine_kw)
    reqs = requests if requests is not None else smoke_requests(
        cfg.vocab_size)
    res = serve(eng, reqs, rate=rate)
    jax.block_until_ready(eng.cache)
    for r in reqs:
        check(r.state is State.FINISHED
              and len(r.output) == r.max_new_tokens,
              f"[{backend}] request {r.req_id} ended {r.state} with "
              f"{len(r.output)}/{r.max_new_tokens} tokens")
    warm = res["steps"] - len(eng.compile_events)
    print(f"[{backend}] served {len(reqs)} requests, {res['tokens']} tokens "
          f"in {res['steps']} steps ({warm} warm) in "
          f"{res['seconds']:.2f}s, {eng.compile_seconds:.2f}s of it "
          f"compiling {len(eng.executables)} executables")
    counts = {f"{ph}/{v}": n for (ph, v), n in
              sorted(eng.dispatch_counts.items())}
    print(f"[{backend}] dispatch_counts: {counts}")
    if eng.prefix_cache is not None:
        st = eng.prefix_cache.stats()
        print(f"[{backend}] prefix cache: {st['cache_hits']} hits, "
              f"{eng.cached_prefill_tokens} prompt tokens reused")
    return eng, reqs


def check_pallas_engine(eng: Engine) -> None:
    variants = {v for (ph, v) in eng.dispatch_counts if ph == "unified"}
    check({"gqa", "segmented"} <= variants,
          f"pallas run dispatched {sorted(variants)}, not gqa and segmented")
    for key, exe in eng.executables.items():
        check("tpu_custom_call" in exe.as_text(),
              f"pallas executable {key} holds no tpu_custom_call")
    print(f"[pallas] tpu_custom_call in all {len(eng.executables)} "
          f"unified executables")


def mixed_step_check(cfg, params) -> None:
    """Drive a Pallas engine to a step that packs decode rows and chunk
    rows, then replay that launch's cache and batch on the XLA path."""
    rng = np.random.default_rng(SEED + 1)
    eng = Engine(cfg, params, backend="pallas", debug_logits=True,
                 max_seqs=8, num_pages=256, max_model_len=1024,
                 enable_chunked_prefill=True, max_prefill_tokens=256)
    for i, n in enumerate((64, 400)):
        eng.add_request(Request(
            prompt=rng.integers(1, cfg.vocab_size, size=n).tolist(),
            max_new_tokens=4, seed=100 + i))
    while True:
        check(eng.sched.has_work, "no step mixed decode and chunk rows")
        stats = eng.step()
        if stats["decode"] and stats["prefill"]:
            break
    # the engine's copy of that launch's cache: the Pallas launch consumed
    # the original, and the XLA replay below consumes this one
    cache_in, batch = eng.last_launch
    xla = SingleDeviceExecutor(
        cfg, backend="xla", max_seqs=eng.max_seqs, fused=True,
        seed=eng.seed, debug_logits=True).build_unified(None)
    _, want, _ = xla(eng.params, cache_in, batch)
    got = np.asarray(eng.last_step_logits, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite logits in the mixed step")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    same = int((got.argmax(-1) == want.argmax(-1)).sum())
    variant = stats["dispatch"]["unified"]["variant"]
    print(f"mixed step ({stats['decode']} decode + {stats['prefill']} chunk "
          f"rows, pallas/{variant}) vs xla: max|dlogit| {err:.5f} of "
          f"max|logit| {scale:.5f} (ratio {err / scale:.5f}, tol "
          f"{BF16_LOGIT_TOL}); argmax equal in {same}/{got.shape[0]} rows")
    check(err <= BF16_LOGIT_TOL * scale, "pallas and xla logits differ")


def kernel_reference_check(cfg) -> None:
    """float32 `paged_attention_unified` against the plain reference at
    the model's head counts and at SmolLM's head_dim 64: 8 decode rows
    (one dead) and two chunks, one resumed after 300 tokens of context,
    reading the last layer of a two-layer pool; then the in-place pool
    write against its `.at[].set` reference."""
    ps, np_ = cfg.page_size, 64
    rng = np.random.default_rng(SEED + 2)
    nd = 8
    dec_ctx = rng.integers(1, np_ * ps + 1, size=nd)
    dec_ctx[3] = 0
    qlens = np.array([1] * nd + [100, 37], np.int32)
    ctx = np.concatenate([dec_ctx, [100, 337]]).astype(np.int32)
    s = len(qlens)
    t = nd + 256
    qsl = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
    p = s * np_ + 1
    f32 = jnp.float32
    pt = jnp.asarray(rng.permutation(p - 1)[: s * np_].reshape(s, np_) + 1,
                     jnp.int32)
    heads = {(cfg.num_q_heads, cfg.num_kv_heads, cfg.resolved_head_dim),
             (9, 3, 64)}
    for hq, hkv, d in sorted(heads):
        q = jnp.asarray(rng.standard_normal((t, hq, d)), f32)
        kv = jnp.asarray(rng.standard_normal((2, hkv, p, ps, 2 * d)), f32)
        args = (q, kv, 1, pt, jnp.asarray(ctx), jnp.asarray(qsl),
                jnp.asarray(qlens))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.paged_attention_prefill_ref(*args))
        for variant in ("baseline", "gqa", "segmented"):
            got = np.asarray(ops.paged_attention_unified(
                *args, num_decode_seqs=nd, variant=variant, num_segments=8))
            err = float((np.abs(got - want) / (1.0 + np.abs(want))).max())
            print(f"float32 paged_attention_unified/{variant} D={d} vs "
                  f"reference: max |d|/(1+|ref|) {err:.2e} "
                  f"(tol {F32_KERNEL_TOL})")
            check(err <= F32_KERNEL_TOL,
                  f"{variant} kernel differs from ref at D={d}")
        # the step's rows: decode rows, a chunk that crosses a page, and
        # trash slots, written into layer 1 in place
        for dtype in (jnp.float32, jnp.bfloat16):
            pool = kv.astype(dtype)
            rows = jnp.asarray(rng.standard_normal((t, hkv, 2 * d)), dtype)
            pos = np.concatenate([dec_ctx - 1, 300 + np.arange(t - nd)])
            seq = np.concatenate([np.arange(nd), np.full(t - nd, nd + 1)])
            slots = (np.asarray(pt)[seq, pos // ps] * ps + pos % ps)
            slots[3] = p * ps  # the dead decode row: trash
            slots[t - 5:] = p * ps  # padding: trash
            slots = jnp.asarray(slots, jnp.int32)
            want = np.asarray(ref.write_rows_ref(pool, 1, rows, slots),
                              np.float32)
            got = np.asarray(ops.write_rows(
                pool, 1, rows, ops.write_plan(slots, ps, p)), np.float32)
            bad = int((got != want).sum())
            print(f"{jnp.dtype(dtype).name} write_rows D={d} vs reference: "
                  f"{bad} elements differ")
            check(bad == 0, f"in-place pool write differs at D={d}")


def tp_phase(cfg) -> None:
    """tp=1 and tp=2 in one process, token-identical greedy outputs.  Two
    KV heads admit at most tp=2.  Two requests that arrive together and
    whose prompts fit one 512-token step, so that each tp compiles two
    executables: the prefill step and the segmented decode step."""
    check(jax.device_count() >= 2, "--tp needs at least two devices")
    engine_kw = dict(SERVE_ENGINE, num_pages=512)
    rng = np.random.default_rng(SEED + 3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (300, 180)]
    init = functools.partial(M.init, cfg)
    key = jax.random.key(SEED)
    outputs = {}
    for tp in (1, 2):
        if tp == 1:
            params = jax.jit(init)(key)
        else:
            # initialise straight into the mesh placement (replicated),
            # so device 0 never holds a second copy of the weights
            params = jax.jit(init, out_shardings=NamedSharding(
                make_mesh((tp,), ("tp",)), PartitionSpec()))(key)
        reqs = [Request(prompt=p, max_new_tokens=16, seed=i)
                for i, p in enumerate(prompts)]
        eng, reqs = serve_phase(cfg, params, "pallas",
                                engine_kw=dict(engine_kw, tp=tp),
                                requests=reqs, rate=len(reqs))
        if tp > 1:
            pool = eng.cache["attn"]["kv_pages"]
            devs = sorted(s.device.id for s in pool.addressable_shards)
            print(f"[tp={tp}] KV pool shards on devices {devs}, "
                  f"mesh {[dv.id for dv in eng.executor.mesh.devices.flat]}")
            check(len(set(devs)) == tp,
                  f"tp={tp} KV pool shards sit on devices {devs}")
        outputs[tp] = [list(r.output) for r in reqs]
        del eng, params
        gc.collect()
    same = sum(a == b for a, b in zip(outputs[1], outputs[2]))
    print(f"tp=1 vs tp=2: {same}/{len(outputs[1])} requests token-identical")
    check(outputs[1] == outputs[2], "tp=2 outputs differ from tp=1")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", action="store_true",
                    help="run only the tp=1 vs tp=2 comparison "
                         "(needs two or more chips)")
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")

    cache_events: collections.Counter = collections.Counter()

    def count_cache(event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            cache_events[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(count_cache)
    print(f"compile cache: {enable_compile_cache()}")

    cfg = ARCHS[ARCH]
    print(f"model: {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_q_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} {cfg.dtype}, weights from seed {SEED}")
    if args.tp:
        tp_phase(cfg)
    else:
        params = jax.jit(functools.partial(M.init, cfg))(
            jax.random.key(SEED))
        eng, reqs = serve_phase(cfg, params, "pallas")
        check_pallas_engine(eng)
        pallas_out = [list(r.output) for r in reqs]
        del eng
        gc.collect()
        eng, reqs = serve_phase(cfg, params, "xla")
        same = sum(a == b for a, b in
                   zip(pallas_out, (r.output for r in reqs)))
        print(f"pallas vs xla greedy outputs: {same}/{len(reqs)} requests "
              f"token-identical")
        del eng
        gc.collect()
        mixed_step_check(cfg, params)
        kernel_reference_check(cfg)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}")
    print(f"compile cache events: {dict(cache_events)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
