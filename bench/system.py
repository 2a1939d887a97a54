"""The system under test, seen from the benchmark.

The only module of the benchmark that imports the program (`src/repro`).
It checks that the program's registry entry states what the configuration
file states (and is of the family its reference module covers), checks
the weights the benchmark made against the program's parameter tree,
builds the `Engine` the window drives, and warms up every executable the cell's
traffic can reach through the engine's public API (`submit` / `step`).
"""
from __future__ import annotations

import dataclasses
import logging

import jax
import numpy as np

from bench.configs import reference
from repro.configs import get_config
from repro.core.attention import heuristics
from repro.models import model as M
from repro.serving.engine import Engine
from repro.serving.request import Request, State
from repro.utils.compile_cache import enable_compile_cache  # noqa: F401
from repro.utils.misc import next_power_of_2

log = logging.getLogger("bench")

# configuration-file key -> the program's ModelConfig attribute
PUBLISHED = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_q_heads",
    "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias", "head_dim": "head_dim",
}
DTYPES = {"bfloat16": "bfloat16"}


def model_config(spec: dict):
    """The program's registry config for this file, with the keys the file
    lists under `reduced` set to the file's values and the engine's KV
    page size; any other departure from the sizes the file states is
    refused."""
    cfg = get_config(spec["arch"])
    cfg = cfg.replace(**{PUBLISHED[k]: spec[k] for k in spec["reduced"]})
    if "page_size" in spec.get("engine", {}):
        cfg = cfg.replace(page_size=spec["engine"]["page_size"])
    got = lambda a: (cfg.resolved_head_dim if a == "head_dim"  # noqa: E731
                     else getattr(cfg, a))
    bad = [f"{k}: file {spec[k]!r}, program {got(a)!r}"
           for k, a in PUBLISHED.items() if k in spec and got(a) != spec[k]]
    if DTYPES.get(spec["torch_dtype"]) != cfg.dtype:
        bad.append(f"torch_dtype: file {spec['torch_dtype']}, "
                   f"program {cfg.dtype}")
    bad += reference(spec).program_mismatch(cfg)
    if bad:
        raise SystemExit("configuration and program disagree: "
                         + "; ".join(bad))
    return cfg


def check_params(cfg, params) -> None:
    """Same tree, shapes and dtypes as the program's own initialiser."""
    want = M.init_abstract(cfg)
    got = jax.eval_shape(lambda p: p, params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("benchmark weights do not match the program's "
                         "parameter tree")


def make_engine(cfg, params, eng_spec: dict) -> Engine:
    """The engine the window drives.  Its sampling seed is fixed: the
    program bakes it into every executable, so a per-run seed would make
    every run compile anew.  Each request's own sampling stream (drawn
    from the run's seed by the traffic generator) varies the draws."""
    return Engine(
        cfg, params, max_seqs=eng_spec["max_seqs"],
        num_pages=eng_spec["num_pages"],
        max_model_len=eng_spec["max_model_len"],
        max_prefill_tokens=eng_spec["max_prefill_tokens"],
        backend=eng_spec["backend"], enable_prefix_caching=True,
        enable_chunked_prefill=True, fused_sampling=True, seed=0)


def new_request(prompt, max_new_tokens, temperature=0.0, top_p=1.0,
                stream=0) -> Request:
    return Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                   temperature=temperature, top_p=top_p, seed=stream)


def is_finished(req: Request) -> bool:
    return req.state is State.FINISHED


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Probe:
    decode_rows: int  # D holders decoding
    ctx_bucket: int  # power-of-two context bucket C of the step
    chunk: int  # fresh prompt tokens packed beside them (0: decode only)


def reachable(eng: Engine, eng_spec: dict) -> dict[tuple, Probe]:
    """Every (token bucket, KernelConfig) the engine can launch for this
    cell's max_seqs, context and chunk budget, each with the cheapest step
    of D decode rows at context bucket C plus one fresh chunk that reaches
    it.  The profile is the one `Engine._unified_profile` builds for such
    a step; the kernel config is the installed tree's choice."""
    cfg = eng.cfg
    ms, ps = eng.max_seqs, cfg.page_size
    budget = eng_spec["max_prefill_tokens"]
    group = max(1, cfg.num_q_heads // max(cfg.num_kv_heads, 1))
    top = next_power_of_2(eng_spec["max_model_len"])
    buckets = [1 << i for i in range(top.bit_length())
               if 32 <= 1 << i <= top]
    chunks = sorted({0} | {n for b in (1 << i for i in range(
        next_power_of_2(budget).bit_length()))
        for n in (b // 2 + 1, b) if 1 <= n <= budget})
    out: dict[tuple, Probe] = {}
    for d in range(ms + 1):
        for n in chunks:
            rows = d + (1 if n else 0)
            if rows == 0 or rows > ms or (n and d + n > budget):
                continue
            for c in buckets:
                if n > c or (d == 0 and c != max(32, next_power_of_2(n))):
                    continue
                total = d + n
                prof = heuristics.BatchProfile(
                    num_seqs=rows, max_context=c, group=group,
                    page_size=ps, decode_share=d / rows,
                    avg_query_len=next_power_of_2(max(total // rows, 1)),
                    total_tokens=next_power_of_2(total), tp=1)
                kcfg = heuristics.validate(heuristics.unified_config(prof),
                                           ps)
                t = ms + (max(next_power_of_2(n), ps) if n else 0)
                probe = Probe(d, c, n)
                old = out.get((t, kcfg))
                if old is None or (d * c, d, c) < (
                        old.decode_rows * old.ctx_bucket, old.decode_rows,
                        old.ctx_bucket):
                    out[(t, kcfg)] = probe
    return out


def _drain(eng: Engine, limit: int = 100_000) -> None:
    steps = 0
    while eng.sched.has_work:
        eng.step()
        steps += 1
        if steps > limit:
            raise RuntimeError("warm-up did not drain")


def warm_up(eng: Engine, eng_spec: dict, vocab: int, seed: int) -> int:
    """Launch every reachable (token bucket, KernelConfig) once; returns
    how many executables the engine then holds."""
    targets = reachable(eng, eng_spec)
    groups: dict[tuple[int, int], set[int]] = {}
    for p in targets.values():
        groups.setdefault((p.decode_rows, p.ctx_bucket), set()).add(p.chunk)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 7])
    budget = eng_spec["max_prefill_tokens"]
    for (d, c), chunks in sorted(groups.items()):
        if d:
            slack = len(chunks) + 4 + -(-d * c // max(budget - d, 1))
            h = c - slack - 1
            if h + 1 <= c // 2 or h < 1:
                log.warning("warm-up: no holder length for D=%d C=%d", d, c)
                continue
            holders = [new_request(rng.integers(1, vocab, size=h), slack,
                                   stream=i) for i in range(d)]
            for r in holders:
                eng.submit(r)
            while not all(r.prefill_done for r in holders):
                eng.step()
        for n in sorted(chunks):
            if n:
                eng.submit(new_request(rng.integers(1, vocab, size=n), 1))
            eng.step()
        _drain(eng)
    missing = [k for k in targets
               if ("unified", 2 * eng.max_seqs) + k not in eng.executables]
    if missing:
        log.warning("warm-up: %d reachable executables not launched: %s",
                    len(missing), missing)
    return len(eng.executables)
