"""Production training launcher (mesh-distributed train loop).

    python -m repro.launch.train --arch smollm-135m [--multi-pod] ...

Fault tolerance:
  * atomic async checkpoints every --ckpt-every steps with data-iterator
    state; restart resumes bit-exact (tests/test_training.py);
  * SIGTERM/preemption hook: one final synchronous checkpoint before exit
    (cloud TPU preemption notice);
  * elastic restart: checkpoints store unsharded leaves, restore device_puts
    them against the *current* mesh's shardings — resuming 2-pod training on
    1 pod (or vice versa) only changes the batch sharding;
  * stragglers: synchronous SPMD steps have no per-step resync point; the
    mitigation ladder is (1) XLA latency-hiding overlap (flags in mesh.py),
    (2) pre-dispatch of N+1 steps (jax dispatch queue), (3) replacing the
    slow host and resuming from the last checkpoint — documented here
    because a CPU host cannot demonstrate it.
"""
from __future__ import annotations

import argparse
import signal

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.configs.registry import reduced
from repro.distributed import param_sharding as PS
from repro.distributed import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.training import checkpoint as C
from repro.training.checkpoint import AsyncCheckpointer
from repro.training.data import DataState, MarkovDataset
from repro.training.trainer import (
    make_train_state, make_train_state_abstract, make_train_step,
)

FSDP_ARCHS = {"llama3-405b", "llama4-maverick-400b-a17b", "deepseek-v2-236b"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-friendly)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    if n_dev >= 256:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:  # whatever this host offers (tests / single chip)
        mesh = SH.make_mesh((n_dev, 1), ("data", "model"))
    rules = SH.make_rules(multi_pod=args.multi_pod,
                          fsdp=cfg.name in FSDP_ARCHS, sp=True)
    batch_axes = ("pod", "data") if args.multi_pod else ("data",)

    with SH.use_rules(mesh, rules):
        step_fn = make_train_step(cfg, base_lr=args.lr, warmup=20,
                                  total_steps=args.steps,
                                  microbatches=args.microbatches)
        state_abs = make_train_state_abstract(cfg)
        state_sh = PS.assign_param_shardings(
            state_abs, mesh=mesh, fsdp=cfg.name in FSDP_ARCHS,
            batch_axes=batch_axes)
        ds = MarkovDataset(cfg.vocab_size, seed=1)
        start = C.latest_step(args.ckpt_dir) if args.ckpt_dir else None
        if start is not None:
            state, start, dstate = C.restore(args.ckpt_dir, state_abs)
            state = jax.device_put(state, state_sh)  # elastic re-shard
            print(f"resumed at step {start}")
        else:
            state = jax.jit(
                lambda k: make_train_state(cfg, k), out_shardings=state_sh
            )(jax.random.key(0))
            dstate = DataState(seed=1)
            start = 0

        ckpt = AsyncCheckpointer()
        stop = {"now": False}

        def _sigterm(_sig, _frm):  # preemption notice -> final checkpoint
            stop["now"] = True

        signal.signal(signal.SIGTERM, _sigterm)

        for i in range(start, args.steps):
            batch, dstate = ds.batch(dstate, batch_size=args.global_batch,
                                     seq_len=args.seq)
            state, metrics = step_fn(
                state, {k: jnp.asarray(v) for k, v in batch.items()})
            if args.ckpt_dir and ((i + 1) % args.ckpt_every == 0
                                  or stop["now"] or i + 1 == args.steps):
                ckpt.save_async(args.ckpt_dir, state, step=i + 1,
                                data_state=dstate)
            if i % 10 == 0 or stop["now"]:
                print(f"step {i} loss {float(metrics['loss']):.4f}",
                      flush=True)
            if stop["now"]:
                print("preemption signal: checkpointed, exiting")
                break
        ckpt.wait()


if __name__ == "__main__":
    main()
