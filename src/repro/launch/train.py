"""End-to-end trainer: data pipeline -> (coded-DP | plain) train loop with
checkpoint/restart, LEA straggler mitigation, and optional gradient
compression.

CPU-runnable examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b --smoke \\
      --steps 20 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt --coded-dp
Resume is automatic: re-running with the same --ckpt-dir picks up the latest
checkpoint, the data cursor, and the LEA estimator counts.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ShapeCell, get_config, get_smoke_config
from repro.data import DataPipeline
from repro.models import api
from repro.optim import adamw_update, cosine_warmup
from repro.runtime.compression import make_compressor
from repro.runtime.fault_tolerance import CodedDPConfig, CodedDataParallelExecutor


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--coded-dp", action="store_true",
                    help="LEA-coded microbatch DP with simulated worker dynamics")
    ap.add_argument("--dp-workers", type=int, default=8)
    ap.add_argument("--dp-r", type=int, default=4)
    ap.add_argument("--dp-shards", type=int, default=8)
    ap.add_argument("--compress", default="none", choices=["none", "int8", "topk"])
    args = ap.parse_args(argv)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, microbatch=1)
    cell = ShapeCell("cli", args.seq, args.batch, "train")
    model = api.get_model(cfg)

    pipe = DataPipeline(cfg.vocab_size, args.batch, args.seq, seed=args.seed)
    state = api.init_state(cfg, jax.random.PRNGKey(args.seed))
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    def loss_fn(params, batch):
        return model.train_loss(params, {k: jnp.asarray(v) for k, v in batch.items()}, cfg)

    grad_fn = jax.jit(jax.grad(loss_fn))
    loss_jit = jax.jit(loss_fn)

    executor = None
    if args.coded_dp:
        executor = CodedDataParallelExecutor(
            CodedDPConfig(n_workers=args.dp_workers, r=args.dp_r, k=args.dp_shards),
            lambda p, b: grad_fn(p, b), seed=args.seed,
        )

    comp_state = None
    comp_apply = None
    if args.compress != "none":
        comp_init, comp_apply = make_compressor(args.compress)

    @jax.jit
    def apply_grads(state, grads, step_lr):
        return adamw_update(state, grads, step_lr)

    start_step = 0
    if mgr is not None:
        s, restored, meta = mgr.restore_latest(state)
        if s is not None:
            state = restored
            start_step = s
            pipe.restore(meta["pipeline"])
            if executor is not None and "lea" in meta:
                executor.load_state_dict(meta["lea"])
            print(f"[resume] step {s}")

    history = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = pipe.next()
        grads = None
        if executor is not None:
            grads, info = executor.round(state.params, batch)
            if grads is None:
                history.append({"step": step, "missed_deadline": True})
                print(f"step {step}: deadline MISS "
                      f"(on-time workers {info['on_time_workers']})")
        else:
            grads = grad_fn(state.params, batch)
        if grads is not None:
            if comp_apply is not None:
                if comp_state is None:
                    comp_state = jax.tree.map(
                        lambda g: jnp.zeros(g.shape, jnp.float32), grads)
                grads, comp_state = comp_apply(grads, comp_state)
            lr = cosine_warmup(jnp.asarray(step + 1), peak_lr=args.lr, warmup=5,
                               total=args.steps)
            state, metrics = apply_grads(state, grads, lr)
            loss = float(loss_jit(state.params, batch))
            history.append({"step": step, "loss": loss})
            print(f"step {step}: loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
        # checkpoint regardless of deadline misses (a miss must not stall FT)
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            meta = {"pipeline": pipe.state.to_dict()}
            if executor is not None:
                meta["lea"] = executor.state_dict()
            mgr.save_async(step + 1, state, extra_meta=meta)
    if mgr is not None:
        mgr.wait()
    out = {
        "history": history,
        "steps_done": len([h for h in history if "loss" in h]),
        "wall_s": time.time() - t0,
    }
    if executor is not None:
        out["timely_throughput"] = executor.timely_throughput
        print(f"timely computation throughput: {executor.timely_throughput:.3f}")
    return out


if __name__ == "__main__":
    # persistent XLA compile cache across restarts (repro.launch.cache);
    # process-wide, so the CLI entry sets it rather than main()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
