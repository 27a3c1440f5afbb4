"""Fault-tolerant distributed training driver.

End-to-end loop wiring every substrate together: deterministic data
pipeline -> pjit'd train step (FSDP + TP sharding rules) -> AdamW on
latent binarized weights -> async atomic checkpoints -> auto-resume.

Fault tolerance contract (tested in tests/test_ft.py):
  * kill the process at any step; rerunning with the same --ckpt-dir
    resumes from the latest complete checkpoint and reproduces exactly
    the step sequence an uninterrupted run would have produced;
  * restore re-shards onto whatever mesh the new process has (elastic:
    device count may change between runs);
  * a step-time watchdog records straggler events.

CPU-runnable:  PYTHONPATH=src python -m repro.launch.train \
    --arch qwen1.5-0.5b --reduced --steps 20 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs import get_arch, reduced
from repro.data import DataConfig, DataIterator
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.optim import adamw
from repro.runtime import sharding as shd
from repro.runtime.straggler import StepWatchdog


def make_train_step(cfg, opt_cfg):
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch))(params)
        params, opt_state, metrics = adamw.apply_updates(
            params, opt_state, grads, opt_cfg)
        return params, opt_state, dict(metrics, loss=loss)
    return step


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 10,
          lr: float = 3e-4, mesh=None, seed: int = 0,
          log_every: int = 5, log_fn=print,
          run_steps: Optional[int] = None) -> Dict[str, Any]:
    """run_steps: execute at most this many steps this invocation
    (simulated preemption — the schedule horizon stays `steps`)."""
    mesh = mesh or make_local_mesh()
    opt_cfg = adamw.AdamWConfig(lr=lr, total_steps=max(steps, 2),
                                warmup_steps=max(2, steps // 10))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch, seed=seed)

    with mesh:
        params = M.init_params(jax.random.PRNGKey(seed), cfg)
        opt_state = adamw.init(params)
        specs = shd.param_specs(params, mesh,
                                stacked_prefixes=("decoder", "encoder"))
        p_shard = shd.named(specs, mesh)
        o_shard = shd.named(adamw.OptState(
            step=jax.sharding.PartitionSpec(), m=specs, v=specs), mesh)
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, o_shard)

        start_step = 0
        data = DataIterator(dcfg)
        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        if ckpt_dir and latest_step(ckpt_dir) is not None:
            (params, opt_state), meta = restore(
                ckpt_dir, (params, opt_state),
                shardings=(p_shard, o_shard))
            start_step = int(meta["extra"]["step"])
            data = DataIterator.from_state(dcfg, meta["extra"]["data"],
                                           shard=0, n_shards=1)
            log_fn(f"[resume] from step {start_step}")

        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg),
            in_shardings=(p_shard, o_shard, None),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1))

        wd = StepWatchdog()
        losses = []
        end = steps if run_steps is None else min(steps,
                                                  start_step + run_steps)
        for it in range(start_step, end):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            wd.start()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            slow = wd.stop()
            losses.append(loss)
            if it % log_every == 0 or it == steps - 1:
                log_fn(f"step {it:5d} loss {loss:.4f} "
                       f"gnorm {float(metrics['grad_norm']):.3f}"
                       + (" [straggler]" if slow else ""))
            if ckpt and ((it + 1) % ckpt_every == 0 or it == end - 1):
                ckpt.save(it + 1, (params, opt_state),
                          extra={"step": it + 1,
                                 "data": data.state_dict()})
        if ckpt:
            ckpt.wait()
    return {"losses": losses, "params": params, "opt_state": opt_state,
            "straggler_events": wd.flags}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg).replace(dtype="float32")
    out = train(cfg, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, lr=args.lr, seed=args.seed)
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
