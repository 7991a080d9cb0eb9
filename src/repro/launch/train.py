"""End-to-end training driver: Colmena-steered LM training.

The Thinker steers a training campaign the way the paper steers
simulation campaigns: the unit task is a *chunk* of K optimizer steps
executed by a stateful worker (params/optimizer live in the worker
registry — the paper's "intelligent initialization"); the steering
agents monitor the loss stream, trigger asynchronous checkpoints,
early-stop on plateau, and recover from (optionally injected) worker
preemptions by restoring from the latest checkpoint.

CPU-sized by default (a few-M-param model); ``--scale`` raises width
toward the ~100M end-to-end config for real hardware.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --steps 200
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 120 \
      --preempt-at 50 --ckpt-dir /tmp/ckpt     # survives a mid-run kill
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from ..configs import get_config, smoke_config
from ..core import (
    BaseThinker,
    FailureInjector,
    LocalColmenaQueues,
    ResourceRequest,
    RetryPolicy,
    TaskServer,
    WorkerPool,
    agent,
    result_processor,
    stateful_task,
)
from ..core.thinker import ResourceCounter
from .compile_cache import use_compile_cache

# Consecutive failures of one chunk before the campaign gives up: the
# server already retries lost workers, so repeats mean the step itself fails.
MAX_CHUNK_FAILURES = 3


def train_config(arch: str, scale: int = 1, seq: int = 64):
    cfg = smoke_config(arch).with_(
        dtype="float32",
        d_model=64 * scale,
        n_heads=4 * scale if 64 * scale % (4 * scale) == 0 else 4,
        head_dim=16,
        d_ff=128 * scale,
        vocab_size=2048,
        grad_accum=1,
    )
    return cfg


@stateful_task
def train_chunk(arch: str, scale: int, start_step: int, k: int, seq: int,
                batch: int, lr: float, ckpt_dir: Optional[str] = None,
                registry: Optional[dict] = None) -> Dict[str, Any]:
    """Run K optimizer steps; worker registry caches the full train state."""
    import jax

    from ..models import build_model
    from ..train import (CheckpointManager, OptimizerConfig, SyntheticLM,
                         init_train_state, make_train_step)

    state = registry.get("train_state")
    if state is None or state["arch"] != arch:
        cfg = train_config(arch, scale, seq)
        model = build_model(cfg)
        oc = OptimizerConfig(lr=lr, warmup_steps=20, total_steps=10_000)
        ck = CheckpointManager(ckpt_dir) if ckpt_dir else None
        params = opt = None
        resume_step = 0
        if ck and ck.latest_step() is not None:
            # fault recovery: restore the newest checkpoint
            params, opt = init_train_state(model, oc, jax.random.PRNGKey(0))
            restored, extra = ck.restore(ck.latest_step(), {"p": params, "o": opt})
            params, opt = restored["p"], restored["o"]
            resume_step = int(extra.get("step", ck.latest_step()))
        else:
            params, opt = init_train_state(model, oc, jax.random.PRNGKey(0))
        state = registry["train_state"] = {
            "arch": arch,
            "cfg": cfg,
            "model": model,
            "params": params,
            "opt": opt,
            "step_fn": jax.jit(make_train_step(model, oc)),
            "data": SyntheticLM(cfg, seq_len=seq, batch=batch),
            "ck": ck,
            "step": resume_step,
        }

    import jax.numpy as jnp

    losses = []
    t0 = time.monotonic()
    for _ in range(k):
        b = {kk: jnp.asarray(v) for kk, v in state["data"].batch_at(state["step"]).items()}
        state["params"], state["opt"], metrics = state["step_fn"](state["params"], state["opt"], b)
        state["step"] += 1
        losses.append(float(metrics["loss"]))
    return {
        "start_step": state["step"] - k,
        "end_step": state["step"],
        "losses": losses,
        "steps_per_s": k / (time.monotonic() - t0),
    }


@stateful_task
def save_checkpoint(registry: Optional[dict] = None) -> Dict[str, Any]:
    """Async sharded checkpoint of the worker-resident train state."""
    state = registry.get("train_state")
    if state is None or state["ck"] is None:
        return {"saved": False}
    state["ck"].save_async(state["step"], {"p": state["params"], "o": state["opt"]},
                           extra={"step": state["step"]})
    return {"saved": True, "step": state["step"]}


class TrainingThinker(BaseThinker):
    """Steers the campaign: chunk submission, loss tracking, checkpoint
    cadence, plateau early-stop. A chunk that fails ``MAX_CHUNK_FAILURES``
    times in a row ends the campaign with ``error`` set."""

    def __init__(self, queues, *, arch: str, scale: int, total_steps: int,
                 chunk: int, seq: int, batch: int, lr: float,
                 ckpt_dir: Optional[str], ckpt_every: int,
                 preempt_at: Optional[int] = None, server=None):
        super().__init__(queues, ResourceCounter(1))
        self.arch, self.scale = arch, scale
        self.total_steps, self.chunk = total_steps, chunk
        self.seq, self.batch, self.lr = seq, batch, lr
        self.ckpt_dir, self.ckpt_every = ckpt_dir, ckpt_every
        self.preempt_at = preempt_at
        self.server = server
        self.losses: List[float] = []
        self.next_step = 0
        self.last_ckpt = 0
        self.preempted = False
        self.chunk_failures = 0
        self.error: Optional[str] = None

    def _submit_chunk(self):
        k = min(self.chunk, self.total_steps - self.next_step)
        self.queues.send_inputs(
            self.arch, self.scale, self.next_step, k, self.seq, self.batch,
            self.lr, self.ckpt_dir,
            method="train_chunk", topic="default",
            resources=ResourceRequest(pool="default"),
        )

    @agent(startup=True)
    def kickoff(self):
        self._submit_chunk()

    @result_processor()
    def on_chunk(self, result):
        if result.method == "save_checkpoint":
            return
        if not result.success:
            self.chunk_failures += 1
            if self.chunk_failures >= MAX_CHUNK_FAILURES:
                self.error = (f"chunk at step {self.next_step} failed "
                              f"{self.chunk_failures} times: {result.failure_info}")
                self.logger.error("%s; giving up", self.error)
                self.done.set()
                return
            self.logger.warning("chunk failed (%s); resubmitting", result.failure_info)
            self._submit_chunk()
            return
        self.chunk_failures = 0
        out = result.value
        self.losses.extend(out["losses"])
        self.next_step = out["end_step"]

        # simulated preemption: kill the training node mid-campaign once
        if (self.preempt_at is not None and not self.preempted
                and self.next_step >= self.preempt_at):
            self.preempted = True
            pool = self.server.pools["default"]
            for w in pool.worker_states():
                pool.kill_worker(w.worker_id)
            self.logger.warning("injected preemption at step %d", self.next_step)

        if self.ckpt_dir and self.next_step - self.last_ckpt >= self.ckpt_every:
            self.last_ckpt = self.next_step
            self.queues.send_inputs(method="save_checkpoint")

        if self.next_step >= self.total_steps:
            self.done.set()
            return
        self._submit_chunk()


def run(arch: str = "gemma-2b", steps: int = 100, chunk: int = 10, scale: int = 1,
        seq: int = 64, batch: int = 8, lr: float = 3e-3,
        ckpt_dir: Optional[str] = None, ckpt_every: int = 40,
        preempt_at: Optional[int] = None) -> Dict[str, Any]:
    queues = LocalColmenaQueues()
    server = TaskServer(
        queues,
        {"train_chunk": train_chunk, "save_checkpoint": save_checkpoint},
        n_workers=1,
        retry=RetryPolicy(max_retries=4),
        heartbeat_timeout_s=2.0,
        straggler=None,
    )
    thinker = TrainingThinker(
        queues, arch=arch, scale=scale, total_steps=steps, chunk=chunk,
        seq=seq, batch=batch, lr=lr, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        preempt_at=preempt_at, server=server,
    )
    server.start()
    t0 = time.monotonic()
    thinker.run(timeout=3600)
    wall = time.monotonic() - t0
    server.stop()
    losses = thinker.losses
    return {
        "arch": arch,
        "steps": len(losses),
        "first_loss": losses[0] if losses else None,
        "final_loss": float(np.mean(losses[-10:])) if losses else None,
        "wall_s": wall,
        "preempted": thinker.preempted,
        "workers_replaced": server.metrics.workers_replaced,
        "tasks_retried": server.metrics.tasks_retried,
        "error": thinker.error,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--chunk", type=int, default=10)
    ap.add_argument("--scale", type=int, default=1, help="width multiplier (4 ~= 100M params)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=40)
    ap.add_argument("--preempt-at", type=int, default=None,
                    help="inject a node failure at this step (tests recovery)")
    args = ap.parse_args()
    use_compile_cache()
    report = run(arch=args.arch, steps=args.steps, chunk=args.chunk, scale=args.scale,
                 seq=args.seq, batch=args.batch, lr=args.lr, ckpt_dir=args.ckpt_dir,
                 ckpt_every=args.ckpt_every, preempt_at=args.preempt_at)
    print(json.dumps(report, indent=2))
    if report["error"] or report["steps"] < args.steps:
        sys.exit(1)


if __name__ == "__main__":
    main()
