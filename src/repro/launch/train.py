"""End-to-end training driver with always-on StageFrontier monitoring.

    PYTHONPATH=src python -m repro.launch.train \
        --arch paper-gpt-125m --steps 200 --batch 8 --seq 512 \
        --ckpt-dir /tmp/ckpt --resume auto --window 50

Fused-step taxonomy (DESIGN.md §3): data.next_wait / step.dispatch /
step.device_wait / callbacks / ckpt / residual.  The monitor gathers
windows, labels them, emits evidence packets, and the policy can arm a
one-window `jax.profiler` trace (the paper's router-to-profiler loop).
Checkpoint/restart: `--resume auto` restarts from the newest valid
manifest, including the data-pipeline cursor.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.ckpt import latest_step, restore_checkpoint, save_checkpoint
from ..configs import get_config
from ..core.contract import fused_schema
from ..data.pipeline import PrefetchPipeline, SyntheticTokens
from ..distributed.policy import Action
from ..distributed.sharding import BASELINE_PLAN, ShardingPlan
from ..models import build_model
from ..optim.adamw import AdamWConfig
from ..telemetry.collector import Monitor
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh
from .steps import batch_shardings, build_train_step, init_train_state


def make_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="paper-gpt-125m")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--reduced", action="store_true", help="smoke-scale config")
    p.add_argument("--window", type=int, default=50)
    p.add_argument("--event-q", type=float, default=0.05)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--resume", default="no", choices=["no", "auto"])
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--profile-dir", default="", help="arm router-triggered traces")
    p.add_argument("--data-stall-ms", type=float, default=0.0,
                   help="inject a data-pipeline stall every 10 steps (demo)")
    p.add_argument("--log-every", type=int, default=20)
    return p


def run(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(
        cfg,
        attn_q_chunk=min(cfg.attn_q_chunk, args.seq),
        attn_kv_chunk=min(cfg.attn_kv_chunk, args.seq),
        ssm_chunk=min(cfg.ssm_chunk, args.seq),
    )
    model = build_model(cfg)
    mesh = make_local_mesh()
    schema = fused_schema(world_size=1)

    profile_state = {"active_until": -1}

    def on_action(action: Action) -> None:
        print(f"[policy] {action.kind}: {action.reason}")
        if action.kind == "trigger_profiler" and args.profile_dir:
            os.makedirs(args.profile_dir, exist_ok=True)
            jax.profiler.start_trace(args.profile_dir)
            profile_state["active_until"] = step_counter["i"] + 10

    monitor = Monitor(
        schema,
        window_steps=args.window,
        event_q=args.event_q,
        on_action=on_action,
    )

    opt_cfg = AdamWConfig(peak_lr=args.lr, warmup_steps=max(10, args.steps // 20),
                          decay_steps=args.steps)
    # the batch is split over the mesh's `data` axis: with `accum`,
    # host-shaped [accum, micro, seq], one microbatch per scan step
    micro = args.batch // args.accum
    batch_specs = {
        k: jax.ShapeDtypeStruct((micro, args.seq), jnp.int32)
        for k in ("tokens", "labels")
    }
    batch_sh = batch_shardings(mesh, BASELINE_PLAN, batch_specs, args.accum)
    host_shape = (args.accum, micro, args.seq) if args.accum > 1 else None
    with mesh:
        train_step, state_sh = build_train_step(
            model, mesh, BASELINE_PLAN, opt_cfg,
            batch_specs=batch_specs, accum_steps=args.accum,
        )
        state = init_train_state(model, jax.random.PRNGKey(0))

        start = 0
        if args.resume == "auto" and args.ckpt_dir:
            restored = restore_checkpoint(args.ckpt_dir, state)
            if restored is not None:
                state, extra, start = restored
                state = jax.tree.map(jnp.asarray, state)
                print(f"[ckpt] resumed from step {start}")

        stall = None
        if args.data_stall_ms > 0:
            stall = lambda s: (args.data_stall_ms / 1e3) if s % 10 == 0 else 0.0
        source = SyntheticTokens(cfg.vocab_size, args.batch, args.seq, seed=1)
        pipeline = PrefetchPipeline(source, start_cursor=start, stall=stall)

        losses = []
        step_seconds = []
        step_counter = {"i": start}
        prev_metrics = None
        t_train0 = time.perf_counter()
        try:
            for i in range(start, args.steps):
                step_counter["i"] = i
                t_step = time.perf_counter()
                with monitor.step():
                    with monitor.stage("data.next_wait"):
                        # host staging is part of the data path: charged here
                        host_batch = next(pipeline)
                        if host_shape is not None:
                            host_batch = {
                                k: v.reshape(host_shape)
                                for k, v in host_batch.items()
                            }
                        batch = jax.device_put(host_batch, batch_sh)
                    t_dispatch = time.perf_counter()
                    with monitor.stage("step.dispatch_cpu_wall"):
                        state, metrics = train_step(state, batch)
                    monitor.observe_output(
                        metrics["loss"], (time.perf_counter() - t_dispatch) * 1e3
                    )
                    with monitor.stage("step.device_wait_cpu_wall"):
                        # fetch the PREVIOUS step's metrics: this is where
                        # device time becomes host-visible (sync displacement
                        # lands here) while this step's work proceeds async.
                        if prev_metrics is not None:
                            losses.append(float(prev_metrics["loss"]))
                        prev_metrics = metrics
                    with monitor.stage("callbacks.cpu_wall"):
                        if i % args.log_every == 0 and losses:
                            print(f"step {i}: loss {losses[-1]:.4f}")
                    with monitor.stage("ckpt.cpu_wall"):
                        if args.ckpt_dir and i and i % args.ckpt_every == 0:
                            save_checkpoint(
                                args.ckpt_dir,
                                i,
                                jax.device_get(state),
                                extra={"data": pipeline.state()},
                            )
                monitor.end_of_step()
                step_seconds.append(time.perf_counter() - t_step)
                if profile_state["active_until"] == i:
                    jax.profiler.stop_trace()
                    profile_state["active_until"] = -1
                    print(f"[policy] heavy trace captured to {args.profile_dir}")
            losses.append(float(jax.device_get(prev_metrics["loss"])))
        finally:
            pipeline.close()
            if profile_state["active_until"] >= 0:
                jax.profiler.stop_trace()
        train_seconds = time.perf_counter() - t_train0
        if args.ckpt_dir:
            save_checkpoint(
                args.ckpt_dir, args.steps, jax.device_get(state),
                extra={"data": pipeline.state()},
            )

    reports = monitor.aggregator.reports
    summary = {
        "arch": cfg.name,
        "steps": args.steps - start,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "losses": losses,
        "step_seconds": step_seconds,
        "train_seconds": train_seconds,
        "monitor_overhead": monitor.overhead_fraction(train_seconds),
        "monitor_total_overhead": monitor.total_overhead_fraction(train_seconds),
        "monitor_metrics": monitor.metrics.as_dict(),
        "windows": [
            {
                "index": r.window_index,
                "labels": list(r.diagnosis.labels),
                "routing": list(r.diagnosis.routing_stages),
                "shares": [round(s, 4) for s in r.diagnosis.shares],
            }
            for r in reports
        ],
        "actions": [dataclasses.asdict(a) for a in monitor.actions],
    }
    return summary


def main() -> None:
    use_compile_cache()
    args = make_argparser().parse_args()
    summary = run(args)
    print(json.dumps(summary, indent=2, default=str))


if __name__ == "__main__":
    main()
