#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: trainer + fleet service
    python chip_smoke.py --four-chips  # the cross-chip paths only (4 chips)

One process drives the chip; it starts no child that touches JAX.  With
no option it runs, on one TPU chip:

  train        `repro.launch.train.run` at paper-gpt-125m's published
               widths (batch 8, seq 1024, 30 steps, Monitor windows of
               10): every loss finite, the last below the first, at least
               two labelled windows;
  train-ref    the initial parameters' loss on one fixed batch, on the
               chip and on the CPU device of the same process;
  fleet        `repro.launch.serve_fleet.run` with 64 jobs x 128 ranks,
               20-step windows, 3 rounds, a shared faulted host: routing
               recovers the injected stage and rank of >= 90% of the
               faulted jobs, one fleet incident names the shared host, and
               the tick lowers to a native kernel (`tpu_custom_call`);
  fleet-ref    `fused_fleet_tick` on the chip against `fused_tick_ref` on
               the CPU device, every family of one [64, 20, 128, 6] tensor.

With `--four-chips` it runs only the paths that span chips: the train
step on a 4-chip data mesh against one chip at the same global batch,
and `ShardedFleetService(shards=4)` against one `FleetService`.

Any failed phase exits non-zero.  Only a clean run prints, as the last
line, `{"ok": true, "device": {"platform", "kind", "count"}}`.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: |loss_tpu - loss_cpu| bound: the model computes in bf16, and XLA:TPU
#: and XLA:CPU round bf16 activations at different points (the TPU keeps
#: f32 inside fusions), so the same parameters give losses that differ
#: far below 1e-2 at a loss of ~ln(50304) = 10.8; a wrong layer, mask or
#: sharding moves it by more
LOSS_ATOL = 1e-2
#: float fields of the fleet tick may differ from the CPU oracle by this
#: many units in the last place: XLA:TPU orders and rounds the epilog's
#: reductions and divisions (shares, gains, duty) its own way.  The trend
#: slope, (n - tbar) * sum_e - sum_prefix over a constant, cancels most
#: of its operands' bits, so it is held to this many ulps of its
#: operands: XLA:CPU contracts the multiply-subtract into one FMA, the
#: TPU rounds the product first
FLOAT_MAX_ULP = 8

TRAIN_ARGS = ["--arch", "paper-gpt-125m", "--batch", "8", "--seq", "1024",
              "--steps", "30", "--window", "10", "--log-every", "10"]
FLEET_ARGS = ["--jobs", "64", "--ranks", "128", "--window", "20",
              "--rounds", "3", "--topology", "shared"]


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


class CompileClock:
    """Seconds JAX spends in backend compiles (or persistent-cache reads),
    and persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# -- checks shared by the phases (plain functions: they also run on CPU) ----


def faulted_jobs(fleet_args) -> dict[str, int]:
    """job id -> injected faulted rank, as `serve_fleet._build_jobs` marks
    them (every `fault_every`-th job, rank `hidden_fault_rank`)."""
    from repro.launch.serve_fleet import SYNC_PROFILES
    from repro.sim.scenarios import hidden_fault_rank

    names = list(SYNC_PROFILES)
    return {
        f"job-{j:03d}-{names[j % len(names)]}": hidden_fault_rank(
            j, fleet_args.ranks
        )
        for j in range(fleet_args.jobs)
        if fleet_args.fault_every > 0 and j % fleet_args.fault_every == 0
    }


def check_fleet_answer(out: dict, truth: dict[str, int]) -> dict:
    """Routing recovers >= 90% of the injected (stage, rank) faults, and
    exactly one fleet incident names the shared host."""
    from repro.launch.serve_fleet import SHARED_HOST

    routed = {r["job"]: (r["stage"], r["rank"]) for r in out["routing"]}
    hits = sum(
        routed.get(job) == ("data.next_wait", rank)
        for job, rank in truth.items()
    )
    fleet_incidents = [
        i for i in out["incidents"]
        if i["scope"] == "fleet" and i["host"] == SHARED_HOST
    ]
    summary = {
        "faulted": len(truth), "routed_right": hits,
        "shared_host_incidents": len(fleet_incidents),
    }
    check(hits >= math.ceil(0.9 * len(truth)),
          f"routing found {hits}/{len(truth)} injected faults")
    check(len(fleet_incidents) == 1,
          f"{len(fleet_incidents)} fleet incidents name {SHARED_HOST}")
    return summary


def ulp_distance(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance in float32 units in the last place between a and b
    (0 where both are the same non-finite value)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    same = (a == b) | (np.isnan(a) & np.isnan(b))
    if same.all():
        return 0
    if not (np.isfinite(a[~same]).all() and np.isfinite(b[~same]).all()):
        return 2**31
    ia = a.view(np.int32).astype(np.int64)
    ib = b.view(np.int32).astype(np.int64)
    # map the sign-magnitude bit patterns onto one monotone integer line
    ia = np.where(ia < 0, -(ia & 0x7FFFFFFF), ia)
    ib = np.where(ib < 0, -(ib & 0x7FFFFFFF), ib)
    return int(np.abs(ia - ib)[~same].max())


def compare_ticks(got, want) -> tuple[dict[str, int], dict[str, int]]:
    """(integer field -> mismatch count, float field -> ulp distance)."""
    ints, floats = {}, {}
    for fam in ("frontier", "whatif", "regimes", "coact"):
        pg, pw = getattr(got, fam), getattr(want, fam)
        check((pg is None) == (pw is None), f"{fam}: presence differs")
        if pg is None:
            continue
        for field in pg._fields:
            g = np.asarray(getattr(pg, field))
            w = np.asarray(getattr(pw, field))
            check(g.shape == w.shape, f"{fam}.{field}: {g.shape} vs {w.shape}")
            if np.issubdtype(g.dtype, np.floating):
                floats[f"{fam}.{field}"] = ulp_distance(g, w)
            else:
                ints[f"{fam}.{field}"] = int((g != w).sum())
    return ints, floats


def slope_operand_ulps(got, want, n: int) -> float:
    """Largest |slope difference| in ulps of the slope's operands,
    (n - tbar) * sum_excess and sum_prefix, scaled by the epilog's
    denominator (`fused._regime_packet`)."""
    tbar, denom = (n - 1) / 2.0, n * (n * n - 1) / 12.0
    scale = np.maximum(np.abs((n - tbar) * want.sum_excess),
                       np.abs(want.sum_prefix)).astype(np.float32)
    unit = np.spacing(np.maximum(scale, np.float32(1e-30))) / denom
    return float((np.abs(got.slope - want.slope) / unit).max())


def fleet_tensor(seed: int = 0, shape=(64, 20, 128, 6)):
    """A seeded [J, N, R, S] window tensor with one slow rank per job, its
    rank->host map and host count."""
    rng = np.random.default_rng(seed)
    j, n, r, s = shape
    d = rng.exponential(0.05, shape).astype(np.float32)
    slow = rng.integers(0, r, j)
    d[np.arange(j), :, slow, 0] += 0.15
    hosts = (np.arange(r) // 8)[None].repeat(j, 0)
    return d, hosts, r // 8


# -- phases -----------------------------------------------------------------


def phase_train() -> dict:
    from repro.configs import get_config
    from repro.launch import train
    from repro.models.attention import attention_path

    cfg = get_config("paper-gpt-125m")
    path = attention_path((8, 1024, cfg.n_heads, cfg.head_dim), cfg.n_kv_heads, None)
    t0 = time.perf_counter()
    summary = train.run(train.make_argparser().parse_args(TRAIN_ARGS))
    wall = time.perf_counter() - t0
    losses = summary["losses"]
    labelled = [w for w in summary["windows"] if w["labels"]]
    steps = summary["step_seconds"]
    out = {
        "first_loss": losses[0], "last_loss": losses[-1],
        "steps": len(losses), "windows_labelled": len(labelled),
        "labels": [w["labels"] for w in labelled],
        "median_step_s_last20": float(np.median(steps[-20:])),
        "first_step_s": steps[0], "wall_s": wall, "attention_path": path,
        "monitor_overhead": summary["monitor_overhead"],
        "monitor_total_overhead": summary["monitor_total_overhead"],
        "monitor_counters": summary["monitor_metrics"]["counters"],
    }
    say("train", **out)
    check(len(losses) == 30, f"{len(losses)} losses for 30 steps")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    check(losses[-1] < losses[0], "the last loss is not below the first")
    check(len(labelled) >= 2, f"{len(labelled)} labelled Monitor windows")
    return out


def _model_and_batch(batch: int, seq: int):
    import jax

    from repro.configs import get_config
    from repro.data.pipeline import SyntheticTokens
    from repro.models import build_model

    cfg = get_config("paper-gpt-125m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    source = SyntheticTokens(cfg.vocab_size, batch, seq, seed=1)
    return cfg, model, params, source


def phase_train_ref() -> dict:
    import jax

    _, model, params, source = _model_and_batch(1, 1024)
    batch = source.batch_at(0)
    loss_fn = jax.jit(model.loss)
    tpu = float(loss_fn(params, batch))
    cpu_dev = jax.devices("cpu")[0]
    with jax.default_device(cpu_dev):
        cpu = float(loss_fn(
            jax.device_put(params, cpu_dev), jax.device_put(batch, cpu_dev)
        ))
    out = {"loss_tpu": tpu, "loss_cpu": cpu, "abs_diff": abs(tpu - cpu),
           "atol": LOSS_ATOL}
    say("train-ref", **out)
    check(math.isfinite(tpu) and abs(tpu - cpu) <= LOSS_ATOL,
          f"chip loss {tpu} vs CPU loss {cpu}")
    return out


def phase_fleet() -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.streaming import WindowStager
    from repro.kernels.frontier import fused_fleet_tick
    from repro.launch import serve_fleet

    args = serve_fleet.make_argparser().parse_args(FLEET_ARGS)
    truth = faulted_jobs(args)
    args.top_k = len(truth)
    t0 = time.perf_counter()
    out = serve_fleet.run(args)
    wall = time.perf_counter() - t0
    result = check_fleet_answer(out, truth)
    # the tick the service dispatched for its DDP cohort: every third
    # job, padded to a power of two; 6 stages, the all-reduce at stage 2
    n_ddp = len(range(0, args.jobs, len(serve_fleet.SYNC_PROFILES)))
    shape = (WindowStager.padded_jobs(n_ddp), args.window, args.ranks, 6)
    lowered = jax.jit(
        lambda d: fused_fleet_tick(d, sync_stages=(2,), with_regimes=False)
    ).lower(jax.ShapeDtypeStruct(shape, jnp.float32))
    native = "tpu_custom_call" in lowered.as_text()
    obs = out.get("obs", {})
    result.update(
        wall_s=wall, native_tick=native,
        routes=[(r["job"], r["stage"], r["rank"]) for r in out["routing"][:3]],
        obs_tick_frontier=obs.get("tick_frontier"),
    )
    say("fleet", **result)
    check(native, "the fused tick did not lower to tpu_custom_call")
    return result


def phase_fleet_ref() -> dict:
    import jax

    from repro.kernels.frontier import fused_fleet_tick, fused_tick_ref

    d, hosts, num_hosts = fleet_tensor()
    kw = dict(sync_stages=(2,), host_index=hosts, num_hosts=num_hosts)
    got = jax.device_get(fused_fleet_tick(d, **kw))
    cpu_dev = jax.devices("cpu")[0]
    with jax.default_device(cpu_dev):
        want = jax.device_get(fused_tick_ref(d, **kw))
    ints, floats = compare_ticks(got, want)
    slope = slope_operand_ulps(got.regimes, want.regimes, d.shape[1])
    out = {
        "bit_equal": sorted(k for k, v in {**ints, **floats}.items() if not v),
        "not_bit_equal_ulp": {k: v for k, v in floats.items() if v},
        "slope_operand_ulp": slope,
    }
    say("fleet-ref", **out)
    check(not any(ints.values()), f"integer fields differ: {ints}")
    bad = {k: v for k, v in floats.items()
           if v > FLOAT_MAX_ULP and k != "regimes.slope"}
    check(not bad, f"float fields beyond {FLOAT_MAX_ULP} ulp: {bad}")
    check(slope <= FLOAT_MAX_ULP,
          f"slope differs by {slope} ulps of its operands")
    return out


def phase_train_four() -> dict:
    """The full-width train step on a 4-chip data mesh against one chip,
    from the same initial state over the same global batches."""
    import jax
    from jax.sharding import Mesh

    from repro.distributed.sharding import BASELINE_PLAN
    from repro.launch.steps import (
        batch_shardings,
        build_train_step,
        init_train_state,
    )

    batch, seq, steps = 32, 1024, 3
    _, model, _, source = _model_and_batch(batch, seq)
    specs = {k: jax.ShapeDtypeStruct((batch, seq), np.int32)
             for k in ("tokens", "labels")}
    devs = jax.devices()
    losses, split = {}, {}
    for name, mesh_devs in (("4chip", devs[:4]), ("1chip", devs[:1])):
        mesh = Mesh(np.asarray(mesh_devs).reshape(-1, 1), ("data", "model"))
        with mesh:
            step, state_sh = build_train_step(
                model, mesh, BASELINE_PLAN, batch_specs=specs
            )
            state = jax.device_put(
                init_train_state(model, jax.random.PRNGKey(0)), state_sh
            )
            batch_sh = batch_shardings(mesh, BASELINE_PLAN, specs)
            out = []
            for i in range(steps):
                b = jax.device_put(source.batch_at(i), batch_sh)
                state, metrics = step(state, b)
                out.append(float(metrics["loss"]))
            tokens = b["tokens"]
            split[name] = {
                "spec": str(tokens.sharding.spec),
                "rows_per_device": tokens.addressable_shards[0].data.shape[0],
            }
            losses[name] = out
    diff = max(abs(a - b) for a, b in zip(losses["4chip"], losses["1chip"]))
    res = {"losses": losses, "max_abs_diff": diff, "batch_split": split}
    say("train-4chip", **res)
    check(split["4chip"]["rows_per_device"] == batch // 4,
          f"batch not split over data: {split['4chip']}")
    check(diff <= LOSS_ATOL, f"4-chip vs 1-chip losses differ by {diff}")
    return res


def phase_fleet_four() -> dict:
    """`ShardedFleetService(shards=4)` on four chips against one
    `FleetService` over the same packets."""
    from repro.launch import serve_fleet

    outs = {}
    for name, extra in (("sharded", ["--shards", "4"]), ("single", [])):
        args = serve_fleet.make_argparser().parse_args(FLEET_ARGS + extra)
        args.top_k = len(faulted_jobs(args))
        out = serve_fleet.run(args)
        out.pop("obs", None)
        outs[name] = out
    sh, si = outs["sharded"], outs["single"]
    same = {
        "routing": sh["routing"] == si["routing"],
        "snapshot": sh["snapshot"] == si["snapshot"],
        "incidents": sh["incidents"] == si["incidents"],
    }
    res = {"equal": same, **check_fleet_answer(sh, faulted_jobs(args))}
    say("fleet-4chip", **res)
    check(all(same.values()), f"sharded vs single differ: {same}")
    return res


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the cross-chip paths, on four chips")
    args = p.parse_args()
    try:
        import jax

        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              "the root of a checkout", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    devs = jax.devices()
    need = 4 if args.four_chips else 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found only "
              f"{devs[0].platform} devices", file=sys.stderr)
        return 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("device", **device, compile_cache=cache_dir)
    clock = CompileClock()
    phases = (
        [phase_train_four, phase_fleet_four] if args.four_chips
        else [phase_train, phase_train_ref, phase_fleet, phase_fleet_ref]
    )
    t0 = time.perf_counter()
    for phase in phases:
        try:
            phase()
        except Exception as e:  # report the phase, then fail the run
            traceback.print_exc()
            print(f"chip_smoke: {phase.__name__} failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 1
    say("done", wall_s=time.perf_counter() - t0,
        compile_s=clock.seconds, cache_hits=clock.cache_hits)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
