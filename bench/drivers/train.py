"""The `train` driver: the program's monitored train step, timed.

Set-up builds one compiled step with its state (weights made on the chip
from the seed by the benchmark), drives it through its first steps with
the same loop as the window, and records what the comparison needs:
the first three losses, the first gradient's leaf norms (from AdamW's
first moment after one step) and the leaf norms of the parameters'
change after three steps.  The window then times steps on the host
clock, keeping each step's `metrics` as the device arrays the step
returned; they are fetched once the window has closed.  After the window
the program's state is freed and the plain reference repeats the first
three steps in float32.

Everything that belongs to one model is in the configuration's
`reference` module, `bench/<reference>.py`, loaded from that file.  It
imports nothing of the program and gives:

- `program_fields(model)`: the program's `ModelConfig` fields for the
  configuration's `model` section;
- `key_for(seed)`, `init_params(key, model, dtype)` and
  `make_params(key, *, model, dtype)` (`model` the section's sorted
  items): the weights, in the tree the program's `model.init` makes;
- `Tokens(vocab_size, batch, seq, seed)` with `batch_at(cursor)`: the
  seeded rows, `{"tokens", "labels"}`;
- `train_step(params, mu, nu, count, tokens, labels, *, model, opt, rows,
  precision)`: one AdamW step in float32, `precision="fp8"` the control;
  returns (params, mu, nu, count, loss, clipped gradient);
- `leaf_norms(tree)`: each leaf's norm by its path;
- `train_flops_per_token(model, seq)`: model FLOPs of one training token.

The loop, the comparison, the control and the result are this file's and
shared by every train configuration.

The loop follows `repro.launch.train.run` line for line (its Monitor
stages, in its order, the previous step's loss fetched after this step's
dispatch); it is copied because `run` fixes its seeds and has no hook
for the harness's clock or profiler.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import resource
import statistics
import time

import harness

LEAF_FLOOR = 1e-3   # leaves whose reference gradient is below this share of the median leaf's
REF_ROWS = 2        # rows of a batch the reference takes at a time, so that it fits
WARMUP_STEPS = 10   # steps in set-up, the first three of them compared with the reference


def program(ref, config: dict, traffic: dict):
    """The program's model, compiled step and Monitor for this cell, whose
    reference module is `ref`."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.contract import fused_schema
    from repro.distributed.sharding import BASELINE_PLAN
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import batch_shardings, build_train_step
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.telemetry.collector import Monitor

    seq = traffic["seq"]
    cfg = dataclasses.replace(
        get_config(config["program_config"]), **ref.program_fields(config["model"]),
        param_dtype=config["dtypes"]["param"], compute_dtype=config["dtypes"]["compute"],
    )
    cfg = dataclasses.replace(
        cfg,
        attn_q_chunk=min(cfg.attn_q_chunk, seq),
        attn_kv_chunk=min(cfg.attn_kv_chunk, seq),
        ssm_chunk=min(cfg.ssm_chunk, seq),
    )
    model = build_model(cfg)
    mesh = make_local_mesh()
    actions = []
    monitor = Monitor(fused_schema(world_size=1), window_steps=traffic["monitor_window"],
                      on_action=actions.append)
    o = config["optimizer"]
    opt_cfg = AdamWConfig(**o)
    specs = {k: jax.ShapeDtypeStruct((traffic["batch"], seq), jnp.int32)
             for k in ("tokens", "labels")}
    batch_sh = batch_shardings(mesh, BASELINE_PLAN, specs)
    with mesh:
        step, state_sh = build_train_step(model, mesh, BASELINE_PLAN, opt_cfg,
                                          batch_specs=specs)
    return model, mesh, monitor, step, state_sh, batch_sh


def make_state(ref, model, state_sh, config: dict, seed: int):
    """The initial weights, made on the device in one call, and the train
    state built from them."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import TrainState
    from repro.optim.adamw import init_opt

    dims, dtype = config["model"], config["dtypes"]["param"]
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    have = jax.eval_shape(lambda k: ref.init_params(k, dims, jnp.dtype(dtype)),
                          ref.key_for(seed))
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))
    ):
        raise harness.BenchError("the program's parameter tree differs from the "
                                 f"layout of bench/{config['reference']}.py")
    p0 = ref.make_params(ref.key_for(seed), model=tuple(sorted(dims.items())), dtype=dtype)
    state = jax.jit(
        # a copy: the step donates the state, and p0 must outlive it
        lambda p: TrainState(params=jax.tree.map(jnp.copy, p), opt=init_opt(p),
                             step=jnp.zeros((), jnp.int32)),
        out_shardings=state_sh,
    )(p0)
    return state, p0


class Loop:
    """`train.run`'s step loop over one state, with the harness's
    annotations around each call into a layer."""

    def __init__(self, step, state, monitor, pipeline, batch_sh, traced: bool):
        import jax

        self.jax, self.step, self.state, self.monitor = jax, step, state, monitor
        self.pipeline, self.batch_sh = pipeline, batch_sh
        self.prev = None
        self.losses: list[float] = []
        #: each step's `metrics` as the step returned them, on the device
        self.metrics: list[dict] = []
        self.step_seconds: list[float] = []
        #: per step: batch fetch, dispatch, loss fetch, Monitor (seconds)
        self.phases: list[tuple] = []
        self.ann = (jax.profiler.TraceAnnotation if traced
                    else lambda name: contextlib.nullcontext())

    def one(self) -> None:
        jax, monitor, ann = self.jax, self.monitor, self.ann
        t_step = time.perf_counter()
        with monitor.step():
            with monitor.stage("data.next_wait"):
                with ann("bench.batch_fetch"):
                    host_batch = next(self.pipeline)
                    batch = jax.device_put(host_batch, self.batch_sh)
            t_dispatch = time.perf_counter()
            with monitor.stage("step.dispatch_cpu_wall"):
                with ann("bench.step_dispatch"):
                    self.state, metrics = self.step(self.state, batch)
                    self.metrics.append(metrics)
            monitor.observe_output(metrics["loss"], (time.perf_counter() - t_dispatch) * 1e3)
            t_fetch = time.perf_counter()
            with monitor.stage("step.device_wait_cpu_wall"):
                with ann("bench.loss_fetch"):
                    if self.prev is not None:
                        self.losses.append(float(self.prev["loss"]))
                    self.prev = metrics
            with monitor.stage("callbacks.cpu_wall"):
                pass
            with monitor.stage("ckpt.cpu_wall"):
                pass
        t_monitor = time.perf_counter()
        with ann("bench.monitor_end_of_step"):
            monitor.end_of_step()
        t_end = time.perf_counter()
        self.step_seconds.append(t_end - t_step)
        self.phases.append((t_dispatch - t_step, t_fetch - t_dispatch,
                            t_monitor - t_fetch, t_end - t_monitor))

    def drain(self) -> None:
        if self.prev is not None:
            self.losses.append(float(self.prev["loss"]))
            self.prev = None


class HostWatch:
    """What the host did to the process over the window: the garbage
    collector's pauses (by `gc.callbacks`), major page faults and
    involuntary context switches (by `getrusage`)."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = None
        self.usage0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._gc)
        self.usage1 = resource.getrusage(resource.RUSAGE_SELF)

    def readings(self) -> dict:
        return {"gc_pauses": len(self.pauses), "gc_max_s": max(self.pauses, default=0.0),
                "gc_total_s": sum(self.pauses),
                "major_faults": self.usage1.ru_majflt - self.usage0.ru_majflt,
                "involuntary_switches": self.usage1.ru_nivcsw - self.usage0.ru_nivcsw}


def first_steps(ref, loop: Loop, config: dict, p0) -> dict:
    """Three steps through the window's own loop, recording the program's
    losses, first-gradient leaf norms and parameter-change leaf norms
    (against the initial weights `p0`)."""
    import jax

    b1 = config["optimizer"]["b1"]
    losses = []
    grad = None
    for i in range(3):
        loop.one()
        losses.append(float(loop.prev["loss"]))
        if i == 0:
            grad = jax.device_get(jax.jit(
                lambda mu: {k: v / (1 - b1) for k, v in ref.leaf_norms(mu).items()}
            )(loop.state.opt.mu))
    change = jax.device_get(change_norms(ref, loop.state.params, p0))
    return {"losses": losses, "grad": grad, "change": change}


def change_norms(ref, params, p0):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda p, q: ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, q)))(params, p0)


def reference_steps(ref, config: dict, traffic: dict, seed: int, precision: str = "f32",
                    rows_kept: int | None = None) -> dict:
    """The reference's first three steps on the same weights and rows.
    `rows_kept` plants the half-batch fault: the mean over the first
    rows only."""
    import jax
    import jax.numpy as jnp

    dims = config["model"]
    model = tuple(sorted(dims.items()))
    opt = tuple(sorted(config["optimizer"].items()))
    params = p0 = ref.make_params(ref.key_for(seed), model=model,
                                  dtype=config["dtypes"]["param"])
    mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    nu = mu
    count = jnp.zeros((), jnp.int32)
    tokens = ref.Tokens(dims["vocab_size"], traffic["batch"], traffic["seq"], seed)
    losses, grad = [], None
    for i in range(3):
        b = tokens.batch_at(i)
        t, l = b["tokens"], b["labels"]
        if rows_kept is not None:
            t, l = t[:rows_kept], l[:rows_kept]
        params, mu, nu, count, loss, g = ref.train_step(
            params, mu, nu, count, jnp.asarray(t), jnp.asarray(l),
            model=model, opt=opt, rows=min(REF_ROWS, t.shape[0]), precision=precision)
        losses.append(float(loss))
        if i == 0:
            grad = jax.device_get(ref.leaf_norms(g))
    change = jax.device_get(change_norms(ref, params, p0))
    return {"losses": losses, "grad": grad, "change": change}


def compare(got: dict, want: dict) -> dict:
    """The three numbers `correct` is decided on: the widest loss gap over
    the three steps, and the worst leaf's gap of first-gradient norms and
    of parameter-change norms, each against the reference's norm of that
    leaf or of the median leaf, whichever is larger.  Leaves whose
    reference gradient is below LEAF_FLOOR of the median leaf's (a bias
    that softmax cancels) move by round-off alone under Adam and are left
    out of the change."""
    loss_gap = max(abs(a - b) if math.isfinite(a) else math.inf
                   for a, b in zip(got["losses"], want["losses"]))
    g_med = statistics.median(float(v) for v in want["grad"].values())
    grad_gap = max(abs(float(got["grad"][k]) - float(v)) / max(float(v), g_med)
                   for k, v in want["grad"].items())
    kept = [k for k, v in want["grad"].items() if float(v) >= LEAF_FLOOR * g_med]
    c_med = statistics.median(float(want["change"][k]) for k in kept)
    change_gap = max(abs(float(got["change"][k]) - float(want["change"][k]))
                     / max(float(want["change"][k]), c_med) for k in kept)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap,
            "leaves_left_out": sorted(set(want["grad"]) - set(kept))}


def run(ctx) -> dict:
    import jax

    from repro.data.pipeline import PrefetchPipeline

    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    ref = harness.reference(config["reference"])
    model, mesh, monitor, step, state_sh, batch_sh = program(ref, config, traffic)
    source = ref.Tokens(config["model"]["vocab_size"], traffic["batch"], traffic["seq"], seed)
    pipeline = PrefetchPipeline(source)
    try:
        with mesh:
            state, p0 = make_state(ref, model, state_sh, config, seed)
            loop = Loop(step, state, monitor, pipeline, batch_sh, ctx.trace)
            del state
            got = first_steps(ref, loop, config, p0)
            del p0
            for _ in range(WARMUP_STEPS - 3):
                loop.one()
            loop.drain()
            jax.block_until_ready(loop.state)
            loop.losses.clear()
            loop.metrics.clear()
            loop.step_seconds.clear()
            loop.phases.clear()
            setup_s = ctx.clock.now()
            compiles0 = ctx.compiles.programs
            mon0 = monitor.monitor_path_seconds
            host = HostWatch()
            with ctx.window() as annotate:
                t0 = time.perf_counter()
                with annotate:
                    while time.perf_counter() - t0 < ctx.seconds:
                        loop.one()
                    loop.drain()
                t1 = time.perf_counter()
            host.close()
            in_window = ctx.compiles.programs - compiles0
            steps = len(loop.step_seconds)
            wall = t1 - t0
            overhead = (monitor.monitor_path_seconds - mon0) / wall
            window_losses = list(loop.losses)
            window_steps = list(loop.step_seconds)
            step_metrics = jax.device_get(loop.metrics)
            slowest = window_steps.index(max(window_steps))
            slowest_phases = loop.phases[slowest]
            labelled = sum(1 for r in monitor.aggregator.reports if r.diagnosis.labels)
    finally:
        pipeline.close()
    memory = harness.memory_peak(ctx.devices)
    del loop
    gc.collect()
    want = reference_steps(ref, config, traffic, seed)
    readings = compare(got, want)
    tokens = steps * traffic["batch"] * traffic["seq"]
    harness.say("train", steps=steps, window_s=wall, compiles_in_window=in_window,
                first_losses=got["losses"], reference_losses=want["losses"],
                leaves_left_out=readings["leaves_left_out"], windows_labelled=labelled,
                step_median_s=statistics.median(window_steps),
                step_max_s=max(window_steps), step_max_at=slowest,
                step_max_phases_s=slowest_phases,
                steps_over_2x_median=sum(x > 2 * statistics.median(window_steps)
                                         for x in window_steps),
                window_minus_steps_s=wall - sum(window_steps), **host.readings())
    return {
        "setup_s": setup_s,
        "end_to_end": {
            "tokens_per_s": tokens / wall,
            "step_p95_ms": harness.p95(window_steps) * 1e3,
        },
        "attempted": steps,
        "failed": sum(1 for x in window_losses if not math.isfinite(x)),
        "checks": {k: harness.check(readings[k], v) for k, v in config["limits"].items()},
        "compiles_in_window": in_window,
        "memory_peak_bytes": memory,
        "layer": {
            "tokens_per_s": tokens / wall,
            "flops_per_token": ref.train_flops_per_token(config["model"], traffic["seq"]),
            "monitor_overhead_fraction": overhead,
            "step_metrics": {k: [m[k].tolist() for m in step_metrics]
                             for k in step_metrics[0] if k != "loss"},
        },
    }


def control(ctx) -> dict:
    """Readings of the control and of the planted half-batch fault, each
    put in the program's place against the float32 reference: the
    reference with float8 products, and the reference's mean over the
    first half of the rows."""
    ref = harness.reference(ctx.config["reference"])
    want = reference_steps(ref, ctx.config, ctx.traffic, ctx.seed)
    low = reference_steps(ref, ctx.config, ctx.traffic, ctx.seed, precision="fp8")
    half = reference_steps(ref, ctx.config, ctx.traffic, ctx.seed,
                           rows_kept=ctx.traffic["batch"] // 2)
    drop = lambda r: {k: v for k, v in r.items() if k != "leaves_left_out"}
    return {"control_fp8": drop(compare(low, want)),
            "fault_half_batch": drop(compare(half, want))}
