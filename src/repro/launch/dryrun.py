"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell against the production meshes and extract roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch all --shape all --mesh both --out experiments/dryrun

`main()` sets XLA_FLAGS to 512 host devices before anything initializes
a JAX backend (jax fixes the device count then); this is the only entry
point that forces them, and importing the module forces nothing.

Per live cell this produces:
  - production-graph compile (scan-over-layers) -> memory_analysis proves
    the per-device fit; collective schedule from the compiled HLO;
  - unrolled-delta cost extraction (DESIGN.md §6): the same step lowered
    with 1 and 2 unrolled layers, extrapolated to L — exact per-step HLO
    FLOPs / bytes / collective bytes despite scan bodies being counted
    once by XLA's cost analysis;
  - the three roofline terms + dominant bottleneck + MODEL_FLOPS ratio.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from ..analysis.roofline import CellCosts, model_flops, roofline
from ..configs import ARCHITECTURES, ASSIGNED, SHAPES, get_config, shape_applicable
from ..distributed.sharding import (
    BASELINE_PLAN,
    DECODE_PLAN,
    DP_ALL_PLAN,
    DP_FSDP_PLAN,
    ShardingPlan,
)
from ..models import build_model
from ..optim.adamw import AdamWConfig
from .mesh import make_production_mesh
from .steps import (
    build_prefill_step,
    build_serve_step,
    build_train_step,
    init_train_state,
)

PLANS = {
    "baseline": BASELINE_PLAN,
    "decode": DECODE_PLAN,
    "dp_all": DP_ALL_PLAN,
    "dp_fsdp": DP_FSDP_PLAN,
}


def _batch_axes_for(shape, mesh, plan) -> tuple[str, ...]:
    """Shard batch over as many DP axes as divide it (B=1 -> replicated)."""
    axes = []
    b = shape.global_batch
    for ax in plan.batch_axes:
        if ax in mesh.axis_names and b % mesh.shape[ax] == 0 and mesh.shape[ax] > 1:
            axes.append(ax)
            b //= mesh.shape[ax]
    return tuple(axes)


def _plan_for(cfg, shape, mesh, plan: ShardingPlan) -> ShardingPlan:
    rules = dict(plan.rules)
    model_size = mesh.shape.get("model", 1)
    # GQA-aware TP: replicate KV projections when the KV head count does not
    # divide the TP degree (padding churn costs more than the tiny KV GEMM).
    if cfg.n_kv_heads and cfg.n_kv_heads % model_size != 0:
        rules["kv_heads"] = None
    return dataclasses.replace(
        plan, rules=rules, batch_axes=_batch_axes_for(shape, mesh, plan)
    )


#: train cells run with microbatch accumulation so activations fit HBM
#: (global batch 256 -> 4 microbatches of 64); part of the recorded baseline.
TRAIN_ACCUM = 4


def lower_cell(
    cfg, shape, mesh, plan: ShardingPlan, *,
    triangular: bool = False, accum: int | None = None, zero1: bool = True,
):
    """Lower + compile the production (scan) graph for one cell."""
    model = build_model(cfg)
    plan = _plan_for(cfg, shape, mesh, plan)
    specs = model.input_specs(shape)
    with mesh:
        if shape.kind == "train":
            accum_steps = TRAIN_ACCUM if accum is None else accum
            if accum_steps > 1:
                # host-side [accum, micro, ...] layout (see steps.py)
                specs = {
                    k: jax.ShapeDtypeStruct(
                        (accum_steps, s.shape[0] // accum_steps) + s.shape[1:],
                        s.dtype,
                    )
                    for k, s in specs.items()
                }
            step, state_sh = build_train_step(
                model, mesh, plan, AdamWConfig(),
                batch_specs=model.input_specs(shape),
                triangular=triangular,
                accum_steps=accum_steps,
                zero1=zero1,
            )
            state_spec = jax.eval_shape(
                lambda: init_train_state(model, jax.random.PRNGKey(0))
            )
            lowered = step.lower(state_spec, specs)
        elif shape.kind == "prefill":
            step, _ = build_prefill_step(
                model, mesh, plan, batch_specs=specs, triangular=triangular
            )
            params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            lowered = step.lower(params_spec, specs)
        else:  # decode
            cache_specs = model.cache_specs(shape)
            step, _ = build_serve_step(
                model, mesh, plan, shape.seq_len,
                cache_specs=cache_specs, token_batch=shape.global_batch,
            )
            params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            lowered = step.lower(
                params_spec, cache_specs, specs["tokens"], jax.ShapeDtypeStruct((), jnp.int32)
            )
        compiled = lowered.compile()
    return compiled


def moe_layer_costs(cfg, shape, mesh, plan) -> "CellCosts":
    """Standalone per-layer MoE cost at PRODUCTION group size.

    MoE cost is linear in tokens at fixed group size (dispatch per token =
    topk*cf*g*D; expert/router per token fixed), so we lower apply_moe on a
    small unrolled token count (4 groups) and scale to the cell's tokens.
    For train shapes the lowering includes the backward (value_and_grad).
    """
    from ..models import moe as moe_lib
    from ..distributed.sharding import sharding_for_axes

    g = cfg.moe_group
    t_small = 4 * g
    t_full = shape.global_batch * shape.seq_len
    mcfg = dataclasses.replace(cfg, unroll_inner=True)
    dtype = jnp.dtype(cfg.compute_dtype)
    p_specs = jax.eval_shape(
        lambda: moe_lib.init_moe(jax.random.PRNGKey(0), mcfg, dtype)
    )
    # batch dim sized to the DP sharding (as in the real model); the group
    # structure operates on the flattened token count either way.
    b_eff = 1
    for ax in plan.batch_axes:
        b_eff *= mesh.shape.get(ax, 1)
    b_eff = max(b_eff, 1)
    x_spec = jax.ShapeDtypeStruct(
        (b_eff, max(t_small // b_eff, 1), cfg.d_model), dtype
    )
    t_small = x_spec.shape[0] * x_spec.shape[1]
    axes = moe_lib.moe_axes()
    p_sh = {
        k: sharding_for_axes(mesh, axes[k], plan) for k in p_specs
    }
    from .steps import batch_sharding as _bs

    def fwd(p, x):
        y, aux = moe_lib.apply_moe(p, x, mcfg)
        return (y.astype(jnp.float32) ** 2).sum() + aux

    fn = jax.grad(fwd) if shape.kind == "train" else fwd
    with mesh:
        compiled = jax.jit(
            fn, in_shardings=(p_sh, _bs(mesh, 3, plan))
        ).lower(p_specs, x_spec).compile()
    c = CellCosts.from_compiled(compiled)
    scale = t_full / t_small
    if shape.kind == "train":
        scale *= 6.0 / 4.0  # grad-of-fwd ~ 4x fwd; a train step ~ 6x fwd
    return CellCosts(
        flops=c.flops * scale,
        bytes_accessed=c.bytes_accessed * scale,
        coll_bytes=c.coll_bytes * scale,
        coll_by_kind={k: v * scale for k, v in c.coll_by_kind.items()},
        coll_counts=c.coll_counts,
    )


def unrolled_delta_costs(
    cfg, shape, mesh, plan, *,
    triangular: bool = False, accum: int | None = None, zero1: bool = True,
):
    """Lower 1- and 2-layer unrolled variants; extrapolate to cfg.n_layers.

    MoE blocks are removed from the trunk here (their group loop at full
    token count cannot be unrolled at sane compile cost) and added back via
    the standalone linear-in-tokens measurement of `moe_layer_costs`.
    """
    is_moe = cfg.n_experts > 0

    def with_layers(l):
        enc = min(cfg.n_enc_layers, l) if cfg.n_enc_layers else 0
        # unroll_inner: attention-chunk / SSD-chunk loops are python-
        # unrolled with identical math so every iteration is counted
        # (XLA cost analysis counts a while body once).  Masked-full
        # attention cost is chunking-invariant, so the unrolled variants
        # use 8k chunks (16 blocks at 32k seq instead of 1024 -- compile
        # time).  Triangular keeps production chunks: its skipped-pair
        # ratio depends on chunk granularity.
        qc, kc = cfg.attn_q_chunk, cfg.attn_kv_chunk
        if not triangular:
            qc, kc = max(qc, 8192), max(kc, 8192)
        return dataclasses.replace(
            cfg, n_layers=l, n_enc_layers=enc, scan_layers=False,
            unroll_inner=True, attn_q_chunk=qc, attn_kv_chunk=kc,
            n_experts=0 if is_moe else cfg.n_experts,
            top_k=0 if is_moe else cfg.top_k,
        )

    # accum=1 here: the microbatch loop is a scan whose body cost analysis
    # would count once; per-step totals are identical at accum=1 (the grad
    # reduction happens once per step either way), so the delta variants
    # lower the unaccumulated step.
    c1 = CellCosts.from_compiled(
        lower_cell(with_layers(1), shape, mesh, plan,
                   triangular=triangular, accum=1, zero1=zero1)
    )
    c2 = CellCosts.from_compiled(
        lower_cell(with_layers(2), shape, mesh, plan,
                   triangular=triangular, accum=1, zero1=zero1)
    )
    # encoder layers extrapolate with the decoder factor (equal counts for
    # the assigned enc-dec arch: 6/6)
    costs = c1.delta_extrapolate(c2, cfg.n_layers)
    if is_moe and shape.kind != "decode":
        mc = moe_layer_costs(cfg, shape, mesh, plan)
        kinds = set(costs.coll_by_kind) | set(mc.coll_by_kind)
        costs = CellCosts(
            flops=costs.flops + cfg.n_layers * mc.flops,
            bytes_accessed=costs.bytes_accessed + cfg.n_layers * mc.bytes_accessed,
            coll_bytes=costs.coll_bytes + cfg.n_layers * mc.coll_bytes,
            coll_by_kind={
                k: costs.coll_by_kind.get(k, 0.0)
                + cfg.n_layers * mc.coll_by_kind.get(k, 0.0)
                for k in kinds
            },
            coll_counts=costs.coll_counts,
        )
    elif is_moe:
        # decode: 128 tokens = a single group; unrolling is free, so lower
        # the delta WITH the MoE blocks intact.
        def with_layers_moe(l):
            return dataclasses.replace(
                cfg, n_layers=l, scan_layers=False, unroll_inner=True
            )

        c1m = CellCosts.from_compiled(
            lower_cell(with_layers_moe(1), shape, mesh, plan,
                       triangular=triangular, accum=1, zero1=zero1)
        )
        c2m = CellCosts.from_compiled(
            lower_cell(with_layers_moe(2), shape, mesh, plan,
                       triangular=triangular, accum=1, zero1=zero1)
        )
        costs = c1m.delta_extrapolate(c2m, cfg.n_layers)
    return costs


def run_cell(
    arch: str,
    shape_name: str,
    mesh_name: str,
    *,
    plan_name: str = "",
    triangular: bool = False,
    skip_production: bool = False,
    accum: int | None = None,
    zero1: bool = True,
    attn_bf16: bool = False,
    attn_remat: bool = True,
    cache_bksd: bool = False,
    moe_wgather: bool = False,
) -> dict:
    cfg = get_config(arch)
    if moe_wgather:
        cfg = dataclasses.replace(cfg, moe_weight_gather=True)
    if attn_bf16:
        cfg = dataclasses.replace(cfg, attn_cast_f32=False)
    if not attn_remat:
        cfg = dataclasses.replace(cfg, attn_remat=False)
    if cache_bksd:
        cfg = dataclasses.replace(cfg, cache_layout="bksd")
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "skipped", "reason": reason,
        }
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size
    base_plan = PLANS[plan_name or ("decode" if shape.kind == "decode" else "baseline")]

    out: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_chips": n_chips, "plan": base_plan.name, "status": "ok",
        "triangular": triangular,
        "accum": (TRAIN_ACCUM if accum is None else accum) if shape.kind == "train" else 1,
        "zero1": zero1,
        "attn_bf16": attn_bf16,
    }
    t0 = time.time()
    if not skip_production:
        compiled = lower_cell(cfg, shape, mesh, base_plan,
                              triangular=triangular, accum=accum, zero1=zero1)
        ma = compiled.memory_analysis()
        out["compile_s"] = round(time.time() - t0, 2)
        out["memory"] = {
            "args_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "total_per_device_gib": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3
            ),
        }
        scan_costs = CellCosts.from_compiled(compiled)
        out["scan_graph_costs"] = dataclasses.asdict(scan_costs)
        del compiled

    t1 = time.time()
    costs = unrolled_delta_costs(cfg, shape, mesh, base_plan,
                                 triangular=triangular, accum=accum, zero1=zero1)
    out["delta_s"] = round(time.time() - t1, 2)
    mf = model_flops(cfg, shape)
    rl = roofline(costs, n_chips, mf)
    out["costs"] = dataclasses.asdict(costs)
    out["roofline"] = rl.as_dict()
    return out


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all")
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--plan", default="", help="override sharding plan")
    p.add_argument("--triangular", action="store_true")
    p.add_argument("--skip-production", action="store_true",
                   help="delta costs only (no full scan-graph compile)")
    p.add_argument("--accum", type=int, default=-1,
                   help="train microbatch accumulation (-1 = default)")
    p.add_argument("--no-zero1", action="store_true")
    p.add_argument("--attn-bf16", action="store_true",
                   help="bf16 attention operands with f32 accumulation")
    p.add_argument("--no-attn-remat", action="store_true",
                   help="save q-block residuals instead of recomputing")
    p.add_argument("--cache-bksd", action="store_true",
                   help="head-major decode cache layout [B,KV,S,D]")
    p.add_argument("--moe-wgather", action="store_true",
                   help="gather expert weights over data at use")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--tag", default="")
    args = p.parse_args()

    archs = list(ASSIGNED) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                tag = f"{args.tag}_" if args.tag else ""
                path = os.path.join(
                    args.out, f"{tag}{mesh_name}__{arch}__{shape_name}.json"
                )
                t0 = time.time()
                try:
                    row = run_cell(
                        arch, shape_name, mesh_name,
                        plan_name=args.plan, triangular=args.triangular,
                        skip_production=args.skip_production,
                        accum=None if args.accum < 0 else args.accum,
                        zero1=not args.no_zero1,
                        attn_bf16=args.attn_bf16,
                        attn_remat=not args.no_attn_remat,
                        cache_bksd=args.cache_bksd,
                        moe_wgather=args.moe_wgather,
                    )
                except Exception as e:
                    failures += 1
                    row = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                row["wall_s"] = round(time.time() - t0, 2)
                with open(path, "w") as f:
                    json.dump(row, f, indent=1)
                status = row["status"]
                extra = ""
                if status == "ok" and "roofline" in row:
                    r = row["roofline"]
                    extra = (
                        f" dom={r['dominant']} c={r['compute_s']:.2e}"
                        f" m={r['memory_s']:.2e} x={r['collective_s']:.2e}"
                        f" useful={r['useful_ratio']:.2f}"
                    )
                print(f"[{mesh_name}] {arch} x {shape_name}: {status}{extra} ({row['wall_s']}s)", flush=True)
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
