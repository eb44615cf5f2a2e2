"""Sharded train / prefill / serve step builders.

`build_*` functions return (jitted_fn, in_shardings, out_shardings) wired
from the logical-axis rules of the model and a ShardingPlan — the same
builders serve the live trainer, the serving loop, and the multi-pod
dry-run (which lowers them against ShapeDtypeStructs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.sharding import (
    ShardingPlan,
    batch_sharding,
    cache_sharding,
    ssm_cache_sharding,
    tree_shardings,
)
from ..models.model_zoo import Model
from ..optim.adamw import AdamWConfig, OptState, apply_updates, init_opt

__all__ = [
    "TrainState",
    "build_train_step",
    "build_prefill_step",
    "build_serve_step",
    "batch_shardings",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: OptState
    step: jax.Array


_ATTN_CACHE_KEYS = {"k", "v", "cross_k", "cross_v"}


def cache_shardings_for(mesh: Mesh, plan: ShardingPlan, cache_specs: Any,
                        seq_dim: int = 2):
    """Attention caches [L,B,S,KV,D] shard batch+cache-seq; SSM state and
    conv-tail caches [L,B,...] shard batch only (identified by key name —
    the conv tail is 4-D but its dim 2 is the conv window, not sequence).
    Cache-seq sharding is dropped when the cache length doesn't divide the
    axis (sliding-window ring buffers)."""
    from jax.sharding import PartitionSpec as PS

    def leaf(path, s):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key in _ATTN_CACHE_KEYS:
            sh = cache_sharding(mesh, s.shape, plan, seq_dim=seq_dim)
            # sanitize: uneven cache-seq or batch dims fall back to replicated
            dims = list(sh.spec) + [None] * (len(s.shape) - len(sh.spec))
            for i, (dim, size) in enumerate(zip(dims, s.shape)):
                if dim is None:
                    continue
                axes_i = (dim,) if isinstance(dim, str) else dim
                prod = 1
                for a in axes_i:
                    prod *= mesh.shape[a]
                if size % prod != 0:
                    dims[i] = None
            return NamedSharding(mesh, PS(*dims))
        sh = ssm_cache_sharding(mesh, s.shape, plan)
        dims = list(sh.spec) + [None] * (len(s.shape) - len(sh.spec))
        for i, (dim, size) in enumerate(zip(dims, s.shape)):
            if dim is None:
                continue
            axes_i = (dim,) if isinstance(dim, str) else dim
            prod = 1
            for a in axes_i:
                prod *= mesh.shape[a]
            if size % prod != 0:
                dims[i] = None
        return NamedSharding(mesh, PS(*dims))

    return jax.tree_util.tree_map_with_path(leaf, cache_specs)


def batch_shardings(
    mesh: Mesh, plan: ShardingPlan, batch_specs: dict, accum_steps: int = 1
) -> dict:
    """Shardings of a batch whose per-microbatch shapes are
    `batch_specs`: the batch dim over the plan's DP axes.  With
    `accum_steps > 1` the batch is host-shaped [accum, micro, ...] and the
    leading accum dim is replicated."""
    lead = (None,) if accum_steps > 1 else ()
    return {
        name: NamedSharding(
            mesh, P(*lead, *batch_sharding(mesh, len(spec.shape), plan).spec)
        )
        for name, spec in batch_specs.items()
    }


def build_train_step(
    model: Model,
    mesh: Mesh,
    plan: ShardingPlan,
    opt_cfg: AdamWConfig | None = None,
    *,
    batch_specs: dict | None = None,
    accum_steps: int = 1,
    triangular: bool = False,
    donate: bool = True,
    zero1: bool = True,
):
    """Fused train step: grads -> clip -> AdamW, optional microbatch accum.

    zero1=True shards AdamW mu/nu over the `data` axis (ZeRO-1): GSPMD
    reduce-scatters grads into the sharded update and all-gathers the new
    params, replacing the replicated-state grad all-reduce.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    param_sh = tree_shardings(mesh, model.param_axes(), plan, params_spec)
    if zero1 and "data" in mesh.axis_names:
        dsize = mesh.shape["data"]

        def opt_leaf(sh: NamedSharding, spec_leaf) -> NamedSharding:
            dims = list(sh.spec) + [None] * (len(spec_leaf.shape) - len(sh.spec))
            used = {
                a
                for dim in dims
                for a in ((dim,) if isinstance(dim, str) else (dim or ()))
            }
            if "data" in used:
                return sh
            for i, (dim, size) in enumerate(zip(dims, spec_leaf.shape)):
                if dim is None and size % dsize == 0 and size >= dsize:
                    dims[i] = "data"
                    return NamedSharding(sh.mesh, P(*dims))
            return sh

        opt_sh = jax.tree.map(opt_leaf, param_sh, params_spec)
    else:
        opt_sh = param_sh
    state_sh = TrainState(
        params=param_sh,
        opt=OptState(mu=opt_sh, nu=opt_sh, count=NamedSharding(mesh, P())),
        step=NamedSharding(mesh, P()),
    )

    def loss_fn(params, batch):
        return model.loss(params, batch, triangular=triangular)

    def train_step(state: TrainState, batch: dict):
        if accum_steps > 1:
            # batch arrives HOST-SHAPED as [accum, micro, ...] with the
            # micro dim data-sharded: reshaping a sharded batch dim on
            # device confuses GSPMD into replicating the microbatch.
            def micro(c, mb):
                loss, grads = jax.value_and_grad(loss_fn)(state.params, mb)
                acc_loss, acc_grads = c
                return (
                    acc_loss + loss,
                    jax.tree.map(jnp.add, acc_grads, grads),
                ), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros(()), zeros), batch
            )
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        params, opt, om = apply_updates(opt_cfg, state.params, grads, state.opt)
        metrics = {"loss": loss, **om}
        return TrainState(params=params, opt=opt, step=state.step + 1), metrics

    batch_sh = (
        batch_shardings(mesh, plan, batch_specs, accum_steps)
        if batch_specs else None
    )
    fn = jax.jit(
        train_step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )
    return fn, state_sh


def build_prefill_step(
    model: Model,
    mesh: Mesh,
    plan: ShardingPlan,
    *,
    batch_specs: dict | None = None,
    triangular: bool = False,
):
    params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    param_sh = tree_shardings(mesh, model.param_axes(), plan, params_spec)

    def prefill(params, batch):
        return model.forward(params, batch, triangular=triangular)

    batch_sh = (
        batch_shardings(mesh, plan, batch_specs) if batch_specs else None
    )
    fn = jax.jit(
        prefill,
        in_shardings=(param_sh, batch_sh),
        out_shardings=None,
    )
    return fn, param_sh


def build_serve_step(
    model: Model,
    mesh: Mesh,
    plan: ShardingPlan,
    seq_len: int,
    *,
    cache_specs: Any = None,
    token_batch: int | None = None,
):
    """One decode token against the KV/state caches (donated)."""
    params_spec = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    param_sh = tree_shardings(mesh, model.param_axes(), plan, params_spec)

    def serve(params, caches, tokens, index):
        logits, new_caches = model.decode_step(params, caches, tokens, index, seq_len)
        return logits, new_caches

    cache_sh = (
        cache_shardings_for(
            mesh, plan, cache_specs,
            seq_dim=3 if model.cfg.cache_layout == "bksd" else 2,
        )
        if cache_specs is not None
        else None
    )
    tok_sh = (
        batch_sharding(mesh, 2, plan) if token_batch is not None else None
    )
    fn = jax.jit(
        serve,
        in_shardings=(param_sh, cache_sh, tok_sh, NamedSharding(mesh, P())),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )
    return fn, param_sh


def init_train_state(model: Model, rng) -> TrainState:
    params = model.init(rng)
    return TrainState(params=params, opt=init_opt(params), step=jnp.zeros((), jnp.int32))
