"""Pallas TPU kernel: batched cross-job co-activation by host.

The incident tier's common-cause question — *which hosts carry a fault
that shows up in more than one job?* — reduces to integer statistics of
per-job host-level activity series.  For a fleet activity tensor
``act[J, N, H, S]`` (job j has an above-threshold candidate on host h in
stage s at step t — the thresholded exposed-increment streams of
`core.regimes`, collapsed over each host's ranks), the per-(stage, host)
evidence is:

  ``jobs[s, h]``    distinct jobs with ANY activation in the window —
                    the promotion predicate (>= 2 jobs = common-cause
                    candidate);
  ``coact[s, h]``   steps where >= 2 jobs are active simultaneously —
                    separates a genuinely shared live fault from two
                    jobs that happened to blip in disjoint step ranges;
  ``active[s, h]``  total active job-steps (the exposure mass).

Layout follows the house rules (hosts ride the rank slot): **hosts on
lanes**, **stages on sublanes**, and the grid sweeps (host tiles, jobs)
with jobs fastest — each grid step streams one job's whole
[N, S_pad, H_TILE] activity block through VMEM, reduces it to its
any-mask, and folds block + mask into accumulators that stay
VMEM-resident across the job fold (the output block index depends only
on the host tile).  One dispatch covers every job; all statistics are
integer reductions, so the route matches `co_activation_ref` EXACTLY
(asserted per shape group in `benchmarks/incident_engine.py`).

Fabric tiers ride the same dispatch: `tiered_co_activation` OR-collapses
the host axis onto each declared tier's node axis (switch, pod — see
`incidents.Topology`), concatenates host + node columns into ONE
combined axis, and scores it with the unchanged kernel — the tiers
share the folded activity series, only the aggregation axis changes, so
scoring every tier costs one dispatch instead of one per tier (and each
tier's slice equals `co_activation_ref` on that tier's collapsed series
exactly — gated in `benchmarks/fabric_attribution.py`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .frontier import resolve_interpret

__all__ = [
    "CoActivationPacket",
    "TierAxes",
    "co_activation",
    "co_activation_loop",
    "co_activation_ref",
    "tiered_co_activation",
    "tiered_co_activation_ref",
]

_SUBLANE = 8
_LANE = 128


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class CoActivationPacket(NamedTuple):
    """Cross-job co-activation statistics, [S, H]-oriented (i32 each)."""

    jobs: jax.Array      # [S, H] distinct jobs with any activation
    coact: jax.Array     # [S, H] steps with >= 2 jobs active at once
    active: jax.Array    # [S, H] total active job-steps


def co_activation_ref(act: np.ndarray) -> CoActivationPacket:
    """NumPy oracle of the kernel route on ``act[J, N, H, S]`` (bool).

    This is the ONE definition of the statistics — the Pallas route must
    match it exactly (integer counts, no float accumulation anywhere).
    """
    a = np.asarray(act).astype(bool)
    if a.ndim != 4:
        raise ValueError(f"expected act [J,N,H,S], got {a.shape}")
    stepsum = a.sum(axis=0, dtype=np.int64)          # [N, H, S]
    jobs = a.any(axis=1).sum(axis=0, dtype=np.int64)  # [H, S]
    coact = (stepsum >= 2).sum(axis=0, dtype=np.int64)
    active = stepsum.sum(axis=0, dtype=np.int64)
    return CoActivationPacket(
        jobs=jobs.T.astype(np.int32),
        coact=coact.T.astype(np.int32),
        active=active.T.astype(np.int32),
    )


def _coactivation_kernel(
    a_ref,        # [N, S_pad, H_TILE] one job's activity block (i32 0/1)
    jobs_ref,     # out [1, S_pad, H_TILE] i32 distinct-job count
    stepsum_ref,  # out [N, S_pad, H_TILE] i32 per-step cross-job sums
):
    j = pl.program_id(1)
    a = a_ref[...]
    any_j = a.max(axis=0)[None]                      # [1, S_pad, H_TILE]

    @pl.when(j == 0)
    def _init():
        jobs_ref[...] = any_j
        stepsum_ref[...] = a

    @pl.when(j != 0)
    def _fold():
        jobs_ref[...] += any_j
        stepsum_ref[...] += a


@functools.partial(
    jax.jit, static_argnames=("n_steps", "h_tile", "interpret")
)
def _coactivation_dispatch(
    a_flat: jax.Array,
    *,
    n_steps: int,
    h_tile: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Run the kernel on padded stage-major input [J*N, S_pad, H_pad]."""
    jn_n, s_pad, h_pad = a_flat.shape
    jobs = jn_n // n_steps
    grid = (h_pad // h_tile, jobs)                   # jobs fastest: VMEM fold
    a_spec = pl.BlockSpec(
        (n_steps, s_pad, h_tile), lambda h, j: (j, 0, h)
    )
    jobs_spec = pl.BlockSpec((1, s_pad, h_tile), lambda h, j: (0, 0, h))
    step_spec = pl.BlockSpec(
        (n_steps, s_pad, h_tile), lambda h, j: (0, 0, h)
    )
    return pl.pallas_call(
        _coactivation_kernel,
        grid=grid,
        in_specs=[a_spec],
        out_specs=[jobs_spec, step_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, s_pad, h_pad), jnp.int32),
            jax.ShapeDtypeStruct((n_steps, s_pad, h_pad), jnp.int32),
        ],
        interpret=interpret,
    )(a_flat)


def _prep_activity(
    act: jax.Array, h_tile: int | None, interpret: bool | None
) -> tuple[jax.Array, int, bool]:
    """Shared front half: bool -> i32, host-major transpose + pad to
    [J*N, S_pad, H_pad].  Padded cells carry 0 — never active."""
    jn, n, h, s = act.shape
    a = jnp.asarray(act).astype(jnp.int32)
    interpret = resolve_interpret(interpret)
    if h_tile is None:
        h_tile = min(_pad_to(h, _LANE), 512)
    s_pad = _pad_to(s, _SUBLANE)
    h_pad = _pad_to(h, h_tile)
    at = jnp.transpose(a, (0, 1, 3, 2)).reshape(jn * n, s, h)
    at = jnp.pad(at, ((0, 0), (0, s_pad - s), (0, h_pad - h)))
    return at, h_tile, interpret


def co_activation(
    act: jax.Array,
    *,
    h_tile: int | None = None,
    interpret: bool | None = None,
) -> CoActivationPacket:
    """Fused co-activation statistics of a fleet activity tensor
    ``act[J, N, H, S]`` (bool / 0-1): one dispatch folds every job.

    Returns [S, H]-oriented integer counts equal to `co_activation_ref`
    exactly.
    """
    jn, n, h, s = act.shape
    at, h_tile, interpret = _prep_activity(act, h_tile, interpret)
    jobs_p, stepsum = _coactivation_dispatch(
        at, n_steps=n, h_tile=h_tile, interpret=interpret
    )
    sl = (slice(0, s), slice(0, h))
    return CoActivationPacket(
        jobs=jobs_p[0][sl],
        coact=(stepsum >= 2).sum(axis=0, dtype=jnp.int32)[sl],
        active=stepsum.sum(axis=0, dtype=jnp.int32)[sl],
    )


class TierAxes(NamedTuple):
    """One fabric tier's aggregation axis over the folded host series.

    `grouping[h]` maps host column h onto this tier's node column
    (values in [0, n_nodes); -1 = the host has no node at this tier and
    contributes nowhere).  The activity series itself is SHARED across
    tiers — only this aggregation axis changes.
    """

    tier: str                 # "switch" | "pod" (host tier is implicit)
    n_nodes: int
    grouping: tuple[int, ...]  # per host column, len == H


def _collapse_tier(a: jax.Array, axes: TierAxes) -> jax.Array:
    """OR-collapse ``act[J, N, H, S]`` host columns onto one tier's node
    columns -> ``[J, N, n_nodes, S]`` (any member host active => the
    node is active).  Integer max == boolean OR, so the collapse is
    exact and the downstream statistics stay integer."""
    group = jnp.asarray(axes.grouping, jnp.int32)
    # unmapped hosts (-1) route to a scratch node that is sliced away
    seg = jnp.where(group < 0, axes.n_nodes, group)
    j, n, h, s = a.shape
    out = jnp.zeros((j, n, axes.n_nodes + 1, s), a.dtype)
    out = out.at[:, :, seg, :].max(a)
    return out[:, :, : axes.n_nodes, :]


def tiered_co_activation_ref(
    act: np.ndarray, tiers: Sequence[TierAxes]
) -> tuple[CoActivationPacket, ...]:
    """NumPy oracle of the tiered route: per tier, collapse the SAME
    host-folded series onto that tier's node axis and score it with
    `co_activation_ref` — packet 0 is the host tier itself, packet i+1
    tier ``tiers[i]``.  The fused route must match EXACTLY per tier."""
    a = np.asarray(act).astype(bool)
    if a.ndim != 4:
        raise ValueError(f"expected act [J,N,H,S], got {a.shape}")
    out = [co_activation_ref(a)]
    for axes in tiers:
        if len(axes.grouping) != a.shape[2]:
            raise ValueError(
                f"tier {axes.tier!r} grouping covers "
                f"{len(axes.grouping)} hosts, series has {a.shape[2]}"
            )
        coll = np.zeros(
            (a.shape[0], a.shape[1], axes.n_nodes, a.shape[3]), bool
        )
        for h, g in enumerate(axes.grouping):
            if g >= 0:
                coll[:, :, g, :] |= a[:, :, h, :]
        out.append(co_activation_ref(coll))
    return tuple(out)


def tiered_co_activation(
    act: jax.Array,
    tiers: Sequence[TierAxes],
    *,
    h_tile: int | None = None,
    interpret: bool | None = None,
) -> tuple[CoActivationPacket, ...]:
    """Score the host tier AND every fabric tier in ONE Pallas dispatch.

    The tiers share the folded activity series ``act[J, N, H, S]`` —
    only the aggregation axis changes — so the jnp prolog OR-collapses
    the host axis onto each tier's node axis (`TierAxes.grouping`,
    exact: integer max), concatenates host + node columns into one
    combined axis of size ``H + sum(n_nodes)``, and runs the unchanged
    co-activation kernel once over it.  The outputs split back per
    tier: packet 0 is the host tier, packet i+1 tier ``tiers[i]`` —
    each EXACTLY equal to `co_activation_ref` on that tier's collapsed
    series (`tiered_co_activation_ref`; gated per shape group in
    `benchmarks/fabric_attribution.py`).

    With no fabric tiers declared this is exactly `co_activation`.
    """
    jn, n, h, s = act.shape
    a = jnp.asarray(act).astype(jnp.int32)
    segments = [a]
    for axes in tiers:
        if len(axes.grouping) != h:
            raise ValueError(
                f"tier {axes.tier!r} grouping covers "
                f"{len(axes.grouping)} hosts, series has {h}"
            )
        segments.append(_collapse_tier(a, axes))
    combined = (
        jnp.concatenate(segments, axis=2) if len(segments) > 1 else a
    )
    packet = co_activation(combined, h_tile=h_tile, interpret=interpret)
    out = []
    lo = 0
    for seg in segments:
        hi = lo + seg.shape[2]
        out.append(
            CoActivationPacket(
                jobs=packet.jobs[:, lo:hi],
                coact=packet.coact[:, lo:hi],
                active=packet.active[:, lo:hi],
            )
        )
        lo = hi
    return tuple(out)


def co_activation_loop(
    act: jax.Array,
    *,
    h_tile: int | None = None,
    interpret: bool | None = None,
) -> CoActivationPacket:
    """Naive per-job loop — the baseline the batched route is gated
    against in `benchmarks/incident_engine.py`.

    Dispatches one kernel per job (grid (host tiles, 1) each) and folds
    the per-job outputs in jnp; identical statistics, J dispatches.
    """
    jn, n, h, s = act.shape
    at, h_tile, interpret = _prep_activity(act, h_tile, interpret)
    jobs_acc = None
    step_acc = None
    for j in range(jn):
        jobs_p, stepsum = _coactivation_dispatch(
            at[j * n:(j + 1) * n],
            n_steps=n,
            h_tile=h_tile,
            interpret=interpret,
        )
        jobs_acc = jobs_p if jobs_acc is None else jobs_acc + jobs_p
        step_acc = stepsum if step_acc is None else step_acc + stepsum
    sl = (slice(0, s), slice(0, h))
    return CoActivationPacket(
        jobs=jobs_acc[0][sl],
        coact=(step_acc >= 2).sum(axis=0, dtype=jnp.int32)[sl],
        active=step_acc.sum(axis=0, dtype=jnp.int32)[sl],
    )
