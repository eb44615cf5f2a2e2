"""Jitted public wrapper for the fused frontier kernel.

Accepts the natural [N, R, S] window layout, performs the one-time
transpose/pad to the TPU-native [N, S_pad, R_pad] stage-major layout,
dispatches the Pallas kernel (interpret mode only on the CPU backend:
`frontier.resolve_interpret`), and
post-processes the tiny [N, S] accumulators into the full evidence packet
(advances, gap, Eq. 2 shares, Eq. 4 gains).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .frontier import (
    frontier_window_kernel,
    regime_stats_kernel,
    resolve_interpret,
    whatif_matrix_kernel,
)
from .ref import (
    FrontierWindow,
    RegimeWindow,
    frontier_window_ref,
    regime_segments_ref,
    sync_segments,
    whatif_matrix_ref,
)

from ...core.regimes import RegimeParams as _RegimeParams

_SUBLANE = 8
_LANE = 128
_F32_TINY = float(jnp.finfo(jnp.float32).tiny)
#: regime-route threshold defaults come from the ONE definition in
#: core.regimes — tuning RegimeParams retunes the kernel routes too.
_REGIME_DEFAULTS = _RegimeParams()


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class FrontierPacket(NamedTuple):
    """Window evidence packet (kernel output + derived shares/gains)."""

    frontier: jax.Array   # [N, S]
    advances: jax.Array   # [N, S]
    leader: jax.Array     # [N, S] i32
    gap: jax.Array        # [N, S]  max - secondmax (+inf when R == 1)
    exposed: jax.Array    # [N]     F[t, S]
    shares: jax.Array     # [S]     Eq. 2
    gains: jax.Array      # [S]     Eq. 4 (clipped static gain)


@functools.partial(jax.jit, static_argnames=("r_tile", "interpret"))
def frontier_window(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> FrontierPacket:
    """Fused frontier accounting of a window tensor d[N, R, S].

    baseline defaults to the cohort median (cross-rank, per-stage) — the
    hidden-rank-exposing default of the labeler.

    Implemented as the J=1 squeeze of the fleet route: one copy of the
    transpose/pad/dispatch/postprocess wrapper serves both.
    """
    p = fleet_frontier_window(
        d[None],
        None if baseline is None else baseline[None],
        r_tile=r_tile,
        interpret=interpret,
    )
    return FrontierPacket(
        frontier=p.frontier[0],
        advances=p.advances[0],
        leader=p.leader[0],
        gap=p.gap[0],
        exposed=p.exposed[0],
        shares=p.shares[0],
        gains=p.gains[0],
    )


class FleetPacket(NamedTuple):
    """Per-job evidence packets for a stacked fleet tensor d[J, N, R, S]."""

    frontier: jax.Array   # [J, N, S]
    advances: jax.Array   # [J, N, S]
    leader: jax.Array     # [J, N, S] i32
    gap: jax.Array        # [J, N, S]
    exposed: jax.Array    # [J, N]
    shares: jax.Array     # [J, S]   Eq. 2 per job
    gains: jax.Array      # [J, S]   Eq. 4 per job


def _fleet_median_baseline(d: jax.Array) -> jax.Array:
    """Per-job cohort median baseline (cross-rank, cross-step, per-stage).

    A subnormal median is flushed to zero here, as the CPU and the TPU
    flush it when it is stored.  The explicit select also keeps XLA from
    contracting the median's `0.5 * (lo + hi)` into a consumer's
    subtraction as one FMA, whose exact subnormal result would then be
    flushed instead: every route sees the same baseline value.
    """
    jn, n, r, s = d.shape
    med = jnp.median(d.reshape(jn, n * r, s), axis=1)       # [J, S]
    med = jnp.where(jnp.abs(med) < _F32_TINY, 0.0, med)
    return jnp.broadcast_to(med[:, None, None, :], d.shape)


def _prep_stage_major(
    d: jax.Array,
    baseline: jax.Array | None,
    *,
    r_tile: int | None,
    interpret: bool | None,
) -> tuple[jax.Array, jax.Array, int, bool]:
    """Shared front half of every kernel route: dtype, default baseline,
    stage-major transpose + pad to [J*N, S_pad, R_pad].

    Padded stages add 0 to every prefix; padded ranks are masked inside
    the kernels.  Returns (dt, bt, r_tile, interpret).
    """
    jn, n, r, s = d.shape
    d = d.astype(jnp.float32)
    if baseline is None:
        baseline = _fleet_median_baseline(d)
    baseline = jnp.broadcast_to(baseline.astype(jnp.float32), d.shape)
    interpret = resolve_interpret(interpret)
    if r_tile is None:
        r_tile = min(_pad_to(r, _LANE), 512)
    s_pad = _pad_to(s, _SUBLANE)
    r_pad = _pad_to(r, r_tile)
    dt = jnp.transpose(d, (0, 1, 3, 2)).reshape(jn * n, s, r)
    bt = jnp.transpose(baseline, (0, 1, 3, 2)).reshape(jn * n, s, r)
    pad = ((0, 0), (0, s_pad - s), (0, r_pad - r))
    return jnp.pad(dt, pad), jnp.pad(bt, pad), r_tile, interpret


@functools.partial(jax.jit, static_argnames=("r_tile", "interpret"))
def fleet_frontier_window(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> FleetPacket:
    """Batched frontier accounting of a stacked-jobs tensor d[J, N, R, S].

    One fused pallas dispatch covers every job: the (job, step) pairs fold
    into the kernel's leading grid dimension (per-step math is independent,
    so [J, N, ...] -> [J*N, ...] is exact), and per-job shares/gains come
    from the tiny [J, N, S] accumulators.  The baseline defaults to each
    job's own cohort median — jobs never share a baseline (heterogeneous
    workloads are not comparable).
    """
    jn, n, r, s = d.shape
    dt, bt, r_tile, interpret = _prep_stage_major(
        d, baseline, r_tile=r_tile, interpret=interpret
    )
    f, lead, sec, clip = frontier_window_kernel(
        dt, bt, r_total=r, r_tile=r_tile, interpret=interpret
    )
    f = f[:, :s].reshape(jn, n, s)
    lead = lead[:, :s].reshape(jn, n, s)
    sec = sec[:, :s].reshape(jn, n, s)
    clip = clip[:, :s].reshape(jn, n, s)
    advances = jnp.diff(f, axis=2, prepend=0.0)
    gap = f - sec                               # sec = -inf when R == 1
    exposed = f[:, :, -1]                       # [J, N]
    denom = jnp.maximum(exposed.sum(axis=1), 1e-30)          # [J]
    shares = advances.sum(axis=1) / denom[:, None]
    gains = (
        jnp.maximum(0.0, (exposed[:, :, None] - clip).sum(axis=1))
        / denom[:, None]
    )
    return FleetPacket(f, advances, lead, gap, exposed, shares, gains)


def fleet_frontier_loop(
    d: jax.Array, baseline: jax.Array | None = None
) -> FleetPacket:
    """Naive per-job loop over `frontier_window` — the fleet baseline.

    Dispatches J separate kernels; exists so the fleet benchmark and tests
    can compare the one-pass batched route against it.
    """
    packets = [
        frontier_window(d[j], None if baseline is None else baseline[j])
        for j in range(d.shape[0])
    ]
    return FleetPacket(
        frontier=jnp.stack([p.frontier for p in packets]),
        advances=jnp.stack([p.advances for p in packets]),
        leader=jnp.stack([p.leader for p in packets]),
        gap=jnp.stack([p.gap for p in packets]),
        exposed=jnp.stack([p.exposed for p in packets]),
        shares=jnp.stack([p.shares for p in packets]),
        gains=jnp.stack([p.gains for p in packets]),
    )


class WhatIfPacket(NamedTuple):
    """Counterfactual what-if output for one window tensor d[N, R, S]."""

    matrix: jax.Array     # [S, R]  recoverable seconds per candidate
    exposed: jax.Array    # [N]     F[t, S] (fraction denominator)


class FleetWhatIfPacket(NamedTuple):
    """Per-job what-if matrices for a stacked fleet tensor d[J, N, R, S]."""

    matrix: jax.Array     # [J, S, R]
    exposed: jax.Array    # [J, N]


def _fleet_imputed_work(
    d: jax.Array, sync_stages: tuple[int, ...] | None
) -> jax.Array:
    """jnp mirror of `core.whatif.imputed_work` on a stacked [J, N, R, S]
    tensor: sync stages get the per-step cross-rank minimum (the only
    wait-free observation a coarse stage vector contains)."""
    if not sync_stages:
        return d
    s = d.shape[-1]
    mask = jnp.zeros(s, bool).at[jnp.asarray(sync_stages)].set(True)
    return jnp.where(mask, d.min(axis=2, keepdims=True), d)


def _whatif_stats(
    wt: jax.Array,
    segments: tuple[tuple[int, int], ...],
    r_total: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-(step, stage) governing-boundary stats for the what-if kernel.

    wt: [NT, S_pad, R_pad] stage-major imputed work.  For each sync
    segment, replays the arrivals at its boundary (previous release +
    segment prefix) and reduces them to (max, second, leader); every stage
    row then carries its own segment's stats.  Returns four [NT, S_pad]
    arrays: amax, second, leader (i32), relprev.
    """
    nt, s_pad, r_pad = wt.shape
    p = jnp.cumsum(wt, axis=1)                            # [NT, S_pad, R_pad]
    lanes = jnp.arange(r_pad)[None, :] < r_total          # [1, R_pad]
    relbase = jnp.zeros((nt,), jnp.float32)
    amax_rows, sec_rows, lead_rows, relp_rows = [], [], [], []
    for start, end in segments:
        seg = p[:, end, :] - (p[:, start - 1, :] if start else 0.0)
        arr = jnp.where(lanes, relbase[:, None] + seg, -jnp.inf)
        amax = arr.max(axis=1)                            # [NT]
        lead = jnp.argmax(arr, axis=1).astype(jnp.int32)  # first on ties
        masked = jnp.where(
            jnp.arange(r_pad)[None, :] == lead[:, None], -jnp.inf, arr
        )
        second = masked.max(axis=1)                       # -inf when R == 1
        for _si in range(start, end + 1):
            amax_rows.append(amax)
            sec_rows.append(second)
            lead_rows.append(lead)
            relp_rows.append(relbase)
        relbase = amax
    return (
        jnp.stack(amax_rows, axis=1),
        jnp.stack(sec_rows, axis=1),
        jnp.stack(lead_rows, axis=1),
        jnp.stack(relp_rows, axis=1),
    )


@functools.partial(
    jax.jit, static_argnames=("sync_stages", "r_tile", "interpret")
)
def whatif_matrix(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> WhatIfPacket:
    """Dense [S, R] counterfactual recoverable-time matrix of d[N, R, S].

    Every (stage, rank) candidate is clipped to the baseline (default:
    cohort median of the imputed work) and the step makespan replayed
    under the declared sync model — candidates batched into the kernel
    tiles, steps on the grid.  `sync_stages` is a static tuple of stage
    indices that end with a group barrier (see `core.whatif`).  The J=1
    squeeze of `fleet_whatif_matrix` (same wrapper, same kernels).
    """
    p = fleet_whatif_matrix(
        d[None],
        None if baseline is None else baseline[None],
        sync_stages=sync_stages,
        r_tile=r_tile,
        interpret=interpret,
    )
    return WhatIfPacket(matrix=p.matrix[0], exposed=p.exposed[0])


@functools.partial(
    jax.jit, static_argnames=("sync_stages", "r_tile", "interpret")
)
def fleet_whatif_matrix(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> FleetWhatIfPacket:
    """Batched per-job what-if matrices for a stacked tensor d[J, N, R, S].

    One fused dispatch covers every job and every candidate: a cheap jnp
    prolog imputes wait-free work and reduces each step's sync-boundary
    arrivals to tiny [J*N, S_pad] stats rows, then `whatif_matrix_kernel`
    folds per-step candidate contributions into per-job [S, R]
    accumulators — (job, step) pairs on the grid, candidates on the
    (sublane, lane) tile axes.  Cost is one kernel HBM read of the window
    tensor instead of S*R replays.  Baselines default to each job's own
    cohort median of the imputed work (jobs never share a baseline).
    `sync_stages` must be identical across the stacked jobs — group
    heterogeneous fleets by sync profile (as `fleet.service` does).
    """
    jn, n, r, s = d.shape
    w = _fleet_imputed_work(d.astype(jnp.float32), sync_stages)
    wt, bt, r_tile, interpret = _prep_stage_major(
        w, baseline, r_tile=r_tile, interpret=interpret
    )
    s_pad = wt.shape[1]
    segments = sync_segments(sync_stages, s, s_pad)
    amax, second, leader, relprev = _whatif_stats(wt, segments, r)
    wk = whatif_matrix_kernel(
        wt,
        bt,
        amax,
        second,
        leader,
        relprev,
        segments=segments,
        r_total=r,
        r_tile=r_tile,
        n_steps=n,
        interpret=interpret,
    )
    # observed per-step makespans (fraction denominator): from d, not w.
    exposed = d.astype(jnp.float32).sum(axis=3).max(axis=2)
    return FleetWhatIfPacket(matrix=wk[:, :s, :r], exposed=exposed)


class FleetRegimePacket(NamedTuple):
    """Per-job regime statistics for a stacked fleet tensor d[J, N, R, S].

    Integer stats mirror `core.regimes.RegimeStats` ([J, S, R] each);
    `duty` and `slope` are the derived temporal evidence the routing
    weight needs, computed in a tiny jnp epilog from the kernel sums.
    """

    count: jax.Array          # [J, S, R] i32 active steps
    onset: jax.Array          # [J, S, R] i32 first active step, -1 = never
    last: jax.Array           # [J, S, R] i32 last active step, -1 = never
    runs: jax.Array           # [J, S, R] i32 distinct bursts
    streak: jax.Array         # [J, S, R] i32 trailing active streak
    sum_excess: jax.Array     # [J, S, R] f32 sum_t e[t]
    sum_prefix: jax.Array     # [J, S, R] f32 C = sum_t A_t (running sums)
    duty: jax.Array           # [J, S, R] f32 active fraction since onset
    slope: jax.Array          # [J, S, R] f32 excess trend, seconds/step


@functools.partial(
    jax.jit,
    static_argnames=(
        "sync_stages", "min_excess_s", "rel_excess", "r_tile", "interpret"
    ),
)
def fleet_regime_stats(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    min_excess_s: float = _REGIME_DEFAULTS.min_excess_s,
    rel_excess: float = _REGIME_DEFAULTS.rel_excess,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> FleetRegimePacket:
    """Batched per-job regime statistics for a stacked tensor d[J, N, R, S].

    One fused dispatch reduces every job's thresholded exposed-increment
    streams (`core.regimes`) to per-candidate temporal statistics:
    (job, step) pairs on the grid, candidates on the (sublane, lane) tile
    axes, per-job accumulators VMEM-resident across the step fold.
    `baseline` is the per-cell reference ([J, R, S], or broadcastable);
    it defaults to each job's cohort median of the sync-imputed work and
    must be constant across the window (the activity threshold is
    per-cell).  Matches `regime_segments_ref` exactly per job.
    """
    jn, n, r, s = d.shape
    w = _fleet_imputed_work(d.astype(jnp.float32), sync_stages)
    if baseline is None:
        b_jrs = _fleet_median_baseline(w)[:, 0]              # [J, R, S]
    else:
        b_jrs = jnp.broadcast_to(
            baseline.astype(jnp.float32), (jn, r, s)
        )
    e = jnp.maximum(0.0, w - b_jrs[:, None])                 # [J, N, R, S]
    thr = jnp.maximum(min_excess_s, rel_excess * b_jrs)      # [J, R, S]
    interpret = resolve_interpret(interpret)
    if r_tile is None:
        r_tile = min(_pad_to(r, _LANE), 512)
    s_pad = _pad_to(s, _SUBLANE)
    r_pad = _pad_to(r, r_tile)
    et = jnp.transpose(e, (0, 1, 3, 2)).reshape(jn * n, s, r)
    et = jnp.pad(et, ((0, 0), (0, s_pad - s), (0, r_pad - r)))
    tt = jnp.transpose(thr, (0, 2, 1))                       # [J, S, R]
    # padded cells carry e = thr = 0, so they are never active
    tt = jnp.pad(tt, ((0, 0), (0, s_pad - s), (0, r_pad - r)))
    count, onset, last, runs, streak, sum_e, sum_pfx = regime_stats_kernel(
        et, tt, r_tile=r_tile, n_steps=n, interpret=interpret
    )
    sl = (slice(None), slice(0, s), slice(0, r))
    count, last = count[sl], last[sl]
    runs, streak = runs[sl], streak[sl]
    sum_e, sum_pfx = sum_e[sl], sum_pfx[sl]
    onset = jnp.where(onset[sl] >= n, -1, onset[sl])         # BIG -> never
    span = jnp.maximum(1, n - onset).astype(jnp.float32)
    duty = jnp.where(onset >= 0, count.astype(jnp.float32) / span, 0.0)
    if n >= 2:
        # sum_t t*e = n*sum_e - C, so the least-squares numerator
        # (sum_t (t - tbar) e) is (n - tbar)*sum_e - C
        tbar = (n - 1) / 2.0
        denom = n * (n * n - 1) / 12.0
        slope = ((n - tbar) * sum_e - sum_pfx) / denom
    else:
        slope = jnp.zeros_like(sum_e)
    return FleetRegimePacket(
        count, onset, last, runs, streak, sum_e, sum_pfx, duty, slope
    )


class RegimePacket(NamedTuple):
    """Single-job regime statistics (the J=1 squeeze), [S, R] each."""

    count: jax.Array
    onset: jax.Array
    last: jax.Array
    runs: jax.Array
    streak: jax.Array
    sum_excess: jax.Array
    sum_prefix: jax.Array
    duty: jax.Array
    slope: jax.Array


@functools.partial(
    jax.jit,
    static_argnames=(
        "sync_stages", "min_excess_s", "rel_excess", "r_tile", "interpret"
    ),
)
def regime_stats_window(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    min_excess_s: float = _REGIME_DEFAULTS.min_excess_s,
    rel_excess: float = _REGIME_DEFAULTS.rel_excess,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> RegimePacket:
    """Regime statistics of one window d[N, R, S] — the J=1 squeeze of
    `fleet_regime_stats` (one wrapper, one kernel)."""
    p = fleet_regime_stats(
        d[None],
        None if baseline is None else baseline[None],
        sync_stages=sync_stages,
        min_excess_s=min_excess_s,
        rel_excess=rel_excess,
        r_tile=r_tile,
        interpret=interpret,
    )
    return RegimePacket(*(f[0] for f in p))


def regime_stats_loop(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    min_excess_s: float = _REGIME_DEFAULTS.min_excess_s,
    rel_excess: float = _REGIME_DEFAULTS.rel_excess,
) -> FleetRegimePacket:
    """Naive per-job loop over `regime_stats_window` — the fleet baseline.

    Dispatches J separate kernels; exists so `benchmarks/regime_detection`
    and tests can compare the one-pass batched route against it.
    """
    packets = [
        regime_stats_window(
            d[j],
            None if baseline is None else baseline[j],
            sync_stages=sync_stages,
            min_excess_s=min_excess_s,
            rel_excess=rel_excess,
        )
        for j in range(d.shape[0])
    ]
    return FleetRegimePacket(
        *(jnp.stack(col) for col in zip(*packets))
    )


def _replay_exposed(
    w: jax.Array, segments: tuple[tuple[int, int], ...]
) -> jax.Array:
    """Per-step replayed makespan [N] of work w[N, R, S] (jnp oracle)."""
    p = jnp.cumsum(w, axis=2)
    relbase = jnp.zeros(w.shape[0], w.dtype)
    for start, end in segments:
        seg = p[:, :, end] - (p[:, :, start - 1] if start else 0.0)
        relbase = (relbase[:, None] + seg).max(axis=1)
    return relbase


def whatif_matrix_loop(
    d: jax.Array,
    baseline: jax.Array | None = None,
    *,
    sync_stages: tuple[int, ...] | None = None,
) -> jax.Array:
    """Per-candidate counterfactual loop — the route the batched kernel is
    benchmarked against: one full sync replay per (stage, rank).

    O(S*R) passes over the window tensor; exists for
    `benchmarks/whatif_matrix.py` and parity tests, never to serve.
    """
    n, r, s = d.shape
    w = _fleet_imputed_work(d.astype(jnp.float32)[None], sync_stages)[0]
    if baseline is None:
        baseline = _fleet_median_baseline(w[None])[0]
    b = jnp.broadcast_to(baseline.astype(jnp.float32), w.shape)
    segments = sync_segments(sync_stages, s)
    base = _replay_exposed(w, segments).sum()
    rows = []
    for si in range(s):
        cols = []
        for ri in range(r):
            clipped = jnp.minimum(w[:, ri, si], b[:, ri, si])
            repl = w.at[:, ri, si].set(clipped)
            cols.append(base - _replay_exposed(repl, segments).sum())
        rows.append(jnp.stack(cols))
    return jnp.stack(rows)                                  # [S, R]


def frontier_window_reference(
    d: jax.Array, baseline: jax.Array | None = None
) -> FrontierPacket:
    """Same packet computed by the pure-jnp oracle (for tests/benchmarks)."""
    n, r, s = d.shape
    d = d.astype(jnp.float32)
    if baseline is None:
        baseline = jnp.broadcast_to(
            jnp.median(d.reshape(n * r, s), axis=0)[None, None, :], d.shape
        )
    baseline = jnp.broadcast_to(baseline.astype(jnp.float32), d.shape)
    ref: FrontierWindow = frontier_window_ref(d, baseline)
    gap = ref.frontier - ref.second
    exposed = ref.frontier[:, -1]
    denom = jnp.maximum(exposed.sum(), 1e-30)
    shares = ref.advances.sum(axis=0) / denom
    gains = jnp.maximum(0.0, (exposed[:, None] - ref.clipped).sum(axis=0)) / denom
    return FrontierPacket(
        ref.frontier, ref.advances, ref.leader, gap, exposed, shares, gains
    )
