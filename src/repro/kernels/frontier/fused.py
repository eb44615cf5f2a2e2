"""Pallas TPU megakernel: the fused fleet tick.

Every tick the fleet service needs four analyses of the same stacked
window tensor d[J, N, R, S] — frontier accounting, the counterfactual
what-if matrix, temporal regime statistics, and host co-activation.
Run as four separate Pallas dispatches the window is read from HBM four
times; at always-on fleet scale the tick is bandwidth-bound, so the
re-reads are the whole cost.  This module fuses them into ONE grid over
(jobs, rank tiles): each grid step streams one job's [N, S_pad, R_TILE]
window block through VMEM once and feeds four accumulator families:

  frontier family   per-(step, stage) frontier / leader / second /
                    clipped final makespan, folded across rank tiles
                    (the `_frontier_kernel` fold, vectorized over steps);
  what-if family    per-(stage, rank) recoverable seconds, the
                    `_whatif_kernel` per-step contributions folded in a
                    sequential step loop;
  regime family     the seven `_regime_kernel` per-candidate temporal
                    statistics (integer stats + the two add-only sums);
  co-activation     per-(step, stage, host) activity counts: the regime
                    activity mask is collapsed rank->host *inside* the
                    kernel (0/1 x host-one-hot dot — exact small-integer
                    arithmetic), then folded across tiles and jobs into
                    the `_coactivation_kernel` statistics.

Correctness contract: **bit-exact** agreement with all four unfused
routes (`fleet_frontier_window`, `fleet_whatif_matrix`,
`fleet_regime_stats`, `co_activation`) and therefore with their oracles.
The fold-order rules that make this possible:

  * max / min / top-2-merge folds are order-independent exact, so the
    frontier family may fold across tiles in any grid order;
  * float step sums are SEQUENTIAL adds in step order (a step loop
    unrolled at trace time, no `jnp.sum` reassociation, no multiply in
    the fold so nothing fuses to an FMA) — identical to the unfused
    kernels' folds;
  * vectorizing the per-step tile math over a leading N axis is
    elementwise-identical to the unfused per-step grid (the shared
    `_stage_prefix` running sum, max and where give the same per-element
    expression trees; asserted bitwise by `tests/test_fused_tick.py` on
    every shape group);
  * all co-activation statistics are integer counts.

`four_dispatch_tick` keeps the unfused composition callable as THE
reference path (same packet types, four kernel dispatches); the service
routes through it when `FleetService(fused=False)`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core.regimes import RegimeParams as _RegimeParams
from ...core.whatif import sync_segments
from .frontier import (
    _BIG_IDX,
    NEG_INF,
    _merge_second,
    _stage_prefix,
    resolve_interpret,
)
from .incidents import CoActivationPacket, co_activation, co_activation_ref
from .ops import (
    FleetPacket,
    FleetRegimePacket,
    FleetWhatIfPacket,
    _fleet_imputed_work,
    _fleet_median_baseline,
    _LANE,
    _pad_to,
    _SUBLANE,
    _whatif_stats,
)
from .ref import frontier_window_ref, regime_segments_ref, whatif_matrix_ref

__all__ = [
    "FusedTickPacket",
    "four_dispatch_tick",
    "fused_fleet_tick",
    "fused_tick_ref",
]

_REGIME_DEFAULTS = _RegimeParams()

#: steps x rank lanes of one window block that fit a TPU v5e's default
#: scoped VMEM with every family on: rehearsal compiles fit N=100 at
#: R_TILE=256 and run out at N=100, R_TILE=512 (tests/test_tpu_compile.py)
_WINDOW_BLOCK_ELEMS = 100 * 256


class FusedTickPacket(NamedTuple):
    """All four per-tick evidence families from one window load.

    `regimes` / `coact` are None when the corresponding family was not
    requested (`with_regimes=False`, `host_index=None`) — the service
    hot path only consumes the first two.
    """

    frontier: FleetPacket              # shares/gains/leaders per job
    whatif: FleetWhatIfPacket          # [J, S, R] recoverable seconds
    regimes: FleetRegimePacket | None  # per-candidate temporal stats
    coact: CoActivationPacket | None   # [S, H] cross-job co-activation


# ---------------------------------------------------------------------------
# the megakernel
# ---------------------------------------------------------------------------


def _fused_tick_kernel(
    *refs,
    segments: tuple[tuple[int, int], ...],
    r_total: int,
    r_tile: int,
    s_pad: int,
    n_steps: int,
    n_tiles: int,
    with_regimes: bool,
    with_hosts: bool,
):
    """One grid step = one (job, rank tile): every family from one load.

    Ref order (inputs): d, bd, w, bw window tiles [N, S_pad, R_TILE];
    amax/second/leader/relprev what-if stats rows [1, N, S_pad]; then, when
    enabled, thr [1, S_pad, R_TILE] and host one-hot [1, R_TILE, H_pad].
    Outputs: frontier family [1, N, S_pad] x4 (revisited across tiles),
    what-if [1, S_pad, R_TILE], the seven regime stats, and the
    co-activation scratch/accumulators (const-indexed, folded across the
    whole grid).
    """
    it = iter(refs)
    d_ref, bd_ref, w_ref, bw_ref = (next(it) for _ in range(4))
    amax_ref, sec_ref, lead_ref, relp_ref = (next(it) for _ in range(4))
    thr_ref = next(it) if (with_regimes or with_hosts) else None
    oneh_ref = next(it) if with_hosts else None
    f_ref, fl_ref, fs_ref, fc_ref = (next(it) for _ in range(4))
    wif_ref = next(it)
    if with_regimes:
        (count_ref, onset_ref, last_ref, runs_ref,
         streak_ref, sume_ref, sumpfx_ref) = (next(it) for _ in range(7))
    if with_hosts:
        hostcnt_ref, jobs_ref, stepsum_ref = (next(it) for _ in range(3))

    job = pl.program_id(0)
    jt = pl.program_id(1)

    lane = jax.lax.broadcasted_iota(jnp.int32, (s_pad, r_tile), 1)
    gidx = lane + jt * r_tile                    # [S_pad, R_TILE]
    valid = gidx < r_total

    # -- frontier family: `_tile_reduce` vectorized over the step axis --
    d = d_ref[...].astype(jnp.float32)           # [N, S_pad, R_TILE]
    bd = bd_ref[...].astype(jnp.float32)
    prefix_d = jnp.where(valid[None], _stage_prefix(d), NEG_INF)
    f_t = prefix_d.max(axis=2)                   # [N, S_pad]
    is_max = prefix_d == f_t[:, :, None]
    lead_t = jnp.where(is_max, gidx[None], _BIG_IDX).min(axis=2)
    masked = jnp.where(gidx[None] == lead_t[:, :, None], NEG_INF, prefix_d)
    sec_t = masked.max(axis=2)
    excess_d = jnp.maximum(0.0, d - bd)
    final_d = prefix_d[:, s_pad - 1:, :]         # [N, 1, R_TILE]
    clip_t = jnp.where(valid[None], final_d - excess_d, NEG_INF).max(axis=2)

    @pl.when(jt == 0)
    def _init_frontier():
        f_ref[0] = f_t
        fl_ref[0] = lead_t
        fs_ref[0] = sec_t
        fc_ref[0] = clip_t

    @pl.when(jt != 0)
    def _fold_frontier():
        f_prev = f_ref[0]
        # lowest-index tie-break across tiles: previous tiles hold lower
        # global indices, so ties keep the previous leader.
        fl_ref[0] = jnp.where(f_t > f_prev, lead_t, fl_ref[0])
        fs_ref[0] = _merge_second(f_prev, fs_ref[0], f_t, sec_t)
        fc_ref[0] = jnp.maximum(fc_ref[0], clip_t)
        f_ref[0] = jnp.maximum(f_prev, f_t)

    # -- what-if family: `_whatif_kernel` per-step contributions --------
    w = w_ref[...].astype(jnp.float32)           # [N, S_pad, R_TILE]
    bw = bw_ref[...].astype(jnp.float32)
    prefix_w = _stage_prefix(w)
    excess_w = jnp.maximum(0.0, w - bw)
    relp = relp_ref[0][:, :, None]               # [N, S_pad, 1]
    # each stage row replays its own segment: row si of `arr` is
    # relp[si] + (P[end] - P[start-1]), selected row by row
    row = jax.lax.broadcasted_iota(jnp.int32, prefix_w.shape, 1)
    arr = prefix_w
    for start, end in segments:
        seg = prefix_w[:, end:end + 1, :] - (
            prefix_w[:, start - 1:start, :] if start else 0.0
        )                                        # [N, 1, R_TILE]
        for si in range(start, min(end + 1, s_pad)):
            arr = jnp.where(row == si, relp[:, si:si + 1, :] + seg, arr)
    amax = amax_ref[0][:, :, None]               # [N, S_pad, 1]
    sec = sec_ref[0][:, :, None]
    lead = lead_ref[0][:, :, None]
    other = jnp.where(gidx[None] == lead, sec, amax)
    new_a = jnp.maximum(other, arr - excess_w)
    contrib = jnp.where(valid[None], jnp.maximum(0.0, amax - new_a), 0.0)

    # the step folds unroll at trace time: a static step index is a static
    # slice of the leading (untiled) axis, which Mosaic lowers; it has no
    # dynamic slice of a value.  The adds keep step order.
    wacc = jnp.zeros((s_pad, r_tile), jnp.float32)
    if with_regimes:
        # -- regime family: the `_regime_kernel` step fold, carrying the
        # what-if accumulator in the same pass over the steps.
        thr = thr_ref[0].astype(jnp.float32)
        zi = jnp.zeros((s_pad, r_tile), jnp.int32)
        count, onset, last, runs, streak, prev = (
            zi, zi + _BIG_IDX, zi - 1, zi, zi, zi
        )
        sume = sumpfx = wacc
        for t in range(n_steps):
            e = excess_w[t]
            act = e > thr
            acti = act.astype(jnp.int32)
            count = count + acti
            onset = jnp.minimum(onset, jnp.where(act, t, _BIG_IDX))
            last = jnp.maximum(last, jnp.where(act, t, -1))
            runs = runs + acti * (1 - prev)
            streak = jnp.where(act, streak + 1, 0)
            prev = acti
            # adds only (no multiply, so no FMA divergence from the
            # oracle): sum_t t*e recovers as n*sum_e - C in the epilog
            sume = sume + e
            sumpfx = sumpfx + sume
            wacc = wacc + contrib[t]
        count_ref[0] = count
        onset_ref[0] = onset
        last_ref[0] = last
        runs_ref[0] = runs
        streak_ref[0] = streak
        sume_ref[0] = sume
        sumpfx_ref[0] = sumpfx
    else:
        for t in range(n_steps):
            wacc = wacc + contrib[t]
    wif_ref[0] = wacc

    # -- co-activation family: rank->host collapse inside the kernel ---
    if with_hosts:
        thr_h = thr_ref[0].astype(jnp.float32)
        act_all = (excess_w > thr_h[None]).astype(jnp.float32)
        oneh = oneh_ref[0].astype(jnp.float32)   # [R_TILE, H_pad]
        # 0/1 x 0/1 dot over <= r_tile lanes: exact small integers in f32
        partial = jax.lax.dot_general(
            act_all, oneh, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)                      # [N, S_pad, H_pad]

        @pl.when(jt == 0)
        def _init_hostcnt():
            hostcnt_ref[...] = partial

        @pl.when(jt != 0)
        def _fold_hostcnt():
            hostcnt_ref[...] += partial

        last_tile = jt == n_tiles - 1

        @pl.when(last_tile & (job == 0))
        def _init_jobs():
            ah = (hostcnt_ref[...] > 0).astype(jnp.int32)
            jobs_ref[...] = ah.max(axis=0)[None]
            stepsum_ref[...] = ah

        @pl.when(last_tile & (job != 0))
        def _fold_jobs():
            ah = (hostcnt_ref[...] > 0).astype(jnp.int32)
            jobs_ref[...] += ah.max(axis=0)[None]
            stepsum_ref[...] += ah


# ---------------------------------------------------------------------------
# shared epilogs (one copy: the kernel wrapper AND the composed ref use
# these, so packet-level equality follows from accumulator equality)
# ---------------------------------------------------------------------------


def _frontier_packet(f, lead, sec, clip, s: int) -> FleetPacket:
    """[J, N, S_pad] accumulators -> FleetPacket (the
    `fleet_frontier_window` epilog, verbatim)."""
    f, lead = f[:, :, :s], lead[:, :, :s]
    sec, clip = sec[:, :, :s], clip[:, :, :s]
    advances = jnp.diff(f, axis=2, prepend=0.0)
    gap = f - sec                                # sec = -inf when R == 1
    exposed = f[:, :, -1]                        # [J, N]
    denom = jnp.maximum(exposed.sum(axis=1), 1e-30)
    shares = advances.sum(axis=1) / denom[:, None]
    gains = (
        jnp.maximum(0.0, (exposed[:, :, None] - clip).sum(axis=1))
        / denom[:, None]
    )
    return FleetPacket(f, advances, lead, gap, exposed, shares, gains)


def _regime_packet(
    count, onset, last, runs, streak, sum_e, sum_pfx,
    *, n: int, s: int, r: int,
) -> FleetRegimePacket:
    """[J, S_pad, R_pad] accumulators -> FleetRegimePacket (the
    `fleet_regime_stats` epilog, verbatim)."""
    sl = (slice(None), slice(0, s), slice(0, r))
    count, last = count[sl], last[sl]
    runs, streak = runs[sl], streak[sl]
    sum_e, sum_pfx = sum_e[sl], sum_pfx[sl]
    onset = jnp.where(onset[sl] >= n, -1, onset[sl])         # BIG -> never
    span = jnp.maximum(1, n - onset).astype(jnp.float32)
    duty = jnp.where(onset >= 0, count.astype(jnp.float32) / span, 0.0)
    if n >= 2:
        tbar = (n - 1) / 2.0
        denom = n * (n * n - 1) / 12.0
        slope = ((n - tbar) * sum_e - sum_pfx) / denom
    else:
        slope = jnp.zeros_like(sum_e)
    return FleetRegimePacket(
        count, onset, last, runs, streak, sum_e, sum_pfx, duty, slope
    )


def _coact_packet(jobs_p, stepsum, *, s: int, h: int) -> CoActivationPacket:
    """Accumulators -> CoActivationPacket (the `co_activation` epilog)."""
    sl = (slice(0, s), slice(0, h))
    return CoActivationPacket(
        jobs=jobs_p[0][sl],
        coact=(stepsum >= 2).sum(axis=0, dtype=jnp.int32)[sl],
        active=stepsum.sum(axis=0, dtype=jnp.int32)[sl],
    )


def _fleet_baselines(d, w, baseline, *, need_jrs: bool):
    """The two baseline families every route agrees on: the frontier
    family clips against the cohort median of the RAW durations, the
    what-if/regime families against the median of the sync-IMPUTED work
    (`_fleet_imputed_work`); an explicit baseline serves both, and must
    be broadcastable to [J, R, S] when the regime/co-activation families
    are enabled (their threshold is per-cell, constant over steps)."""
    jn, n, r, s = d.shape
    if baseline is None:
        bd = _fleet_median_baseline(d)
        bw_jrs = _fleet_median_baseline(w)[:, 0]             # [J, R, S]
        bw = jnp.broadcast_to(bw_jrs[:, None], d.shape)
    else:
        b = jnp.asarray(baseline).astype(jnp.float32)
        bd = jnp.broadcast_to(b, d.shape)
        bw = bd
        bw_jrs = jnp.broadcast_to(b, (jn, r, s)) if need_jrs else None
    return bd, bw, bw_jrs


# ---------------------------------------------------------------------------
# fused dispatch
# ---------------------------------------------------------------------------


def _fused_tick_impl(
    d, baseline, host_index, *,
    sync_stages, num_hosts, with_regimes,
    min_excess_s, rel_excess, r_tile, interpret,
):
    jn, n, r, s = d.shape
    with_hosts = host_index is not None
    d = d.astype(jnp.float32)
    w = _fleet_imputed_work(d, sync_stages)
    bd, bw, bw_jrs = _fleet_baselines(
        d, w, baseline, need_jrs=with_regimes or with_hosts
    )
    interpret = resolve_interpret(interpret)
    if r_tile is None:
        # the four window blocks are [N, S_pad, R_TILE] each, so a long
        # window takes a narrower rank tile to fit scoped VMEM
        r_tile = min(
            _pad_to(r, _LANE), 512,
            max(_LANE, _WINDOW_BLOCK_ELEMS // n // _LANE * _LANE),
        )
    s_pad = _pad_to(s, _SUBLANE)
    r_pad = _pad_to(r, r_tile)
    pad = ((0, 0), (0, s_pad - s), (0, r_pad - r))

    def _sm(x):  # stage-major [J*N, S_pad, R_pad]
        return jnp.pad(
            jnp.transpose(x, (0, 1, 3, 2)).reshape(jn * n, s, r), pad
        )

    segments = sync_segments(sync_stages, s, s_pad)
    wt = _sm(w)
    # per-(step, stage) what-if stats as [J, N, S_pad]: a (1, N, S_pad)
    # block spans the array's last two dims, legal for any window N
    stats = [
        x.reshape(jn, n, s_pad) for x in _whatif_stats(wt, segments, r)
    ]
    inputs = [_sm(d), _sm(bd), wt, _sm(bw), *stats]

    n_tiles = r_pad // r_tile
    win_spec = pl.BlockSpec((n, s_pad, r_tile), lambda job, t: (job, 0, t))
    stat_spec = pl.BlockSpec((1, n, s_pad), lambda job, t: (job, 0, 0))
    in_specs = [win_spec] * 4 + [stat_spec] * 4
    if with_regimes or with_hosts:
        # padded cells carry e = thr = 0, so they are never active
        thr = jnp.maximum(min_excess_s, rel_excess * bw_jrs)  # [J, R, S]
        inputs.append(jnp.pad(jnp.transpose(thr, (0, 2, 1)), pad))
        in_specs.append(
            pl.BlockSpec((1, s_pad, r_tile), lambda job, t: (job, 0, t))
        )
    h_pad = 0
    if with_hosts:
        h_pad = _pad_to(max(num_hosts, 1), _LANE)
        # padded ranks get index -1 -> an all-zero one-hot row
        hi = jnp.pad(
            host_index.astype(jnp.int32),
            ((0, 0), (0, r_pad - r)),
            constant_values=-1,
        )
        inputs.append(jax.nn.one_hot(hi, h_pad, dtype=jnp.float32))
        in_specs.append(
            pl.BlockSpec((1, r_tile, h_pad), lambda job, t: (job, t, 0))
        )

    front_spec = pl.BlockSpec((1, n, s_pad), lambda job, t: (job, 0, 0))
    cell_spec = pl.BlockSpec((1, s_pad, r_tile), lambda job, t: (job, 0, t))
    fns = jax.ShapeDtypeStruct((jn, n, s_pad), jnp.float32)
    ins = jax.ShapeDtypeStruct((jn, n, s_pad), jnp.int32)
    fc = jax.ShapeDtypeStruct((jn, s_pad, r_pad), jnp.float32)
    ic = jax.ShapeDtypeStruct((jn, s_pad, r_pad), jnp.int32)
    out_specs = [front_spec] * 4 + [cell_spec]
    out_shape = [fns, ins, fns, fns, fc]
    if with_regimes:
        out_specs += [cell_spec] * 7
        out_shape += [ic, ic, ic, ic, ic, fc, fc]
    if with_hosts:
        host_scratch = pl.BlockSpec((n, s_pad, h_pad), lambda job, t: (0, 0, 0))
        out_specs += [
            host_scratch,
            pl.BlockSpec((1, s_pad, h_pad), lambda job, t: (0, 0, 0)),
            host_scratch,
        ]
        out_shape += [
            jax.ShapeDtypeStruct((n, s_pad, h_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, s_pad, h_pad), jnp.int32),
            jax.ShapeDtypeStruct((n, s_pad, h_pad), jnp.int32),
        ]

    kernel = functools.partial(
        _fused_tick_kernel,
        segments=segments,
        r_total=r,
        r_tile=r_tile,
        s_pad=s_pad,
        n_steps=n,
        n_tiles=n_tiles,
        with_regimes=with_regimes,
        with_hosts=with_hosts,
    )
    outs = list(pl.pallas_call(
        kernel,
        grid=(jn, n_tiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs))

    front = _frontier_packet(outs[0], outs[1], outs[2], outs[3], s)
    # observed per-step makespans (fraction denominator): from d, not w.
    whatif = FleetWhatIfPacket(
        matrix=outs[4][:, :s, :r],
        exposed=d.sum(axis=3).max(axis=2),
    )
    k = 5
    regimes = None
    if with_regimes:
        regimes = _regime_packet(*outs[k:k + 7], n=n, s=s, r=r)
        k += 7
    coact = None
    if with_hosts:
        coact = _coact_packet(outs[k + 1], outs[k + 2], s=s, h=num_hosts)
    return FusedTickPacket(front, whatif, regimes, coact)


_STATIC = (
    "sync_stages", "num_hosts", "with_regimes",
    "min_excess_s", "rel_excess", "r_tile", "interpret",
)
_fused_tick_jit = jax.jit(_fused_tick_impl, static_argnames=_STATIC)


def fused_fleet_tick(
    d,
    baseline=None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    host_index=None,
    num_hosts: int = 0,
    with_regimes: bool = True,
    min_excess_s: float = _REGIME_DEFAULTS.min_excess_s,
    rel_excess: float = _REGIME_DEFAULTS.rel_excess,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> FusedTickPacket:
    """All four per-tick analyses of d[J, N, R, S] in ONE Pallas dispatch.

    Args:
      d: stacked fleet window tensor [J, N, R, S].
      baseline: explicit clip reference (broadcastable to d; must be
        broadcastable to [J, R, S] when regimes/co-activation are on).
        None = each job's own cohort medians (raw d for the frontier
        family, sync-imputed work for the rest — the unfused defaults).
      sync_stages: static tuple of barrier-bearing stage indices
        (identical across the stacked jobs, as in `fleet_whatif_matrix`).
      host_index: [J, R] i32 rank->host map (with `num_hosts`); enables
        the co-activation family.  None = family off.
      with_regimes: compute the regime-statistics family.

    Returns a `FusedTickPacket` bit-exact against the four unfused
    routes on every field.
    """
    d = jnp.asarray(d)
    sync_stages = tuple(sorted({int(i) for i in (sync_stages or ())}))
    if host_index is not None:
        if num_hosts <= 0:
            raise ValueError("host_index requires num_hosts >= 1")
        host_index = jnp.asarray(host_index, jnp.int32)
        if host_index.shape != (d.shape[0], d.shape[2]):
            raise ValueError(
                f"host_index must be [J, R]={d.shape[0], d.shape[2]}, "
                f"got {host_index.shape}"
            )
    return _fused_tick_jit(
        d, baseline, host_index,
        sync_stages=sync_stages,
        num_hosts=int(num_hosts),
        with_regimes=bool(with_regimes),
        min_excess_s=float(min_excess_s),
        rel_excess=float(rel_excess),
        r_tile=r_tile,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# the four-dispatch reference path + the composed oracle
# ---------------------------------------------------------------------------


def _host_activity(w, bw_jrs, host_index, num_hosts, min_excess_s, rel_excess):
    """[J, N, H, S] bool host-level activity: the regime activity mask
    (e > thr, same formulas as the kernels) collapsed rank -> host."""
    e = jnp.maximum(0.0, w - bw_jrs[:, None])                # [J, N, R, S]
    thr = jnp.maximum(min_excess_s, rel_excess * bw_jrs)     # [J, R, S]
    act = e > thr[:, None]
    oneh = jax.nn.one_hot(
        jnp.asarray(host_index, jnp.int32), num_hosts, dtype=bool
    )                                                        # [J, R, H]
    # any over each host's ranks
    return jnp.einsum("jnrs,jrh->jnhs", act, oneh) > 0


def four_dispatch_tick(
    d,
    baseline=None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    host_index=None,
    num_hosts: int = 0,
    with_regimes: bool = True,
    min_excess_s: float = _REGIME_DEFAULTS.min_excess_s,
    rel_excess: float = _REGIME_DEFAULTS.rel_excess,
    r_tile: int | None = None,
    interpret: bool | None = None,
) -> FusedTickPacket:
    """The SAME packet via the four separate unfused kernel dispatches.

    This is the reference tick path the megakernel is gated against
    (`benchmarks/fused_tick.py`) and the route `FleetService(fused=False)`
    falls back to: `fleet_frontier_window` + `fleet_whatif_matrix` +
    `fleet_regime_stats` + `co_activation`, each re-reading the window.
    """
    from .ops import (
        fleet_frontier_window,
        fleet_regime_stats,
        fleet_whatif_matrix,
    )

    d = jnp.asarray(d).astype(jnp.float32)
    sync_stages = tuple(sorted({int(i) for i in (sync_stages or ())}))
    front = fleet_frontier_window(
        d, baseline, r_tile=r_tile, interpret=interpret
    )
    whatif = fleet_whatif_matrix(
        d, baseline, sync_stages=sync_stages, r_tile=r_tile,
        interpret=interpret,
    )
    regimes = None
    if with_regimes:
        regimes = fleet_regime_stats(
            d, baseline, sync_stages=sync_stages,
            min_excess_s=min_excess_s, rel_excess=rel_excess,
            r_tile=r_tile, interpret=interpret,
        )
    coact = None
    if host_index is not None:
        if num_hosts <= 0:
            raise ValueError("host_index requires num_hosts >= 1")
        w = _fleet_imputed_work(d, sync_stages)
        _, _, bw_jrs = _fleet_baselines(d, w, baseline, need_jrs=True)
        act_host = _host_activity(
            w, bw_jrs, host_index, num_hosts, min_excess_s, rel_excess
        )
        coact = co_activation(act_host, interpret=interpret)
    return FusedTickPacket(front, whatif, regimes, coact)


def fused_tick_ref(
    d,
    baseline=None,
    *,
    sync_stages: tuple[int, ...] | None = None,
    host_index=None,
    num_hosts: int = 0,
    with_regimes: bool = True,
    min_excess_s: float = _REGIME_DEFAULTS.min_excess_s,
    rel_excess: float = _REGIME_DEFAULTS.rel_excess,
) -> FusedTickPacket:
    """Oracle: the fused tick COMPOSED from the four per-job references.

    Runs `frontier_window_ref`, `whatif_matrix_ref`,
    `regime_segments_ref` job by job and `co_activation_ref` on the
    host-collapsed activity (NumPy), stacks the primitives, and applies
    the same epilogs as the kernel wrapper — so the fused route must
    match it bit for bit on every field of every family.
    """
    d = jnp.asarray(d).astype(jnp.float32)
    jn, n, r, s = d.shape
    sync_stages = tuple(sorted({int(i) for i in (sync_stages or ())}))
    w = _fleet_imputed_work(d, sync_stages)
    need_jrs = with_regimes or host_index is not None
    bd, bw, bw_jrs = _fleet_baselines(d, w, baseline, need_jrs=need_jrs)

    fws = [frontier_window_ref(d[j], bd[j]) for j in range(jn)]
    # The shared epilogs run under jit here because the kernel wrapper
    # runs them under jit: XLA CPU's compiled elementwise arithmetic
    # (division, mul-sub contraction) differs from the eager op-by-op
    # path in the last ulp, and the parity contract is bitwise.
    front = jax.jit(_frontier_packet, static_argnames=("s",))(
        jnp.stack([p.frontier for p in fws]),
        jnp.stack([p.leader for p in fws]),
        jnp.stack([p.second for p in fws]),
        jnp.stack([p.clipped for p in fws]),
        s=s,
    )
    whatif = FleetWhatIfPacket(
        matrix=jnp.stack([
            whatif_matrix_ref(d[j], bw[j], sync_stages) for j in range(jn)
        ]),
        exposed=jax.jit(lambda x: x.sum(axis=3).max(axis=2))(d),
    )
    regimes = None
    if with_regimes:
        rws = [
            regime_segments_ref(
                d[j], bw_jrs[j], sync_stages=sync_stages,
                min_excess_s=min_excess_s, rel_excess=rel_excess,
            )
            for j in range(jn)
        ]
        regimes = jax.jit(
            _regime_packet, static_argnames=("n", "s", "r")
        )(
            *(jnp.stack([getattr(p, f) for p in rws])
              for f in rws[0]._fields),
            n=n, s=s, r=r,
        )
    coact = None
    if host_index is not None:
        if num_hosts <= 0:
            raise ValueError("host_index requires num_hosts >= 1")
        act_host = np.asarray(_host_activity(
            w, bw_jrs, host_index, num_hosts, min_excess_s, rel_excess
        ))
        coact = co_activation_ref(act_host)
    return FusedTickPacket(front, whatif, regimes, coact)
