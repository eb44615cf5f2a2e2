"""Pallas TPU kernel: fused frontier accounting over a telemetry window.

TPU-native layout (DESIGN.md §4 — adapted, not ported):

  * ranks along **lanes** (128-wide vector reductions for `max_r`),
  * stages along **sublanes** (S padded to 8; the prefix sum over stages is
    a short unrolled loop),
  * steps along the **grid**.

Input arrives as d[N, S_pad, R_pad] (callers transpose once, in `ops.py`);
each grid step (t, j) streams one [S_pad, R_TILE] tile of one step through
VMEM and folds it into per-step accumulators:

  frontier[t, s], leader[t, s] (global rank index, lowest-on-ties),
  second[t, s] (for the max-minus-secondmax gap), and
  clipped[t, s] = max_r (P_final[r] - max(0, d[r, s] - b[r, s]))
                  — the Eq. 4 recompute via the final-prefix shift identity,
                  fused so the whole evidence packet costs ONE HBM read of
                  the window tensor instead of S+1 frontier passes.

The kernel is bandwidth-bound by design (arithmetic intensity ~ S flops per
loaded float); the roofline target is HBM speed-of-light for the window
tensor, which is what `benchmarks/kernel_frontier.py` reports.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")
_BIG_IDX = 2**30  # python literal: becomes an immediate inside the kernel


def resolve_interpret(interpret: bool | None) -> bool:
    """The one interpret-mode rule for every Pallas route in this package.

    None means: interpret iff JAX's default backend is the CPU.  On any
    other backend the kernel is handed to the native compiler, and a
    kernel it refuses raises there; nothing falls back to interpret mode.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _merge_second(m1, s1, m2, s2):
    """Top-2 merge: second of the union of two (max, second) summaries."""
    return jnp.maximum(jnp.minimum(m1, m2), jnp.maximum(s1, s2))


def _stage_prefix(x):
    """Running sum over the stage (sublane, second-to-last) axis.

    Unrolled in stage order, p[i] = p[i-1] + x[i]: the same sequential
    fold as `np.cumsum`, so every route that shares this helper (and the
    oracles) stays bit-exact.  Built from static one-row slices and
    selects, which Mosaic lowers; it has no `cumsum`.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)
    run = x[..., 0:1, :]
    out = jnp.broadcast_to(run, x.shape)
    for i in range(1, x.shape[-2]):
        run = run + x[..., i:i + 1, :]
        out = jnp.where(row == i, run, out)
    return out


def _tile_reduce(d, b, j, *, r_total: int, r_tile: int, s_pad: int):
    """Per-tile reduction shared by the single-job and fleet kernels.

    d, b: [S_pad, R_TILE] f32 tiles of tile index j.
    Returns (f_t, lead_t, sec_t, clip_t), each [S_pad].
    """
    # Global lane indices for this tile and validity mask for padded ranks.
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_pad, r_tile), 1)
    gidx = lane + j * r_tile                     # [S_pad, R_TILE]
    valid = gidx < r_total

    # Prefix over stages (sublanes): short unrolled running sum.
    prefix = _stage_prefix(d)                    # [S_pad, R_TILE]
    prefix = jnp.where(valid, prefix, NEG_INF)

    # Tile-local frontier / leader (lowest global index on ties) / second.
    f_t = prefix.max(axis=1)                     # [S_pad]
    is_max = prefix == f_t[:, None]
    lead_t = jnp.where(is_max, gidx, _BIG_IDX).min(axis=1)
    # mask exactly the winning lane, keep tied duplicates for `second`
    masked = jnp.where(gidx == lead_t[:, None], NEG_INF, prefix)
    sec_t = masked.max(axis=1)

    # Clipped final makespan per stage (final-prefix shift identity).
    excess = jnp.maximum(0.0, d - b)             # [S_pad, R_TILE]
    final = prefix[s_pad - 1, :][None, :]        # [1, R_TILE] (valid-masked)
    clip_t = jnp.where(valid, final - excess, NEG_INF).max(axis=1)
    return f_t, lead_t, sec_t, clip_t


def _frontier_kernel(
    d_ref,      # [1, S_pad, R_TILE] durations tile (stage-major, rank lanes)
    b_ref,      # [1, S_pad, R_TILE] clipped-gain baseline tile
    f_ref,      # out [1, S_pad] frontier
    lead_ref,   # out [1, S_pad] leader (global rank idx)
    sec_ref,    # out [1, S_pad] second max
    clip_ref,   # out [1, S_pad] clipped final makespan per stage
    *,
    r_total: int,
    r_tile: int,
    s_pad: int,
):
    j = pl.program_id(1)
    f_t, lead_t, sec_t, clip_t = _tile_reduce(
        d_ref[0].astype(jnp.float32),
        b_ref[0].astype(jnp.float32),
        j,
        r_total=r_total,
        r_tile=r_tile,
        s_pad=s_pad,
    )

    @pl.when(j == 0)
    def _init():
        f_ref[0, :] = f_t
        lead_ref[0, :] = lead_t
        sec_ref[0, :] = sec_t
        clip_ref[0, :] = clip_t

    @pl.when(j != 0)
    def _fold():
        f_prev = f_ref[0, :]
        lead_prev = lead_ref[0, :]
        sec_prev = sec_ref[0, :]
        clip_prev = clip_ref[0, :]
        f_new = jnp.maximum(f_prev, f_t)
        # lowest-index tie-break across tiles: previous tiles hold lower
        # global indices, so ties keep the previous leader.
        lead_new = jnp.where(f_t > f_prev, lead_t, lead_prev)
        sec_new = _merge_second(f_prev, sec_prev, f_t, sec_t)
        f_ref[0, :] = f_new
        lead_ref[0, :] = lead_new
        sec_ref[0, :] = sec_new
        clip_ref[0, :] = jnp.maximum(clip_prev, clip_t)


@functools.partial(
    jax.jit, static_argnames=("r_total", "r_tile", "interpret")
)
def frontier_window_kernel(
    d_srp: jax.Array,
    b_srp: jax.Array,
    *,
    r_total: int | None = None,
    r_tile: int = 512,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run the fused kernel on stage-major input.

    Args:
      d_srp: [N, S_pad, R_pad] durations, stage-major, rank lanes; R_pad must
        be a multiple of r_tile (callers pad; padded ranks are masked out).
      b_srp: same shape, clipped-gain baseline.
      r_total: number of real ranks (defaults to R_pad).
      r_tile: rank lanes per VMEM tile (multiple of 128).

    Returns (frontier[N,S_pad], leader[N,S_pad], second[N,S_pad],
             clipped[N,S_pad]) — all f32 except leader (i32).
    """
    n, s_pad, r_pad = d_srp.shape
    if r_pad % r_tile:
        raise ValueError(f"R_pad={r_pad} not a multiple of r_tile={r_tile}")
    r_total = r_pad if r_total is None else r_total
    grid = (n, r_pad // r_tile)
    kernel = functools.partial(
        _frontier_kernel, r_total=r_total, r_tile=r_tile, s_pad=s_pad
    )
    out_spec = pl.BlockSpec((1, s_pad), lambda t, j: (t, 0))
    in_spec = pl.BlockSpec((1, s_pad, r_tile), lambda t, j: (t, 0, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, s_pad), jnp.float32),
            jax.ShapeDtypeStruct((n, s_pad), jnp.int32),
            jax.ShapeDtypeStruct((n, s_pad), jnp.float32),
            jax.ShapeDtypeStruct((n, s_pad), jnp.float32),
        ],
        interpret=interpret,
    )(d_srp, b_srp)


# The fleet route ([J, N, R, S] — see ops.fleet_frontier_window) reuses this
# kernel unchanged: per-step accounting is independent, so stacked jobs fold
# into the leading grid dimension as a [J*N, ...] reshape — one dispatch for
# the whole fleet, no second kernel to keep in sync.


# ---------------------------------------------------------------------------
# Counterfactual what-if matrix kernel
# ---------------------------------------------------------------------------
#
# Candidate-batched counterfactual recompute: for EVERY (stage, rank)
# candidate, substitute the clipped baseline on that single cell and
# re-derive the step makespan under the declared sync model.  The candidate
# axes ride the existing layout for free — ranks are already on lanes and
# stages on sublanes, so one [S_pad, R_TILE] tile evaluates S_pad * R_TILE
# candidates at once and the grid sweeps (rank tiles, steps).
#
# Sync segments are STATIC (a tuple of (start, end) stage spans, each
# ending at a declared barrier or the window end), so the per-segment
# arrival reconstruction unrolls at trace time: within a segment, a rank's
# replayed arrival at the governing boundary is
#
#     arr[r] = relprev + P[end, r] - P[start-1, r]
#
# with P the in-tile stage cumsum of the (imputed) work and relprev the
# previous segment's release.  The per-step boundary stats the shift
# identity needs (release max / leader / second / previous release) are
# tiny [NT, S_pad] rows precomputed by the wrapper, so the whole dense
# [S, R] matrix costs one HBM read of the window tensor instead of S*R
# replays.
#
# Accumulation: steps are the FASTEST grid axis and the output block index
# depends only on (job, rank tile), so consecutive iterations revisit the
# same output block — it stays resident in VMEM while the per-step
# contributions fold in (same pattern as the rank-tile fold above).


def _whatif_kernel(
    w_ref,      # [1, S_pad, R_TILE] work tile (stage-major, rank lanes)
    b_ref,      # [1, S_pad, R_TILE] baseline tile
    amax_ref,   # [1, S_pad] governing-boundary release (max arrival)
    sec_ref,    # [1, S_pad] governing-boundary second max (-inf when R == 1)
    lead_ref,   # [1, S_pad] i32 governing-boundary leader (global rank idx)
    relp_ref,   # [1, S_pad] previous segment's release (0 for the first)
    out_ref,    # out [1, S_pad, R_TILE] recoverable-seconds accumulator
    *,
    segments: tuple[tuple[int, int], ...],
    r_total: int,
    r_tile: int,
    s_pad: int,
    n_steps: int,
):
    j = pl.program_id(0)
    t = pl.program_id(1)
    w = w_ref[0].astype(jnp.float32)             # [S_pad, R_TILE]
    b = b_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_pad, r_tile), 1)
    gidx = lane + j * r_tile
    valid = gidx < r_total

    prefix = _stage_prefix(w)                    # [S_pad, R_TILE]
    excess = jnp.maximum(0.0, w - b)             # [S_pad, R_TILE]

    # Replayed arrival of each lane at its stage's governing boundary —
    # constant across the stages of one segment, so build it row-wise from
    # the static segment table (padded stages live in the last segment and
    # carry w = b = 0, so their contribution is exactly 0).
    rows = []
    for start, end in segments:
        seg = prefix[end, :] - (prefix[start - 1, :] if start else 0.0)
        for si in range(start, min(end + 1, s_pad)):
            rows.append(relp_ref[0, si] + seg)
    arr = jnp.stack(rows, axis=0)                # [S_pad, R_TILE]

    amax = amax_ref[0, :][:, None]               # [S_pad, 1]
    sec = sec_ref[0, :][:, None]
    lead = lead_ref[0, :][:, None]
    # max over the OTHER ranks' arrivals: the leader lane sees the second
    # max (tied maxima keep second == max), every other lane the max.
    other = jnp.where(gidx == lead, sec, amax)   # [S_pad, R_TILE]
    new_a = jnp.maximum(other, arr - excess)
    contrib = jnp.where(valid, jnp.maximum(0.0, amax - new_a), 0.0)

    @pl.when(t % n_steps == 0)
    def _init():
        out_ref[0] = contrib

    @pl.when(t % n_steps != 0)
    def _fold():
        out_ref[0] += contrib


# ---------------------------------------------------------------------------
# Temporal regime statistics kernel
# ---------------------------------------------------------------------------
#
# Per-(stage, rank) reductions of the thresholded exposed-increment
# streams (core.regimes): active count, onset / last active step, burst
# count, trailing streak, and the two sums the trend slope needs.  The
# candidate axes ride the standard layout (ranks on lanes, stages on
# sublanes); each grid step owns one (job, rank tile) pair, streams that
# job's whole [N, S_pad, R_TILE] step block through VMEM, and folds the
# steps in a fori_loop carry — every output block is written exactly
# once (no cross-grid-step revisits, unlike the what-if fold: the regime
# statistics need the previous step's activity, which lives naturally in
# the loop carry).
#
# Integer statistics are exact whatever the fold order; the float sums
# are accumulated with ADDS ONLY in step order (the t-weighted sum the
# trend slope needs is recovered analytically from the running-prefix
# sum, never multiplied in the fold — a multiply-accumulate would fuse
# to an FMA and drift from the oracle by an ulp), so the route matches
# `regime_segments_ref` exactly.


def _regime_kernel(
    e_ref,      # [N, S_pad, R_TILE] one job's excess block (stage-major)
    thr_ref,    # [1, S_pad, R_TILE] the job's activity threshold tile
    count_ref,  # out [1, S_pad, R_TILE] i32 active steps
    onset_ref,  # out [1, S_pad, R_TILE] i32 first active step (BIG = never)
    last_ref,   # out [1, S_pad, R_TILE] i32 last active step (-1 = never)
    runs_ref,   # out [1, S_pad, R_TILE] i32 distinct bursts
    streak_ref, # out [1, S_pad, R_TILE] i32 trailing active streak
    sume_ref,   # out [1, S_pad, R_TILE] f32 sum_t e[t]
    sumpfx_ref, # out [1, S_pad, R_TILE] f32 prefix-sum sum C = sum_t A_t
    *,
    n_steps: int,
):
    e_all = e_ref[...].astype(jnp.float32)       # [N, S_pad, R_TILE]
    thr = thr_ref[0].astype(jnp.float32)
    shape = thr.shape
    zi = jnp.zeros(shape, jnp.int32)
    zf = jnp.zeros(shape, jnp.float32)

    def body(t, carry):
        count, onset, last, runs, streak, prev, sume, sumpfx = carry
        e = jax.lax.dynamic_index_in_dim(e_all, t, 0, keepdims=False)
        act = e > thr
        acti = act.astype(jnp.int32)
        count = count + acti
        onset = jnp.minimum(onset, jnp.where(act, t, _BIG_IDX))
        last = jnp.maximum(last, jnp.where(act, t, -1))
        runs = runs + acti * (1 - prev)
        streak = jnp.where(act, streak + 1, 0)
        # adds only (no multiply, so no FMA divergence from the oracle):
        # sum_t t*e recovers analytically as n*A_{n-1} - C in the wrapper
        sume = sume + e
        sumpfx = sumpfx + sume
        return (count, onset, last, runs, streak, acti, sume, sumpfx)

    init = (zi, zi + _BIG_IDX, zi - 1, zi, zi, zi, zf, zf)
    count, onset, last, runs, streak, _prev, sume, sumpfx = (
        jax.lax.fori_loop(0, n_steps, body, init)
    )
    count_ref[0] = count
    onset_ref[0] = onset
    last_ref[0] = last
    runs_ref[0] = runs
    streak_ref[0] = streak
    sume_ref[0] = sume
    sumpfx_ref[0] = sumpfx


@functools.partial(
    jax.jit, static_argnames=("r_tile", "n_steps", "interpret")
)
def regime_stats_kernel(
    e_srp: jax.Array,
    thr_srp: jax.Array,
    *,
    r_tile: int = 512,
    n_steps: int | None = None,
    interpret: bool,
) -> tuple[jax.Array, ...]:
    """Batched regime statistics on stage-major excess streams.

    Args:
      e_srp: [NT, S_pad, R_pad] excess (NT = jobs * steps), stage-major,
        rank lanes; R_pad a multiple of r_tile.  Padded cells must carry
        e = thr = 0 so they are never active.
      thr_srp: [NT // n_steps, S_pad, R_pad] per-job activity thresholds.
      n_steps: steps per job (defaults to NT: one job).

    Returns (count, onset, last, runs, streak, sum_e, sum_prefix), each
    [NT // n_steps, S_pad, R_pad] — i32 for the first five, f32 for the
    sums.  `sum_prefix` is C = sum_t A_t (A_t the running excess sum),
    from which sum_t t*e = n*sum_e - C follows analytically —
    accumulated with adds only so the fold is bit-reproducible.  `onset`
    uses BIG (2^30) for never-active (the wrapper converts to -1).
    """
    nt, s_pad, r_pad = e_srp.shape
    if r_pad % r_tile:
        raise ValueError(f"R_pad={r_pad} not a multiple of r_tile={r_tile}")
    n_steps = nt if n_steps is None else n_steps
    if nt % n_steps:
        raise ValueError(f"NT={nt} not a multiple of n_steps={n_steps}")
    jobs = nt // n_steps
    grid = (jobs, r_pad // r_tile)
    kernel = functools.partial(_regime_kernel, n_steps=n_steps)
    e_spec = pl.BlockSpec((n_steps, s_pad, r_tile), lambda job, j: (job, 0, j))
    thr_spec = pl.BlockSpec((1, s_pad, r_tile), lambda job, j: (job, 0, j))
    out_spec = pl.BlockSpec((1, s_pad, r_tile), lambda job, j: (job, 0, j))
    i32 = jax.ShapeDtypeStruct((jobs, s_pad, r_pad), jnp.int32)
    f32 = jax.ShapeDtypeStruct((jobs, s_pad, r_pad), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[e_spec, thr_spec],
        out_specs=[out_spec] * 7,
        out_shape=[i32, i32, i32, i32, i32, f32, f32],
        interpret=interpret,
    )(e_srp, thr_srp)


@functools.partial(
    jax.jit,
    static_argnames=("segments", "r_total", "r_tile", "n_steps", "interpret"),
)
def whatif_matrix_kernel(
    w_srp: jax.Array,
    b_srp: jax.Array,
    amax: jax.Array,
    second: jax.Array,
    leader: jax.Array,
    relprev: jax.Array,
    *,
    segments: tuple[tuple[int, int], ...],
    r_total: int | None = None,
    r_tile: int = 512,
    n_steps: int | None = None,
    interpret: bool,
) -> jax.Array:
    """Candidate-batched counterfactual matrix on stage-major input.

    Args:
      w_srp: [NT, S_pad, R_pad] imputed work (NT = jobs * steps),
        stage-major, rank lanes; R_pad a multiple of r_tile (padded ranks
        masked out).
      b_srp: same shape, clipped baseline.
      amax / second / leader / relprev: [NT, S_pad] per-(step, stage)
        governing-boundary stats (see `ops._whatif_stats`).
      segments: static sync segmentation over the S_pad stage rows.
      n_steps: steps per job (defaults to NT: one job); output rows
        accumulate per job.

    Returns W[NT // n_steps, S_pad, R_pad] f32 — per-job recoverable
    seconds for every (stage, rank) candidate.
    """
    nt, s_pad, r_pad = w_srp.shape
    if r_pad % r_tile:
        raise ValueError(f"R_pad={r_pad} not a multiple of r_tile={r_tile}")
    r_total = r_pad if r_total is None else r_total
    n_steps = nt if n_steps is None else n_steps
    if nt % n_steps:
        raise ValueError(f"NT={nt} not a multiple of n_steps={n_steps}")
    jobs = nt // n_steps
    grid = (r_pad // r_tile, nt)                 # steps fastest: VMEM fold
    kernel = functools.partial(
        _whatif_kernel,
        segments=segments,
        r_total=r_total,
        r_tile=r_tile,
        s_pad=s_pad,
        n_steps=n_steps,
    )
    tile_spec = pl.BlockSpec((1, s_pad, r_tile), lambda j, t: (t, 0, j))
    stat_spec = pl.BlockSpec((1, s_pad), lambda j, t: (t, 0))
    out_spec = pl.BlockSpec(
        (1, s_pad, r_tile), lambda j, t: (t // n_steps, 0, j)
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile_spec, tile_spec] + [stat_spec] * 4,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((jobs, s_pad, r_pad), jnp.float32),
        interpret=interpret,
    )(w_srp, b_srp, amax, second, leader, relprev)
