"""Differential parity: the fused fleet-tick megakernel vs the four
unfused routes and the composed NumPy oracle.

The fused kernel's correctness contract is BIT-EXACT agreement — every
field of every accumulator family, `assert_array_equal`, never allclose —
with (a) `four_dispatch_tick`, the unfused composition of the four
independently-tested kernels, and (b) `fused_tick_ref`, the oracle
composed from the four per-job references.  The suite sweeps every
existing shape group plus the degenerate shapes the grid logic must
survive: J=1 (single-job fleet), R=1 (no second-place rank), R not a
multiple of the lane tile (masked lanes), multi-tile R (cross-tile
folds), heterogeneous cohorts (S=4 and S=6 through the same service),
and empty-activity windows (no candidate above threshold anywhere).
"""
import numpy as np
import pytest

from repro.fleet import FleetService
from repro.kernels.frontier import (
    four_dispatch_tick,
    fused_fleet_tick,
    fused_tick_ref,
)
from repro.replay import generate_trace, parse_trace, replay_trace
from repro.telemetry.packets import EvidencePacket

# the per-job (N, R, S) groups the unfused suites pin (test_whatif /
# test_regimes), exercised here with a fleet J axis on top
_SHAPE_GROUPS = [(2, 3, 6), (4, 8, 3), (1, 1, 4), (3, 16, 8)]

_FAMILIES = ("frontier", "whatif", "regimes", "coact")


def _assert_tick_equal(got, want, *, context=""):
    """Every family present on both sides, every field bit-identical."""
    for fam in _FAMILIES:
        pg, pw = getattr(got, fam), getattr(want, fam)
        assert (pg is None) == (pw is None), f"{context}: {fam} presence"
        if pg is None:
            continue
        for field in pg._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(pg, field)),
                np.asarray(getattr(pw, field)),
                err_msg=f"{context}: {fam}.{field}",
            )


def _window(shape, seed, scale=1.0):
    d = np.random.default_rng(seed).exponential(scale, shape)
    return d.astype(np.float32)


def _tick_all_three(d, baseline=None, **kw):
    return (
        fused_fleet_tick(d, baseline, **kw),
        four_dispatch_tick(d, baseline, **kw),
        fused_tick_ref(d, baseline, **kw),
    )


class TestFusedParityShapeGroups:
    @pytest.mark.parametrize("shape", _SHAPE_GROUPS)
    @pytest.mark.parametrize("jobs", [1, 3])
    def test_all_families_exact(self, shape, jobs):
        n, r, s = shape
        d = _window((jobs, n, r, s), seed=n * 100 + r * 10 + s + jobs)
        hosts = np.random.default_rng(jobs).integers(0, 3, (jobs, r))
        kw = dict(sync_stages=(1, s - 1), host_index=hosts, num_hosts=3)
        fused, four, ref = _tick_all_three(d, **kw)
        _assert_tick_equal(fused, four, context=f"{shape} vs four-dispatch")
        _assert_tick_equal(fused, ref, context=f"{shape} vs composed ref")

    @pytest.mark.parametrize("shape", _SHAPE_GROUPS)
    def test_no_declared_syncs(self, shape):
        n, r, s = shape
        d = _window((2, n, r, s), seed=7)
        fused, four, ref = _tick_all_three(d, sync_stages=None)
        _assert_tick_equal(fused, four, context=f"{shape} nosync four")
        _assert_tick_equal(fused, ref, context=f"{shape} nosync ref")

    @pytest.mark.parametrize("shape", _SHAPE_GROUPS)
    def test_frontier_whatif_only_path(self, shape):
        # the service refresh configuration: no regimes, no hosts
        n, r, s = shape
        d = _window((4, n, r, s), seed=11)
        kw = dict(sync_stages=(0,), with_regimes=False)
        fused, four, ref = _tick_all_three(d, **kw)
        assert fused.regimes is None and fused.coact is None
        _assert_tick_equal(fused, four, context=f"{shape} minimal four")
        _assert_tick_equal(fused, ref, context=f"{shape} minimal ref")


class TestFusedParityDegenerate:
    def test_single_job(self):
        d = _window((1, 5, 6, 5), seed=0)
        hosts = np.zeros((1, 6), np.int64)
        fused, four, ref = _tick_all_three(
            d, sync_stages=(2,), host_index=hosts, num_hosts=1
        )
        _assert_tick_equal(fused, four, context="J=1 four")
        _assert_tick_equal(fused, ref, context="J=1 ref")

    def test_single_rank(self):
        # R=1: no second place (gap = +inf), host collapse is the identity
        d = _window((3, 4, 1, 4), seed=1)
        hosts = np.zeros((3, 1), np.int64)
        fused, four, ref = _tick_all_three(
            d, sync_stages=(1,), host_index=hosts, num_hosts=1
        )
        _assert_tick_equal(fused, four, context="R=1 four")
        _assert_tick_equal(fused, ref, context="R=1 ref")

    def test_rank_count_off_lane_tile(self):
        # R=129 with the default 128-lane tile: two tiles, the second
        # all-but-one masked
        d = _window((2, 3, 129, 4), seed=2)
        hosts = np.random.default_rng(2).integers(0, 5, (2, 129))
        fused, four, ref = _tick_all_three(
            d, sync_stages=(1, 3), host_index=hosts, num_hosts=5
        )
        _assert_tick_equal(fused, four, context="R=129 four")
        _assert_tick_equal(fused, ref, context="R=129 ref")

    def test_multi_tile_fold(self):
        # r_tile=128 forced, R=300: three tiles, cross-tile frontier and
        # co-activation folds
        d = _window((2, 3, 300, 4), seed=3)
        hosts = np.random.default_rng(3).integers(0, 4, (2, 300))
        kw = dict(
            sync_stages=(2,), host_index=hosts, num_hosts=4, r_tile=128
        )
        fused = fused_fleet_tick(d, **kw)
        four = four_dispatch_tick(
            d, sync_stages=(2,), host_index=hosts, num_hosts=4
        )
        ref = fused_tick_ref(
            d, sync_stages=(2,), host_index=hosts, num_hosts=4
        )
        _assert_tick_equal(fused, four, context="R=300 four")
        _assert_tick_equal(fused, ref, context="R=300 ref")

    def test_empty_activity_window(self):
        # perfectly uniform work: nothing exceeds the median baseline,
        # every activity series is empty, the what-if matrix is all-zero
        d = np.full((2, 4, 6, 5), 0.25, np.float32)
        hosts = np.random.default_rng(4).integers(0, 2, (2, 6))
        fused, four, ref = _tick_all_three(
            d, sync_stages=(2,), host_index=hosts, num_hosts=2
        )
        assert not np.asarray(fused.whatif.matrix).any()
        assert not np.asarray(fused.coact.active).any()
        assert (np.asarray(fused.regimes.onset) == -1).all()
        _assert_tick_equal(fused, four, context="empty four")
        _assert_tick_equal(fused, ref, context="empty ref")

    def test_subnormal_median_baseline(self):
        # the case hypothesis found: one step of one job is 1.0 on every
        # rank of stage 1 except rank 4, which is FLT_MIN.  That stage's
        # cohort median is 0.5 * (0 + FLT_MIN), a subnormal.  Unflushed,
        # XLA:CPU fused the median's multiply into the four-dispatch
        # route's `w - median` as one FMA and flushed the exact result
        # FLT_MIN/2 to 0, while the fused route subtracted the stored
        # (flushed) median and kept FLT_MIN.  The baseline now flushes a
        # subnormal median to 0 itself, as the chip does, so every route
        # sees the same value.
        tiny = np.finfo(np.float32).tiny
        d = np.zeros((3, 2, 9, 5), np.float32)
        d[1, 1, :, 1] = 1.0
        d[1, 1, 4, 1] = tiny
        hosts = np.zeros((3, 9), np.int64)
        fused, four, ref = _tick_all_three(
            d, sync_stages=(), host_index=hosts, num_hosts=1
        )
        assert fused.regimes.sum_excess[1, 1, 4] == tiny
        _assert_tick_equal(fused, four, context="subnormal median four")
        _assert_tick_equal(fused, ref, context="subnormal median ref")

    def test_explicit_baseline(self):
        d = _window((2, 4, 5, 4), seed=5)
        # explicit cohort-shared per-stage reference ([S]: broadcastable
        # to both the [J, N, R, S] clip and the [J, R, S] threshold)
        base = np.median(d, axis=(0, 1, 2)).astype(np.float32)
        fused, four, ref = _tick_all_three(d, base, sync_stages=(1,))
        _assert_tick_equal(fused, four, context="explicit baseline four")
        _assert_tick_equal(fused, ref, context="explicit baseline ref")


def _packet(d, stages, sync_names, widx=0):
    """Minimal window-carrying EvidencePacket for direct registry tests."""
    return EvidencePacket(
        window_index=widx,
        schema_hash=f"schema-{len(stages)}",
        stages=tuple(stages),
        steps=d.shape[0],
        world_size=d.shape[1],
        gather_ok=True,
        labels=(),
        routing_stages=(),
        shares=(),
        gains=(),
        co_critical_stages=(),
        downgrade_reasons=(),
        leader_rank=-1,
        sync_stages=tuple(sync_names),
        window=d,
    )


class TestFusedServicePath:
    def test_hetero_cohorts_fused_equals_unfused(self):
        # two cohorts with different stage vocabularies (S=4 and S=6)
        # refresh as separate shape groups through the same service; the
        # fused and four-dispatch services must agree bit for bit on
        # every kernel-refreshed field
        rng = np.random.default_rng(6)
        svc_f = FleetService(fused=True)
        svc_u = FleetService(fused=False)
        cohorts = [
            ("small", ("a", "b", "c", "d"), ("b", "d")),
            ("large", ("a", "b", "c", "d", "e", "f"), ("c", "f")),
        ]
        job_ids = []
        for name, stages, sync in cohorts:
            for j in range(3):
                d = rng.exponential(0.1, (5, 4, len(stages)))
                pkt = _packet(d, stages, sync)
                for svc in (svc_f, svc_u):
                    assert svc.registry.update(f"{name}-{j}", pkt, 0)
                job_ids.append(f"{name}-{j}")
        assert len(svc_f.registry.dirty_groups()) == 2
        assert svc_f.refresh_batched() == 6
        assert svc_u.refresh_batched() == 6
        for jid in job_ids:
            jf, ju = svc_f.registry.get(jid), svc_u.registry.get(jid)
            np.testing.assert_array_equal(jf.kernel_shares, ju.kernel_shares)
            np.testing.assert_array_equal(jf.kernel_gains, ju.kernel_gains)
            np.testing.assert_array_equal(jf.whatif, ju.whatif)
            assert jf.kernel_leader == ju.kernel_leader
            assert jf.last_window is None and ju.last_window is None

    def test_stager_recycles_buffers_across_ticks(self):
        # steady-state ticks of the same cohort shape reuse one staging
        # buffer; results stay correct after the rebind
        svc = FleetService(fused=True)
        stages, sync = ("a", "b", "c", "d"), ("b",)
        rng = np.random.default_rng(8)
        for tick in range(3):
            for j in range(2):
                d = rng.exponential(0.1, (4, 3, 4))
                svc.registry.update(f"j{j}", _packet(d, stages, sync, tick), tick)
            assert svc.refresh_batched() == 2
        assert len(svc._stager._buffers) == 1

    @pytest.mark.parametrize("fault_every", [0, 3])
    def test_replay_fused_equals_unfused(self, fault_every):
        # end-to-end: the synthetic trace generator emits worker/ps/eval
        # task groups with heterogeneous stage vocabularies, so a replay
        # exercises multi-cohort grouping through the real service path
        text = generate_trace(
            jobs=6, ticks=8, window_steps=6, world_size=8, seed=9,
            fault_every=fault_every,
        )
        trace_f = parse_trace(text, name="par")
        trace_u = parse_trace(text, name="par")
        rep_f = replay_trace(trace_f, fused=True)
        rep_u = replay_trace(trace_u, fused=False)
        df, du = rep_f.as_dict(), rep_u.as_dict()
        # "obs" is the self-observability section: wall-clock by
        # construction, excluded like the other timing fields
        for k in ("elapsed_s", "windows_per_s", "obs"):
            df.pop(k, None)
            du.pop(k, None)
        assert df == du
