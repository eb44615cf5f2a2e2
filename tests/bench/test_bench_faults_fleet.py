"""A run of a fleet cell, driven on the CPU with the look for a chip
skipped, decides `correct` from the oracle and the injected truth: true
for the program, false with the timed path broken underneath."""
import pytest

import harness
from conftest import TINY_FLEET, TINY_FLEET_TRAFFIC, TINY_LIMITS, cpu_context

CELL = "fleet-ddp128-steady"


def plant(monkeypatch, fault):
    import repro.fleet.registry as registry
    import repro.fleet.service as service
    import repro.kernels.frontier as frontier

    if fault == "answer_altered":
        orig = frontier.fused_fleet_tick

        def tick(*args, **kwargs):
            out = orig(*args, **kwargs)
            return out._replace(whatif=out.whatif._replace(matrix=out.whatif.matrix * 1.001))

        monkeypatch.setattr(frontier, "fused_fleet_tick", tick)
    elif fault == "state_unchanged":
        monkeypatch.setattr(service.FleetService, "refresh_batched", lambda self, **kw: 0)
    else:
        orig = registry.FleetRegistry.dirty_groups

        def half(self):
            return {k: jobs[: len(jobs) // 2] for k, jobs in orig(self).items()}

        monkeypatch.setattr(registry.FleetRegistry, "dirty_groups", half)


@pytest.mark.parametrize("cell", [CELL, "fleet-pai8-steady"])
def test_sound_run_is_correct(tiny_bench, cell):
    out = tiny_bench(cell, seed=2**31 + 3)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"route_p95_ms", "routed_windows_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch", "state_unchanged"])
def test_broken_tick_is_not_correct(tiny_bench, monkeypatch, fault):
    plant(monkeypatch, fault)
    out = tiny_bench(CELL, seed=4)
    assert not out["correct"], out["checks"]


def test_bfloat16_control_fails_a_limit():
    config = {**harness.load_json("configs", "fleet-ddp128"), **TINY_FLEET}
    fleet = harness.load_module("drivers", "fleet")
    got = fleet.control(cpu_context(config, TINY_FLEET_TRAFFIC, seed=6))
    assert got["control_bf16"]["kernel_gap"] > TINY_LIMITS["fleet"]["kernel_gap"], got
