"""A run of each train cell, driven on the CPU with the look for a chip
skipped, decides `correct` from the comparison with its reference: true
for the program, false with the timed path broken underneath.  Every
cell in BENCHMARK.json whose configuration runs the `train` driver is
checked, at its configuration's CPU size and limits
(`tests/bench/tiny/<config>.json`)."""
import jax.numpy as jnp
import pytest

import harness
from conftest import cpu_context, tiny_cell, train_cells

TRAIN_CELLS = train_cells()


def broken_step(monkeypatch, fault):
    """Plant `fault` in the program's compiled train step."""
    import repro.launch.steps as steps

    orig = steps.build_train_step

    def build(*args, **kwargs):
        kwargs["donate"] = False
        fn, state_sh = orig(*args, **kwargs)

        def step(state, batch):
            if fault == "state_unchanged":
                return state, fn(state, batch)[1]
            if fault == "loss_altered":
                new, metrics = fn(state, batch)
                return new, {**metrics, "loss": metrics["loss"] * 1.001}
            half = {k: jnp.concatenate([v[: v.shape[0] // 2]] * 2) for k, v in batch.items()}
            return fn(state, half)

        return step, state_sh

    monkeypatch.setattr(steps, "build_train_step", build)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_sound_run_is_correct(tiny_bench, cell):
    out = tiny_bench(cell, seed=2**31 + 11)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "step_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_broken_step_is_not_correct(tiny_bench, monkeypatch, cell, fault):
    broken_step(monkeypatch, fault)
    out = tiny_bench(cell, seed=12)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_float8_control_fails_a_limit(cell):
    config, traffic = tiny_cell(cell)
    train = harness.load_module("drivers", "train")
    got = train.control(cpu_context(config, traffic, seed=13))
    limits = config["limits"]
    assert any(got["control_fp8"][k] > limits[k] for k in limits), got
