"""The benchmark's plain references against the program, at a size a test
can hold: the GPT reference on the same seeded weights, and the fleet
oracle and packet decoder on the same packets."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fleet_oracle
import fleet_traffic
import gpt_reference as ref
import harness
from conftest import TINY_TRAIN_TRAFFIC, tiny_train_config


def test_gpt_reference_matches_the_program_model_in_float32():
    config = tiny_train_config()
    config["dtypes"] = {"param": "float32", "compute": "float32", "optimizer_state": "float32"}
    train = harness.load_module("drivers", "train")
    model = train.program(ref, config, TINY_TRAIN_TRAFFIC)[0]
    dims = config["model"]
    params = ref.init_params(ref.key_for(2**33 + 5), dims, jnp.float32)
    batch = ref.Tokens(dims["vocab_size"], 4, 32, seed=3).batch_at(0)
    tokens, labels = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(model.loss)(params, {"tokens": tokens, "labels": labels})
    want, g_want = jax.value_and_grad(ref.loss_fn)(params, tokens, labels, dims)
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(jnp.linalg.norm(b)) + 1e-7


def test_fp8_control_rounds_every_product():
    x = jnp.linspace(-1.0, 1.0, 1001)
    q = ref.fp8(x)
    assert float(jnp.max(jnp.abs(q - x))) > 1e-3          # e4m3: 3 mantissa bits
    assert float(jnp.max(jnp.abs(q - x))) < 0.07


FLEET = {"jobs": 6, "ranks": 8, "window_steps": 20, "fault_every": 3, "delay_ms": 150,
         "pool_windows": 2, "ranks_per_host": 2, "placement": "shared", "compress": "int8"}


@pytest.fixture(scope="module")
def packets():
    fleet = fleet_traffic.build_fleet(FLEET, 2**32 + 9)
    return fleet, fleet_traffic.encode_pool(fleet, FLEET)


def test_decoder_reads_what_the_program_decodes(packets):
    from repro.telemetry.packets import decode_packet

    _, pool = packets
    for wire in (pool[0][0], pool[4][1]):
        wire = fleet_traffic.renumber(fleet_traffic.template(wire), 41, 820)
        header, window = fleet_oracle.decode_window(wire)
        pkt = decode_packet(wire)
        assert header["window_index"] == pkt.window_index == 41
        assert pkt.first_step == 820
        np.testing.assert_array_equal(window, pkt.window)


def test_fleet_oracle_matches_the_program_oracle(packets):
    from repro.kernels.frontier import fused_tick_ref

    fleet, pool = packets
    for j in range(FLEET["jobs"]):
        _, window = fleet_oracle.decode_window(pool[j][-1])
        sync = tuple(fleet_traffic.STAGES.index(s)
                     for s in fleet_traffic.SYNC_PROFILES[fleet.profiles[j]])
        want = fused_tick_ref(window.astype(np.float32)[None], sync_stages=sync,
                              with_regimes=False)
        got = fleet_oracle.tick(window, sync)
        assert fleet_oracle.gap(got["shares"], np.asarray(want.frontier.shares[0])) < 1e-6
        assert fleet_oracle.gap(got["gains"], np.asarray(want.frontier.gains[0])) < 1e-6
        assert fleet_oracle.gap(got["whatif"], np.asarray(want.whatif.matrix[0])) < 1e-6
        np.testing.assert_array_equal(got["leader"], np.asarray(want.frontier.leader[0]))
        low = fleet_oracle.tick(window, sync, "bf16")
        assert fleet_oracle.gap(low["whatif"], got["whatif"]) > 1e-4


def test_traffic_is_a_function_of_the_seed(packets):
    fleet, pool = packets
    again = fleet_traffic.build_fleet(FLEET, 2**32 + 9)
    np.testing.assert_array_equal(fleet.durations, again.durations)
    assert fleet.fault_rank == again.fault_rank
    other = fleet_traffic.build_fleet(FLEET, 3)
    assert not np.array_equal(fleet.durations, other.durations)
    sched = fleet_traffic.schedule(fleet, pool, FLEET, {"rate_windows_per_s": 12.0}, 5, 3.0)
    assert np.all(np.diff(sched.due) >= 0) and sched.due[-1] < 3.0
    # one window per job per period, every job at its own phase
    assert len(sched.due) in range(6 * 6, 6 * 7 + 1)
