"""The flash-attention path of `chunked_causal_attention` and its dispatch rule.

The kernel runs here in Pallas's TPU interpret mode, reached through the
model's own entry point with the rule's device check steered to "one TPU"
(`_one_tpu`); everything else in the rule is what the real call sees.
Outputs and the gradients for q, k and v are compared with the XLA path
and with a float32 softmax reference on the same bf16 inputs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash import block_sizes
from repro.models import attention
from repro.models.attention import attention_path, chunked_causal_attention

#: both paths round their outputs and gradients to bf16 once, and the
#: kernel multiplies the probabilities by v in bf16: each may sit a few
#: bf16 ulps (2**-7 relative) from the f32 reference, so the bound is two
#: such steps of the largest reference magnitude
TOL = 2 * 2.0**-7


def _reference(q, k, v):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
    scores = scores * q.shape[-1] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _value_and_grads(fn, q, k, v, do):
    out, pull = jax.vjp(fn, q, k, v)
    return (out, *pull(do.astype(out.dtype)))


def _xla(q, k, v):
    return chunked_causal_attention(q, k, v, q_chunk=128, kv_chunk=128)


@pytest.mark.parametrize("shape", [(1, 256, 2, 64), (2, 384, 1, 128)])
def test_flash_path_matches_xla_and_reference(monkeypatch, shape):
    keys = jax.random.split(jax.random.PRNGKey(sum(shape)), 4)
    q, k, v, do = (
        jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        for key in keys
    )
    xla = jax.jit(lambda *a: _value_and_grads(_xla, *a))(q, k, v, do)
    ref = jax.jit(lambda *a: _value_and_grads(_reference, *a))(q, k, v, do)

    monkeypatch.setattr(attention, "_one_tpu", lambda: True)
    assert attention_path(shape, shape[2], None) == "flash"
    assert "pallas_call" in str(jax.make_jaxpr(_xla)(q, k, v))
    with pltpu.force_tpu_interpret_mode():
        flash = jax.jit(lambda *a: _value_and_grads(_xla, *a))(q, k, v, do)
        jax.block_until_ready(flash)

    for name, f, x, r in zip(("out", "dq", "dk", "dv"), flash, xla, ref):
        assert f.dtype == jnp.bfloat16 and f.shape == shape, name
        f, x, r = (a.astype(jnp.float32) for a in (f, x, r))
        bound = TOL * float(jnp.abs(r).max())
        assert float(jnp.abs(f - r).max()) <= bound, name
        assert float(jnp.abs(f - x).max()) <= bound, name


@pytest.mark.parametrize(
    "q_shape, n_kv, window, one_tpu, want",
    [
        # paper-gpt-125m's cell shape
        ((8, 1024, 12, 64), 12, None, True, "flash"),
        ((8, 1024, 12, 64), 12, None, False, "xla"),
        ((8, 1024, 12, 64), 12, 256, True, "xla"),       # sliding window
        ((8, 1024, 12, 64), 4, None, True, "xla"),       # GQA
        ((8, 1000, 12, 64), 12, None, True, "xla"),      # S % 128 != 0
        ((2, 384, 8, 96), 8, None, True, "flash"),       # head dim <= 128
        ((2, 384, 8, 192), 8, None, True, "xla"),        # > 128, not a multiple
        ((2, 384, 8, 256), 8, None, True, "flash"),      # multiple of 128
    ],
)
def test_attention_path(monkeypatch, q_shape, n_kv, window, one_tpu, want):
    monkeypatch.setattr(attention, "_one_tpu", lambda: one_tpu)
    assert attention_path(q_shape, n_kv, window) == want


def test_cpu_takes_xla_path():
    assert jax.default_backend() == "cpu"
    assert attention_path((8, 1024, 12, 64), 12, None) == "xla"
    with jax.default_device(jax.devices("cpu")[0]):
        assert attention_path((8, 1024, 12, 64), 12, None) == "xla"


@pytest.mark.parametrize("s", [128, 256, 384, 640, 1024, 4096])
@pytest.mark.parametrize("head_dim", [64, 256])
def test_block_sizes_divide_the_sequence(s, head_dim):
    b = block_sizes(s, head_dim)
    for size in (b.block_q, b.block_k_major, b.block_q_major_dkv,
                 b.block_k_major_dkv, b.block_q_dq, b.block_k_major_dq):
        assert size % 128 == 0 and s % size == 0 and size <= s
