"""Causal flash attention on the TPU, in the models' [B, S, H, D] layout.

The kernel is the one that ships with JAX
(`jax.experimental.pallas.ops.tpu.flash_attention`): each score tile stays
in VMEM, tiles above the diagonal are skipped, and its backward recomputes
the probabilities tile by tile from the saved row statistics, so no
[B, H, S, S] tensor reaches HBM in either direction.  It multiplies in the
inputs' dtype with f32 accumulation and keeps the softmax statistics in
f32.  This module only transposes to the kernel's [B, H, S, D] layout and
picks the tile sizes.

`repro.models.attention.attention_path` decides when this runs.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas.ops.tpu import flash_attention as fa

#: the tile size for every block of the forward, dkv and dq kernels: of 128,
#: 256 and 512, 512 was fastest for each of the three at paper-gpt-125m's
#: shape (B 8, H 12, S 1024, D 64) on one TPU v5e (PERF.md §6)
_BLOCK = 512


def block_sizes(s: int, head_dim: int) -> fa.BlockSizes:
    """The kernel's tile sizes for sequence length `s` (a multiple of 128):
    the largest of `_BLOCK`, `_BLOCK`/2, ... that divides `s`.  Heads wider
    than 128, which the sweep did not cover, take the kernel's own default
    of 128."""
    b = min(_BLOCK if head_dim <= 128 else 128, s)
    while s % b:
        b //= 2
    return fa.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_q_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
        block_q_dq=b, block_k_major_dq=b, block_k_dq=b,
    )


def flash_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """q, k, v: [B, S, H, D] with the same H (no GQA) -> [B, S, H, D]."""
    s, d = q.shape[1], q.shape[3]
    out = fa.flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=True, sm_scale=d**-0.5, block_sizes=block_sizes(s, d),
    )
    return out.swapaxes(1, 2)
