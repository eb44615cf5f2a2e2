"""Attention: causal prefill (a Pallas flash kernel on the TPU, else a
chunked online softmax in XLA), bidirectional cross attention, and
single-token KV-cache decode.

`attention_path` is the one rule for which causal implementation runs.
On a single TPU device, for multi-head attention (no GQA, no sliding
window) at a sequence length that is a multiple of 128, it is JAX's
Pallas flash-attention kernel (`repro.kernels.flash`): score tiles stay in
VMEM, tiles above the diagonal are skipped, and the kernel's own VJP
recomputes probabilities tile by tile.  It takes q, k and v in the
activations' dtype (the configuration's compute dtype) and accumulates in
f32, so `cast_f32`, `remat_qblock`, the chunk sizes and `triangular` have
no effect there.  Everything else (the CPU, GQA, sliding windows,
unaligned lengths, the dry run's unrolled cost extraction on the CPU,
and decode) runs the XLA path below.

GQA is computed *grouped* (no `jnp.repeat` materialization): queries are
reshaped to [B, S, KV, G, D] and contracted against the un-expanded KV, so
HBM traffic for KV stays at the true GQA size — this matters for the decode
roofline where KV-cache reads dominate.

The XLA prefill is a double-chunked online softmax (lax.scan over KV
chunks inside a scan over Q chunks): peak scores memory is
q_chunk x kv_chunk instead of S^2.  With ``triangular=True`` the Q-chunk
loop is unrolled with exact KV ranges, skipping fully-masked KV chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels.flash import flash_causal_attention

NEG = -1e30


def _group_q(q: jax.Array, n_kv: int) -> jax.Array:
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _chunk_attn_block(
    qg: jax.Array,      # [B, Sq, KV, G, D]
    k: jax.Array,       # [B, Skv, KV, D]
    v: jax.Array,       # [B, Skv, KV, D]
    mask: jax.Array,    # [Sq, Skv] bool (True = attend)
    state: tuple[jax.Array, jax.Array, jax.Array] | None,
    scale: float,
    cast_f32: bool = True,
):
    """One online-softmax accumulation step. state = (m, l, acc).

    cast_f32=False keeps bf16 operands with f32 MXU accumulation
    (preferred_element_type): no materialized f32 copies of K/V.
    """
    if cast_f32:
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
    else:
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
        ) * scale
    s = jnp.where(mask[None, None, None], s, NEG)
    m_new = s.max(axis=-1)                                   # [B,KV,G,Sq]
    p = jnp.exp(s - m_new[..., None])
    l_new = p.sum(axis=-1)
    if cast_f32:
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    else:
        pv = jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
    if state is None:
        return m_new, l_new, pv
    m, l, acc = state
    m2 = jnp.maximum(m, m_new)
    c_old = jnp.exp(m - m2)
    c_new = jnp.exp(m_new - m2)
    return m2, l * c_old + l_new * c_new, acc * c_old[..., None] + pv * c_new[..., None]


def _finish(m, l, acc, b, sq, h, d, dtype):
    out = acc / jnp.maximum(l[..., None], 1e-30)             # [B,KV,G,Sq,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(dtype)


def _one_tpu() -> bool:
    """Whether a computation traced now lands on a TPU, in a process that
    sees one device.  The platform is the default device's where one is
    set (`jax.default_device`), else the default backend's.  Mosaic
    kernels cannot be partitioned by GSPMD, so a multi-device process
    keeps the XLA path."""
    dev = jax.config.jax_default_device
    if dev is None:
        platform = jax.default_backend()
    else:
        platform = dev if isinstance(dev, str) else dev.platform
    return platform == "tpu" and jax.device_count() == 1


def attention_path(q_shape: tuple, n_kv: int, window: int | None) -> str:
    """"flash" where `chunked_causal_attention` runs the Pallas kernel,
    else "xla".  q_shape: [B, S, H, D]."""
    _, s, h, d = q_shape
    if (
        window is None
        and n_kv == h
        and s % 128 == 0
        and (d <= 128 or d % 128 == 0)
        and _one_tpu()
    ):
        return "flash"
    return "xla"


def chunked_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    window: int | None = None,
    triangular: bool = False,
    unroll: bool = False,
    cast_f32: bool = True,
    remat_qblock: bool = True,
) -> jax.Array:
    """Causal (optionally sliding-window) attention, O(q_chunk*kv_chunk) memory.

    q: [B, S, H, D]; k, v: [B, S, KV, D].  S must divide by the chunk sizes
    (configs guarantee this; smoke tests use small aligned chunks).  Where
    `attention_path` says "flash", the keyword arguments other than
    `window` have no effect.
    """
    if attention_path(q.shape, k.shape[2], window) == "flash":
        return flash_causal_attention(q, k, v)
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    scale = 1.0 / (d**0.5)
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s)
    nq, nkv = s // q_chunk, s // kv_chunk
    qg = _group_q(q, n_kv)                                    # [B,S,KV,G,D]
    qs = qg.reshape(b, nq, q_chunk, n_kv, h // n_kv, d)
    ks = k.reshape(b, nkv, kv_chunk, n_kv, d)
    vs = v.reshape(b, nkv, kv_chunk, n_kv, d)

    qpos_in = jnp.arange(q_chunk)
    kpos_in = jnp.arange(kv_chunk)

    def mask_for(iq, jk):
        qpos = iq * q_chunk + qpos_in                          # [q_chunk]
        kpos = jk * kv_chunk + kpos_in                         # [kv_chunk]
        m = qpos[:, None] >= kpos[None, :]
        if window is not None:
            m &= qpos[:, None] - kpos[None, :] < window
        return m

    def q_block_raw(iq, qb):
        # qb: [B, q_chunk, KV, G, D]
        def kv_step(state, jk):
            mask = mask_for(iq, jk)
            kb = jax.lax.dynamic_index_in_dim(ks, jk, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vs, jk, 1, keepdims=False)
            new = _chunk_attn_block(qb, kb, vb, mask, state, scale, cast_f32)
            return new, None

        init = (
            jnp.full((b, n_kv, h // n_kv, q_chunk), NEG, jnp.float32),
            jnp.zeros((b, n_kv, h // n_kv, q_chunk), jnp.float32),
            jnp.zeros((b, n_kv, h // n_kv, q_chunk, d), jnp.float32),
        )
        if triangular:
            # static KV range: only chunks overlapping [lo, hi] are touched.
            hi = (iq + 1) * q_chunk  # exclusive
            lo = 0 if window is None else max(0, iq * q_chunk - window + 1)
            j0, j1 = lo // kv_chunk, (hi + kv_chunk - 1) // kv_chunk
            state = init
            for jk in range(j0, j1):
                state = _chunk_attn_block(
                    qb, ks[:, jk], vs[:, jk], mask_for(iq, jk), state, scale,
                    cast_f32,
                )
            m, l, acc = state
        elif unroll:
            # IDENTICAL math to the scan (all chunk pairs, masked), python-
            # unrolled so HLO cost analysis counts every pair (dry-run mode).
            state = init
            for jk in range(nkv):
                state = _chunk_attn_block(
                    qb, ks[:, jk], vs[:, jk], mask_for(iq, jk), state, scale,
                    cast_f32,
                )
            m, l, acc = state
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nkv))
        return _finish(m, l, acc, b, q_chunk, h, d, q.dtype)

    # flash-style backward: recompute the online-softmax internals instead
    # of saving per-(q,kv)-chunk probability residuals (which would cost
    # ~q_chunk*kv_chunk*heads f32 per chunk pair in HBM during the grad).
    # Optional: under layer-level remat this nests recomputes (3x attention
    # fwd per step); DP-heavy plans with small per-device batch turn it off.
    q_block = (
        jax.checkpoint(q_block_raw, static_argnums=(0,))
        if remat_qblock
        else q_block_raw
    )

    if triangular or unroll:
        outs = [q_block(iq, qs[:, iq]) for iq in range(nq)]
        return jnp.concatenate(outs, axis=1)

    def scan_q(_, iq):
        qb = jax.lax.dynamic_index_in_dim(qs, iq, 1, keepdims=False)
        return None, q_block(iq, qb)

    _, blocks = jax.lax.scan(scan_q, None, jnp.arange(nq))    # [nq,B,qc,H,D]
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def full_cross_attention(
    q: jax.Array, k: jax.Array, v: jax.Array
) -> jax.Array:
    """Bidirectional (encoder / cross) attention, grouped GQA, un-chunked."""
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    scale = 1.0 / (d**0.5)
    qg = _group_q(q, n_kv)
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def decode_attention_bksd(
    q: jax.Array,          # [B, 1, H, D]
    k_cache: jax.Array,    # [B, KV, S_cache, D]  (head-major layout)
    v_cache: jax.Array,
    length: jax.Array,
    cast_f32: bool = True,
) -> jax.Array:
    """Head-major-cache decode attention: the cache's (B, KV) leading dims
    are exactly the einsum batch dims, so no cache-sized transposes."""
    b, n_kv, s_cache, d = k_cache.shape
    h = q.shape[2]
    scale = 1.0 / (d**0.5)
    qg = _group_q(q, n_kv)                                    # [B,1,KV,G,D]
    if cast_f32:
        s = jnp.einsum(
            "bqkgd,bksd->bkgqs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
        ) * scale
    else:
        s = jnp.einsum(
            "bqkgd,bksd->bkgqs", qg, k_cache, preferred_element_type=jnp.float32
        ) * scale
    pos = jnp.arange(s_cache)
    s = jnp.where(pos[None, None, None, None, :] < length, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    if cast_f32:
        out = jnp.einsum("bkgqs,bksd->bkgqd", p, v_cache.astype(jnp.float32))
    else:
        out = jnp.einsum(
            "bkgqs,bksd->bkgqd", p.astype(v_cache.dtype), v_cache,
            preferred_element_type=jnp.float32,
        )
    return out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h, d).astype(q.dtype)


def update_kv_cache_bksd(k_cache, v_cache, k_new, v_new, index):
    """k_new/v_new: [B, 1, KV, D] -> write at [:, :, index, :]."""
    kn = jnp.swapaxes(k_new, 1, 2).astype(k_cache.dtype)      # [B, KV, 1, D]
    vn = jnp.swapaxes(v_new, 1, 2).astype(v_cache.dtype)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, kn, index, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, vn, index, axis=2)
    return k_cache, v_cache


def decode_attention(
    q: jax.Array,          # [B, 1, H, D]
    k_cache: jax.Array,    # [B, S_cache, KV, D]
    v_cache: jax.Array,
    length: jax.Array,     # [] current valid cache length (incl. new token)
    cast_f32: bool = True,
) -> jax.Array:
    """Single-token attention against a (possibly partially-filled) cache.

    cast_f32=False reads the cache in bf16 with f32 accumulation: the cache
    is the dominant HBM traffic of a decode step, and a materialized f32
    copy doubles it (§Perf iteration on gemma-7b/decode_32k).
    """
    b, s_cache, n_kv, d = k_cache.shape
    h = q.shape[2]
    scale = 1.0 / (d**0.5)
    qg = _group_q(q, n_kv)                                    # [B,1,KV,G,D]
    if cast_f32:
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
        ) * scale
    else:
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_cache, preferred_element_type=jnp.float32
        ) * scale
    pos = jnp.arange(s_cache)
    s = jnp.where(pos[None, None, None, None, :] < length, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    if cast_f32:
        out = jnp.einsum("bkgqs,bskd->bkgqd", p, v_cache.astype(jnp.float32))
    else:
        out = jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache,
            preferred_element_type=jnp.float32,
        )
    return out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h, d).astype(q.dtype)


def update_kv_cache(
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,      # [B, 1, KV, D]
    v_new: jax.Array,
    index: jax.Array,      # [] write position
):
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), index, axis=1
    )
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), index, axis=1
    )
    return k_cache, v_cache
