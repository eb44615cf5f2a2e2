"""Plain reference for the `fleet` driver: the wire packet's window, and
the fused tick's frontier and what-if families of one job, in NumPy.

Imports nothing of the program.  `decode_window` reads an SFP2 packet
(fixed header, JSON header, present ranks, host / switch / pod name
sections, int8 payload with one scale per stage).  `tick` follows the
semantics of the jnp oracles the kernel is held to
(`kernels/frontier/ref.py`, `fused_tick_ref`): per stage, the clip
baseline is the median over steps and ranks (of the raw durations for the
frontier family, of the sync-imputed work for the what-if family); barrier
stages carry the per-step minimum over ranks as their imputed work.

The control, `precision="bf16"`, rounds the window and every
intermediate to bfloat16, the step below the kernel's float32.
"""
from __future__ import annotations

import json
import struct
import zlib

import numpy as np

_F32_TINY = np.finfo(np.float32).tiny


def decode_window(wire: bytes) -> tuple[dict, np.ndarray]:
    """(header, window [N, R, S] float64) of an SFP2 int8 packet."""
    magic, version, flags, hlen = struct.unpack_from("<4sBBI", wire, 0)
    if magic != b"SFP2" or not flags & 1:
        raise ValueError("not an SFP2 packet with a window")
    off = 10
    header = json.loads(wire[off:off + hlen])
    off += hlen
    (nranks,) = struct.unpack_from("<I", wire, off)
    off += 4 + 4 * nranks
    sections = {1: 0, 2: 1, 3: 3}[version]        # host, switch, pod name lists
    for _ in range(sections):
        (count,) = struct.unpack_from("<I", wire, off)
        off += 4
        for _ in range(count):
            (nl,) = struct.unpack_from("<H", wire, off)
            off += 2 + nl
    plen, checksum = struct.unpack_from("<II", wire, off)
    off += 8
    payload = wire[off:off + plen]
    if zlib.adler32(payload) != checksum:
        raise ValueError("payload checksum mismatch")
    meta = header["window"]
    if meta["dtype"] != "int8" or meta.get("codec", "raw") != "raw":
        raise ValueError(f"unsupported window encoding {meta}")
    q = np.frombuffer(payload, np.int8).reshape(meta["shape"])
    return header, q.astype(np.float64) * np.asarray(meta["scales"], np.float64)


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


def tick(d: np.ndarray, sync_idx: tuple, precision: str = "f32") -> dict:
    """Frontier shares and gains [S], per-step leader at every stage
    [N, S], and the what-if matrix [S, R] of one window d[N, R, S]."""
    r = _bf16 if precision == "bf16" else (lambda a: np.asarray(a, np.float32))
    d = r(d)
    n, ranks, s = d.shape

    def median(x):
        m = r(np.median(x.reshape(n * ranks, s), axis=0))
        return np.where(np.abs(m) < _F32_TINY, np.float32(0), m)

    prefix = r(np.cumsum(d, axis=2, dtype=np.float32))          # [N, R, S]
    front = prefix.max(axis=1)                                    # [N, S]
    leader = prefix.argmax(axis=1)
    exposed = front[:, -1]
    clipped = r(prefix[:, :, -1:] - r(np.maximum(0, d - median(d)))).max(axis=1)
    advances = r(np.diff(front, axis=1, prepend=0))
    denom = max(float(exposed.sum()), 1e-30)
    shares = r(advances.sum(axis=0) / denom)
    gains = r(np.maximum(0, (exposed[:, None] - clipped).sum(axis=0)) / denom)

    w = d.copy()
    if sync_idx:
        w[:, :, list(sync_idx)] = d[:, :, list(sync_idx)].min(axis=1, keepdims=True)
    excess = r(np.maximum(0, w - median(w)))
    wpre = r(np.cumsum(w, axis=2, dtype=np.float32))
    contrib = np.zeros((n, ranks, s), np.float32)
    relbase = np.zeros(n, np.float32)
    bounds, start = [], 0
    for i in sorted(sync_idx):
        bounds.append((start, i))
        start = i + 1
    if start < s:
        bounds.append((start, s - 1))
    for a, b in bounds:
        seg = wpre[:, :, b] - (wpre[:, :, a - 1] if a else 0)
        arr = r(relbase[:, None] + seg)                            # [N, R]
        amax = arr.max(axis=1)
        lead = arr.argmax(axis=1)
        second = np.where(np.arange(ranks)[None] == lead[:, None], -np.inf, arr).max(axis=1)
        other = np.where(np.arange(ranks)[None] == lead[:, None], second[:, None], amax[:, None])
        new_a = np.maximum(other[:, :, None], r(arr[:, :, None] - excess[:, :, a:b + 1]))
        contrib[:, :, a:b + 1] = r(np.maximum(0, amax[:, None, None] - new_a))
        relbase = amax
    return {"shares": shares, "gains": gains, "leader": leader,
            "whatif": r(contrib.sum(axis=0)).T}


def top_leader(out: dict) -> int:
    """The service's reading of the leader: the most frequent per-step
    leader at the top-share stage (lowest rank on a tie)."""
    top = int(np.argmax(out["shares"]))
    ranks, counts = np.unique(out["leader"][:, top], return_counts=True)
    return int(ranks[np.argmax(counts)])


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest difference over the reference's largest magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
