"""Operations and bytes the algorithms need, from their shapes alone.

Kept with the benchmark so that every change is measured with the same
arithmetic; nothing here reads the program.
"""
from __future__ import annotations


def gpt_matmul_params(model: dict) -> int:
    """Weights that take part in a matrix product per token: the four
    attention projections and the two MLP matrices of every layer, and
    the output head (the tied embedding, read as a [d, V] matrix)."""
    d, ff, v, layers = (model["n_embd"], model["n_inner"], model["vocab_size"],
                        model["n_layer"])
    return layers * (4 * d * d + 2 * d * ff) + d * v


def gpt_train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs of one training token: 6 per matrix weight (forward 2,
    backward 4) and 12 * layers * d * seq for the two attention products
    (scores and values, forward and backward), counted over the whole
    sequence as PaLM's appendix B and nanoGPT's `estimate_mfu` count them.
    Recomputation is not counted."""
    attn = 12 * model["n_layer"] * model["n_embd"] * seq
    return 6.0 * gpt_matmul_params(model) + attn


# Elementwise operations per window cell [t, r, s] of one fused tick, by
# family (kernels/frontier: the frontier and the counterfactual what-if
# routes the service dispatches, regimes and co-activation off):
#   frontier: prefix add 1, max over ranks 1, second max 1, excess
#             (subtract, max) 2, final minus excess 1, max over ranks 1;
#   what-if:  imputed work (min over ranks) 1, excess 2, prefix add 1,
#             shifted arrival (subtract, max) 2, gain (subtract, max) 2,
#             sum over steps 1.
TICK_OPS_PER_CELL = 7 + 9


def tick_flops(jobs: int, steps: int, ranks: int, stages: int) -> float:
    """Operations of one fused tick over the live, unpadded windows."""
    return float(TICK_OPS_PER_CELL * jobs * steps * ranks * stages)


def tick_bytes(jobs: int, steps: int, ranks: int, stages: int) -> float:
    """Bytes one fused tick must move: the live float32 windows read once,
    and the outputs the service reads back (shares and gains [J, S], the
    per-step leader [J, N, S] int32, the what-if matrix [J, S, R])."""
    window = jobs * steps * ranks * stages
    outputs = jobs * (2 * stages + steps * stages + stages * ranks)
    return 4.0 * (window + outputs)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
