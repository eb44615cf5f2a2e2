"""Pallas TPU kernels for the paper's compute hot-spots.

frontier/ — fused frontier accounting (Eq. 2 shares + Eq. 4 gains + leader
evidence in one HBM pass).  Each kernel ships <name>.py (pl.pallas_call +
BlockSpec), ops.py (jitted wrapper; interpret mode iff the backend is
the CPU, see frontier.resolve_interpret) and ref.py
(pure-jnp oracle swept by tests/test_kernel_frontier.py).

flash.py — causal attention for the models on the TPU: JAX's own Pallas
flash-attention kernel behind the models' [B, S, H, D] layout, with the
tile sizes measured for it (`models.attention.attention_path` decides
where it runs).
"""
