"""Useful operations and bytes of the benchmark's work, from shapes alone.

These are the yardstick of every roofline share and utilization the
benchmark reports, so they count what the algorithm needs, never what an
implementation happens to do: padding, recomputation and masked-out work
are not in them.
"""
from __future__ import annotations


# ------------------------------------------------------------ sparse MTTKRP


def mttkrp_ops(nnz: int, nmodes: int, rank: int) -> int:
    """One mode's MTTKRP: per nonzero, (N-1)*R multiplies (value times the
    N-1 other factors' rows, elementwise) and R adds into the output row."""
    return nnz * ((nmodes - 1) * rank + rank)


def mttkrp_bytes(nnz: int, nmodes: int, rank: int, out_rows: int,
                 index_bytes: int = 4, value_bytes: int = 4,
                 row_bytes: int = 1, out_bytes: int = 4) -> int:
    """One mode's MTTKRP, least traffic: each nonzero's N indices and its
    value read once, its N-1 gathered factor rows (int8 words, as the
    pSRAM path stores them) read once, the output written once."""
    per_nnz = nmodes * index_bytes + value_bytes \
        + (nmodes - 1) * rank * row_bytes
    return nnz * per_nnz + out_rows * rank * out_bytes


def mttkrp_least_s(nnz, dims, rank, peak_ops, bytes_per_s):
    """Least time of one sweep's N stream MTTKRPs on the chip and which
    side binds: ``(seconds, "memory" | "compute")``."""
    n = len(dims)
    t_ops = t_mem = 0.0
    for d in dims:
        t_ops += mttkrp_ops(nnz, n, rank) / peak_ops
        t_mem += mttkrp_bytes(nnz, n, rank, d) / bytes_per_s
    return max(t_ops, t_mem), ("memory" if t_mem >= t_ops else "compute")


def cp_als_sweep_ops(nnz: int, dims, rank: int) -> int:
    """Useful operations of one CP-ALS sweep: N MTTKRPs for the updates and
    one more for the fit, each factor's (R,R) Gram and its solve
    ``M @ pinv(G)`` (2*I*R^2 each), and the fit's inner product over the
    last factor (2*I*R)."""
    n = len(dims)
    ops = (n + 1) * mttkrp_ops(nnz, n, rank)
    for d in dims:
        ops += 2 * d * rank * rank      # Gram
        ops += 2 * d * rank * rank      # M @ pinv(G)
    ops += 2 * dims[-1] * rank
    return ops


# --------------------------------------------------------- decoder LM


def layer_params(d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                 d_ff: int) -> int:
    """Matmul weights of one llama-style layer: q, k, v, o projections and
    a gated (SwiGLU) feed-forward."""
    q, kv = n_heads * head_dim, n_kv_heads * head_dim
    return d_model * (q + 2 * kv) + q * d_model + 3 * d_model * d_ff


def prefill_flops(prompt_len: int, layers: int, d_model: int, n_heads: int,
                  n_kv_heads: int, head_dim: int, d_ff: int,
                  vocab: int) -> int:
    """One prompt's prefill: every token through every layer's matmuls,
    causal attention (QK^T and PV over the L(L+1)/2 pairs a causal mask
    keeps), and the unembedding at the one position whose logits are
    used."""
    p = layer_params(d_model, n_heads, n_kv_heads, head_dim, d_ff)
    pairs = prompt_len * (prompt_len + 1) // 2
    attn = 2 * 2 * n_heads * head_dim * pairs
    return layers * (2 * prompt_len * p + attn) + 2 * d_model * vocab


def model_dims(cfg: dict) -> dict:
    """The keyword arguments above, from a configuration file's numbers."""
    return {"layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "d_ff": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"]}
