"""Operations and bytes the algorithm needs, computed from shapes.

Convention (stated in PERF.md): per trained token,
  6 x (matmul parameters: per layer 4 d^2 + 2 d d_ff, and vocab x d once
       for the tied unembedding; wpe and the embedding gather count
       nothing)
  + causal attention 6 x n_layer x d x T  (QK^T and PV, forward 2 x 2 x d x
       T/2 multiply-adds per token and layer, backward twice that: half of
       the full-T count).
Recomputed operations (remat, the kernel's backward recomputing scores)
do not count.
"""

from __future__ import annotations

from typing import Dict


def matmul_params(n_layer: int, d: int, d_ff: int, vocab: int) -> int:
    return n_layer * (4 * d * d + 2 * d * d_ff) + vocab * d


def train_flops_per_token(n_layer: int, d: int, d_ff: int, vocab: int,
                          seq_len: int) -> float:
    return (6.0 * matmul_params(n_layer, d, d_ff, vocab)
            + 6.0 * n_layer * d * seq_len)


def flash_causal_train(batch: int, heads: int, seq_len: int, head_dim: int,
                       n_layer: int, dtype_bytes: int = 2
                       ) -> Dict[str, float]:
    """What causal attention needs for one step, forward and backward, over
    all layers: the FLOPs of the mathematics (forward 2 matmuls, backward
    4, each 2 x T x T/2 x head_dim multiply-adds' worth per head and row),
    and the bytes a kernel that keeps scores on chip must move (forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv; the per-row log-sum-exp is left out as small)."""
    per_matmul = 2.0 * batch * heads * seq_len * (seq_len / 2.0) * head_dim
    tensor = float(batch * heads * seq_len * head_dim * dtype_bytes)
    return {"flops": n_layer * 6.0 * per_matmul,
            "bytes": n_layer * (4.0 + 8.0) * tensor}


def roofline_share_pct(flops: float, nbytes: float, seconds: float,
                       peak_flops: float, peak_bytes: float):
    """(share in %, which bound): the least time the chip could take over
    the time it took."""
    t_compute, t_memory = flops / peak_flops, nbytes / peak_bytes
    least = max(t_compute, t_memory)
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * least / seconds, bound
