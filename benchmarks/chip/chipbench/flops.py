"""Operations and bytes that the algorithm needs, from shapes alone.

They count the work an ideal implementation must do, not what today's code
does, so that a later kernel that skips work reads the same count:

- a decode-attention call reads K and V of each active row up to its
  length, reads q and writes out, and does 4 * len * H * hd operations per
  row (q.k and p.v, a multiply and an add each);
- a causal flash-prefill call over n tokens does 4 * H * hd * n(n+1)/2
  operations and reads q, k, v and writes o once;
- the model's operations are 2 per weight a token meets, plus attention:
  prefill computes the LM head for the last position only.

Bytes are of bfloat16 (2 bytes an element).
"""
from __future__ import annotations

from typing import Dict

BYTES = 2


def matmul_params(d: Dict[str, int]) -> int:
    """Weights one token meets in one layer's matrix products."""
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F


def decode_attn(d: Dict[str, int], rows: int, sum_ctx: int) -> Dict[str, float]:
    """All layers of one decode step: ``rows`` active rows whose attended
    lengths sum to ``sum_ctx``."""
    L, H, KV, hd = d["L"], d["H"], d["KV"], d["hd"]
    flops = L * 4 * H * hd * sum_ctx
    by = L * BYTES * (2 * KV * hd * sum_ctx + 2 * rows * H * hd)
    return {"flops": float(flops), "bytes": float(by)}


def flash_prefill(d: Dict[str, int], n: int) -> Dict[str, float]:
    """All layers of one causal prefill over ``n`` tokens."""
    L, H, KV, hd = d["L"], d["H"], d["KV"], d["hd"]
    flops = L * 4 * H * hd * n * (n + 1) // 2
    by = L * BYTES * n * hd * (2 * H + 2 * KV)
    return {"flops": float(flops), "bytes": float(by)}


def model_decode(d: Dict[str, int], rows: int, sum_ctx: int) -> float:
    """Model operations of one decode step."""
    per_row = 2 * (d["L"] * matmul_params(d) + d["D"] * d["V"])
    return float(rows * per_row) + decode_attn(d, rows, sum_ctx)["flops"]


def model_prefill(d: Dict[str, int], n: int) -> float:
    """Model operations of one prefill over ``n`` tokens."""
    return (2.0 * n * d["L"] * matmul_params(d) + 2.0 * d["D"] * d["V"]
            + flash_prefill(d, n)["flops"])


def least_time(work: Dict[str, float], peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(work["flops"] / peaks["bf16_flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
