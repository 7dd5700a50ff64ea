"""Operations and bytes that the served work needs, from shapes and the live
rows' KV lengths: the yardstick of the kernel's roofline share and of the
whole step's share of the chip's peak.

The counts are of what the algorithm needs, not of what a kernel happens to
touch: a paged-attention call over a row of ``L`` cached tokens needs those
``L`` tokens' K and V, their pages' scales, its queries and its outputs,
however many pages its grid walks. So a kernel that reads pages no row
holds shows as a low share, not as a larger count.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    page_size: int
    kv_bytes: float = 1.0      # stored bytes per K or V element (int8)
    q_bytes: int = 2           # the kernel's query operand (bf16)
    out_bytes: int = 4         # the kernel's output (float32)
    scale_bytes: int = 4       # one float32 scale per page, for K and for V


def attention_call(shape: Shape, rows: Iterable[Tuple[int, int]]):
    """(flops, bytes) of ONE layer's paged-attention call. ``rows`` holds
    ``(n, end)`` per live row: ``n`` new queries at positions ``end - n ..
    end - 1``, each attending causally to every cached position up to its
    own (``end`` tokens of history for the last one)."""
    hhd = shape.heads * shape.head_dim
    flops = 0
    nbytes = 0
    for n, end in rows:
        start = end - n
        # QK^T and PV: 2 * 2 * H * hd per (query, key) pair
        flops += 4 * hhd * (n * start + n * (n + 1) // 2)
        pages = -(-end // shape.page_size)
        nbytes += (2 * end * shape.kv_heads * shape.head_dim * shape.kv_bytes
                   + 2 * pages * shape.scale_bytes
                   + n * hhd * (shape.q_bytes + shape.out_bytes))
    return flops, nbytes


def least_time(shape: Shape, peaks: dict, calls) -> float:
    """Least seconds the chip could spend on ``calls`` (each a ``rows``
    list as in :func:`attention_call`), every layer's call bounded by the
    larger of its operations over peak bf16 FLOP/s and its bytes over peak
    HBM bandwidth."""
    total = 0.0
    for rows in calls:
        flops, nbytes = attention_call(shape, rows)
        total += max(flops / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return shape.layers * total


def dense_flops_per_token(shape: Shape) -> int:
    """Matmul operations of one token through one layer, attention's
    score and value products excluded."""
    d, hhd = shape.d_model, shape.heads * shape.head_dim
    kvhd = shape.kv_heads * shape.head_dim
    return 2 * (d * hhd + 2 * d * kvhd + hhd * d + 3 * d * shape.d_ff)


def model_flops(shape: Shape, rows: Iterable[Tuple[int, int]],
                head: bool) -> int:
    """Model operations of one forward over ``rows`` (as in
    :func:`attention_call`), through every layer, plus the LM head for
    each row's last token when ``head`` (decode emits a token per row;
    chunked prefill skips the head)."""
    rows = list(rows)
    tokens = sum(n for n, _ in rows)
    attn, _ = attention_call(shape, rows)
    flops = shape.layers * (tokens * dense_flops_per_token(shape) + attn)
    if head:
        flops += len(rows) * 2 * shape.d_model * shape.vocab
    return flops
