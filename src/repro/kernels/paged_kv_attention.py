"""Pallas kernel: GQA variable-length attention over a paged, quantized KV
cache — ONE kernel for chunked prefill (S >= 1) and decode (S == 1).

Generalizes ``kv_attention.py``'s dense int8 kernel to the paged pool of
``repro.core.paged_kv``: KV history lives in fixed-size pages scattered
through a shared pool, each page stored in its quantized container (int8
grid, or a 4-bit grid lane-packed into int32 words along the head dim) with a
per-page dequant scale. The dense kernel is a thin wrapper that builds an
identity page table (see ``kv_attention.py``), and the historical decode
entry point (:func:`paged_kv_attention_decode`) is now the single-query-row
special case of the chunk kernel below.

Reachable via ``ops.paged_kv_attention`` / ``ops.paged_kv_attention_chunk``
(oracle-verified in tests/test_kernels.py); the serving forward routes BOTH
bucketed chunk prefill and decode through here under ``--attn-impl pallas``
(``models.attention.route_paged_attention``), with the jnp gather path kept
as the bitwise-reference mode.

The page table, per-row chunk start positions, per-row valid lengths and
the per-row page scales ride as **scalar-prefetch** operands
(`pltpu.PrefetchScalarGridSpec`): the BlockSpec index maps read
``page_table[b, p]`` to pick which pool page the next DMA fetches, so the
gather happens in the pipeline, not the kernel body — the standard TPU
paged attention pattern. Each DMA brings one whole page (all KV heads); in
VMEM it is unpacked (for sub-byte containers), dequantized by its page
scale, and folded into the online-softmax state.

Grid (B, NQ, NP): NQ blocks of ``block_q`` chunk queries, NP pool pages
innermost sequential; (m, l, acc) scratch carries across pages per query
block. Each key position is masked **causally against its per-row absolute
query positions** (``q_start[b] + query index``) and against the row's
``kv_len`` — partial last pages fall out of the length mask, padded chunk
tails (positions past the row's real tokens) produce garbage rows that no
caller reads (their pool writes were already scratch-redirected by
``paged_update``). Unused page-table entries must point at a valid pool
page (page 0 / scratch) — their positions are masked the same way.
``kv_len`` must be >= 1 per row with at least one real query, else the
masked softmax degenerates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.qtensor import unpack_bits

NEG_INF = -1e30
# float32 dots: the TPU's default rounds float32 operands to bf16, which put
# the kernel 0.1 off its float32 oracle at qwen2-72b widths on a v5e
_F32 = jax.lax.Precision.HIGHEST


def _dequant(x, scale, *, bits, head_dim):
    """(ps, hdw) stored page -> (ps, head_dim) f32 values.

    Shares the lane-unpack convention with core.qtensor (pure jnp right
    shifts, safe on TPU) so kernel == container == oracle. The per-page
    scale applies to every container, float pages included (writers keep
    their scales at 1.0)."""
    if 0 < bits < 8:
        x = unpack_bits(x, bits, head_dim)
    return x.astype(jnp.float32) * scale


def _chunk_kernel(pt_ref, qs_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref,
                  v_ref, o_ref, m_ref, l_ref, acc_ref, *, np_, ps, bq, kv, g,
                  bits, head_dim, sm_scale):
    """One grid step = one query block x one WHOLE pool page (all KV heads,
    ``(1, ps, KV, hdw)`` contiguous in the pool layout). The softmax state
    is carried for all heads at once (rows = KV * bq * G); the per-head
    dots are a static python loop (KV is small). The page's dequant scales
    are SMEM scalars (``ks_ref[b, p]``), gathered per row outside the
    kernel so their size follows the page table, not the pool."""
    b, qb, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, KV, G, hd)
    k = _dequant(k_ref[0], ks_ref[b, p], bits=bits,
                 head_dim=head_dim)                      # (ps, KV, hd)
    v = _dequant(v_ref[0], vs_ref[b, p], bits=bits,
                 head_dim=head_dim)                      # (ps, KV, hd)

    # one causal/length mask, shared by every kv head: flattened row r is
    # chunk query r // G, at absolute position q_start[b] + qb*bq + r // G
    pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    qrow = jax.lax.broadcasted_iota(jnp.int32, (bq * g, 1), 0) // g
    q_pos = qs_ref[b] + qb * bq + qrow                   # (bq*G, 1)
    mask = (pos <= q_pos) & (pos < len_ref[b])           # (bq*G, ps)

    scores = []
    for h in range(kv):                                  # static unroll
        qh = (q[:, h].reshape(bq * g, head_dim) * sm_scale)
        s = jnp.dot(qh, k[:, h].T, precision=_F32,
                    preferred_element_type=jnp.float32)  # (bq*G, ps)
        scores.append(jnp.where(mask, s, NEG_INF))
    s = jnp.concatenate(scores, axis=0)                  # (KV*bq*G, ps)

    m_prev = m_ref[...]                                  # (KV*bq*G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    pexp = jnp.exp(s - m_new)                            # (KV*bq*G, ps)
    corr = jnp.exp(m_prev - m_new)                       # (KV*bq*G, 1)
    l_ref[...] = l_ref[...] * corr + pexp.sum(axis=1, keepdims=True)
    upd = jnp.concatenate(
        [jnp.dot(pexp[h * bq * g:(h + 1) * bq * g], v[:, h],
                 precision=_F32, preferred_element_type=jnp.float32)
         for h in range(kv)],
        axis=0)                                          # (KV*bq*G, hd)
    acc_ref[...] = acc_ref[...] * corr + upd
    m_ref[...] = m_new

    @pl.when(p == np_ - 1)
    def _fin():
        o = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
             ).reshape(kv, bq, g, head_dim)
        o_ref[0] = jnp.moveaxis(o, 0, 1).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_q", "interpret"))
def paged_kv_attention_chunk(q, k_pages, v_pages, k_scale, v_scale,
                             page_table, q_start, kv_len, *, bits: int = 8,
                             block_q: int = 8, interpret: bool = False):
    """Variable-length chunk attention over a paged quantized KV pool.

    q: (B, S, H, hd) float — S chunk queries per sequence (S == 1: decode).
    k_pages/v_pages: (P, ps, KV, hdw) — int8 grid (bits=8), int32 lane-packed
        words with hdw = hd * bits / 32 (bits < 8), or float (bits=0).
    k_scale/v_scale: (P,) f32 per-page dequant scales (value = grid * scale).
    page_table: (B, NP) int32 pool-page ids; unused entries must reference a
        valid page (use the scratch page 0).
    q_start: (B,) int32 absolute position of chunk token 0 per row (== the
        row's cache write offset); query i sits at ``q_start + i`` and
        attends keys causally up to that position.
    kv_len: (B,) int32 valid history length per row INCLUDING the chunk's
        real tokens (>= 1). For padded chunks, query rows past the valid
        tail produce garbage outputs that no caller reads.
    bits must match the page container. Returns (B, S, H, hd) float32.

    Every block's last two dims are whole array dims (``(KV, hdw)`` for
    pages, ``(G, hd)`` for queries), which is what the TPU compiler's
    tiling rule accepts at any head count.
    """
    B, S, H, hd = q.shape
    P, ps, KV, hdw = k_pages.shape
    NP = page_table.shape[1]
    G = H // KV
    bq = max(1, min(block_q, S))
    nq = -(-S // bq)
    sp = nq * bq
    sm_scale = float(1.0 / np.sqrt(hd))
    pt = jnp.asarray(page_table, jnp.int32)
    qs = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32).reshape(-1), (B,))
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    # per-row page scales (B, NP): SMEM holds what the table addresses, so
    # the scalar operands stay small however large the pool grows
    ks = jnp.take(k_scale.astype(jnp.float32), pt, axis=0)
    vs = jnp.take(v_scale.astype(jnp.float32), pt, axis=0)
    qb_in = q.reshape(B, S, KV, G, hd)
    if sp != S:
        qb_in = jnp.pad(qb_in, ((0, 0), (0, sp - S), (0, 0), (0, 0),
                                (0, 0)))
    # scalar operands: page_table, q_start, kv_len, k/v page scales
    page_map = lambda b, qb, p, pt, qs, ln, ks, vs: (pt[b, p], 0, 0, 0)
    row_map = lambda b, qb, p, pt, qs, ln, ks, vs: (b, qb, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nq, NP),
        in_specs=[
            pl.BlockSpec((1, bq, KV, G, hd), row_map),
            pl.BlockSpec((1, ps, KV, hdw), page_map),
            pl.BlockSpec((1, ps, KV, hdw), page_map),
        ],
        out_specs=pl.BlockSpec((1, bq, KV, G, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((KV * bq * G, 1), jnp.float32),    # m
            pltpu.VMEM((KV * bq * G, 1), jnp.float32),    # l
            pltpu.VMEM((KV * bq * G, hd), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, np_=NP, ps=ps, bq=bq, kv=KV, g=G,
                          bits=bits, head_dim=hd, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, sp, KV, G, hd), jnp.float32),
        interpret=interpret,
        name="paged_kv_attention",
    )(pt, qs, lens, ks, vs, qb_in, k_pages, v_pages)
    return out[:, :S].reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def paged_kv_attention_decode(q, k_pages, v_pages, k_scale, v_scale,
                              page_table, kv_len, *, bits: int = 8,
                              interpret: bool = False):
    """Decode attention over a paged quantized KV pool — the S == 1 special
    case of :func:`paged_kv_attention_chunk` (the sole query row sits at
    ``kv_len - 1``, so the causal bound collapses into the length mask).

    q: (B, H, hd) float — one new token per sequence; other shapes as in
    the chunk kernel. kv_len: (B,) int32 valid history length (>= 1).
    Returns (B, H, hd) float32.
    """
    B = q.shape[0]
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    out = paged_kv_attention_chunk(
        q[:, None], k_pages, v_pages, k_scale, v_scale, page_table,
        lens - 1, lens, bits=bits, block_q=1, interpret=interpret)
    return out[:, 0]
