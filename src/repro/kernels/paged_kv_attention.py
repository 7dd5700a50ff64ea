"""Pallas kernel: GQA variable-length attention over a paged, quantized KV
cache — ONE kernel for chunked prefill (S >= 1) and decode (S == 1).

Reads the paged pool of ``repro.core.paged_kv``: KV history lives in
fixed-size pages scattered through a shared pool, each page stored in its
quantized container (int8 grid, or a 4-bit grid lane-packed into int32 words
along the head dim) with a per-page dequant scale. The decode entry point
(:func:`paged_kv_attention_decode`) is the single-query-row special case of
the chunk kernel below. A dense (B, T, KV, hd) cache is the special case of a
pool whose page table is the identity; tests/test_kernels.py checks that case
against ``ref.kv_attention_ref``.

Reachable via ``ops.paged_kv_attention`` / ``ops.paged_kv_attention_chunk``
(oracle-verified in tests/test_kernels.py); the serving forward routes BOTH
bucketed chunk prefill and decode through here under ``--attn-impl pallas``
(``models.attention.route_paged_attention``), with the jnp gather path kept
as the bitwise-reference mode.

Grid: a compacted work list (:func:`work_list`), one step per live
``(row b, query block qb, page block i)``, ordered by ``(b, qb)`` and then
``i``. Each row has NQ blocks of ``block_q`` chunk queries, and each query
block walks its live extent in page blocks of ``ppb`` page-table entries
(``ppb`` pages of ``ps`` keys, at most 128 keys a block, fewer where a
sub-byte container's unpack would outgrow VMEM; the last block may be
ragged). The grid's bound is the item count W, a traced scalar
(``grid=(W,)``), so a step runs only where there is work: one for a free
slot (``kv_len`` 1), ``ceil(n / ppb)`` for a query block of ``n`` live
entries, and the whole box ``B x NQ x ceil(NP / ppb)`` only when every row
fills the table. Step ``w`` decodes item ``w`` of the list in every index
map. A query block's items are consecutive and start at block 0, so its
query and output blocks keep their index across them: the (m, l, acc)
online-softmax scratch is reset at block 0, carries across the blocks,
and the output block is written at the last one and written back once.
The list depends on the rows' positions alone, so the serving layer scan
builds it once a step and hands it to every layer's call
(``models.attention.paged_kernel_work``).

The pool is the STACKED pool of every layer the layer scan runs, ``(L,
P, ps, KV, hdw)``, and the kernel reads layer ``layer`` of it in place:
the serving step never slices a layer's pool out
(``models.transformer._segment_scan``). The page table, per-row chunk
start positions, per-row valid lengths, the per-row page scales, the
layer index and the work list ride as **scalar-prefetch** operands
(`pltpu.PrefetchScalarGridSpec`). Each grid step fetches ``ppb`` whole
pages (all KV heads each), one ``(ps, KV, hdw)`` block per slot: slot
``j``'s BlockSpec index map reads ``(layer, page_table[b, e])`` for its
entry ``e``, so the gather happens in the pipeline, which double-buffers
every slot (a step's pages are fetched while the step before it
computes). The pool is handed to the kernel once per slot; each slot's DMA
moves one whole page, the one shape the TPU compiler takes for a
lane-packed container too. In VMEM each page is unpacked (sub-byte
containers), dequantized in float32 by its page scale, and the block is
folded into the online softmax.

Live extent (:func:`live_extent`): query block ``qb`` of row ``b`` can see
the keys below ``min(kv_len[b], q_start[b] + (qb+1) * block_q)``, i.e. the
first ``n = ceil(that / ps)`` table entries, at most all NP of them (a
padded chunk may end past the table), and the kernel reads no other entry:
a block past the extent has no work item. In the extent's ragged last
block the slots past ``n`` hold the entry they held in the block before
(``_entry``, so the pipeline sees an unchanged block index and fetches
nothing), or the extent's last entry in a one-block extent; their keys
fall out of the mask below, so they add exactly zero. Entry 0 is always
inside the extent, so every query block has an item and every query row
meets a live key in its first block. Inside the extent each key position
is masked **causally against its per-row absolute query positions**
(``q_start[b] + query index``) and against the row's ``kv_len``, cut at
the table's end (NP * ps) — partial last pages fall out of the length
mask, padded chunk tails (positions past the row's real tokens) produce
garbage rows that no caller reads (their pool writes were already
scratch-redirected by ``paged_update``).

Table entries past a row's extent are never read here, but the jnp gather
path (``core.paged_kv.paged_gather``) still reads every entry, so unused
entries must keep pointing at a valid pool page (page 0 / scratch).
``kv_len`` must be >= 1 per row with at least one real query, else the
masked softmax degenerates.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

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
# keys a grid step covers: one lane-width of scores per query row
_BLOCK_KEYS = 128
# chunk queries a grid step covers (prefill; decode runs one)
BLOCK_Q = 8
# VMEM for a block's lane-unpack: each stored word of a sub-byte page is
# widened to a 128-lane row of its fields before they are regrouped
_UNPACK_BYTES = 8 << 20


def pages_per_block(page_size: int, num_table_pages: int, *, kv_heads: int,
                    words: int, bits: int) -> int:
    """Page-table entries one grid step fetches and attends: up to
    ``_BLOCK_KEYS`` keys, fewer where a sub-byte container's unpack would
    outgrow ``_UNPACK_BYTES`` (``words``: stored words a key and head)."""
    keys = _BLOCK_KEYS
    if 0 < bits < 8:
        keys = min(keys, _UNPACK_BYTES // (kv_heads * words * 128 * 4))
    return max(1, min(num_table_pages, keys // page_size))


def _grid(seq_len: int, block_q: int, num_table_pages: int, page_size: int,
          *, kv_heads: int, words: int, bits: int):
    """``(bq, nq, ppb)`` of a call: queries a block, query blocks, and
    page-table entries a page block."""
    bq = max(1, min(block_q, seq_len))
    return bq, -(-seq_len // bq), pages_per_block(
        page_size, num_table_pages, kv_heads=kv_heads, words=words,
        bits=bits)


def live_extent(q_start, kv_len, qb, *, ps: int, bq: int,
                num_table_pages: int, xp=jnp):
    """Table entries query block ``qb`` of a row can see: ``ceil(min(kv_len,
    q_start + (qb+1) * bq) / ps)``, at most the table's ``num_table_pages``
    (a chunk whose padded tail runs past the table walks the whole table).
    The one rule for the kernel's :func:`work_list` (``xp=jnp``) and for
    the host-side count :func:`page_walk` (``xp=np``)."""
    hi = xp.minimum(kv_len, q_start + (qb + 1) * bq)
    return xp.minimum((hi + ps - 1) // ps, num_table_pages)


def page_walk(q_start, kv_len, *, seq_len: int, num_table_pages: int,
              page_size: int, ppb: int, block_q: int = BLOCK_Q):
    """Host-side count for one call: ``(walked, table, steps, box)``.
    ``walked``: the page-table entries the kernel fetches (each query
    block's live extent, summed over rows and query blocks); ``table``: the
    entries a walk of the whole table by every query block would visit;
    ``steps``: the grid steps the kernel runs, the items of its
    :func:`work_list` (each extent in blocks of ``ppb`` entries, as
    :func:`pages_per_block` gives the kernel); ``box``: the steps of a
    grid over every row, query block and page block. ``block_q`` as
    passed to the kernel."""
    bq = max(1, min(block_q, seq_len))
    nq = -(-seq_len // bq)
    n = live_extent(np.asarray(q_start, np.int64)[:, None],
                    np.asarray(kv_len, np.int64)[:, None], np.arange(nq),
                    ps=page_size, bq=bq, num_table_pages=num_table_pages,
                    xp=np)
    return (int(n.sum()), n.size * num_table_pages,
            int(((n + ppb - 1) // ppb).sum()),
            n.size * -(-num_table_pages // ppb))


class WorkList(NamedTuple):
    """A call's compacted grid (:func:`work_list`)."""
    steps: jax.Array      # () int32: the item count, the grid's bound
    keys: jax.Array       # (box,) int32: (b, qb, i), :func:`unpack_item`
    extents: jax.Array    # (box,) int32: the query block's live extent


def work_list(q_start, kv_len, *, seq_len: int, num_table_pages: int,
              page_size: int, kv_heads: int, words: int, bits: int,
              block_q: int = BLOCK_Q) -> WorkList:
    """The grid of a :func:`paged_kv_attention_chunk` call with these
    shapes (``kv_heads``, ``words``: a page's KV heads and stored words a
    head) as a compacted list of work items, one for each live ``(row b,
    query block qb, page block i)``: ``i`` below ``ceil(live_extent /
    ppb)``, ordered by ``(b, qb)`` and then ``i`` ascending, so a query
    block's items are consecutive and start at ``i`` 0. Its arrays have
    the box's length ``B * nq * ceil(NP / ppb)``; past the last item they
    repeat it, and the grid never gets there. Entry 0 is inside every
    extent (``kv_len >= 1``), so every query block has an item. Two words
    an item: four (row, query block, block, extent) outgrew the 1 MiB of
    SMEM at 32 rows of a 128-token chunk (int4 at 32 KV heads).

    Every layer of a serving step walks the same table, so the layer scan
    builds the list once and hands it to each layer's call."""
    bq, nq, ppb = _grid(seq_len, block_q, num_table_pages, page_size,
                        kv_heads=kv_heads, words=words, bits=bits)
    B = q_start.shape[0]
    nb = -(-num_table_pages // ppb)
    n = live_extent(q_start[:, None], kv_len[:, None],
                    jnp.arange(nq, dtype=jnp.int32), ps=page_size, bq=bq,
                    num_table_pages=num_table_pages).reshape(-1)
    blocks = (n + ppb - 1) // ppb                        # per (b, qb)
    ends = jnp.cumsum(blocks)
    w = jnp.arange(B * nq * nb, dtype=jnp.int32)
    grp = jnp.minimum(jnp.searchsorted(ends, w, side="right",
                                       method="compare_all"), B * nq - 1)
    i = jnp.minimum(w - (ends - blocks)[grp], blocks[grp] - 1)
    qbits, ibits = (nq - 1).bit_length(), (nb - 1).bit_length()
    keys = ((grp // nq) << (qbits + ibits)) | ((grp % nq) << ibits) | i
    return WorkList(ends[-1], keys, n[grp])


def unpack_item(key, *, nq: int, nb: int):
    """``(b, qb, i)`` of a :func:`work_list` key (jnp or numpy ints): the
    fields are bit ranges, so the kernel's index maps decode them with a
    shift and a mask each."""
    qbits, ibits = (nq - 1).bit_length(), (nb - 1).bit_length()
    return (key >> (qbits + ibits), (key >> ibits) & ((1 << qbits) - 1),
            key & ((1 << ibits) - 1))


def _dequant(x, scale, *, bits, head_dim):
    """(ps, hdw) stored page -> (ps, head_dim) f32 values.

    Shares the lane-unpack convention with core.qtensor (pure jnp right
    shifts, safe on TPU) so kernel == container == oracle. The per-page
    scale applies to every container, float pages included (writers keep
    their scales at 1.0)."""
    if 0 < bits < 8:
        x = unpack_bits(x, bits, head_dim)
    return x.astype(jnp.float32) * scale


def _entry(i, j, n, ppb):
    """Table entry slot ``j`` of live page block ``i`` holds, for a query
    block whose live extent is ``n`` entries: its own entry inside the
    extent; past it (the spare slots of a ragged last block), the entry
    that slot held in the block before, so the pipeline sees an unchanged
    block index and fetches nothing, or the extent's last entry where the
    block is the first."""
    e = i * ppb + j
    return jnp.where(e < n, e, jnp.where(j < n, e - ppb, n - 1))


def _chunk_kernel(pt_ref, qs_ref, len_ref, ks_ref, vs_ref, ly_ref, wk_ref,
                  wn_ref, q_ref, *refs, ppb, ps, bq, nq, np_, kv, g, bits,
                  head_dim, sm_scale):
    """One grid step = one work item (:func:`work_list`): query block
    ``qb`` of row ``b`` x page block ``i`` of its live extent, ``ppb``
    page-table entries (each page ``(1, ps, KV, hdw)``, all KV heads,
    contiguous in the pool layout). The softmax state is carried for all
    heads at once (rows = KV * bq * G); the per-head dots are a static
    python loop (KV is small). The pages' dequant scales are SMEM scalars
    (``ks_ref[b, e]``), gathered per row outside the kernel so their size
    follows the page table, not the pool. ``ly_ref`` (the layer) is read
    only by the page index maps."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb:]
    w = pl.program_id(0)
    b, qb, i = unpack_item(wk_ref[w], nq=nq, nb=-(-np_ // ppb))
    n = wn_ref[w]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, KV, G, hd)
    # each slot holds the page of _entry(...) (a live page): dequantize it
    # by that entry's scale; slots past the extent fall out of the mask
    # below, and their values are real, finite page data
    es = [_entry(i, j, n, ppb) for j in range(ppb)]
    k = jnp.concatenate(
        [_dequant(k_refs[j][0], ks_ref[b, es[j]], bits=bits,
                  head_dim=head_dim) for j in range(ppb)],
        axis=0)                                          # (ppb*ps, KV, hd)
    v = jnp.concatenate(
        [_dequant(v_refs[j][0], vs_ref[b, es[j]], bits=bits,
                  head_dim=head_dim) for j in range(ppb)],
        axis=0)                                          # (ppb*ps, KV, hd)

    # one causal/length mask, shared by every kv head: flattened row r is
    # chunk query r // G, at absolute position q_start[b] + qb*bq + r // G
    pos = i * ppb * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ppb * ps),
                                                  1)
    qrow = jax.lax.broadcasted_iota(jnp.int32, (bq * g, 1), 0) // g
    q_pos = qs_ref[b] + qb * bq + qrow                   # (bq*G, 1)
    # keys the row holds: kv_len, cut at the table's end where a padded
    # chunk runs past it (the ragged last block's spare slots sit there)
    kv_end = jnp.minimum(len_ref[b], np_ * ps)
    mask = (pos <= q_pos) & (pos < kv_end)               # (bq*G, ppb*ps)

    scores = []
    for h in range(kv):                                  # static unroll
        qh = (q[:, h].reshape(bq * g, head_dim) * sm_scale)
        s = jnp.dot(qh, k[:, h].T, precision=_F32,
                    preferred_element_type=jnp.float32)
        scores.append(jnp.where(mask, s, NEG_INF))
    s = jnp.concatenate(scores, axis=0)                  # (KV*bq*G, ppb*ps)

    m_prev = m_ref[...]                                  # (KV*bq*G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)                       # (KV*bq*G, 1)
    l_ref[...] = l_ref[...] * corr + pexp.sum(axis=1, keepdims=True)
    upd = jnp.concatenate(
        [jnp.dot(pexp[h * bq * g:(h + 1) * bq * g], v[:, h],
                 precision=_F32, preferred_element_type=jnp.float32)
         for h in range(kv)],
        axis=0)                                          # (KV*bq*G, hd)
    acc_ref[...] = acc_ref[...] * corr + upd
    m_ref[...] = m_new

    @pl.when(i == (n + ppb - 1) // ppb - 1)
    def _fin():
        o = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
             ).reshape(kv, bq, g, head_dim)
        o_ref[0] = jnp.moveaxis(o, 0, 1).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_q", "interpret"))
def paged_kv_attention_chunk(q, k_pages, v_pages, k_scale, v_scale,
                             page_table, q_start, kv_len, layer=0,
                             work=None, *, bits: int = 8,
                             block_q: int = BLOCK_Q,
                             interpret: bool = False):
    """Variable-length chunk attention over a paged quantized KV pool.

    q: (B, S, H, hd) float — S chunk queries per sequence (S == 1: decode).
    k_pages/v_pages: (L, P, ps, KV, hdw), the stacked pools of L layers —
        int8 grid (bits=8), int32 lane-packed words with hdw = hd * bits /
        32 (bits < 8), or float (bits=0).
    k_scale/v_scale: (L, P) f32 per-page dequant scales (value = grid *
        scale).
    page_table: (B, NP) int32 pool-page ids; the kernel reads only each
        row's live extent (module docstring), but unused entries must still
        reference a valid page (the scratch page 0) for the gather path.
    q_start: (B,) int32 absolute position of chunk token 0 per row (== the
        row's cache write offset); query i sits at ``q_start + i`` and
        attends keys causally up to that position.
    kv_len: (B,) int32 valid history length per row INCLUDING the chunk's
        real tokens (>= 1; it may run past the table, NP * ps, where a
        padded chunk ends past it). For padded chunks, query rows past the
        valid tail produce garbage outputs that no caller reads.
    layer: int32 scalar (may be traced), the layer of the stacked pools the
        chunk attends; pages and scales of other layers are never read.
    work: the call's :func:`work_list`, built here when None (a caller
        that attends the same rows at every layer builds it once).
    bits must match the page container. Returns (B, S, H, hd) float32.

    The query and output blocks' last two dims are whole array dims
    (``(G, hd)``), and each page copy moves a whole ``(ps, KV, hdw)`` page,
    which is what the TPU compiler's tiling rule accepts at any head count.
    """
    B, S, H, hd = q.shape
    _, P, ps, KV, hdw = k_pages.shape
    NP = page_table.shape[1]
    G = H // KV
    bq, nq, ppb = _grid(S, block_q, NP, ps, kv_heads=KV, words=hdw,
                        bits=bits)
    nb = -(-NP // ppb)
    sp = nq * bq
    sm_scale = float(1.0 / np.sqrt(hd))
    pt = jnp.asarray(page_table, jnp.int32)
    qs = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32).reshape(-1), (B,))
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    ly = jnp.asarray(layer, jnp.int32).reshape(1)
    # per-row page scales (B, NP): SMEM holds what the table addresses, so
    # the scalar operands stay small however large the pool grows
    ks = k_scale[ly[0], pt].astype(jnp.float32)
    vs = v_scale[ly[0], pt].astype(jnp.float32)
    qb_in = q.reshape(B, S, KV, G, hd)
    if sp != S:
        qb_in = jnp.pad(qb_in, ((0, 0), (0, sp - S), (0, 0), (0, 0),
                                (0, 0)))
    if work is None:
        work = work_list(qs, lens, seq_len=S, num_table_pages=NP,
                         page_size=ps, kv_heads=KV, words=hdw, bits=bits,
                         block_q=block_q)
    elif work.keys.shape != (B * nq * nb,):
        raise ValueError(f"a work list of {work.keys.shape} items for a "
                         f"grid box of {B * nq * nb}")
    # scalar operands: page_table, q_start, kv_len, k/v page scales, layer,
    # and the work list's keys and extents

    def row_map(w, pt, qs, ln, ks, vs, ly, wk, wn):
        b, qb, _ = unpack_item(wk[w], nq=nq, nb=nb)
        return (b, qb, 0, 0, 0)

    def page_spec(j):
        def page_map(w, pt, qs, ln, ks, vs, ly, wk, wn):
            b, _, i = unpack_item(wk[w], nq=nq, nb=nb)
            return (ly[0], pt[b, _entry(i, j, wn[w], ppb)], 0, 0, 0)
        return pl.BlockSpec((None, 1, ps, KV, hdw), page_map)

    pages = [page_spec(j) for j in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(work.steps,),
        in_specs=[pl.BlockSpec((1, bq, KV, G, hd), row_map)] + pages + pages,
        out_specs=pl.BlockSpec((1, bq, KV, G, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((KV * bq * G, 1), jnp.float32),    # m
            pltpu.VMEM((KV * bq * G, 1), jnp.float32),    # l
            pltpu.VMEM((KV * bq * G, hd), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, ppb=ppb, ps=ps, bq=bq, nq=nq,
                          np_=NP, kv=KV, g=G, bits=bits, head_dim=hd,
                          sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, sp, KV, G, hd), jnp.float32),
        interpret=interpret,
        name="paged_kv_attention",
    )(pt, qs, lens, ks, vs, ly, work.keys, work.extents, qb_in,
      *[k_pages] * ppb, *[v_pages] * ppb)
    return out[:, :S].reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def paged_kv_attention_decode(q, k_pages, v_pages, k_scale, v_scale,
                              page_table, kv_len, layer=0, *, bits: int = 8,
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
        lens - 1, lens, layer, bits=bits, block_q=1, interpret=interpret)
    return out[:, 0]
