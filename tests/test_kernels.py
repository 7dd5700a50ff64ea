"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU). Hypothesis drives the shape space; tolerances are exact for
grid ops (quantization is deterministic) and ~1e-4 for float accumulation."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")

_SETTINGS = dict(max_examples=20, deadline=None)


# ---------------------------------------------------------------------------
# quant_cast
# ---------------------------------------------------------------------------
@settings(**_SETTINGS)
@given(m=st.integers(1, 300), n=st.integers(1, 700),
       i=st.integers(1, 8), f=st.integers(0, 8),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_quant_cast_matches_ref(m, n, i, f, dtype):
    key = jax.random.PRNGKey(m * 1000 + n)
    x = (jax.random.normal(key, (m, n), jnp.float32) * 5).astype(dtype)
    y = ops.quant_cast(x, i, f)
    yr = ref.quant_cast_ref(x, i, f)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))
    assert y.dtype == x.dtype


def test_quant_cast_3d_and_idempotent():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 37, 129)) * 3
    y = ops.quant_cast(x, 3, 5)
    y2 = ops.quant_cast(y, 3, 5)
    np.testing.assert_array_equal(y, y2)  # grid projection is idempotent
    # values are on the grid
    scaled = np.asarray(y) * 2**5
    np.testing.assert_allclose(scaled, np.round(scaled), atol=1e-5)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------
@settings(**_SETTINGS)
@given(m=st.integers(1, 64), words=st.integers(1, 16),
       bits=st.sampled_from([2, 4, 8, 16]))
def test_pack_unpack_roundtrip(m, words, bits):
    vpw = 32 // bits
    n = words * vpw
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    q = jax.random.randint(jax.random.PRNGKey(m * 31 + words), (m, n),
                           lo, hi + 1, jnp.int32)
    w = ops.pack(q, bits)
    assert w.shape == (m, words)
    np.testing.assert_array_equal(w, ref.pack_ref(q, bits))
    q2 = ops.unpack(w, bits)
    np.testing.assert_array_equal(q2, q)
    np.testing.assert_array_equal(ref.unpack_ref(w, bits), q)


def test_pack_footprint():
    q = jnp.zeros((8, 128), jnp.int32)
    for bits in (2, 4, 8, 16):
        w = ops.pack(q, bits)
        assert w.size * 32 == q.size * bits  # true N-bit footprint


# ---------------------------------------------------------------------------
# quant_matmul
# ---------------------------------------------------------------------------
@settings(**_SETTINGS)
@given(m=st.integers(1, 130), k=st.integers(1, 300), n=st.integers(1, 130),
       adt=st.sampled_from(["float32", "bfloat16"]))
def test_quant_matmul_matches_ref(m, k, n, adt):
    key = jax.random.PRNGKey(m + k * 7 + n * 11)
    a = (jax.random.normal(key, (m, k), jnp.float32)).astype(adt)
    wq = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128,
                            jnp.int32).astype(jnp.int8)
    s = jax.random.uniform(jax.random.fold_in(key, 2), (n,),
                           minval=0.001, maxval=0.05)
    out = ops.qmatmul(a, wq, s)
    expect = ref.quant_matmul_ref(a, wq, s)
    tol = 2e-2 if adt == "bfloat16" else 1e-4
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol * 10)


# ---------------------------------------------------------------------------
# dense int8 cache through the paged kernel
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("frac_bits", "page_size"))
def _dense_paged_attention(q, k_q, v_q, kv_len, frac_bits, page_size):
    """Decode attention over a dense (B, T, KV, hd) int8 Q(I,F) cache, read
    as a one-layer paged pool whose page table is the identity (row b's
    page p is pool page b * NP + p) and whose page scales are all 2^-F."""
    B, T, KV, hd = k_q.shape
    pad = ((0, 0), (0, (-T) % page_size), (0, 0), (0, 0))
    k_q, v_q = jnp.pad(k_q, pad), jnp.pad(v_q, pad)
    NP = k_q.shape[1] // page_size
    k_pages = k_q.reshape(1, B * NP, page_size, KV, hd)
    v_pages = v_q.reshape(1, B * NP, page_size, KV, hd)
    pt = jnp.arange(B * NP, dtype=jnp.int32).reshape(B, NP)
    scale = jnp.full((1, B * NP), 2.0 ** -frac_bits, jnp.float32)
    lens = jnp.full((B,), kv_len, jnp.int32)
    return ops.paged_kv_attention(q, k_pages, v_pages, scale, scale, pt,
                                  lens, bits=8)


@settings(**_SETTINGS)
@given(b=st.integers(1, 3), kv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2, 4]), hd=st.sampled_from([16, 32, 64]),
       t=st.integers(8, 200), frac=st.integers(4, 7))
def test_kv_attention_matches_ref(b, kv, g, hd, t, frac):
    key = jax.random.PRNGKey(b * 97 + t)
    h = kv * g
    q = jax.random.normal(key, (b, h, hd), jnp.float32)
    k_q = jax.random.randint(jax.random.fold_in(key, 1), (b, t, kv, hd),
                             -128, 128, jnp.int32).astype(jnp.int8)
    v_q = jax.random.randint(jax.random.fold_in(key, 2), (b, t, kv, hd),
                             -128, 128, jnp.int32).astype(jnp.int8)
    kv_len = max(1, t - 3)
    out = _dense_paged_attention(q, k_q, v_q, kv_len, frac, min(64, t))
    expect = ref.kv_attention_ref(q, k_q, v_q, 2, frac, kv_len)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# paged_kv_attention
# ---------------------------------------------------------------------------
# shared with benchmarks/kernel_bench.py — one fixture, one pool layout
_mk_fragmented_pool = ref.make_fragmented_pool


@settings(max_examples=12, deadline=None)
@given(b=st.integers(1, 3), kv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 4]), hd=st.sampled_from([16, 32]),
       np_pages=st.integers(1, 5), ps=st.sampled_from([8, 16]),
       bits=st.sampled_from([0, 4, 8]), tail=st.integers(0, 7))
def test_paged_kv_attention_matches_ref(b, kv, g, hd, np_pages, ps, bits,
                                        tail):
    """Paged kernel vs dense-gather oracle on randomized fragmented page
    layouts, including a partially filled last page (``tail``)."""
    rng = np.random.default_rng(b * 1000 + np_pages * 17 + ps + bits + tail)
    h = kv * g
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, b, np_pages, ps, kv, hd,
                                             bits)
    # per-row lengths; at least 1, last page partially filled by `tail`
    full = np_pages * ps
    lens = np.maximum(1, full - tail - rng.integers(0, ps, b)).astype(np.int32)
    out = ops.paged_kv_attention(q, kq, vq, ks, vs, jnp.asarray(pt),
                                 jnp.asarray(lens), bits=bits)
    expect = ref.paged_kv_attention_ref(q, kq, vq, ks, vs, pt, lens,
                                        bits=bits)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_paged_kv_attention_fragmented_vs_contiguous():
    """The same logical cache must give the same output regardless of WHICH
    pool pages back it (fragmentation invariance)."""
    rng = np.random.default_rng(0)
    B, KV, G, hd, ps, NP = 2, 2, 2, 32, 8, 3
    q = jnp.asarray(rng.normal(size=(B, KV * G, hd)), jnp.float32)
    logical_k = rng.integers(-128, 128, (B, NP, ps, KV, hd))
    logical_v = rng.integers(-128, 128, (B, NP, ps, KV, hd))
    lens = jnp.asarray([20, 17], jnp.int32)
    outs = []
    for perm_seed in (1, 2):
        prng = np.random.default_rng(perm_seed)
        ids = np.arange(1, 1 + B * NP)
        prng.shuffle(ids)
        pt = ids.reshape(B, NP).astype(np.int32)
        P = 1 + B * NP
        kq = np.zeros((1, P, ps, KV, hd), np.int8)
        vq = np.zeros((1, P, ps, KV, hd), np.int8)
        kq[0, pt] = logical_k
        vq[0, pt] = logical_v
        sc = np.full((1, P), 2.0 ** -5, np.float32)
        outs.append(ops.paged_kv_attention(
            q, jnp.asarray(kq), jnp.asarray(vq), jnp.asarray(sc),
            jnp.asarray(sc), jnp.asarray(pt), lens, bits=8))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_paged_int4_matches_int8_on_same_grid():
    """A 4-bit grid stored packed (bits=4) and widened to int8 (bits=8) must
    produce identical attention outputs — packing is lossless."""
    from repro.core.qtensor import pack_bits
    rng = np.random.default_rng(3)
    B, KV, G, hd, ps, NP = 1, 2, 2, 16, 8, 2
    P = 1 + B * NP
    q = jnp.asarray(rng.normal(size=(B, KV * G, hd)), jnp.float32)
    grid_k = rng.integers(-8, 8, (1, P, ps, KV, hd))
    grid_v = rng.integers(-8, 8, (1, P, ps, KV, hd))
    sc = jnp.full((1, P), 0.25, jnp.float32)
    pt = jnp.asarray([[2, 1]], jnp.int32)
    lens = jnp.asarray([13], jnp.int32)
    o8 = ops.paged_kv_attention(q, jnp.asarray(grid_k, jnp.int8),
                                jnp.asarray(grid_v, jnp.int8), sc, sc, pt,
                                lens, bits=8)
    k4, _ = pack_bits(jnp.asarray(grid_k, jnp.int32), 4)
    v4, _ = pack_bits(jnp.asarray(grid_v, jnp.int32), 4)
    o4 = ops.paged_kv_attention(q, k4, v4, sc, sc, pt, lens, bits=4)
    np.testing.assert_array_equal(np.asarray(o8), np.asarray(o4))


# ---------------------------------------------------------------------------
# paged_kv_attention_chunk (variable-length prefill-chunk kernel)
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(b=st.integers(1, 2), kv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2]), hd=st.sampled_from([16, 32]),
       ps=st.sampled_from([8, 16]), s=st.sampled_from([2, 5, 8, 13]),
       start=st.integers(0, 19), bits=st.sampled_from([0, 4, 8]))
def test_paged_kv_attention_chunk_matches_ref(b, kv, g, hd, ps, s, start,
                                              bits):
    """Chunk kernel vs dense-gather oracle on fragmented page tables:
    per-row start positions straddle page boundaries (``start`` is
    arbitrary, so chunks begin/end mid-page → partial last pages), history
    lengths differ per row, and every container is swept."""
    rng = np.random.default_rng(b * 1000 + ps * 31 + s * 7 + start + bits)
    h = kv * g
    # per-row starts: row r begins a little earlier than `start`
    starts = np.maximum(0, start - rng.integers(0, 4, b)).astype(np.int32)
    np_pages = max(1, -(-int(starts.max() + s) // ps))
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, b, np_pages, ps, kv, hd,
                                             bits)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    lens = starts + s
    out = ops.paged_kv_attention_chunk(q, kq, vq, ks, vs, jnp.asarray(pt),
                                       jnp.asarray(starts),
                                       jnp.asarray(lens), bits=bits,
                                       block_q=4)
    expect = ref.paged_kv_attention_chunk_ref(q, kq, vq, ks, vs, pt, starts,
                                              lens, bits=bits)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_paged_chunk_block_q_invariance():
    """The query-block size is a tiling knob, not a numerics knob: the same
    chunk attended at block_q 1/4/8 gives the same output (page-order
    accumulation is identical, only the grid changes)."""
    rng = np.random.default_rng(9)
    B, KV, G, hd, ps, NP, S = 2, 2, 2, 16, 8, 3, 7
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, B, NP, ps, KV, hd, 8)
    q = jnp.asarray(rng.normal(size=(B, S, KV * G, hd)), jnp.float32)
    starts = jnp.asarray([2, 9], jnp.int32)
    lens = starts + S
    outs = [np.asarray(ops.paged_kv_attention_chunk(
        q, kq, vq, ks, vs, jnp.asarray(pt), starts, lens, bits=8,
        block_q=bq)) for bq in (1, 4, 8)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 2), kv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2]), s=st.sampled_from([1, 5, 8]),
       start=st.integers(0, 19), bits=st.sampled_from([0, 4, 8]))
def test_paged_chunk_block_kv_matches_default(b, kv, g, s, start, bits):
    """The whole-page (all KV heads per DMA) kernel against the oracle on
    fragmented tables, every container, decode (S=1) and prefill shapes,
    at the serving page size; the decode entry point must equal the chunk
    kernel's S=1 rows wherever the chunk is a single query."""
    rng = np.random.default_rng(b * 577 + s * 13 + start * 3 + bits)
    hd, ps = 32, 16
    starts = np.maximum(0, start - rng.integers(0, 4, b)).astype(np.int32)
    np_pages = max(1, -(-int(starts.max() + s) // ps))
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, b, np_pages, ps, kv, hd,
                                             bits)
    q = jnp.asarray(rng.normal(size=(b, s, kv * g, hd)), jnp.float32)
    lens = starts + s
    args = (q, kq, vq, ks, vs, jnp.asarray(pt), jnp.asarray(starts),
            jnp.asarray(lens))
    out = ops.paged_kv_attention_chunk(*args, bits=bits, block_q=4)
    expect = ref.paged_kv_attention_chunk_ref(q, kq, vq, ks, vs, pt, starts,
                                              lens, bits=bits)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)
    if s == 1:
        d = ops.paged_kv_attention(q[:, 0], kq, vq, ks, vs, jnp.asarray(pt),
                                   jnp.asarray(lens), bits=bits)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(out[:, 0]))


def test_paged_decode_is_chunk_special_case():
    """The decode entry point == the chunk kernel at S=1 with the causal
    bound collapsed into the length mask (exact: same kernel, same grid
    accumulation)."""
    rng = np.random.default_rng(4)
    B, KV, G, hd, ps, NP = 2, 2, 2, 16, 8, 3
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, B, NP, ps, KV, hd, 8)
    q = jnp.asarray(rng.normal(size=(B, KV * G, hd)), jnp.float32)
    lens = jnp.asarray([13, 20], jnp.int32)
    d = ops.paged_kv_attention(q, kq, vq, ks, vs, jnp.asarray(pt), lens,
                               bits=8)
    c = ops.paged_kv_attention_chunk(q[:, None], kq, vq, ks, vs,
                                     jnp.asarray(pt), lens - 1, lens,
                                     bits=8, block_q=1)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(c[:, 0]))


def _live_extent_pool(rng, lens, np_, ps, kv, hd, bits, layers):
    """A stacked pool and (B, NP) table in the serving layout: each row's
    entries below ``ceil(kv_len / ps)`` hold its own pages, every entry
    past that references the scratch page 0. Returns the pool arrays, the
    table and the ids of the pages no live extent addresses (page 0 among
    them)."""
    B = len(lens)
    extents = -(-np.asarray(lens) // ps)
    kq, vq, ks, vs, _ = _mk_fragmented_pool(rng, B, np_, ps, kv, hd, bits,
                                            layers=layers)
    ids = rng.permutation(np.arange(1, kq.shape[1]))[:int(extents.sum())]
    pt = np.zeros((B, np_), np.int32)
    for r, live in enumerate(np.split(ids, np.cumsum(extents)[:-1])):
        pt[r, :len(live)] = live
    dead = np.setdiff1d(np.arange(kq.shape[1]), ids)
    return kq, vq, ks, vs, pt, dead


@pytest.mark.parametrize("bits", [8, 4, 0], ids=["int8", "int4", "fp"])
@pytest.mark.parametrize("s", [1, 8], ids=["decode", "chunk8"])
@pytest.mark.parametrize("g", [1, 8], ids=["g1", "g8"])
def test_paged_kernel_never_reads_dead_pages(bits, s, g):
    """Wide tables (NP far past the live pages) with an empty-slot row at
    kv_len 1 beside long rows, read at layer 1 of a 3-layer stacked pool:
    every pool page outside the rows' live extents, the scratch page 0
    included, is NaN (values and scale), and so is every page of layers 0
    and 2; the kernel must still equal the oracle on the same pool with
    those pages zeroed — a walk past the extent, or into another layer,
    would put NaN in the PV dot."""
    rng = np.random.default_rng(s * 10 + g + bits)
    kv, hd, ps, np_ = 2, 16, 8, 40
    # q_start > 0 on every row of the chunk; row 0 is an empty slot at decode
    starts = np.asarray([0 if s == 1 else 3, 150, 37], np.int32)
    lens = starts + s
    kq, vq, ks, vs, pt, dead = _live_extent_pool(rng, lens, np_, ps, kv, hd,
                                                 bits, layers=3)
    q = jnp.asarray(rng.normal(size=(3, s, kv * g, hd)), jnp.float32)
    zero = lambda x: x.at[:, dead].set(0)
    expect = ref.paged_kv_attention_chunk_ref(
        q, zero(kq), zero(vq), ks, vs, pt, starts, lens, 1, bits=bits)
    nan = lambda x: x.at[:, dead].set(jnp.nan).at[::2].set(jnp.nan)
    if bits == 0:
        kq, vq = nan(kq), nan(vq)
    ks, vs = nan(ks), nan(vs)
    out = ops.paged_kv_attention_chunk(q, kq, vq, ks, vs, jnp.asarray(pt),
                                       jnp.asarray(starts),
                                       jnp.asarray(lens), 1, bits=bits)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("np_", [20, 32], ids=["np20", "np32"])
@pytest.mark.parametrize("edge", [-1, 0, 1, "full", "past"],
                         ids=["block-1", "block", "block+1", "full", "past"])
@pytest.mark.parametrize("s", [1, 8], ids=["decode", "chunk8"])
def test_paged_kernel_ragged_blocks(np_, edge, s):
    """kv_len at a page-block edge (ppb * ps keys, +-1) and at the full
    table, with NP a multiple of the derived block (32 entries, blocks of
    16) and not one (20: a ragged last block of 4), against the oracle;
    every container, rotating with the case. ``past``: rows whose chunk
    ends past the table (kv_len = q_start + 8 > NP * ps), as a decode row
    at the table's end inside a fused 8-token chunk, or a padded prefill
    tail, puts them: the walk stops at the table and the output is still
    written."""
    from repro.kernels.paged_kv_attention import pages_per_block
    kv, g, hd, ps = 2, 2, 16, 8
    bits = {-1: 8, 0: 4, 1: 0, "full": 8, "past": 4}[edge]
    hdw = hd * bits // 32 if 0 < bits < 8 else hd
    ppb = pages_per_block(ps, np_, kv_heads=kv, words=hdw, bits=bits)
    assert ppb == 16
    if edge == "past":
        starts = np_ * ps - np.asarray([2, 5], np.int32)
        lens = starts + 8
    else:
        n = np_ * ps if edge == "full" else ppb * ps + edge
        lens = np.asarray([n, max(s, n - 37)], np.int32)
        starts = lens - s
    rng = np.random.default_rng(np_ + s + int(lens[0]))
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, 2, np_, ps, kv, hd, bits)
    q = jnp.asarray(rng.normal(size=(2, s, kv * g, hd)), jnp.float32)
    out = ops.paged_kv_attention_chunk(q, kq, vq, ks, vs, jnp.asarray(pt),
                                       jnp.asarray(starts),
                                       jnp.asarray(lens), bits=bits)
    expect = ref.paged_kv_attention_chunk_ref(q, kq, vq, ks, vs, pt, starts,
                                              lens, bits=bits)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_page_walk_counts_live_extents():
    """The serving loop's host-side count of the kernel's walk: each query
    block's live extent, against a brute-force count of the table entries
    that block's queries can see (a chunk ending past the table sees all of
    them); the table side is every entry a block; the grid steps are each
    extent in blocks of ``ppb`` entries, the box every block of every
    query block."""
    from repro.kernels.paged_kv_attention import page_walk
    ps, np_, S, bq, ppb = 16, 12, 20, 8, 5
    starts = np.asarray([0, 5, 100, 170, 188], np.int32)
    lens = starts + S - np.asarray([0, 3, 0, 9, 0])
    want = steps = 0
    for st, ln in zip(starts, lens):
        for qb in range(-(-S // bq)):
            last_q = st + min(S, (qb + 1) * bq) - 1      # causal bound
            n = min(-(-min(int(ln), last_q + 1) // ps), np_)
            want, steps = want + n, steps + -(-n // ppb)
    walked, table, got_steps, box = page_walk(
        starts, lens, seq_len=S, num_table_pages=np_, page_size=ps, ppb=ppb,
        block_q=bq)
    assert table == 5 * 3 * np_
    assert walked == want
    assert (got_steps, box) == (steps, 5 * 3 * 3)
    dec = page_walk(starts, starts + 1, seq_len=1, num_table_pages=np_,
                    page_size=ps, ppb=ppb)
    n = starts // ps + 1
    assert dec == (int(n.sum()), 5 * np_, int((-(-n // ppb)).sum()), 5 * 3)


_WORK_CASES = {
    # serving decode: 30 free slots (kv_len 1) beside a full and a ragged
    # row
    "decode-free-slots": ([0] * 30 + [319, 150], [1] * 30 + [320, 151], 1,
                          1),
    # a chunk of 3 query blocks straddling block edges, one row past the
    # table's end
    "chunk": ([0, 37, 122, 300], [20, 57, 142, 320], 20, 8),
    # every row at the full table: the list is the whole box
    "full": ([312, 312, 312], [320, 320, 320], 8, 8),
}


@pytest.mark.parametrize("case", list(_WORK_CASES))
def test_work_list_matches_brute_force(case):
    """The kernel's compacted grid against a brute-force enumeration of the
    live (row, query block, page block) triples, in (row, query block,
    block) order: each query block's items start at block 0 and end at its
    last live block, the count is the sum of its extents in blocks (at
    least one a query block; the whole box when every row is full), the
    host-side count agrees, and the padding repeats the last item."""
    from repro.kernels.paged_kv_attention import (live_extent, page_walk,
                                                  unpack_item, work_list)
    starts, lens, S, bq = _WORK_CASES[case]
    ps, np_, ppb = 8, 40, 16
    nq, nb = -(-S // bq), -(-np_ // ppb)
    want = []
    for b, (st, ln) in enumerate(zip(starts, lens)):
        for qb in range(nq):
            n = int(live_extent(st, ln, qb, ps=ps, bq=bq,
                                num_table_pages=np_, xp=np))
            want += [(b, qb, i, n) for i in range(-(-n // ppb))]
    # int8 pages of 2 KV heads: 128 keys a block, 16 pages of 8
    steps, keys, extents = work_list(
        jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32),
        seq_len=S, num_table_pages=np_, page_size=ps, kv_heads=2, words=16,
        bits=8, block_q=bq)
    got = np.stack([*unpack_item(np.asarray(keys), nq=nq, nb=nb),
                    np.asarray(extents)], axis=1)
    box = len(starts) * nq * nb
    assert got.shape == (box, 4) and int(steps) == len(want)
    assert [tuple(r) for r in got[:len(want)]] == want
    assert (got[len(want):] == got[len(want) - 1]).all()
    assert len(want) >= len(starts) * nq
    if case == "full":
        assert len(want) == box
    walk = page_walk(starts, lens, seq_len=S, num_table_pages=np_,
                     page_size=ps, ppb=ppb, block_q=bq)
    assert walk[2:] == (len(want), box)


@pytest.mark.parametrize("bits", [8, 4, 0], ids=["int8", "int4", "fp"])
@pytest.mark.parametrize("s", [1, 8], ids=["decode", "chunk8"])
def test_paged_kernel_free_slots(s, bits):
    """The serving decode's shape: 32 rows, 30 of them free slots at kv_len
    1 beside one row at the full table and one ragged row, so the kernel's
    work list skips most of the box; against the oracle, every container,
    at decode and at an 8-token chunk."""
    rng = np.random.default_rng(s * 7 + bits)
    B, kv, g, hd, ps, np_ = 32, 2, 2, 16, 8, 40
    lens = np.asarray([1] * 30 + [np_ * ps, 151], np.int32)
    starts = np.maximum(lens - s, 0).astype(np.int32)
    kq, vq, ks, vs, pt = _mk_fragmented_pool(rng, B, np_, ps, kv, hd, bits)
    q = jnp.asarray(rng.normal(size=(B, s, kv * g, hd)), jnp.float32)
    out = ops.paged_kv_attention_chunk(q, kq, vq, ks, vs, jnp.asarray(pt),
                                       jnp.asarray(starts),
                                       jnp.asarray(lens), bits=bits)
    expect = ref.paged_kv_attention_chunk_ref(q, kq, vq, ks, vs, pt, starts,
                                              lens, bits=bits)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_kv_attention_masks_tail():
    """Entries beyond kv_len must not affect the output."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 4, 32))
    k_q = jax.random.randint(key, (1, 64, 2, 32), -128, 128,
                             jnp.int32).astype(jnp.int8)
    v_q = jax.random.randint(jax.random.fold_in(key, 1), (1, 64, 2, 32),
                             -128, 128, jnp.int32).astype(jnp.int8)
    out1 = _dense_paged_attention(q, k_q, v_q, 10, 5, 16)
    np.testing.assert_allclose(out1, ref.kv_attention_ref(q, k_q, v_q, 2, 5,
                                                          10),
                               rtol=1e-4, atol=1e-4)
    k_q2 = k_q.at[:, 10:].set(127)
    v_q2 = v_q.at[:, 10:].set(-128)
    out2 = _dense_paged_attention(q, k_q2, v_q2, 10, 5, 16)
    np.testing.assert_allclose(out1, out2, rtol=1e-6)
