"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Shared numerics with the core library where it matters: the fake-quant grid
definition is imported from core.fixedpoint, so kernel == library == paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.fixedpoint import fake_quant, format_params

NEG_INF = -1e30


def quant_cast_ref(x, int_bits: int, frac_bits: int):
    """Fake-quant Q(I,F): round-half-away, clip, rescale (paper §2.1)."""
    return fake_quant(x, int_bits, frac_bits)


# ---------------------------------------------------------------------------
# Lane packing: k N-bit fields per int32 word, little-endian in bit order.
# ---------------------------------------------------------------------------
def values_per_word(bits: int) -> int:
    assert bits in (2, 4, 8, 16), bits
    return 32 // bits


def pack_ref(q, bits: int):
    """q: (..., N) int32 integer-grid values in [-2^(bits-1), 2^(bits-1)-1].
    Returns (..., N // vpw) int32 packed words."""
    vpw = values_per_word(bits)
    assert q.shape[-1] % vpw == 0
    mask = jnp.uint32((1 << bits) - 1)
    qu = q.astype(jnp.uint32) & mask
    grp = qu.reshape(*q.shape[:-1], q.shape[-1] // vpw, vpw)
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits)
    word = jnp.bitwise_or.reduce(grp << shifts, axis=-1)
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def unpack_ref(w, bits: int):
    """Inverse of pack_ref (sign-extending). w: (..., M) int32 ->
    (..., M * vpw) int32."""
    vpw = values_per_word(bits)
    wu = jax.lax.bitcast_convert_type(w, jnp.uint32)
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits)
    mask = jnp.uint32((1 << bits) - 1)
    fields = (wu[..., None] >> shifts) & mask              # (..., M, vpw)
    sign = jnp.uint32(1 << (bits - 1))
    vals = (fields ^ sign).astype(jnp.int32) - jnp.int32(sign)
    return vals.reshape(*w.shape[:-1], w.shape[-1] * vpw)


# ---------------------------------------------------------------------------
# Quantized matmul: W stored on an int grid, per-output-channel scale.
# ---------------------------------------------------------------------------
def quant_matmul_ref(a, wq, scales):
    """a: (M, K) float; wq: (K, N) int8/int16 grid; scales: (N,) fp32.
    out (M, N) fp32 = a @ (wq * scales)."""
    af = a.astype(jnp.float32)
    wf = wq.astype(jnp.float32) * scales[None, :].astype(jnp.float32)
    return af @ wf


# ---------------------------------------------------------------------------
# Decode attention over an int8-quantized KV cache (per-layer Q(I,F)).
# ---------------------------------------------------------------------------
def masked_decode_attention_ref(q, k, v, kv_len):
    """Full-materialization decode attention. q: (B, H, hd); k/v:
    (B, T, KV, hd) float; kv_len: (B,) or scalar. Returns (B, H, hd) f32."""
    B, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32) / np.sqrt(hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, k.astype(jnp.float32))
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    mask = jnp.arange(T)[None, None, None, :] < lens[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd)


def paged_kv_attention_ref(q, k_pages, v_pages, k_scale, v_scale, page_table,
                           kv_len, layer=0, *, bits: int = 8, head_dim=None):
    """Oracle for the paged kernel: gather layer ``layer``'s pages into the
    logical dense view, dequantize with the per-page scales, run masked
    softmax attention.

    Shapes as in ``paged_kv_attention_decode`` (stacked pools); supports
    fragmented page tables and per-row kv_len (partial last pages are
    masked).
    """
    from ..core.paged_kv import paged_gather
    container = {0: "fp", 8: "int8", 4: "int4"}[bits]
    pool = {"k_pages": k_pages, "v_pages": v_pages,
            "k_scale": k_scale, "v_scale": v_scale}
    hd = head_dim if head_dim is not None else q.shape[-1]
    k, v = paged_gather(pool, jnp.asarray(page_table, jnp.int32),
                        container=container, head_dim=hd, layer=layer)
    return masked_decode_attention_ref(q, k, v, kv_len)


def make_fragmented_pool(rng, B, NP, ps, kv, hd, bits, extra_pages=3,
                         layers=1):
    """Shared oracle-test/bench fixture: a random quantized pool of
    ``layers`` stacked layers, as the serving scan carries it, plus an
    OUT-OF-ORDER page table (non-scratch page ids shuffled across
    sequences) — the fragmentation the paged kernels must be invariant to.
    Returns ``(k_pages, v_pages, k_scale, v_scale, page_table)``: pages
    ``(layers, P, ps, KV, hdw)``, scales ``(layers, P)``, the page table as
    a numpy (B, NP) int32 array (callers jnp.asarray as needed). ``bits``:
    8 (int8 grid), 4 (lane-packed int32), 0 (float)."""
    from ..core.qtensor import pack_bits
    P = 1 + B * NP + extra_pages
    shape = (layers, P, ps, kv, hd)
    if bits == 8:
        kq = jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
        vq = jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
    elif bits == 4:
        kq, _ = pack_bits(jnp.asarray(rng.integers(-8, 8, shape),
                                      jnp.int32), 4)
        vq, _ = pack_bits(jnp.asarray(rng.integers(-8, 8, shape),
                                      jnp.int32), 4)
    else:
        kq = jnp.asarray(rng.normal(size=shape), jnp.float32)
        vq = jnp.asarray(rng.normal(size=shape), jnp.float32)
    ks = jnp.asarray(rng.uniform(0.005, 0.08, (layers, P)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.08, (layers, P)), jnp.float32)
    ids = np.arange(1, P)
    rng.shuffle(ids)
    pt = ids[:B * NP].reshape(B, NP).astype(np.int32)
    return kq, vq, ks, vs, pt


def paged_kv_attention_chunk_ref(q, k_pages, v_pages, k_scale, v_scale,
                                 page_table, q_start, kv_len, layer=0, *,
                                 bits: int = 8, head_dim=None):
    """Oracle for the variable-length chunk kernel: gather layer ``layer``'s
    pages into the logical dense view, dequantize with the per-page scales,
    run softmax attention with per-row causal masking against absolute
    query positions (``q_start[b] + i``) and the row's ``kv_len``.

    q: (B, S, H, hd); other shapes as in ``paged_kv_attention_chunk``.
    """
    from ..core.paged_kv import paged_gather
    container = {0: "fp", 8: "int8", 4: "int4"}[bits]
    pool = {"k_pages": k_pages, "v_pages": v_pages,
            "k_scale": k_scale, "v_scale": v_scale}
    B, S, H, _ = q.shape
    hd = head_dim if head_dim is not None else q.shape[-1]
    k, v = paged_gather(pool, jnp.asarray(page_table, jnp.int32),
                        container=container, head_dim=hd, layer=layer)
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qs = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32).reshape(-1), (B,))
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32) / np.sqrt(hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k.astype(jnp.float32))
    pos = jnp.arange(T)
    q_pos = qs[:, None] + jnp.arange(S)[None, :]            # (B, S)
    mask = (pos[None, None, :] <= q_pos[:, :, None]) & \
        (pos[None, None, :] < lens[:, None, None])
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd)


def kv_attention_ref(q, k_q, v_q, int_bits, frac_bits, kv_len):
    """q: (B, H, hd) float; k_q/v_q: (B, T, KV, hd) int8 grid; kv_len: int.
    GQA decode: one new token attends to the first kv_len cache entries.
    Returns (B, H, hd) float32."""
    scale, _, _ = format_params(int_bits, frac_bits)
    k = k_q.astype(jnp.float32) / scale
    v = v_q.astype(jnp.float32) / scale
    return masked_decode_attention_ref(q, k, v, kv_len)
