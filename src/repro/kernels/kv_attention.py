"""Dense int8 KV-cache decode attention — thin wrapper over the paged kernel.

The original standalone Pallas kernel was absorbed into
``paged_kv_attention.py``: a contiguous (B, T, KV, hd) cache is just the
special case of a one-layer paged pool whose page table is the identity
mapping (sequence b's page p is pool page b * NP + p) and whose per-page
scales are all the layer's Q(I,F) scale 2^-F. Tile size ``block_t``
becomes the page size, so the VMEM working set and the online-softmax loop
structure are unchanged from the old kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_kv_attention import paged_kv_attention_decode


@functools.partial(jax.jit, static_argnames=("int_bits", "frac_bits",
                                             "block_t", "interpret"))
def kv_attention_decode(q, k_q, v_q, kv_len, *, int_bits: int,
                        frac_bits: int, block_t: int = 512,
                        interpret: bool = False):
    """q: (B, H, hd) float; k_q/v_q: (B, T, KV, hd) int8 Q(I,F) grid;
    kv_len: scalar int32. Returns (B, H, hd) float32."""
    del int_bits  # range already encoded in the stored grid
    B, H, hd = q.shape
    T, KV = k_q.shape[1], k_q.shape[2]
    ps = min(block_t, T)
    pt = (-T) % ps
    if pt:
        k_q = jnp.pad(k_q, ((0, 0), (0, pt), (0, 0), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pt), (0, 0), (0, 0)))
    NP = k_q.shape[1] // ps
    k_pages = k_q.reshape(1, B * NP, ps, KV, hd)
    v_pages = v_q.reshape(1, B * NP, ps, KV, hd)
    page_table = jnp.arange(B * NP, dtype=jnp.int32).reshape(B, NP)
    scale = jnp.full((1, B * NP), 2.0 ** -frac_bits, jnp.float32)
    lens = jnp.full((B,), jnp.asarray(kv_len, jnp.int32))
    return paged_kv_attention_decode(
        q, k_pages, v_pages, scale, scale, page_table, lens, bits=8,
        interpret=interpret)
