"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (this container) and False on TPU —
the same call sites serve tests and production. Each op has a ``*_ref``
oracle in kernels.ref; tests/test_kernels.py sweeps shapes/dtypes
(hypothesis) asserting allclose.
"""
from __future__ import annotations

import jax

from . import ref
from .paged_kv_attention import \
    paged_kv_attention_chunk as _paged_kv_attention_chunk
from .paged_kv_attention import BLOCK_Q, paged_kv_attention_decode
from .pack import pack_2d, unpack_2d, values_per_word
from .quant_cast import quant_cast_2d
from .quant_matmul import quant_matmul


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def quant_cast(x, int_bits: int, frac_bits: int, *, interpret=None):
    """Fake-quant Q(I,F) on arbitrary-rank input (kernel works on 2-D)."""
    interpret = _default_interpret() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim != 2 else x
    if x2.ndim == 1:
        x2 = x2[None, :]
    y = quant_cast_2d(x2, int_bits=int_bits, frac_bits=frac_bits,
                      interpret=interpret)
    return y.reshape(shape)


def pack(q, bits: int, *, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    shape = q.shape
    q2 = q.reshape(-1, shape[-1])
    w = pack_2d(q2, bits=bits, interpret=interpret)
    return w.reshape(*shape[:-1], shape[-1] // values_per_word(bits))


def unpack(w, bits: int, *, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    shape = w.shape
    w2 = w.reshape(-1, shape[-1])
    q = unpack_2d(w2, bits=bits, interpret=interpret)
    return q.reshape(*shape[:-1], shape[-1] * values_per_word(bits))


def qmatmul(a, wq, scales, *, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return quant_matmul(a, wq, scales, interpret=interpret)


def paged_kv_attention(q, k_pages, v_pages, k_scale, v_scale, page_table,
                       kv_len, layer=0, *, bits: int = 8, interpret=None):
    """Decode attention over layer ``layer`` of stacked paged quantized KV
    pools (see kernels.paged_kv_attention for shapes). bits: 8 (int8
    pages), 4 (int32 lane-packed pages) or 0 (float pages)."""
    interpret = _default_interpret() if interpret is None else interpret
    return paged_kv_attention_decode(q, k_pages, v_pages, k_scale, v_scale,
                                     page_table, kv_len, layer, bits=bits,
                                     interpret=interpret)


def paged_kv_attention_chunk(q, k_pages, v_pages, k_scale, v_scale,
                             page_table, q_start, kv_len, layer=0, work=None,
                             *, bits: int = 8, block_q: int = BLOCK_Q,
                             interpret=None):
    """Variable-length (S >= 1) chunk attention over a paged quantized KV
    pool — the prefill-chunk generalization of ``paged_kv_attention`` (see
    kernels.paged_kv_attention for shapes). q: (B, S, H, hd); ``q_start``
    (B,) is the absolute position of each row's first chunk query;
    ``work``: the call's work list, built by the kernel when None."""
    interpret = _default_interpret() if interpret is None else interpret
    return _paged_kv_attention_chunk(q, k_pages, v_pages, k_scale, v_scale,
                                     page_table, q_start, kv_len, layer,
                                     work, bits=bits, block_q=block_q,
                                     interpret=interpret)


__all__ = ["quant_cast", "pack", "unpack", "qmatmul", "paged_kv_attention",
           "paged_kv_attention_chunk", "ref"]
