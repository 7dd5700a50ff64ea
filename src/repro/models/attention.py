"""Attention: GQA (with optional QKV bias / M-RoPE) and MLA (DeepSeek-V3).

Three memory-relevant design points, all tied to the paper:

* **Chunked (online-softmax) attention** — scores never materialize beyond a
  (..., S, chunk) tile, so 32k prefill and 500k decode stay within HBM. This
  is the pure-JAX analogue of the Pallas ``paged_kv_attention`` kernel and
  serves as its oracle at integration level.
* **Quantized KV cache** — the paper's per-layer "data" quantization applied
  to the tensor that dominates decode traffic. The cache stores an int8/int16
  integer grid; (scale, qmin, qmax) ride through ``lax.scan`` as per-layer
  scalars.
* Caches are preallocated to ``max_len`` and updated with dynamic slices, so
  decode steps compile once.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.fixedpoint import format_params
from ..core.paged_kv import (PagedKVLayout, init_paged_pool, paged_gather,
                             paged_update)
from ..parallel.hints import constrain
from .common import apply_mrope, apply_rope, dense_init, init_rmsnorm, rmsnorm

NEG_INF = -1e30


def _len_col(kv_len, ndim):
    """kv_len (scalar or (B,)) -> broadcastable (B|1, 1, ..) column for
    masking a trailing KV-position axis."""
    return jnp.asarray(kv_len).reshape((-1,) + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """Per-layer fixed-point spec for the KV cache (the paper's data bits).

    ``scale_mode`` (paged caches only): "static" stores on the layer's
    Q(I,F) grid; "page" calibrates a per-page max-abs scale at write time
    (see ``core.paged_kv.paged_update``).
    """

    int_bits: object  # python int or traced scalar (inside lax.scan)
    frac_bits: object
    container: str = "int8"  # static storage dtype
    scale_mode: str = "static"

    @property
    def dtype(self):
        if self.container == "int4":
            raise ValueError("int4 KV container requires a paged cache "
                             "(lane-packed pages); dense caches support "
                             "int8/int16")
        return {"int8": jnp.int8, "int16": jnp.int16}[self.container]


def init_kv_cache(batch, max_len, n_kv, head_dim, dtype,
                  quant: Optional[KVQuantSpec] = None):
    store = quant.dtype if quant is not None else dtype
    shape = (batch, max_len, n_kv, head_dim)
    return {"k": jnp.zeros(shape, store), "v": jnp.zeros(shape, store)}


def init_paged_kv_cache(num_pages, page_size, n_kv, head_dim, dtype,
                        quant: Optional[KVQuantSpec] = None):
    """Paged pool for one GQA layer (no batch dim — pages are shared)."""
    layout = PagedKVLayout(
        num_pages=num_pages, page_size=page_size, num_kv_heads=n_kv,
        head_dim=head_dim,
        container="fp" if quant is None else quant.container, dtype=dtype)
    return init_paged_pool(layout)


def _q_store(x, quant: Optional[KVQuantSpec]):
    if quant is None:
        return x
    scale, qmin, qmax = format_params(quant.int_bits, quant.frac_bits)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * scale), qmin, qmax)
    return q.astype(quant.dtype)


def _q_load(x, quant: Optional[KVQuantSpec], dtype):
    if quant is None:
        return x.astype(dtype)
    scale, _, _ = format_params(quant.int_bits, quant.frac_bits)
    return (x.astype(jnp.float32) / scale).astype(dtype)


def seq_update(buf, new, pos):
    """Write ``new`` (B, S, ...) into ``buf`` (B, T, ...) at token offset
    ``pos`` — scalar (shared clock) or (B,) per-row offsets."""
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, pos, 1)
    return jax.vmap(
        lambda b, n, p: jax.lax.dynamic_update_slice_in_dim(b, n, p, 0)
    )(buf, new, jnp.asarray(pos, jnp.int32))


def cache_update(cache, k_new, v_new, pos, quant=None):
    """Write S_new tokens at offset ``pos`` (scalar or (B,) int32)."""
    k_q = _q_store(k_new, quant)
    v_q = _q_store(v_new, quant)
    k = seq_update(cache["k"], k_q.astype(cache["k"].dtype), pos)
    v = seq_update(cache["v"], v_q.astype(cache["v"].dtype), pos)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Paged KV cache (page-table indirection; core.paged_kv holds the pool ops)
# ---------------------------------------------------------------------------
def _paged_container(cache) -> str:
    dt = cache["k_pages"].dtype
    if jnp.issubdtype(dt, jnp.floating):
        return "fp"
    return "int8" if dt == jnp.int8 else "int4"


def paged_cache_update(cache, k_new, v_new, page_table, pos,
                       quant: Optional[KVQuantSpec] = None, valid_len=None,
                       layer=None):
    """Append S new tokens through the page table (pos scalar or (B,)).

    ``valid_len`` marks trailing chunk tokens as padding (their writes go to
    the scratch page) — the bucketed-prefill contract (core.paged_kv).
    ``layer`` addresses one layer of a stacked ``(L, P, ...)`` pool.
    """
    container = _paged_container(cache)
    return paged_update(
        cache, k_new, v_new, page_table, pos,
        page_size=cache["k_pages"].shape[-3], container=container,
        int_bits=None if quant is None else quant.int_bits,
        frac_bits=None if quant is None else quant.frac_bits,
        valid_len=valid_len,
        scale_mode="static" if quant is None else quant.scale_mode,
        layer=layer)


def paged_cache_view(cache, page_table, *, head_dim, dtype, layer=None):
    """Logical dense (B, NP*ps, KV, hd) float view of a paged cache."""
    return paged_gather(cache, page_table, container=_paged_container(cache),
                        head_dim=head_dim, dtype=dtype, layer=layer)


def route_paged_attention(q, cache, page_table, positions, cache_pos, *,
                          cfg, attn_impl: str = "gather",
                          operand_dtype=jnp.float32, layer=None, work=None):
    """Unified variable-length paged attention entry point.

    ONE routing layer for every paged attention read — chunked prefill
    (S > 1) and decode (S == 1) alike — keyed by (impl, chunk shape,
    container):

    * ``attn_impl="pallas"`` sends the chunk through
      ``kernels.paged_kv_attention`` (scalar-prefetch DMA over the page
      table, dequant in VMEM, per-row causal masking against absolute cache
      positions). Per-page online softmax reorders accumulation, so
      pallas == gather only within float tolerance. The kernel's mask is causal by construction, so a
      non-causal config is refused.
    * ``attn_impl="gather"`` reads the pool through the jnp gather path —
      identical chunk accumulation order to the dense cache, which keeps
      paged serving bitwise-equal to the dense layout (the reference mode
      the equivalence tests rely on).

    ``q``: (B, S, H, hd) post-RoPE queries; ``cache``: the pool dict AFTER
    this chunk's ``paged_cache_update`` write, a stacked ``(L, P, ...)``
    pool read at ``layer`` or (``layer=None``) one layer's pool;
    ``cache_pos``: scalar or (B,) position of the chunk's first token.
    Padded chunk tails need no special masking here: the causal bound of
    every REAL query is tighter than the padding positions, and padded
    queries' outputs are garbage nobody reads (their pool writes were
    scratch-redirected). ``work``: the kernel's work list for the step
    (``paged_kernel_work``), or None for the kernel to build it. Returns
    (B, S, H, hd) in q.dtype.
    """
    B, S, H, hd = q.shape
    base = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32).reshape(-1),
                            (B,))
    if attn_impl == "pallas":
        if not cfg.causal:
            raise ValueError("the paged attention kernel masks causally; "
                             "a non-causal config needs attn_impl='gather'")
        return _paged_kernel_attention(q, cache, page_table, base, layer,
                                       work)
    kd, vd = paged_cache_view(cache, page_table, head_dim=hd,
                              dtype=operand_dtype, layer=layer)
    return attend_chunked(q, kd, vd, positions, 0, causal=cfg.causal,
                          kv_len=base + S, chunk=cfg.attn_chunk,
                          operand_dtype=operand_dtype)


def _kernel_tp(cache, mesh=None) -> int:
    """Shards the paged kernel runs on: the "model" axis of ``mesh`` (the
    abstract mesh of the trace when None) where it divides the pool's KV
    heads (tensor-parallel serving), else 1."""
    mesh = jax.sharding.get_abstract_mesh() if mesh is None else mesh
    tp = 1 if mesh.empty else dict(mesh.shape).get("model", 1)
    return tp if tp > 1 and cache["k_pages"].shape[-2] % tp == 0 else 1


def paged_kernel_pages(cache, mesh=None) -> dict:
    """The page geometry each paged-kernel call sees in ``cache``'s pool,
    as ``kernels.paged_kv_attention`` takes it: page size, KV heads (a
    shard's under tensor parallelism, :func:`_kernel_tp`), stored words a
    head and the container's bits."""
    *_, ps, kv, words = cache["k_pages"].shape
    return {"page_size": ps, "kv_heads": kv // _kernel_tp(cache, mesh),
            "words": words,
            "bits": {"int8": 8, "int4": 4, "fp": 0}[_paged_container(cache)]}


def paged_kernel_work(cache, page_table, cache_pos, seq_len: int):
    """The Pallas paged kernel's work list
    (``kernels.paged_kv_attention.work_list``) for a chunk of ``seq_len``
    queries from ``cache_pos`` (scalar or (B,)) over ``cache``'s pool, as
    each layer's kernel call (each shard's, under tensor parallelism)
    walks it. Every layer of a step walks the same table, so the layer
    scan builds it once (``models.transformer._segment_scan``)."""
    from ..kernels.paged_kv_attention import work_list
    B, NP = page_table.shape
    base = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32).reshape(-1),
                            (B,))
    return work_list(base, base + seq_len, seq_len=seq_len,
                     num_table_pages=NP, **paged_kernel_pages(cache))


def _paged_kernel_attention(q, cache, page_table, base, layer, work=None):
    """The Pallas paged kernel on the chunk ``q`` (B, S, H, hd), reading
    layer ``layer`` of the stacked pool in place (a single layer's pool,
    ``layer=None``, is read as a stack of one), over the step's ``work``
    list (:func:`paged_kernel_work`; the kernel builds it when None).
    Under a mesh context whose "model" axis divides the KV heads
    (tensor-parallel serving: pools sharded on KV heads, queries on
    heads), the kernel runs per shard through ``shard_map`` on that
    shard's heads and pages, so the partitioner never gathers the pools
    onto one device."""
    from ..kernels.ops import paged_kv_attention_chunk
    S = q.shape[1]
    bits = paged_kernel_pages(cache)["bits"]
    pools = [cache[f] for f in ("k_pages", "v_pages", "k_scale", "v_scale")]
    if layer is None:
        pools, layer = [a[None] for a in pools], 0

    def kernel(q, k_pages, v_pages, k_scale, v_scale, pt, base, layer, work):
        return paged_kv_attention_chunk(q, k_pages, v_pages, k_scale,
                                        v_scale, pt, base, base + S,
                                        layer=layer, work=work, bits=bits)

    args = (q, *pools, page_table, base, jnp.asarray(layer, jnp.int32),
            work)
    if _kernel_tp(cache) > 1:
        from jax.sharding import PartitionSpec as P
        heads = P(None, None, "model", None)
        pool = P(None, None, None, "model", None)
        kernel = jax.shard_map(
            kernel, mesh=jax.sharding.get_abstract_mesh(),
            in_specs=(heads, pool, pool, P(), P(), P(), P(), P(), P()),
            out_specs=heads, check_vma=False)
    return kernel(*args).astype(q.dtype)


# ---------------------------------------------------------------------------
# Core attention math (grouped heads, online softmax over KV chunks)
# ---------------------------------------------------------------------------
def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


def attend_full(q, k, v, q_pos, kv_pos, *, causal=True, kv_len=None,
                scale=None):
    """Reference full-materialization attention (small shapes / oracle).

    q: (B,S,H,hd); k,v: (B,T,KV,hd); q_pos: (B,S); kv_pos: (T,)
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    vd = v.shape[-1]            # may differ from hd (MLA: dn+dr vs dv)
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((B, S, k.shape[1]), bool)
    if causal:
        mask &= kv_pos[None, None, :] <= q_pos[:, :, None]
    if kv_len is not None:
        mask &= kv_pos[None, None, :] < _len_col(kv_len, 3)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, vd).astype(q.dtype)


def attend_chunked(q, k, v, q_pos, kv_start, *, causal=True, kv_len=None,
                   chunk=1024, kv_quant: Optional[KVQuantSpec] = None,
                   scale=None, operand_dtype=jnp.float32):
    """Flash-style online-softmax attention, scanning KV in chunks.

    k/v may be an integer-grid quantized cache; each chunk is dequantized in
    registers (the jnp analogue of the Pallas kernel's VMEM dequant).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    vd = v.shape[-1]            # may differ from hd (MLA: dn+dr vs dv)
    T = k.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    chunk = min(chunk, T)
    if T % chunk:
        pad = chunk - T % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        T = T + pad
        if kv_len is None:
            kv_len = T - pad
    nc = T // chunk
    if S == 1:
        # decode: grouped (KV, G) math — tensors are tiny at S=1 and the
        # G-fold K/V expansion of the training path would multiply the
        # dominant cache-read bytes by the group size (§Perf iteration)
        return _attend_chunked_grouped(q, k, v, q_pos, kv_start,
                                       causal=causal, kv_len=kv_len,
                                       chunk=chunk, kv_quant=kv_quant,
                                       scale=scale, nc=nc)
    # Work in EXPANDED H-head space, not (KV, G): H is divisible by the TP
    # degree when KV isn't (GQA kv=8 on a 16-way model axis), so all chunk
    # transients shard. K/V chunks expand on the fly (head h -> kv h // G).
    # ``operand_dtype=bf16`` (cfg.attn_bf16) halves the q/k/v chunk bytes +
    # gathers; softmax state and dot accumulation stay fp32.
    odt = operand_dtype
    qh = constrain((q.astype(jnp.float32) * scale).astype(odt),
                   "dp", None, "tp", None)

    k_c = jnp.moveaxis(k.reshape(B, nc, chunk, KV, hd), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nc, chunk, KV, vd), 1, 0)

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, S, H, vd), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        idx, kc, vc = inp
        kc = _q_load(kc, kv_quant, odt)
        vc = _q_load(vc, kv_quant, odt)
        if G > 1:
            kc = jnp.repeat(kc, G, axis=2)
            vc = jnp.repeat(vc, G, axis=2)
        kc = constrain(kc, "dp", None, "tp", None)
        vc = constrain(vc, "dp", None, "tp", None)
        s = jnp.einsum("bshd,bthd->bhst", qh, kc,
                       preferred_element_type=jnp.float32)
        pos = kv_start + idx * chunk + jnp.arange(chunk)
        valid = jnp.ones((B, S, chunk), bool)
        if causal:
            valid &= pos[None, None, :] <= q_pos[:, :, None]
        if kv_len is not None:
            valid &= pos[None, None, :] < _len_col(kv_len, 3)
        s = jnp.where(valid[:, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p, vc,
                        preferred_element_type=jnp.float32)
        corr_t = jnp.transpose(corr, (0, 2, 1))[..., None]   # (B,S,H,1)
        acc_new = acc * corr_t + pv
        return (m_new, l_new, acc_new), None

    idxs = jnp.arange(nc)
    # checkpoint the chunk body: backward recomputes s/p per chunk instead of
    # saving the stacked (nc, B, H, S, chunk) probabilities — the flash-
    # attention memory property at the jnp level
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (idxs, k_c, v_c))
    l_t = jnp.transpose(l, (0, 2, 1))[..., None]
    out = acc / jnp.maximum(l_t, 1e-30)
    return out.astype(q.dtype)


def _attend_chunked_grouped(q, k, v, q_pos, kv_start, *, causal, kv_len,
                            chunk, kv_quant, scale, nc):
    """Online-softmax decode attention in grouped (B,KV,G) layout; K/V are
    read chunk-by-chunk in their stored (possibly int8) form and never
    expanded across the group dim. S is 1 (a single new token)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    vd = v.shape[-1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32) * scale

    k_c = jnp.moveaxis(k.reshape(B, nc, chunk, KV, hd), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nc, chunk, KV, vd), 1, 0)

    m0 = jnp.full((B, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    a0 = jnp.zeros((B, KV, G, vd), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        idx, kc, vc = inp
        kc = _q_load(kc, kv_quant, jnp.float32)
        vc = _q_load(vc, kv_quant, jnp.float32)
        s = jnp.einsum("bkgh,btkh->bkgt", qg, kc,
                       preferred_element_type=jnp.float32)
        pos = kv_start + idx * chunk + jnp.arange(chunk)
        valid = pos[None, :] <= q_pos[:, -1:]  # causal vs the new token
        if kv_len is not None:
            valid = valid & (pos[None, :] < _len_col(kv_len, 2))
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgt,btkh->bkgh", p, vc, preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(nc), k_c, v_c))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, 1, H, vd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------
def init_gqa(key, cfg):
    """cfg: ModelConfig (configs.base). One layer; no leading L dim."""
    ks = jax.random.split(key, 4)
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.param_jnp_dtype
    p = {
        "wq": dense_init(ks[0], (D, H * hd), dt),
        "wk": dense_init(ks[1], (D, KV * hd), dt),
        "wv": dense_init(ks[2], (D, KV * hd), dt),
        "wo": dense_init(ks[3], (H * hd, D), dt, scale=1.0 / np.sqrt(H * hd)),
    }
    if cfg.attention_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    return p


def gqa_apply(params, x, positions, *, cfg, cache=None, cache_pos=None,
              kv_quant: Optional[KVQuantSpec] = None, mrope_positions=None,
              chunked: Optional[bool] = None, page_table=None,
              attn_impl: str = "gather", kv_valid_len=None,
              cache_layer=None, attn_work=None):
    """Returns (y, new_cache). ``positions``: (B, S) absolute positions.

    Train/prefill: cache=None -> attends within the sequence (causal per cfg),
    optionally returning a fresh cache when ``cache`` is a preallocated dict.
    Decode: cache given and S is the new-token count (usually 1);
    ``cache_pos`` is a scalar (shared clock) or (B,) per-row offsets. A paged
    cache (dict with "k_pages") additionally needs ``page_table`` (B, NP).

    ``attn_impl`` selects the paged attention backend for EVERY chunk shape
    (see ``route_paged_attention``): "gather" reads the pool through the jnp
    path (bitwise-reference mode, identical chunk order to the dense cache),
    "pallas" routes both chunked prefill (S > 1) and decode (S == 1) through
    the variable-length ``kernels.paged_kv_attention`` chunk kernel
    (scalar-prefetch DMA; per-page online softmax, so equal to gather only
    within float tolerance).
    ``kv_valid_len`` (scalar or (B,)) marks only the first tokens of a padded
    prefill chunk as real; padded tails scatter to the scratch page.
    ``cache_layer`` (scalar, may be traced) marks a paged ``cache`` as the
    whole stacked ``(L, P, ...)`` pool of the layer scan and names this
    layer's index in it: the pool is written and read in place, and the
    whole stacked pool is returned. ``attn_work``: the Pallas kernel's work
    list for the step (``paged_kernel_work``), None to build it per call.
    """
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = x.dtype

    q = x @ params["wq"].astype(cd)
    k = x @ params["wk"].astype(cd)
    v = x @ params["wv"].astype(cd)
    if cfg.attention_bias:
        q = q + params["bq"].astype(cd)
        k = k + params["bk"].astype(cd)
        v = v + params["bv"].astype(cd)
    q = constrain(_split_heads(q, H, hd), "dp", None, "tp", None)
    k = constrain(_split_heads(k, KV, hd), "dp", None, "tp", None)
    v = constrain(_split_heads(v, KV, hd), "dp", None, "tp", None)

    if cfg.mrope and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    use_chunked = (chunked if chunked is not None
                   else (S * max(S, 1) > cfg.attn_chunk ** 2 or cache is not None))

    odt = jnp.bfloat16 if cfg.attn_bf16 else jnp.float32
    if cache is not None and "k_pages" in cache:
        if page_table is None:
            raise ValueError("paged KV cache needs a page_table")
        if attn_impl not in ("gather", "pallas"):
            raise ValueError(f"attn_impl must be 'gather' or 'pallas', "
                             f"got {attn_impl!r}")
        new_cache = paged_cache_update(cache, k, v, page_table, cache_pos,
                                       kv_quant, valid_len=kv_valid_len,
                                       layer=cache_layer)
        # ONE entry point for chunk prefill AND decode: the routing layer
        # picks the Pallas chunk kernel (S >= 1; per-page online softmax,
        # float-tolerance equal) or the jnp gather path (bitwise reference)
        o = route_paged_attention(q, new_cache, page_table, positions,
                                  cache_pos, cfg=cfg, attn_impl=attn_impl,
                                  operand_dtype=odt, layer=cache_layer,
                                  work=attn_work)
    elif cache is not None:
        pos = cache_pos
        new_cache = cache_update(cache, k, v, pos, kv_quant)
        kv_len = pos + S
        o = attend_chunked(q, new_cache["k"], new_cache["v"], positions, 0,
                           causal=cfg.causal, kv_len=kv_len,
                           chunk=cfg.attn_chunk, kv_quant=kv_quant,
                           operand_dtype=odt)
    else:
        new_cache = None
        if use_chunked:
            o = attend_chunked(q, k, v, positions, 0, causal=cfg.causal,
                               chunk=cfg.attn_chunk,
                               operand_dtype=jnp.bfloat16 if cfg.attn_bf16
                               else jnp.float32)
        else:
            o = attend_full(q, k, v, positions, jnp.arange(S),
                            causal=cfg.causal)

    y = o.reshape(B, S, H * hd) @ params["wo"].astype(cd)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): low-rank latent KV — the cache holds only
# (kv_lora_rank + rope_dim) per token, and that latent is what we quantize.
# ---------------------------------------------------------------------------
def init_mla(key, cfg):
    ks = jax.random.split(key, 6)
    D, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.param_jnp_dtype
    return {
        "wq_a": dense_init(ks[0], (D, qr), dt),
        "q_norm": init_rmsnorm(qr, dt),
        "wq_b": dense_init(ks[1], (qr, H * (dn + dr)), dt),
        "wkv_a": dense_init(ks[2], (D, kvr + dr), dt),
        "kv_norm": init_rmsnorm(kvr, dt),
        "wkv_b": dense_init(ks[3], (kvr, H * (dn + dv)), dt),
        "wo": dense_init(ks[4], (H * dv, D), dt, scale=1.0 / np.sqrt(H * dv)),
    }


def init_mla_cache(batch, max_len, cfg, dtype,
                   quant: Optional[KVQuantSpec] = None):
    store = quant.dtype if quant is not None else dtype
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {"latent": jnp.zeros((batch, max_len, width), store)}


def mla_apply(params, x, positions, *, cfg, cache=None, cache_pos=None,
              kv_quant: Optional[KVQuantSpec] = None, absorbed: bool = False):
    """Returns (y, new_cache). Latent cache = [c_kv(kvr) ; k_rope(dr)].

    ``absorbed=False`` (baseline) expands the latent to per-head K/V at use.
    ``absorbed=True`` folds W_uk into the query and W_uv into the output
    projection so decode attends directly in latent space — the beyond-paper
    perf option (see EXPERIMENTS.md §Perf).
    """
    B, S, D = x.shape
    H = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cd = x.dtype
    sm_scale = 1.0 / np.sqrt(dn + dr)

    # --- queries ---
    cq = rmsnorm(params["q_norm"], x @ params["wq_a"].astype(cd), cfg.norm_eps)
    q = (cq @ params["wq_b"].astype(cd)).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # --- latent KV ---
    kv_a = x @ params["wkv_a"].astype(cd)
    c_kv = rmsnorm(params["kv_norm"], kv_a[..., :kvr], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., kvr:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)  # (B, S, kvr+dr)

    if cache is not None:
        lat_q = _q_store(latent, kv_quant)
        new_cache = {"latent": seq_update(
            cache["latent"], lat_q.astype(cache["latent"].dtype), cache_pos)}
        lat_all = _q_load(new_cache["latent"], kv_quant, cd)
        kv_len = cache_pos + S
        T = lat_all.shape[1]
    else:
        new_cache = None
        lat_all, kv_len, T = latent, None, S

    c_all, kr_all = lat_all[..., :kvr], lat_all[..., kvr:]

    wkv_b = params["wkv_b"].astype(cd).reshape(kvr, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]  # (kvr, H, dn), (kvr, H, dv)

    if absorbed:
        # fold W_uk into q: q_lat = q_nope @ W_uk^T per head -> (B,S,H,kvr)
        q_lat = jnp.einsum("bshd,khd->bshk", q_nope, w_uk)
        # scores over latent + rope parts; latent plays the role of K
        k_lat = c_all  # (B,T,kvr) shared across heads
        s = (jnp.einsum("bshk,btk->bhst", q_lat.astype(jnp.float32),
                        k_lat.astype(jnp.float32))
             + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                          kr_all.astype(jnp.float32))) * sm_scale
        mask = jnp.ones((B, S, T), bool)
        if cfg.causal:
            mask &= jnp.arange(T)[None, None, :] <= positions[:, :, None]
        if kv_len is not None:
            mask &= jnp.arange(T)[None, None, :] < _len_col(kv_len, 3)
        s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btk->bshk", p, c_all.astype(jnp.float32))
        o = jnp.einsum("bshk,khd->bshd", o_lat, w_uv.astype(jnp.float32))
        o = o.astype(cd)
    else:
        # expand latent to per-head K/V (baseline; memory-heavier at decode)
        # pin head sharding at the source: without it GSPMD all-gathers the
        # (B,T,H,dn+dr) expansion to FULL H around the attention chunk scan
        # (§Perf deepseek-v3 iteration)
        k_nope = jnp.einsum("btk,khd->bthd", c_all, w_uk)
        vv = constrain(jnp.einsum("btk,khd->bthd", c_all, w_uv),
                       "dp", None, "tp", None)
        k_full = constrain(jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all[:, :, None, :], (B, T, H, dr))],
            axis=-1), "dp", None, "tp", None)
        q_full = constrain(jnp.concatenate([q_nope, q_rope], axis=-1),
                           "dp", None, "tp", None)
        o = attend_chunked(q_full, k_full, vv, positions, 0, causal=cfg.causal,
                           kv_len=kv_len, chunk=cfg.attn_chunk, scale=sm_scale,
                           operand_dtype=jnp.bfloat16 if cfg.attn_bf16
                           else jnp.float32)

    y = o.reshape(B, S, H * dv) @ params["wo"].astype(cd)
    return y, new_cache
