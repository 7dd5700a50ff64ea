"""Model assembly: blocks, segmented scan-over-layers, train/serve steps.

Layers are grouped into **segments** of identical structure (e.g. DeepSeek-V3:
3 dense layers then 58 MoE layers; Jamba: 4 periods of the 8-layer
mamba/attn/MoE pattern). Each segment is a single ``lax.scan`` over stacked
parameters, so an 80-layer model compiles one block body — essential for the
1-CPU-core 512-fake-device dry-run, and it is also how per-layer precision
stays free: the per-layer Q(I,F) scale/bound vectors are just more scanned
operands (DESIGN.md §3).

Per-layer quantization hooks (all optional, driven by ``ModelQuant``):
  * weights: fake-quant of >=2-D block params before use (paper "weights"),
  * residual stream: fake-quant of each block's output (paper "data"),
  * KV/SSM state: integer-grid storage via KVQuantSpec / state_quant.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.fixedpoint import fake_quant
from ..core.paged_kv import PagedCacheSpec
from ..parallel.hints import constrain
from .attention import (KVQuantSpec, gqa_apply, init_gqa, init_kv_cache,
                        init_mla, init_mla_cache, init_paged_kv_cache,
                        mla_apply, paged_kernel_work)
from .common import (chunked_ce_loss, cross_entropy, dense_init, embed_tokens,
                     init_embedding, init_lm_head, init_rmsnorm, lm_head,
                     rmsnorm)
from .mlp import gelu_mlp_apply, init_gelu_mlp, init_swiglu, swiglu_apply
from .moe import init_moe, moe_apply
from .ssm import (init_mamba, init_mamba_state, init_mlstm, init_mlstm_state,
                  init_slstm, init_slstm_state, mamba_apply, mlstm_apply,
                  slstm_apply)


# ---------------------------------------------------------------------------
# Quantization plumbing
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelQuant:
    """Stacked per-layer Q(I,F) parameters; (L,) float32 arrays (or None).

    Built from a PrecisionPolicy by ``repro.quant.apply.build_model_quant``.

    ``kv_container`` is the uniform storage container; ``kv_containers``
    (optional, static tuple of one container name per layer, "fp" marking an
    unquantized layer) switches the paged serving cache to **per-layer KV
    precision profiles**: contiguous same-container layer runs are grouped
    into scanned sub-segments (``_segment_scan_grouped``) so a realistic
    profile still compiles O(distinct runs) block bodies; only length-1
    runs — pathological alternating profiles — unroll. ``kv_unroll=True``
    forces the fully unrolled reference path (``_segment_unrolled``,
    per-period pools) for debugging and identity tests. ``kv_scale_mode``
    ("static" | "page") picks the paged dequant scale calibration (see
    ``core.paged_kv.paged_update``).
    """

    w_int: Optional[jnp.ndarray] = None
    w_frac: Optional[jnp.ndarray] = None
    a_int: Optional[jnp.ndarray] = None
    a_frac: Optional[jnp.ndarray] = None
    kv_int: Optional[jnp.ndarray] = None
    kv_frac: Optional[jnp.ndarray] = None
    kv_container: str = "int8"
    kv_containers: Optional[Tuple[str, ...]] = None  # per-layer (static)
    kv_scale_mode: str = "static"
    kv_unroll: bool = False       # force the fully unrolled profile path

    def layer_slice(self, sl):
        """Slice all stacked arrays with ``sl`` (layer indices).

        Only valid on uniform-container quants: per-layer containers are
        static python strings and cannot ride a scan — the profile paths
        slice with :meth:`layer_static` / ``_run_quant`` instead."""
        assert self.kv_containers is None, \
            "per-layer KV containers require the unrolled (layer_static) path"
        f = lambda a: None if a is None else a[sl]
        return ModelQuant(f(self.w_int), f(self.w_frac), f(self.a_int),
                          f(self.a_frac), f(self.kv_int), f(self.kv_frac),
                          self.kv_container,
                          kv_scale_mode=self.kv_scale_mode,
                          kv_unroll=self.kv_unroll)

    def layer_static(self, li: int) -> "ModelQuant":
        """Static single-layer view for the unrolled segment path: scalars
        plus THIS layer's concrete container ("fp" layers drop the KV quant
        entirely, so their pools store float pages)."""
        cont = (self.kv_containers[li] if self.kv_containers is not None
                else self.kv_container)
        f = lambda a: None if a is None else a[li]
        kv_i, kv_f = f(self.kv_int), f(self.kv_frac)
        if cont == "fp":
            kv_i = kv_f = None
            cont = self.kv_container
        return ModelQuant(f(self.w_int), f(self.w_frac), f(self.a_int),
                          f(self.a_frac), kv_i, kv_f, cont,
                          kv_scale_mode=self.kv_scale_mode,
                          kv_unroll=self.kv_unroll)


def _mq_flatten(mq):
    return ((mq.w_int, mq.w_frac, mq.a_int, mq.a_frac, mq.kv_int,
             mq.kv_frac),
            (mq.kv_container, mq.kv_containers, mq.kv_scale_mode,
             mq.kv_unroll))


def _mq_unflatten(aux, children):
    return ModelQuant(*children, kv_container=aux[0], kv_containers=aux[1],
                      kv_scale_mode=aux[2], kv_unroll=aux[3])


jax.tree_util.register_pytree_node(ModelQuant, _mq_flatten, _mq_unflatten)


def _quant_weights(params, w_int, w_frac):
    """Fake-quant all >=2-D float leaves (the paper's weight quantization;
    1-D leaves — biases, norm scales, SSM log-decays — stay full precision)."""
    if w_int is None:
        return params

    def q(leaf):
        if (hasattr(leaf, "ndim") and leaf.ndim >= 2
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            return fake_quant(leaf, w_int, w_frac)
        return leaf

    return jax.tree_util.tree_map(q, params)


# ---------------------------------------------------------------------------
# Segment structure
# ---------------------------------------------------------------------------
def layer_signatures(cfg) -> Tuple[Tuple[str, str], ...]:
    """Per-layer (kind, ffn) with ffn in {mlp, moe, none}."""
    sigs = []
    kinds = cfg.layer_kinds
    for i in range(cfg.num_layers):
        kind = kinds[i]
        if kind in ("mlstm", "slstm"):
            ffn = "none"
        elif cfg.is_moe_layer(i):
            ffn = "moe"
        else:
            ffn = "mlp"
        sigs.append((kind, ffn))
    return tuple(sigs)


def layer_segments(cfg):
    """Split layers into (pattern, periods, start_idx) segments where
    ``pattern`` repeats exactly ``periods`` times."""
    sigs = layer_signatures(cfg)
    bounds = [0]
    if 0 < cfg.first_k_dense < cfg.num_layers:
        bounds.append(cfg.first_k_dense)
    bounds.append(cfg.num_layers)
    segments = []
    for b0, b1 in zip(bounds[:-1], bounds[1:]):
        seg = sigs[b0:b1]
        n = len(seg)
        period = n
        for p in range(1, n + 1):
            if n % p == 0 and all(seg[i] == seg[i % p] for i in range(n)):
                period = p
                break
        segments.append((tuple(seg[:period]), n // period, b0))
    return segments


# ---------------------------------------------------------------------------
# Single block (pre-norm residual): x += mixer(norm(x)); x += ffn(norm(x))
# ---------------------------------------------------------------------------
def init_block(key, cfg, sig):
    kind, ffn = sig
    ks = jax.random.split(key, 4)
    dt = cfg.param_jnp_dtype
    p = {"norm1": init_rmsnorm(cfg.d_model, dt)}
    if kind == "attn":
        p["mixer"] = (init_mla(ks[0], cfg) if cfg.attention_type == "mla"
                      else init_gqa(ks[0], cfg))
    elif kind == "mamba":
        p["mixer"] = init_mamba(ks[0], cfg)
    elif kind == "mlstm":
        p["mixer"] = init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["mixer"] = init_slstm(ks[0], cfg)
    else:
        raise ValueError(kind)
    if ffn != "none":
        p["norm2"] = init_rmsnorm(cfg.d_model, dt)
        if ffn == "moe":
            p["ffn"] = init_moe(ks[1], cfg)
        elif cfg.family == "encoder":
            p["ffn"] = init_gelu_mlp(ks[1], cfg.d_model, cfg.d_ff, dt)
        else:
            p["ffn"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dt)
    return p


def block_apply(params, x, positions, *, cfg, sig, cache=None, cache_pos=None,
                quant: Optional[ModelQuant] = None, mrope_positions=None,
                page_table=None, attn_impl: str = "gather",
                kv_valid_len=None, cache_layer=None, attn_work=None):
    """Returns (x, new_cache, aux). ``quant`` holds per-THIS-layer scalars.

    ``attn_impl``/``kv_valid_len`` only affect paged GQA attention: kernel
    vs gather routing (one variable-length path for chunk prefill AND
    decode) and padded-chunk masking (see ``attention.gqa_apply``).
    ``cache_layer`` is this layer's index in a stacked paged ``cache``
    (``_segment_scan``), which is then updated in place and returned whole;
    ``attn_work`` is the Pallas kernel's work list the scan built for the
    step (``attention.paged_kernel_work``).
    """
    kind, ffn = sig
    aux = {}
    if quant is not None:
        params = _quant_weights(params, quant.w_int, quant.w_frac)
        kv_quant = (KVQuantSpec(quant.kv_int, quant.kv_frac,
                                quant.kv_container,
                                scale_mode=quant.kv_scale_mode)
                    if quant.kv_int is not None else None)
        state_quant = ((quant.kv_int, quant.kv_frac)
                       if quant.kv_int is not None else None)
    else:
        kv_quant = state_quant = None

    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        if cfg.attention_type == "mla":
            y, new_cache = mla_apply(params["mixer"], h, positions, cfg=cfg,
                                     cache=cache, cache_pos=cache_pos,
                                     kv_quant=kv_quant,
                                     absorbed=cfg.mla_absorbed)
        else:
            y, new_cache = gqa_apply(params["mixer"], h, positions, cfg=cfg,
                                     cache=cache, cache_pos=cache_pos,
                                     kv_quant=kv_quant,
                                     mrope_positions=mrope_positions,
                                     page_table=page_table,
                                     attn_impl=attn_impl,
                                     kv_valid_len=kv_valid_len,
                                     cache_layer=cache_layer,
                                     attn_work=attn_work)
    elif kind == "mamba":
        y, new_cache = mamba_apply(params["mixer"], h, cfg=cfg, state=cache,
                                   state_quant=state_quant)
    elif kind == "mlstm":
        y, new_cache = mlstm_apply(params["mixer"], h, cfg=cfg, state=cache,
                                   state_quant=state_quant)
    elif kind == "slstm":
        y, new_cache = slstm_apply(params["mixer"], h, cfg=cfg, state=cache,
                                   state_quant=state_quant)
    else:
        raise ValueError(kind)
    x = x + y

    if ffn != "none":
        h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe_apply(params["ffn"], h, cfg=cfg)
        elif cfg.family == "encoder":
            y = gelu_mlp_apply(params["ffn"], h)
        else:
            y = swiglu_apply(params["ffn"], h)
        x = x + y

    if quant is not None and quant.a_int is not None:
        x = fake_quant(x, quant.a_int, quant.a_frac)  # paper's "data" bits
    # SP: the residual carried between blocks (== the remat-saved tensor) is
    # sequence-sharded over "model"; compute inside the block re-gathers.
    # Cuts saved-activation HBM by the TP degree (16x on the prod mesh).
    x = constrain(x, "dp", "tp", None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Cache construction (stacked per segment/position)
# ---------------------------------------------------------------------------
def init_block_cache(cfg, sig, batch, max_len, dtype, kv_quant=None,
                     paged: Optional[PagedCacheSpec] = None):
    kind, _ = sig
    if kind == "attn":
        if cfg.attention_type == "mla":
            if paged is not None:
                raise NotImplementedError(
                    "paged KV cache supports GQA attention; MLA latent "
                    "paging is future work")
            return init_mla_cache(batch, max_len, cfg, dtype, kv_quant)
        if paged is not None:
            return init_paged_kv_cache(paged.num_pages, paged.page_size,
                                       cfg.num_kv_heads, cfg.head_dim,
                                       dtype, kv_quant)
        return init_kv_cache(batch, max_len, cfg.num_kv_heads, cfg.head_dim,
                             dtype, kv_quant)
    if kind == "mamba":
        return init_mamba_state(batch, cfg, dtype)
    if kind == "mlstm":
        return init_mlstm_state(batch, cfg, dtype)
    if kind == "slstm":
        return init_slstm_state(batch, cfg, dtype)
    raise ValueError(kind)


def init_cache(cfg, batch, max_len, quant: Optional[ModelQuant] = None,
               paged: Optional[PagedCacheSpec] = None):
    """Full-model cache: list per segment of tuple per pattern position of
    stacked (periods, ...) block caches.

    ``paged`` switches attention layers to page-table pools (see
    core.paged_kv): each attention layer gets a (num_pages, page_size, KV,
    hd) pool instead of a (batch, max_len, KV, hd) slab, so HBM scales with
    allocated pages, not worst-case request length. SSM states are O(batch)
    and stay dense.

    With a **per-layer precision profile** (``quant.kv_containers``), pools
    cannot be broadcast-stacked across the whole segment — an int4 layer's
    pool has a different store dtype/shape than an int8 layer's — so each
    (segment, position) entry becomes a LIST of pools per contiguous
    same-container period RUN (each run's pools stacked ``(run_len, ...)``)
    and the forward scans run-by-run (``_segment_scan_grouped``). With
    ``quant.kv_unroll`` the entry degenerates to one UNSTACKED pool per
    period and the forward fully unrolls (``_segment_unrolled``). Requires
    a paged cache."""
    per_layer = quant is not None and quant.kv_containers is not None
    if per_layer and paged is None:
        raise ValueError("per-layer KV containers require a paged cache "
                         "(--page-size > 0)")
    kv_quant = None
    if quant is not None and quant.kv_int is not None:
        kv_quant = KVQuantSpec(8, 0, quant.kv_container)  # container only
    caches = []
    for pattern, periods, start in layer_segments(cfg):
        seg = []
        npos = len(pattern)
        if per_layer:
            runs, _ = _container_runs(quant.kv_containers, start, periods,
                                      npos)
            if quant.kv_unroll:
                runs = [(p, p + 1) for p in range(periods)]
            for pi, sig in enumerate(pattern):
                pools = []
                for p0, p1 in runs:
                    cont = quant.kv_containers[start + p0 * npos + pi]
                    kvq = (None if cont == "fp"
                           else KVQuantSpec(8, 0, cont))
                    one = init_block_cache(
                        cfg, sig, batch, max_len, cfg.compute_jnp_dtype,
                        kvq, paged)
                    if quant.kv_unroll:
                        pools.append(one)            # per-period, unstacked
                    else:
                        pools.append(jax.tree_util.tree_map(
                            lambda a: jnp.broadcast_to(
                                a[None], (p1 - p0,) + a.shape), one))
                seg.append(pools)
            caches.append(tuple(seg))
            continue
        for pi, sig in enumerate(pattern):
            one = init_block_cache(cfg, sig, batch, max_len,
                                   cfg.compute_jnp_dtype, kv_quant, paged)
            seg.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (periods,) + a.shape), one))
        caches.append(tuple(seg))
    return caches


# ---------------------------------------------------------------------------
# Model init / forward
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=1)
def init_model(key, cfg):
    """Random parameters in ``cfg.param_dtype``. One jitted program, so
    each leaf is drawn and cast in one fused pass: a bf16 model never holds
    a float32 copy of a full-size leaf. Wrap it in ``jax.jit`` with
    ``out_shardings`` to build each leaf straight onto its devices."""
    k_embed, k_head, k_mtp, k_layers = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    params["embed"] = init_embedding(k_embed, cfg.vocab_size, cfg.d_model,
                                     cfg.param_jnp_dtype)
    params["final_norm"] = init_rmsnorm(cfg.d_model, cfg.param_jnp_dtype)
    if not cfg.tie_embeddings:
        params["head"] = init_lm_head(k_head, cfg.d_model, cfg.vocab_size,
                                      cfg.param_jnp_dtype)
    segs = []
    for si, (pattern, periods, start) in enumerate(layer_segments(cfg)):
        seg_params = []
        for pi, sig in enumerate(pattern):
            keys = jax.random.split(
                jax.random.fold_in(k_layers, si * 64 + pi), periods)
            stacked = jax.vmap(lambda k: init_block(k, cfg, sig))(keys)
            seg_params.append(stacked)
        segs.append(tuple(seg_params))
    params["segments"] = segs
    if cfg.mtp_depth > 0:
        params["mtp"] = {
            "proj": dense_init(k_mtp, (2 * cfg.d_model, cfg.d_model),
                               cfg.param_jnp_dtype),
            "block": init_block(jax.random.fold_in(k_mtp, 1), cfg,
                                ("attn", "mlp")),
            "norm": init_rmsnorm(cfg.d_model, cfg.param_jnp_dtype),
        }
    return params


def _segment_scan(seg_params, x, positions, *, cfg, pattern, start, periods,
                  caches=None, cache_pos=None, quant=None,
                  mrope_positions=None, page_table=None,
                  attn_impl: str = "gather", kv_valid_len=None):
    """Scan one segment. Returns (x, new_caches, aux_sums).

    Paged KV pools (dicts with ``k_pages``, stacked ``(periods, P, ...)``)
    ride the scan's CARRY whole, and each period writes and reads its
    layer of them in place through its index in the segment (an ``xs``
    operand): no layer's pool is ever sliced out or restacked, so with the
    caches donated the step updates the pool in place. Dense caches and
    SSM states are per-period ``xs``/``ys`` as before."""
    npos = len(pattern)
    layer_idx = start + jnp.arange(periods * npos).reshape(periods, npos)
    quant_x = (quant.layer_slice(layer_idx) if quant is not None else None)
    caches = caches if caches is not None else (None,) * npos
    paged = tuple(isinstance(c, dict) and "k_pages" in c for c in caches)
    pools = tuple(c if p else None for c, p in zip(caches, paged))
    scanned = tuple(None if p else c for c, p in zip(caches, paged))
    # every layer's kernel walks the same table: its work list, once
    work = (paged_kernel_work(pools[paged.index(True)], page_table,
                              cache_pos, x.shape[1])
            if attn_impl == "pallas" and any(paged) else None)

    def body(carry, xs):
        x, pools = carry
        seg_p, cache_p, q_p, period = xs
        pools, new_caches, auxes = list(pools), [], []
        for pi, sig in enumerate(pattern):
            q_i = (q_p.layer_slice(pi) if q_p is not None else None)
            x, nc, aux = block_apply(
                seg_p[pi], x, positions, cfg=cfg, sig=sig,
                cache=pools[pi] if paged[pi] else cache_p[pi],
                cache_pos=cache_pos, quant=q_i,
                mrope_positions=mrope_positions, page_table=page_table,
                attn_impl=attn_impl, kv_valid_len=kv_valid_len,
                cache_layer=period if paged[pi] else None, attn_work=work)
            if paged[pi]:
                pools[pi], nc = nc, None
            new_caches.append(nc)
            auxes.append(aux.get("moe_lb_loss", jnp.zeros((), jnp.float32)))
        return (x, tuple(pools)), (tuple(new_caches), jnp.stack(auxes).sum())

    body_fn = body
    if cfg.remat != "none":
        body_fn = jax.checkpoint(body,
                                 policy=jax.checkpoint_policies.nothing_saveable
                                 if cfg.remat == "full" else None)

    xs = (tuple(seg_params), scanned, quant_x, jnp.arange(periods))
    (x, pools), (new_caches, aux_per) = jax.lax.scan(body_fn, (x, pools), xs)
    new_caches = tuple(pl if p else c
                       for pl, c, p in zip(pools, new_caches, paged))
    return x, new_caches, aux_per.sum()


def _segment_unrolled(seg_params, x, positions, *, cfg, pattern, start,
                      periods, caches=None, cache_pos=None, quant=None,
                      mrope_positions=None, page_table=None,
                      attn_impl: str = "gather", kv_valid_len=None):
    """Unrolled twin of ``_segment_scan`` for per-layer KV containers.

    A layer's storage container is static program structure (pool dtype,
    int4 lane-packing), so it cannot vary across a ``lax.scan`` — the
    serving path with a per-layer precision profile runs the segment as a
    python loop instead. Caches arrive/leave as per-period LISTS (see
    ``init_cache``); compile cost is O(layers), acceptable for the few-layer
    serving configs the profile path targets."""
    npos = len(pattern)
    new_caches: Tuple[list, ...] = tuple([] for _ in pattern)
    moe_aux = jnp.zeros((), jnp.float32)
    for p in range(periods):
        for pi, sig in enumerate(pattern):
            li = start + p * npos + pi
            q_i = quant.layer_static(li) if quant is not None else None
            c_i = caches[pi][p] if caches is not None else None
            seg_p = jax.tree_util.tree_map(lambda a: a[p], seg_params[pi])
            x, nc, aux = block_apply(
                seg_p, x, positions, cfg=cfg, sig=sig, cache=c_i,
                cache_pos=cache_pos, quant=q_i,
                mrope_positions=mrope_positions, page_table=page_table,
                attn_impl=attn_impl, kv_valid_len=kv_valid_len)
            new_caches[pi].append(nc)
            moe_aux = moe_aux + aux.get("moe_lb_loss",
                                        jnp.zeros((), jnp.float32))
    return x, tuple(list(c) for c in new_caches), moe_aux


def _container_runs(containers, start, periods, npos):
    """Group a segment's periods into contiguous RUNS with an identical
    per-position container signature. Each run can ride one ``lax.scan``
    (static program structure is uniform inside it); a pathological
    alternating profile degenerates to length-1 runs (full unroll).

    Returns ``(runs, sig)`` with ``runs`` a list of ``(p0, p1)`` period
    ranges and ``sig[p]`` the per-position container tuple of period p.
    """
    sig = [tuple(containers[start + p * npos + pi] for pi in range(npos))
           for p in range(periods)]
    runs = []
    p0 = 0
    for p in range(1, periods + 1):
        if p == periods or sig[p] != sig[p0]:
            runs.append((p0, p))
            p0 = p
    return runs, sig


def _run_quant(quant, *, start, npos, p0, p1, sig):
    """Per-position ModelQuant views for one same-container run: Q(I,F)
    arrays stacked ``(run_len,)`` (they ride the scan), containers STATIC
    per position ("fp" positions drop the KV quant — their pools store
    float pages)."""
    out = []
    for pi in range(npos):
        idx = jnp.asarray([start + p * npos + pi for p in range(p0, p1)])
        cont = sig[p0][pi]
        f = lambda a: None if a is None else a[idx]   # noqa: E731
        kv_i, kv_f = f(quant.kv_int), f(quant.kv_frac)
        if cont == "fp":
            kv_i = kv_f = None
            cont = quant.kv_container
        out.append(ModelQuant(f(quant.w_int), f(quant.w_frac),
                              f(quant.a_int), f(quant.a_frac), kv_i, kv_f,
                              cont, kv_scale_mode=quant.kv_scale_mode))
    return tuple(out)


def _segment_scan_grouped(seg_params, x, positions, *, cfg, pattern, start,
                          periods, caches=None, cache_pos=None, quant=None,
                          mrope_positions=None, page_table=None,
                          attn_impl: str = "gather", kv_valid_len=None):
    """Scan-over-layers for **per-layer KV containers**: contiguous
    same-container period runs are scanned (one compiled block body per
    run, so a realistic two-regime ``core.search`` profile costs ~2 bodies
    instead of O(layers)); length-1 runs inline. Caches arrive/leave as
    per-position LISTS of per-run stacked pools (see ``init_cache``).
    Token-identical to ``_segment_unrolled`` — the layer math is the same,
    only the loop structure differs (asserted in tests/test_serve_fast)."""
    npos = len(pattern)
    runs, sig = _container_runs(quant.kv_containers, start, periods, npos)
    new_caches: Tuple[list, ...] = tuple([] for _ in pattern)
    moe_aux = jnp.zeros((), jnp.float32)

    def body(carry, xs):
        x = carry
        seg_p, cache_p, q_p = xs
        new_cs, auxes = [], []
        for pi, bsig in enumerate(pattern):
            c_i = cache_p[pi] if cache_p is not None else None
            x, nc, aux = block_apply(
                seg_p[pi], x, positions, cfg=cfg, sig=bsig, cache=c_i,
                cache_pos=cache_pos, quant=q_p[pi],
                mrope_positions=mrope_positions, page_table=page_table,
                attn_impl=attn_impl, kv_valid_len=kv_valid_len)
            new_cs.append(nc)
            auxes.append(aux.get("moe_lb_loss", jnp.zeros((), jnp.float32)))
        return x, (tuple(new_cs), jnp.stack(auxes).sum())

    body_fn = body
    if cfg.remat != "none":
        body_fn = jax.checkpoint(body,
                                 policy=jax.checkpoint_policies.nothing_saveable
                                 if cfg.remat == "full" else None)

    for ri, (p0, p1) in enumerate(runs):
        q_pos = _run_quant(quant, start=start, npos=npos, p0=p0, p1=p1,
                           sig=sig)
        run_params = tuple(
            jax.tree_util.tree_map(lambda a: a[p0:p1], seg_params[pi])
            for pi in range(npos))
        run_caches = (tuple(caches[pi][ri] for pi in range(npos))
                      if caches is not None else None)
        if p1 - p0 == 1:
            # pathological alternating profile: inline the single period
            first = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            x, (nc_run, aux_run) = body(
                x, (tuple(first(p) for p in run_params),
                    (None if run_caches is None
                     else tuple(first(c) for c in run_caches)),
                    tuple(first(q) for q in q_pos)))
            nc_run = tuple(jax.tree_util.tree_map(lambda a: a[None], nc)
                           for nc in nc_run)
            moe_aux = moe_aux + aux_run
        else:
            xs = (run_params, run_caches, q_pos)
            x, (nc_run, aux_per) = jax.lax.scan(body_fn, x, xs)
            moe_aux = moe_aux + aux_per.sum()
        for pi in range(npos):
            new_caches[pi].append(nc_run[pi])
    return x, tuple(list(c) for c in new_caches), moe_aux


def forward_hidden(params, batch, cfg, *, quant: Optional[ModelQuant] = None,
                   caches=None, cache_pos=None, page_table=None,
                   attn_impl: str = "gather", kv_valid_len=None):
    """Backbone only: returns (hidden_after_final_norm, aux); aux carries
    "caches" when caches were threaded.

    batch: {"tokens": (B,S)} or {"embeds": (B,S,D)} (stub frontends), plus
    optional "positions" (B,S), "mrope_positions" (B,S,3).
    ``cache_pos`` is a scalar (shared decode clock) or (B,) per-sequence
    offsets; ``page_table`` (B, NP) activates paged KV caches;
    ``attn_impl`` ("gather" | "pallas") picks the paged attention backend
    for EVERY chunk shape — decode and bucketed prefill share one routing
    layer (``models.attention.route_paged_attention``);
    ``kv_valid_len`` masks padded bucketed-prefill chunk tails.
    """
    cd = cfg.compute_jnp_dtype
    if "embeds" in batch:
        x = batch["embeds"].astype(cd)
    else:
        x = embed_tokens(params["embed"], batch["tokens"],
                         onehot=cfg.embedding_onehot, compute_dtype=cd)
    B, S = x.shape[0], x.shape[1]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        base = cache_pos if cache_pos is not None else 0
        base = jnp.asarray(base, jnp.int32).reshape(-1, 1)  # scalar or (B,)
        positions = jnp.broadcast_to(base + jnp.arange(S)[None, :], (B, S))
    mrope_positions = batch.get("mrope_positions")

    if quant is not None and quant.a_int is not None:
        x = fake_quant(x, quant.a_int[0], quant.a_frac[0])  # embed output
    x = constrain(x, "dp", None, None)   # batch over ("pod","data")

    new_caches, moe_aux = [], jnp.zeros((), jnp.float32)
    if quant is not None and quant.kv_containers is not None:
        # per-layer KV containers: scan contiguous same-container runs
        # (kv_unroll forces the fully unrolled reference path)
        seg_fn = _segment_unrolled if quant.kv_unroll \
            else _segment_scan_grouped
    else:
        seg_fn = _segment_scan
    for si, (pattern, periods, start) in enumerate(layer_segments(cfg)):
        seg_cache = caches[si] if caches is not None else None
        x, nc, aux = seg_fn(
            params["segments"][si], x, positions, cfg=cfg, pattern=pattern,
            start=start, periods=periods, caches=seg_cache,
            cache_pos=cache_pos, quant=quant, mrope_positions=mrope_positions,
            page_table=page_table, attn_impl=attn_impl,
            kv_valid_len=kv_valid_len)
        new_caches.append(nc)
        moe_aux = moe_aux + aux

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"moe_lb_loss": moe_aux,
               "caches": (new_caches if caches is not None else None)}


def forward(params, batch, cfg, *, quant: Optional[ModelQuant] = None,
            caches=None, cache_pos=None, page_table=None,
            attn_impl: str = "gather", kv_valid_len=None):
    """Returns (hidden, logits, new_caches, aux)."""
    x, aux = forward_hidden(params, batch, cfg, quant=quant, caches=caches,
                            cache_pos=cache_pos, page_table=page_table,
                            attn_impl=attn_impl, kv_valid_len=kv_valid_len)
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = lm_head(params.get("head"), x, tied_table=tied)
    return x, logits, aux.pop("caches"), aux


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------
def _head_weight(params, cfg):
    return (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"]["kernel"])


def train_loss(params, batch, cfg, *, quant=None, lb_coeff=0.01):
    if cfg.loss_chunk > 0:
        # fused head+CE over seq chunks: the (B,S,V) logits never materialize
        hidden, aux = forward_hidden(params, batch, cfg, quant=quant)
        loss = chunked_ce_loss(hidden, _head_weight(params, cfg),
                               batch["labels"], chunk=cfg.loss_chunk,
                               mask=batch.get("mask"))
    else:
        hidden, logits, _, aux = forward(params, batch, cfg, quant=quant)
        loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
    metrics = {"ce_loss": loss, "moe_lb_loss": aux["moe_lb_loss"]}
    if cfg.num_experts:
        loss = loss + lb_coeff * aux["moe_lb_loss"]
    if cfg.mtp_depth > 0:
        mtp = params["mtp"]
        cd = cfg.compute_jnp_dtype
        nxt = embed_tokens(params["embed"], batch["tokens"][:, 1:],
                           onehot=cfg.embedding_onehot, compute_dtype=cd)
        h = jnp.concatenate([hidden[:, :-1], nxt], axis=-1) @ \
            mtp["proj"].astype(cd)
        pos = jnp.broadcast_to(jnp.arange(h.shape[1])[None, :], h.shape[:2])
        h, _, _ = block_apply(mtp["block"], h, pos, cfg=cfg,
                              sig=("attn", "mlp"))
        h = rmsnorm(mtp["norm"], h, cfg.norm_eps)
        if cfg.loss_chunk > 0:
            mtp_loss = chunked_ce_loss(h, _head_weight(params, cfg),
                                       batch["labels"][:, 1:],
                                       chunk=cfg.loss_chunk)
        else:
            tied = params["embed"]["table"] if cfg.tie_embeddings else None
            mtp_logits = lm_head(params.get("head"), h, tied_table=tied)
            mtp_loss = cross_entropy(mtp_logits, batch["labels"][:, 1:])
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    metrics["loss"] = loss
    return loss, metrics


def prefill(params, batch, cfg, *, quant=None, max_len):
    """Run the prompt through the model, building caches. Returns
    (logits_last, caches, next_pos)."""
    B, S = (batch["tokens"].shape if "tokens" in batch
            else batch["embeds"].shape[:2])
    caches = init_cache(cfg, B, max_len, quant)
    _, logits, caches, _ = forward(params, batch, cfg, quant=quant,
                                   caches=caches, cache_pos=0)
    return logits[:, -1], caches, S


def decode_step(params, tokens, pos, caches, cfg, *, quant=None,
                page_table=None, attn_impl="gather"):
    """One decode step. tokens: (B,) int32; pos: scalar or (B,) int32
    current lengths. Returns (logits (B,V), new_caches)."""
    batch = {"tokens": tokens[:, None]}
    _, logits, caches, _ = forward(params, batch, cfg, quant=quant,
                                   caches=caches, cache_pos=pos,
                                   page_table=page_table, attn_impl=attn_impl)
    return logits[:, 0], caches
