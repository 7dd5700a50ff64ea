"""Paged quantized KV-cache subsystem (vLLM-style block tables, paper bits).

The dense serving cache allocates ``batch x max_len`` up front, so HBM scales
with the worst-case request. Here the cache is a **pool of fixed-size pages**
shared by all sequences; a per-sequence **page table** maps logical token
positions to pool pages, and pages are allocated/freed per request by a
host-side free-list allocator. Combined with the paper's reduced-precision
storage, a page holds its tokens in the quantized container:

* ``container="int8"``  — int8 integer grid (Q(I,F) with I+F <= 8),
* ``container="int4"``  — 4-bit grid lane-packed into int32 words along the
  head dim via :func:`repro.core.qtensor.pack_bits` (true N/32 footprint),
* ``container="fp"``    — unquantized pages in the compute dtype (kv_bits=0).

Each page additionally carries a **per-page dequant scale** (value = grid *
scale). With a per-layer Q(I,F) policy the scale is uniform across pages of a
layer (2^-F), but the storage/kernels are per-page so calibrated or dynamic
per-page scaling drops in without a layout change.

Page 0 is **reserved as a scratch page**: idle batch slots keep writing their
stale token somewhere, and pointing their page-table rows at page 0 keeps
those writes off live data. The allocator therefore never hands out page 0.

Device-side ops here are pure jnp (scatter/gather) and serve as the oracle
for the Pallas kernel in ``repro.kernels.paged_kv_attention``, which gathers
pages via scalar-prefetch DMA and dequantizes in VMEM. The serving
integration (``models.attention.gqa_apply``) routes per ``attn_impl``:
``"gather"`` (default) attends through the jnp path — bitwise-identical to
the dense layout (same online-softmax chunk order), the reference mode the
equivalence tests rely on — while ``"pallas"`` sends S=1 decode through the
kernel (interpret-mode on CPU, compiled on TPU; per-page accumulation order,
so equal only to float tolerance).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .fixedpoint import format_params
from .qtensor import pack_bits, unpack_bits, values_per_word

SCRATCH_PAGE = 0

_CONTAINERS = ("int8", "int4", "fp")


class OutOfPagesError(RuntimeError):
    """A request's page demand cannot be backed by the pool.

    Raised *before* any page is handed out (admission preflight) or when the
    free list empties mid-run, always with the counts needed to size
    ``--num-pages`` correctly. ``reserved`` separates pages *promised* to
    live requests but not yet written (admission reservations) from
    ``written`` pages already holding live KV — under prefix sharing a
    request's demand is suffix-only, so deferral decisions need the split,
    not just the free count. ``evictable`` counts unreferenced prefix-cache
    pages that eviction could reclaim, ``requantizable`` the cold cached
    pages the quant-adaptation tier could narrow in place (freeing their
    device pages without a host round trip — why an adapt-enabled pool
    admits more), ``host_pages`` the pages currently parked in the
    host-memory tier (demoted prefixes + preempted requests) — together
    the full device/adapt/host/evictable inventory.
    """

    def __init__(self, *, needed: int, free: int, total: int,
                 rid: Optional[int] = None, reserved: int = 0,
                 written: int = 0, evictable: int = 0,
                 requantizable: int = 0, host_pages: int = 0):
        self.needed, self.free, self.total, self.rid = needed, free, total, rid
        self.reserved, self.written = reserved, written
        self.evictable = evictable
        self.requantizable = requantizable
        self.host_pages = host_pages
        who = f"request {rid}" if rid is not None else "allocation"
        extra = ""
        if reserved or written or evictable or requantizable or host_pages:
            extra = (f" [{written} written, {reserved} reserved-unwritten, "
                     f"{evictable} evictable-cached, "
                     f"{requantizable} requantizable, "
                     f"{host_pages} host-tier]")
        super().__init__(
            f"KV page pool cannot back {who}: needs {needed} page(s), "
            f"{free} free of {total} usable (page 0 is scratch){extra}; "
            f"raise --num-pages, shrink --max-new, or lower concurrency")


@dataclasses.dataclass(frozen=True)
class PagedCacheSpec:
    """What a model needs to know to build paged caches: pool geometry.

    ``num_pages`` includes the reserved scratch page 0. Every attention layer
    gets its own pool of this geometry (layers see the same page table, so
    one host-side allocator serves the whole model).
    """

    page_size: int
    num_pages: int

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")


@dataclasses.dataclass(frozen=True)
class PagedKVLayout:
    """Static shape/dtype description of one layer's paged KV pool."""

    num_pages: int          # pool pages, including the reserved scratch page
    page_size: int          # tokens per page
    num_kv_heads: int
    head_dim: int
    container: str = "int8"
    dtype: object = jnp.float32  # compute/storage dtype for container="fp"

    def __post_init__(self):
        if self.container not in _CONTAINERS:
            raise ValueError(f"container must be one of {_CONTAINERS}, "
                             f"got {self.container!r}")
        if self.container == "int4" and self.head_dim % values_per_word(4):
            raise ValueError("int4 packing needs head_dim % 8 == 0, got "
                             f"{self.head_dim}")
        if self.num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")

    @property
    def bits(self) -> int:
        return {"int8": 8, "int4": 4}.get(self.container, 0)

    @property
    def store_head_dim(self) -> int:
        """Last-dim extent of the stored page (packed for int4)."""
        if self.container == "int4":
            return self.head_dim // values_per_word(4)
        return self.head_dim

    @property
    def store_dtype(self):
        return {"int8": jnp.int8, "int4": jnp.int32,
                "fp": self.dtype}[self.container]

    @property
    def page_bytes(self) -> int:
        """Stored bytes of ONE page of ONE of k/v (scales excluded)."""
        itemsize = jnp.dtype(self.store_dtype).itemsize
        return (self.page_size * self.num_kv_heads * self.store_head_dim
                * itemsize)

    def tokens_to_pages(self, tokens: int) -> int:
        return -(-tokens // self.page_size)


def max_pages_per_seq(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


# ---------------------------------------------------------------------------
# Host-side page allocator
# ---------------------------------------------------------------------------
class PageAllocator:
    """Refcounted free-list allocator over pages 1..num_pages-1 (0: scratch).

    Pure host-side bookkeeping: the device pool is preallocated; "allocating"
    a page just hands out an index. Fragmentation is free — any page serves
    any (sequence, logical-block) slot via the page table.

    **Refcounts** are what make prefix sharing safe: ``alloc`` returns a page
    at refcount 1, ``incref`` adds a reference (a sharer's page table aliasing
    the page, or the prefix cache retaining it), and ``free`` RELEASES one
    reference per page — the page returns to the free list only when its
    count reaches zero, so no caller can ever free a page out from under a
    sharer, and releasing a page twice from the same owner raises.

    ``reclaim`` (optional callable ``n -> pages_freed``) is invoked when
    the free list empties mid-``alloc`` — the prefix cache registers its
    eviction here, which under a tiered page store DEMOTES unreferenced
    cached prefixes to host memory (destructive LRU drop otherwise), so
    pool pressure recycles pages instead of failing the allocation.
    ``pressure`` is a list of further callbacks (same ``n -> freed``
    contract, ``add_pressure``) tried in order after ``reclaim`` — an
    extension point for additional reclaimers (e.g. future async offload
    writeback); nothing in the serving stack registers one today.
    ``host_inventory`` (optional zero-arg callable -> page count) lets
    :class:`OutOfPagesError` report the host-tier inventory alongside the
    device counts; ``requant_inventory`` does the same for the pages the
    quant-adaptation tier could still narrow in place.

    ``metrics`` (optional :class:`repro.runtime.telemetry.MetricsRegistry`)
    counts allocations ("alloc.allocs") and pressure invocations
    ("alloc.reclaims"), and registers a live "alloc.free_pages" gauge —
    free-list occupancy readable from any snapshot.
    """

    def __init__(self, num_pages: int, *, metrics=None):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self.reclaim = None  # optional: n_pages -> n_freed (LRU eviction)
        self.pressure: List = []      # further n -> n_freed callbacks
        self.host_inventory = None    # optional: () -> host-tier page count
        self.requant_inventory = None  # optional: () -> requantizable pages
        if metrics is None:
            from ..runtime.telemetry import MetricsRegistry
            metrics = MetricsRegistry()
        self.metrics = metrics
        self._c_allocs = metrics.counter("alloc.allocs")
        self._c_reclaims = metrics.counter("alloc.reclaims")
        metrics.register_gauge("alloc.free_pages", lambda: len(self._free))

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_usable(self) -> int:
        return self.num_pages - 1

    def refcount(self, page: int) -> int:
        """Live references on ``page`` (0 = free / never allocated)."""
        return self._refs.get(page, 0)

    def check(self, needed: int, *, rid: Optional[int] = None) -> None:
        """Preflight: raise OutOfPagesError unless ``needed`` pages are free.

        Deliberately CONSERVATIVE: only the free list is consulted, not the
        ``reclaim``/``pressure`` hooks — pages eviction could recover don't
        count here (the serving admission path does its own reclaim-aware
        accounting).
        """
        if needed > self.num_free:
            raise OutOfPagesError(needed=needed, free=self.num_free,
                                  total=self.num_usable, rid=rid,
                                  requantizable=self.requant_pages(),
                                  host_pages=self.host_pages())

    def host_pages(self) -> int:
        """Pages currently parked in the host tier (0 without a tier)."""
        return int(self.host_inventory()) if self.host_inventory else 0

    def requant_pages(self) -> int:
        """Cold cached pages the quant tier could narrow in place (0
        without an adaptation tier)."""
        return (int(self.requant_inventory())
                if self.requant_inventory else 0)

    def add_pressure(self, fn) -> None:
        """Register an ``n_pages -> n_freed`` pressure callback (tried after
        ``reclaim`` when the free list empties mid-``alloc``)."""
        self.pressure.append(fn)

    def _apply_pressure(self, needed: int) -> None:
        if self._free:
            return
        self._c_reclaims.inc()
        if self.reclaim is not None:
            self.reclaim(needed)
        for fn in self.pressure:
            if self._free:
                return
            fn(needed)

    def alloc(self) -> int:
        if not self._free:
            self._apply_pressure(1)
        if not self._free:
            raise OutOfPagesError(needed=1, free=0, total=self.num_usable,
                                  requantizable=self.requant_pages(),
                                  host_pages=self.host_pages())
        page = self._free.pop()
        self._refs[page] = 1
        self._c_allocs.inc()
        return page

    def incref(self, page: int) -> None:
        if self._refs.get(page, 0) <= 0:
            raise ValueError(f"incref of unallocated page {page}")
        self._refs[page] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Release ONE reference per page; recycle pages that hit zero."""
        for p in pages:
            if not (0 < p < self.num_pages):
                raise ValueError(f"freeing invalid page id {p}")
            refs = self._refs.get(p, 0)
            if refs <= 0:
                raise ValueError(f"double free of page {p}")
            if refs == 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = refs - 1


# ---------------------------------------------------------------------------
# Device-side pool ops (pure jnp; oracle for the Pallas kernel)
# ---------------------------------------------------------------------------
def init_paged_pool(layout: PagedKVLayout) -> Dict[str, jnp.ndarray]:
    """One layer's paged pool: k/v pages + per-page dequant scales."""
    shape = (layout.num_pages, layout.page_size, layout.num_kv_heads,
             layout.store_head_dim)
    return {
        "k_pages": jnp.zeros(shape, layout.store_dtype),
        "v_pages": jnp.zeros(shape, layout.store_dtype),
        "k_scale": jnp.ones((layout.num_pages,), jnp.float32),
        "v_scale": jnp.ones((layout.num_pages,), jnp.float32),
    }


def _quant_grid(x, int_bits, frac_bits):
    """float (..., hd) -> (integer grid float array, reciprocal scale)."""
    scale, qmin, qmax = format_params(int_bits, frac_bits)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * scale), qmin, qmax)
    return q, 1.0 / scale


def _pack_grid(q, bits):
    packed, _ = pack_bits(q.astype(jnp.int32), bits)
    return packed


def paged_update(pool, k_new, v_new, page_table, pos, *, page_size: int,
                 container: str = "int8", int_bits=None, frac_bits=None,
                 valid_len=None, scale_mode: str = "static", layer=None):
    """Append S new tokens per sequence to the paged pool.

    k_new/v_new: (B, S, KV, hd) float; page_table: (B, NP) int32;
    pos: scalar or (B,) int32 — the logical position of the FIRST new token
    per sequence. ``valid_len`` (scalar or (B,) int32, optional) marks only
    the first ``valid_len`` of the S tokens as real: the rest are padding
    (bucketed prefill pads chunks up to a power-of-two) and their writes are
    redirected to the scratch page, so a padded chunk can never clobber live
    pages (a padded tail position can even alias back into the sequence's
    last page once ``pos + S`` exceeds the page-table span, because the
    block gather clamps). Returns the updated pool dict.

    ``scale_mode`` picks the dequant-scale calibration for int containers:

    * ``"static"`` (default) — the layer's Q(I,F) grid scale, uniform across
      pages (bitwise-reproducible; the reference mode).
    * ``"page"``  — **dynamic per-page max-abs calibration**: each touched
      page's scale is the running max-abs of the values written to it over
      the container's symmetric grid, so small-magnitude layers get a far
      finer step than the static Q(I,F) grid. When a write raises a page's
      scale, the page's existing grid values are requantized in place
      (gather -> rescale -> scatter of just the touched pages), so earlier
      tokens stay correct under the new scale.

    ``layer`` (a scalar, possibly traced) addresses one layer of a STACKED
    pool ``(L, P, ps, KV, hdw)``: every read and write carries the index,
    so the pool is scattered into in place and no layer is sliced out
    (the layer scan carries the stacked pools, ``models.transformer``).
    ``None`` takes ``pool`` as one layer's ``(P, ps, KV, hdw)`` pool.

    Distinct sequences must map to distinct pages (the allocator guarantees
    it; prefix-shared pages are never written by sharers), so the scatter is
    collision-free except on the shared scratch page, where any write order
    is acceptable.
    """
    at = _layer_index(layer)
    B, S = k_new.shape[0], k_new.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    blocks = positions // page_size                       # (B, S)
    blocks = jnp.minimum(blocks, page_table.shape[1] - 1)
    offsets = positions % page_size                       # (B, S)
    pids = jnp.take_along_axis(page_table, blocks, axis=1)  # (B, S)
    valid = jnp.ones((B, S), bool)
    if valid_len is not None:
        vl = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32).reshape(-1),
                              (B,))
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < vl[:, None]
        pids = jnp.where(valid, pids, SCRATCH_PAGE)

    if container == "fp":
        # fp writes store raw floats under a UNIT page scale. A recycled
        # page can carry a stale non-unit scale from a quant-tier restore
        # (widen_blob keeps the parked grid + scale for fp pools), so the
        # page's first write (offset 0) resets its scale; writes at higher
        # offsets extend a page this owner already reset (or a CoW copy,
        # which copy_pool_pages folds to unit scale).
        first = jnp.where(offsets == 0, pids, SCRATCH_PAGE)
        return {
            "k_pages": pool["k_pages"].at[at + (pids, offsets)].set(
                k_new.astype(pool["k_pages"].dtype)),
            "v_pages": pool["v_pages"].at[at + (pids, offsets)].set(
                v_new.astype(pool["v_pages"].dtype)),
            "k_scale": pool["k_scale"].at[at + (first,)].set(1.0),
            "v_scale": pool["v_scale"].at[at + (first,)].set(1.0),
        }

    if scale_mode == "page":
        return _paged_update_page_scale(
            pool, k_new, v_new, page_table, pos, pids, offsets, valid,
            page_size=page_size, container=container, at=at)
    if scale_mode != "static":
        raise ValueError(f"scale_mode must be 'static' or 'page', "
                         f"got {scale_mode!r}")

    k_q, rscale = _quant_grid(k_new, int_bits, frac_bits)
    v_q, _ = _quant_grid(v_new, int_bits, frac_bits)
    if container == "int4":
        k_q, v_q = _pack_grid(k_q, 4), _pack_grid(v_q, 4)
    sc = jnp.broadcast_to(jnp.asarray(rscale, jnp.float32), pids.shape)
    return {
        "k_pages": pool["k_pages"].at[at + (pids, offsets)].set(
            k_q.astype(pool["k_pages"].dtype)),
        "v_pages": pool["v_pages"].at[at + (pids, offsets)].set(
            v_q.astype(pool["v_pages"].dtype)),
        "k_scale": pool["k_scale"].at[at + (pids,)].set(sc),
        "v_scale": pool["v_scale"].at[at + (pids,)].set(sc),
    }


def _layer_index(layer):
    """Index prefix that addresses one layer of a stacked pool (empty for
    a single layer's pool)."""
    return () if layer is None else (layer,)


_SCALE_EPS = 2.0 ** -20   # floor for all-zero chunks (avoids 0-division)


def _paged_update_page_scale(pool, k_new, v_new, page_table, pos, pids,
                             offsets, valid, *, page_size: int,
                             container: str, at=()):
    """Per-page max-abs calibrated write (``scale_mode="page"``).

    Touched pages form a contiguous block range per row (positions are
    contiguous), so at most ``ceil((S-1)/ps) + 2`` pages per row are
    gathered, requantized under the (possibly raised) new scale, scattered
    back, and only then receive the new tokens. Pages past the row's table
    span and fully-invalid slots redirect to the scratch page, whose content
    is never read un-masked — duplicate scratch scatters are don't-care.

    SHARING CONTRACT: a scale raise rewrites the touched pages' existing
    grids IN PLACE, which silently changes the dequant values any aliased
    reader sees — so every page in the written block range must be at
    refcount 1. The serving layer enforces this (in page-scale mode the
    prefix cache never retains a page the owner will keep writing, and
    ``BatchedServer._ensure_page`` asserts refcount 1 on the write-target
    block); static-scale mode has no such hazard because old grids are
    never rewritten.

    ``at`` is the layer index prefix of a stacked pool (``paged_update``):
    the touched pages are gathered with it before anything is scattered,
    so the whole update stays in place.
    """
    B, S = k_new.shape[0], k_new.shape[1]
    ps, NP = page_size, page_table.shape[1]
    bits = {"int8": 8, "int4": 4}[container]
    qmax = float(2 ** (bits - 1) - 1)
    hd = k_new.shape[-1]

    nb = (S - 1) // ps + 2                      # static touched-block bound
    blk_first = pos // ps                       # (B,)
    # tokens past the page-table span clamp into the LAST page in static
    # mode (harmless there: uniform scale, stale rewrite). Under per-page
    # scales that rewrite would disagree with the page's stored scale, so
    # out-of-span tokens redirect to the scratch page instead.
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    in_tok = positions // ps < NP               # (B, S)
    pids = jnp.where(in_tok, pids, SCRATCH_PAGE)
    blocks_nb = blk_first[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :]
    in_span = blocks_nb < NP
    pids_nb = jnp.take_along_axis(page_table,
                                  jnp.minimum(blocks_nb, NP - 1), axis=1)
    pids_nb = jnp.where(in_span, pids_nb, SCRATCH_PAGE)   # (B, nb)

    # a block is "fresh" iff this chunk's first write to it lands at offset
    # 0 — its prior content (freed-page garbage) must not pin the old scale
    fresh = (jnp.arange(nb, dtype=jnp.int32)[None, :] > 0) | \
        ((pos % ps) == 0)[:, None]              # (B, nb)

    rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, S))
    lb = jnp.clip(positions // ps - blk_first[:, None], 0, nb - 1)  # (B, S)

    def _page_scales(x_new, old_scale):
        amax = jnp.max(jnp.abs(x_new.astype(jnp.float32)),
                       axis=(-2, -1))           # (B, S) per-token max-abs
        amax = jnp.where(valid & in_tok, amax, 0.0)
        need = jnp.zeros((B, nb), jnp.float32).at[rows, lb].max(amax) / qmax
        old = old_scale[at + (pids_nb,)]        # (B, nb)
        new = jnp.maximum(jnp.where(fresh, 0.0, old),
                          jnp.maximum(need, _SCALE_EPS))
        ratio = jnp.where(fresh, 1.0, old / new)
        return new, ratio

    def _requant_and_write(pages, scale, x_new, new_scale, ratio):
        got = pages[at + (pids_nb,)]            # (B, nb, ps, KV, hdw)
        if container == "int4":
            got = unpack_bits(got, 4, hd)
        re = jnp.round(got.astype(jnp.float32)
                       * ratio[:, :, None, None, None])
        re = jnp.clip(re, -qmax, qmax)
        sc_tok = new_scale[rows, lb]            # (B, S) per-token page scale
        q = jnp.clip(jnp.round(x_new.astype(jnp.float32)
                               / sc_tok[:, :, None, None]), -qmax, qmax)
        if container == "int4":
            re = _pack_grid(re, 4)
            q = _pack_grid(q, 4)
        pages = pages.at[at + (pids_nb,)].set(re.astype(pages.dtype))
        pages = pages.at[at + (pids, offsets)].set(q.astype(pages.dtype))
        return pages, scale.at[at + (pids_nb,)].set(new_scale)

    k_ns, k_ratio = _page_scales(k_new, pool["k_scale"])
    v_ns, v_ratio = _page_scales(v_new, pool["v_scale"])
    k_pages, k_scale = _requant_and_write(pool["k_pages"], pool["k_scale"],
                                          k_new, k_ns, k_ratio)
    v_pages, v_scale = _requant_and_write(pool["v_pages"], pool["v_scale"],
                                          v_new, v_ns, v_ratio)
    return {"k_pages": k_pages, "v_pages": v_pages,
            "k_scale": k_scale, "v_scale": v_scale}


def paged_gather(pool, page_table, *, container: str = "int8",
                 head_dim: Optional[int] = None, dtype=jnp.float32,
                 layer=None):
    """Materialize the logical dense cache view (B, NP*ps, KV, hd) in float.

    Gathers each sequence's pages and dequantizes with the per-page scales.
    This is the oracle/integration path — the Pallas kernel does the same
    gather page-by-page in VMEM without ever materializing the dense view.
    ``layer`` addresses one layer of a stacked pool, as in ``paged_update``.
    """
    at = _layer_index(layer)
    kg = pool["k_pages"][at + (page_table,)]      # (B, NP, ps, KV, hdw)
    vg = pool["v_pages"][at + (page_table,)]
    ks = pool["k_scale"][at + (page_table,)]      # (B, NP)
    vs = pool["v_scale"][at + (page_table,)]
    B, NP, ps, KV = kg.shape[:4]

    if container == "int4":
        assert head_dim is not None
        kg = unpack_bits(kg, 4, head_dim)
        vg = unpack_bits(vg, 4, head_dim)
    # per-page scales apply to every container; float-page writers keep
    # their scales at 1.0
    k = (kg.astype(jnp.float32) * ks[:, :, None, None, None]).astype(dtype)
    v = (vg.astype(jnp.float32) * vs[:, :, None, None, None]).astype(dtype)
    hd = k.shape[-1]
    return (k.reshape(B, NP * ps, KV, hd), v.reshape(B, NP * ps, KV, hd))


def copy_pool_pages(pool, src: int, dst: int, *, page_axis: int = 0):
    """Copy one page's stored bytes + scales ``src -> dst`` (copy-on-write).

    The prefix cache calls this when a request diverges *inside* a partially
    shared page: the sharer gets a private copy to extend while the cached
    source page stays byte-identical for its other readers. ``page_axis``
    is 0 for a single layer's pool and 1 for the (periods, NP, ...) stacked
    pools the segmented scan carries.

    For FP pools the source scale is folded into the copied floats and the
    copy gets a unit scale: the copier extends the page with fresh fp
    writes, which store raw floats under a unit page scale, while the
    source may be a quant-tier restore whose non-unit scale must keep
    applying to the untouched original (``page_store.widen_blob``). Int
    pools copy bytes + scales verbatim (extension writes there recalibrate
    against the page scale explicitly).
    """
    idx = (slice(None),) * page_axis

    def cp(a):
        return a.at[idx + (dst,)].set(a[idx + (src,)])

    if pool_container(pool) == "fp":
        def fold(pages, scale):
            s = scale[idx + (src,)]
            vals = (pages[idx + (src,)].astype(jnp.float32)
                    * s[..., None, None, None])
            return (pages.at[idx + (dst,)].set(vals.astype(pages.dtype)),
                    scale.at[idx + (dst,)].set(1.0))

        k_pages, k_scale = fold(pool["k_pages"], pool["k_scale"])
        v_pages, v_scale = fold(pool["v_pages"], pool["v_scale"])
        return {"k_pages": k_pages, "v_pages": v_pages,
                "k_scale": k_scale, "v_scale": v_scale}

    return {"k_pages": cp(pool["k_pages"]), "v_pages": cp(pool["v_pages"]),
            "k_scale": cp(pool["k_scale"]), "v_scale": cp(pool["v_scale"])}


def pool_bytes(pool) -> int:
    """True stored bytes of one layer's pool (pages + scales)."""
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(pool))


def pool_container(pool) -> str:
    """Container name of a pool dict, inferred from the stored dtype."""
    dt = pool["k_pages"].dtype
    if jnp.issubdtype(dt, jnp.floating):
        return "fp"
    return "int8" if dt == jnp.dtype(jnp.int8) else "int4"


# ---------------------------------------------------------------------------
# Full-model cache traversal (used by the tiered page store + benchmarks)
# ---------------------------------------------------------------------------
def iter_kv_pools(caches):
    """Yield ``(pool_dict, page_axis)`` for every paged attention pool in a
    full-model cache structure (``models.transformer.init_cache``), in a
    DETERMINISTIC traversal order.

    Handles all three layouts: stacked ``(periods, NP, ...)`` entries and the
    per-run stacked lists of the grouped per-layer-profile scan (both
    ``page_axis=1``), and the per-period unstacked dicts of the fully
    unrolled profile path (``page_axis=0``). Non-paged entries (dense KV
    slabs, SSM states) are skipped.
    """
    for seg in caches:
        for entry in seg:
            for d in (entry if isinstance(entry, list) else [entry]):
                if isinstance(d, dict) and "k_pages" in d:
                    yield d, (1 if d["k_pages"].ndim == 5 else 0)


def map_kv_pools(caches, fn):
    """Rebuild a full-model cache structure, replacing every paged pool dict
    with ``fn(pool, page_axis)``; non-pool entries pass through unchanged.
    Traversal order matches :func:`iter_kv_pools`."""

    def one(d):
        if isinstance(d, dict) and "k_pages" in d:
            return fn(d, 1 if d["k_pages"].ndim == 5 else 0)
        return d

    new_caches = []
    for seg in caches:
        seg_new = []
        for entry in seg:
            if isinstance(entry, list):
                seg_new.append([one(d) for d in entry])
            else:
                seg_new.append(one(entry))
        new_caches.append(tuple(seg_new))
    return new_caches


def caches_kv_bytes(caches) -> Dict[str, int]:
    """Device at-rest bytes of every paged pool, split per container — one
    half of the device/host inventory the tiered page store reports."""
    out: Dict[str, int] = {}
    for pool, _ in iter_kv_pools(caches):
        cont = pool_container(pool)
        out[cont] = out.get(cont, 0) + pool_bytes(pool)
    return out
