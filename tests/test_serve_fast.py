"""Serving hot-path tests: bucketed + batched prefill identity, unified
kernel-routed attention (prefill AND decode), and admission preflight.

* Bucketed chunked prefill must produce token-identical output to the
  slot-granular (token-at-a-time) reference prefill across bucket
  boundaries, at kv-bits {0, 8, 4}.
* Multi-request BATCHED prefill (same-bucket rows stacked into one
  [n, bucket] forward) must be token-identical to one-at-a-time bucketed
  prefill at kv-bits {0, 8, 4}, with strictly fewer forwards; an
  OutOfPagesError mid-batch rolls back every partially admitted row.
* ``attn_impl="pallas"`` (kernels.paged_kv_attention, interpret mode on
  CPU) must match the jnp gather path on fragmented page tables to float
  tolerance for BOTH chunk shapes — S=1 decode and S>1 prefill chunks
  (partial last pages, padded tails, mixed per-layer profiles); the
  kernel's per-page online softmax reorders accumulation, so the contract
  is allclose, not bitwise.
* Paged admission preflights worst-case page demand and raises
  ``OutOfPagesError`` with counts instead of dying mid-prefill.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.paged_kv import OutOfPagesError, PageAllocator
from repro.launch.serve import BatchedServer, Request, _pow2_bucket
from repro.models.attention import (KVQuantSpec, gqa_apply, init_gqa,
                                    init_paged_kv_cache, paged_cache_update)
from repro.models.transformer import init_model

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2-72b")
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_pow2_bucket():
    assert [_pow2_bucket(n, 16) for n in (1, 2, 3, 7, 8, 9, 16, 17, 40)] \
        == [1, 2, 4, 8, 8, 16, 16, 16, 16]


# ---------------------------------------------------------------------------
# Bucketed prefill == stepwise prefill, token for token
# ---------------------------------------------------------------------------
# Prompt lengths straddle the bucket-8 boundaries: 1 (no prefill at all),
# bucket-1, bucket, bucket+1, sub-bucket, and multi-chunk (21 -> chunks of
# 16-capped bucket 8: 8 + 8 + 4).
_BUCKET_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)

def mk():
    rng = np.random.default_rng(7)
    lens = [1, 7, 8, 9, 3, 21]
    return [Request(i, rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    5 + (i % 3)) for i, L in enumerate(lens)]

for kv_bits in (0, 8, 4):
    ref = BatchedServer(cfg, params, batch_size=3, max_len=32,
                        kv_bits=kv_bits, page_size=8, prefill="stepwise")
    out_ref = ref.run(mk())
    fast = BatchedServer(cfg, params, batch_size=3, max_len=32,
                         kv_bits=kv_bits, page_size=8, prefill="bucketed",
                         prefill_bucket=8)
    out_fast = fast.run(mk())
    for a, b in zip(out_ref, out_fast):
        assert a.out == b.out, (kv_bits, a.rid, a.out, b.out)
    assert all(r.done for r in out_fast)
    # the whole point: O(prompt) whole-batch forwards -> O(prompt/bucket)
    assert fast.prefill_forwards < ref.prefill_forwards, (
        fast.prefill_forwards, ref.prefill_forwards)
    assert fast.allocator.num_free == fast.allocator.num_usable
    print(f"kv_bits={kv_bits} identical "
          f"({ref.prefill_forwards} -> {fast.prefill_forwards} prefill fwd)")
print("BUCKETED_IDENTITY_OK")
"""


def test_bucketed_prefill_matches_stepwise():
    """Bucketed chunked prefill == token-at-a-time prefill, token for token,
    across bucket boundaries at kv-bits {0, 8, 4}.

    Runs in a subprocess with single-threaded XLA: multi-threaded XLA:CPU
    GEMMs are not bitwise deterministic under thread contention, and exact
    argmax token identity needs bitwise-equal logits."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")])
    res = subprocess.run([sys.executable, "-c", _BUCKET_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "BUCKETED_IDENTITY_OK" in res.stdout


# ---------------------------------------------------------------------------
# Batched prefill == one-at-a-time bucketed prefill, token for token
# ---------------------------------------------------------------------------
# Lens stack a same-bucket first wave (three 9s + a 5 into one admission
# cycle at batch 4), a multi-chunk prompt (21), and a straggler — so the
# trace exercises stacked [n, bucket] forwards, mixed-bucket cycles, AND
# later single-row cycles, all of which must be bitwise-neutral per row.
_BATCHED_PREFILL_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)

def mk():
    rng = np.random.default_rng(7)
    lens = [9, 9, 9, 5, 21, 9]
    return [Request(i, rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    5 + (i % 3)) for i, L in enumerate(lens)]

for kv_bits in (0, 8, 4):
    seq = BatchedServer(cfg, params, batch_size=4, max_len=32,
                        kv_bits=kv_bits, page_size=8, prefill="bucketed",
                        prefill_bucket=8, prefill_batch=1)
    out_seq = seq.run(mk())
    bat = BatchedServer(cfg, params, batch_size=4, max_len=32,
                        kv_bits=kv_bits, page_size=8, prefill="bucketed",
                        prefill_bucket=8, prefill_batch=4)
    out_bat = bat.run(mk())
    for a, b in zip(out_seq, out_bat):
        assert a.out == b.out, (kv_bits, a.rid, a.out, b.out)
    assert all(r.done for r in out_bat)
    # the whole point: same-bucket rows share forwards
    assert bat.prefill_forwards < seq.prefill_forwards, (
        bat.prefill_forwards, seq.prefill_forwards)
    assert bat.allocator.num_free == bat.allocator.num_usable
    print(f"kv_bits={kv_bits} identical "
          f"({seq.prefill_forwards} -> {bat.prefill_forwards} prefill fwd)")
print("BATCHED_PREFILL_IDENTITY_OK")
"""


def test_batched_prefill_matches_sequential():
    """Multi-request batched prefill (same-bucket prompt rows stacked into
    one [n, bucket] forward) == one-at-a-time bucketed prefill, token for
    token, at kv-bits {0, 8, 4} — while running strictly fewer prefill
    forwards.

    Runs in a subprocess with single-threaded XLA: multi-threaded XLA:CPU
    GEMMs are not bitwise deterministic under thread contention, and exact
    argmax token identity needs bitwise-equal logits."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")])
    res = subprocess.run([sys.executable, "-c",
                          _BATCHED_PREFILL_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "BATCHED_PREFILL_IDENTITY_OK" in res.stdout


def test_batched_admission_rolls_back_on_out_of_pages(smoke_model):
    """An OutOfPagesError raised mid-batched-admission (normally
    unreachable: the preflight reserves worst-case demand) must roll back
    EVERY partially admitted row of the batch — pages released, page-table
    rows re-parked on the scratch page, reservations zeroed, slots vacated
    — before the error surfaces, so accounting stays leak-free."""
    from repro.core.paged_kv import SCRATCH_PAGE
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=32, kv_bits=8,
                        page_size=8, prefill="bucketed", prefill_batch=2)
    real_alloc = srv.allocator.alloc
    calls = {"n": 0}

    def flaky_alloc():
        calls["n"] += 1
        if calls["n"] > 1:   # second row of the batch fails
            raise OutOfPagesError(needed=1, free=0,
                                  total=srv.allocator.num_usable)
        return real_alloc()

    srv.allocator.alloc = flaky_alloc
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                    4) for i in range(2)]
    with pytest.raises(OutOfPagesError):
        srv.run(reqs)
    srv.allocator.alloc = real_alloc
    # every row of the failed batch rolled back: no slot claimed, no page
    # leaked, no reservation outstanding
    assert all(s is None for s in srv.slots)
    assert all(r == 0 for r in srv.slot_reserved)
    assert all(not p for p in srv.slot_pages)
    assert (srv.page_table == SCRATCH_PAGE).all()
    assert srv.allocator.num_free == srv.allocator.num_usable
    assert all(isinstance(r.error, OutOfPagesError) for r in reqs)


# ---------------------------------------------------------------------------
# Compile-count discipline of the two serving programs
# ---------------------------------------------------------------------------
def test_separate_programs_compile_once_per_shape(smoke_model):
    """The decode step traces once, and the chunk-prefill step once per
    prefill bucket it runs, however the trace interleaves admissions and
    retirements: a benchmark that warms those shapes compiles nothing in
    its measured window. Every launch is a decode step or a prefill
    forward. Prompts of 5, 9 and 13 tokens prefill in buckets 4 and 8
    (13 -> 8 + 4) at batch 2, and staggered answer lengths refill slots
    mid-run."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=48, kv_bits=8,
                        page_size=8, prefill="bucketed", prefill_bucket=8,
                        prefill_batch=1)
    shapes = set()
    group = srv._prefill_group

    def group_rec(rows, bucket):
        shapes.add((len(rows), bucket))
        return group(rows, bucket)

    srv._prefill_group = group_rec
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    3 + 2 * i) for i, L in enumerate([9, 5, 13, 9, 5])]
    out = srv.run(reqs)
    assert all(r.done for r in out)
    assert shapes == {(1, 4), (1, 8)}, shapes
    assert srv.decode._cache_size() == 1, srv.decode._cache_size()
    assert srv._chunk_prefill._cache_size() == len(shapes), (
        srv._chunk_prefill._cache_size(), shapes)
    assert srv.program_launches == srv.decode_steps + srv.prefill_forwards, (
        srv.program_launches, srv.decode_steps, srv.prefill_forwards)
    assert srv.allocator.num_free == srv.allocator.num_usable


# ---------------------------------------------------------------------------
# Prefix sharing on == off, token for token (incl. per-layer profile)
# ---------------------------------------------------------------------------
# The trace makes every sharing mechanism fire: a common system prompt whose
# length (11) is NOT page-aligned at ps=8 forces full-page aliasing AND a
# copy-on-write inside page 1, a repeated identical prompt gives a full-chain
# hit (zero prefill forwards), and distinct suffixes exercise divergence.
_PREFIX_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.core.fixedpoint import FixedPointFormat
from repro.core.policy import LayerPolicy, PrecisionPolicy
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(11)
sys_prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)

def mk():
    r = np.random.default_rng(13)
    reqs = [Request(i, np.concatenate(
                [sys_prompt, r.integers(0, cfg.vocab_size, 2 + i)
                 .astype(np.int32)]), 4 + i % 3) for i in range(4)]
    reqs.append(Request(4, reqs[0].prompt.copy(), 6))   # full-chain hit
    return reqs

profile = PrecisionPolicy(
    tuple(f"layer_{i:03d}" for i in range(cfg.num_layers)),
    tuple(LayerPolicy(None, FixedPointFormat(2, 6 if i % 2 == 0 else 2))
          for i in range(cfg.num_layers)))

for tag, kw in [("kv0", dict(kv_bits=0)), ("kv8", dict(kv_bits=8)),
                ("kv4", dict(kv_bits=4)),
                ("profile", dict(kv_profile=profile))]:
    for prefill in ("bucketed", "stepwise"):
        # prefill_batch=1: compare sharing on/off at EQUAL prefill
        # discipline (auto would batch only the off side, muddying the
        # forward-count assertion; batched-vs-sequential identity has its
        # own test)
        base = dict(batch_size=2, max_len=32, page_size=8, prefill=prefill,
                    prefill_bucket=8, prefill_batch=1, **kw)
        off = BatchedServer(cfg, params, prefix_cache="off", **base)
        out_off = off.run(mk())
        on = BatchedServer(cfg, params, prefix_cache="on", **base)
        out_on = on.run(mk())
        for a, b in zip(out_off, out_on):
            assert a.out == b.out, (tag, prefill, a.rid, a.out, b.out)
        assert all(r.done for r in out_on)
        st = on.prefix_cache.stats()
        assert st["hits"] >= 4 and st["cow_copies"] >= 1, st
        assert on.prefill_forwards < off.prefill_forwards, (
            tag, prefill, on.prefill_forwards, off.prefill_forwards)
        assert on.release_prefix_cache() == 0          # no refcount leak
        assert on.allocator.num_free == on.allocator.num_usable
        print(f"{tag}/{prefill} identical "
              f"({off.prefill_forwards} -> {on.prefill_forwards} fwd, "
              f"{st['hit_tokens']} tokens reused, {st['cow_copies']} CoW)")
print("PREFIX_IDENTITY_OK")
"""


def test_prefix_sharing_matches_unshared():
    """--prefix-cache on produces token-identical output to off, across
    kv-bits {0, 8, 4} and a mixed per-layer profile, in both prefill modes,
    while saving prefill forwards and leaking no pages.

    Runs in a subprocess with single-threaded XLA: multi-threaded XLA:CPU
    GEMMs are not bitwise deterministic under thread contention, and exact
    argmax token identity needs bitwise-equal logits."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")])
    res = subprocess.run([sys.executable, "-c", _PREFIX_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PREFIX_IDENTITY_OK" in res.stdout


# ---------------------------------------------------------------------------
# Per-layer profile: grouped run-scan == fully unrolled reference
# ---------------------------------------------------------------------------
# Two profile shapes: contiguous (int8,int8,int4,int4 -> 2 scanned runs —
# the realistic core.search output) and pathologically alternating
# (int8,int4,int8,int4 -> all length-1 runs, i.e. full unroll through the
# grouped path). Both must match the _segment_unrolled reference token for
# token; fp layers ride along via a None-format layer.
_PROFILE_SCAN_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.core.fixedpoint import FixedPointFormat
from repro.core.policy import LayerPolicy, PrecisionPolicy
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)

def mk():
    r = np.random.default_rng(5)
    return [Request(i, r.integers(0, cfg.vocab_size, 7 + i)
                    .astype(np.int32), 5) for i in range(3)]

def prof(fmt_fn):
    return PrecisionPolicy(
        tuple(f"layer_{i:03d}" for i in range(cfg.num_layers)),
        tuple(LayerPolicy(None, fmt_fn(i)) for i in range(cfg.num_layers)))

L = cfg.num_layers
profiles = {
    "contig": prof(lambda i: FixedPointFormat(2, 6 if i < L // 2 else 2)),
    "alt": prof(lambda i: FixedPointFormat(2, 6 if i % 2 == 0 else 2)),
    "fpmix": PrecisionPolicy(
        tuple(f"layer_{i:03d}" for i in range(L)),
        tuple(LayerPolicy(None, None if i == 0 else FixedPointFormat(2, 6))
              for i in range(L))),
}
for name, p in profiles.items():
    outs = {}
    for scan in ("group", "unroll"):
        srv = BatchedServer(cfg, params, batch_size=2, max_len=32,
                            page_size=8, kv_profile=p, kv_profile_scan=scan)
        outs[scan] = [r.out for r in srv.run(mk())]
        assert srv.allocator.num_free == srv.allocator.num_usable
    assert outs["group"] == outs["unroll"], (name, outs)
    print(f"{name}: grouped-scan == unrolled")
print("PROFILE_SCAN_IDENTITY_OK")
"""


def test_profile_grouped_scan_matches_unrolled():
    """The grouped run-scan forward for per-layer KV containers (contiguous
    same-container runs ride lax.scan) is token-identical to the fully
    unrolled _segment_unrolled reference, for contiguous, alternating, and
    fp-mixed profiles.

    Runs in a subprocess with single-threaded XLA: multi-threaded XLA:CPU
    GEMMs are not bitwise deterministic under thread contention, and exact
    argmax token identity needs bitwise-equal logits."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")])
    res = subprocess.run([sys.executable, "-c",
                          _PROFILE_SCAN_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PROFILE_SCAN_IDENTITY_OK" in res.stdout


def test_per_layer_profile_shrinks_at_rest_bytes(smoke_model):
    """A profile with >= 2 distinct layer bit-widths stores its paged pools
    below uniform int8 (and above uniform int4) at rest."""
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.paged_kv import pool_bytes
    from repro.core.policy import LayerPolicy, PrecisionPolicy
    cfg, params = smoke_model

    def kv_bytes(srv):
        total = 0
        for seg in srv.caches:
            for entry in seg:
                for d in (entry if isinstance(entry, list) else [entry]):
                    if isinstance(d, dict) and "k_pages" in d:
                        total += pool_bytes(d)
        return total

    profile = PrecisionPolicy(
        tuple(f"layer_{i:03d}" for i in range(cfg.num_layers)),
        tuple(LayerPolicy(None, FixedPointFormat(2, 6 if i % 2 == 0 else 2))
              for i in range(cfg.num_layers)))
    mk = lambda kw: BatchedServer(cfg, params, batch_size=2, max_len=32,
                                  page_size=8, **kw)
    prof = kv_bytes(mk(dict(kv_profile=profile)))
    u8 = kv_bytes(mk(dict(kv_bits=8)))
    u4 = kv_bytes(mk(dict(kv_bits=4)))
    assert u4 < prof < u8, (u4, prof, u8)


def test_kv_profile_validation(smoke_model):
    cfg, params = smoke_model
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.policy import PrecisionPolicy
    profile = PrecisionPolicy.uniform(
        [f"layer_{i:03d}" for i in range(cfg.num_layers)], None,
        FixedPointFormat(2, 6))
    with pytest.raises(ValueError, match="paged"):
        BatchedServer(cfg, params, batch_size=2, max_len=32,
                      kv_profile=profile)
    with pytest.raises(ValueError, match="supersedes"):
        BatchedServer(cfg, params, batch_size=2, max_len=32, page_size=8,
                      kv_bits=8, kv_profile=profile)


# ---------------------------------------------------------------------------
# Pallas decode == gather decode on fragmented page tables (oracle-style)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_bits", [0, 8, 4])
def test_pallas_attn_impl_matches_gather_fragmented(kv_bits):
    """gqa_apply with attn_impl="pallas" (interpret mode) matches the gather
    path on a deliberately fragmented page table with partial last pages."""
    cfg = get_smoke_config("qwen2-72b")
    rng = np.random.default_rng(3)
    B, ps, NP = 3, 8, 4
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    quant = (None if kv_bits == 0 else
             KVQuantSpec(2, kv_bits - 2, "int8" if kv_bits == 8 else "int4"))
    cache = init_paged_kv_cache(1 + B * NP, ps, KV, hd,
                                cfg.compute_jnp_dtype, quant)
    # fragmented: pages interleaved across sequences, shuffled ids
    ids = np.arange(1, 1 + B * NP)
    rng.shuffle(ids)
    pt = jnp.asarray(ids.reshape(B, NP).astype(np.int32))
    lens = np.array([5, ps * 2, ps * 3 - 1], np.int32)  # partial last pages
    for t in range(int(lens.max())):
        k = jnp.asarray(rng.normal(size=(B, 1, KV, hd)) * 0.5, jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 1, KV, hd)) * 0.5, jnp.float32)
        # rows past their length write their stale position (t clamped):
        # the serving loop does the same via per-row pos
        pos = jnp.asarray(np.minimum(t, lens - 1), jnp.int32)
        cache = paged_cache_update(cache, k, v, pt, pos, quant)

    params = init_gqa(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)) * 0.3,
                    cfg.compute_jnp_dtype)
    cache_pos = jnp.asarray(lens - 1, jnp.int32)  # writing the last token
    positions = cache_pos[:, None]
    outs = {}
    for impl in ("gather", "pallas"):
        y, _ = gqa_apply(params, x, positions, cfg=cfg, cache=cache,
                         cache_pos=cache_pos, kv_quant=quant,
                         page_table=pt, attn_impl=impl)
        outs[impl] = np.asarray(y, np.float32)
    np.testing.assert_allclose(outs["pallas"], outs["gather"],
                               rtol=2e-5, atol=2e-5)


def test_pallas_attn_impl_serving_smoke(smoke_model):
    """End-to-end: a pallas-routed server completes a mixed trace and agrees
    with the gather server on ~all tokens (argmax can flip on float-tolerance
    logit ties, so require agreement, not identity)."""
    cfg, params = smoke_model
    rng = np.random.default_rng(5)
    mk = lambda: [Request(i, rng.integers(0, cfg.vocab_size, 6)
                          .astype(np.int32), 6) for i in range(4)]
    a = BatchedServer(cfg, params, batch_size=2, max_len=256, kv_bits=8,
                      page_size=8, attn_impl="gather")
    rng = np.random.default_rng(5)
    out_a = a.run(mk())
    b = BatchedServer(cfg, params, batch_size=2, max_len=256, kv_bits=8,
                      page_size=8, attn_impl="pallas")
    rng = np.random.default_rng(5)
    out_b = b.run(mk())
    agree = np.mean([np.mean(np.asarray(x.out) == np.asarray(y.out))
                     for x, y in zip(out_a, out_b)])
    assert all(r.done for r in out_b)
    assert agree >= 0.9, agree
    # the kernel's walk, per program call: short rows in a 32-entry table
    # of two page blocks (16 entries a grid step)
    walk = lambda srv: [srv.metrics.counter(f"serve.attn_{k}").value
                        for k in ("pages_live", "pages_table", "grid_steps",
                                  "grid_box")]
    live, table, steps, box = walk(b)
    assert 0 < live < table, (live, table)
    assert 0 < steps < box, (steps, box)
    assert walk(a) == [0, 0, 0, 0]      # the gather path runs no kernel


@pytest.mark.parametrize("kv_bits", [0, 8, 4])
def test_pallas_chunk_prefill_matches_gather_fragmented(kv_bits):
    """gqa_apply with a PREFILL CHUNK (S > 1) and attn_impl="pallas" routes
    the variable-length chunk kernel and matches the gather path on a
    fragmented page table with per-row start positions that straddle page
    boundaries (partial last pages included) — the S>=1 generalization of
    the decode oracle test above."""
    cfg = get_smoke_config("qwen2-72b")
    rng = np.random.default_rng(17)
    B, ps, NP, S = 3, 8, 4, 6
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    quant = (None if kv_bits == 0 else
             KVQuantSpec(2, kv_bits - 2, "int8" if kv_bits == 8 else "int4"))
    cache = init_paged_kv_cache(1 + B * NP, ps, KV, hd,
                                cfg.compute_jnp_dtype, quant)
    ids = np.arange(1, 1 + B * NP)
    rng.shuffle(ids)
    pt = jnp.asarray(ids.reshape(B, NP).astype(np.int32))
    lens = np.array([0, 5, ps * 2 + 3], np.int32)  # history before the chunk
    for t in range(int(lens.max())):
        k = jnp.asarray(rng.normal(size=(B, 1, KV, hd)) * 0.5, jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 1, KV, hd)) * 0.5, jnp.float32)
        pos = jnp.asarray(np.minimum(t, np.maximum(lens - 1, 0)), jnp.int32)
        cache = paged_cache_update(cache, k, v, pt, pos, quant)

    params = init_gqa(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)) * 0.3,
                    cfg.compute_jnp_dtype)
    cache_pos = jnp.asarray(lens, jnp.int32)
    positions = cache_pos[:, None] + jnp.arange(S)[None, :]
    outs = {}
    for impl in ("gather", "pallas"):
        y, _ = gqa_apply(params, x, positions, cfg=cfg, cache=cache,
                         cache_pos=cache_pos, kv_quant=quant,
                         page_table=pt, attn_impl=impl)
        outs[impl] = np.asarray(y, np.float32)
    np.testing.assert_allclose(outs["pallas"], outs["gather"],
                               rtol=2e-5, atol=2e-5)


def test_pallas_chunk_prefill_padded_tail_matches_gather():
    """A padded bucketed-prefill chunk (kv_valid_len < S): the kernel and
    gather paths agree on every REAL query row; padded rows are garbage
    nobody reads (their pool writes go to the scratch page)."""
    cfg = get_smoke_config("qwen2-72b")
    rng = np.random.default_rng(23)
    B, ps, NP, S, valid = 2, 8, 3, 8, 5
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    quant = KVQuantSpec(2, 6, "int8")
    cache = init_paged_kv_cache(1 + B * NP, ps, KV, hd,
                                cfg.compute_jnp_dtype, quant)
    ids = np.arange(1, 1 + B * NP)
    rng.shuffle(ids)
    pt = jnp.asarray(ids.reshape(B, NP).astype(np.int32))
    params = init_gqa(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)) * 0.3,
                    cfg.compute_jnp_dtype)
    cache_pos = jnp.asarray([0, 3], jnp.int32)
    positions = cache_pos[:, None] + jnp.arange(S)[None, :]
    vl = jnp.asarray([valid, valid], jnp.int32)
    outs = {}
    for impl in ("gather", "pallas"):
        y, _ = gqa_apply(params, x, positions, cfg=cfg, cache=cache,
                         cache_pos=cache_pos, kv_quant=quant,
                         page_table=pt, attn_impl=impl, kv_valid_len=vl)
        outs[impl] = np.asarray(y, np.float32)
    np.testing.assert_allclose(outs["pallas"][:, :valid],
                               outs["gather"][:, :valid],
                               rtol=2e-5, atol=2e-5)


def test_pallas_serving_with_mixed_profile(smoke_model):
    """End-to-end --attn-impl pallas over a MIXED per-layer precision
    profile (int8/int4/fp containers via _segment_scan_grouped): bucketed
    chunk prefill and decode both route the kernel per-layer-bits, and the
    server agrees with the gather reference on ~all tokens."""
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.policy import LayerPolicy, PrecisionPolicy
    cfg, params = smoke_model
    L = cfg.num_layers
    profile = PrecisionPolicy(
        tuple(f"layer_{i:03d}" for i in range(L)),
        tuple(LayerPolicy(None, None if i == 0
                          else FixedPointFormat(2, 6 if i % 2 else 2))
              for i in range(L)))
    mk = lambda: [Request(i, np.random.default_rng(i).integers(
        0, cfg.vocab_size, 7 + i).astype(np.int32), 5) for i in range(3)]
    outs = {}
    for impl in ("gather", "pallas"):
        srv = BatchedServer(cfg, params, batch_size=2, max_len=32,
                            page_size=8, kv_profile=profile, attn_impl=impl,
                            prefill="bucketed", prefill_bucket=8)
        outs[impl] = srv.run(mk())
        assert all(r.done for r in outs[impl])
    agree = np.mean([np.mean(np.asarray(a.out) == np.asarray(b.out))
                     for a, b in zip(outs["gather"], outs["pallas"])])
    assert agree >= 0.9, agree


def test_pallas_requires_paged(smoke_model):
    cfg, params = smoke_model
    with pytest.raises(ValueError, match="page-size"):
        BatchedServer(cfg, params, batch_size=2, max_len=32,
                      attn_impl="pallas")


# ---------------------------------------------------------------------------
# Admission preflight: OutOfPagesError semantics
# ---------------------------------------------------------------------------
def test_allocator_preflight_and_exhaustion():
    al = PageAllocator(4)           # 3 usable
    al.check(3)                     # fits
    with pytest.raises(OutOfPagesError) as ei:
        al.check(4, rid=7)
    assert ei.value.needed == 4 and ei.value.free == 3
    assert ei.value.total == 3 and ei.value.rid == 7
    for _ in range(3):
        al.alloc()
    with pytest.raises(OutOfPagesError):
        al.alloc()


def test_admission_rejects_impossible_request(smoke_model):
    """A request whose prompt + max_new can NEVER be backed by the pool is
    rejected up front with counts, not an opaque failure mid-prefill."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=64,
                        kv_bits=8, page_size=8, num_pages=3)  # 2 usable
    rng = np.random.default_rng(0)
    req = Request(0, rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                  20)               # needs ceil(39/8)=5 pages > 2 usable
    with pytest.raises(OutOfPagesError) as ei:
        srv.run([req])
    assert ei.value.needed == 5 and ei.value.total == 2
    assert "request 0" in str(ei.value)
    assert srv.allocator.num_free == 2          # nothing leaked


def test_preflight_counts_forced_token_at_max_new_zero(smoke_model):
    """The decode loop always generates >= 1 token, so a max_new=0 request
    whose prompt exactly fills the pool must be REJECTED at admission (page
    demand includes the forced token), not die allocating mid-decode."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=1, max_len=64,
                        kv_bits=8, page_size=16, num_pages=2)  # 1 usable
    req = Request(0, (np.arange(17) % cfg.vocab_size).astype(np.int32), 0)
    with pytest.raises(OutOfPagesError):
        srv.run([req])
    assert srv.allocator.num_free == 1   # rejected up front, nothing leaked


def test_admission_defers_until_pages_free(smoke_model):
    """A request that merely has to WAIT for live requests to release pages
    is deferred, not rejected: the queue drains as slots complete."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=64,
                        kv_bits=8, page_size=8, num_pages=4)  # 3 usable
    rng = np.random.default_rng(1)
    # each request needs ceil((6-1+8)/8) = 2 pages; two concurrent would
    # need 4 > 3 usable, so the second must wait for the first to finish
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    8) for i in range(3)]
    srv.run(reqs)
    assert all(r.done and len(r.out) == 8 for r in reqs)
    assert srv.allocator.num_free == 3


def test_out_of_pages_reports_requantizable_inventory():
    """With a quant tier attached, admission rejects report how many cold
    cached pages could be requantized in place (``requantizable``) next to
    the evictable/host counts — the operator-facing hint that --kv-adapt
    headroom exists. Without a tier the field stays 0."""
    al = PageAllocator(4)           # 3 usable
    al.requant_inventory = lambda: 2
    with pytest.raises(OutOfPagesError) as ei:
        al.check(9, rid=3)
    assert ei.value.requantizable == 2
    assert "2 requantizable" in str(ei.value)
    al2 = PageAllocator(4)
    with pytest.raises(OutOfPagesError) as ei2:
        al2.check(9)
    assert ei2.value.requantizable == 0


# ---------------------------------------------------------------------------
# Online precision adaptation (--kv-adapt): identity off / under no pressure,
# requantization under pressure, page-scale sharing contract, validation
# ---------------------------------------------------------------------------
_ADAPT_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)
rng0 = np.random.default_rng(19)
sys_prompt = rng0.integers(0, cfg.vocab_size, 9).astype(np.int32)

def mk():
    r = np.random.default_rng(29)
    reqs = [Request(i, np.concatenate(
                [sys_prompt, r.integers(0, cfg.vocab_size, 2 + i)
                 .astype(np.int32)]), 4 + i % 3) for i in range(4)]
    reqs.append(Request(4, reqs[0].prompt.copy(), 6))   # full-chain hit
    return reqs

# --kv-adapt off must be a pure no-op: bitwise-identical to a server built
# without the flag at all, at every pool container
for kv_bits in (0, 8, 4):
    base = dict(batch_size=2, max_len=32, kv_bits=kv_bits, page_size=8,
                prefill="bucketed", prefill_bucket=8, prefill_batch=1,
                prefix_cache="on")
    seed = BatchedServer(cfg, params, **base)
    out_seed = seed.run(mk())
    off = BatchedServer(cfg, params, kv_adapt="off", **base)
    out_off = off.run(mk())
    for a, b in zip(out_seed, out_off):
        assert a.out == b.out, (kv_bits, a.rid, a.out, b.out)
    assert off.quant_tier is None
    print(f"kv_bits={kv_bits} adapt-off == seed")

# adapt ON with a roomy pool: the tier attaches but pressure never fires,
# so every token must stay bitwise-identical to adapt-off (requant only
# ever runs under eviction pressure, never on the hot path). kv_bits=4 is
# excluded: an int4 pool is already at the tier floor and the tier refuses
# to attach (asserted in test_kv_adapt_validation).
for kv_bits in (0, 8):
    base = dict(batch_size=2, max_len=32, kv_bits=kv_bits, page_size=8,
                prefill="bucketed", prefill_bucket=8, prefill_batch=1,
                prefix_cache="on")
    off = BatchedServer(cfg, params, kv_adapt="off", **base)
    out_off = off.run(mk())
    on = BatchedServer(cfg, params, kv_adapt="on", **base)
    out_on = on.run(mk())
    for a, b in zip(out_off, out_on):
        assert a.out == b.out, (kv_bits, a.rid, a.out, b.out)
    assert all(r.done for r in out_on)
    st = on.prefix_cache.stats()
    assert st["requants"] == 0 and st["tier_promotions"] == 0, st
    assert on.quant_tier.num_pages == 0 and on.quant_tier.nbytes == 0
    assert on.release_prefix_cache() == 0
    assert on.allocator.num_free == on.allocator.num_usable
    print(f"kv_bits={kv_bits} adapt-on (no pressure) == adapt-off")
print("ADAPT_IDENTITY_OK")
"""


def test_kv_adapt_off_matches_seed_and_on_is_noop_without_pressure():
    """--kv-adapt off is bitwise-identical to a server built without the
    flag (kv-bits {0, 8, 4}); --kv-adapt on with a roomy pool is
    bitwise-identical to off (requantization runs only under eviction
    pressure, never on the hot path) and ends with an empty, leak-free
    quant tier.

    Runs in a subprocess with single-threaded XLA: multi-threaded XLA:CPU
    GEMMs are not bitwise deterministic under thread contention, and exact
    argmax token identity needs bitwise-equal logits."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")])
    res = subprocess.run([sys.executable, "-c", _ADAPT_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ADAPT_IDENTITY_OK" in res.stdout


def test_kv_adapt_requantizes_under_pressure(smoke_model):
    """End-to-end --kv-adapt on under real pool pressure: distinct
    per-tenant prefixes overflow a 9-page pool, so eviction must narrow
    cold cached pages into the quant tier BEFORE any host demotion, every
    request still completes, and pool + host + tier all drain leak-free."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=64, kv_bits=8,
                        page_size=4, num_pages=10, prefill="bucketed",
                        prefill_bucket=8, prefill_batch=1,
                        prefix_cache="on", kv_offload="host",
                        kv_adapt="on", adapt_pages=36)
    rng = np.random.default_rng(31)
    reqs = []
    for g in range(4):              # 4 tenants, distinct 8-token prefixes
        sys_p = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
        sfx = rng.integers(0, cfg.vocab_size, 3).astype(np.int32)
        reqs.append(Request(g, np.concatenate([sys_p, sfx]), 4,
                            arrive_step=2 * g))
    srv.run(reqs)
    assert all(r.done and r.error is None and len(r.out) == 4 for r in reqs)
    st = srv.prefix_cache.stats()
    assert st["requants"] >= 1, st
    if st["demotions"]:             # requant strictly preceded host demotion
        assert st["requants_at_first_demotion"] >= 1, st
    assert srv.quant_tier.peak_pages >= 1
    # the new inventory surfaces in admission rejects while pages are cold
    verdict, info = srv._admission_plan(
        Request(99, rng.integers(0, cfg.vocab_size, 40).astype(np.int32),
                20))
    assert verdict == "reject"
    assert info["err"].requantizable == srv.prefix_cache.requantizable_pages()
    assert info["err"].requantizable >= 1
    # drain: releasing the cache empties the tier too
    assert srv.release_prefix_cache() == 0
    assert srv.quant_tier.num_pages == 0 and srv.quant_tier.nbytes == 0
    assert srv.host_store.num_pages == 0
    assert srv.allocator.num_free == srv.allocator.num_usable


def test_page_scale_sharing_preserves_sharer_bytes(smoke_model):
    """Page-scale sharing contract (regression): in --kv-scale page mode a
    per-page scale raise REWRITES the page's packed grid in place, so the
    prefix cache must never index the partial tail page its owner keeps
    writing. Only full pages are cached, and a later request that aliases
    a cached page and decodes onward leaves the shared page's packed bytes
    untouched."""
    from repro.core.page_store import extract_page
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=48, kv_bits=8,
                        page_size=8, kv_scale="page", prefix_cache="on",
                        prefill="bucketed", prefill_bucket=8,
                        prefill_batch=1)
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    srv.run([Request(0, base, 4)])
    # prompt prefills 10 tokens = 1 full page + 2-token tail; page mode
    # caches ONLY the full page (static mode would index the tail too)
    hit = srv.prefix_cache.lookup(base)
    assert len(hit.nodes) == 1 and hit.matched == 8
    assert hit.cow_node is None, "partial tail leaked into the page-scale " \
                                 "cache"
    shared = int(hit.nodes[0].page)
    before = extract_page(srv.caches, shared)
    # a sharer aliases the page and decodes well past it: its scale raises
    # must land in its OWN pages, never the aliased one
    ext = Request(1, np.concatenate(
        [base, rng.integers(0, cfg.vocab_size, 6).astype(np.int32)]), 8)
    srv.run([ext])
    assert ext.done and ext.error is None
    assert srv.prefix_cache.stats()["hits"] >= 1
    after = extract_page(srv.caches, shared)
    for ra, rb in zip(before.arrays, after.arrays):
        for key in ("k", "v", "ks", "vs"):
            assert np.array_equal(ra[key], rb[key]), \
                f"shared page {key!r} bytes changed under an aliased reader"
    assert srv.release_prefix_cache() == 0
    assert srv.allocator.num_free == srv.allocator.num_usable


def test_kv_adapt_validation(smoke_model):
    cfg, params = smoke_model
    base = dict(batch_size=2, max_len=32)
    with pytest.raises(ValueError, match="kv_adapt"):
        BatchedServer(cfg, params, kv_adapt="maybe", **base)
    with pytest.raises(ValueError, match="prefix-cache"):
        BatchedServer(cfg, params, kv_bits=8, page_size=8, kv_adapt="on",
                      **base)
    with pytest.raises(ValueError, match="page-size"):
        BatchedServer(cfg, params, kv_adapt="on", **base)
    with pytest.raises(ValueError, match="floor_bits"):
        BatchedServer(cfg, params, kv_bits=8, page_size=8,
                      prefix_cache="on", kv_adapt="on", adapt_floor_bits=6,
                      **base)
    # a uniform-int4 pool is already at the tier floor: nothing to narrow
    with pytest.raises(ValueError, match="nothing to narrow"):
        BatchedServer(cfg, params, kv_bits=4, page_size=8,
                      prefix_cache="on", kv_adapt="on", **base)
