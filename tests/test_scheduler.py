"""SLO scheduler + preemption tests.

* ordering/victim-selection policy units (``launch.scheduler``),
* the legacy-FIFO head-skip regression (a permanently-too-large head no
  longer starves the queue behind it),
* bounded out-of-order admission past a deferred head,
* end-to-end preemption: a preempted request's pages demote to the host
  tier, resume promotes them back, and the token stream is BITWISE
  identical to an uninterrupted run at kv-bits {0, 8, 4} (gather mode,
  single-threaded-XLA subprocess like the other identity tests).
"""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.paged_kv import OutOfPagesError
from repro.launch.scheduler import (SchedPolicy, SLOScheduler, request_key)
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_smoke_config("qwen2-72b")
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _req(rid, *, priority=0, deadline=None, arrive=0, prompt_len=4,
         max_new=4):
    return Request(rid, (np.arange(prompt_len) % 7).astype(np.int32),
                   max_new, priority=priority, deadline_step=deadline,
                   arrive_step=arrive)


# ---------------------------------------------------------------------------
# Ordering + victim policy units
# ---------------------------------------------------------------------------
def test_request_key_priority_then_deadline_then_arrival():
    hi = _req(0, priority=5)
    edf_soon = _req(1, priority=0, deadline=10)
    edf_late = _req(2, priority=0, deadline=99)
    no_dl = _req(3, priority=0)
    later = _req(4, priority=0, arrive=7)
    order = sorted([later, no_dl, edf_late, edf_soon, hi], key=request_key)
    assert [r.rid for r in order] == [0, 1, 2, 3, 4]


def test_sort_queue_is_stable_for_ties():
    sched = SLOScheduler()
    a, b = _req(1), _req(2)
    q = [a, b]
    sched.sort_queue(q)
    assert [r.rid for r in q] == [1, 2]


def test_choose_victims_strictly_less_urgent_least_first():
    sched = SLOScheduler(SchedPolicy(max_preempt_per_admit=2))
    urgent = _req(0, priority=5)
    low1, low2, mid = _req(1, priority=0), _req(2, priority=0,
                                                arrive=3), _req(3, priority=3)
    running = [(0, low1, 0), (1, mid, 0), (2, low2, 0)]
    gains = {0: 2, 1: 2, 2: 2}
    victims = sched.choose_victims(urgent, running, 2, gains.get)
    assert victims == [2]          # least urgent (latest arrival) first
    victims = sched.choose_victims(urgent, running, 4, gains.get)
    assert victims == [2, 0]       # accumulates until the shortfall is met
    # equally/more urgent peers are never victims
    peer = _req(9, priority=5)
    assert sched.choose_victims(peer, [(0, urgent, 0)], 1,
                                gains.get) == []
    # insufficient total gain -> no pointless churn
    assert sched.choose_victims(urgent, running, 99, gains.get) == []
    # preemption disabled
    off = SLOScheduler(SchedPolicy(preempt=False))
    assert off.choose_victims(urgent, running, 1, gains.get) == []


# ---------------------------------------------------------------------------
# Legacy FIFO: too-large head is skipped, not starving the tail
# ---------------------------------------------------------------------------
def test_fifo_skips_permanently_too_large_head(smoke_model):
    """Regression: the old admission raised on the spot for a never-fit
    head, killing every serviceable request queued behind it. Now the head
    is recorded+skipped, the tail is served, and the error surfaces at the
    end of the run."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=64, kv_bits=8,
                        page_size=8, num_pages=5)        # 4 usable
    rng = np.random.default_rng(0)
    huge = Request(0, rng.integers(0, cfg.vocab_size, 30).astype(np.int32),
                   30)              # needs 8 pages > 4 usable: never fits
    ok = [Request(i, rng.integers(0, cfg.vocab_size, 6).astype(np.int32), 6)
          for i in (1, 2)]
    with pytest.raises(OutOfPagesError) as ei:
        srv.run([huge] + ok)
    # the too-large head was rejected with full counts...
    assert ei.value.rid == 0 and ei.value.needed > ei.value.total
    assert isinstance(huge.error, OutOfPagesError) and huge.done
    assert huge.out == []
    # ...but the tail behind it was served to completion first
    assert all(r.done and len(r.out) == 6 and r.error is None for r in ok)
    assert srv.allocator.num_free == srv.allocator.num_usable


def test_slo_records_reject_instead_of_raising(smoke_model):
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=64, kv_bits=8,
                        page_size=8, num_pages=5, sched="slo")
    rng = np.random.default_rng(0)
    huge = Request(0, rng.integers(0, cfg.vocab_size, 30).astype(np.int32),
                   30)
    ok = Request(1, rng.integers(0, cfg.vocab_size, 6).astype(np.int32), 6)
    out = srv.run([huge, ok])      # no raise in slo mode
    assert out is not None
    assert isinstance(huge.error, OutOfPagesError)
    assert ok.done and len(ok.out) == 6 and ok.error is None
    assert srv.rejected == [huge]


# ---------------------------------------------------------------------------
# Out-of-order admission past a deferred head
# ---------------------------------------------------------------------------
def test_slo_admits_small_request_past_deferred_head(smoke_model):
    """A head that must WAIT for pages no longer blocks a small request
    behind it: the scheduler admits within the window, and the head admits
    later once pages free up."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=2, max_len=64, kv_bits=8,
                        page_size=8, num_pages=7, sched="slo")  # 6 usable
    rng = np.random.default_rng(1)
    # big needs ceil((11+20)/8)=4 pages; blocker holds 3 -> big defers
    blocker = Request(0, rng.integers(0, cfg.vocab_size, 8)
                      .astype(np.int32), 16)               # 3 pages
    big = Request(1, rng.integers(0, cfg.vocab_size, 12)
                  .astype(np.int32), 20)                   # 4 pages
    small = Request(2, rng.integers(0, cfg.vocab_size, 4)
                    .astype(np.int32), 4, arrive_step=2)   # 1 page
    srv.run([blocker, big, small])
    assert all(r.done and r.error is None for r in (blocker, big, small))
    assert srv.scheduler.ooo_admissions >= 1
    assert srv.allocator.num_free == srv.allocator.num_usable

    # window=0 restores strict (priority-sorted) FIFO: no OOO admissions
    srv2 = BatchedServer(cfg, params, batch_size=2, max_len=64, kv_bits=8,
                         page_size=8, num_pages=7, sched="slo",
                         admit_window=0)
    rng = np.random.default_rng(1)
    srv2.run([Request(0, rng.integers(0, cfg.vocab_size, 8)
                      .astype(np.int32), 16),
              Request(1, rng.integers(0, cfg.vocab_size, 12)
                      .astype(np.int32), 20),
              Request(2, rng.integers(0, cfg.vocab_size, 4)
                      .astype(np.int32), 4, arrive_step=2)])
    assert srv2.scheduler.ooo_admissions == 0


# ---------------------------------------------------------------------------
# End-to-end preemption wiring (single-process; bitwise test below)
# ---------------------------------------------------------------------------
def test_preemption_demotes_resumes_and_completes(smoke_model):
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=1, max_len=48, kv_bits=4,
                        page_size=8, num_pages=4, kv_offload="host",
                        sched="slo")
    rng = np.random.default_rng(2)
    low = Request(0, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                  16, priority=0)
    hi = Request(1, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                 6, priority=5, arrive_step=4, deadline_step=20)
    srv.run([low, hi])
    assert low.done and hi.done and low.preemptions >= 1
    assert len(low.out) == 16 and len(hi.out) == 6
    assert srv.preempt_count == srv.resume_count >= 1
    assert srv.host_store.num_pages == 0      # resume drained the handles
    assert srv.allocator.num_free == srv.allocator.num_usable
    # the preempted request kept ONE contiguous output stream
    assert low._paused is None and low.error is None


def test_preempt_realias_skips_host_copies(smoke_model):
    """With the prefix cache on, a victim's prompt pages are cache nodes
    (refcount > 1) — preempting it must RE-ALIAS them (pin the node, drop
    the slot ref, no host copy) instead of paying offload for pages that
    free nothing, and resume must re-incref them with the token stream
    intact and zero leaks."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=1, max_len=48, kv_bits=8,
                        page_size=8, num_pages=5, kv_offload="host",
                        sched="slo", prefix_cache="on")
    rng = np.random.default_rng(2)
    low = Request(0, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                  16, priority=0)
    hi = Request(1, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                 6, priority=5, arrive_step=4, deadline_step=20)
    srv.run([low, hi])
    assert low.done and hi.done and low.preemptions >= 1
    assert srv.preempt_count == srv.resume_count >= 1
    # at least the victim's full prompt page skipped the host round trip
    assert srv.realias_skipped >= 1
    assert srv.host_store.num_pages == 0
    assert srv.release_prefix_cache() == 0
    assert srv.allocator.num_free == srv.allocator.num_usable


def test_reject_before_resume_releases_pins_and_host_pages(smoke_model):
    """Regression: a preempted request that is REJECTED (or rolled back)
    before it ever resumes must release its parked resume state — unpin
    every re-aliased prefix node and drop its host-tier blobs. The old
    ``_reject`` path only recorded the error, leaving the nodes pinned
    forever (phantom retained pages in the leak gate) and the host blobs
    counting against --host-pages until process exit."""
    cfg, params = smoke_model
    srv = BatchedServer(cfg, params, batch_size=1, max_len=48, kv_bits=8,
                        page_size=8, num_pages=8, kv_offload="host",
                        sched="slo", prefix_cache="on", prefill_batch=1,
                        kv_scale="page")
    rng = np.random.default_rng(3)
    req = Request(0, rng.integers(0, cfg.vocab_size, 12).astype(np.int32), 8)
    jobs = []
    srv._admit_slo([req], jobs)
    assert srv.slots[0] is req and not jobs   # prefill ran inline
    # page-scale mode: the full page is cached (-> alias-pinned on
    # preempt), the partial tail is private (-> host blob), so BOTH parked
    # resource flavors are exercised
    victim = srv._preempt_slot(0)
    kinds = {k for k, _ in victim._paused.entries}
    assert kinds == {"alias", "host"}, victim._paused.entries
    assert srv.host_store.num_pages >= 1
    queue = [victim]
    srv._reject(queue, 0, RuntimeError("cancelled before resume"))
    assert victim.done and victim._paused is None
    assert srv.host_store.num_pages == 0          # parked blobs dropped
    assert srv.release_prefix_cache() == 0        # pins released, no leak
    assert srv.allocator.num_free == srv.allocator.num_usable


def test_preempt_requires_host_offload(smoke_model):
    cfg, params = smoke_model
    with pytest.raises(ValueError, match="host"):
        BatchedServer(cfg, params, batch_size=1, max_len=32, kv_bits=8,
                      page_size=8, sched="slo", preempt=True)
    with pytest.raises(ValueError, match="slo"):
        BatchedServer(cfg, params, batch_size=1, max_len=32, kv_bits=8,
                      page_size=8, kv_offload="host", preempt=True)


# ---------------------------------------------------------------------------
# Preempt/resume is BITWISE identical to an uninterrupted run
# ---------------------------------------------------------------------------
_PREEMPT_IDENTITY_SCRIPT = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, Request
from repro.models.transformer import init_model

cfg = get_smoke_config("qwen2-72b")
params = init_model(jax.random.PRNGKey(0), cfg)

def mk():
    rng = np.random.default_rng(11)
    low = Request(0, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                  14, priority=0)
    hi = Request(1, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                 5, priority=5, arrive_step=4)
    mid = Request(2, rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                  6, priority=1, arrive_step=8)
    # shares low's first 5 prompt tokens: a hit that diverges mid-page
    dup = Request(3, np.concatenate([low.prompt[:5], rng.integers(
        0, cfg.vocab_size, 6).astype(np.int32)]), 5, arrive_step=12)
    return [low, hi, mid, dup]

for kv_bits in (0, 8, 4):
    for prefix, pager_async in (("off", "off"), ("on", "off"), ("on", "on")):
        # tight pool + slots: the high-priority latecomer must preempt.
        # prefix="on" additionally routes the victim's prompt pages through
        # PREEMPTION RE-ALIASING (pinned cache nodes, no host copy), which
        # must be just as bitwise-invisible as the host round trip, and
        # gives the late duplicate a copy-on-write hit. The step programs
        # donate the caches, so a holder that kept a stale pool would
        # fail here: host-tier page-out and page-in (sync and async
        # pager), the CoW copy, and every retire and readmit of the one
        # slot all run between steps.
        srv = BatchedServer(cfg, params, batch_size=1, max_len=48,
                            kv_bits=kv_bits, page_size=8, num_pages=4,
                            kv_offload="host", sched="slo",
                            prefix_cache=prefix, pager_async=pager_async)
        reqs = srv.run(mk())
        assert srv.preempt_count >= 1, "trace failed to trigger preemption"
        assert srv.resume_count == srv.preempt_count
        assert all(r.done and r.error is None for r in reqs)
        assert srv.host_store.pops >= 1, "no page came back from the host"
        if prefix == "on":
            assert srv.realias_skipped >= 1, "re-aliasing never fired"
            assert srv.prefix_cache.cow_copies >= 1, "no copy-on-write hit"
            assert srv.release_prefix_cache() == 0
        assert srv.host_store.num_pages == 0
        # uninterrupted reference: same requests, roomy pool, no preemption
        ref = BatchedServer(cfg, params, batch_size=4, max_len=48,
                            kv_bits=kv_bits, page_size=8)
        ref_reqs = ref.run(mk())
        assert ref.preempt_count == 0
        by_rid = {r.rid: r for r in ref_reqs}
        for r in reqs:
            assert r.out == by_rid[r.rid].out, (kv_bits, prefix, r.rid,
                                                r.out, by_rid[r.rid].out)
        n_pre = sum(r.preemptions for r in reqs)
        print(f"kv_bits={kv_bits} prefix={prefix} async={pager_async} "
              f"bitwise-identical after {n_pre} preemption(s), "
              f"{srv.realias_skipped} demotions skipped, "
              f"{srv.pager.demotions} demoted, {srv.pager.promotions} "
              f"promoted")
print("PREEMPT_IDENTITY_OK")
"""


def test_preempt_resume_bitwise_identical():
    """A preempted-then-resumed request emits bitwise-identical tokens to an
    unpreempted run at kv-bits {0, 8, 4}: demote->promote restores the
    packed page bytes exactly and decode continues from the restored state
    (no re-prefill). With the prefix cache on, under the sync and the async
    pager, a late request's copy-on-write hit matches too, all under the
    step programs' donated caches.

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
    res = subprocess.run([sys.executable, "-c", _PREEMPT_IDENTITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PREEMPT_IDENTITY_OK" in res.stdout
