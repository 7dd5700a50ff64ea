"""The serving steps update the stacked paged KV pool in place.

The layer scan carries each segment's stacked pools ``(L, P, ps, KV,
hdw)`` and every layer writes and reads its own layer of them through its
index (``models.transformer._segment_scan``); the step programs donate the
caches (``launch.serve._jit_on_mesh``). Two checks at the deepseek-7b
smoke shapes, MHA and GQA (G 2, QKV bias):

* the optimized program holds no slice, update-slice or copy of a whole
  pool or of one layer's pool, and aliases the pool parameters to its
  outputs (on the CPU through the gather path; the Pallas kernel's path
  is compiled for a described v5e in tests/test_tpu_compile.py, since
  interpret mode copies its operands by construction);
* the step equals a per-layer reference written here, the semantics the
  scan had when pools were sliced out and restacked: the pool bitwise,
  the logits bitwise on the gather path and within the kernel's tolerance
  through the Pallas kernel (interpret mode). The comparison runs in a
  subprocess with single-threaded XLA, whose float results do not depend
  on thread scheduling.
"""
import functools
import json
import os
import subprocess
import sys

import pytest

from _stacked_pool import (pool_copies, smoke_server, step_program,
                           unaliased_pool_params)

SHAPES = ["mha", "gqa"]
STEPS = ["decode", "prefill128"]


@pytest.mark.parametrize("step", STEPS)
@pytest.mark.parametrize("shape", SHAPES)
def test_step_updates_stacked_pool_in_place(shape, step):
    srv = smoke_server(shape, "gather")
    fn, args = step_program(srv, step)
    hlo = fn.lower(*args).compile().as_text()
    pool = srv.caches[0][0]["k_pages"]
    assert pool.ndim == 5 and pool.shape[0] == 2
    assert pool_copies(hlo, pool) == []
    missing, n = unaliased_pool_params(hlo, pool)
    assert n == 2 and missing == []


_EQUIVALENCE_SCRIPT = r"""
import functools, json, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_platform_name", "cpu")
from _stacked_pool import BUCKET, smoke_server
from repro.models.common import embed_tokens, lm_head, rmsnorm
from repro.models.transformer import block_apply, layer_segments


def per_layer_reference(srv, tokens, start, valid, caches, page_table):
    # each layer's pool sliced out of the stack, updated by its block and
    # restacked: the layer scan's semantics before pools rode its carry
    cfg, params = srv.cfg, srv.params
    x = embed_tokens(params["embed"], tokens, onehot=cfg.embedding_onehot,
                     compute_dtype=cfg.compute_jnp_dtype)
    positions = start[:, None] + jnp.arange(tokens.shape[1])[None, :]
    [(pattern, periods, _)] = layer_segments(cfg)
    pools = caches[0][0]
    layers = []
    for li in range(periods):
        take = lambda a: a[li]
        x, pool, _ = block_apply(
            jax.tree_util.tree_map(take, params["segments"][0][0]), x,
            positions, cfg=cfg, sig=pattern[0],
            cache=jax.tree_util.tree_map(take, pools), cache_pos=start,
            quant=srv.quant.layer_slice(li), page_table=page_table,
            attn_impl=srv.attn_impl, kv_valid_len=valid)
        layers.append(pool)
    pools = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_head(params.get("head"), x)[:, -1], [(pools,)]


out = {}
shape = sys.argv[1]
for impl in ("gather", "pallas"):
    srv = smoke_server(shape, impl)
    rng = np.random.default_rng(7)
    B, NP, V = srv.B, srv.np_max, srv.cfg.vocab_size
    pt = jnp.asarray(1 + np.arange(B * NP).reshape(B, NP), jnp.int32)
    ref = jax.jit(functools.partial(per_layer_reference, srv))
    # history: a first chunk, the rows' tails ending mid-page
    toks = jnp.asarray(rng.integers(0, V, (B, BUCKET)), jnp.int32)
    valid = jnp.asarray([BUCKET, 90], jnp.int32)
    _, hist = ref(toks, jnp.zeros((B,), jnp.int32), valid, srv.caches, pt)
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, hist)
    # a second chunk, starting mid-page on row 1
    toks = jnp.asarray(rng.integers(0, V, (B, BUCKET)), jnp.int32)
    start, valid2 = valid, jnp.asarray([100, BUCKET], jnp.int32)
    got = srv._chunk_prefill(srv.params, toks, start, valid2, fresh(), pt)
    _, want = ref(toks, start, valid2, hist, pt)
    cases = {"prefill128": (got, want, None, None)}
    # one decode step from the history
    tok = jnp.asarray(rng.integers(0, V, (B,)), jnp.int32)
    _, logits, got = srv.decode(srv.params, tok, valid, fresh(), pt)
    ref_logits, want = ref(tok[:, None], valid, None, hist, pt)
    cases["decode"] = (got, want, logits, ref_logits)
    for step, (got, want, lg, rl) in cases.items():
        same = jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
            got, want)
        rec = {"pool_equal": all(jax.tree_util.tree_leaves(same))}
        if lg is not None:
            rec["logits_equal"] = bool(np.array_equal(np.asarray(lg),
                                                      np.asarray(rl)))
            rec["logit_gap"] = float(np.abs(np.asarray(lg)
                                            - np.asarray(rl)).max())
        out[f"{step}-{impl}"] = rec
print("EQUIVALENCE " + json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _equivalence(shape: str) -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [here, os.path.join(here, "..", "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    res = subprocess.run(
        [sys.executable, "-c", _EQUIVALENCE_SCRIPT, shape], env=env,
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("EQUIVALENCE ")][-1]
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("step", STEPS)
@pytest.mark.parametrize("shape", SHAPES)
def test_step_matches_per_layer_reference(shape, step, impl):
    rec = _equivalence(shape)[f"{step}-{impl}"]
    assert rec["pool_equal"], rec
    if step == "decode":
        if impl == "gather":
            assert rec["logits_equal"], rec
        else:
            assert rec["logit_gap"] < 1e-4, rec
