"""Shared by the stacked-pool step tests (tests/test_stacked_pool.py, on
the CPU) and their compile for a described v5e (tests/test_tpu_compile.py):
the deepseek-7b smoke shapes as the benchmark's smoke run has them (two
layers, paged int8 pages with a max-abs scale a page), the serving step
programs as ``BatchedServer`` jits them, and a scan of their optimized HLO
for whole-pool copies.
"""
import dataclasses
import re

import jax
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.launch.serve import BatchedServer, _jit_on_mesh
from repro.launch.steps import make_chunk_prefill_step, make_decode_step
from repro.models.transformer import init_model

BUCKET = 128


def smoke_server(shape: str, attn_impl: str) -> BatchedServer:
    """``mha``: the deepseek-7b smoke model cut to two layers; ``gqa``: the
    same with 2 KV heads (G 2) and QKV bias."""
    cfg = dataclasses.replace(get_smoke_config("deepseek-7b"), num_layers=2)
    if shape == "gqa":
        cfg = dataclasses.replace(cfg, num_kv_heads=2, attention_bias=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return BatchedServer(cfg, params, batch_size=2, max_len=256, kv_bits=8,
                         page_size=16, kv_scale="page", attn_impl=attn_impl,
                         prefill="bucketed", prefill_bucket=BUCKET)


def step_program(srv, step: str, sharding=None):
    """``(jitted program, argument shapes)`` of the decode step or of one
    ``BUCKET``-token prefill chunk, donating the caches as the server
    does; ``sharding`` places every argument (a described chip). ``srv``
    is a ``BatchedServer`` or anything with its ``cfg``, ``quant``,
    ``attn_impl``, ``params``, ``caches`` (arrays or shapes), ``B`` and
    ``np_max``."""
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32, sharding=sharding)

    params = jax.tree_util.tree_map(sds, srv.params)
    caches = jax.tree_util.tree_map(sds, srv.caches)
    B, NP = srv.B, srv.np_max
    if step == "decode":
        make, arg = make_decode_step, 3
        args = (params, i32(B), i32(B), caches, i32(B, NP))
    else:
        make, arg = make_chunk_prefill_step, 4
        args = (params, i32(1, BUCKET), i32(1), i32(1), caches, i32(1, NP))
    fn = _jit_on_mesh(make(srv.cfg, quant=srv.quant,
                           attn_impl=srv.attn_impl), None, arg)
    return fn, args


def _shape_text(dtype, shape) -> str:
    return f"{np.dtype(dtype).name.replace('int', 's')}[" \
        f"{','.join(map(str, shape))}]"


def pool_copies(hlo: str, pool) -> list:
    """Instructions of the optimized module that slice, update-slice or
    copy a whole stacked pool (``pool``: its ``k_pages``) or one layer's
    slice of it, by opcode or by the name of the fusion they form."""
    shapes = (_shape_text(pool.dtype, pool.shape),
              _shape_text(pool.dtype, pool.shape[1:]))
    bad = []
    for ln in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+?)\{[^}]*\} (\S+?)\(", ln)
        if m and m.group(2) in shapes and re.search(
                r"dynamic-slice|dynamic-update-slice|copy",
                m.group(1) + " " + m.group(3)):
            bad.append(ln.strip()[:160])
    return bad


def unaliased_pool_params(hlo: str, pool) -> list:
    """Entry parameters of the stacked pool's shape (``k_pages`` and
    ``v_pages``) that the module's ``input_output_alias`` does not alias to
    an output, and how many such parameters there are."""
    shape = _shape_text(pool.dtype, pool.shape)
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    params = [int(n) for n in re.findall(
        re.escape(shape) + r"\{[^}]*\} parameter\((\d+)\)", entry)]
    header = hlo.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}",
                                          header.split(
                                              "input_output_alias=")[-1])}
    return [p for p in params if p not in aliased], len(params)
