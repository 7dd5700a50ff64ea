"""Step functions shared by dryrun / train / serve.

Everything here is mesh-agnostic pure functions; launchers wrap them in
jax.jit with shardings from parallel.sharding.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..models.transformer import (forward, forward_hidden, init_cache,
                                  init_model, train_loss)
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from ..optim.compress import CompressionConfig, compress_gradients, \
    error_feedback_init


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    lr: float = 3e-4
    adamw: AdamWConfig = AdamWConfig()
    lb_coeff: float = 0.01
    grad_compress: Optional[CompressionConfig] = None


def init_train_state(key, cfg, hp: TrainHParams):
    params = init_model(key, cfg)
    state = {"params": params, "opt": adamw_init(params, hp.adamw)}
    if hp.grad_compress is not None:
        state["ef_residual"] = error_feedback_init(params)
    return state


def make_train_step(cfg, hp: TrainHParams, *, quant=None):
    """Returns fn(state, batch) -> (state, metrics)."""

    def step(state, batch):
        def loss_fn(params):
            return train_loss(params, batch, cfg, quant=quant,
                              lb_coeff=hp.lb_coeff)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        if hp.grad_compress is not None:
            grads, new_res = compress_gradients(
                grads, state["ef_residual"], hp.grad_compress)
        params, opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], hp.lr, hp.adamw)
        new_state = {"params": params, "opt": opt}
        if hp.grad_compress is not None:
            new_state["ef_residual"] = new_res
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return new_state, metrics

    return step


def make_prefill_step(cfg, *, max_len: int, quant=None):
    """fn(params, batch) -> (last_logits, caches). Encoder archs return
    (logits, None) — a plain forward.

    This is the dryrun/whole-prompt prefill against a fresh DENSE cache; the
    serving path prefills incrementally into a shared paged pool via
    ``make_chunk_prefill_step`` below."""

    def step(params, batch):
        if cfg.family == "encoder":
            _, logits, _, _ = forward(params, batch, cfg, quant=quant)
            return logits, None
        B = (batch["tokens"].shape[0] if "tokens" in batch
             else batch["embeds"].shape[0])
        caches = init_cache(cfg, B, max_len, quant)
        _, logits, caches, _ = forward(params, batch, cfg, quant=quant,
                                       caches=caches, cache_pos=0)
        return logits[:, -1], caches

    return step


def make_chunk_prefill_step(cfg, *, quant=None, attn_impl: str = "gather"):
    """fn(params, tokens (Bp, S), start_pos (Bp,), valid_len (Bp,), caches,
    page_table (Bp, NP)) -> caches.

    One **bucketed prefill** program: runs a whole prompt chunk through the
    backbone in a single forward, quantizing K/V per layer and scattering the
    chunk into the paged pool via the page table. ``Bp`` is the number of
    stacked same-bucket prompt rows (multi-request batched prefill — each
    row carries its own page table, start position, and valid length) and
    ``S`` the bucket size (callers pad prompts up to a power-of-two bucket
    and jit retraces per bucket, so a max bucket of 2^k costs at most k+1
    compilations per row count); only the first ``valid_len`` tokens of a
    row are real — padded tails are masked out of the pool write
    (scratch-page redirect) and their hidden states are garbage that nobody
    reads. Skips the LM head entirely (prefill logits are never sampled; the
    decode step consumes the last prompt token), which is why this wraps
    ``forward_hidden`` and not ``forward``.

    The returned function is named ``chunk_prefill_step`` (likewise
    ``decode_step`` below), so its XLA module is
    ``jit_chunk_prefill_step`` in compiles and profiler traces.

    ``attn_impl`` routes the chunk's attention reads exactly like decode
    ("gather" = jnp bitwise reference, "pallas" = the variable-length paged
    chunk kernel) — prefill and decode share ONE attention entry point
    (``models.attention.route_paged_attention``).

    Prefix sharing composes here for free: a prefix-cache hit aliases the
    shared pages into the slot's page table and the server calls this step
    with ``start_pos`` at the first NON-shared token — fully cached pages
    never see a forward (O(suffix/bucket) admission), while the chunk's
    attention still reads the shared history through the same page table.
    """
    def chunk_prefill_step(params, tokens, start_pos, valid_len, caches,
                           page_table):
        batch = {"tokens": tokens}
        _, aux = forward_hidden(params, batch, cfg, quant=quant,
                                caches=caches, cache_pos=start_pos,
                                page_table=page_table, attn_impl=attn_impl,
                                kv_valid_len=valid_len)
        return aux["caches"]

    return chunk_prefill_step


def make_decode_step(cfg, *, quant=None, greedy: bool = True,
                     attn_impl: str = "gather"):
    """fn(params, tokens (B,), pos, caches, page_table=None) ->
    (next_tokens, logits, caches).

    One new token per sequence against a preallocated cache — the function
    the decode_32k / long_500k cells lower. ``pos`` is a scalar (shared
    clock) or (B,) per-sequence lengths; ``page_table`` (B, NP) drives a
    paged cache (see core.paged_kv); ``attn_impl`` ("gather" | "pallas")
    picks the paged attention backend (models.attention.gqa_apply)."""

    def decode_step(params, tokens, pos, caches, page_table=None):
        batch = {"tokens": tokens[:, None]}
        _, logits, caches, _ = forward(params, batch, cfg, quant=quant,
                                       caches=caches, cache_pos=pos,
                                       page_table=page_table,
                                       attn_impl=attn_impl)
        logits = logits[:, 0]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, caches

    return decode_step


def make_embed_decode_step(cfg, *, quant=None):
    """Decode step for frontend-stub archs (inputs are embeds)."""

    def step(params, embeds, pos, caches):
        batch = {"embeds": embeds}
        _, logits, caches, _ = forward(params, batch, cfg, quant=quant,
                                       caches=caches, cache_pos=pos)
        logits = logits[:, 0]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, caches

    return step
