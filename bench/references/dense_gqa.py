"""Plain float32 reference of a dense decoder block with grouped-query
attention: pre-norm RMSNorm, Q/K/V projections (with bias where the
configuration has ``attention_bias``), rotary embeddings on the two halves
of each head (the Qwen2 / DeepSeek-LLM convention), causal softmax
attention, an output projection, and a SwiGLU feed-forward. A final RMSNorm
and an untied LM head give the logits.

It imports nothing of the program and takes nothing the program made. Its
weights are the benchmark's own (``bench/weights.py``): drawn from the
seed's key layer by layer, the same values the harness fed the program,
rounded to the configuration's weight dtype and computed in float32 at
``highest`` matmul precision.

It runs layer by layer over all the sequences it is given, so only one
layer's weights are on the device at a time; sequences are padded to whole
``PAD`` blocks (padding sits after every real token, so causal attention
never lets it in).

``kv_bits`` > 0 gives the control: the same forward with K and V rounded to
a ``kv_bits`` integer grid per page of ``page_size`` positions, on one
max-abs scale per page, as a quantized paged cache would hold them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

PAD = 1024
PICK_PAD = 512
QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    bias: bool
    weight_dtype: str
    page_size: int

    @classmethod
    def of(cls, conf: dict, page_size: int):
        heads = conf["num_attention_heads"]
        return cls(conf["num_hidden_layers"], conf["hidden_size"], heads,
                   conf["num_key_value_heads"],
                   conf.get("head_dim") or conf["hidden_size"] // heads,
                   conf["intermediate_size"], conf["vocab_size"],
                   float(conf["rms_norm_eps"]), float(conf["rope_theta"]),
                   bool(conf.get("attention_bias", False)),
                   conf["dtype"]["weights"], page_size)


def _draw(key, role, layer, shape, dims: Dims):
    dt = jnp.dtype(dims.weight_dtype)
    return weights.leaf(key, role, layer, shape, dt).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer_weights(key, layer, dims: Dims):
    d, hhd = dims.d, dims.heads * dims.head_dim
    kvhd = dims.kv_heads * dims.head_dim
    shapes = {"attn_norm": (d,), "mlp_norm": (d,), "wq": (d, hhd),
              "wk": (d, kvhd), "wv": (d, kvhd), "wo": (hhd, d),
              "w_gate": (d, dims.ff), "w_up": (d, dims.ff),
              "w_down": (dims.ff, d)}
    if dims.bias:
        shapes.update(bq=(hhd,), bk=(kvhd,), bv=(kvhd,))
    return {r: _draw(key, r, layer, s, dims) for r, s in shapes.items()}


@functools.partial(jax.jit, static_argnums=(1,))
def _embed_head(key, dims: Dims):
    return (_draw(key, "embed", 0, (dims.vocab, dims.d), dims),
            _draw(key, "head", 0, (dims.d, dims.vocab), dims),
            _draw(key, "final_norm", 0, (dims.d,), dims))


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale


def _rope(x, theta):
    t, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _page_quant(x, bits, ps):
    t = x.shape[0]
    pages = x.reshape(t // ps, ps, *x.shape[1:])
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(pages), axis=(1, 2, 3), keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(pages / scale), -qmax - 1, qmax) * scale
    return q.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(w, x, dims: Dims, kv_bits: int):
    t = x.shape[0]
    h, kvh, hd = dims.heads, dims.kv_heads, dims.head_dim
    g = h // kvh
    a = _rms(x, w["attn_norm"], dims.eps)
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    if dims.bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(t, h, hd), dims.theta)
    k = _rope(k.reshape(t, kvh, hd), dims.theta)
    v = v.reshape(t, kvh, hd)
    if kv_bits:
        k = _page_quant(k, kv_bits, dims.page_size)
        v = _page_quant(v, kv_bits, dims.page_size)
    qb = q.reshape(t // QUERY_BLOCK, QUERY_BLOCK, kvh, g, hd) / np.sqrt(hd)
    kpos = jnp.arange(t)

    def attend(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(attend, (jnp.arange(t // QUERY_BLOCK), qb))
    x = x + o.reshape(t, h * hd) @ w["wo"]
    a = _rms(x, w["mlp_norm"], dims.eps)
    return x + (jax.nn.silu(a @ w["w_gate"]) * (a @ w["w_up"])) @ w["w_down"]


@jax.jit
def _read(x, idx, head, norm, picks, eps):
    lg = _rms(x[idx], norm, eps) @ head                      # (PICK_PAD, V)
    return (lg.max(-1), jnp.take_along_axis(lg, picks.T, axis=1).T,
            lg.argmax(-1))


class Reference:
    """``score`` runs the forward over whole sequences and reads, at the
    given positions, each position's best logit, its argmax, and the logit
    of each token id in ``picks``."""

    def __init__(self, conf: dict, key, page_size: int):
        self.dims = Dims.of(conf, page_size)
        self.key = key

    def score(self, seqs: Sequence[np.ndarray], positions: Sequence[np.ndarray],
              picks: Sequence[np.ndarray], kv_bits: int = 0) -> List[dict]:
        """``seqs[j]``: token ids; ``positions[j]``: (n,) positions to read
        (n <= PICK_PAD); ``picks[j]``: (k, n) token ids whose logits to
        read there. Returns per sequence ``best`` (n,), ``picked`` (k, n)
        and ``argmax`` (n,) as numpy."""
        dims = self.dims
        with jax.default_matmul_precision("highest"):
            emb, head, norm = _embed_head(self.key, dims)
            xs = []
            for s in seqs:
                t = -(-len(s) // PAD) * PAD
                ids = np.zeros((t,), np.int32)
                ids[:len(s)] = s
                xs.append(emb[jnp.asarray(ids)])
            del emb
            for i in range(dims.layers):
                w = _layer_weights(self.key, i, dims)
                xs = [_block(w, x, dims, kv_bits) for x in xs]
                del w
            out = []
            for x, pos, pk in zip(xs, positions, picks):
                n = len(pos)
                idx = np.zeros((PICK_PAD,), np.int32)
                idx[:n] = pos
                pp = np.zeros((pk.shape[0], PICK_PAD), np.int32)
                pp[:, :n] = pk
                best, picked, arg = _read(x, jnp.asarray(idx), head, norm,
                                          jnp.asarray(pp), dims.eps)
                out.append({"best": np.asarray(best)[:n],
                            "picked": np.asarray(picked)[:, :n],
                            "argmax": np.asarray(arg)[:n]})
        return out
