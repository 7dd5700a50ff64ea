"""A run's weights, drawn from its seed by the benchmark itself and fed
alike to the program under test and to the plain reference.

Every leaf has a role (``wq``, ``attn_norm``, ``head``, ...) and, inside a
layer, the layer's index; its values come from the seed's key folded with
both, so the reference can draw any one layer on its own. No leaf is left
at a value that hides a path: norm scales are drawn from [0.5, 1.5], the
query and key biases from N(0, 0.5^2) and the value bias from
N(0, 0.05^2), so a program that drops a bias or a norm scale, or adds a
bias after the rotary embedding, serves other tokens. (A value bias as
large as the others survives attention's average over a long context as
one constant vector a layer, and the model then serves one or two tokens
over and over, which no lower precision changes.)

``PROGRAM_ROLES`` maps the program's parameter paths (the last two names of
each leaf) to roles. A leaf it does not name is an error, so a program that
renames a leaf stops the run instead of serving weights nobody checked.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROLES = ("embed", "head", "final_norm", "attn_norm", "mlp_norm", "wq", "wk",
         "wv", "wo", "bq", "bk", "bv", "w_gate", "w_up", "w_down")
MATRICES = ("head", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("final_norm", "attn_norm", "mlp_norm")
BIAS_STD = {"bq": 0.5, "bk": 0.5, "bv": 0.05}
EMBED_STD = 0.02

PROGRAM_ROLES = {
    ("embed", "table"): "embed", ("head", "kernel"): "head",
    ("final_norm", "scale"): "final_norm",
    ("norm1", "scale"): "attn_norm", ("norm2", "scale"): "mlp_norm",
    ("mixer", "wq"): "wq", ("mixer", "wk"): "wk", ("mixer", "wv"): "wv",
    ("mixer", "wo"): "wo", ("mixer", "bq"): "bq", ("mixer", "bk"): "bk",
    ("mixer", "bv"): "bv", ("ffn", "w_gate"): "w_gate",
    ("ffn", "w_up"): "w_up", ("ffn", "w_down"): "w_down",
}


def leaf(key, role: str, layer, shape, dtype):
    """One leaf (or one layer's slice of a stacked leaf) in ``dtype``.
    ``layer`` may be traced, so a stack of layers is one ``vmap``."""
    k = jax.random.fold_in(jax.random.fold_in(key, ROLES.index(role)), layer)
    if role in MATRICES:
        w = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
        w = w / np.sqrt(shape[0])
    elif role in NORMS:
        w = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
    elif role in BIAS_STD:
        w = jax.random.normal(k, shape, jnp.float32) * BIAS_STD[role]
    elif role == "embed":
        w = jax.random.normal(k, shape, jnp.float32) * EMBED_STD
    else:
        raise KeyError(role)
    return w.astype(dtype)


def _names(path):
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def program_params(key, shapes):
    """The program's parameter tree, shaped as ``shapes`` (the
    ``jax.eval_shape`` of its init), with every leaf drawn by ``leaf``.
    Stacked layers sit under ``segments``: one tuple per segment of one
    stack per pattern position, stacked over the segment's periods, so
    layer ``start + period * len(pattern) + position``."""
    def top(path, s):
        names = _names(path)
        return leaf(key, PROGRAM_ROLES[names[-2:]], 0, s.shape, s.dtype)

    out = jax.tree_util.tree_map_with_path(
        top, {k: v for k, v in shapes.items() if k != "segments"})
    segs, start = [], 0
    for seg in shapes.get("segments", []):
        npos = len(seg)
        periods = jax.tree_util.tree_leaves(seg)[0].shape[0]
        stacks = []
        for pos, stack in enumerate(seg):
            layers = start + pos + npos * jnp.arange(periods)

            def stacked(path, s):
                role = PROGRAM_ROLES[_names(path)[-2:]]
                return jax.vmap(lambda i: leaf(key, role, i, s.shape[1:],
                                               s.dtype))(layers)
            stacks.append(jax.tree_util.tree_map_with_path(stacked, stack))
        segs.append(tuple(stacks))
        start += npos * periods
    if "segments" in shapes:
        out["segments"] = segs
    return out
