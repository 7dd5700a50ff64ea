"""The one traffic generator. A traffic mix is a JSON file of parameters,
``bench/traffic/<mix>.json``; this module reads any of them.

Arrivals are in seconds on the wall clock. A window of ``T`` seconds at
``rate_rps`` holds ``round(rate_rps * T)`` requests whose arrival times are
uniform order statistics on ``[0, T)`` (a Poisson process conditioned on
its count).

Every seed gets the same work at the same times: the sizes (prompt and
output lengths, documents' lengths, which document each request asks
about) and the arrival times are drawn once from the mix's own
``shape_seed``, and the run's ``--seed`` draws every token id (and, in the
harness, the weights). The server's spans end when the earliest live row
finishes, so a request's wait for admission swings by seconds with the
order of lengths; a seed that reordered them would change the work, not
only its content.

Mix keys:

- ``rate_rps``: offered load in the window; ``lead_in_s``: seconds of the
  same traffic, from its own streams, that run before the window opens, so
  the window starts in steady state (counted as set-up).
- ``prompt``/``output``: lognormal lengths by their mean (``mu = ln(mean) -
  sigma^2/2``), rounded and clipped to ``[min, cap]``.
- ``documents`` (optional): ``count`` documents with lognormal ``length``;
  each request picks one by Zipf(``zipf_a``) over their ranks and adds a
  fresh question of ``question.min``..``question.max`` tokens. Without it
  prompts are unique.
- ``check``: how many finished requests, and at least how many of their
  served tokens, the correctness check samples.
- ``smoke``: overrides for the CPU rehearsal's smoke-size models.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


@dataclasses.dataclass
class Arrival:
    due_s: float          # relative to the window's start (negative: lead-in)
    prompt: np.ndarray    # (P,) int32
    max_new: int
    doc: int = -1         # document index, -1 for a unique prompt


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_mix(name: str, smoke: bool = False) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        mix = json.load(f)
    if smoke:
        mix = merge(mix, mix.get("smoke", {}))
    return mix


def stream(seed: int, *tags: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    words = [int(seed) % 2**64] + [int.from_bytes(t.encode(), "little")
                                   for t in tags]
    return np.random.default_rng(np.random.SeedSequence(words))


def lognormal_lengths(rng: np.random.Generator, spec: dict,
                      n: int) -> np.ndarray:
    mu = np.log(spec["mean"]) - 0.5 * spec["sigma"] ** 2
    x = np.rint(rng.lognormal(mu, spec["sigma"], n))
    return np.clip(x, spec.get("min", 1), spec["cap"]).astype(np.int64)


def zipf_choice(rng: np.random.Generator, n_items: int, a: float,
                n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n_items + 1) ** a
    return rng.choice(n_items, size=n, p=w / w.sum())


def documents(mix: dict, seed: int, vocab: int) -> List[np.ndarray]:
    spec = mix.get("documents")
    if not spec:
        return []
    lens = lognormal_lengths(stream(mix["shape_seed"], "doc-lengths"),
                             spec["length"], spec["count"])
    rng = stream(seed, "doc-tokens")
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def _phase(mix: dict, rate: float, seconds: float, start: float, tag: str,
           seed: int, vocab: int, docs: List[np.ndarray]) -> List[Arrival]:
    n = max(1, int(round(rate * seconds)))
    shape = stream(mix["shape_seed"], tag)
    times = np.sort(shape.uniform(0.0, seconds, n))
    outs = lognormal_lengths(shape, mix["output"], n)
    if docs:
        spec = mix["documents"]
        picks = zipf_choice(shape, len(docs), spec["zipf_a"], n)
        qlens = shape.integers(spec["question"]["min"],
                               spec["question"]["max"] + 1, n)
        sizes = list(zip(picks.tolist(), qlens.tolist(), outs.tolist()))
    else:
        plens = lognormal_lengths(shape, mix["prompt"], n)
        sizes = list(zip([-1] * n, plens.tolist(), outs.tolist()))
    tok = stream(seed, tag, "tokens")
    out = []
    for due, (doc, plen, max_new) in zip(start + times, sizes):
        fresh = tok.integers(0, vocab, plen).astype(np.int32)
        prompt = np.concatenate([docs[doc], fresh]) if doc >= 0 else fresh
        out.append(Arrival(float(due), prompt, int(max_new), doc))
    return out


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None):
    """(lead-in arrivals, window arrivals), both sorted by due time."""
    rate = mix["rate_rps"] if rate is None else rate
    docs = documents(mix, seed, vocab)
    lead_s = float(mix.get("lead_in_s", 0.0))
    lead = (_phase(mix, rate, lead_s, -lead_s, "lead-in", seed, vocab, docs)
            if lead_s > 0 else [])
    window = _phase(mix, rate, seconds, 0.0, "window", seed, vocab, docs)
    return lead, window
