"""Whether what the timed path served is right.

Once the window has closed and the program's state is freed, a sample of
the requests that finished, drawn from the seed and always holding the
longest, is run through the configuration's plain float32 reference
(``bench/references/<reference>.py``): each prompt with the tokens served
for it. Decoding is greedy, so every served token should be the
reference's best there, up to rounding. The number compared is the widest
gap by which a served token's reference logit lies below the reference's
best logit at its position, against the configuration's
``check.max_logit_gap``. A control run (``bench/calibrate.py``)
puts a lower-precision reference in the program's place and has to come
out not correct.
"""
from __future__ import annotations

import importlib
from typing import List

import numpy as np

from bench import traffic


def sample(records, seed: int, spec: dict) -> List:
    """Finished window requests: the longest, then others in an order
    drawn from the seed, until ``spec["requests"]`` requests or
    ``spec["tokens"]`` served tokens."""
    done = [r for r in records if r.window and r.req is not None
            and r.req.done and r.req.error is None and r.req.out]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.prompt) + len(r.req.out))
    order = traffic.stream(seed, "check").permutation(len(done))
    picked, tokens = [longest], len(longest.req.out)
    for i in order:
        if len(picked) >= spec["requests"] or tokens >= spec["tokens"]:
            break
        r = done[i]
        if r is not longest:
            picked.append(r)
            tokens += len(r.req.out)
    return picked


def reference(conf: dict, key):
    mod = importlib.import_module(f"bench.references.{conf['reference']}")
    return mod.Reference(conf, key, conf["server"]["page_size"])


def served(picked):
    """(sequences, positions read, served tokens) for the reference."""
    seqs, pos, toks = [], [], []
    for r in picked:
        p, out = np.asarray(r.req.prompt), np.asarray(r.req.out, np.int32)
        seqs.append(np.concatenate([p, out[:-1]]).astype(np.int32))
        pos.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
        toks.append(out)
    return seqs, pos, toks


def check(conf: dict, key, seed: int, records, spec: dict, vocab: int,
          control_bits: int = 0) -> dict:
    """The verdict on a run's served tokens. With ``control_bits`` > 0 the
    control takes the program's place: the reference with its KV on a
    ``control_bits`` grid, read at the same positions of the same prompts
    and served tokens, where its number is the widest gap of the token it
    puts first. That number then decides, by the same limit, and the
    program's own number is only reported (``served_max_logit_gap``)."""
    limit = conf["check"]["max_logit_gap"]
    picked = sample(records, seed, spec)
    if not picked:
        return {"ok": False, "checks": {
            "sampled_tokens_at_least": {"value": 0, "limit": 1},
            "max_logit_gap": {"value": None, "limit": limit}}}
    seqs, pos, toks = served(picked)
    in_vocab = all(((t >= 0) & (t < vocab)).all() for t in toks)
    served_gap = control = None
    if in_vocab:
        ref = reference(conf, key)
        picks = [t[None] for t in toks]
        if control_bits:
            low = ref.score(seqs, pos, picks, kv_bits=control_bits)
            picks = [np.stack([t, c["argmax"]]) for t, c in zip(toks, low)]
        res = ref.score(seqs, pos, picks)
        served_gap = float(max((r["best"] - r["picked"][0]).max()
                               for r in res))
        if control_bits:
            control = float(max((r["best"] - r["picked"][1]).max()
                                for r in res))
    gap = control if control_bits else served_gap
    n = int(sum(len(t) for t in toks))
    out = {"ok": gap is not None and gap <= limit, "checks": {
        "sampled_tokens_at_least": {"value": n, "limit": 1},
        "max_logit_gap": {"value": gap, "limit": limit}}}
    if control_bits:
        out["control"] = {"bits": control_bits,
                          "served_max_logit_gap": served_gap}
    return out
