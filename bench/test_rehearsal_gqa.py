"""Whole runs of the grouped-query cell on the CPU at smoke sizes (the
rehearsal path: interpret-mode kernel, no look for a chip). The smoke copy
of ``qwen2-72b-pp16`` keeps what sets it apart from the MHA cells: two
query heads a KV head and drawn Q/K/V biases. A sound run comes out
correct; its control (the reference with int4 KV in the program's place)
does not; nor does a run whose program is fed zeroed biases while the
reference keeps its own, so the check sees the bias path."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness

CELL = "qwen2-72b-pp16.chat"
SEED = 2**31 + 15
BIASES = ("bq", "bk", "bv")


def _run(**kw):
    return harness.run(CELL, SEED, 2.0, False, time.perf_counter(),
                       smoke=True, **kw)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_smoke_cell_is_grouped_with_biases():
    cell = harness.load_cell(CELL, trace=False, smoke=True)
    mcfg = harness.model_config(cell.conf)
    assert mcfg.num_heads // mcfg.num_kv_heads == 2
    assert mcfg.attention_bias


def test_sound_gqa_run_is_correct(sound):
    c = sound["checks"]
    assert sound["correct"], c
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_gqa_control_fails_the_limit():
    r = _run(control_bits=4)
    assert not r["correct"]
    c = r["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
    # the program's own tokens in that run were sound
    assert r["control"]["served_max_logit_gap"] <= c["limit"]


def test_zeroed_biases_are_not_correct(monkeypatch):
    drawn = harness.init_params

    def without_biases(key, mcfg, mesh):
        def zero(path, x):
            name = getattr(path[-1], "key", None)
            return jnp.zeros_like(x) if name in BIASES else x
        return jax.tree_util.tree_map_with_path(zero,
                                                drawn(key, mcfg, mesh))

    monkeypatch.setattr(harness, "init_params", without_biases)
    r = _run()
    assert r["failed"] == 0
    assert not r["correct"]
    c = r["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
