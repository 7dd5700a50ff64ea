"""Whole runs of the harness on the CPU at smoke sizes (the rehearsal
path: interpret-mode kernel, no look for a chip): a sound run comes out
correct, its control (the reference with int4 KV in the program's place)
comes out not correct, and so does a run whose served tokens are altered
where they are produced. The chip command never takes
this path; ``bench/run.py`` exits 2 on a CPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sound():
    return harness.run("deepseek-7b-pp4.docqa", 2**31 + 5, 2.0, True,
                       time.perf_counter(), smoke=True)


def test_sound_run_is_correct(sound):
    c = sound["checks"]
    assert sound["correct"], c
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_control_fails_the_limit():
    r = harness.run("deepseek-7b-pp4.chat", 2**31 + 5, 2.0, False,
                    time.perf_counter(), smoke=True, control_bits=4)
    assert not r["correct"]
    c = r["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
    # the program's own tokens in that run were sound
    assert r["control"]["served_max_logit_gap"] <= c["limit"]
    assert list(r)[-1] == "checks"


def test_result_names_device_and_per_layer_metrics(sound):
    assert sound["device"]["platform"] == "cpu"
    assert {"count", "kind", "memory_peak_bytes"} <= set(sound["device"])
    assert list(sound)[-1] == "checks"
    m = sound["metrics"]
    for name in ("decode_rows_mean", "queue_wait_p90_s",
                 "kv_pages_used_peak", "prefix_hit_share"):
        assert name in m and m[name]["value"] >= 0
    # no device trace on the CPU: device metrics are left out, never 0
    for name in ("paged_attn_roofline", "device_idle_share", "step_mfu"):
        assert name not in m


def test_altered_token_is_not_correct():
    r = harness.run("deepseek-7b-pp4.chat", 2**31 + 9, 2.0, False,
                    time.perf_counter(), smoke=True, fault="token")
    assert not r["correct"]
    c = r["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
    assert set(r["metrics"]) == {"ttft_p50_s", "tpot_p90_ms", "output_tok_s",
                                 "setup_s"}


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "deepseek-7b-pp4.chat", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "not 'tpu'" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
