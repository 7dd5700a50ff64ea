"""The program's spans and device programs read from a trace
(``bench/program_trace.py``): the readings from a hand-made trace, gap
labels, that ``reduce`` reads the same events as before, a real CPU
profiler session, and one rehearsed run of the command."""
import json
import os
import subprocess
import sys

import pytest

from bench import program_trace as pt
from bench import reduce

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(FIX, "trace_program_spans.json")) as f:
        return json.load(f)["events"]


@pytest.fixture(scope="module")
def prog(events):
    return pt.reduce_program(events)


def test_decode_span_p90_from_spans_ending_in_the_window(prog):
    # ending in 0.1..1.1 s: 0.05..0.15, 0.26..0.9, 0.96..0.99; the spans
    # ending at 0.06 and 1.19 are out. p90 of [0.03, 0.1, 0.64]:
    # 0.1 + 0.8 * 0.54
    assert sorted(prog["spans"]["serve.decode_span"]) == pytest.approx(
        [0.03, 0.1, 0.64])
    assert pt.decode_span_p90_s(prog) == pytest.approx(0.532)


def test_program_times_from_executions_starting_in_the_window(prog):
    # decode: 0.3..0.4, 0.46..0.585, 0.6..0.68, 1.05..1.15 (the one from 0
    # starts before the window); median of [0.08, 0.1, 0.1, 0.125]
    assert pt.decode_step_ms(prog) == pytest.approx(100.0)
    # prefill: 0.13..0.23 and 0.24..0.3, median of [0.06, 0.1]
    assert pt.prefill_chunk_ms(prog) == pytest.approx(80.0)
    # device time clipped to the window: 0.02 + 0.1 + 0.125 + 0.08 + 0.05
    assert prog["module_s"] == pytest.approx(
        {"jit_decode_step": 0.375, "jit_chunk_prefill_step": 0.16,
         "jit_step": 0.32})


def test_gaps_take_the_innermost_program_span(prog):
    assert prog["idle_gaps"] == [
        ["serve.decode_step", pytest.approx(0.06)],   # 0.4..0.46
        ["wait_arrival", pytest.approx(0.05)],        # no program span
        ["serve.dispatch", pytest.approx(0.015)],     # 0.585..0.6
        ["serve.decode_span", pytest.approx(0.01)],   # 0.12..0.13
    ]


def test_reduce_reads_the_ops_line_alone(events):
    ops = pt.ops_events(events)
    assert {e["line"] for e in ops if e["plane"].startswith("/device:")} \
        == {"XLA Ops"}
    assert not any(e["name"].startswith("serve.") for e in ops)
    r = reduce.reduce(ops, kernel="paged_kv_attention")
    # union of ops in 0.1..1.1: 0.02 + 0.27 + 0.125 + 0.4 + 0.05
    assert r["busy_s"] == pytest.approx(0.865)
    assert r["kernel_s"] == pytest.approx(0.125)
    assert not any(n.startswith("jit_") for n, _ in r["device_ops"])
    # the same gaps as reduce_program, under the harness's labels
    assert [lbl for lbl, _ in r["idle_gaps"]] == [
        "tick", "wait_arrival", "decode", "tick"]
    assert [s for _, s in r["idle_gaps"]] == pytest.approx(
        [s for _, s in pt.reduce_program(events)["idle_gaps"]])


def test_nothing_to_read_gives_none(events):
    assert pt.reduce_program([]) is None
    # a trace of a program without named steps or spans (the readings
    # must return None, not raise)
    bare = [e for e in events if not e["name"].startswith(
        ("serve.", "jit_decode_step", "jit_chunk_prefill_step"))]
    prog = pt.reduce_program(bare)
    for read in pt.READINGS.values():
        assert read(prog) is None
    assert {lbl for lbl, _ in prog["idle_gaps"]} <= {
        "tick", "wait_arrival", "decode"}
    out = pt.summary(prog, busy_s=0.865)
    assert out["steps_share_of_busy"] == 0.0
    assert pt.summary(None, busy_s=None) == {n: None for n in pt.READINGS}


def test_summary_shares_and_counts(prog):
    out = pt.summary(prog, busy_s=0.865)
    assert out["decode_step_ms"] == pytest.approx(100.0)
    assert out["steps_share_of_busy"] == pytest.approx(
        100.0 * (0.375 + 0.16) / 0.865)
    assert out["span_s"]["serve.decode_step"] == [2, pytest.approx(0.34)]
    assert out["span_s"]["serve.tick"] == [2, pytest.approx(0.96)]


def test_program_of_strips_the_fingerprint():
    assert pt.program_of("jit_decode_step(1234567)") == "jit_decode_step"
    assert pt.program_of("jit_fused_step") == "jit_fused_step"


def test_load_events_keeps_program_and_harness_spans(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("serve.decode_span", steps=3):
                with jax.profiler.TraceAnnotation("unrelated"):
                    pass
    ev = pt.load_events(str(tmp_path))
    assert sorted(e["name"] for e in ev) == ["bench.window",
                                             "serve.decode_span"]
    assert pt.ops_events(ev) == [e for e in ev
                                 if e["name"] == "bench.window"]
    assert pt.load_events(str(tmp_path / "none")) == []


def test_command_rehearsal_reads_the_program_spans():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/program_trace.py",
                        "--workload", "deepseek-7b-pp4.chat", "--seed",
                        str(2**31 + 11), "--seconds", "2", "--rehearse"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] and list(r)[-1] == "checks"
    assert {"ttft_p50_s", "tpot_p90_ms", "output_tok_s", "setup_s",
            "decode_rows_mean"} <= set(r["metrics"])
    prog = r["program"]
    assert prog["decode_span_p90_s"] > 0
    for name in ("serve.tick", "serve.admission", "serve.admit",
                 "serve.prefill_chunk", "serve.decode_span",
                 "serve.decode_step", "serve.dispatch", "serve.span_fetch",
                 "serve.retire"):
        assert prog["span_s"][name][0] > 0, name
    # no device plane on the CPU: no program times, no gaps
    assert prog["decode_step_ms"] is None and prog["module_s"] == {}
    assert prog["idle_gaps"] == []
