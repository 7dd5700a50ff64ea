"""The benchmark's yardstick on the CPU, without a chip: traffic, the
percentile rule, the kernel's and the model's operation and byte counts,
the trace reduction and the device checks."""
import json
import os
import types

import numpy as np
import pytest

from bench import costs, harness, peaks, reduce, stats, traffic

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


# -- traffic ------------------------------------------------------------------
@pytest.mark.parametrize("mix", ["chat-c3072", "chat-c3584", "docqa-32docs"])
def test_traffic_same_seed_same_requests(mix):
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, 2**31 + 123, 40.0, 1000)
    b = traffic.schedule(m, 2**31 + 123, 40.0, 1000)
    for x, y in zip(a[0] + a[1], b[0] + b[1]):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat-c3072", "docqa-32docs"])
def test_traffic_arrivals_in_seconds(mix):
    m = traffic.load_mix(mix)
    lead, window = traffic.schedule(m, 7, 40.0, 1000, rate=2.0)
    assert len(window) == 80
    due = [a.due_s for a in window]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    assert len(lead) == round(2.0 * m["lead_in_s"])
    assert all(-m["lead_in_s"] <= a.due_s < 0 for a in lead)


@pytest.mark.parametrize("mix", ["chat-c3072", "docqa-32docs"])
def test_traffic_seeds_share_work_not_tokens(mix):
    """Every seed gets the same sizes at the same times; the seed draws
    the token ids."""
    m = traffic.load_mix(mix)
    for a, b in zip(*(traffic.schedule(m, s, 40.0, 1000)[1] for s in (1, 2))):
        assert (a.due_s, len(a.prompt), a.max_new, a.doc) == \
            (b.due_s, len(b.prompt), b.max_new, b.doc)
        assert not np.array_equal(a.prompt, b.prompt)


def test_traffic_documents_shared_and_capped():
    m = traffic.load_mix("docqa-32docs")
    _, window = traffic.schedule(m, 5, 40.0, 1000)
    docs = traffic.documents(m, 5, 1000)
    assert len(docs) == m["documents"]["count"]
    for a in window:
        d = docs[a.doc]
        assert np.array_equal(a.prompt[:len(d)], d)
        q = len(a.prompt) - len(d)
        assert m["documents"]["question"]["min"] <= q \
            <= m["documents"]["question"]["max"]
    picks = np.bincount([a.doc for a in window], minlength=len(docs))
    assert picks[0] == picks.max() and picks[0] > 1


# -- percentiles --------------------------------------------------------------
def test_percentile_interpolates():
    assert stats.percentile([3.0, 1.0, 2.0, 4.0], 50) == 2.5
    assert stats.percentile([1.0], 90) == 1.0
    assert stats.percentile([], 90) is None


def test_missing_counts_as_latest():
    got = [0.5, 1.0, 2.0]
    late = stats.latest_for_missing(got, waited=1.5)
    assert late > max(got) and late > 1.5
    xs = got + [late] * 2
    assert stats.percentile(xs, 90) > max(got)


def test_ttft_counts_requests_without_a_token():
    rec = lambda due, first: harness.Record(None, due, True, first=first)  # noqa
    run = types.SimpleNamespace(
        window_records=[rec(0.0, 0.5), rec(1.0, 2.0), rec(2.0, None)],
        t_drain_end=5.0)
    from bench import latency
    v = latency.ttfts(run)
    assert v[:2] == [0.5, 1.0] and v[2] > 3.0


def test_spread_is_interquartile_over_median():
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    v = [9.0, 10.0, 10.0, 11.0, 10.0, 10.0]
    assert 0.0 < stats.spread(v) < 0.2


# -- operation and byte counts ------------------------------------------------
SHAPE = costs.Shape(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16,
                    d_ff=128, vocab=100, page_size=16, kv_bytes=1.0)


def test_kernel_count_from_live_kv_lengths():
    flops, nbytes = costs.attention_call(SHAPE, [(1, 17)])
    assert flops == 4 * 4 * 16 * 17
    # 17 tokens of int8 K and V on 2 heads, 2 pages of K and V scales,
    # bf16 queries and f32 outputs of 4 heads
    assert nbytes == 2 * 17 * 2 * 16 + 2 * 2 * 4 + 4 * 16 * 6


def test_kernel_count_ignores_pages_no_row_holds():
    short = costs.attention_call(SHAPE, [(1, 10)])
    assert short == costs.attention_call(SHAPE, [(1, 10)])
    two = costs.attention_call(SHAPE, [(1, 10), (1, 30)])
    one = costs.attention_call(SHAPE, [(1, 30)])
    assert two[0] == short[0] + one[0] and two[1] == short[1] + one[1]


def test_prefill_count_is_causal():
    flops, _ = costs.attention_call(SHAPE, [(3, 10)])
    # queries at positions 7, 8, 9 see 8, 9, 10 keys
    assert flops == 4 * 4 * 16 * (8 + 9 + 10)


def test_least_time_takes_the_larger_bound():
    pk = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    f, b = costs.attention_call(SHAPE, [(1, 1000)])
    assert costs.least_time(SHAPE, pk, [[(1, 1000)]]) == \
        pytest.approx(2 * max(f / 1e12, b / 1e9))


def test_model_flops_counts_every_layer_and_the_head():
    d = costs.dense_flops_per_token(SHAPE)
    attn, _ = costs.attention_call(SHAPE, [(1, 5)])
    assert costs.model_flops(SHAPE, [(1, 5)], head=True) == \
        2 * (d + attn) + 2 * 64 * 100
    assert costs.model_flops(SHAPE, [(1, 5)], head=False) == 2 * (d + attn)


# -- trace reduction ----------------------------------------------------------
def test_reduce_synthetic_trace():
    with open(os.path.join(FIX, "trace_synthetic.json")) as f:
        ev = json.load(f)["events"]
    r = reduce.reduce(ev, kernel="paged_kv_attention")
    # window 0.1..1.1 s; fusion.1 clipped to 0.1..0.15; kernel 0.3..0.7;
    # fusion.1 0.6..0.8 overlaps it; fusion.2 lies outside
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(0.05 + 0.5)
    assert r["kernel_s"] == pytest.approx(0.4)
    ops = dict(r["device_ops"])
    assert ops["paged_kv_attention.3"] == pytest.approx(0.4)
    assert ops["fusion.1"] == pytest.approx(0.25)
    assert "fusion.2" not in ops
    gaps = r["idle_gaps"]
    assert gaps[0] == ["tick", pytest.approx(0.3)]          # 0.8..1.1
    assert gaps[1] == ["admit", pytest.approx(0.15)]        # 0.15..0.3
    assert len(gaps) == 2


def test_reduce_recorded_v5e_trace():
    """40 ms of a real window: a prefill chunk's ops on one v5e."""
    with open(os.path.join(FIX, "trace_v5e_qwen_chat.json")) as f:
        ev = json.load(f)["events"]
    r = reduce.reduce(ev, kernel="paged_kv_attention")
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.04)
    assert r["busy_s"] == pytest.approx(0.03869459, rel=1e-6)
    assert r["kernel_s"] == pytest.approx(0.020799444, rel=1e-6)
    assert r["kernel_s"] < r["busy_s"] < r["window_s"]
    top, t = r["device_ops"][0]
    assert top == "paged_kv_attention.8 f32[1,128,8,8,128]"
    assert t == pytest.approx(r["kernel_s"], rel=1e-3)
    assert not any(n.startswith("while") for n, _ in r["device_ops"])
    idle = r["window_s"] - r["busy_s"]
    assert sum(s for _, s in r["idle_gaps"]) <= idle + 1e-9
    assert {lbl for lbl, _ in r["idle_gaps"]} <= {"prefill_chunk", "admit",
                                                  "tick"}


def test_op_labels():
    name = ("%paged_kv_attention.11 = f32[32,1,8,8,128]{4,3,2,1,0:T(8,128)}"
            " custom-call(s32[32,224]{1,0} %copy-done.4)")
    assert reduce.op_label(name) == "paged_kv_attention.11 f32[32,1,8,8,128]"
    assert reduce.is_container("%while.6 = (s32[]{:T(128)}) while(%t)")
    assert not reduce.is_container(name)


def test_reduce_needs_a_window_and_device_ops():
    assert reduce.reduce([], kernel="k") is None
    ev = [{"plane": "/host:CPU", "line": "python", "name": "bench.window",
           "start_ns": 0.0, "dur_ns": 10.0}]
    assert reduce.reduce(ev, kernel="k") is None


# -- the device checks --------------------------------------------------------
def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_refuses_a_platform_other_than_tpu():
    with pytest.raises(harness.Refusal, match="not 'tpu'"):
        harness.devices(1, smoke=False)


def test_refuses_an_unknown_tpu_kind(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(harness.Refusal, match="no entry"):
        harness.devices(1, smoke=False)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(harness.Refusal, match="needs 4 chips"):
        harness.devices(4, smoke=False)


# -- the weights both sides are fed -------------------------------------------
@pytest.mark.parametrize("config", ["qwen2-72b-pp16", "deepseek-7b-pp4"])
def test_program_weights_are_the_reference_weights(config):
    import jax
    import jax.numpy as jnp
    from bench import weights
    from bench.references import dense_gqa
    from repro.models.transformer import init_model
    conf = harness.load_json(f"bench/configs/{config}.json")
    conf = traffic.merge(conf, conf["smoke"])
    mcfg = harness.model_config(conf)
    key = harness.weights_key(2**31 + 77)
    params = harness.init_params(key, mcfg, None)
    assert (jax.tree_util.tree_structure(params) == jax.tree_util.
            tree_structure(jax.eval_shape(lambda k: init_model(k, mcfg), key)))
    dims = dense_gqa.Dims.of(conf, 16)
    emb, head, norm = dense_gqa._embed_head(key, dims)
    np.testing.assert_array_equal(params["embed"]["table"].astype(jnp.float32),
                                  emb)
    np.testing.assert_array_equal(params["head"]["kernel"].astype(jnp.float32),
                                  head)
    np.testing.assert_array_equal(
        params["final_norm"]["scale"].astype(jnp.float32), norm)
    (stack,), = params["segments"]
    names = weights.PROGRAM_ROLES
    for i in range(dims.layers):
        ref = dense_gqa._layer_weights(key, i, dims)
        got = {names[(a, b)]: stack[a][b][i] for a in stack for b in stack[a]}
        assert set(got) == set(ref)
        for role, w in ref.items():
            np.testing.assert_array_equal(got[role].astype(jnp.float32), w,
                                          err_msg=role)
    # norm scales and biases are drawn, so dropping one changes the output
    ref = dense_gqa._layer_weights(key, 0, dims)
    assert float(jnp.std(ref["attn_norm"])) > 0.1
    if conf["attention_bias"]:
        assert float(jnp.std(ref["bk"])) > 0.1
