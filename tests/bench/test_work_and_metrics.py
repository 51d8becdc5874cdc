"""The benchmark's arithmetic on the CPU: operations and bytes from
shapes (the ``dense_ln`` family's, by hand), every metric reader on a
synthetic run record, and the traffic generators."""

import importlib
import json
import math
import pathlib

import numpy as np
import pytest

from bench.archs import family, least_time_s
from bench.peaks import peaks_for
from bench.traffic import load_mix, make_schedule

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

SMALL = {"arch_family": "dense_ln", "num_hidden_layers": 2,
         "hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
         "ffn_gated": True, "kv_cache_bytes": 4, "bitstopper": {"bits": 12}}
work = family(SMALL).work


def test_matmul_params_by_hand():
    # per layer: q 8x2x4, k and v 8x1x4, o 8x8, gated ffn 3 x 8x16
    per_layer = 64 + 2 * 32 + 64 + 3 * 128
    assert work.matmul_params_per_token(SMALL) == 2 * per_layer + 8 * 10
    plain = dict(SMALL, ffn_gated=False)
    assert work.matmul_params_per_token(plain) == \
        2 * (per_layer - 128) + 80


def test_decode_flops_by_hand():
    # 3 tokens through 1232 weights, 50 attended tokens x 2 layers x
    # (2 heads x 4 dims) x 4
    counters = {"decode_tokens": 3, "decode_kv_tokens": 50}
    assert work.decode_step_flops(SMALL, counters) == 2 * 1232 * 3 + 3200


def test_besf_kernel_work_by_hand():
    flops, nbytes = work.besf_kernel_work(SMALL, {"decode_kv_tokens": 50})
    # per layer and token: 12 planes x 2 x 2 heads x 4 dims + 2 x 8
    assert flops == 2 * 50 * (12 * 16 + 16)
    # per layer and token: 12 bits x 1 KV head x 4 dims / 8 + 4 x 4 bytes
    assert nbytes == 2 * 50 * (6 + 16)
    t, bound = least_time_s(flops, nbytes, peaks_for("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(2200 / 819e9)


def test_kv_bytes_per_token_by_hand():
    assert work.kv_bytes_per_token(SMALL) == 2 * (2 * 4 * 4 + 12 * 4 // 8)


def test_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def synthetic_record():
    conf = json.loads((ROOT / "bench/configs/stablelm-1.6b.json").read_text())
    reqs = [
        {"due": 0.0, "sent": 0.001, "times": [1.0, 1.25, 1.5, 1.75]},
        # warmed up before the window opened: its early tokens are outside
        {"due": -30.0, "sent": -30.0, "times": [-2.0, -1.0, 0.5]},
        {"due": 0.5, "sent": 0.503, "times": [2.0, 2.5, 9.9, 10.5]},
    ]
    return {
        "window_s": 10.0, "setup_s": 12.5, "requests": reqs,
        "unsent_due": [9.0],
        "counters": {"prefill_tokens": 3000, "prefix_hit_tokens": 1000,
                     "prefill_chunks": 47, "decode_tokens": 6,
                     "decode_steps": 4, "decode_kv_tokens": 15000},
        "pool": {"peak_live_blocks": 120, "pool_blocks": 481},
        "memory_peak_bytes": 13_500_000_000, "conf": conf,
        "peaks": peaks_for("TPU v5 lite"),
        "trace": {"window_s": 10.0, "busy_s": 9.0,
                  "modules": {"jit_prefill_fn": 2.35, "jit_decode_fn": 0.8},
                  "ops": {"jit_decode_fn/paged_bitstopper_decode.7": 0.5,
                          "jit_decode_fn/fusion.3": 0.3},
                  "counters": {"prefill_chunks": 47, "decode_steps": 4,
                               "decode_tokens": 6,
                               "decode_kv_tokens": 15000}},
        "planes": {"fetched": 1150, "dense": 1200},
    }


EXPECTED = {
    "setup_s": 12.5,
    "output_tok_s": 0.8,                       # 8 tokens in 0..10 s
    "ttft_p50_ms": 1000.0,                     # waits 1.0, 1.5, 1.0
    "gen_lag_p95_ms": 2.9,
    "prefix_hit_share": 25.0,
    "decode_batch_mean": 1.5,
    "kv_pool_peak_frac": 25.0,
    "peak_hbm_gb": 13.5,
    "device_idle_share": 10.0,
    "prefill_chunk_ms": 50.0,
    "decode_step_ms": 200.0,
    "besf_plane_frac": 100 * 1150 / 1200,
}


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_reads_a_record(metric):
    read = importlib.import_module(f"bench.metrics.{metric}").read
    v = read(synthetic_record())
    assert isinstance(v, float) and math.isfinite(v) and v >= 0
    if metric in EXPECTED:
        assert v == pytest.approx(EXPECTED[metric], rel=1e-9)


def test_itl_is_the_tail_of_every_gap():
    read = importlib.import_module("bench.metrics.itl_p95_ms").read
    # gaps 0.25 x 3, 0.5, 7.4 (the token at 10.5 is after the close, and
    # the warmed request's only token in the window has no gap in it)
    assert read(synthetic_record()) == pytest.approx(
        1e3 * np.percentile([0.25, 0.25, 0.25, 0.5, 7.4], 95))


def test_shares_of_a_peak_follow_from_work():
    rec = synthetic_record()
    mfu = importlib.import_module("bench.metrics.decode_step_mfu").read(rec)
    counters = {"decode_tokens": 6, "decode_kv_tokens": 15000}
    assert counters.items() <= rec["trace"]["counters"].items()
    own = family(rec["conf"]).work
    flops = own.decode_step_flops(rec["conf"], counters)
    assert mfu == pytest.approx(100 * flops / (0.8 * 197e12))
    roof = importlib.import_module(
        "bench.metrics.besf_kernel_roofline").read(rec)
    t, bound = least_time_s(*own.besf_kernel_work(rec["conf"], counters),
                            rec["peaks"])
    assert bound == "bytes"
    assert roof == pytest.approx(100 * t / 0.5)


def test_trace_metrics_are_silent_without_a_trace():
    rec = synthetic_record()
    del rec["trace"], rec["planes"]
    for m in ("device_idle_share", "prefill_chunk_ms", "decode_step_ms",
              "decode_step_mfu", "besf_kernel_roofline", "besf_plane_frac"):
        assert importlib.import_module(f"bench.metrics.{m}").read(rec) is None


MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})


@pytest.mark.parametrize("mix", MIXES)
def test_generators_are_deterministic_per_seed(mix):
    m = load_mix(mix)
    a = make_schedule(m, 2 ** 31 + 77, 1000)
    b = make_schedule(m, 2 ** 31 + 77, 1000)
    sa, sb = _specs(a), _specs(b)
    assert len(sa) == len(sb)
    for x, y in zip(sa, sb):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
        assert x.due == y.due and x.client == y.client


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_change_the_tokens_not_the_work(mix):
    m = load_mix(mix)
    a, b = (_specs(make_schedule(m, s, 1000)) for s in (1, 2))
    assert [x.max_new for x in a] == [x.max_new for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.due for x in a] == [x.due for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    out = m["output_tokens"]
    assert all(out["min"] <= x.max_new <= out["max"] for x in a)
    e = m["engine"]
    assert all(len(x.prompt) + x.max_new <= e["max_len"] for x in a)
    assert all(0 <= x.prompt.min() and x.prompt.max() < 1000 for x in a)


def test_closed_loop_follows_its_distributions():
    m = load_mix("long_decode")
    s = make_schedule(m, 5, 1000)
    assert len(s.clients) == m["clients"]
    lens = np.array([len(x.prompt) for c in s.clients for x in c])
    p = m["prompt_tokens"]
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    mid = (p["min"] + p["max"]) / 2
    assert abs(lens.mean() - mid) < 0.1 * (p["max"] - p["min"])


def test_open_loop_follows_its_distributions():
    m = load_mix("chat_poisson")
    s = make_schedule(m, 5, 1000)
    due = np.array([x.due for x in s.arrivals])
    assert due[0] == 0 and np.all(np.diff(due) >= 0)
    assert np.mean(np.diff(due)) == pytest.approx(1 / m["rate_per_s"],
                                                  rel=0.15)
    sp = m["system_prompts"]["tokens"]
    user = np.array([len(x.prompt) - sp for x in s.arrivals])
    u = m["prompt_tokens"]
    assert user.min() >= u["min"] and user.max() <= u["max"]
    assert np.median(user) == pytest.approx(u["median"], rel=0.15)
    out = np.array([x.max_new for x in s.arrivals])
    assert np.median(out) == pytest.approx(m["output_tokens"]["median"],
                                           rel=0.15)
    # every request opens with one of the shared system prompts
    heads = {tuple(x.prompt[:sp]) for x in s.arrivals}
    assert len(heads) == m["system_prompts"]["count"]


def _specs(schedule):
    if schedule.loop == "closed":
        return [x for c in schedule.clients for x in c]
    return list(schedule.arrivals)


def test_the_result_line_has_the_contract_keys_and_checks_last():
    from bench.run import result_line
    checks = {"served_logit_gap": {"value": 0.01, "limit": 0.1}}
    plain = result_line(True, 5, 0, {}, {"platform": "tpu"}, checks)
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device", "checks"]
    traced = result_line(False, 5, 1, {}, {}, checks,
                         breakdown={"device_ops": [], "idle_gaps": []})
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert traced["checks"] == checks


@pytest.mark.parametrize("seconds,expected", [(50.0, (25.0, 8.0)),
                                              (12.0, (4.0, 8.0)),
                                              (2.0, (0.0, 2.0))])
def test_the_traced_slice_lies_in_the_window_middle(seconds, expected):
    from bench.run import trace_slice
    start, length = trace_slice(seconds)
    assert (start, length) == expected
    assert start + length <= seconds
