"""Reading the engine's own tracing (``bench/tracing.py``) on the CPU: the
scopes of a tiny compiled forward's ops, the profile reduction against
``bench/trace.py`` on a trace recorded on the chip, idle time by the
innermost span and the clock offset by hand, each metric on a synthetic
record, and the tiny cell's time to first token split into its phases."""

import collections
import copy
import pathlib
import re

import pytest

import tiny
from bench import tracing as TR
from bench.trace import extract, reduce_events
from test_work_and_metrics import synthetic_record

DATA = pathlib.Path(__file__).resolve().parents[2] / "bench" / "testdata"
MS = 1_000_000
SEED = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def engine():
    from bench.archs import family
    from bench.run import serve_config
    from repro.serving import PagedEngine
    cell = tiny.cell()
    model = family(cell.conf).model
    cfg = model.program_config(cell.conf)
    return PagedEngine(cfg, model.make_params(cell.conf, SEED),
                       serve_config(cell))


def _op_names(text):
    out = {}
    for line in text.splitlines():
        m = TR.INSTR.match(line)
        on = TR.OP_NAME.search(line)
        if m and on:
            out[m.group(1)] = on.group(1)
    return out


def test_the_engine_hands_out_its_programs(engine):
    progs = engine.programs()
    assert list(progs) == ["decode_fn", "prefill_fn@16", "prefill_fn@32"]
    for fn, args in progs.values():
        assert fn.lower(*args) is not None


def test_cache_ops_land_where_decode_cache_ms_reads(engine):
    fn, args = engine.programs()["decode_fn"]
    text = fn.lower(*args).compile().as_text()
    smap = TR.scope_map([text])
    names = _op_names(text)
    cache = {n for n, on in names.items()
             if smap["name"][n] in ("kv_write", "layers")}
    # the cache write, and the scan stacking each layer's new cache
    assert any("/kv_write/" in names[n] for n in cache)
    stacking = [n for n, on in names.items()
                if re.search(r"/layers/while/body/dynamic_update_slice$", on)]
    assert stacking and all(smap["name"][n] == "layers" for n in stacking)
    # a layer's matmuls are not cache work
    dots = [n for n, on in names.items() if on.endswith("dot_general")]
    assert dots and not cache & set(dots)
    labels = set(smap["name"].values())
    assert {"kv_write", "layers", "block", "attn", "mlp", "lm_head"} <= labels


def test_prefill_attention_carries_its_scopes(engine):
    maps = TR.scope_maps(engine)
    assert set(maps) == {"jit_decode_fn", "jit_prefill_fn"}
    labels = set(maps["jit_prefill_fn"]["key"].values())
    assert {"kv_gather", "attn", "kv_write"} <= labels


def test_scope_labels_by_hand():
    assert TR.scope_label("jit(f)/layers/while/body/dynamic_slice") == \
        "layers"
    assert TR.scope_label("jit(f)/layers/broadcast_in_dim") == "layers"
    assert TR.scope_label(
        "jit(f)/layers/while/body/closed_call/dot_general") == "block"
    assert TR.scope_label("jit(f)/layers/while/body/closed_call/kv_write/"
                          "cond/branch_0_fun/while/body/scatter") == \
        "kv_write"
    assert TR.scope_label("jit(f)/lm_head/dot_general") == "lm_head"
    assert TR.scope_label("jit(f)/jit(_take)/gather") == ""


def test_profile_op_names_find_their_compiled_op():
    compiled = ('  %fusion.3 = bf16[1,64]{1,0:T(8,128)} fusion(bf16[1,64]'
                '{1,0} %p.1), kind=kLoop, calls=%fc.3, metadata={op_name='
                '"jit(f)/layers/while/body/closed_call/attn/add"}')
    other = ('  ROOT %fusion.3 = bf16[1,32]{1,0:T(8,128)} fusion(bf16[1,32]'
             '{1,0} %p.1), kind=kLoop, metadata={op_name="jit(f)/lm_head/x"}')
    smap = TR.scope_map([compiled, other])
    event = ("%fusion.3 = bf16[1,64]{1,0:T(8,128)} fusion(bf16[1,64]{1,0} "
             "%p.1), kind=kLoop, calls=%fc.3")
    assert TR.label_of(smap, event) == "attn"
    # the bare name is ambiguous between the two variants
    assert TR.label_of(smap, "fusion.3") is None
    tuple_op = ("%copy-start = (f32[8]{0:S(1)}, u32[]{:S(2)}) copy-start("
                "f32[8]{0} %x.1)")
    assert TR.op_key(tuple_op) == ("copy-start",
                                   "(f32[8]{0:S(1)}, u32[]{:S(2)})")
    assert TR.label_of(None, event) is None


def test_the_profile_reduction_matches_the_harness_on_a_chip_trace():
    """On a trace recorded on a TPU v5e, the one-pass reduction gives the
    harness's own numbers; with no scope maps every op is unknown."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "tiny.xplane.pb"))
    mine = TR.reduce_profile(pd, {})
    theirs = reduce_events(extract(pd))
    for k in ("window_s", "busy_s", "modules", "ops", "idle_by_span",
              "breakdown"):
        assert mine[k] == theirs[k]
    (prog, by), = mine["ops_by_scope"].items()
    assert set(by) == {"?"}
    assert by["?"] == pytest.approx(theirs["busy_s"])


def test_idle_by_innermost_span_by_hand():
    spans = [["bench.step", 0, 100 * MS], ["engine.step", 1 * MS, 90 * MS],
             ["engine.emit", 10 * MS, 20 * MS],
             ["engine.admit", 30 * MS, 40 * MS],
             ["bench.record", 90 * MS, 95 * MS],
             ["bench.step", 96 * MS, 200 * MS],
             ["engine.decode", 97 * MS, 150 * MS]]
    parts = TR.gap_parts(
        [(15 * MS, 35 * MS), (88 * MS, 99 * MS), (250 * MS, 260 * MS)],
        spans)
    got = sum(parts, collections.Counter())
    assert got == pytest.approx({
        "engine.emit": 0.005, "engine.step": 0.012, "engine.admit": 0.005,
        "bench.record": 0.005, "bench.step": 0.002, "engine.decode": 0.002,
        "none": 0.010})
    assert [p.most_common(1)[0][0] for p in parts] == [
        "engine.step", "bench.record", "none"]


def test_the_longest_gaps_are_labelled_by_their_largest_part():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "tiny.xplane.pb"))
    mine = TR.reduce_profile(pd, {})
    theirs = reduce_events(extract(pd))["breakdown"]["idle_gaps"]
    # with the harness's spans alone each gap's largest part is the span
    # the harness's breakdown names, at the same time and length
    assert len(mine["idle_gaps"]) == len(theirs)
    for (a, da), (b, db) in zip(mine["idle_gaps"], theirs):
        assert da == pytest.approx(db)
        assert a.split("@")[1] == b.split("@")[1]
        label = b.split("@")[0]
        assert a.split("@")[0] == ("none" if label == "harness"
                                   else "bench." + label)


def test_host_device_offset_by_hand():
    spans = [["engine.decode", 100 * MS, 101 * MS],
             ["engine.decode", 102 * MS, 150 * MS],
             ["engine.decode", 300 * MS, 310 * MS],
             ["engine.admit", 299 * MS, 300 * MS]]
    mods = [["jit_decode_fn", 103 * MS, 140 * MS],
            # read 1 ms before its dispatch's span on the host's clock
            ["jit_decode_fn", 299 * MS, 330 * MS],
            ["jit_prefill_fn", 200 * MS, 250 * MS]]
    assert TR.host_device_offset(mods, spans) == pytest.approx(-0.001)
    assert TR.host_device_offset(mods[2:], spans) is None


def traced_record():
    rec = synthetic_record()
    for rid, r in enumerate(rec["requests"]):
        r["rid"] = rid
    rec["tracer"] = {
        "spans": {},
        "steps": [
            {"t": -1.0, "s": 9.0, "phases": {"step": 9.0}},
            {"t": 0.5, "s": 0.3, "phases": {"prefill_wait": 0.1,
                                            "readback": 0.15, "step": 0.05}},
            {"t": 1.0, "s": 0.2, "phases": {"readback": 0.17,
                                            "decode": 0.03}},
        ],
        "events": [[0, "submit", 0.002], [0, "admit", 0.003],
                   [0, "first_token", 0.8],
                   [1, "submit", -30.0], [1, "admit", -29.0],
                   [1, "first_token", -2.5],
                   [2, "submit", 0.504], [2, "admit", 0.504],
                   [2, "first_token", 1.9]],
    }
    rec["trace"]["ops_by_scope"] = {
        "jit_decode_fn": {"kv_write": 0.02, "layers": 0.04, "block": 0.5,
                          "attn": 0.4},
        "jit_prefill_fn": {"kv_gather": 0.5, "attn": 1.85, "mlp": 0.2}}
    return rec


EXPECTED = {
    "first_token_hold_p50_ms": 150.0,          # holds 0.2 and 0.1 s
    "prefill_wait_p50_ms": 1096.5,             # 0.797 and 1.396 s
    "step_host_ms": 40.0,                      # 0.05 and 0.03 s
    "decode_cache_ms": 15.0,                   # 0.06 s over 4 steps
    "prefill_attn_ms": 50.0,                   # 2.35 s over 47 chunks
}


@pytest.mark.parametrize("metric", sorted(TR.READERS))
def test_every_tracing_metric_reads_a_record(metric):
    assert TR.READERS[metric](traced_record()) == \
        pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(TR.READERS))
def test_tracing_metrics_are_silent_without_the_tracer(metric):
    """A record of a program without the tracer or the scopes (the
    harness as it is) has nothing for them to read."""
    rec = synthetic_record()
    assert TR.READERS[metric](rec) is None
    rec = copy.deepcopy(traced_record())
    del rec["tracer"]
    rec["trace"]["ops_by_scope"] = {}
    assert TR.READERS[metric](rec) is None


def test_time_to_first_token_splits_into_its_phases(no_cache):
    """On the tiny cell, every request's time to first token on the
    harness's clock is its send lag, queue wait, prefill wait and
    first-token hold on the tracer's, within 1 ms."""
    out = TR.run(tiny.cell(), SEED, 2.0, trace=False, require_tpu=False)
    phases = out["requests"]
    assert phases
    for p in phases:
        parts = p["lag"] + p["queue"] + p["prefill"] + p["hold"]
        assert abs(p["ttft"] - parts) < 1e-3
        assert min(p["queue"], p["prefill"], p["hold"]) >= 0
    assert {"first_token_hold_p50_ms", "prefill_wait_p50_ms",
            "step_host_ms", "output_tok_s"} <= set(out["metrics"])
    assert out["slow_steps"] and all(
        sum(st["phases"].values()) == pytest.approx(st["s"])
        and 0 <= st["t"] <= 2.0 for st in out["slow_steps"])
