"""The serving engine's tracer (``runtime/trace.py``): spans, phase splits
and request stamps on a manual clock, a tracer without a clock that only
annotates, and served tokens and counters unchanged by any tracer."""

import pathlib

import jax
import numpy as np
import pytest

from repro.analysis.lint import run_lint
from repro.configs import reduced_config
from repro.models import transformer as T
from repro.runtime import ManualClock, SystemClock, Tracer
from repro.serving import PagedEngine, Request, ServeConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _tick(tr, clock, prefill=2.0, readback=3.0, decode=4.0, own=1.0):
    with tr.span("step"):
        clock.advance(own)
        with tr.span("prefill"):
            clock.advance(prefill)
            with tr.span("readback"):
                clock.advance(readback)
        with tr.span("decode"):
            clock.advance(decode)


def test_nested_spans_total_and_split_each_step():
    clock = ManualClock(start=100.0)
    tr = Tracer(clock)
    _tick(tr, clock)
    _tick(tr, clock, prefill=0.5, readback=0.25, decode=0.25, own=0.0)
    rec = tr.records()
    assert rec["spans"] == {"step": {"s": 11.0, "n": 2},
                            "prefill": {"s": 5.75, "n": 2},
                            "readback": {"s": 3.25, "n": 2},
                            "decode": {"s": 4.25, "n": 2}}
    first, second = rec["steps"]
    assert first == {"t": 100.0, "s": 10.0, "phases": {
        "step": 1.0, "prefill": 2.0, "readback": 3.0, "decode": 4.0}}
    # each phase is its span less the spans nested in it: they sum to
    # the step
    assert sum(second["phases"].values()) == pytest.approx(second["s"])
    assert second["t"] == 110.0 and second["phases"]["step"] == 0.0


def test_slowest_steps_come_first_and_the_logs_are_bounded(monkeypatch):
    from repro.runtime import trace
    monkeypatch.setattr(trace, "MAX_STEPS", 3)
    monkeypatch.setattr(trace, "MAX_EVENTS", 2)
    clock = ManualClock()
    tr = Tracer(clock)
    for i, d in enumerate([5.0, 1.0, 7.0, 2.0, 6.0]):
        _tick(tr, clock, decode=d)
        tr.event(i, "submit")
    # only the last three steps are kept
    assert [st["phases"]["decode"] for st in tr.steps] == [7.0, 2.0, 6.0]
    assert [st["phases"]["decode"] for st in tr.slow_steps(2)] == [7.0, 6.0]
    assert [e[0] for e in tr.records()["events"]] == [3, 4]
    assert tr.records()["spans"]["step"]["n"] == 5


def test_request_events_carry_the_clock():
    clock = ManualClock(start=3.0)
    tr = Tracer(clock)
    tr.event(7, "submit")
    clock.advance(0.5)
    tr.event(7, "admit")
    assert tr.records()["events"] == [[7, "submit", 3.0], [7, "admit", 3.5]]


def test_without_a_clock_spans_only_annotate(monkeypatch):
    entered = []

    class Ann:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    tr = Tracer()
    with tr.span("step"):
        with tr.span("decode"):
            tr.event(1, "admit")
    assert entered == ["engine.step", "engine.decode"]
    assert tr.records() == {"spans": {}, "steps": [], "events": []}


def test_a_failing_span_still_closes():
    clock = ManualClock()
    tr = Tracer(clock)
    with pytest.raises(RuntimeError):
        with tr.span("step"):
            clock.advance(1.0)
            raise RuntimeError("boom")
    _tick(tr, clock)
    assert [st["s"] for st in tr.steps] == [1.0, 10.0]


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("stablelm-1.6b").replace(attn_impl="bitstopper_xla")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(model, tracer):
    cfg, params = model
    eng = PagedEngine(cfg, params,
                      ServeConfig(max_len=64, max_slots=2, page_size=8,
                                  prefill_bucket=8, prefill_chunk=16,
                                  temperature=0.8),
                      tracer=tracer)
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab, 16, dtype=np.int32)
    reqs = [Request(prompt=np.concatenate(
                [shared, rng.integers(0, cfg.vocab, n, dtype=np.int32)]),
                    max_new_tokens=m)
            for n, m in [(5, 6), (19, 4), (2, 7)]]
    eng.generate(reqs, seed=11)
    return [r.generated for r in reqs], dict(eng.counters), eng


def test_tokens_and_counters_do_not_depend_on_the_tracer(model):
    clock = ManualClock()
    runs = [_serve(model, None), _serve(model, Tracer(SystemClock())),
            _serve(model, Tracer(clock))]
    toks, counters, _ = runs[0]
    assert counters["prefix_hit_tokens"] > 0 and counters["decode_steps"]
    for t, c, _ in runs[1:]:
        assert t == toks and c == counters
    eng = runs[1][2]
    rec = eng.tracer.records()
    names = {"step", "admit", "prefill_wait", "prefill", "decode", "sample",
             "readback", "emit"}
    assert set(rec["spans"]) == names
    assert rec["spans"]["step"]["n"] == len(rec["steps"]) == eng.ticks
    by_rid = {}
    for rid, ev, t in rec["events"]:
        by_rid.setdefault(rid, []).append(ev)
    assert by_rid == {r: ["submit", "admit", "first_token"] for r in range(3)}
    # a ManualClock that never moves still records every span, at zero
    assert runs[2][2].tracer.records()["spans"]["step"]["s"] == 0.0


def test_engine_defaults_to_a_tracer_without_a_clock(model):
    _, _, eng = _serve(model, None)
    assert eng.tracer.clock is None
    assert eng.tracer.records() == {"spans": {}, "steps": [], "events": []}


def test_serving_reads_no_clock():
    """The engine is handed its tracer's clock; ``serving/`` imports no
    wall clock (lint rule repo-tick-wallclock)."""
    findings = run_lint(ROOT, read_trees=("src",), config_specs=[],
                        allocator_paths=[])
    assert [f for f in findings if f.rule == "repo-tick-wallclock"] == []
    assert "import time" not in (
        ROOT / "src/repro/serving/engine.py").read_text()
