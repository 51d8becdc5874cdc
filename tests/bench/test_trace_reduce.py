"""The trace reduction: by hand on a synthetic event list, and on two
traces recorded once on a TPU v5e (``bench/testdata``): a small program's
``.xplane.pb``, and the events of the first 0.6 s of a traced
``stablelm-1.6b.long_decode`` window."""

import gzip
import json
import pathlib

import pytest

from bench.trace import extract, reduce_events, short_name

DATA = pathlib.Path(__file__).resolve().parents[2] / "bench" / "testdata"
MS = 1_000_000


def test_reduction_by_hand():
    ev = {"spans": [["trace", 0, 100 * MS], ["step", 0, 60 * MS],
                    ["wait", 60 * MS, 95 * MS]],
          "devices": [{
              "modules": [["jit_decode_fn", 5 * MS, 40 * MS],
                          ["jit_prefill_fn", 70 * MS, 80 * MS],
                          ["jit_prefill_fn", 98 * MS, 130 * MS]],
              "ops": [["fusion.1", "jit_decode_fn", 5 * MS, 15 * MS],
                      ["paged_bitstopper_decode.7", "jit_decode_fn",
                       15 * MS, 40 * MS],
                      ["while.3", "jit_prefill_fn", 70 * MS, 80 * MS],
                      # nested in while.3: 4 of its 10 ms are its own
                      ["fusion.9", "jit_prefill_fn", 71 * MS, 77 * MS],
                      ["late", "jit_prefill_fn", 98 * MS, 130 * MS]]}]}
    r = reduce_events(ev)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [5, 40] and [70, 80] and [98, 100] (clipped to the window)
    assert r["busy_s"] == pytest.approx(0.047)
    assert r["modules"]["jit_decode_fn"] == pytest.approx(0.035)
    assert r["modules"]["jit_prefill_fn"] == pytest.approx(0.012)
    assert r["ops"]["jit_decode_fn/paged_bitstopper_decode.7"] == \
        pytest.approx(0.025)
    assert r["ops"]["jit_prefill_fn/while.3"] == pytest.approx(0.004)
    assert r["ops"]["jit_prefill_fn/fusion.9"] == pytest.approx(0.006)
    # self times tile the busy time
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    # gaps: [0, 5) in a step, [40, 70) mostly in a step (20 of its 30 ms),
    # [80, 98) waiting
    assert r["idle_by_span"] == pytest.approx({"step": 0.035,
                                               "wait": 0.018})
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["step@0.040s"] == pytest.approx(0.030)
    assert gaps["wait@0.080s"] == pytest.approx(0.018)
    assert r["breakdown"]["device_ops"][0][0] == \
        "jit_decode_fn/paged_bitstopper_decode.7"


def test_short_names():
    assert short_name("%while.110 = (s32[], bf16[1,64]) while(...)") == \
        "while.110"
    assert short_name("fusion.3") == "fusion.3"


def test_no_device_plane_reduces_to_nothing():
    assert reduce_events({"spans": [], "devices": []}) is None
    assert reduce_events({"spans": [], "devices": [
        {"modules": [], "ops": []}]}) is None


def test_recorded_xplane_extracts_and_reduces():
    """A small program (a matmul program and a reduction, three rounds
    with 5 ms of host waiting between) traced on a TPU v5e."""
    from jax.profiler import ProfileData
    ev = extract(ProfileData.from_file(str(DATA / "tiny.xplane.pb")))
    assert [n for n, _, _ in ev["spans"]] == \
        ["trace"] + ["step", "wait"] * 3
    assert len(ev["devices"]) == 1
    dev = ev["devices"][0]
    assert [m[0] for m in dev["modules"]] == ["jit__lambda"] * 6
    assert {op[1] for op in dev["ops"]} == {"jit__lambda"}
    r = reduce_events(ev)
    assert r["window_s"] == pytest.approx(0.02141692)
    assert r["busy_s"] == pytest.approx(5.3627e-05)
    assert r["modules"] == pytest.approx({"jit__lambda": 5.3644e-05})
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["breakdown"]["device_ops"][0][0] == "jit__lambda/fusion"
    # the device's timestamps here are about 1 ms earlier than the host
    # spans that caused them, so the gaps fall in the waits
    assert sum(r["idle_by_span"].values()) == \
        pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_decode_window_reduces():
    """The extracted events of the first 0.1 s of a traced
    ``stablelm-1.6b.long_decode`` window (two slots decoding at ~2,900
    cached tokens): the fused decode kernel takes 92% of the device's
    time."""
    with gzip.open(DATA / "long_decode_window.events.json.gz", "rt") as f:
        ev = json.load(f)
    r = reduce_events(ev)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.09687697)
    assert r["modules"]["jit_decode_fn"] == pytest.approx(0.096874809)
    kernel = r["ops"]["jit_decode_fn/paged_bitstopper_decode.7"]
    assert kernel == pytest.approx(0.089471075)
    assert r["breakdown"]["device_ops"][0] == \
        ["jit_decode_fn/paged_bitstopper_decode.7", kernel]
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["idle_by_span"] == pytest.approx(
        {"step": r["window_s"] - r["busy_s"]})
