"""The harness end to end on the CPU, at the tiny cell's size, with the
look for a chip skipped: a sound run is correct, a run whose served
tokens are altered where they are produced is not, the float8 control is
not, a cell, mix and metric added as files alone run, as does a new arch
family, and without a chip (or without the program) no result is
printed."""

import copy
import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np

import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "testdata"
# The tiny cell's limit, set as a chip cell's is: the program read at
# most 0.0328 over ten seeds (CPU, bf16 program against the float32
# reference); the float8 control read 0.287 to 0.435 on the same seeds.
TINY_LIMIT = 0.1
SEED = 2 ** 31 + 12345


def _run(R, cell=None, **kw):
    return R.run(cell or tiny.cell(limit=TINY_LIMIT), seed=SEED,
                 seconds=2.0, trace=False, require_tpu=False, **kw)


def test_sound_run_is_correct_and_prints_the_contract_keys(no_cache):
    out = _run(no_cache)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    gap = out["checks"]["served_logit_gap"]
    assert gap == {"value": gap["value"], "limit": TINY_LIMIT}
    assert gap["value"] <= TINY_LIMIT


def test_a_token_altered_where_it_is_produced_is_caught(no_cache,
                                                        monkeypatch):
    from repro.serving.engine import PagedEngine
    sample = PagedEngine._sample_rows

    def altered(self, logits, rids, counts):
        toks = np.array(sample(self, logits, rids, counts))
        toks[0] = (toks[0] + 1) % self.cfg.vocab
        return toks

    monkeypatch.setattr(PagedEngine, "_sample_rows", altered)
    out = _run(no_cache)
    assert out["correct"] is False
    assert out["checks"]["served_logit_gap"]["value"] > TINY_LIMIT


def test_the_float8_control_is_not_correct(no_cache):
    """The float8 control's tokens, compared in the served tokens' place,
    fail the same comparison that a sound run passes."""
    out = _run(no_cache, control="float8")
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is False
    assert out["checks"]["served_logit_gap"]["value"] > TINY_LIMIT


def test_a_cell_mix_and_metric_are_added_as_files_alone(no_cache, tmp_path):
    """A new configuration, traffic mix, limit and per-layer metric, as
    files under the benchmark's directories and entries in a copy of
    BENCHMARK.json, run with no change to the harness."""
    tag = f"t{os.getpid()}"
    files = {
        ROOT / f"bench/configs/{tag}.json": json.dumps(tiny.CONF),
        ROOT / f"bench/traffic/{tag}.json": json.dumps(tiny.MIX),
        ROOT / f"bench/limits/{tag}.cell.json":
            json.dumps({"served_logit_gap": TINY_LIMIT}),
        ROOT / f"bench/metrics/{tag}_tokens.py":
            "def read(rec):\n    return float(rec['counters']"
            "['decode_tokens'])\n",
    }
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": tag, "file": f"bench/configs/{tag}.json"})
    bench["workloads"].append({"name": f"{tag}.cell", "config": tag,
                               "traffic": tag, "chips": 1})
    bench["per_layer"].append({"name": f"{tag}_tokens", "unit": "tokens",
                               "workloads": [f"{tag}.cell"]})
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    try:
        for path, text in files.items():
            path.write_text(text)
        cell = no_cache.load_cell(f"{tag}.cell", bench_file)
        assert [m["name"] for m in cell.metrics["per_layer"]] == \
            [f"{tag}_tokens"]
        out = no_cache.run(cell, seed=3, seconds=2.0, trace=True,
                           require_tpu=False)
    finally:
        for path in files:
            path.unlink(missing_ok=True)
    assert out["correct"] is True
    assert out["metrics"][f"{tag}_tokens"]["value"] > 0


# A family package that serves as dense_ln does, reached through the
# lookup, and records each call.
DELEGATING = '''from bench.archs import family

_BASE = family({{"arch_family": "dense_ln"}}).{module}
CALLS = []


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(_BASE, name)(*args, **kwargs)
    return call


{defs}
'''


def test_a_new_arch_family_is_added_as_files_alone(no_cache, tmp_path,
                                                   monkeypatch):
    """A configuration naming a new arch family, the family's package under
    ``bench/archs/``, a mix, a limit and entries in a copy of
    BENCHMARK.json run with no change to the harness: the run is correct,
    and the new family built the weights, gave the reference logits and
    counted the kernel's work.  The CPU has no device plane to trace, so a
    slice recorded on the chip stands in for the trace and the chip's
    peaks are given, so that the work readers run."""
    import bench.trace
    from bench.archs import FUNCTIONS, family
    tag = f"a{os.getpid()}"
    pkg = ROOT / "bench" / "archs" / tag
    files = {pkg / "__init__.py": ""}
    for module, functions in FUNCTIONS.items():
        files[pkg / f"{module}.py"] = DELEGATING.format(
            module=module, defs="\n".join(f"{f} = _recorded({f!r})"
                                          for f in functions))
    conf = dict(tiny.CONF, arch_family=tag)
    files.update({
        ROOT / f"bench/configs/{tag}.json": json.dumps(conf),
        ROOT / f"bench/traffic/{tag}.json": json.dumps(tiny.MIX),
        ROOT / f"bench/limits/{tag}.cell.json":
            json.dumps({"served_logit_gap": TINY_LIMIT}),
    })
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": tag, "file": f"bench/configs/{tag}.json"})
    spec["workloads"].append({"name": f"{tag}.cell", "config": tag,
                               "traffic": tag, "chips": 1})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.get("workloads", []).append(f"{tag}.cell")
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(spec))
    with gzip.open(DATA / "long_decode_window.events.json.gz", "rt") as f:
        recorded = bench.trace.reduce_events(json.load(f))
    monkeypatch.setattr(bench.trace, "reduce_dir",
                        lambda *a, **k: copy.deepcopy(recorded))
    monkeypatch.setattr(no_cache, "device_info", lambda chips, req: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    try:
        pkg.mkdir()
        for path, text in files.items():
            path.write_text(text)
        cell = no_cache.load_cell(f"{tag}.cell", bench_file)
        fam = family(cell.conf)
        out = no_cache.run(cell, seed=SEED, seconds=2.0, trace=True,
                           require_tpu=False)
        calls = {m: list(getattr(fam, m).CALLS) for m in FUNCTIONS}
    finally:
        for path in files:
            path.unlink(missing_ok=True)
        shutil.rmtree(pkg, ignore_errors=True)
    assert fam.name == tag
    assert out["correct"] is True
    assert {"program_config", "make_params", "plane_stats"} <= \
        set(calls["model"])
    assert "logits_at" in calls["reference"]
    assert {"besf_kernel_work", "decode_step_flops"} <= set(calls["work"])
    assert {"besf_kernel_roofline", "decode_step_mfu"} <= set(out["metrics"])


def test_no_chip_no_result(capsys):
    from bench import run as R
    rc = R.main(["--workload", "stablelm-1.6b.long_decode", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_the_benchmark_alone_does_not_run(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    (no program) exits non-zero and prints nothing."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        dst = tmp_path / p
        dst.parent.mkdir(parents=True, exist_ok=True)
        subprocess.run(["cp", "-r", str(ROOT / p), str(dst)], check=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        bench["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
