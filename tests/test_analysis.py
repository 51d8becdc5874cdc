"""Static-analysis subsystem: seeded violations per kernel rule class,
lint rule negatives, and the CLI's JSON report.

The kernel negatives build tiny in-test ``pl.pallas_call`` invocations
under the recorder (the kernel body never runs) and assert each
deliberately-broken spec is reported with *this* file and the call line
— a checker that can't localize is a checker nobody acts on.
"""

import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import kernel_contracts as kc
from repro.analysis.lint import ConfigSpec, run_lint
from repro.analysis.report import KERNEL_RULES, Finding, summarize

THIS = pathlib.Path(__file__).name


def _record_one(fn):
    with kc.record_pallas_calls() as recs:
        fn()
    assert len(recs) == 1
    return recs[0]


def _rules(findings):
    return {f.rule for f in findings}


def _here(findings, rule):
    f = next(f for f in findings if f.rule == rule)
    assert f.file.endswith(THIS), f.file
    assert f.line > 0
    return f


def _noop_kernel(*refs):
    pass


# ---------------------------------------------------------------------------
# recorder + positive control
# ---------------------------------------------------------------------------


def test_recorder_returns_zeros_without_running_kernel():
    def boom(*refs):
        raise RuntimeError("kernel body must not execute")

    with kc.record_pallas_calls() as recs:
        out = pl.pallas_call(
            boom, grid=(2,),
            in_specs=[pl.BlockSpec((4, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        )(jnp.ones((8, 8), jnp.float32))
    assert out.shape == (8, 8) and not out.any()
    assert recs[0].grid == (2,)
    assert kc.check_record(recs[0]) == []


def test_seeded_index_map_out_of_bounds():
    def run():
        pl.pallas_call(
            _noop_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((4, 8), lambda i: (i, 0))],  # 2 blocks
            out_specs=pl.BlockSpec((4, 8), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
        )(jnp.zeros((8, 8), jnp.float32))

    findings = kc.check_record(_record_one(run))
    f = _here(findings, "kernel-index-map-bounds")
    assert "grid point (2,)" in f.message


def test_seeded_output_coverage_gap():
    def run():
        pl.pallas_call(
            _noop_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((4, 8), lambda i: (0, 0)),  # never (1, 0)
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        )(jnp.zeros((8, 8), jnp.float32))

    findings = kc.check_record(_record_one(run))
    f = _here(findings, "kernel-output-coverage")
    assert "never written" in f.message


def test_seeded_block_non_divisor():
    def run():
        pl.pallas_call(
            _noop_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((3, 8), lambda i: (0, 0))],  # 3 ∤ 8
            out_specs=pl.BlockSpec((4, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        )(jnp.zeros((8, 8), jnp.float32))

    _here(kc.check_record(_record_one(run)), "kernel-block-divisor")


def test_seeded_tile_multiple_violation():
    def run():
        pl.pallas_call(
            _noop_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((8, 64), lambda i: (0, i))],
            out_specs=pl.BlockSpec((8, 256), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32),
        )(jnp.zeros((8, 256), jnp.float32))

    rec = _record_one(run)
    # 64 divides 256, so only the tile rule fires — and only when asked.
    assert "kernel-tile-multiple" not in _rules(kc.check_record(rec))
    findings = kc.check_record(rec, tile_check=True)
    f = _here(findings, "kernel-tile-multiple")
    assert "128" in f.message


@pytest.mark.parametrize("block,shape,flagged", [
    ((1, 128), (4, 128), True),     # one row of a taller array
    ((8, 1), (8, 4), True),         # one lane of a wider array
    ((1, 128), (1, 128), False),    # a unit dim spanning the whole array
])
def test_seeded_unit_block_dim_not_exempt(block, shape, flagged):
    """A block dim of 1 in the last two positions is a partial tile —
    Mosaic refuses it (the old per-(slot, page) ``(1, 1)`` counter block
    of the paged kernels) — unless it spans the whole array dim."""
    grid = tuple(s // b for s, b in zip(shape, block))

    def run():
        pl.pallas_call(
            _noop_kernel, grid=grid,
            in_specs=[pl.BlockSpec(block, lambda i, j: (i, j))],
            out_specs=pl.BlockSpec(shape, lambda i, j: (0, 0)),
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        )(jnp.zeros(shape, jnp.float32))

    findings = kc.check_record(_record_one(run), tile_check=True)
    if flagged:
        _here(findings, "kernel-tile-multiple")
    else:
        assert "kernel-tile-multiple" not in _rules(findings)


def test_seeded_float_scalar_prefetch():
    def run():
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec((4,), lambda i, s: (0,))],
            out_specs=pl.BlockSpec((4,), lambda i, s: (0,)),
        )
        pl.pallas_call(
            _noop_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
        )(jnp.zeros((2,), jnp.float32),      # scalar operand: not integer
          jnp.zeros((4,), jnp.float32))

    findings = kc.check_record(_record_one(run))
    f = _here(findings, "kernel-scalar-prefetch")
    assert "integer" in f.message


def test_seeded_interpret_mismatch():
    def run():
        pl.pallas_call(
            _noop_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((4,), lambda i: (0,))],
            out_specs=pl.BlockSpec((4,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            interpret=False,
        )(jnp.zeros((4,), jnp.float32))

    rec = _record_one(run)
    findings = kc.check_record(rec, expected_interpret=True)
    f = _here(findings, "kernel-interpret-routing")
    assert "resolve_interpret" in f.message
    assert kc.check_record(rec, expected_interpret=False) == []


def test_seeded_scratch_mismatch():
    def run():
        pl.pallas_call(
            _noop_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((4,), lambda i: (0,))],
            out_specs=pl.BlockSpec((4,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            scratch_shapes=[pltpu.VMEM((4,), jnp.float32)],
        )(jnp.zeros((4,), jnp.float32))

    rec = _record_one(run)
    good = kc.check_record(rec, expected_scratch=[((4,), jnp.float32)],
                           expected_sems=0)
    assert good == []
    findings = kc.check_record(rec, expected_scratch=[((8,), jnp.float32)],
                               expected_sems=1)
    assert sum(1 for f in findings if f.rule == "kernel-scratch") == 2
    _here(findings, "kernel-scratch")


def test_contract_run_findings():
    """A case that records nothing, and a case that crashes, both surface
    as kernel-contract-run instead of vacuously passing."""
    def cases():
        return [kc.Case("empty", lambda: None),
                kc.Case("crash", lambda: (_ for _ in ()).throw(
                    ValueError("seeded crash")))]

    contract = kc.KernelContract(
        "seeded", "repro.kernels.flash_attention",
        ("repro.kernels.flash_attention",), cases)
    findings, meta = kc.run_kernel_contracts([contract])
    msgs = [f.message for f in findings
            if f.rule == "kernel-contract-run"]
    assert len(msgs) == 2
    assert any("recorded no pallas_call" in m for m in msgs)
    assert any("seeded crash" in m for m in msgs)
    assert meta["cases"] == 2 and meta["pallas_calls_checked"] == 0


def test_unrouted_interpret_is_reported():
    """A pallas_call reached without consulting resolve_interpret (the
    module spy never fires) is an interpret-routing finding even if the
    flag happens to be right."""
    from repro.kernels.runtime import resolve_interpret

    def run():
        pl.pallas_call(
            _noop_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((4,), lambda i: (0,))],
            out_specs=pl.BlockSpec((4,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            interpret=resolve_interpret(None),   # right value, wrong route
        )(jnp.zeros((4,), jnp.float32))

    contract = kc.KernelContract(
        "unrouted", "repro.kernels.flash_attention",
        ("repro.kernels.flash_attention",),
        lambda: [kc.Case("direct", run)])
    findings, _ = kc.run_kernel_contracts([contract])
    assert any(f.rule == "kernel-interpret-routing"
               and "never called" in f.message for f in findings)


def test_repo_contracts_cover_all_entry_points():
    mods = {c.module for c in kc.CONTRACTS}
    assert mods == {
        "repro.kernels.paged_decode",
        "repro.kernels.paged_verify",
        "repro.kernels.bitstopper_qk",
        "repro.kernels.flash_attention",
        "repro.kernels.ops",
    }


# ---------------------------------------------------------------------------
# lint rule negatives (seeded fixture tree)
# ---------------------------------------------------------------------------


def _write(root, rel, body):
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return rel


def _lint(root, **kw):
    kw.setdefault("read_trees", ("src",))
    kw.setdefault("config_specs", [])
    kw.setdefault("allocator_paths", [])
    return run_lint(root, **kw)


def test_seeded_private_import(tmp_path):
    rel = _write(tmp_path, "src/mod.py", """\
        from repro.models.transformer import _segments
        from repro.models import transformer as T

        def f(p, x, cfg):
            return T._forward_impl(p, x, cfg)
        """)
    findings = _lint(tmp_path)
    got = [(f.file, f.line) for f in findings
           if f.rule == "repo-private-import"]
    assert (rel, 1) in got and (rel, 5) in got


def test_private_self_attribute_not_flagged(tmp_path):
    _write(tmp_path, "src/mod.py", """\
        class Pool:
            def __init__(self):
                self._free = []

            def take(self):
                return self._free.pop()
        """)
    assert _lint(tmp_path) == []


def test_seeded_unread_config_field(tmp_path):
    _write(tmp_path, "src/cfg.py", """\
        import dataclasses

        @dataclasses.dataclass
        class Knobs:
            used: int = 1
            dead: int = 2
        """)
    _write(tmp_path, "src/use.py", """\
        def f(k):
            return k.used
        """)
    findings = _lint(tmp_path,
                     config_specs=[ConfigSpec("src/cfg.py", "Knobs")])
    got = [f for f in findings if f.rule == "repo-config-field-unread"]
    assert len(got) == 1
    assert got[0].file == "src/cfg.py" and got[0].line == 6
    assert "dead" in got[0].message


def test_seeded_allocator_device_ops(tmp_path):
    rel = _write(tmp_path, "src/alloc.py", """\
        import jax.numpy as jnp

        def free_mask(n):
            return jnp.zeros(n)
        """)
    findings = _lint(tmp_path, allocator_paths=[rel])
    got = [f for f in findings if f.rule == "repo-allocator-device-ops"]
    assert len(got) == 1 and got[0].line == 1


def test_seeded_nondeterminism(tmp_path):
    rel = _write(tmp_path, "src/mod.py", """\
        import os
        import random
        import time

        def jitter():
            return random.random() + time.time()

        def cache_fresh(path, built_at):
            return time.time() - os.path.getmtime(path) < 60
        """)
    findings = _lint(tmp_path)
    got = [(f.line, f.message) for f in findings
           if f.rule == "repo-nondeterminism"]
    lines = [ln for ln, _ in got]
    assert 6 in lines                      # random.random() and time.time()
    assert len([ln for ln in lines if ln == 6]) == 2
    assert 9 not in lines                  # mtime comparison is exempt


def test_seeded_tick_wallclock(tmp_path):
    """serving/ tick paths are wall-clock-free by rule (docs/robustness.md):
    importing time or datetime there at all is a finding — engine decisions
    must key on the tick counter, and the watchdog (the one legitimate
    clock consumer) lives in runtime/ with an injected clock."""
    rel = _write(tmp_path, "src/repro/serving/sched.py", """\
        import time
        from datetime import datetime

        def stamp():
            return time.monotonic()
        """)
    findings = _lint(tmp_path, tickpath_dirs=["src/repro/serving"])
    got = [(f.file, f.line) for f in findings
           if f.rule == "repo-tick-wallclock"]
    assert (rel, 1) in got and (rel, 2) in got


def test_seeded_async_boundary(tmp_path):
    """Core serving/ may not import asyncio — the engine is a synchronous
    tick loop; only serving/frontdoor/ (the async door) is exempt."""
    rel = _write(tmp_path, "src/repro/serving/engine.py", """\
        import asyncio
        from concurrent.futures import ThreadPoolExecutor

        async def step_async(eng):
            await asyncio.sleep(0)
        """)
    _write(tmp_path, "src/repro/serving/frontdoor/door.py", """\
        import asyncio

        async def run(door):
            await asyncio.sleep(0)
        """)
    findings = _lint(tmp_path)
    got = [(f.file, f.line) for f in findings
           if f.rule == "repo-async-boundary"]
    assert (rel, 1) in got and (rel, 2) in got
    assert all(f == rel for f, _ in got)   # frontdoor/ is exempt


def test_tick_wallclock_scoped_to_serving(tmp_path):
    # The same imports OUTSIDE the tick-path dirs are not this rule's
    # business (repo-nondeterminism separately polices call sites).
    _write(tmp_path, "src/repro/runtime/dog.py", """\
        import time

        CLOCK = time.monotonic
        """)
    findings = _lint(tmp_path, tickpath_dirs=["src/repro/serving"])
    assert [f for f in findings if f.rule == "repo-tick-wallclock"] == []


def test_lint_clean_on_this_repo():
    root = pathlib.Path(__file__).resolve().parent.parent
    findings = run_lint(root)
    assert findings == [], "\n".join(f.format() for f in findings)


# ---------------------------------------------------------------------------
# report + CLI
# ---------------------------------------------------------------------------


def test_summarize_zero_seeds_all_rules():
    fs = [Finding("pool-refcount", "x.py", 3, "m")]
    counts = summarize(fs, KERNEL_RULES + ["pool-refcount"])
    assert counts["pool-refcount"] == 1
    assert all(counts[r] == 0 for r in KERNEL_RULES)


def test_cli_writes_json_report(tmp_path):
    from repro.analysis.__main__ import ALL_RULES, main
    out = tmp_path / "ANALYSIS.json"
    rc = main(["--only", "pool", "--only", "lint",
               "--root", str(pathlib.Path(__file__).resolve().parent.parent),
               "--out", str(out), "--check"])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] is True
    assert set(report["rules"]) == set(ALL_RULES)
    assert len(ALL_RULES) >= 8
    # 6 KV-pool scenarios + 2 host-tier (SwapPool ledger) scenarios.
    assert report["pool_scenarios"] == 8


def test_cli_check_fails_on_findings(tmp_path):
    from repro.analysis.__main__ import main
    _write(tmp_path, "src/mod.py", "import time\nt = time.time()\n")
    out = tmp_path / "ANALYSIS.json"
    rc = main(["--only", "lint", "--root", str(tmp_path),
               "--out", str(out), "--check"])
    assert rc == 1
    report = json.loads(out.read_text())
    assert report["ok"] is False
    assert report["rules"]["repo-nondeterminism"] == 1
    assert report["findings"][0]["file"] == "src/mod.py"
