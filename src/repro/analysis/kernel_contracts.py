"""Static Pallas kernel-contract checker.

Every Pallas entry point in the repo is listed in :data:`CONTRACTS` with
a sweep of representative shape cases.  Each case invokes the entry
point under an interception context that replaces ``pl.pallas_call``
with a recorder: the kernel body never runs — the recorder captures the
grid, BlockSpecs, operand/output avals, scratch shapes and the resolved
``interpret`` flag, and returns zeros of ``out_shape`` so the
surrounding host code traces through.  The captured records are then
checked *statically*:

* ``kernel-index-map-bounds``  — every BlockSpec index map, evaluated at
  every grid point, yields in-range block indices for its operand.
* ``kernel-output-coverage``   — the union of blocks an output's index
  map visits over the whole grid covers the output (no never-written
  block of garbage memory escapes the kernel).
* ``kernel-block-divisor``     — block shapes have the operand's rank
  and divide its dims (the repo pads to block multiples by contract).
* ``kernel-tile-multiple``     — at production shapes (``tile_check``
  cases) blocked dims respect the TPU native tile: a blocked last dim is
  the full dim or a multiple of 128, a blocked sublane dim the full dim
  or a multiple of the dtype's min sublane (f32 8, bf16 16, int8 32).
  A block dim of 1 is held to the same rule — Mosaic refuses a (1, n)
  block of a taller array like any other partial tile.
* ``kernel-scalar-prefetch``   — ``PrefetchScalarGridSpec`` scalar
  operands are integer arrays (they become SMEM DMA addressing).
* ``kernel-interpret-routing`` — the entry resolved ``interpret``
  through ``kernels/runtime.py:resolve_interpret`` and passed exactly
  that to ``pallas_call`` (observed via a spy on the module binding).
* ``kernel-scratch``           — scratch shapes equal the contract's
  declared shapes for the case's parameters (swept across cases, this
  proves scratch scales with the grid/block geometry, not the operand),
  and the VMEM working set (blocked operands + scratch) fits the ~16 MB
  per-core budget.
* ``kernel-contract-run``      — the case ran and produced at least one
  record (a silent zero-record case would vacuously pass everything).

Interception notes: entry points are invoked through ``.__wrapped__``
(the un-jitted function under ``functools.partial(jax.jit, ...)``) so
tracing always reaches ``pallas_call``; ``jax.clear_caches()`` runs
before and after every case so traces of inner jitted kernels built
against the fake ``pallas_call`` can never leak into later real calls.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import itertools
import os
import traceback
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.report import KERNEL_RULES, Finding
from repro.kernels.runtime import resolve_interpret

__all__ = ["KERNEL_RULES", "PallasCallRecord", "record_pallas_calls",
           "spy_resolve_interpret", "check_record", "Case",
           "KernelContract", "CONTRACTS", "run_kernel_contracts"]

VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_LANE = 128


@dataclasses.dataclass
class PallasCallRecord:
    """One intercepted ``pl.pallas_call`` invocation."""

    file: str
    line: int
    grid: tuple[int, ...]
    in_specs: list
    out_specs: list
    operands: list          # ShapeDtypeStruct per operand (incl. scalars)
    out_shapes: list        # ShapeDtypeStruct per output
    scratch: list           # raw scratch_shapes entries
    num_scalar_prefetch: int
    interpret: bool


def _aval(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(x.shape), jnp.dtype(x.dtype))


def _call_site() -> tuple[str, int]:
    """Source location of the pallas_call invocation: the innermost frame
    that is neither this module nor jax internals."""
    here = os.path.abspath(__file__)
    for fr in reversed(traceback.extract_stack()):
        fn = os.path.abspath(fr.filename)
        if fn == here:
            continue
        if os.sep + "jax" + os.sep in fn or os.sep + "jaxlib" + os.sep in fn:
            continue
        return fr.filename, fr.lineno or 0
    return "<unknown>", 0


@contextlib.contextmanager
def record_pallas_calls():
    """Replace ``pl.pallas_call`` with a recorder that skips kernel
    execution and returns zeros of ``out_shape``.  Yields the list of
    :class:`PallasCallRecord` as they are captured."""
    records: list[PallasCallRecord] = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid_spec=None, grid=None,
                         in_specs=None, out_specs=None, out_shape=None,
                         scratch_shapes=(), interpret=False, **kw):
        file, line = _call_site()
        if grid_spec is not None:
            g = grid_spec.grid
            ins = list(grid_spec.in_specs)
            outs = grid_spec.out_specs
            scratch = list(grid_spec.scratch_shapes or ())
            nsp = getattr(grid_spec, "num_scalar_prefetch", 0) or 0
        else:
            g = grid if isinstance(grid, tuple) else (grid,)
            ins = list(in_specs or [])
            outs = out_specs
            scratch = list(scratch_shapes or ())
            nsp = 0
        outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
        single_out = not isinstance(out_shape, (list, tuple))
        shapes = [out_shape] if single_out else list(out_shape)

        def runner(*args):
            records.append(PallasCallRecord(
                file=file, line=line,
                grid=tuple(int(d) for d in g),
                in_specs=ins, out_specs=outs,
                operands=[_aval(a) for a in args],
                out_shapes=[jax.ShapeDtypeStruct(tuple(s.shape),
                                                 jnp.dtype(s.dtype))
                            for s in shapes],
                scratch=scratch,
                num_scalar_prefetch=int(nsp),
                interpret=bool(interpret),
            ))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return zeros[0] if single_out else zeros

        return runner

    pl.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pl.pallas_call = real


@contextlib.contextmanager
def spy_resolve_interpret(module_names: tuple[str, ...]):
    """Wrap each kernel module's ``resolve_interpret`` binding (they all
    ``from ... import resolve_interpret``, so the binding is per-module)
    with a recorder.  Yields ``{module: [resolved values]}``."""
    calls: dict[str, list[bool]] = {m: [] for m in module_names}
    originals = {}

    def make_spy(name, orig):
        def spy(x):
            r = orig(x)
            calls[name].append(r)
            return r
        return spy

    for name in module_names:
        mod = importlib.import_module(name)
        originals[name] = mod.resolve_interpret
        mod.resolve_interpret = make_spy(name, originals[name])
    try:
        yield calls
    finally:
        for name in module_names:
            importlib.import_module(name).resolve_interpret = originals[name]


# ---------------------------------------------------------------------------
# record checks
# ---------------------------------------------------------------------------

def _min_sublane(dtype) -> int:
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def _blocked(spec) -> bool:
    bs = getattr(spec, "block_shape", None)
    return bs is not None and all(isinstance(b, int) for b in bs)


def _eval_index_map(spec, idx, nsp):
    return spec.index_map(*idx, *([0] * nsp))


def check_record(rec: PallasCallRecord, *,
                 expected_interpret: bool | None = None,
                 expected_scratch: list | None = None,
                 expected_sems: int | None = None,
                 tile_check: bool = False) -> list[Finding]:
    findings: list[Finding] = []
    where = (rec.file, rec.line)

    def report(rule, msg):
        findings.append(Finding(rule, where[0], where[1], msg))

    # scalar-prefetch operands must be integers
    for i in range(min(rec.num_scalar_prefetch, len(rec.operands))):
        dt = rec.operands[i].dtype
        if not jnp.issubdtype(dt, jnp.integer):
            report("kernel-scalar-prefetch",
                   f"scalar-prefetch operand #{i} has dtype {dt} — SMEM "
                   f"addressing operands must be integer arrays")

    tensor_ops = rec.operands[rec.num_scalar_prefetch:]
    pairs = ([("in", i, s, a) for i, (s, a) in
              enumerate(zip(rec.in_specs, tensor_ops))]
             + [("out", i, s, a) for i, (s, a) in
                enumerate(zip(rec.out_specs, rec.out_shapes))])

    vmem_bytes = 0
    grid_points = list(itertools.product(*(range(d) for d in rec.grid)))

    for kind, i, spec, aval in pairs:
        if not _blocked(spec):
            continue                     # ANY / SMEM / full-array specs
        block = tuple(spec.block_shape)
        label = f"{kind}_specs[{i}]"
        if len(block) != len(aval.shape):
            report("kernel-block-divisor",
                   f"{label}: block rank {len(block)} != operand rank "
                   f"{len(aval.shape)} (shape {aval.shape})")
            continue
        bad_div = False
        for d, (b, s) in enumerate(zip(block, aval.shape)):
            if b < 1 or s % b:
                report("kernel-block-divisor",
                       f"{label}: block dim {d} of size {b} does not "
                       f"divide operand dim {s} (shape {aval.shape}, "
                       f"block {block})")
                bad_div = True
        if bad_div:
            continue
        vmem_bytes += _size_bytes(block, aval.dtype)
        if tile_check and len(block) >= 2:
            # A unit block dim is no exemption: Mosaic refuses a (1, n)
            # block over a taller array just like any other partial tile.
            b_lane, s_lane = block[-1], aval.shape[-1]
            if b_lane != s_lane and b_lane % _LANE:
                report("kernel-tile-multiple",
                       f"{label}: blocked last dim {b_lane} is neither "
                       f"the full dim ({s_lane}) nor a multiple of "
                       f"{_LANE} lanes")
            b_sub, s_sub = block[-2], aval.shape[-2]
            sub = _min_sublane(aval.dtype)
            if b_sub != s_sub and b_sub % sub:
                report("kernel-tile-multiple",
                       f"{label}: blocked sublane dim {b_sub} is neither "
                       f"the full dim ({s_sub}) nor a multiple of the "
                       f"{jnp.dtype(aval.dtype).name} min sublane {sub}")

        nblocks = tuple(s // b for s, b in zip(aval.shape, block))
        visited: set[tuple[int, ...]] = set()
        oob_reported = False
        for idx in grid_points:
            try:
                bi = _eval_index_map(spec, idx, rec.num_scalar_prefetch)
            except Exception as e:       # noqa: BLE001 — any failure is a finding
                report("kernel-index-map-bounds",
                       f"{label}: index map not statically evaluable at "
                       f"grid point {idx}: {e}")
                oob_reported = True
                break
            bi = tuple(int(x) for x in (bi if isinstance(bi, tuple)
                                        else (bi,)))
            if len(bi) != len(block):
                report("kernel-index-map-bounds",
                       f"{label}: index map returned {len(bi)} indices "
                       f"for a rank-{len(block)} block")
                oob_reported = True
                break
            if any(x < 0 or x >= n for x, n in zip(bi, nblocks)):
                report("kernel-index-map-bounds",
                       f"{label}: index map at grid point {idx} yields "
                       f"block index {bi}, outside the {nblocks} block "
                       f"grid of operand shape {aval.shape}")
                oob_reported = True
                break
            visited.add(bi)
        if kind == "out" and not oob_reported:
            want = set(itertools.product(*(range(n) for n in nblocks)))
            missing = want - visited
            if missing:
                report("kernel-output-coverage",
                       f"{label}: {len(missing)} of {len(want)} output "
                       f"block(s) never written over the {rec.grid} grid "
                       f"(e.g. block {sorted(missing)[0]})")

    # scratch
    vmem_scratch, n_sems = [], 0
    for s in rec.scratch:
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        try:
            dt = jnp.dtype(dtype) if dtype is not None else None
        except TypeError:
            dt = None                    # semaphore dtypes aren't numpy dtypes
        if shape is not None and dt is not None:
            vmem_scratch.append((tuple(shape), dt))
            vmem_bytes += _size_bytes(tuple(shape), dt)
        else:
            n_sems += 1
    if expected_scratch is not None:
        want = [(tuple(sh), jnp.dtype(dt)) for sh, dt in expected_scratch]
        if vmem_scratch != want:
            report("kernel-scratch",
                   f"scratch shapes {vmem_scratch} do not match the "
                   f"contract's declared {want} for this case's geometry")
    if expected_sems is not None and n_sems != expected_sems:
        report("kernel-scratch",
               f"{n_sems} semaphore scratch entries, contract declares "
               f"{expected_sems}")
    if vmem_bytes > VMEM_BUDGET_BYTES:
        report("kernel-scratch",
               f"VMEM working set {vmem_bytes} bytes (blocks + scratch) "
               f"exceeds the {VMEM_BUDGET_BYTES} budget")

    if expected_interpret is not None and rec.interpret != expected_interpret:
        report("kernel-interpret-routing",
               f"pallas_call got interpret={rec.interpret} but "
               f"resolve_interpret would give {expected_interpret} — the "
               f"entry point must route interpret through "
               f"kernels/runtime.py:resolve_interpret")
    return findings


def _size_bytes(shape, dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * jnp.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# contract registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Case:
    label: str
    run: Callable[[], None]
    expected_scratch: Callable[[], list] | None = None
    expected_sems: int | None = None
    tile_check: bool = False


@dataclasses.dataclass
class KernelContract:
    name: str
    module: str                       # module owning the pallas_call
    interpret_modules: tuple[str, ...]
    cases: Callable[[], list[Case]]


def _paged_pools(P, bits, bs, Hkv, D):
    """The serving cache's lane-dense plane and V pools (init_cache)."""
    from repro.core.quantization import plane_pool_shape
    return (jnp.zeros(plane_pool_shape(P, bits, bs, Hkv * D), jnp.uint8),
            jnp.zeros((P, bs, Hkv * D), jnp.float32))


def _paged_scratch(Sq, Hq, Hkv, D, bs, bits):
    """Scratch of the shared paged BESF kernel (kernels/paged_decode.py):
    one page's plane and V page as the pools store them, lane-dense."""
    kq, v = _paged_pools(1, bits, bs, Hkv, D)
    SH = Sq * Hq
    return [((2,) + kq.shape[2:], jnp.uint8),
            (v.shape[1:], jnp.float32),
            ((SH, bs), jnp.int32),
            ((SH, 1), jnp.float32),
            ((SH, 1), jnp.float32),
            ((SH, 1), jnp.float32),
            ((SH, Hkv * D), jnp.float32)]


def _decode_cases() -> list[Case]:
    from repro.core.besf import BitStopperConfig
    from repro.kernels import paged_decode as m
    cfg = BitStopperConfig()
    bits = cfg.bits

    def mk(B, Hq, Hkv, D, bs, MB, P, window, stats, tile_check=False):
        def run():
            q = jnp.ones((B, Hq, D), jnp.float32)
            kq, v = _paged_pools(P, bits, bs, Hkv, D)
            amax = jnp.ones((Hkv,), jnp.float32)
            m.paged_bitstopper_decode.__wrapped__(
                q, kq, v, jnp.zeros((B, MB), jnp.int32),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                amax, amax, cfg=cfg, window=window, stats=stats)

        return Case(
            label=(f"decode B{B} Hq{Hq} Hkv{Hkv} D{D} bs{bs} MB{MB} "
                   f"win{window} stats{stats}"),
            run=run,
            expected_scratch=lambda: _paged_scratch(1, Hq, Hkv, D, bs,
                                                    bits),
            expected_sems=2, tile_check=tile_check)

    return [
        mk(2, 4, 2, 8, 8, 3, 5, None, True),
        mk(1, 2, 2, 16, 16, 2, 4, 8, False),
        mk(2, 8, 2, 128, 128, 2, 4, None, True, tile_check=True),
        # Sharded serving (ServeConfig.mesh) calls the kernel inside
        # shard_map at per-shard geometry: local Hkv = n_kv_heads / tp
        # (grouped Q heads ride along), local batch = slots / dp.  The
        # contract must hold at these shapes too — e.g. tp=2 over the
        # Hq4/Hkv2 case above, down to a single local KV head.
        mk(1, 2, 1, 8, 8, 3, 5, None, True),
        mk(1, 4, 1, 128, 128, 2, 4, None, True, tile_check=True),
        # stablelm-1.6b as the engine serves it (32 x 64 heads, page 16,
        # 4 slots, 67-page tables for max_len 1064) and its tp=4
        # per-shard geometry.
        mk(4, 32, 32, 64, 16, 67, 269, None, False, tile_check=True),
        mk(4, 8, 8, 64, 16, 67, 269, None, False, tile_check=True),
    ]


def _verify_cases() -> list[Case]:
    from repro.core.besf import BitStopperConfig
    from repro.kernels import paged_verify as m
    cfg = BitStopperConfig()
    bits = cfg.bits

    def mk(B, Sq, Hq, Hkv, D, bs, MB, P, window, stats, tile_check=False):
        def run():
            q = jnp.ones((B, Sq, Hq, D), jnp.float32)
            kq, v = _paged_pools(P, bits, bs, Hkv, D)
            amax = jnp.ones((Hkv,), jnp.float32)
            m.paged_bitstopper_verify.__wrapped__(
                q, kq, v, jnp.zeros((B, MB), jnp.int32),
                jnp.zeros((B, Sq), jnp.int32),
                jnp.zeros((B, Sq), jnp.int32),
                amax, amax, cfg=cfg, window=window, stats=stats)

        return Case(
            label=(f"verify B{B} Sq{Sq} Hq{Hq} Hkv{Hkv} D{D} bs{bs} "
                   f"MB{MB} win{window} stats{stats}"),
            run=run,
            expected_scratch=lambda: _paged_scratch(Sq, Hq, Hkv, D, bs,
                                                    bits),
            expected_sems=2, tile_check=tile_check)

    return [
        mk(2, 2, 2, 1, 8, 8, 2, 4, None, True),
        mk(1, 3, 4, 2, 16, 8, 3, 5, 4, False),
        mk(1, 2, 4, 2, 128, 128, 2, 4, None, True, tile_check=True),
        # Per-shard geometry under ServeConfig.mesh (tp=2 over the
        # Hq4/Hkv2 cases above): the Sq-tiled verify kernel must also
        # hold its contract at local Hkv = 1 with grouped Q heads.
        mk(1, 3, 2, 1, 16, 8, 3, 5, 4, False),
        mk(1, 2, 2, 1, 128, 128, 2, 4, None, True, tile_check=True),
        # stablelm-1.6b draft blocks (k=4 -> Sq=5), full and tp=4 shard.
        mk(4, 5, 32, 32, 64, 16, 67, 269, None, False, tile_check=True),
        mk(4, 5, 8, 8, 64, 16, 67, 269, None, False, tile_check=True),
    ]


def _bitstopper_cases() -> list[Case]:
    from repro.core.besf import BitStopperConfig
    from repro.kernels import bitstopper_qk as m
    cfg = BitStopperConfig()
    bits = cfg.bits

    def mk(shape_q, Sk, d, bq, bk, causal, tile_check=False):
        def run():
            q = jnp.ones(shape_q + (d,), jnp.float32)
            k = jnp.ones(shape_q[:-1] + (Sk, d), jnp.float32)
            v = jnp.ones(shape_q[:-1] + (Sk, d), jnp.float32)
            m.bitstopper_attention_kernel.__wrapped__(
                q, k, v, cfg=cfg, block_q=bq, block_k=bk, causal=causal)

        def scratch():
            bq_eff = min(bq, shape_q[-1])
            bk_eff = min(bk, Sk)
            return [((2, bk_eff // 8, d), jnp.uint8),
                    ((bk_eff, d), jnp.float32),
                    ((bq_eff, bk_eff), jnp.int32),
                    ((bq_eff,), jnp.float32),
                    ((bq_eff,), jnp.float32),
                    ((bq_eff, d), jnp.float32),
                    ((bq_eff,), jnp.float32)]

        return Case(
            label=f"bitstopper q{shape_q} Sk{Sk} d{d} b{bq}/{bk} "
                  f"causal{causal}",
            run=run, expected_scratch=scratch, expected_sems=2,
            tile_check=tile_check)

    return [
        mk((16,), 16, 8, 8, 8, False),
        mk((8,), 16, 8, 8, 8, True),
        mk((2, 16), 16, 8, 8, 8, False),          # batched: vmapped trace
        mk((256,), 256, 128, 128, 128, True, tile_check=True),
    ]


def _flash_cases() -> list[Case]:
    from repro.kernels import flash_attention as m

    def mk(Sq, Sk, d, bq, bk, causal, tile_check=False):
        def run():
            m.flash_attention_single.__wrapped__(
                jnp.ones((Sq, d), jnp.float32),
                jnp.ones((Sk, d), jnp.float32),
                jnp.ones((Sk, d), jnp.float32),
                causal=causal, block_q=bq, block_k=bk)

        def scratch():
            bq_eff = min(bq, Sq)
            return [((bq_eff,), jnp.float32),
                    ((bq_eff,), jnp.float32),
                    ((bq_eff, d), jnp.float32)]

        return Case(label=f"flash Sq{Sq} Sk{Sk} d{d} b{bq}/{bk} "
                          f"causal{causal}",
                    run=run, expected_scratch=scratch, expected_sems=0,
                    tile_check=tile_check)

    return [
        mk(16, 16, 8, 8, 8, False),
        mk(32, 32, 8, 8, 16, True),
        mk(256, 256, 128, 128, 128, True, tile_check=True),
    ]


def _ops_cases() -> list[Case]:
    from repro.kernels import ops as m

    def run_flash():
        q = jnp.ones((2, 2, 16, 8), jnp.float32)
        m.attention(q, q, q, impl="flash", causal=True,
                    block_q=8, block_k=8)

    def run_bitstopper():
        q = jnp.ones((24, 8), jnp.float32)
        m.attention(q, q, q, impl="bitstopper", causal=False,
                    block_q=8, block_k=8)

    return [
        Case(label="ops impl=flash batched", run=run_flash),
        Case(label="ops impl=bitstopper 2d", run=run_bitstopper),
    ]


CONTRACTS: list[KernelContract] = [
    KernelContract("paged_decode", "repro.kernels.paged_decode",
                   ("repro.kernels.paged_decode",), _decode_cases),
    KernelContract("paged_verify", "repro.kernels.paged_verify",
                   ("repro.kernels.paged_verify",), _verify_cases),
    KernelContract("bitstopper_qk", "repro.kernels.bitstopper_qk",
                   ("repro.kernels.bitstopper_qk",), _bitstopper_cases),
    KernelContract("flash_attention", "repro.kernels.flash_attention",
                   ("repro.kernels.flash_attention",), _flash_cases),
    KernelContract("ops", "repro.kernels.ops",
                   ("repro.kernels.flash_attention",
                    "repro.kernels.bitstopper_qk"), _ops_cases),
]


def run_kernel_contracts(
        contracts: list[KernelContract] | None = None
        ) -> tuple[list[Finding], dict]:
    """Run every contract case; returns (findings, meta) where meta feeds
    the JSON report (entry points covered, case/record counts)."""
    contracts = CONTRACTS if contracts is None else contracts
    findings: list[Finding] = []
    n_cases = n_records = 0
    expected = resolve_interpret(None)
    for contract in contracts:
        mod = importlib.import_module(contract.module)
        mod_file = getattr(mod, "__file__", contract.module)
        for case in contract.cases():
            n_cases += 1
            jax.clear_caches()
            try:
                with spy_resolve_interpret(contract.interpret_modules) \
                        as calls, record_pallas_calls() as recs:
                    case.run()
            except Exception as e:      # noqa: BLE001 — a crash is a finding
                findings.append(Finding(
                    "kernel-contract-run", mod_file, 0,
                    f"{contract.name} [{case.label}] raised during "
                    f"contract tracing: {type(e).__name__}: {e}"))
                continue
            finally:
                jax.clear_caches()
            n_records += len(recs)
            if not recs:
                findings.append(Finding(
                    "kernel-contract-run", mod_file, 0,
                    f"{contract.name} [{case.label}] recorded no "
                    f"pallas_call — entry point no longer reaches Pallas"))
                continue
            if not any(calls.values()):
                findings.append(Finding(
                    "kernel-interpret-routing", mod_file, 0,
                    f"{contract.name} [{case.label}] never called "
                    f"resolve_interpret — interpret must route through "
                    f"kernels/runtime.py"))
            for rec in recs:
                findings.extend(check_record(
                    rec,
                    expected_interpret=expected,
                    expected_scratch=(case.expected_scratch()
                                      if case.expected_scratch else None),
                    expected_sems=case.expected_sems,
                    tile_check=case.tile_check))
    meta = {
        "entry_points": [c.module for c in contracts],
        "cases": n_cases,
        "pallas_calls_checked": n_records,
    }
    return findings, meta
