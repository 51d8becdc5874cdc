"""Per-decode-step microbenchmark: fused paged BESF decode vs the dense
gather path vs a flash baseline, across fill levels and pool sizes.

The serving decode hot path used to gather each slot's dense logical view
``[B, max_blocks_per_req * page_size, H, D]`` per layer per token and
re-derive K bit planes from scratch — O(table width) HBM traffic and
compute regardless of how full a row actually is.  The fused paged path
walks physical pages through the block table, stops at each row's fill
level, and early-terminates plane/V traffic per page via LATS.  This
benchmark quantifies both effects:

* **wall-clock per decode step** — ``gather`` (full-view gather +
  ``besf_attention_decode``), ``paged`` (``besf_attention_decode_paged``,
  the kernel's semantic model and the serving fallback), ``paged-kernel``
  (the Pallas kernel; interpret mode off-TPU, timed for completeness but
  only representative when compiled), and ``flash`` (dense f32 attention
  over the gathered view — the no-BitStopper baseline).
* **modeled HBM bytes per step** — dense paths move the full padded
  K+V view; the paged path moves ``rounds[b,page] * page_size/8 * Hkv * D``
  plane bytes plus V only for pages with survivors (measured from the
  oracle's stats, so early termination shows up in the bytes).

    PYTHONPATH=src python benchmarks/decode_microbench.py
    PYTHONPATH=src python benchmarks/decode_microbench.py --smoke --check

Writes ``results/BENCH_decode.json`` — field-by-field reference (and what
the ``--smoke --check`` CI gate asserts): ``docs/benchmarks.md``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

if __package__ in (None, ""):                 # direct `python benchmarks/..`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

if "--host-devices" in sys.argv:
    # Must land in XLA_FLAGS before jax is imported: forces N host (CPU)
    # devices so --mesh runs on a single-machine CI runner.
    _n = int(sys.argv[sys.argv.index("--host-devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                                 f"={_n}")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.besf import BitStopperConfig, besf_attention_decode, \
    besf_attention_decode_paged
from repro.kernels.paged_decode import paged_bitstopper_decode
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_serve_mesh
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.models.attention import gather_paged_view

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def roofline_fields(fn, q, modeled_bytes):
    """Roofline fields for one decode-step callable (launch/roofline.py
    constants + launch/hlo_cost.py HLO accounting).

    ``hlo_flops``/``hlo_bytes`` come from the compiled module — per-device
    when the step was compiled under a mesh (SPMD modules are per-device).
    ``roofline_fraction`` is modeled-minimal HBM time over the compiled
    program's bound time, max(t_compute, t_hbm): 1.0 means the program
    moves exactly the modeled intrinsic bytes and nothing else dominates;
    the shortfall is XLA-side overhead traffic (gather materialization,
    layout copies) the fused path exists to eliminate."""
    try:
        txt = jax.jit(fn).lower(q).compile().as_text()
    except Exception as e:                       # interpret-mode edge cases
        return {"roofline_note": f"hlo unavailable: {type(e).__name__}"}
    cost = analyze_hlo(txt)
    t_comp = cost.flops / PEAK_FLOPS
    t_hbm = cost.bytes / HBM_BW
    bound = max(t_comp, t_hbm)
    return {
        "hlo_flops": cost.flops,
        "hlo_bytes": cost.bytes,
        "t_compute_s": t_comp,
        "t_hbm_s": t_hbm,
        "bound": "hbm" if t_hbm >= t_comp else "compute",
        "roofline_fraction": (modeled_bytes / HBM_BW) / bound if bound
                             else 0.0,
    }


def build_pool_state(B, MB, bs, Hkv, D, seed=0):
    """Fully-written block pool with LLM-like per-row content (Zipfian
    token importance, clustered keys) so LATS termination is realistic.
    Row b owns physical pages 1 + b*MB .. 1 + (b+1)*MB - 1; fill levels
    are swept via ``lengths`` against this fixed content."""
    from benchmarks.common import llm_like_qkv
    P = 1 + B * MB
    S = MB * bs
    k_pool = np.zeros((P, bs, Hkv, D), np.float32)
    v_pool = np.zeros((P, bs, Hkv, D), np.float32)
    q = np.zeros((B, Hkv, D), np.float32)
    for b in range(B):
        for h in range(Hkv):
            qh, kh, vh = llm_like_qkv(seed * 131 + b * 17 + h, S, d=D, Sq=1)
            blocks = np.asarray(kh).reshape(MB, bs, D)
            k_pool[1 + b * MB: 1 + (b + 1) * MB, :, h] = blocks
            v_pool[1 + b * MB: 1 + (b + 1) * MB, :, h] = \
                np.asarray(vh).reshape(MB, bs, D)
            q[b, h] = np.asarray(qh)[0]
    table = 1 + np.arange(B * MB, dtype=np.int32).reshape(B, MB)
    # lane-dense [P, bs, Hkv*D], as the serving cache stores them
    return dict(
        q=jnp.asarray(q), k_pool=jnp.asarray(k_pool.reshape(P, bs, -1)),
        v_pool=jnp.asarray(v_pool.reshape(P, bs, -1)),
        table=jnp.asarray(table),
        k_amax=jnp.asarray(np.abs(k_pool).max(axis=(0, 1, 3))),
        v_amax=jnp.asarray(np.abs(v_pool).max(axis=(0, 1, 3))),
    )


def _timeit(fn, *args, reps=5, warmup=2):
    """Median-free mean wall clock after ``warmup`` untimed calls (the
    first triggers compilation; the second settles allocator/cache
    state — timing never includes JIT work)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _pack_pool(k_pool, k_amax, bits):
    from repro.core.quantization import pack_pool_planes
    return pack_pool_planes(k_pool, k_amax, bits)


def bench_config(state, bs, fill, cfg, reps, run_kernel):
    """One (pool, fill) point: times + modeled bytes for every impl.

    Two strict phases per config: first every impl is built, compiled and
    warmed (including the oracle stats pass the bytes model needs), THEN
    the timing loops run back to back — no timing window ever overlaps
    another impl's JIT compilation, which is what made the wall-clock
    asserts contention-flaky on shared CI runners."""
    q, k_pool, v_pool = state["q"], state["k_pool"], state["v_pool"]
    table = state["table"]
    B, MB = table.shape
    Hkv, D = state["k_amax"].shape[0], q.shape[-1]
    Tv = MB * bs
    itemsize = k_pool.dtype.itemsize
    n_live = max(1, round(MB * fill))
    lengths = jnp.full((B,), n_live * bs, jnp.int32)
    q_pos = lengths - 1

    dense_bytes = B * Tv * Hkv * D * itemsize * 2          # K + V view

    # -- gather: dense view + besf_attention_decode (the old decode path)
    cache = {"k": k_pool, "v": v_pool,
             "pos": jnp.zeros(k_pool.shape[:2], jnp.int32),
             "table": table, "length": lengths}

    @jax.jit
    def gather_step(q):
        k_view, v_view, _ = gather_paged_view(cache)
        kr = k_view.reshape(B, Tv, Hkv, D).swapaxes(1, 2)  # G == 1
        vr = v_view.reshape(B, Tv, Hkv, D).swapaxes(1, 2)
        mask = (jnp.arange(Tv)[None] < lengths[:, None])[:, None, None, :]
        return besf_attention_decode(q[:, :, None], kr, vr, cfg=cfg,
                                     mask=mask).out

    # -- flash baseline: dense f32 attention over the same gathered view
    @jax.jit
    def flash_step(q):
        k_view, v_view, _ = gather_paged_view(cache)
        k_view = k_view.reshape(B, Tv, Hkv, D)
        v_view = v_view.reshape(B, Tv, Hkv, D)
        mask = jnp.arange(Tv)[None] < lengths[:, None]
        logits = jnp.einsum("bhd,bthd->bht", q, k_view) / D ** 0.5
        logits = jnp.where(mask[:, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bht,bthd->bhd", p, v_view)

    # -- paged: pure-JAX paged walk over the FULL-width table, exactly as
    # the serving fallback receives it — dead pages are skipped at runtime
    # (lax.cond in the oracle, pl.when in the kernel), which is where the
    # fill-proportional wall clock comes from.
    def paged_step(q):
        return besf_attention_decode_paged(
            q, k_pool, v_pool, table, lengths, q_pos,
            state["k_amax"], state["v_amax"], cfg=cfg)

    # Phase 1: bytes model (also compiles/warms the oracle) + impl table.
    stats = paged_step(q)
    rounds = np.asarray(stats.rounds)
    v_fetched = np.asarray(stats.v_fetched)
    plane_bytes = int(rounds.sum()) * (bs // 8) * Hkv * D
    v_bytes = int(v_fetched.sum()) * bs * Hkv * D * itemsize
    paged_bytes = plane_bytes + v_bytes

    steps = [
        ("gather", gather_step, reps, dense_bytes, {}),
        ("flash", flash_step, reps, dense_bytes, {}),
        ("paged", lambda q: paged_step(q).out, reps, paged_bytes, {}),
    ]
    if run_kernel:
        kq_pool = _pack_pool(k_pool, state["k_amax"], cfg.bits)
        interp = jax.default_backend() != "tpu"

        def kernel_step(q):
            return paged_bitstopper_decode(
                q, kq_pool, v_pool, table, lengths, q_pos,
                state["k_amax"], state["v_amax"], cfg=cfg,
                stats=False).out

        # interpret off-TPU: timing is NOT representative there, the
        # bytes model is identical to `paged`
        steps.append(("paged-kernel", kernel_step, max(1, reps // 5),
                      paged_bytes, {"interpret": interp}))

    for _, fn, _, _, _ in steps:
        jax.block_until_ready(fn(q))          # compile everything up front

    # Phase 2: serial timing, nothing left to compile.
    rows = []
    for impl, fn, r, bts, extra in steps:
        rows.append(dict(impl=impl, ms_per_step=_timeit(fn, q, reps=r),
                         modeled_hbm_bytes_per_step=bts, **extra,
                         **roofline_fields(fn, q, bts)))

    for r in rows:
        r.update(fill=fill, pool_blocks=int(1 + B * MB),
                 max_blocks_per_req=int(MB), batch=int(B),
                 page_size=int(bs), view_tokens=int(Tv),
                 live_tokens=int(n_live * bs))
    return rows


def _by_impl(all_rows):
    by = {}
    for r in all_rows:
        by.setdefault((r["impl"], r["max_blocks_per_req"]),
                      {})[r["fill"]] = r
    return by


def check_bytes(all_rows):
    """Deterministic traffic-model asserts (never retried: the bytes are
    measured from the oracle's stats, not from the clock)."""
    by = _by_impl(all_rows)
    for (impl, MB), pts in by.items():
        fl = sorted(pts)
        if impl == "gather":
            assert len({pts[f]["modeled_hbm_bytes_per_step"]
                        for f in fl}) == 1, \
                "gather bytes should not depend on fill"
        if impl == "paged":
            bts = [pts[f]["modeled_hbm_bytes_per_step"] for f in fl]
            assert all(a < b for a, b in zip(bts, bts[1:])), \
                f"paged bytes must grow with fill: {bts}"
            # bytes depend on fill (unlike the fill-blind gather); the
            # growth is sub-linear because LATS terminates the extra
            # pages early — that's the point, so only the direction
            # and a real dependence are asserted.
            assert bts[0] < 0.85 * bts[-1], \
                f"paged bytes barely depend on fill: {bts}"


def check_timing(all_rows):
    """Wall-clock acceptance: paged beats gather where the structural
    margin is large (>= 50% fill the gather path still pays the whole
    padded view).  Raises AssertionError on the first violation."""
    by = _by_impl(all_rows)
    for (impl, MB), pts in by.items():
        if impl != "paged":
            continue
        for f in sorted(pts):
            if f < 0.5:
                continue
            g = by[("gather", MB)][f]["ms_per_step"]
            p = pts[f]["ms_per_step"]
            # strict-ish win at half fill (large structural margin, but
            # a shared CPU runner still jitters — allow 10%); generous
            # slack near full fill so a real ~1x point can't flake.
            bound = g * (1.1 if f <= 0.5 else 1.5)
            raise_if = p >= bound
            assert not raise_if, \
                f"paged not faster at fill={f}: {p:.2f}ms vs {g:.2f}ms " \
                f"(bound {bound:.2f}ms)"


def run_sweep(args, cfg, bs, B, Hkv, D, mbs, fills, reps):
    all_rows = []
    for mb_i, MB in enumerate(mbs):
        state = build_pool_state(B, MB, bs, Hkv, D, seed=mb_i)
        for fill in fills:
            run_kernel = args.kernel or (mb_i == 0 and fill == fills[0]) \
                or args.smoke
            rows = bench_config(state, bs, fill, cfg, reps, run_kernel)
            all_rows.extend(rows)
            line = " ".join(
                f"{r['impl']}={r['ms_per_step']:8.2f}ms/"
                f"{r['modeled_hbm_bytes_per_step'] / 1024:.0f}KiB"
                for r in rows)
            print(f"[decode] MB={MB:4d} fill={fill:4.2f} {line}")
    return all_rows


def run_sharded(args, cfg, bs, B, Hkv, D, MB, fills, reps, mesh):
    """Sharded decode rows: the serving shard_map (KV heads over "model",
    slots over "data") around the paged oracle, timed on the mesh, with
    *per-device* modeled bytes from per-shard oracle stats.

    Each shard fetches plane/V traffic only for its ``Hkv/tp`` heads, and
    its per-page LATS round count is the max over *fewer* heads — so
    per-device bytes are <= single-device/tp by construction; the rows
    quantify how close the split comes to the ideal 1/tp.  Output is
    asserted equal to the single-device oracle at every fill (up to XLA
    per-shape reduction-order ulps; see the inline note)."""
    from repro.models.attention import _shard_paged_attention
    from repro.sharding.rules import make_serve_rules

    tp = mesh.shape["model"]
    rules = make_serve_rules(mesh)
    state = build_pool_state(B, MB, bs, Hkv, D, seed=0)
    q, k_pool, v_pool = state["q"], state["k_pool"], state["v_pool"]
    table = state["table"]
    itemsize = k_pool.dtype.itemsize
    Hl = Hkv // tp
    rows = []
    for fill in fills:
        n_live = max(1, round(MB * fill))
        lengths = jnp.full((B,), n_live * bs, jnp.int32)
        q_pos = lengths - 1

        def model_bytes(st, heads):
            plane = int(np.asarray(st.rounds).sum()) * (bs // 8) * heads * D
            v = int(np.asarray(st.v_fetched).sum()) * bs * heads * D * itemsize
            return plane + v

        # Single-device reference: the bit-identity target + global bytes.
        ref = besf_attention_decode_paged(
            q, k_pool, v_pool, table, lengths, q_pos,
            state["k_amax"], state["v_amax"], cfg=cfg)
        global_bytes = model_bytes(ref, Hkv)

        # Per-device modeled bytes: the oracle at each shard's local
        # geometry (exactly what that device's shard_map body computes).
        per_dev = []
        for s in range(tp):
            hs = slice(s * Hl, (s + 1) * Hl)
            ls = slice(s * Hl * D, (s + 1) * Hl * D)        # its lanes
            st = besf_attention_decode_paged(
                q[:, hs], k_pool[:, :, ls], v_pool[:, :, ls], table,
                lengths, q_pos, state["k_amax"][hs], state["v_amax"][hs],
                cfg=cfg)
            per_dev.append(model_bytes(st, Hl))

        call = functools.partial(besf_attention_decode_paged, cfg=cfg)

        @jax.jit
        def sharded_step(q, lengths=lengths, q_pos=q_pos):
            return _shard_paged_attention(
                call, rules, q, k_pool, v_pool, table, lengths, q_pos,
                state["k_amax"], state["v_amax"])

        out = jax.block_until_ready(sharded_step(q))
        # Survivor sets are identical per head (pruning is per-head; the
        # page's shared round counter only keeps feeding already-dead
        # heads), so the only sharded-vs-single difference is XLA
        # reassociating reductions when it compiles the smaller per-shard
        # shapes — ulp-level.  Bit-identity of full serving *traces*
        # (the invariant that matters) is asserted token-for-token in
        # tests/test_serving_sharded.py and serve_throughput.py.
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.out),
                                   rtol=0, atol=1e-6)
        row = dict(impl="paged-sharded",
                   ms_per_step=_timeit(sharded_step, q, reps=reps),
                   modeled_hbm_bytes_per_step=max(per_dev),
                   modeled_hbm_bytes_per_device=per_dev,
                   single_device_bytes=global_bytes,
                   mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
                   fill=fill, pool_blocks=int(1 + B * MB),
                   max_blocks_per_req=int(MB), batch=int(B),
                   page_size=int(bs), view_tokens=int(MB * bs),
                   live_tokens=int(n_live * bs),
                   **roofline_fields(sharded_step, q, max(per_dev)))
        rows.append(row)
        print(f"[decode] MB={MB:4d} fill={fill:4.2f} paged-sharded="
              f"{row['ms_per_step']:8.2f}ms/"
              f"{max(per_dev) / 1024:.0f}KiB per device "
              f"(ideal 1/tp = {global_bytes / tp / 1024:.0f}KiB)")
    return rows


def check_sharded(all_rows):
    """Deterministic sharded asserts: per-device modeled bytes <= the
    single-device row's bytes / tp (per-shard LATS terminates no later
    over fewer heads) and within 2x of that ideal split (the KV heads
    share the plane/V traffic roughly evenly)."""
    seen = 0
    for r in all_rows:
        if r["impl"] != "paged-sharded":
            continue
        seen += 1
        tp = r["mesh"]["model"]
        ideal = r["single_device_bytes"] / tp
        got = r["modeled_hbm_bytes_per_step"]
        assert got <= ideal, \
            f"per-device bytes exceed the 1/tp split: {got} > {ideal}"
        assert got >= 0.5 * ideal, \
            f"per-device bytes implausibly far below 1/tp: {got} vs {ideal}"
    assert seen, "no paged-sharded rows to check"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few reps (CI)")
    ap.add_argument("--check", action="store_true",
                    help="assert fill-scaling + wall-clock acceptance")
    ap.add_argument("--kernel", action="store_true",
                    help="also time the Pallas kernel on every config "
                         "(slow in interpret mode; by default only the "
                         "smallest config runs it)")
    ap.add_argument("--alpha", type=float, default=0.6)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="also run the sharded serving path (shard_map: KV "
                         "heads over 'model', batch over 'data') on the "
                         "smallest pool: emits paged-sharded rows with "
                         "per-device modeled bytes and asserts "
                         "bit-identity vs single-device")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N host (CPU) devices via XLA_FLAGS so "
                         "--mesh runs on a single machine (consumed "
                         "before jax import)")
    ap.add_argument("--timing-retries", type=int, default=1,
                    help="re-measure the sweep this many times before a "
                         "wall-clock assertion failure is fatal (CPU CI "
                         "runners jitter 3-5x under contention; the bytes "
                         "asserts are deterministic and never retried)")
    ap.add_argument("--out", default=os.path.join(RESULTS_DIR,
                                                  "BENCH_decode.json"))
    args = ap.parse_args()

    cfg = BitStopperConfig(alpha=args.alpha)
    bs = 16
    # smoke keeps the view big enough (Tv=512) that the asymptotics the
    # check asserts are visible; only reps and the sweep shrink.
    B, Hkv, D = (2, 2, 32) if args.smoke else (4, 4, 64)
    mbs = [32] if args.smoke else [32, 128]
    fills = [0.5, 1.0] if args.smoke else [0.25, 0.5, 0.75, 1.0]
    reps = 2 if args.smoke else 5

    mesh = None
    if args.mesh is not None:
        try:
            dp, tp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh expects 'dp,tp' (got {args.mesh!r})")
        n_dev = len(jax.devices())
        if dp * tp > n_dev:
            ap.error(f"--mesh {dp},{tp} needs {dp * tp} devices, "
                     f"{n_dev} visible (use --host-devices on CPU)")
        if Hkv % tp != 0:
            ap.error(f"--mesh tp={tp} must divide n_kv_heads={Hkv}")
        mesh = make_serve_mesh(dp, tp)

    def measure():
        rows = run_sweep(args, cfg, bs, B, Hkv, D, mbs, fills, reps)
        if mesh is not None:
            rows += run_sharded(args, cfg, bs, B, Hkv, D, mbs[0], fills,
                                reps, mesh)
        return rows

    all_rows = measure()

    def write_report(rows):
        report = {
            "config": dict(batch=B, n_kv_heads=Hkv, head_dim=D,
                           page_size=bs, alpha=args.alpha, bits=cfg.bits,
                           backend=jax.default_backend(),
                           smoke=args.smoke),
            "note": ("modeled_hbm_bytes_per_step: dense impls move the "
                     "full padded K+V view; paged impls move measured "
                     "plane bytes (rounds * page_size/8 * Hkv * D) + V "
                     "pages with survivors. paged-kernel timing is "
                     "interpret-mode (not representative) unless backend "
                     "== tpu."),
            "rows": rows,
        }
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"[decode] wrote {args.out}")

    write_report(all_rows)

    if args.check:
        check_bytes(all_rows)
        if mesh is not None:
            check_sharded(all_rows)
        for attempt in range(args.timing_retries + 1):
            try:
                check_timing(all_rows)
                break
            except AssertionError as e:
                if attempt == args.timing_retries:
                    raise
                print(f"[decode] timing check failed ({e}); re-measuring "
                      f"serially (attempt {attempt + 2}/"
                      f"{args.timing_retries + 1})")
                all_rows = measure()
                # the artifact must hold the rows the check passed on,
                # not the jittered sweep the retry rejected
                write_report(all_rows)
        print("[decode] checks passed: paged bytes scale with fill; "
              "paged beats gather wall-clock at >=50% fill")


if __name__ == "__main__":
    main()
