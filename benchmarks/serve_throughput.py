"""Serving throughput: paged vs contiguous continuous batching vs static
length bucketing, plus oversubscribed admission vs worst-case reservation.

Three traces (field-by-field output reference: ``docs/benchmarks.md``):

* **mixed** — prompt lengths cycle, generation lengths vary: the workload
  where static bucketing loses (it pads every batch to the bucket length,
  cannot refill a finished row, and serializes buckets).
* **shared-prefix** — every request starts with the same system prompt.
  The paged engine maps the shared full blocks into each request's block
  table (refcount++, prefill skipped) so the common prefix is resident
  ONCE; the report includes peak KV bytes resident next to tokens/sec,
  paged-shared vs paged-unshared vs the contiguous reservation.
* **long-tail oversubscribed** — mixed ``max_new_tokens`` with a heavy
  tail, served through a pool too small for the worst-case reservations
  of all admitted requests.  Worst-case admission (``reserved``)
  serializes the queue and idles the pool; ``oversubscribed`` admission
  reserves prompt-sized budgets, preempts a victim when the pool runs dry
  mid-decode, and resumes it losslessly — same tokens (asserted against
  an ample-pool ``uncontended`` run), fewer scheduler ticks, higher
  utilization.

``--chaos`` adds a fourth section (:func:`run_chaos`): the mixed trace
re-served through the deterministic chaos harness — scripted host
crashes with snapshot/restore, drafter and kernel faults, forced
preemptions, an interrupted snapshot write — plus a QoS trace with SLO
classes, deadlines and load shedding.  Everything it reports (snapshots
taken, requests shed, degradations, the ``bit_identical`` flag) is a
pure function of the trace, so the fields gate in CI like any counter.

``--check`` turns the claims into assertions (the CI gate): the
oversubscribed arm must observe >= 1 preemption, emit token streams
bit-identical to the uncontended run, and spend fewer decode ticks than
worst-case reservation — all scheduling-level counters, deterministic on
any host.  With ``--chaos`` it also asserts the fault storm changed no
token and the shed/truncation sets are exact.  ``--out`` writes every
trace's rows to ``results/BENCH_serve.json``.

    PYTHONPATH=src python benchmarks/serve_throughput.py
    PYTHONPATH=src python benchmarks/serve_throughput.py --impl bitstopper_xla
    PYTHONPATH=src python benchmarks/serve_throughput.py --smoke --check
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

if "--host-devices" in sys.argv:
    # Must land in XLA_FLAGS before jax is imported: forces N host (CPU)
    # devices so --mesh runs on a single-machine CI runner.
    _n = int(sys.argv[sys.argv.index("--host-devices") + 1])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                                 f"={_n}")

import jax
import numpy as np

from repro.configs import reduced_config
from repro.core.besf import BitStopperConfig
from repro.launch.mesh import make_serve_mesh
from repro.models import transformer as T
from repro.serving import (
    ContinuousBatchingEngine,
    FaultPlan,
    PagedEngine,
    Request,
    ServeConfig,
    StaticBucketEngine,
    serve_with_chaos,
)


def make_trace(rng, vocab, n_requests, lens, new_lo, new_hi,
               shared_prefix=0):
    """Heterogeneous trace: prompt lengths cycle through `lens`, generation
    lengths vary — the shape that defeats static bucketing.  With
    ``shared_prefix`` every prompt carries the same leading system prompt
    (an int draws one of that length; an array is used verbatim)."""
    if isinstance(shared_prefix, (int, np.integer)):
        prefix = rng.integers(0, vocab, shared_prefix, dtype=np.int32)
    else:
        prefix = np.asarray(shared_prefix, np.int32)
    reqs = []
    for i in range(n_requests):
        tail = rng.integers(0, vocab, int(lens[i % len(lens)]),
                            dtype=np.int32)
        reqs.append(Request(
            prompt=np.concatenate([prefix, tail]),
            max_new_tokens=int(rng.integers(new_lo, new_hi + 1))))
    return reqs


def _timed(engine, trace, seed, publish=None, warm_full=False):
    # Warm-up on a full same-shaped copy of the trace (short generations):
    # every jit shape the engine will hit — per-bucket prefill and decode
    # batch shapes included — compiles outside the timed region.  The jit
    # caches live on the engine instance, so the SAME instance is measured.
    # ``warm_full`` replays the trace's real generation lengths instead:
    # an oversubscribed engine only hits its preemption-resume prefill
    # shapes when the pool actually runs dry, which short warm generations
    # never trigger.
    warm = [Request(prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens if warm_full else 2)
            for r in trace]
    engine.generate(warm, seed=seed)
    if hasattr(engine, "pool"):
        # Fresh pool: the warm-up served the SAME prompts, so its prefix
        # registry would let the timed run skip prefill entirely —
        # replay caching, not the cross-request sharing being measured.
        # (Stale device blocks are unobservable: tables are zeroed and
        # reads are fill-level masked.)
        from repro.serving import KVBlockPool
        engine.pool = KVBlockPool(engine.layout.pool_blocks,
                                  engine.layout.page_size,
                                  prefix_sharing=engine.scfg.prefix_sharing)
        if publish is not None and len(publish):
            # Steady-state framing for the shared-prefix trace: the system
            # prompt is resident from prior traffic.  Only the SHARED
            # prefix is published — per-request tails still prefill.
            engine.generate([Request(prompt=np.asarray(publish, np.int32),
                                     max_new_tokens=1)], seed=seed)
        engine.pool.peak_live_blocks = 0
    if hasattr(engine, "counters"):
        engine.counters = {k: 0 for k in engine.counters}

    reqs = [Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens)
            for r in trace]
    t0 = time.monotonic()
    engine.generate(reqs, seed=seed)
    dt = time.monotonic() - t0
    n_tok = sum(len(r.generated) for r in reqs)
    return n_tok, dt, engine, reqs


def _row(name, engine, n_tok, dt):
    row = {"engine": name, "tokens": n_tok, "seconds": dt,
           "tok_per_s": n_tok / dt}
    if hasattr(engine, "counters"):
        row.update(engine.counters)
    if isinstance(engine, (PagedEngine, ContinuousBatchingEngine)):
        row["kv_bytes_resident"] = engine.kv_bytes_resident()
    return row


def run(arch="stablelm-1.6b", impl="xla", alpha=0.6, n_requests=8,
        slots=4, seed=0, lens=(8, 24, 40), new_lo=8, new_hi=24, mesh=None):
    """Mixed-length trace: paged vs contiguous vs static-bucket.  With a
    ``mesh``, a fourth ``paged-sharded`` arm serves the same trace over the
    (data, model) device mesh and must emit bit-identical tokens."""
    cfg = reduced_config(arch).replace(
        attn_impl=impl, bitstopper=BitStopperConfig(alpha=alpha))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    max_len = max(lens) + new_hi + 8
    scfg = ServeConfig(max_len=max_len, max_slots=slots, prefill_bucket=8,
                       page_size=8)

    rng = np.random.default_rng(seed)
    trace = make_trace(rng, cfg.vocab, n_requests, lens, new_lo, new_hi)

    rows, outs = [], {}
    arms = [
        ("paged", PagedEngine(cfg, params, scfg)),
        ("continuous", ContinuousBatchingEngine(cfg, params, scfg)),
        ("static-bucket", StaticBucketEngine(cfg, params, scfg)),
    ]
    if mesh is not None:
        arms.append(("paged-sharded", PagedEngine(
            cfg, params, dataclasses.replace(scfg, mesh=mesh))))
    for name, eng in arms:
        n, dt, eng, reqs = _timed(eng, trace, seed)
        row = _row(name, eng, n, dt)
        if name == "paged-sharded":
            row["mesh"] = dict(zip(mesh.axis_names, mesh.devices.shape))
        rows.append(row)
        outs[name] = [r.generated for r in reqs]
    if mesh is not None:
        # The standing serving invariant, now across devices: the sharded
        # engine must re-serve the exact single-device token streams.
        assert outs["paged-sharded"] == outs["paged"], \
            "sharded serving diverged from single-device paged serving"
    return rows


def run_shared_prefix(arch="stablelm-1.6b", impl="xla", alpha=0.6,
                      n_requests=8, slots=4, seed=0, prefix_len=48,
                      tail_lens=(4, 12, 20), new_lo=8, new_hi=16):
    """Shared-prefix trace (common system prompt): tokens/sec and peak KV
    bytes resident, paged-shared vs paged-unshared vs contiguous."""
    cfg = reduced_config(arch).replace(
        attn_impl=impl, bitstopper=BitStopperConfig(alpha=alpha))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    max_len = prefix_len + max(tail_lens) + new_hi + 8
    base = dict(max_len=max_len, max_slots=slots, prefill_bucket=8,
                page_size=8)

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, prefix_len, dtype=np.int32)
    trace = make_trace(rng, cfg.vocab, n_requests, tail_lens, new_lo,
                       new_hi, shared_prefix=prefix)

    rows = []
    for name, eng in (
        ("paged-shared",
         PagedEngine(cfg, params, ServeConfig(**base))),
        ("paged-unshared",
         PagedEngine(cfg, params,
                     ServeConfig(**base, prefix_sharing=False))),
        ("contiguous",
         ContinuousBatchingEngine(cfg, params, ServeConfig(**base))),
    ):
        n, dt, eng, _ = _timed(eng, trace, seed, publish=prefix)
        rows.append(_row(name, eng, n, dt))
    return rows


def run_oversubscribed(arch="stablelm-1.6b", impl="xla", alpha=0.6,
                       n_requests=8, slots=4, seed=0, lens=(8, 16, 12),
                       new_short=8, new_long=48, long_every=3,
                       pool_blocks=None, check=False):
    """Long-tail oversubscribed trace: most requests generate a few
    tokens' worth of ``max_new_tokens`` budget, every ``long_every``-th
    carries a worst case ``new_long`` budget — and every request runs its
    budget to the end, so the *reservation* gap (not an eos lottery) is
    what the arms differ on.  The pool is sized for roughly the actual
    long-tail footprint: far below the sum of worst-case reservations.

    Arms: ``reserved`` (worst-case admission, same small pool: the head
    of line blocks until capacity frees — utilization idles),
    ``oversubscribed`` (prompt-sized reservations + victim preemption),
    and ``uncontended`` (ample pool — the losslessness reference)."""
    cfg = reduced_config(arch).replace(
        attn_impl=impl, bitstopper=BitStopperConfig(alpha=alpha))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    page = 8
    max_len = max(lens) + new_long + 8
    rng = np.random.default_rng(seed)
    trace = make_trace(rng, cfg.vocab, n_requests, lens, new_short,
                       new_short)
    for i in range(0, n_requests, long_every):
        trace[i].max_new_tokens = new_long
    if pool_blocks is None:
        # Roughly the long-tail working set: every request's prompt + the
        # SHORT generation budget, plus one long tail — far below the
        # worst case `sum(prompt + new_long)` a reserved admission needs
        # to run all slots concurrently.
        need = sum(-(-(len(r.prompt) + new_short) // page) for r in trace)
        pool_blocks = 1 + max(need // 2, -(-(max(lens) + new_long) // page) + 2)
    base = dict(max_len=max_len, max_slots=slots, prefill_bucket=8,
                page_size=page, prefix_sharing=False)

    rows, outs = [], {}
    for name, scfg in (
        ("uncontended", ServeConfig(**base)),
        ("reserved", ServeConfig(**base, pool_blocks=pool_blocks)),
        ("oversubscribed", ServeConfig(**base, pool_blocks=pool_blocks,
                                       oversubscribe=True)),
    ):
        n, dt, eng, reqs = _timed(PagedEngine(cfg, params, scfg), trace,
                                  seed, warm_full=True)
        row = _row(name, eng, n, dt)
        row["pool_blocks"] = eng.layout.pool_blocks
        row["peak_live_blocks"] = eng.pool.peak_live_blocks
        # What a worst-case-reserved pool would need to admit the same
        # peak concurrency this arm reached — the residency the
        # oversubscribed scheduler stops paying for.
        row["worst_case_blocks"] = sum(
            -(-(len(r.prompt) + r.max_new_tokens - 1) // page)
            for r in trace)
        rows.append(row)
        outs[name] = [r.generated for r in reqs]

    if check:
        over = next(r for r in rows if r["engine"] == "oversubscribed")
        res = next(r for r in rows if r["engine"] == "reserved")
        unc = next(r for r in rows if r["engine"] == "uncontended")
        assert over["preemptions"] >= 1, \
            f"oversubscribed trace saw no preemption ({over})"
        assert outs["oversubscribed"] == outs["uncontended"], \
            "oversubscribed tokens diverged from the uncontended run"
        assert outs["reserved"] == outs["uncontended"], \
            "reserved tokens diverged from the uncontended run"
        assert over["decode_steps"] < res["decode_steps"], \
            (f"oversubscription should serve the trace in fewer ticks: "
             f"{over['decode_steps']} vs {res['decode_steps']}")
        assert unc["preemptions"] == 0 and res["preemptions"] == 0
    return rows


def run_chaos(arch="stablelm-1.6b", impl="xla", alpha=0.6, seed=0,
              check=False):
    """Chaos section (docs/robustness.md): two scenarios, four arms, all
    scheduling fields deterministic.

    **Fault storm** — a mixed trace (shared system prompt + n-gram
    speculative decoding + oversubscribed pool) served twice: once
    undisturbed, once through :func:`serve_with_chaos` under a scripted
    :class:`FaultPlan` (host crashes with snapshot/restore, a drafter
    failure, a forced pool-dry preemption, an interrupted snapshot write
    — plus a fused-kernel fault and circuit-breaker degrade when the
    fused BitStopper kernel is on).  The ``bit_identical`` field records
    the headline claim: the fault storm must not change one token.

    **QoS** — a saturated trace with SLO classes, a shed watermark and
    per-request deadlines, against a no-QoS reference: sheds are exact
    (``shed_rids``), deadline truncation keeps every emitted stream a
    prefix of the reference, and both sets are pure functions of the
    trace — committed into the smoke baseline like any counter."""
    import tempfile

    cfg = reduced_config(arch).replace(
        attn_impl=impl, bitstopper=BitStopperConfig(alpha=alpha))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    fused = impl == "bitstopper_xla"
    rng = np.random.default_rng(seed)

    # --- fault-storm scenario -----------------------------------------
    # Generations deliberately run past the prompt+1-block oversubscribed
    # reservation, so decode makes *unreserved* claims — the seam the
    # scripted pool_dry fault (and natural preemption) bites on.
    prefix_len, tail_lens, new_lo, new_hi = 16, (4, 9, 6), 14, 20
    prefix = rng.integers(0, cfg.vocab, prefix_len, dtype=np.int32)
    trace = make_trace(rng, cfg.vocab, 6, tail_lens, new_lo, new_hi,
                       shared_prefix=prefix)
    scfg = ServeConfig(max_len=prefix_len + max(tail_lens) + new_hi + 8,
                       max_slots=3, prefill_bucket=8, page_size=8,
                       pool_blocks=16, oversubscribe=True,
                       speculative="ngram", fused_decode=fused,
                       snapshot_every=2)

    def copies():
        return [Request(prompt=r.prompt.copy(),
                        max_new_tokens=r.max_new_tokens) for r in trace]

    rows = []
    ref = copies()
    t0 = time.monotonic()
    eng = PagedEngine(cfg, params, scfg)
    eng.generate(ref, seed=seed)
    row = _row("undisturbed", eng, sum(len(r.generated) for r in ref),
               time.monotonic() - t0)
    row["pool_blocks"] = eng.layout.pool_blocks
    rows.append(row)

    events = [("crash", 2), ("drafter_fail", 3), ("pool_dry", 5),
              ("checkpoint_interrupt", 6), ("crash", 8)]
    if fused:
        events.append(("kernel_fail", 2))
    plan = FaultPlan.scripted(events)
    snap_dir = tempfile.mkdtemp(prefix="bench_chaos_")
    t0 = time.monotonic()
    creqs, rep = serve_with_chaos(
        lambda: PagedEngine(cfg, params, scfg), copies(), seed=seed,
        plan=plan, snapshot_dir=snap_dir)
    dt = time.monotonic() - t0
    c = rep["engine_counters"]
    crow = {"engine": "chaos", "tokens": sum(len(r.generated)
                                             for r in creqs),
            "seconds": dt, "tok_per_s": sum(len(r.generated)
                                            for r in creqs) / dt}
    crow.update(c)
    crow.update({k: rep[k] for k in
                 ("crashes", "restores", "snapshots_taken",
                  "snapshots_interrupted", "staging_reclaimed")})
    crow["fired_by_kind"] = rep["fired_by_kind"]
    crow["bit_identical"] = ([r.generated for r in creqs]
                             == [r.generated for r in ref])
    crow["pool_blocks"] = scfg.pool_blocks
    rows.append(crow)

    # --- QoS scenario --------------------------------------------------
    qlens = (9,)
    qtrace = make_trace(rng, cfg.vocab, 4, qlens, 8, 8)
    qtrace[0].max_new_tokens = 10

    def qcopies(qos):
        out = []
        for i, r in enumerate(qtrace):
            out.append(Request(
                prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens,
                slo="standard" if i == 0 else "besteffort",
                deadline_ticks=6 if (qos and i == 0) else None))
        return out

    qbase = dict(max_len=64, max_slots=4, prefill_bucket=8, page_size=8,
                 pool_blocks=6, oversubscribe=True)
    qref = qcopies(qos=False)
    t0 = time.monotonic()
    eng = PagedEngine(cfg, params, ServeConfig(**qbase))
    eng.generate(qref, seed=seed)
    rows.append(_row("qos-reference", eng,
                     sum(len(r.generated) for r in qref),
                     time.monotonic() - t0))

    qreqs = qcopies(qos=True)
    t0 = time.monotonic()
    eng = PagedEngine(cfg, params,
                      ServeConfig(**qbase, shed_watermark=0.5))
    eng.generate(qreqs, seed=seed)
    qrow = _row("qos", eng, sum(len(r.generated) for r in qreqs),
                time.monotonic() - t0)
    qrow["shed_rids"] = sorted(r.rid for r in qreqs if r.shed_reason)
    qrow["truncated_rids"] = sorted(r.rid for r in qreqs
                                    if r.deadline_hit)
    rows.append(qrow)

    if check:
        assert crow["bit_identical"], \
            "fault storm changed the served tokens"
        assert crow["crashes"] >= 1 and crow["restores"] == crow["crashes"]
        assert crow["snapshots_interrupted"] >= 1
        assert crow["staging_reclaimed"] >= 1
        assert crow["drafter_failures"] >= 1
        assert crow["forced_preemptions"] >= 1, \
            "pool_dry fault never forced a preemption"
        assert crow["degradations"] == (1 if fused else 0)
        assert qrow["requests_shed"] >= 1 and qrow["shed_watermark"] >= 1
        assert qrow["deadline_truncated"] >= 1
        by_rid = {r.rid: r for r in qref}
        for r in qreqs:
            if r.shed_reason:
                assert r.slo == "besteffort" and not r.generated
            else:
                assert r.generated == by_rid[r.rid].generated[
                    :len(r.generated)], \
                    f"rid {r.rid} diverged from the QoS-free reference"
        assert qreqs[0].deadline_hit and \
            len(qreqs[0].generated) < qtrace[0].max_new_tokens
    return rows


def run_hierarchy(arch="stablelm-1.6b", impl="xla", alpha=0.6, seed=0,
                  check=False):
    """Memory-hierarchy section (docs/serving.md "Memory hierarchy"): two
    deterministic scenarios, four arms, gated on counters and token
    bit-identity — never wall clock.

    **Swap-to-host resume** — an oversubscribed preempting trace served
    twice: ``recompute-resume`` (no host budget: victims re-prefill from
    their token history) vs ``swap-resume`` (victim KV device→host
    copied at preemption, resume splices it back).  The gate is the
    losslessness claim pinned by tests/test_swap.py: identical tokens,
    every swap-out consumed by a splice (no recompute fallbacks), and
    strictly fewer resume prefill chunks than the recompute arm.

    **Persistent prefix store** — a shared-system-prompt trace served by
    a seeding engine whose registered prefix blocks are flushed to an
    on-disk store (graceful shutdown), then re-served by a ``store-cold``
    engine (no store) and a ``store-warmed`` restarted engine: the warm
    arm must re-emit the cold arm's tokens while prefilling strictly
    fewer chunks (>=1 store hit)."""
    import tempfile

    cfg = reduced_config(arch).replace(
        attn_impl=impl, bitstopper=BitStopperConfig(alpha=alpha))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)

    # --- swap-to-host resume ------------------------------------------
    # Lengths sized so a third admission preempts a decoding victim that
    # owns >1 block of exclusive KV — enough history that recompute
    # resume pays visibly more prefill chunks than a splice.
    trace = make_trace(rng, cfg.vocab, 3, (12, 9, 11), 16, 16)
    base = dict(max_len=64, max_slots=3, prefill_bucket=8, page_size=8,
                pool_blocks=10, oversubscribe=True)
    rows, outs = [], {}
    for name, scfg in (
        ("recompute-resume", ServeConfig(**base)),
        ("swap-resume", ServeConfig(**base, swap_host_bytes=1 << 22)),
    ):
        n, dt, eng, reqs = _timed(PagedEngine(cfg, params, scfg), trace,
                                  seed, warm_full=True)
        row = _row(name, eng, n, dt)
        row["pool_blocks"] = eng.layout.pool_blocks
        row.update({k: v for k, v in eng.memory_report().items()
                    if k in ("host_swap_bytes", "host_swap_bytes_peak")})
        rows.append(row)
        outs[name] = [r.generated for r in reqs]

    # --- persistent prefix store --------------------------------------
    store_dir = tempfile.mkdtemp(prefix="bench_prefix_store_")
    prefix = rng.integers(0, cfg.vocab, 16, dtype=np.int32)
    strace = make_trace(rng, cfg.vocab, 3, (6, 9, 7), 8, 8,
                        shared_prefix=prefix)
    # prefill_chunk=8: store injection only covers whole chunk groups, so
    # the chunk boundary must not exceed the 16-token system prompt.
    sbase = dict(max_len=64, max_slots=2, prefill_bucket=8, page_size=8,
                 prefill_chunk=8)

    def copies(trace_):
        return [Request(prompt=r.prompt.copy(),
                        max_new_tokens=r.max_new_tokens) for r in trace_]

    seeder = PagedEngine(cfg, params,
                         ServeConfig(**sbase, prefix_store_dir=store_dir))
    seeder.generate(copies(strace), seed=seed)
    flushed = seeder.flush_prefixes()
    del seeder

    for name, scfg in (
        ("store-cold", ServeConfig(**sbase)),
        ("store-warmed", ServeConfig(**sbase,
                                     prefix_store_dir=store_dir)),
    ):
        reqs = copies(strace)
        t0 = time.monotonic()
        eng = PagedEngine(cfg, params, scfg)
        eng.generate(reqs, seed=seed)
        dt = time.monotonic() - t0
        row = _row(name, eng, sum(len(r.generated) for r in reqs), dt)
        row["disk_prefix_bytes"] = eng.memory_report()["disk_prefix_bytes"]
        if name == "store-warmed":
            row["prefix_records_flushed"] = flushed
        rows.append(row)
        outs[name] = [r.generated for r in reqs]

    if check:
        swp = next(r for r in rows if r["engine"] == "swap-resume")
        rec = next(r for r in rows if r["engine"] == "recompute-resume")
        assert outs["swap-resume"] == outs["recompute-resume"], \
            "swap-resume tokens diverged from recompute-resume"
        assert swp["preemptions"] >= 1 and rec["preemptions"] >= 1, \
            "hierarchy trace never preempted"
        assert swp["swap_outs"] >= 1 and \
            swp["swap_ins"] == swp["swap_outs"] and \
            swp["swap_fallbacks"] == 0, \
            f"swap arm did not splice every swap-out back ({swp})"
        assert rec["swap_outs"] == 0 and rec["swap_ins"] == 0
        assert swp["prefill_chunks"] < rec["prefill_chunks"], \
            (f"swap resume should re-prefill fewer chunks: "
             f"{swp['prefill_chunks']} vs {rec['prefill_chunks']}")
        assert swp["host_swap_bytes"] == 0, \
            "swap records leaked past their resume"

        wrm = next(r for r in rows if r["engine"] == "store-warmed")
        cld = next(r for r in rows if r["engine"] == "store-cold")
        assert outs["store-warmed"] == outs["store-cold"], \
            "store-warmed tokens diverged from the cold engine"
        assert wrm["prefix_store_hits"] >= 1 and \
            wrm["prefix_store_tokens"] >= 16, \
            f"store warm start never hit the disk store ({wrm})"
        assert wrm["prefill_chunks"] < cld["prefill_chunks"], \
            (f"store warm start should prefill fewer chunks: "
             f"{wrm['prefill_chunks']} vs {cld['prefill_chunks']}")
        assert cld["prefix_store_hits"] == 0
    return rows


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if values else None


def _reset_serving_state(eng):
    """Post-warmup reset: fresh pool, zeroed counters, EMPTY rid space —
    the async arms' bit-identity gate needs door-assigned rids to start
    at 0 exactly like the synchronous reference trace."""
    from repro.serving import KVBlockPool
    eng.pool = KVBlockPool(eng.layout.pool_blocks, eng.layout.page_size,
                           prefix_sharing=eng.scfg.prefix_sharing)
    eng.counters = {k: 0 for k in eng.counters}
    eng.requests.clear()
    eng._next_rid = 0
    eng.ticks = 0


def run_async(arch="stablelm-1.6b", impl="xla", alpha=0.6, n_requests=8,
              slots=4, seed=0, lens=(8, 24, 40), new_lo=8, new_hi=24,
              check=False):
    """Async front-door arms: the mixed trace served through
    ``AsyncFrontDoor`` streams, colocated (paged backend) and
    disaggregated (prefill engine -> transfer queue -> decode engine).

    Deterministic gated fields: streamed tokens must be bit-identical to
    the synchronous ``PagedEngine`` trace (``bit_identical``), the
    fairness scheduler's ``admission_order`` and the SLA mapper's
    ``deadline_ticks_mapped`` are exact, TTFT percentiles are reported in
    engine *ticks* (``ttft_ticks_*``), and the disaggregation arm's
    transfer-queue counters are exact.  Wall-clock TTFT/TPOT percentiles
    (``ttft_ms_*``/``tpot_ms_*``) ride along for humans and are never
    gated (scripts/check_bench.py skips wall-clock fields)."""
    import asyncio

    from repro.runtime import ManualClock
    from repro.serving.frontdoor import (AsyncFrontDoor, DisaggController,
                                         SlaMapper)

    cfg = reduced_config(arch).replace(
        attn_impl=impl, bitstopper=BitStopperConfig(alpha=alpha))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    max_len = max(lens) + new_hi + 8
    base = dict(max_len=max_len, prefill_bucket=8, page_size=8)

    rng = np.random.default_rng(seed)
    trace = make_trace(rng, cfg.vocab, n_requests, lens, new_lo, new_hi)
    # SLO classes cycle so fairness admission visibly reorders (rids are
    # pinned at arrival, so reordering is token-neutral — the gate).
    slos = [("besteffort", "strict", "standard")[i % 3]
            for i in range(n_requests)]
    # Every request carries a wall-clock deadline; the ManualClock never
    # advances, so the mapper keeps its default tick estimate and the
    # wall->tick mapping is a deterministic, gateable constant.
    sla = SlaMapper(granularity=1e-3, default_tick_s=1e-2)
    deadline_s = 2.0
    deadline_ticks = sla.ticks_for(deadline_s)

    ref = [Request(prompt=r.prompt.copy(),
                   max_new_tokens=r.max_new_tokens) for r in trace]
    PagedEngine(cfg, params,
                ServeConfig(max_slots=slots, **base)).generate(ref, seed=seed)
    ref_tokens = [r.generated for r in ref]
    # The door admits round-robin: one request per non-empty SLO class
    # per cycle, strict first.
    classed = {c: [i for i in range(n_requests) if slos[i] == c]
               for c in ("strict", "standard", "besteffort")}
    expected_admission = []
    while any(classed.values()):
        for c in ("strict", "standard", "besteffort"):
            if classed[c]:
                expected_admission.append(classed[c].pop(0))

    def drive(door):
        """Submit the trace, run the door, stream every request; returns
        (per-rid token lists, wall timings)."""
        async def go():
            t_sub = {}
            rids = []
            for r, slo in zip(trace, slos):
                rid = door.submit(r.prompt.copy(),
                                  max_new_tokens=r.max_new_tokens,
                                  slo=slo, deadline_s=deadline_s)
                t_sub[rid] = time.monotonic()
                rids.append(rid)
            task = asyncio.create_task(door.run())

            async def collect(rid):
                toks, stamps = [], []
                async for tok in door.stream(rid):
                    toks.append(tok)
                    stamps.append(time.monotonic())
                return rid, toks, stamps

            gathered = asyncio.gather(*(collect(r) for r in rids))
            door.shutdown("drain")
            results = await gathered
            await task
            return rids, results, t_sub

        t0 = time.monotonic()
        rids, results, t_sub = asyncio.run(go())
        dt = time.monotonic() - t0
        toks = {rid: t for rid, t, _ in results}
        ttft_ms = [1e3 * (s[0] - t_sub[rid]) for rid, t, s in results if s]
        tpot_ms = [1e3 * (s[-1] - s[0]) / (len(s) - 1)
                   for _, _, s in results if len(s) > 1]
        return rids, toks, dt, ttft_ms, tpot_ms

    def arm_row(name, door, backend_counters):
        rids, toks, dt, ttft_ms, tpot_ms = drive(door)
        n_tok = sum(len(t) for t in toks.values())
        ticks = sorted(door.first_token_tick[rid] for rid in rids)
        row = {"engine": name, "tokens": n_tok, "seconds": dt,
               "tok_per_s": n_tok / dt,
               "bit_identical": [toks[r] for r in rids] == ref_tokens,
               "admission_order": list(door.admission_log),
               "ticks_run": door.ticks_run,
               "deadline_ticks_mapped": deadline_ticks,
               "ttft_ticks_p50": _percentile(ticks, 50),
               "ttft_ticks_p95": _percentile(ticks, 95),
               "ttft_ms_p50": _percentile(ttft_ms, 50),
               "ttft_ms_p95": _percentile(ttft_ms, 95),
               "tpot_ms_p50": _percentile(tpot_ms, 50)}
        row.update(backend_counters())
        if check:
            assert row["bit_identical"], \
                f"{name}: streamed tokens diverged from the synchronous " \
                f"paged trace"
            assert row["admission_order"] == expected_admission, \
                f"{name}: admission order {row['admission_order']} != " \
                f"round-robin expectation {expected_admission}"
            sub = door.backend.requests[rids[0]]
            assert sub.deadline_ticks == deadline_ticks
        return row

    # --- colocated: one paged engine behind the door -------------------
    eng = PagedEngine(cfg, params, ServeConfig(max_slots=slots, **base))
    eng.generate([Request(prompt=r.prompt.copy(), max_new_tokens=2)
                  for r in trace], seed=seed)              # warm jit shapes
    _reset_serving_state(eng)
    door = AsyncFrontDoor(eng, clock=ManualClock(), sla=sla, seed=seed)
    door.start()
    rows = [arm_row("async-colocated", door, lambda: dict(eng.counters))]

    # --- disaggregated: prefill engine -> transfer queue -> decode -----
    pe = PagedEngine(cfg, params,
                     ServeConfig(max_slots=max(1, slots // 2), **base))
    de = PagedEngine(cfg, params, ServeConfig(max_slots=slots, **base))
    ctl = DisaggController(pe, de)
    ctl.generate([Request(prompt=r.prompt.copy(), max_new_tokens=2)
                  for r in trace], seed=seed)              # warm both engines
    for e in (pe, de):
        _reset_serving_state(e)
    ctl.requests.clear()
    ctl.queue.clear()
    ctl._next_rid = 0
    ctl.ticks = 0
    ctl.xfer.counters = {k: 0 for k in ctl.xfer.counters}
    sla2 = SlaMapper(granularity=1e-3, default_tick_s=1e-2)
    door2 = AsyncFrontDoor(ctl, clock=ManualClock(), sla=sla2, seed=seed)
    door2.start()

    def disagg_counters():
        out = dict(de.counters)
        out.update(ctl.xfer.counters)
        return out

    drow = arm_row("async-disagg", door2, disagg_counters)
    if check:
        assert drow["prefixes_transferred"] == n_requests, \
            "every request must cross the transfer queue exactly once"
        assert drow["payload_bytes"] > 0 and drow["blocks_transferred"] > 0
    rows.append(drow)
    return rows


def _print_rows(title, rows):
    print(f"\n[serve_throughput] {title}")
    for r in rows:
        extra = ""
        if "decode_steps" in r:
            extra += (f"  decode_steps={r['decode_steps']}"
                      f" prefill_tokens={r['prefill_tokens']}")
        if "prefix_hit_tokens" in r:
            extra += f" prefix_hits={r['prefix_hit_tokens']}"
        if "kv_bytes_resident" in r:
            extra += f" kv_resident={r['kv_bytes_resident'] / 1024:.1f}KiB"
        print(f"  {r['engine']:>15}: {r['tokens']:4d} tokens in "
              f"{r['seconds']:6.2f}s = {r['tok_per_s']:7.1f} tok/s{extra}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--impl", default="xla",
                    choices=["xla", "bitstopper_xla"])
    ap.add_argument("--alpha", type=float, default=0.6)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-len", type=int, default=48,
                    help="system-prompt length for the shared-prefix trace")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI: fewer/shorter requests")
    ap.add_argument("--check", action="store_true",
                    help="assert the oversubscription gate: >=1 "
                         "preemption, tokens bit-identical to the "
                         "uncontended run, fewer decode ticks than "
                         "worst-case reservation (with --chaos, also the "
                         "chaos gate: fault-storm tokens bit-identical, "
                         "sheds/truncations exact)")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="add the async front-door section: the mixed "
                         "trace streamed through AsyncFrontDoor, "
                         "colocated and disaggregated (prefill/decode "
                         "two-instance) — TTFT/TPOT percentiles plus the "
                         "deterministic gate (streamed tokens "
                         "bit-identical to the synchronous engine, exact "
                         "admission order, exact transfer counters)")
    ap.add_argument("--chaos", action="store_true",
                    help="add the chaos section: the mixed trace under a "
                         "scripted fault plan (crashes + snapshot/restore, "
                         "drafter/kernel faults, forced preemptions) plus "
                         "a QoS trace with deadlines and load shedding "
                         "(docs/robustness.md)")
    ap.add_argument("--hierarchy", action="store_true",
                    help="add the memory-hierarchy section: an "
                         "oversubscribed trace resumed by host swap-in "
                         "vs recompute, plus a cross-restart prefix-store "
                         "warm start (docs/serving.md, tests/test_swap.py)"
                         " — with --check, the losslessness gate "
                         "(bit-identical tokens, every swap-out spliced, "
                         ">=1 store hit, fewer prefill chunks)")
    ap.add_argument("--out", default=None,
                    help="write all trace rows to this JSON path "
                         "(default: results/BENCH_serve.json)")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="add a paged-sharded arm to the mixed trace: "
                         "serve over a (data, model) mesh and assert "
                         "tokens bit-identical to the single-device paged "
                         "arm.  Needs dp*tp devices (see --host-devices)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force this many host (CPU) devices via XLA_FLAGS "
                         "(parsed before jax import; enables --mesh on a "
                         "single-machine runner)")
    args = ap.parse_args()

    mesh = None
    if args.mesh is not None:
        dp, tp = (int(x) for x in args.mesh.split(","))
        if dp * tp > len(jax.devices()):
            ap.error(f"--mesh {dp},{tp} needs {dp * tp} devices, "
                     f"{len(jax.devices())} visible (use --host-devices)")
        mesh = make_serve_mesh(dp, tp)

    kw = dict(arch=args.arch, impl=args.impl, alpha=args.alpha,
              n_requests=args.requests, slots=args.slots, seed=args.seed)
    if args.smoke:
        kw.update(n_requests=3, slots=2)
        rows = run(**kw, lens=(5, 9), new_lo=3, new_hi=4, mesh=mesh)
        srows = run_shared_prefix(**kw, prefix_len=16, tail_lens=(3, 7),
                                  new_lo=3, new_hi=4)
        orows = run_oversubscribed(**dict(kw, n_requests=3, slots=3),
                                   lens=(10, 7, 9), new_short=4,
                                   new_long=16, long_every=1,
                                   pool_blocks=10, check=args.check)
    else:
        rows = run(**kw, mesh=mesh)
        srows = run_shared_prefix(**kw, prefix_len=args.prefix_len)
        orows = run_oversubscribed(**kw, check=args.check)
    crows = None
    if args.chaos:
        crows = run_chaos(arch=args.arch, impl=args.impl, alpha=args.alpha,
                          seed=args.seed, check=args.check)
    hrows = None
    if args.hierarchy:
        hrows = run_hierarchy(arch=args.arch, impl=args.impl,
                              alpha=args.alpha, seed=args.seed,
                              check=args.check)
    arows = None
    if args.async_:
        akw = dict(kw, check=args.check)
        if args.smoke:
            arows = run_async(**akw, lens=(5, 9), new_lo=3, new_hi=4)
        else:
            arows = run_async(**akw)

    _print_rows(f"mixed trace arch={args.arch} impl={args.impl} "
                f"requests={kw['n_requests']} slots={kw['slots']}", rows)
    static = next(r for r in rows if r["engine"] == "static-bucket")
    speedup = rows[0]["tok_per_s"] / static["tok_per_s"]
    print(f"  paged/static throughput ratio: {speedup:.2f}x")
    if mesh is not None:
        print(f"  paged-sharded arm (mesh {args.mesh}): tokens bit-identical"
              f" to single-device paged")

    _print_rows(f"shared-prefix trace prefix_len="
                f"{16 if args.smoke else args.prefix_len}", srows)
    shared = next(r for r in srows if r["engine"] == "paged-shared")
    unshared = next(r for r in srows if r["engine"] == "paged-unshared")
    contig = next(r for r in srows if r["engine"] == "contiguous")
    print(f"  KV resident: shared {shared['kv_bytes_resident'] / 1024:.1f}KiB"
          f" vs unshared {unshared['kv_bytes_resident'] / 1024:.1f}KiB"
          f" vs contiguous {contig['kv_bytes_resident'] / 1024:.1f}KiB")

    _print_rows("long-tail oversubscribed trace", orows)
    over = next(r for r in orows if r["engine"] == "oversubscribed")
    res = next(r for r in orows if r["engine"] == "reserved")
    print(f"  pool: {over['pool_blocks']} blocks vs "
          f"{over['worst_case_blocks']} worst-case-reserved; "
          f"oversubscribed served in {over['decode_steps']} decode ticks "
          f"({over['preemptions']} preemptions) vs {res['decode_steps']} "
          f"reserved — "
          f"{res['decode_steps'] / max(over['decode_steps'], 1):.2f}x "
          f"fewer ticks, peak {over['peak_live_blocks']} live blocks")
    if args.check:
        print("[serve_throughput] oversubscription gate OK: preemption "
              "observed, tokens lossless, fewer ticks than worst-case "
              "reservation")

    if crows is not None:
        _print_rows("chaos trace (scripted fault plan + QoS)", crows)
        cr = next(r for r in crows if r["engine"] == "chaos")
        qr = next(r for r in crows if r["engine"] == "qos")
        print(f"  fault storm: {cr['crashes']} crashes / {cr['restores']} "
              f"restores, {cr['snapshots_taken']} snapshots "
              f"({cr['snapshots_interrupted']} interrupted), "
              f"{cr['degradations']} kernel degradations, "
              f"{cr['drafter_failures']} drafter failures, "
              f"{cr['forced_preemptions']} forced preemptions — "
              f"bit_identical={cr['bit_identical']}")
        print(f"  qos: shed rids {qr['shed_rids']} "
              f"(watermark {qr['shed_watermark']}, deadline "
              f"{qr['shed_deadline']}), truncated rids "
              f"{qr['truncated_rids']}")
        if args.check:
            print("[serve_throughput] chaos gate OK: fault-storm tokens "
                  "bit-identical, sheds and truncations exact")

    if hrows is not None:
        _print_rows("memory-hierarchy trace (swap + prefix store)", hrows)
        swp = next(r for r in hrows if r["engine"] == "swap-resume")
        rec = next(r for r in hrows if r["engine"] == "recompute-resume")
        wrm = next(r for r in hrows if r["engine"] == "store-warmed")
        cld = next(r for r in hrows if r["engine"] == "store-cold")
        print(f"  resume: {swp['swap_outs']} swap-outs / "
              f"{swp['swap_ins']} swap-ins ({swp['swap_in_tokens']} tokens"
              f" spliced, {swp['swap_fallbacks']} fallbacks) -> "
              f"{swp['prefill_chunks']} prefill chunks vs "
              f"{rec['prefill_chunks']} recompute; host swap peak "
              f"{swp['host_swap_bytes_peak']} bytes")
        print(f"  warm start: {wrm['prefix_store_hits']} store hits "
              f"({wrm['prefix_store_tokens']} tokens) -> "
              f"{wrm['prefill_chunks']} prefill chunks vs "
              f"{cld['prefill_chunks']} cold; "
              f"{wrm['prefix_records_flushed']} records / "
              f"{wrm['disk_prefix_bytes']} bytes on disk")
        if args.check:
            print("[serve_throughput] hierarchy gate OK: swap resume and "
                  "store warm start bit-identical, fewer prefill chunks, "
                  "swap records fully consumed")

    if arows is not None:
        _print_rows("async front-door trace (streamed)", arows)
        colo = next(r for r in arows if r["engine"] == "async-colocated")
        dis = next(r for r in arows if r["engine"] == "async-disagg")
        print(f"  streamed-vs-sync bit_identical: colocated "
              f"{colo['bit_identical']}, disagg {dis['bit_identical']}; "
              f"admission order {colo['admission_order']} "
              f"(deadline {colo['deadline_ticks_mapped']} ticks)")
        print(f"  TTFT p50/p95: colocated {colo['ttft_ticks_p50']:.0f}/"
              f"{colo['ttft_ticks_p95']:.0f} ticks "
              f"({colo['ttft_ms_p50']:.0f}/{colo['ttft_ms_p95']:.0f} ms), "
              f"TPOT p50 {colo['tpot_ms_p50']:.1f} ms")
        print(f"  disagg vs colocated: {dis['tok_per_s']:.1f} vs "
              f"{colo['tok_per_s']:.1f} tok/s; transfers: "
              f"{dis['prefixes_transferred']} prefixes / "
              f"{dis['blocks_transferred']} blocks / "
              f"{dis['payload_bytes']} payload bytes")
        if args.check:
            print("[serve_throughput] async gate OK: streamed and "
                  "disaggregated tokens bit-identical to the synchronous "
                  "engine; admission and transfer sets exact")

    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_serve.json")
    payload = {
        "config": {"arch": args.arch, "impl": args.impl,
                   "alpha": args.alpha, "smoke": args.smoke,
                   "seed": args.seed},
        "mixed": rows,
        "shared_prefix": srows,
        "oversubscribed": orows,
    }
    if crows is not None:
        payload["chaos"] = crows
    if hrows is not None:
        payload["hierarchy"] = hrows
    if arows is not None:
        payload["async"] = arows
    if os.path.dirname(out):
        os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"[serve_throughput] wrote {out}")


if __name__ == "__main__":
    main()
