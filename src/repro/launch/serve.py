"""Serving launcher: paged continuous-batching generation with BitStopper
sparse attention over a mixed-length request trace.

``python -m repro.launch.serve --arch stablelm-1.6b --impl bitstopper_xla``

By default the model is the arch's reduced same-family config (d_model 64,
vocab 256), sized for CPU smoke runs.  ``--full`` builds the published
config (``configs/registry.py:get_config``) with bf16 parameters and
activations — the width a chip serves; ``chip_smoke.py`` drives that path
through :func:`build` and :func:`serve`.

Engines:

* ``--engine paged`` (default) — block-pool KV cache with copy-on-write
  prefix sharing and chunked prefill.  Admission is bounded by pool
  capacity (``--pool-blocks``) rather than a per-slot ``max_len``; block
  granularity is ``--page-size`` tokens and prompts prefill
  ``--prefill-chunk`` tokens per scheduler tick, interleaved with decode.
  With ``--oversubscribe`` admission reserves prompt-sized block budgets
  instead of worst-case ``max_new_tokens`` and mid-decode exhaustion
  preempts a victim (``--preempt-policy``), which later resumes
  losslessly — see ``docs/serving.md`` for the full request lifecycle.
* ``--engine continuous`` — the contiguous per-slot cache (each slot
  reserves ``max_len`` rows); the paged engine is bit-identical to it on
  the dense path, at a fraction of the resident KV memory.
* ``--engine static`` — legacy length-bucketed batcher (the baseline
  ``benchmarks/serve_throughput.py`` measures against).

Robustness knobs (paged engine; docs/robustness.md): ``--deadline`` /
``--shed-watermark`` bound per-request latency and queue growth;
``--snapshot-dir`` + ``--snapshot-every`` persist crash snapshots; a
``--fault-plan`` drives the whole trace through the deterministic chaos
harness (scripted crashes, kernel faults, drafter faults, …) with
kill-and-restore recovery — served tokens are bit-identical to an
undisturbed run.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.besf import BitStopperConfig
from repro.launch.mesh import make_serve_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import (
    ContinuousBatchingEngine,
    FaultPlan,
    PagedEngine,
    Request,
    ServeConfig,
    StaticBucketEngine,
    serve_with_chaos,
)


def make_trace(rng, vocab, n_requests, min_len, max_len, new_tokens,
               shared_prefix=0):
    """Mixed-length request trace; with ``shared_prefix`` > 0 every request
    starts with the same system prompt (the prefix-sharing workload)."""
    prefix = rng.integers(0, vocab, shared_prefix, dtype=np.int32)
    reqs = []
    for _ in range(n_requests):
        tail = rng.integers(0, vocab,
                            int(rng.integers(min_len, max_len + 1)),
                            dtype=np.int32)
        reqs.append(Request(prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=new_tokens))
    return reqs


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and cross-validate launcher options (``ap.error`` on misuse)."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config at full width and "
                         "depth with bf16 parameters and activations "
                         "(default: the reduced CPU smoke config)")
    ap.add_argument("--impl", default="bitstopper_xla",
                    choices=["xla", "bitstopper_xla"])
    ap.add_argument("--engine", default="paged",
                    choices=["paged", "continuous", "static"])
    ap.add_argument("--alpha", type=float, default=0.6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common system prompt of this many "
                         "tokens to every request (prefix-sharing demo)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged engine: physical KV blocks in the pool "
                         "(default: full capacity for all slots)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged engine: prompt tokens prefetched per "
                         "scheduler tick (multiple of the prefill bucket)")
    ap.add_argument("--oversubscribe", action="store_true",
                    help="paged engine: admit against prompt-sized "
                         "reservations instead of worst-case "
                         "max-new-tokens; mid-decode pool exhaustion "
                         "preempts a victim (freed + requeued; resume is "
                         "lossless, tokens never change)")
    ap.add_argument("--preempt-policy", default="fewest_tokens",
                    choices=["fewest_tokens", "lifo"],
                    help="victim choice under --oversubscribe: least "
                         "generated output (cheapest recompute) or newest "
                         "admission")
    ap.add_argument("--fused-decode", default="auto",
                    choices=["auto", "on", "off"],
                    help="paged BitStopper decode through the fused Pallas "
                         "kernel (on), the pure-JAX gather fallback (off), "
                         "or kernel iff on TPU (auto)")
    ap.add_argument("--speculative", default="off",
                    choices=["off", "ngram", "draft"],
                    help="paged engine: speculative decoding with the "
                         "n-gram prompt-lookup self-drafter (ngram) or a "
                         "draft transformer (draft; self-drafts with the "
                         "target model).  Lossless: served tokens never "
                         "change, only how many verify forwards they take")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per speculative tick")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="paged engine: serve over a (data, model) device "
                         "mesh — slots shard over the dp axis, KV heads "
                         "(paged pools + per-head BESF attention) over tp. "
                         "Output is bit-identical to single-device "
                         "(docs/serving.md).  Needs dp*tp visible devices "
                         "(CPU: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--deadline", type=int, default=None, metavar="TICKS",
                    help="paged engine: default per-request deadline in "
                         "scheduler ticks from submission; expiry "
                         "truncates started requests (emitted tokens stay "
                         "a prefix of the undisturbed stream) and sheds "
                         "never-started ones")
    ap.add_argument("--shed-watermark", type=float, default=None,
                    metavar="FRAC",
                    help="paged engine: shed queued besteffort requests "
                         "while pool saturation exceeds this fraction "
                         "(requires --oversubscribe)")
    ap.add_argument("--besteffort-tail", type=int, default=0, metavar="N",
                    help="mark the last N trace requests slo=besteffort "
                         "(sheddable; preferred preemption victims)")
    ap.add_argument("--swap-host-bytes", type=int, default=0, metavar="B",
                    help="paged engine: host-RAM budget for swap-to-host "
                         "preemption — victims' exclusive blocks copy to "
                         "host and resume by splice instead of chunked-"
                         "prefill recompute (requires --oversubscribe; "
                         "0 = recompute only)")
    ap.add_argument("--prefix-store-dir", default=None,
                    help="paged engine: persistent prefix store — cold "
                         "registered prefix blocks spill here (atomic "
                         "stage-then-promote) and a restarted engine warms "
                         "its prefix cache from it")
    ap.add_argument("--prefix-host-bytes", type=int, default=0, metavar="B",
                    help="paged engine: host-RAM tier between the device "
                         "prefix LRU and the disk store (evictions cascade "
                         "downward; 0 = spill straight to disk)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="persist crash snapshots (engine host state; "
                         "atomic stage-then-promote) under this directory")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="N",
                    help="snapshot cadence in engine ticks (with "
                         "--snapshot-dir; 0 = only the initial snapshot)")
    ap.add_argument("--fault-plan", default=None, metavar="PLAN",
                    help="drive the trace through the chaos harness under "
                         "this fault plan: inline JSON [[kind, tick], ...] "
                         "or @file.json.  Crashes need --snapshot-dir to "
                         "restore from")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.mesh is not None:
        if args.engine != "paged":
            ap.error("--mesh requires --engine paged")
        try:
            dp, tp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh expects 'dp,tp' (got {args.mesh!r})")
        n_dev = len(jax.devices())
        if dp * tp > n_dev:
            ap.error(f"--mesh {dp},{tp} needs {dp * tp} devices, "
                     f"{n_dev} visible")
    if args.speculative != "off" and args.engine != "paged":
        ap.error("--speculative requires --engine paged "
                 "(block-table rollback)")
    if args.oversubscribe and args.engine != "paged":
        ap.error("--oversubscribe requires --engine paged "
                 "(block-pool preemption)")
    if args.engine != "paged" and (
            args.swap_host_bytes or args.prefix_host_bytes
            or args.prefix_store_dir is not None):
        ap.error("--swap-host-bytes/--prefix-store-dir/--prefix-host-bytes "
                 "require --engine paged (docs/serving.md)")
    if args.engine != "paged" and (
            args.fault_plan is not None or args.snapshot_dir is not None
            or args.deadline is not None
            or args.shed_watermark is not None or args.snapshot_every):
        ap.error("--fault-plan/--snapshot-dir/--snapshot-every/--deadline/"
                 "--shed-watermark require --engine paged "
                 "(docs/robustness.md)")
    return args


def model_config(args) -> ModelConfig:
    """The served model: published widths in bf16 with ``--full``, else
    the reduced same-family smoke config."""
    if args.full:
        cfg = get_config(args.arch).replace(dtype="bfloat16",
                                            param_dtype="bfloat16")
    else:
        cfg = reduced_config(args.arch)
    return cfg.replace(attn_impl=args.impl,
                       bitstopper=BitStopperConfig(alpha=args.alpha))


@dataclasses.dataclass
class Deployment:
    """Everything :func:`serve` needs: the model (random weights from
    ``--seed``), the engine configuration and the request trace."""
    cfg: ModelConfig
    params: object
    scfg: ServeConfig
    reqs: list
    make_engine: Callable


def build(args) -> Deployment:
    """Build the model, engine configuration and trace from parsed
    options — no engine runs yet."""
    mesh = None
    if args.mesh is not None:
        dp, tp = (int(x) for x in args.mesh.split(","))
        mesh = make_serve_mesh(dp, tp)
    cfg = model_config(args)
    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    scfg = ServeConfig(
        max_len=args.shared_prefix + args.max_prompt + args.new_tokens + 8,
        max_slots=args.slots, temperature=args.temperature,
        page_size=args.page_size, pool_blocks=args.pool_blocks,
        prefill_chunk=args.prefill_chunk,
        fused_decode={"auto": None, "on": True, "off": False}[
            args.fused_decode],
        speculative=args.speculative, draft_k=args.draft_k,
        oversubscribe=args.oversubscribe,
        preempt_policy=args.preempt_policy, mesh=mesh,
        deadline_ticks=args.deadline, shed_watermark=args.shed_watermark,
        snapshot_every=args.snapshot_every,
        swap_host_bytes=args.swap_host_bytes,
        prefix_store_dir=args.prefix_store_dir,
        prefix_host_bytes=args.prefix_host_bytes)
    def make_engine():
        return {"paged": PagedEngine,
                "continuous": ContinuousBatchingEngine,
                "static": StaticBucketEngine}[args.engine](cfg, params, scfg)

    rng = np.random.default_rng(args.seed)
    reqs = make_trace(rng, cfg.vocab, args.requests,
                      args.min_prompt, args.max_prompt, args.new_tokens,
                      shared_prefix=args.shared_prefix)
    if args.besteffort_tail:
        for r in reqs[len(reqs) - args.besteffort_tail:]:
            r.slo = "besteffort"
    return Deployment(cfg=cfg, params=params, scfg=scfg, reqs=reqs,
                      make_engine=make_engine)


def serve(dep: Deployment, seed: int = 0, engine=None):
    """Serve the deployment's trace to completion — on ``engine`` if given
    (e.g. one already warmed up), else on a fresh one; returns
    ``(engine, seconds)``, the engine for its counters."""
    engine = dep.make_engine() if engine is None else engine
    t0 = time.monotonic()
    engine.generate(dep.reqs, seed=seed)
    return engine, time.monotonic() - t0


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    dep = build(args)
    reqs = dep.reqs
    if args.fault_plan is not None or args.snapshot_dir is not None:
        plan = None
        if args.fault_plan is not None:
            text = args.fault_plan
            if text.startswith("@"):
                with open(text[1:]) as f:
                    text = f.read()
            plan = FaultPlan.from_json(text)
        t0 = time.monotonic()
        reqs, rep = serve_with_chaos(
            dep.make_engine, reqs, seed=args.seed, plan=plan,
            snapshot_dir=args.snapshot_dir)
        dt = time.monotonic() - t0
        n_tok = sum(len(r.generated) for r in reqs)
        c = rep["engine_counters"]
        print(f"[serve] {len(reqs)} requests / {n_tok} new tokens in "
              f"{dt:.2f}s ({n_tok / dt:.1f} tok/s, engine={args.engine}, "
              f"impl={args.impl}, chaos)")
        print(f"[serve] chaos: {rep['crashes']} crashes / "
              f"{rep['restores']} restores, "
              f"{rep['snapshots_taken']} snapshots "
              f"({rep['snapshots_interrupted']} interrupted, "
              f"{rep['staging_reclaimed']} staging orphans reclaimed), "
              f"fired={rep['fired_by_kind']}, unfired={rep['unfired']}")
        print(f"[serve] chaos: {c.get('degradations', 0)} kernel "
              f"degradations, {c.get('drafter_failures', 0)} drafter "
              f"failures, {c.get('forced_preemptions', 0)} forced "
              f"preemptions, {c.get('requests_shed', 0)} shed "
              f"(watermark {c.get('shed_watermark', 0)} / deadline "
              f"{c.get('shed_deadline', 0)}), "
              f"{c.get('deadline_truncated', 0)} deadline-truncated")
        print(f"[serve] counters: {c}")
        return

    engine, dt = serve(dep, seed=args.seed)
    n_tok = sum(len(r.generated) for r in reqs)
    print(f"[serve] {len(reqs)} requests / {n_tok} new tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, engine={args.engine}, impl={args.impl})")
    if isinstance(engine, (PagedEngine, ContinuousBatchingEngine)):
        print(f"[serve] counters: {engine.counters}")
        if isinstance(engine, PagedEngine) and args.speculative != "off":
            c = engine.counters
            acc = (c["spec_accepted"] / c["spec_proposed"]
                   if c["spec_proposed"] else 0.0)
            print(f"[serve] speculative({args.speculative}, k={args.draft_k}):"
                  f" {c['spec_ticks']} verify ticks, "
                  f"{c['spec_accepted']}/{c['spec_proposed']} drafts "
                  f"accepted ({acc:.0%}), {c['spec_bailouts']} "
                  f"scale-growth bailouts, "
                  f"{c['decode_tokens']}/{c['decode_steps']} tokens/tick")
        if isinstance(engine, PagedEngine) and args.oversubscribe:
            c = engine.counters
            print(f"[serve] oversubscribed({args.preempt_policy}): "
                  f"{c['preemptions']} preemptions, "
                  f"{c['preempt_freed_blocks']} blocks reclaimed, "
                  f"{c['preempt_dropped_tokens']} cached tokens dropped "
                  f"(resume re-maps registered blocks, recomputes the "
                  f"unshared tail)")
        if isinstance(engine, PagedEngine) and (
                args.swap_host_bytes or args.prefix_host_bytes
                or args.prefix_store_dir is not None):
            if args.prefix_store_dir is not None:
                # Graceful shutdown: persist still-registered prefix
                # blocks so the next launch warms from the store.
                flushed = engine.flush_prefixes()
                print(f"[serve] prefix store: flushed {flushed} "
                      f"record(s) to {args.prefix_store_dir}")
            c = engine.counters
            print(f"[serve] hierarchy: {c['swap_outs']} swap-outs / "
                  f"{c['swap_ins']} swap-ins ({c['swap_in_tokens']} tokens "
                  f"spliced, {c['swap_fallbacks']} recompute fallbacks), "
                  f"{c['prefix_spills']} prefix spills, "
                  f"{c['prefix_store_hits']} store hits "
                  f"({c['prefix_store_tokens']} tokens warmed); "
                  f"tiers={engine.memory_report()}")
        if isinstance(engine, PagedEngine):
            print(f"[serve] kv pool: page_size={engine.layout.page_size} "
                  f"blocks={engine.layout.pool_blocks} "
                  f"peak_live={engine.pool.peak_live_blocks} "
                  f"resident={engine.kv_bytes_resident() / 1024:.1f} KiB "
                  f"(contiguous would reserve "
                  f"{engine.kv_bytes_contiguous_equiv() / 1024:.1f} KiB)")
        rep = engine.sparsity_report([r.prompt for r in reqs])
        if rep:
            agg = {k: round(v, 4) for k, v in rep.items()
                   if k != "per_request"}
            print(f"[serve] measured sparsity (aggregate): {agg}")
            for r in rep["per_request"]:
                print(f"[serve]   len={r['prompt_len']:4d} "
                      f"planes={r['plane_fraction']:.2f} "
                      f"survivors={r['survivor_fraction']:.2f}")


if __name__ == "__main__":
    main()
