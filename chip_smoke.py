#!/usr/bin/env python3
"""Chip smoke: the paged BitStopper serving path, end to end, on a TPU.

    python3 chip_smoke.py             # one chip: stablelm-1.6b at full width
    python3 chip_smoke.py --chips 4   # (data=1, model=4) mesh vs one device
    python3 chip_smoke.py --reduced   # CPU rehearsal: reduced config,
                                      # kernels interpreted

The default run builds stablelm-1.6b at its published width and depth
(24 layers, d_model 2048, 32 x 64 heads, vocab 100352) with bf16 random
weights from ``--seed`` through the launcher's own build and serve steps
(``repro.launch.serve``), serves a seeded 8-request trace (prompts of
128-1024 tokens, one pair sharing a 256-token prefix, 32 greedy new tokens
each) on 4 slots of the paged engine, and checks:

* the backend is a TPU, the paged kernels compile natively
  (``interpret`` resolves False) and the engine runs the fused decode
  (``fused_decode`` True, no circuit-breaker degradation);
* every request is served in full and the shared prefix is hit;
* the fused paged decode and verify kernels match their pure-JAX oracles
  on the chip at the model's head geometry — plane counts and survivors
  exactly, outputs within ``KERNEL_OUT_ATOL``;
* the compiled decode step holds the Pallas kernel (``tpu_custom_call``)
  and its logits on a live 4-slot state agree with the same step through
  the gather oracle within ``LOGIT_REL_TOL``.

``--chips 4`` runs only the mesh path: the same trace on a (data=1,
model=4) mesh (KV heads tensor-parallel) and on one device, and requires
identical tokens.

Timings printed are smoke timing, not metrics.  Any failed check exits
non-zero before the last line; the last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import quantization as qlib  # noqa: E402
from repro.core.besf import (  # noqa: E402
    besf_attention_decode_paged,
    besf_attention_verify_paged,
)
from repro.kernels.paged_decode import paged_bitstopper_decode  # noqa: E402
from repro.kernels.paged_verify import paged_bitstopper_verify  # noqa: E402
from repro.kernels.runtime import resolve_interpret  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import Request  # noqa: E402

ARCH = "stablelm-1.6b"
# Kernel vs oracle attention outputs.  Both walk the same exact-integer
# plane scores and LATS decisions (checked bit for bit) and run the
# softmax * V stage in f32 at full matmul precision, differing only in
# summation order: 2.4e-7 to 7.2e-7 on a v5e.  A wrong V page, V scale
# or epilogue moves an output by a sizeable share of max|out| (printed
# beside the difference); 1e-5 leaves room for the float order only.
KERNEL_OUT_ATOL = 1e-5
# Fused vs gather-oracle decode logits at full depth, relative to the
# largest oracle logit.  Per layer the two paths agree to float rounding
# (above), but each of 24 bf16 layers of a random-init model can turn a
# rounding difference into a bf16 flip that later layers amplify: 1.25e-2
# on a v5e with seed 0.  The per-layer bit-exact checks are the
# sharp test; this bound catches a decode step that diverges wholesale.
LOGIT_REL_TOL = 2e-2


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def memory(phase: str) -> None:
    """Device memory held now and the high-water mark so far."""
    stats = jax.devices()[0].memory_stats() or {}
    say(f"memory after {phase}: bytes_in_use="
        f"{stats.get('bytes_in_use', 'not reported')} peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


# Prompt lengths are multiples of the 64-token prefill chunk (one jit
# shape); request 4 shares request 0's first 256 tokens (16 pages) and is
# admitted only after request 0 has published them.  The CPU rehearsal
# divides every length by 16.
PROMPT_LENS = (1024, 128, 512, 768, 576, 896, 256, 640)
SHARED_PREFIX = 256
NEW_TOKENS = 32


def trace(vocab: int, seed: int, reduced: bool) -> list[Request]:
    scale = 16 if reduced else 1
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, L // scale, dtype=np.int32)
               for L in PROMPT_LENS]
    prompts[4][:SHARED_PREFIX // scale] = prompts[0][:SHARED_PREFIX // scale]
    new = NEW_TOKENS // (4 if reduced else 1)
    return [Request(prompt=p, max_new_tokens=new) for p in prompts]


def build(seed: int, reduced: bool, mesh: str | None = None):
    """The launcher's own build step, with the smoke's trace."""
    scale = 16 if reduced else 1
    argv = ["--arch", ARCH, "--impl", "bitstopper_xla", "--slots", "4",
            "--page-size", "16", "--seed", str(seed),
            "--max-prompt", str(max(PROMPT_LENS) // scale),
            "--new-tokens", str(NEW_TOKENS // (4 if reduced else 1)),
            # auto on a chip (must resolve to the kernel); the interpreted
            # kernel on the CPU rehearsal
            "--fused-decode", "on" if reduced else "auto"]
    if not reduced:
        argv.append("--full")
    if mesh is not None:
        argv += ["--mesh", mesh]
    t0 = time.monotonic()
    dep = serve.build(serve.parse_args(argv))
    jax.block_until_ready(dep.params)
    say(f"built params in {time.monotonic() - t0:.1f}s (smoke timing)")
    return dataclasses.replace(dep, reqs=trace(dep.cfg.vocab, seed, reduced))


def describe(dep) -> None:
    cfg, scfg = dep.cfg, dep.scfg
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(dep.params))
    say(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.head_dim} kv_heads={cfg.n_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} params={n_params} "
        f"param_dtype={cfg.param_dtype} dtype={cfg.dtype} "
        f"attn_impl={cfg.attn_impl}")
    say(f"serve: slots={scfg.max_slots} page_size={scfg.page_size} "
        f"max_len={scfg.max_len} pool_blocks={scfg.resolved_pool_blocks()} "
        f"cache_dtype={scfg.cache_dtype} mesh={scfg.mesh is not None}")


def served_tokens(reqs) -> list[list[int]]:
    return [list(r.generated) for r in reqs]


def check_served(reqs, vocab: int) -> int:
    for i, r in enumerate(reqs):
        check(len(r.generated) == r.max_new_tokens,
              f"request {i} got {len(r.generated)}/{r.max_new_tokens} tokens")
        check(all(0 <= t < vocab for t in r.generated),
              f"request {i} produced a token outside the vocab")
    return sum(len(r.generated) for r in reqs)


def kernel_parity(cfg, seed: int) -> None:
    """Fused decode and verify kernels vs their oracles at the model's
    head geometry, on random pools behind shuffled block tables."""
    Hq, Hkv, D, bs, bits = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16,
                            cfg.bitstopper.bits)
    B, MB = 4, 8
    P = 1 + B * MB
    rng = np.random.default_rng(seed)
    k_pool = rng.normal(size=(P, bs, Hkv, D)).astype(np.float32) * 2
    v_pool = rng.normal(size=(P, bs, Hkv, D)).astype(np.float32)
    k_amax = jnp.asarray(np.abs(k_pool).max(axis=(0, 1, 3)))
    v_amax = jnp.asarray(np.abs(v_pool).max(axis=(0, 1, 3)))
    # lane-dense [P, bs, Hkv*D], as the serving cache stores them
    k_pool = jnp.asarray(k_pool.reshape(P, bs, -1))
    v_pool = jnp.asarray(v_pool.reshape(P, bs, -1))
    pools = dict(k=k_pool, v=v_pool, k_amax=k_amax, v_amax=v_amax,
                 kq=qlib.pack_pool_planes(k_pool, k_amax, bits),
                 table=jnp.asarray(1 + rng.permutation(B * MB).reshape(B, MB),
                                   jnp.int32),
                 length=jnp.asarray([MB * bs, 100, 77, 0], jnp.int32))
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    report("decode", [decode_parity(cfg, pools, q)])

    Sq = 5
    qv = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    lengths = pools["length"]
    qpos = jnp.maximum(lengths[:, None] - Sq + jnp.arange(Sq), 0)
    vlen = jnp.where(lengths[:, None] > 0, qpos + 1, 0)
    args = (pools["v"], pools["table"], vlen, qpos, k_amax, v_amax)
    ora = besf_attention_verify_paged(qv, pools["k"], *args,
                                      cfg=cfg.bitstopper)
    ker = paged_bitstopper_verify(qv, pools["kq"], *args, cfg=cfg.bitstopper)
    report("verify", [compare(ora, ker)])


def decode_parity(cfg, pools, q):
    """Kernel vs oracle decode over one layer's pools (``pools`` holds a
    paged cache's leaves: k, v, kq, k_amax, v_amax, table, length)."""
    lengths = pools["length"]
    args = (pools["v"], pools["table"], lengths, jnp.maximum(lengths - 1, 0),
            pools["k_amax"], pools["v_amax"])
    ora = besf_attention_decode_paged(q, pools["k"], *args,
                                      cfg=cfg.bitstopper)
    ker = paged_bitstopper_decode(q, pools["kq"], *args, cfg=cfg.bitstopper)
    return compare(ora, ker)


def compare(ora, ker):
    """(plane counts and survivors identical, max |out diff|, max |oracle
    out|, planes fetched, planes of a dense walk) for one kernel/oracle
    pair."""
    exact = (np.array_equal(np.asarray(ora.rounds), np.asarray(ker.rounds))
             and np.array_equal(np.asarray(ora.survivors),
                                np.asarray(ker.survivors)))
    out = np.asarray(ora.out, np.float32)
    diff = float(np.max(np.abs(out - np.asarray(ker.out, np.float32))))
    rounds = np.asarray(ker.rounds)
    return (exact, diff, float(np.max(np.abs(out))), int(rounds.sum()),
            int((rounds > 0).sum()) * 12)


def report(name, results) -> None:
    exact = all(r[0] for r in results)
    diff = max(r[1] for r in results)
    scale = max(r[2] for r in results)
    planes, dense = sum(r[3] for r in results), sum(r[4] for r in results)
    say(f"kernel parity {name}: rounds+survivors equal={exact} "
        f"max|out diff|={diff!r} (tol {KERNEL_OUT_ATOL}) "
        f"max|out|={scale!r} "
        f"planes fetched={planes} of {dense} on reached pages")
    check(exact, f"{name}: kernel plane counts / survivors differ from the "
          f"oracle")
    check(np.isfinite(diff) and diff <= KERNEL_OUT_ATOL,
          f"{name}: kernel output differs from the oracle by {diff!r}")


def paged_layers(tree):
    """Every BitStopper layer's paged cache in a (possibly scan-stacked)
    cache pytree, one dict of leaves per layer."""
    if isinstance(tree, dict) and "kq" in tree:
        if tree["table"].ndim == 3:                  # [layers, B, MB]
            for i in range(tree["table"].shape[0]):
                yield jax.tree_util.tree_map(lambda a, i=i: a[i], tree)
        else:
            yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from paged_layers(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from paged_layers(v)


def decode_step(cfg):
    def step(params, tokens, caches, positions):
        logits, _, _ = T.forward(params, tokens, cfg, caches=caches,
                                 positions=positions)
        return logits[:, -1].astype(jnp.float32)
    return jax.jit(step)


def oracle_check(engine, seed: int, reduced: bool) -> None:
    """Bring 4 probe requests to a live decode state, then run one decode
    step of that state through the fused kernel and the gather oracle."""
    scale = 16 if reduced else 1
    rng = np.random.default_rng(seed + 1)
    for L in (256, 384, 512, 640):
        engine.submit(Request(
            prompt=rng.integers(0, engine.cfg.vocab, L // scale,
                                dtype=np.int32),
            max_new_tokens=32 // (2 if reduced else 1)))
    while not all(st is not None and st.prefilled() for st in engine.slots):
        check(engine.step(), "probe requests finished before all 4 slots "
              "reached decode")
    inputs = engine.decode_inputs()
    ctx = [int(x) for x in engine.lengths]
    say(f"probe state: 4 decoding slots, context lengths {ctx}")

    # The kernel on every layer's live serving cache — the plane pool as
    # the cache write path built it — against the oracle's on-the-fly
    # quantization of the f32 pool, for queries in the activation dtype.
    cfg = engine.cfg
    rng = np.random.default_rng(seed + 3)
    q = jnp.asarray(rng.normal(size=(len(ctx), cfg.n_heads, cfg.head_dim)),
                    cfg.activation_dtype)
    layers = [decode_parity(cfg, pools, q)
              for pools in paged_layers(inputs[1])]
    check(len(layers) == cfg.n_layers, f"found {len(layers)} paged layers")
    report(f"decode, live cache of {len(layers)} layers", layers)
    memory("live-cache parity")

    t0 = time.monotonic()
    fused = decode_step(engine.cfg).lower(engine.params, *inputs).compile()
    say(f"fused decode step compiled in {time.monotonic() - t0:.1f}s "
        f"(smoke timing)")
    has_kernel = "tpu_custom_call" in fused.as_text()
    say(f"decode step HLO holds tpu_custom_call: {has_kernel}")
    if not reduced:
        check(has_kernel, "the compiled decode step holds no Pallas kernel")
    lf = np.asarray(fused(engine.params, *inputs))
    oracle = decode_step(engine.cfg.replace(fused_decode=False))
    lo = np.asarray(oracle(engine.params, *inputs))
    check(np.isfinite(lf).all() and np.isfinite(lo).all(),
          "non-finite decode logits")
    diff = float(np.max(np.abs(lf - lo)))
    scale_o = float(np.max(np.abs(lo)))
    rel = diff / max(scale_o, 1e-30)
    agree = float(np.mean(lf.argmax(-1) == lo.argmax(-1)))
    say(f"fused vs gather-oracle decode logits: max|diff|={diff!r} "
        f"max|logit|={scale_o!r} relative={rel!r} (tol {LOGIT_REL_TOL}) "
        f"argmax agreement={agree}")
    check(rel <= LOGIT_REL_TOL,
          f"fused decode logits differ from the oracle by {rel!r} relative")


def run_one_chip(seed: int, reduced: bool) -> None:
    dep = build(seed, reduced)
    describe(dep)
    interp = resolve_interpret(None)
    say(f"resolved interpret={interp}")
    if not reduced:
        check(not interp, "Pallas kernels would run interpreted")

    memory("build")
    kernel_parity(dep.cfg, seed)
    memory("kernel parity")

    engine = dep.make_engine()
    say(f"engine fused_decode={engine.cfg.fused_decode}")
    check(engine.cfg.fused_decode, "engine resolved to the gather oracle")
    rng = np.random.default_rng(seed + 2)
    warm = Request(prompt=rng.integers(0, dep.cfg.vocab, 64, dtype=np.int32),
                   max_new_tokens=2)
    t0 = time.monotonic()
    engine.generate([warm], seed=seed)
    say(f"compile + warm-up request: {time.monotonic() - t0:.1f}s "
        f"(smoke timing)")
    engine, dt = serve.serve(dep, seed=seed, engine=engine)
    n_tok = check_served(dep.reqs, dep.cfg.vocab)
    c = engine.counters
    say(f"served {len(dep.reqs)} requests / {n_tok} new tokens "
        f"({c['prefill_tokens']} prefill tokens, {c['decode_steps']} decode "
        f"ticks) in {dt:.2f}s wall (smoke timing, not a metric)")
    say(f"prefix_hit_tokens={c['prefix_hit_tokens']} "
        f"degradations={c['degradations']}")
    check(c["prefix_hit_tokens"] > 0, "the shared prefix was never hit")
    check(c["degradations"] == 0, "the fused kernel degraded to the oracle")
    memory("serving")

    oracle_check(engine, seed, reduced)
    memory("oracle check")
    check(engine.counters["degradations"] == 0,
          "the fused kernel degraded to the oracle")


def run_four_chips(seed: int, reduced: bool) -> None:
    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, "
          f"{len(jax.devices())} visible")
    dep = build(seed, reduced)
    describe(dep)
    engine, dt = serve.serve(dep, seed=seed)
    check_served(dep.reqs, dep.cfg.vocab)
    single = served_tokens(dep.reqs)
    say(f"single device: {sum(map(len, single))} tokens in {dt:.2f}s wall "
        f"(smoke timing), fused_decode={engine.cfg.fused_decode}")
    del engine, dep
    gc.collect()

    dep = build(seed, reduced, mesh="1,4")
    describe(dep)
    engine, dt = serve.serve(dep, seed=seed)
    check_served(dep.reqs, dep.cfg.vocab)
    sharded = served_tokens(dep.reqs)
    say(f"mesh (data=1, model=4): {sum(map(len, sharded))} tokens in "
        f"{dt:.2f}s wall (smoke timing), "
        f"fused_decode={engine.cfg.fused_decode}, "
        f"degradations={engine.counters['degradations']}")
    if not reduced:
        check(engine.cfg.fused_decode, "mesh engine resolved to the oracle")
    check(engine.counters["degradations"] == 0,
          "the fused kernel degraded to the oracle")
    same = sharded == single
    say(f"sharded tokens identical to single-device: {same}")
    if not same:
        first = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                      None) for x, y in zip(single, sharded)]
        say(f"first differing token per request: {first}")
    check(same, "mesh-sharded tokens differ from the single-device run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal: reduced config, kernels "
                         "interpreted (never the default)")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    say(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if not args.reduced:
        check(dev.platform == "tpu",
              f"no TPU: JAX found {dev.platform} devices")
    enable_compile_cache()

    if args.chips == 4:
        run_four_chips(args.seed, args.reduced)
    else:
        run_one_chip(args.seed, args.reduced)

    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
