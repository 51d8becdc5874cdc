"""Continuous-batching serving engines with decode-specialized BitStopper.

BitStopper is an *inference* accelerator: these engines are where the
paper's technique is deployed.  Two continuous batchers share the
scheduler surface:

* :class:`PagedEngine` (the default ``ServingEngine``) — a vLLM-style
  **paged** batcher: the KV cache is a refcounted block pool
  (``serving/kv_pool.py`` + ``init_caches(..., paged=PagedLayout(...))``),
  admission is bounded by pool capacity rather than ``max_len``, full
  prompt-prefix blocks are shared copy-on-write across requests, and
  prompts prefill in fixed-size chunks interleaved with decode steps.
* :class:`ContinuousBatchingEngine` — the contiguous per-slot cache
  (``init_caches(..., per_slot=True)``): each slot reserves ``max_len``
  rows; retained as the bit-identity baseline for the paged engine.

Both run decode through the single-query BitStopper fast path
(``besf_attention_decode``): all bit-plane contributions in one fused
integer contraction, per-round LATS logic reduced to elementwise ops.

Sampling is deterministic under a passed-in PRNG seed and
*schedule-invariant*: token n of request rid draws from
``fold_in(fold_in(base_key, rid), n)``, so the same trace + seed
reproduces every token on either engine regardless of slot assignment or
prefill chunking.

``sparsity_report()`` returns measured plane-fetch / survivor statistics
both aggregated and **per request**, feeding the Fig. 12/13 benchmarks
with served-traffic numbers.

``StaticBucketEngine`` preserves the pre-continuous-batching static
length-bucketed batcher as the baseline that
``benchmarks/serve_throughput.py`` compares against.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: repro.analysis.pool_sanitizer is imported lazily at pool
# construction — it imports serving.kv_pool, and a module-level import
# here would be circular through serving/__init__.
from repro.kernels.paged_decode import page_bounds
from repro.models import transformer as T
from repro.models.attention import (POS_SENTINEL, PagedLayout,
                                    apply_inject_amax_rule,
                                    extract_block_rows, repack_block_planes,
                                    requant_plane_pools, splice_block_rows)
from repro.models.config import ModelConfig
from repro.runtime.trace import Tracer
from repro.serving.chaos import CheckpointInterrupted, KernelFault
from repro.serving.kv_pool import KVBlockPool


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 512                # contiguous: KV capacity per slot;
                                      # paged: default sizing for the pool
    max_slots: int = 4                # concurrent decode batch width
    prefill_bucket: int = 16          # prompts pad up to a multiple of this
    temperature: float = 0.0          # 0 = greedy
    cache_dtype: str = "float32"
    eos_id: int | None = None         # optional early stop token
    # ---- paged engine (PagedEngine) knobs ----
    page_size: int = 16               # tokens per KV block
    pool_blocks: int | None = None    # physical blocks incl. the null block
                                      # (default: full capacity, no paging
                                      # pressure: 1 + slots*max_blocks)
    max_blocks_per_req: int | None = None  # block-table width per slot
                                      # (default: ceil(max_len / page_size))
    prefill_chunk: int | None = None  # prompt tokens per prefill tick
                                      # (default: 4*prefill_bucket; must be
                                      # a multiple of prefill_bucket)
    prefix_sharing: bool = True       # share full prompt-prefix blocks
    oversubscribe: bool = False       # paged: admit against prompt-sized
                                      # reservations instead of worst case;
                                      # mid-decode exhaustion preempts a
                                      # victim (freed + requeued, lossless
                                      # resume via chunked-prefill recompute)
    preempt_policy: str = "fewest_tokens"  # victim choice under
                                      # oversubscription: "fewest_tokens"
                                      # (least generated -> cheapest
                                      # recompute) | "lifo" (newest admitted)
    fused_decode: bool | None = None  # BitStopper decode through the fused
                                      # paged Pallas kernel (True), the
                                      # pure-JAX gather fallback (False), or
                                      # auto: kernel iff running on TPU
    # ---- speculative decoding (PagedEngine) ----
    speculative: str = "off"          # "off" | "ngram" (prompt-lookup
                                      # self-drafter) | "draft" (draft
                                      # transformer; defaults to self-draft)
    draft_k: int = 4                  # draft tokens proposed per tick
    # ---- multi-device serving (PagedEngine) ----
    mesh: Any = None                  # jax.sharding.Mesh over ("data",
                                      # "model"): slots shard over "data",
                                      # KV heads (pools + attention) over
                                      # "model"; params replicated.  Output
                                      # stays bit-identical to mesh=None
                                      # (docs/serving.md).  None =
                                      # single-device.
    # ---- robustness (PagedEngine; docs/robustness.md) ----
    deadline_ticks: int | None = None # default per-request deadline, in
                                      # scheduler ticks from submission
                                      # (Request.deadline_ticks overrides);
                                      # expiry truncates a started request
                                      # (its tokens stay a prefix of the
                                      # undisturbed stream) and sheds a
                                      # never-started one.  None = none.
    shed_watermark: float | None = None  # pool-saturation fraction past
                                      # which queued "besteffort" requests
                                      # are rejected-with-reason instead of
                                      # admitted; needs oversubscribe=True
                                      # (worst-case-reserved admission
                                      # blocks instead of overcommitting,
                                      # so shedding could never relieve
                                      # preemption pressure).  None = off.
    snapshot_every: int = 0           # crash-snapshot cadence in ticks for
                                      # serving/chaos.serve_with_chaos and
                                      # launch/serve --snapshot-every
                                      # (0 = only the initial snapshot)
    # ---- KV memory hierarchy (PagedEngine; docs/serving.md) ----
    swap_host_bytes: int = 0          # host-RAM budget for swap-to-host
                                      # preemption: a victim's exclusive
                                      # blocks copy to host and resume by
                                      # splice instead of chunked-prefill
                                      # recompute (0 = recompute only)
    prefix_store_dir: str | None = None  # persistent prefix store: cold
                                      # registered prefix blocks spill to
                                      # disk via checkpoint/store.py and a
                                      # (re)started engine warms its prefix
                                      # cache from it.  None = off.
    prefix_host_bytes: int = 0        # host-RAM tier between the device
                                      # prefix LRU and the disk store
                                      # (evictions cascade downward);
                                      # 0 = spill straight to disk

    def __post_init__(self):
        if self.mesh is not None:
            axes = set(getattr(self.mesh, "axis_names", ()))
            if not axes or not axes <= {"data", "model"}:
                raise ValueError(
                    "ServeConfig.mesh must be a Mesh over axes named "
                    f"'data'/'model', got axes {sorted(axes)}")
        # Fail at construction with a nameable field, not deep inside jit.
        for name in ("max_len", "max_slots", "prefill_bucket", "page_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got "
                                 f"{getattr(self, name)}")
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got "
                             f"{self.temperature}")
        if self.cache_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"cache_dtype must be float32|bfloat16, got "
                             f"{self.cache_dtype!r}")
        if self.pool_blocks is not None and self.pool_blocks < 2:
            raise ValueError("pool_blocks must be >= 2 (block 0 is the "
                             f"null block), got {self.pool_blocks}")
        if self.max_blocks_per_req is not None and self.max_blocks_per_req < 1:
            raise ValueError(f"max_blocks_per_req must be >= 1, got "
                             f"{self.max_blocks_per_req}")
        if self.prefill_chunk is not None:
            if self.prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{self.prefill_chunk}")
            if self.prefill_chunk % self.prefill_bucket:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"multiple of prefill_bucket ({self.prefill_bucket}): "
                    f"chunks are bucket-padded jit shapes")
        if self.fused_decode and self.page_size % 8:
            raise ValueError(
                f"fused_decode needs page_size % 8 == 0 (bit planes pack 8 "
                f"tokens/byte along the page axis), got page_size="
                f"{self.page_size}")
        if self.preempt_policy not in ("fewest_tokens", "lifo"):
            raise ValueError(
                f"preempt_policy must be fewest_tokens|lifo, got "
                f"{self.preempt_policy!r}")
        if self.speculative not in ("off", "ngram", "draft"):
            raise ValueError(
                f"speculative must be off|ngram|draft, got "
                f"{self.speculative!r}")
        if self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {self.draft_k}")
        if self.deadline_ticks is not None and self.deadline_ticks < 1:
            raise ValueError(f"deadline_ticks must be >= 1, got "
                             f"{self.deadline_ticks}")
        if self.shed_watermark is not None:
            if not 0.0 < self.shed_watermark < 1.0:
                raise ValueError(
                    f"shed_watermark must be in (0, 1), got "
                    f"{self.shed_watermark}")
            if not self.oversubscribe:
                raise ValueError(
                    "shed_watermark requires oversubscribe=True: worst-case"
                    "-reserved admission blocks the head of line instead of "
                    "overcommitting the pool, so saturation-based shedding "
                    "could never relieve preemption pressure")
        if self.snapshot_every < 0:
            raise ValueError(f"snapshot_every must be >= 0, got "
                             f"{self.snapshot_every}")
        if self.swap_host_bytes < 0:
            raise ValueError(f"swap_host_bytes must be >= 0, got "
                             f"{self.swap_host_bytes}")
        if self.prefix_host_bytes < 0:
            raise ValueError(f"prefix_host_bytes must be >= 0, got "
                             f"{self.prefix_host_bytes}")
        if self.swap_host_bytes and not self.oversubscribe:
            raise ValueError(
                "swap_host_bytes requires oversubscribe=True: swap-to-host "
                "captures preemption victims, and only oversubscribed "
                "admission ever preempts")
        if ((self.prefix_store_dir is not None or self.prefix_host_bytes)
                and not self.prefix_sharing):
            raise ValueError(
                "the prefix store extends the registered-prefix LRU tier "
                "downward; it needs prefix_sharing=True")

    # Resolved paged-layout sizes (None fields get max_len-derived defaults).
    def resolved_max_blocks(self) -> int:
        return self.max_blocks_per_req or -(-self.max_len // self.page_size)

    def resolved_pool_blocks(self) -> int:
        return (self.pool_blocks
                or 1 + self.max_slots * self.resolved_max_blocks())

    def resolved_chunk(self) -> int:
        return self.prefill_chunk or 4 * self.prefill_bucket


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                # [S] int32
    max_new_tokens: int = 32
    generated: list = dataclasses.field(default_factory=list)
    rid: int = -1                     # assigned at submit()
    # ---- robustness / QoS (PagedEngine; docs/robustness.md) ----
    deadline_ticks: int | None = None # per-request deadline in scheduler
                                      # ticks from submission (overrides
                                      # ServeConfig.deadline_ticks)
    slo: str = "standard"             # "besteffort" (sheddable past the
                                      # watermark, victimized first) |
                                      # "standard" | "strict" (victimized
                                      # last)
    # per-request accounting, filled by the engine
    prefill_len: int = 0
    admitted_step: int = -1
    finished_step: int = -1
    preemptions: int = 0              # times this request was victimized
    submitted_tick: int = -1          # engine tick at submit()
    shed_reason: str | None = None    # "watermark" | "deadline" when the
                                      # engine rejected it (no tokens)
    deadline_hit: bool = False        # finished by deadline truncation
                                      # (generated is a PREFIX of the
                                      # undisturbed stream)


class InsufficientBlocks(RuntimeError):
    """The pool cannot cover an engine-API ``prefill()``/``insert()``
    right now.  Retryable: capacity returns as requests finish — callers
    (the disaggregation controller, the async door) back off a tick
    instead of failing the request."""


@dataclasses.dataclass
class Prefix:
    """Handle to a prefilled context: paged block handles plus sampling
    state — the currency of the JetStream-style engine API
    (``PagedEngine.prefill() -> insert() -> generate_step()``).

    Two forms:

    * **attached** (``pool`` is the source engine's pool): ``blocks``
      holds physical block ids whose references the Prefix OWNS — insert
      into the same engine is a pure block-table splice, no KV moves.
    * **detached** (``payload`` set, ``pool``/``blocks`` cleared by
      ``extract()``): block contents serialized through the pool to host
      arrays, so a *different* engine instance — its own pool, its own
      block numbering — can ``insert()`` it.  This is the
      prefill/decode-disaggregation handoff.
    """
    req: Request
    chain: np.ndarray          # cached context tokens [L] int32
    length: int                # tokens cached (== len(chain))
    last_token: int            # next decode input (already appended to
                               # req.generated by the prefill sample)
    blocks: list               # attached: block ids, refs owned here
    pool: Any = None           # pool identity the blocks live in
    payload: Any = None        # detached: {"layers": [...], "amax": [...]}
    finished: bool = False     # request completed during prefill (eos /
                               # max_new_tokens == 1 / deadline) — nothing
                               # to insert, tokens already in req.generated


def _supported(cfg: ModelConfig) -> None:
    mixers = {spec.mixer for unit, _ in cfg.segments for spec in unit}
    bad = mixers - {"attn", "local_attn"}
    if bad:
        raise ValueError(
            f"continuous batching serves attention models only "
            f"(per-slot KV cache); config has mixers {sorted(bad)}")


@partial(jax.jit, static_argnames=("temperature",))
def _sample_tokens(base_key, logits, rids, counts, temperature: float):
    """Per-request deterministic sampling: row i's key is
    ``fold_in(fold_in(base_key, rid_i), n_generated_i)`` — a pure function
    of (seed, request, token index), so the sampled trace is independent of
    scheduling (slot assignment, chunked vs one-shot prefill, interleaving
    order) and identical across engines."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)

    def one(row, rid, n):
        key = jax.random.fold_in(jax.random.fold_in(base_key, rid), n)
        return jax.random.categorical(key, row / temperature)

    return jax.vmap(one)(logits, rids, counts)


def _kv_bytes_per_token(cfg: ModelConfig, dtype) -> int:
    """KV-cache bytes one cached token costs across all attention layers."""
    itemsize = jnp.dtype(dtype).itemsize
    total = 0
    for unit, reps in cfg.segments:
        for spec in unit:
            if spec.mixer in ("attn", "local_attn"):
                acfg = cfg.attn_config(spec.mixer == "local_attn")
                total += reps * 2 * acfg.n_kv_heads * acfg.head_dim * itemsize
    return total


def _plane_bytes_per_token(cfg: ModelConfig) -> int:
    """Bit-plane-pool bytes one cached token costs across BitStopper
    layers when the fused decode kernel maintains the packed ``kq`` pool:
    ``bits`` planes x 1 bit x Hkv x D per token."""
    total = 0
    for unit, reps in cfg.segments:
        for spec in unit:
            if spec.mixer not in ("attn", "local_attn"):
                continue
            acfg = cfg.attn_config(spec.mixer == "local_attn")
            if (acfg.impl in ("bitstopper", "bitstopper_xla")
                    and acfg.fused_decode):
                total += (reps * acfg.bitstopper.bits
                          * acfg.n_kv_heads * acfg.head_dim) // 8
    return total


def _amax_static_bytes(cfg: ModelConfig) -> int:
    """Pool-wide running quant-scale state (``k_amax``/``v_amax``, f32 per
    KV head) carried by every BitStopper layer's paged cache — static in
    the pool size but part of the honest resident footprint."""
    total = 0
    for unit, reps in cfg.segments:
        for spec in unit:
            if spec.mixer not in ("attn", "local_attn"):
                continue
            acfg = cfg.attn_config(spec.mixer == "local_attn")
            if acfg.impl in ("bitstopper", "bitstopper_xla"):
                total += reps * 2 * acfg.n_kv_heads * 4
    return total


def _kv_bytes_contiguous(cfg: ModelConfig, scfg: ServeConfig, dtype) -> int:
    """Resident bytes of the contiguous per-slot cache: max_len rows per
    slot per layer, except sliding-window layers whose ring buffers only
    allocate min(max_len, window) rows."""
    itemsize = jnp.dtype(dtype).itemsize
    total = 0
    for unit, reps in cfg.segments:
        for spec in unit:
            if spec.mixer not in ("attn", "local_attn"):
                continue
            acfg = cfg.attn_config(spec.mixer == "local_attn")
            rows = scfg.max_len
            if spec.mixer == "local_attn" and acfg.window:
                rows = min(rows, acfg.window)
            total += (reps * rows * 2 * acfg.n_kv_heads * acfg.head_dim
                      * itemsize)
    return total * scfg.max_slots


def _amax_leaves(caches) -> list:
    """Every ``k_amax``/``v_amax`` leaf of a paged cache pytree, in a
    deterministic traversal order (used to detect pool-wide quant-scale
    growth across a speculative draft-block write)."""
    out = []
    if isinstance(caches, dict):
        for key in sorted(caches):
            if key in ("k_amax", "v_amax"):
                out.append(caches[key])
            else:
                out.extend(_amax_leaves(caches[key]))
    elif isinstance(caches, (list, tuple)):
        for c in caches:
            out.extend(_amax_leaves(c))
    return out


def _set_amax_leaves(caches, values: list):
    """Write quant-scale leaves back into a paged cache pytree, in the
    same deterministic traversal order :func:`_amax_leaves` reads them —
    the restore half of engine snapshotting.  The running scales are
    monotone and order-dependent (growth overshoots by ``AMAX_HEADROOM``),
    so a restored engine must inherit the crash-time scales rather than
    re-derive them from recomputed tokens: with identical scales the
    recompute writes trigger no growth and every future growth event fires
    identically to the undisturbed run."""
    it = iter(values)

    def rec(c):
        if isinstance(c, dict):
            out = {}
            for key in sorted(c):
                if key in ("k_amax", "v_amax"):
                    ref = c[key]
                    out[key] = jnp.asarray(
                        np.asarray(next(it), np.float32).reshape(ref.shape),
                        ref.dtype)
                else:
                    out[key] = rec(c[key])
            return out
        if isinstance(c, (list, tuple)):
            new = [rec(x) for x in c]
            return new if isinstance(c, list) else tuple(new)
        return c

    new = rec(caches)
    leftover = sum(1 for _ in it)
    if leftover:
        raise ValueError(f"snapshot carries {leftover} extra quant-scale "
                         f"leaves the cache has no home for")
    return new


def _attach_tables(caches, table: np.ndarray, length: np.ndarray):
    """Rebuild a paged cache pytree with the engine's authoritative block
    table / fill levels attached to every layer (stacked layers broadcast
    along their leading reps axis).  K/V/pos pool leaves pass through."""
    t = jnp.asarray(table, jnp.int32)
    ln = jnp.asarray(length, jnp.int32)

    def rec(c):
        if isinstance(c, dict):
            if "table" in c:
                nt, nl = t, ln
                if c["table"].ndim == 3:          # scanned: [reps, B, MB]
                    reps = c["table"].shape[0]
                    nt = jnp.broadcast_to(t[None], (reps,) + t.shape)
                    nl = jnp.broadcast_to(ln[None], (reps,) + ln.shape)
                return dict(c, table=nt, length=nl)
            return {k: rec(v) for k, v in c.items()}
        if isinstance(c, list):
            return [rec(x) for x in c]
        return c

    return rec(caches)


class _EngineCommon:
    """Shared scheduler-loop + measurement surface of the serving engines."""

    # Spans and request stamps (runtime/trace.py).  A tracer without a
    # clock only annotates the profiler's timeline and keeps no state, so
    # one instance can be every engine's default.
    tracer: Tracer = Tracer()

    def begin(self, seed: int = 0) -> None:
        """Fix the sampling seed for a serving run.  Split out of
        :meth:`run` so external drivers (``serving/chaos.py``) can own the
        tick loop; a restored engine re-derives the same base key, keeping
        every continuation token under its original (seed, rid, n) key."""
        self._seed = seed
        self._base_key = jax.random.PRNGKey(seed)

    def pending(self) -> bool:
        """True while any submitted request is unfinished (queued or in a
        slot) — the tick-loop condition."""
        return bool(self.queue or any(r is not None for r in self.slots))

    def run(self, seed: int = 0) -> None:
        """Drain queue + slots to completion, deterministically under seed."""
        self.begin(seed)
        while self.pending():
            self.step()

    def generate(self, requests: list[Request], seed: int = 0):
        """Serve a list of requests (arbitrary prompt lengths) to
        completion; returns the same list with ``generated`` filled."""
        for r in requests:
            self.submit(r)
        self.run(seed)
        return requests

    def _sample_rows(self, logits, rids, counts) -> np.ndarray:
        with self.tracer.span("sample"):
            toks = _sample_tokens(self._base_key, logits,
                                  jnp.asarray(rids, jnp.int32),
                                  jnp.asarray(counts, jnp.int32),
                                  self.scfg.temperature)
        with self.tracer.span("readback"):
            return np.asarray(toks, np.int32)

    def _bucketed(self, L: int) -> int:
        b = self.scfg.prefill_bucket
        return min(self.scfg.max_len, -(-L // b) * b)

    # ------------------------------------------------------------------
    # measured-traffic reporting
    # ------------------------------------------------------------------

    def sparsity_report(self, prompts) -> dict[str, Any]:
        """Measured BitStopper traffic, per request and aggregated.

        ``prompts``: 2-D int array [B, S] or a list of 1-D int arrays of
        arbitrary (per-request) lengths.  Each request's prefill attention
        at the first attention layer is run through the block-granular
        semantic model; returns mean planes fetched per (q, kv-block),
        plane fraction vs dense 12-bit, block-level V-fetch fraction and
        token survivor fraction — aggregated under the legacy keys, plus a
        ``per_request`` list for served-traffic benchmarks."""
        if isinstance(prompts, np.ndarray) and prompts.ndim == 2:
            prompts = list(prompts)
        per_request = []
        for p in prompts:
            rep = _prompt_sparsity(self.cfg, self.params, np.asarray(p))
            if rep:
                per_request.append(rep)
        if not per_request:
            return {}
        # Weighted aggregation: a long prompt has many more (q-tile,
        # kv-block) units and (q, k) pairs than a short one — an
        # unweighted mean over requests would let short prompts skew the
        # traffic headline.
        blocks = np.array([r["n_blocks"] for r in per_request], np.float64)
        pairs = np.array([r["n_pairs"] for r in per_request], np.float64)

        def wmean(key, w):
            vals = np.array([r[key] for r in per_request], np.float64)
            return float((vals * w).sum() / w.sum())

        return {
            "mean_rounds": wmean("mean_rounds", blocks),
            "plane_fraction": wmean("plane_fraction", blocks),
            "block_alive_fraction": wmean("block_alive_fraction", blocks),
            "survivor_fraction": wmean("survivor_fraction", pairs),
            "per_request": per_request,
        }


class ContinuousBatchingEngine(_EngineCommon):
    """Request-level continuous batching over a per-slot KV cache."""

    def __init__(self, cfg: ModelConfig, params,
                 scfg: ServeConfig = ServeConfig()):
        _supported(cfg)
        if scfg.speculative != "off":
            raise ValueError(
                "speculative decoding needs the paged engine (block-table "
                "rollback); use PagedEngine")
        if scfg.oversubscribe:
            raise ValueError(
                "oversubscription needs the paged engine (block-pool "
                "preemption); use PagedEngine")
        if (scfg.deadline_ticks is not None
                or scfg.shed_watermark is not None or scfg.snapshot_every):
            raise ValueError(
                "deadlines / load shedding / crash snapshots are "
                "PagedEngine features (docs/robustness.md); use PagedEngine")
        if (scfg.swap_host_bytes or scfg.prefix_host_bytes
                or scfg.prefix_store_dir is not None):
            raise ValueError(
                "the KV memory hierarchy (swap_host_bytes / "
                "prefix_store_dir / prefix_host_bytes) is a PagedEngine "
                "feature (docs/serving.md); use PagedEngine")
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        if scfg.mesh is not None:
            raise ValueError(
                "ServeConfig.mesh is a PagedEngine feature (the contiguous "
                "per-slot engine is the single-device baseline)")
        self._dtype = (jnp.bfloat16 if scfg.cache_dtype == "bfloat16"
                       else jnp.float32)

        def prefill_fn(params, tokens, caches, positions, last_idx):
            # tokens/positions [1, Sp] (bucket-padded; pads hold the
            # sentinel position and are dropped by the cache write).
            logits, caches, _ = T.forward(params, tokens, cfg, caches=caches,
                                          positions=positions)
            last = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1, axis=1)
            return last[:, 0], caches

        def decode_fn(params, tokens, caches, positions):
            # tokens/positions [B, 1] — B slots, each at its own position.
            logits, caches, _ = T.forward(params, tokens, cfg, caches=caches,
                                          positions=positions)
            return logits[:, -1], caches

        def insert_fn(big, small, slot):
            def ins(b, s):
                # The slot (batch) axis is the first one where the engine
                # cache (max_slots wide) and the batch-1 prefill cache
                # differ; with max_slots == 1 every axis matches and the
                # insert is a whole-cache replacement.
                axis = next((i for i, (x, y) in
                             enumerate(zip(b.shape, s.shape)) if x != y),
                            None)
                if axis is None:
                    return s.astype(b.dtype)
                starts = tuple(slot if i == axis else 0
                               for i in range(b.ndim))
                return jax.lax.dynamic_update_slice(
                    b, s.astype(b.dtype), starts)

            return jax.tree_util.tree_map(ins, big, small)

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn)
        self._insert = jax.jit(insert_fn)

        B = scfg.max_slots
        self.caches = T.init_caches(cfg, B, scfg.max_len, self._dtype,
                                    per_slot=True)
        # Reused on every admission: jax arrays are immutable and prefill
        # is functional, so one empty 1-slot cache serves all requests.
        self._empty_slot = T.init_caches(cfg, 1, scfg.max_len, self._dtype,
                                         per_slot=True)
        self.slots: list[Request | None] = [None] * B
        self.queue: collections.deque[Request] = collections.deque()
        self.lengths = np.zeros((B,), np.int32)       # tokens in each slot
        self.last_token = np.zeros((B,), np.int32)    # next decode input
        self._next_rid = 0
        self._step = 0
        self._seed = None
        self._base_key = jax.random.PRNGKey(0)
        self.counters = {"prefill_tokens": 0, "decode_tokens": 0,
                         "decode_steps": 0, "requests_finished": 0}

    def kv_bytes_resident(self) -> int:
        """KV memory the cache keeps resident: contiguous slots reserve
        their full capacity (ring buffers: the window) no matter the
        occupancy."""
        return _kv_bytes_contiguous(self.cfg, self.scfg, self._dtype)

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> Request:
        L = len(req.prompt)
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if L + req.max_new_tokens > self.scfg.max_len:
            raise ValueError(
                f"request needs {L}+{req.max_new_tokens} tokens, "
                f"max_len={self.scfg.max_len}")
        req.rid = self._next_rid
        self._next_rid += 1
        self.queue.append(req)
        return req

    def _admit(self) -> None:
        while self.queue and None in self.slots:
            slot = self.slots.index(None)
            req = self.queue.popleft()
            L = len(req.prompt)
            Sp = self._bucketed(L)
            tokens = np.zeros((1, Sp), np.int32)
            tokens[0, :L] = np.asarray(req.prompt, np.int32)
            positions = np.full((1, Sp), POS_SENTINEL, np.int32)
            positions[0, :L] = np.arange(L, dtype=np.int32)

            last_logits, small = self._prefill(
                self.params, jnp.asarray(tokens), self._empty_slot,
                jnp.asarray(positions), jnp.asarray(L - 1, jnp.int32))
            self.caches = self._insert(self.caches, small,
                                       jnp.asarray(slot, jnp.int32))

            tok = int(self._sample_rows(last_logits, [req.rid], [0])[0])
            req.generated.append(tok)
            req.prefill_len = L
            req.admitted_step = self._step
            self.counters["prefill_tokens"] += L
            self.slots[slot] = req
            self.lengths[slot] = L
            self.last_token[slot] = tok
            self._maybe_evict(slot, tok)

    def _maybe_evict(self, slot: int, tok: int) -> None:
        req = self.slots[slot]
        if req is None:
            return
        done = len(req.generated) >= req.max_new_tokens
        if self.scfg.eos_id is not None and tok == self.scfg.eos_id:
            done = True
        if done:
            req.finished_step = self._step
            self.counters["requests_finished"] += 1
            self.slots[slot] = None

    def step(self) -> bool:
        """One scheduler tick: admit from the queue, then one decode step
        over every active slot.  Returns False when there is no work."""
        self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return bool(self.queue)
        self._step += 1
        tokens = jnp.asarray(self.last_token[:, None])
        positions = jnp.asarray(self.lengths[:, None])
        logits, self.caches = self._decode(
            self.params, tokens, self.caches, positions)
        rids = [r.rid if r is not None else 0 for r in self.slots]
        counts = [len(r.generated) if r is not None else 0
                  for r in self.slots]
        toks = self._sample_rows(logits, rids, counts)
        self.counters["decode_steps"] += 1
        for i in active:
            req = self.slots[i]
            req.generated.append(int(toks[i]))
            self.counters["decode_tokens"] += 1
            self.lengths[i] += 1
            self.last_token[i] = toks[i]
            self._maybe_evict(i, int(toks[i]))
        return True


# ---------------------------------------------------------------------------
# Paged engine: block-pool KV cache, prefix sharing, chunked prefill
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _PagedSlot:
    """Scheduler-side state of one occupied serving slot."""
    req: Request
    next_prefill: int          # ctx tokens [0, next_prefill) are cached
    blocks_reserved: int       # reservation units not yet turned into allocs
    ctx: np.ndarray            # prefill token sequence: the prompt, or —
                               # resuming a preempted request — the prompt
                               # plus every generated token already cached
                               # (all but the last, which is the next
                               # decode input, never written back yet)
    resumed: bool = False      # resuming after preemption: the tail of
                               # ``generated`` is replayed, not re-sampled
    seq: int = 0               # admission order (LIFO victim policy)

    def prefilled(self) -> bool:
        return self.next_prefill >= len(self.ctx)


class PagedEngine(_EngineCommon):
    """Continuous batching over a paged block-pool KV cache.

    Differences from :class:`ContinuousBatchingEngine`:

    * **Paged KV.**  One batch-free K/V pool per layer; slots address it
      through refcounted block tables (``kv_pool.KVBlockPool`` owns the
      host-side allocation).  Admission is bounded by *pool capacity*, not
      ``max_len``: a request may generate past ``max_len`` as long as its
      block-table width (``max_blocks_per_req``) and the pool allow.
    * **Prefix sharing.**  Full prompt blocks are published under their
      token-chain key; a later request with the same prompt prefix maps the
      shared physical blocks into its table (refcount++), skips recomputing
      those tokens, and pays near-zero duplicate KV memory — the
      system-prompt workload.
    * **Chunked prefill.**  A prompt is prefilled ``prefill_chunk`` tokens
      per scheduler tick, interleaved with decode steps of in-flight slots,
      bounding decode-latency jitter from long prompts.
    * **Oversubscription** (``ServeConfig.oversubscribe``).  Admission
      reserves only the context blocks plus one decode block instead of
      the worst case — a pool sized for realistic traffic admits more
      concurrency than worst-case ``max_new_tokens`` would allow.  When a
      mid-decode block claim then finds the pool dry, the scheduler
      preempts a victim (``preempt_policy``: fewest tokens generated, or
      newest admission): its exclusively-owned blocks free outright,
      shared/registered prefix blocks drop a reference (staying mapped or
      parking resurrectable in the LRU), and the request requeues at the
      head of the line.  Resume is **lossless**: the victim re-admits with
      its context (prompt + generated tokens), re-maps still-registered
      prefix blocks for free, recomputes the unshared tail through the
      ordinary chunked prefill, and continues decoding from its last
      sampled token — sampling keys are a pure function of (seed, rid,
      token index), so the served trace is bit-identical to an uncontended
      run (on the dense path; see ``docs/serving.md`` for the BitStopper
      quant-scale caveat).

    On the dense (``xla``) score path the served tokens are bit-identical
    to the contiguous engine: per-query attention sees the same KV set
    under the same mask, and masked view slots are exact zeros (padding
    with exact zeros/NEG_INF never perturbs f32 accumulation).  The
    BitStopper paths track the contiguous engine within LATS/quantization
    tolerance, not bit-for-bit: block prefill tiles per chunk, and paged
    decode quantizes K/V under the pool-wide running scales (a shared
    physical page must mean the same integers to every table mapping it)
    where the contiguous engine re-derives per-row view scales.

    **Fused paged decode.**  With a BitStopper impl (and ``page_size``
    divisible by 8) the cache additionally maintains an incremental
    bit-plane pool at write time, and the decode tick never gathers the
    dense per-row KV view: it hands the pool + block tables + fill levels
    straight to the paged BESF decode — the fused Pallas kernel
    (``kernels/paged_decode.py``) when ``fused_decode`` resolves True,
    else the pure-JAX paged oracle (``besf_attention_decode_paged``, the
    retained gather fallback).  The two are bit-identical (tested), so
    flipping the switch never changes served tokens.

    **Speculative decoding** (``ServeConfig.speculative``).  Each decode
    tick a drafter proposes up to ``draft_k`` tokens per slot
    (``serving/speculative.py``); the tick then runs ONE Sq=k+1 verify
    forward — [last sampled token, draft 1..k] written into the paged
    cache in a batched scatter, BitStopper attention through the
    multi-query paged verify (each query bit-identical to the Sq=1 decode
    at its position; fused Sq-tiled kernel or oracle per ``fused_decode``)
    — and accepts the longest draft prefix matching the target's own
    greedy/seeded samples.  Acceptance is **lossless**: token n is always
    sampled from logits bit-identical to non-speculative decode under the
    same ``fold_in(fold_in(seed, rid), n)`` key, so traces never change,
    only how many forwards they take.  The rejected tail is a *rollback*,
    not a rewrite: fill levels retreat (stale pool slots are unobservable
    behind the fill-level masks) and draft-tail blocks return to the pool
    with their reservation units restored (``KVBlockPool.rollback``) —
    never past the prompt/shared-prefix boundary, which lives below the
    decode region by construction.  A write that grows the pool-wide quant
    scale mid-draft-block would make earlier queries see a "future" scale;
    the engine detects scale growth on the device, discards the whole
    speculative step (immutable-cache snapshot restore) and replays it as
    a plain decode tick — rare after warmup, and the replay is the
    non-speculative path itself, so losslessness is unconditional."""

    def __init__(self, cfg: ModelConfig, params,
                 scfg: ServeConfig = ServeConfig(), drafter=None,
                 tracer: Tracer | None = None):
        _supported(cfg)
        if tracer is not None:
            self.tracer = tracer
        # Resolve the decode-kernel choice once: the fused paged Pallas
        # kernel wants compiled Pallas (TPU); everywhere else the pure-JAX
        # paged oracle (the gather fallback) is the fast interpreter-free
        # path.  An explicit ServeConfig.fused_decode bool always wins —
        # fused_decode=True off-TPU runs the kernel in interpret mode,
        # which is how CI validates it.  On TPU, auto never settles for the
        # oracle in silence: a page size the kernel cannot pack is an error
        # (fused_decode=False asks for the oracle explicitly).
        fused = scfg.fused_decode
        if fused is None:
            fused = jax.default_backend() == "tpu"
            if (fused and scfg.page_size % 8
                    and cfg.attn_impl in ("bitstopper", "bitstopper_xla")):
                raise ValueError(
                    f"on TPU the paged BitStopper decode runs the fused "
                    f"kernel, which needs page_size % 8 == 0 (bit planes "
                    f"pack 8 tokens/byte), got page_size={scfg.page_size}; "
                    f"pass fused_decode=False to serve through the gather "
                    f"oracle instead")
        # Serving routes dropless: a token's expert outputs never depend on
        # how its chunk or decode batch was routed (models/moe.py).
        cfg = self.cfg = cfg.replace(fused_decode=bool(fused),
                                     moe_dropless=True)
        self.params = params
        self.scfg = scfg
        # Mesh-sharded serving: slots over "data", KV heads (paged pools +
        # per-head BESF attention) over "model", parameters replicated —
        # the layout under which sharded output is bit-identical to
        # single-device (make_serve_rules / docs/serving.md).  The rules
        # are entered inside the jitted closures so constrain() and the
        # paged shard_map see them at trace time; the host-side scheduler,
        # KVBlockPool allocator, block tables and fill levels are untouched
        # (replicated across "model", so CoW sharing / preemption /
        # rollback / the sanitizer ledger work unchanged).
        # (MQA fallback: a KV-head count the model axis doesn't divide
        # replicates the pools via PAGED_CACHE_RULES' divisibility check
        # and skips the attention shard_map — still correct, still
        # bit-identical, just not tensor-parallel.)
        self._rules = None
        if scfg.mesh is not None:
            from repro.sharding.rules import make_serve_rules
            self._rules = make_serve_rules(scfg.mesh)
        self._dtype = (jnp.bfloat16 if scfg.cache_dtype == "bfloat16"
                       else jnp.float32)
        self._page = scfg.page_size
        # The span window layers attend (None: the model has none).
        self._window = (cfg.window if any(
            spec.mixer == "local_attn" for unit, _ in cfg.segments
            for spec in unit) else None)
        self._mb = scfg.resolved_max_blocks()
        self._chunk = scfg.resolved_chunk()
        self.layout = PagedLayout(scfg.resolved_pool_blocks(), self._page,
                                  self._mb)
        # Under REPRO_SANITIZE=1 this is the shadow-ledger wrapper with
        # freed-page poisoning (see analysis/pool_sanitizer.py); otherwise
        # a plain KVBlockPool.
        from repro.analysis.pool_sanitizer import make_kv_pool, make_swap_pool
        # KV memory hierarchy (docs/serving.md "Memory hierarchy"): host
        # swap records for preemption victims, plus a host-RAM -> disk
        # spill cascade for registered prefix blocks the device LRU
        # evicts.  The pool's evict_cb fires while the stolen block's
        # device content is still intact (before the new owner can write
        # and before the sanitizer poisons), so the spill copy is exact.
        self._swap = (make_swap_pool(scfg.swap_host_bytes)
                      if scfg.swap_host_bytes else None)
        self._prefix_host = (
            make_swap_pool(scfg.prefix_host_bytes,
                           evict_cb=self._spill_prefix_record)
            if scfg.prefix_host_bytes else None)
        evict_cb = (self._on_prefix_evict
                    if (self._prefix_host is not None
                        or scfg.prefix_store_dir is not None) else None)
        self.pool = make_kv_pool(self.layout.pool_blocks, self._page,
                                 prefix_sharing=scfg.prefix_sharing,
                                 poison_cb=self._poison_blocks,
                                 evict_cb=evict_cb)

        # Deterministic fault injection (serving/chaos.py): when a
        # FaultInjector is attached, the engine consults it at its
        # injection points (pool claim, fused kernel call, drafter) keyed
        # on self.ticks — nothing else in the tick path changes.
        self.chaos = None

        # Speculative decoding: drafter selection happens before the jits
        # are built (the verify closure exists iff a drafter does).
        self._drafter = None
        self._spec_k = scfg.draft_k
        if scfg.speculative != "off":
            if (cfg.attn_impl in ("bitstopper", "bitstopper_xla")
                    and scfg.page_size % 8):
                raise ValueError(
                    "speculative BitStopper serving needs page_size % 8 == "
                    "0 (the paged verify shares the pool-wide quant state; "
                    f"got page_size={scfg.page_size})")
            from repro.serving.speculative import make_drafter
            self._drafter = drafter if drafter is not None else \
                make_drafter(scfg.speculative, cfg, params)
        elif drafter is not None:
            raise ValueError(
                "drafter passed but ServeConfig.speculative == 'off'")

        self._build_jits()

        B = scfg.max_slots
        self.caches = T.init_caches(cfg, B, scfg.max_len, self._dtype,
                                    paged=self.layout)
        if self._rules is not None:
            # Commit the pool leaves to their mesh placement (KV-head shard
            # over "model", bookkeeping replicated) and the params to full
            # replication; jit keeps these shardings on the returned caches,
            # so every subsequent tick runs sharded without further movement.
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.sharding.rules import cache_shardings
            self.caches = jax.device_put(
                self.caches, cache_shardings(self._rules, self.caches))
            self.params = jax.device_put(
                self.params, NamedSharding(scfg.mesh, PartitionSpec()))
        self.slots: list[_PagedSlot | None] = [None] * B
        self.queue: collections.deque[Request] = collections.deque()
        self.table = np.zeros((B, self._mb), np.int32)
        self.lengths = np.zeros((B,), np.int32)
        self.last_token = np.zeros((B,), np.int32)
        self._prefill_fifo: collections.deque[int] = collections.deque()
        self._next_rid = 0
        self._admit_seq = 0
        self._step = 0
        self._seed = None
        self._base_key = jax.random.PRNGKey(0)
        # Public monotone tick counter: every fault-injection decision,
        # deadline, and snapshot cadence keys on it (never wall clock), so
        # chaos runs are replayable bit-for-bit.  Persisted by snapshot().
        self.ticks = 0
        # Every request ever submitted, by rid — makes a snapshot (and a
        # post-crash restore) self-contained: the full trace output is
        # recoverable from the engine alone.
        self.requests: dict[int, Request] = {}
        self.counters = {"prefill_tokens": 0, "prefix_hit_tokens": 0,
                         "prefill_chunks": 0, "decode_tokens": 0,
                         "decode_steps": 0, "decode_kv_tokens": 0,
                         # paged BESF kernel grid steps of one layer's
                         # call, and those inside a slot's reach
                         "decode_page_slots": 0, "decode_pages_reached": 0,
                         # one window layer's attended tokens and grid
                         # steps reached (0 without window layers)
                         "decode_window_kv_tokens": 0,
                         "decode_window_pages_reached": 0,
                         "requests_finished": 0,
                         "spec_ticks": 0, "spec_proposed": 0,
                         "spec_accepted": 0, "spec_bailouts": 0,
                         "preemptions": 0, "preempt_freed_blocks": 0,
                         "preempt_dropped_tokens": 0,
                         "requests_shed": 0, "shed_watermark": 0,
                         "shed_deadline": 0, "deadline_truncated": 0,
                         "degradations": 0, "drafter_failures": 0,
                         "forced_preemptions": 0,
                         # JetStream-style engine API (frontdoor/disagg)
                         "prefixes_prefilled": 0, "prefixes_inserted": 0,
                         "prefix_transfers": 0,
                         # KV memory hierarchy (docs/serving.md)
                         "swap_outs": 0, "swap_ins": 0,
                         "swap_fallbacks": 0, "swap_in_tokens": 0,
                         "prefix_spills": 0, "prefix_store_hits": 0,
                         "prefix_store_tokens": 0,
                         "prefix_store_interrupts": 0}

    # ------------------------------------------------------------------
    # jitted forwards + the kernel circuit breaker
    # ------------------------------------------------------------------

    def _build_jits(self) -> None:
        """(Re)build the jitted forward closures from the *current*
        ``self.cfg`` — at construction, and again when the circuit breaker
        flips ``fused_decode`` off.  The closures capture cfg by value, so
        a degrade must rebuild them; the cache pytree itself is untouched
        (the read path keys on cfg, the write path on cache structure, and
        the f32 pool is always maintained — the fallback reads the same
        cache the kernel did)."""
        cfg = self.cfg
        from repro.sharding.api import use_rules

        def prefill_fn(params, tokens, caches, positions, last_idx):
            # tokens/positions [1, Sp]: one chunk of one slot's prompt,
            # written straight into the shared pool through the slot's
            # block-table row — no post-hoc cache insert.
            with use_rules(self._rules):
                logits, caches, _ = T.forward(params, tokens, cfg,
                                              caches=caches,
                                              positions=positions)
            last = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1, axis=1)
            return last[:, 0], caches

        def decode_fn(params, tokens, caches, positions):
            with use_rules(self._rules):
                logits, caches, _ = T.forward(params, tokens, cfg,
                                              caches=caches,
                                              positions=positions)
            return logits[:, -1], caches

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn)

        # The Sq=k+1 verify forward closes over spec_verify=True so
        # multi-query BitStopper attention routes through the paged verify
        # (NOT block prefill).
        if self._drafter is not None:
            cfg_v = cfg.replace(spec_verify=True)

            def verify_fn(params, tokens, caches, positions):
                with use_rules(self._rules):
                    logits, new_caches, _ = T.forward(
                        params, tokens, cfg_v, caches=caches,
                        positions=positions)
                # Scale-growth probe: did this draft-block write grow any
                # layer's pool-wide running max-abs?  (Non-BitStopper
                # impls carry no amax leaves: grew is constant False.)
                old_amax = _amax_leaves(caches)
                new_amax = _amax_leaves(new_caches)
                grew = jnp.zeros((), bool)
                for o, n in zip(old_amax, new_amax):
                    grew |= jnp.any(n > o)
                return logits, new_caches, grew

            self._verify = jax.jit(verify_fn)

    def _degrade(self, why: str) -> None:
        """Per-engine circuit breaker: a fused-kernel fault flips the
        engine onto the pure-JAX gather oracle for the rest of its life.
        Still **lossless** — fused and fallback decode/verify are
        bit-identical (tests/test_paged_decode.py, fused-vs-fallback trace
        tests), so degrading never changes served tokens, only per-step
        traffic.  Counter-reported as ``degradations``."""
        if not self.cfg.fused_decode:
            raise RuntimeError(
                f"kernel fault on the gather-fallback path ({why}): the "
                f"breaker has nothing simpler to fall back to")
        self.cfg = self.cfg.replace(fused_decode=False)
        self._build_jits()
        self.counters["degradations"] += 1

    def _guarded_decode(self, *args):
        """The decode forward behind the circuit breaker.  A failed jitted
        call leaves ``self.caches`` unmutated (the caller assigns only on
        return), so the post-degrade retry re-runs the *same tick* through
        the fallback against identical state — bit-identical recovery."""
        try:
            if (self.chaos is not None and self.cfg.fused_decode
                    and self.chaos.fire("kernel_fail", self.ticks)):
                raise KernelFault(
                    f"injected fused-decode fault at tick {self.ticks}")
            return self._decode(*args)
        except KernelFault as e:
            self._degrade(str(e))
            return self._decode(*args)

    def _guarded_verify(self, *args):
        try:
            if (self.chaos is not None and self.cfg.fused_decode
                    and self.chaos.fire("kernel_fail", self.ticks)):
                raise KernelFault(
                    f"injected fused-verify fault at tick {self.ticks}")
            return self._verify(*args)
        except KernelFault as e:
            self._degrade(str(e))
            return self._verify(*args)

    # ------------------------------------------------------------------
    # sanitizer poison hook
    # ------------------------------------------------------------------

    def _poison_blocks(self, bids: list[int]) -> None:
        """REPRO_SANITIZE poison mode: overwrite freed blocks' pool pages
        with loud sentinels the moment they return to the free list (and
        before any realloc can hand them to a new owner).  A read through
        a stale block table or a fill-level hole then produces wildly
        wrong values instead of silently reusing stale KV; correctly
        masked paths are unaffected because every dead-lane consumer
        multiplies by zero or selects away — finite poison stays exactly
        maskable (``0 * POISON_KV == 0``)."""
        if not bids or getattr(self, "caches", None) is None:
            return
        from repro.analysis.pool_sanitizer import (POISON_BYTE, POISON_KV,
                                                   POISON_POS)
        idx = jnp.asarray(sorted(set(bids)), jnp.int32)

        def poison_layer(c):
            if not isinstance(c, dict):
                if isinstance(c, list):
                    return [poison_layer(x) for x in c]
                return c
            if "table" not in c:
                return {k: poison_layer(v) for k, v in c.items()}
            # paged layer: stacked (scanned) layers carry a leading reps
            # axis on every pool leaf; the table's rank tells which.
            stacked = c["table"].ndim == 3

            def pset(a, val):
                return a.at[:, idx].set(val) if stacked else \
                    a.at[idx].set(val)

            new = dict(c)
            new["k"] = pset(c["k"], POISON_KV)
            new["v"] = pset(c["v"], POISON_KV)
            new["pos"] = pset(c["pos"], POISON_POS)
            if "kq" in c:
                new["kq"] = pset(c["kq"], POISON_BYTE)
            return new

        self.caches = poison_layer(self.caches)

    # ------------------------------------------------------------------
    # capacity accounting
    # ------------------------------------------------------------------

    def _blocks_for(self, req: Request) -> int:
        """Worst-case block need: the final sampled token is never written
        back, so at most prompt + max_new_tokens - 1 slots are cached."""
        tokens = len(req.prompt) + req.max_new_tokens - 1
        return max(1, -(-tokens // self._page))

    def kv_bytes_resident(self, peak: bool = True) -> int:
        """KV memory actually backed by live blocks (peak over the run by
        default) — the paged analogue of the contiguous engine's static
        ``max_slots * max_len`` reservation.

        BitStopper caches are charged for everything a live block really
        carries: the f32 K/V rows AND, when the fused decode kernel is on,
        the packed bit-plane pool (``kq``: bits x Hkv x D bits per token —
        the plane-pool overhead the fused path trades for its traffic
        win), plus the tiny static ``k_amax``/``v_amax`` scale state."""
        blocks = (self.pool.peak_live_blocks if peak
                  else self.pool.live_blocks())
        per_tok = _kv_bytes_per_token(self.cfg, self._dtype)
        extra = 0
        if self._page % 8 == 0:
            per_tok += _plane_bytes_per_token(self.cfg)
            extra = _amax_static_bytes(self.cfg)
        return blocks * self._page * per_tok + extra

    def kv_bytes_contiguous_equiv(self) -> int:
        """What a contiguous per-slot cache of the same ServeConfig would
        keep resident (window layers: ring-buffer rows), for benchmark
        comparisons."""
        return _kv_bytes_contiguous(self.cfg, self.scfg, self._dtype)

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def _validate_request(self, req: Request) -> None:
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        need = self._blocks_for(req)
        if need > self._mb:
            raise ValueError(
                f"request needs {need} KV blocks, block table holds "
                f"{self._mb} (raise max_blocks_per_req or max_len)")
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} KV blocks, pool has "
                f"{self.pool.capacity} (raise pool_blocks)")
        if req.slo not in ("besteffort", "standard", "strict"):
            raise ValueError(
                f"slo must be besteffort|standard|strict, got {req.slo!r}")
        if req.deadline_ticks is not None and req.deadline_ticks < 1:
            raise ValueError(
                f"deadline_ticks must be >= 1, got {req.deadline_ticks}")

    def _register(self, req: Request) -> None:
        """Record the request under its rid, assigning one if unset.
        Pre-assigned rids let an external admission layer
        (``serving/frontdoor``) fix each request's sampling identity at
        ARRIVAL time and then reorder actual submission freely: keys are
        ``fold_in(fold_in(seed, rid), n)``, so fairness reordering cannot
        change a single served token."""
        if req.rid < 0:
            req.rid = self._next_rid
        elif (req.rid in self.requests
              and self.requests[req.rid] is not req):
            raise ValueError(
                f"rid {req.rid} already belongs to another request")
        self._next_rid = max(self._next_rid, req.rid + 1)
        self.requests[req.rid] = req

    def submit(self, req: Request) -> Request:
        self._validate_request(req)
        self._register(req)
        req.submitted_tick = self.ticks
        self.queue.append(req)
        self.tracer.event(req.rid, "submit")
        return req

    def _deadline_of(self, req: Request) -> int | None:
        """Effective deadline in ticks from submission (request override,
        else the config default).  A request submitted at tick t is
        expired once ``self.ticks > t + deadline`` — it had ``deadline``
        full ticks of service."""
        return (req.deadline_ticks if req.deadline_ticks is not None
                else self.scfg.deadline_ticks)

    def _expired(self, req: Request) -> bool:
        ddl = self._deadline_of(req)
        return ddl is not None and self.ticks > req.submitted_tick + ddl

    def _match_prefix(self, tokens: np.ndarray,
                      keep_last: bool = True) -> list[int]:
        """Longest chain of already-cached full blocks of ``tokens`` (refs
        taken).  With ``keep_last`` at least one token is always left to
        prefill — its forward produces the logits that sample the first new
        token.  A resumed request passes ``keep_last=False``: its next
        input token is already known (``generated[-1]``), so a fully-cached
        context needs no prefill forward at all."""
        bs = self._page
        matched: list[int] = []
        for j in range((len(tokens) - (1 if keep_last else 0)) // bs):
            key = tuple(int(t) for t in tokens[:(j + 1) * bs])
            bid = self.pool.lookup(key)
            if bid is None:
                break
            matched.append(bid)
        return matched

    def _reserve_goal(self, total: int, n_ctx: int) -> int:
        """Blocks admission must secure.  Default: the worst case, so
        mid-decode allocation can never fail.  Oversubscribed: just the
        context blocks plus one decode block — enough to prefill and make
        decode progress; further blocks are claimed unreserved and may
        preempt a victim when the pool runs dry."""
        if not self.scfg.oversubscribe:
            return total
        return min(total, n_ctx + 1)

    def _admit(self) -> None:
        while self.queue and None in self.slots:
            req = self.queue[0]
            # Deadline expiry in queue: a request that already produced
            # tokens (a preemption victim awaiting resume) *finishes
            # truncated* — its emitted tokens are a prefix of the
            # undisturbed stream, never divergent; a request with nothing
            # emitted yet is shed outright (reject-with-reason).
            if self._expired(req):
                self.queue.popleft()
                req.finished_step = self._step
                if req.generated:
                    req.deadline_hit = True
                    self.counters["deadline_truncated"] += 1
                    self.counters["requests_finished"] += 1
                else:
                    req.shed_reason = "deadline"
                    self.counters["requests_shed"] += 1
                    self.counters["shed_deadline"] += 1
                continue
            # Load shedding: past the saturation watermark, besteffort
            # requests that never started are rejected instead of queued
            # into a preemption storm.  Started requests are never shed —
            # shedding is lossy only for work with zero sunk cost.
            if (self.scfg.shed_watermark is not None
                    and req.slo == "besteffort" and not req.generated
                    and self.pool.saturation() > self.scfg.shed_watermark):
                self.queue.popleft()
                req.finished_step = self._step
                req.shed_reason = "watermark"
                self.counters["requests_shed"] += 1
                self.counters["shed_watermark"] += 1
                continue
            resumed = len(req.generated) > 0
            # Resume context: everything already cached at preemption time
            # — the prompt plus all generated tokens but the last (which is
            # the next decode input, never written back yet).
            ctx = (np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(req.generated[:-1], np.int32)])
                   if resumed else np.asarray(req.prompt, np.int32))
            Lc = len(ctx)
            total = self._blocks_for(req)
            n_ctx = -(-Lc // self._page)
            goal = self._reserve_goal(total, n_ctx)
            # Cheap pre-check before building O(L^2/page) prefix keys: if
            # even a full prefix match couldn't fit, the head of line is
            # blocked — don't churn the registry every tick.
            max_match = (Lc - (0 if resumed else 1)) // self._page
            if goal - max_match > self.pool.available():
                break
            matched = self._match_prefix(ctx, keep_last=not resumed)
            need = goal - len(matched)
            if need > self.pool.available():
                # Head-of-line blocked on capacity: roll the prefix refs
                # back and wait for evictions to return blocks.
                for bid in matched:
                    self.pool.decref(bid)
                break
            self.queue.popleft()
            self.pool.reserve(need)
            slot = self.slots.index(None)
            row = np.zeros((self._mb,), np.int32)
            row[:len(matched)] = matched
            # Blocks covering the un-shared context tail are claimed now;
            # decode-tail blocks stay reserved (or, oversubscribed, unmet)
            # and materialize lazily.
            for j in range(len(matched), n_ctx):
                row[j] = self.pool.alloc(reserved=True)
            hit_len = len(matched) * self._page
            # Fill freshly claimed context blocks from the memory
            # hierarchy (host swap record, then prefix store) instead of
            # recomputing them — a no-op when no tier is configured.
            cached_len = self._rehydrate(req, row, ctx, len(matched),
                                         hit_len, resumed)
            self.table[slot] = row
            self.lengths[slot] = cached_len
            self.slots[slot] = _PagedSlot(
                req, next_prefill=cached_len,
                blocks_reserved=goal - n_ctx,
                ctx=ctx, resumed=resumed, seq=self._admit_seq)
            self._admit_seq += 1
            self.tracer.event(req.rid, "admit")
            if cached_len < Lc:
                self._prefill_fifo.append(slot)
            else:
                # Fully-cached resume (every ctx block resurrected from the
                # registry): no prefill forward needed — decode continues
                # from the already-sampled last token.
                self.last_token[slot] = int(req.generated[-1])
            if not resumed:
                req.prefill_len = Lc
                req.admitted_step = self._step
            self.counters["prefix_hit_tokens"] += hit_len

    def _prefill_tick(self) -> None:
        """Run ONE bucket-padded chunk of the oldest admitted-but-unprefilled
        request — long prompts no longer monopolize a scheduler tick.  A
        resumed (previously preempted) request prefills its *context* —
        prompt plus already-generated tokens — through the identical path:
        recompute of the unshared tail is just more chunked prefill."""
        if not self._prefill_fifo:
            return
        # Each step writes a whole new cache (the layer scan rebuilds it),
        # and a step queued on the device already holds its output: wait
        # for the previous step so at most two caches are live.  Decode and
        # verify ticks wait anyway, on the tokens they sample.
        with self.tracer.span("prefill_wait"):
            jax.block_until_ready(self.caches)
        with self.tracer.span("prefill"):
            self._prefill_chunk()

    def _prefill_chunk(self) -> None:
        slot = self._prefill_fifo[0]
        st = self.slots[slot]
        req = st.req
        L = len(st.ctx)
        s = st.next_prefill
        e = min(s + self._chunk, L)
        n = e - s
        Sp = min(self._chunk, -(-n // self.scfg.prefill_bucket)
                 * self.scfg.prefill_bucket)
        tokens = np.zeros((1, Sp), np.int32)
        tokens[0, :n] = np.asarray(st.ctx[s:e], np.int32)
        positions = np.full((1, Sp), POS_SENTINEL, np.int32)
        positions[0, :n] = np.arange(s, e, dtype=np.int32)
        caches = _attach_tables(self.caches, self.table[slot:slot + 1],
                                self.lengths[slot:slot + 1])
        last_logits, self.caches = self._prefill(
            self.params, jnp.asarray(tokens), caches, jnp.asarray(positions),
            jnp.asarray(n - 1, jnp.int32))
        self.lengths[slot] += n
        st.next_prefill = e
        self.counters["prefill_tokens"] += n
        self.counters["prefill_chunks"] += 1

        # Publish newly completed full context blocks for prefix sharing
        # (re-registration of already-shared blocks is a no-op).  Keys are
        # the full token chain, so generated-region blocks of a resumed
        # request share exactly like prompt blocks — a second preemption
        # resumes them for free.
        bs = self._page
        for j in range(s // bs, e // bs):
            key = tuple(int(t) for t in st.ctx[:(j + 1) * bs])
            self.pool.register(key, int(self.table[slot, j]))

        if e == L:
            self._prefill_fifo.popleft()
            if st.resumed:
                # The context's successor token was already sampled before
                # the preemption — replay it as the next decode input
                # instead of re-sampling (the logits are not consumed, so
                # the resumed trace stays bit-identical).
                self.last_token[slot] = int(req.generated[-1])
            else:
                tok = int(self._sample_rows(last_logits, [req.rid], [0])[0])
                with self.tracer.span("emit"):
                    req.generated.append(tok)
                    self.tracer.event(req.rid, "first_token")
                    self.last_token[slot] = tok
                    self._maybe_evict(slot, tok)

    def _maybe_evict(self, slot: int, tok: int) -> None:
        st = self.slots[slot]
        if st is None:
            return
        req = st.req
        done = len(req.generated) >= req.max_new_tokens
        if self.scfg.eos_id is not None and tok == self.scfg.eos_id:
            done = True
        # Mid-decode deadline: finish truncated after this tick's token.
        # Truncation only ever *shortens* the stream — the emitted tokens
        # are exactly the undisturbed stream's prefix.
        if not done and self._expired(req):
            req.deadline_hit = True
            self.counters["deadline_truncated"] += 1
            done = True
        if not done:
            return
        req.finished_step = self._step
        self.counters["requests_finished"] += 1
        for j in range(self._mb):
            bid = int(self.table[slot, j])
            if bid:
                self.pool.decref(bid)
        self.pool.cancel_reservation(st.blocks_reserved)
        self.table[slot] = 0
        self.lengths[slot] = 0
        self.last_token[slot] = 0
        self.slots[slot] = None

    def step(self) -> bool:
        """One scheduler tick: admit, one prefill chunk, one decode step
        (plain or speculative) over every prefilled slot.  Returns False
        when there is no work."""
        with self.tracer.span("step"):
            return self._tick()

    def _tick(self) -> bool:
        # The tick counter is the engine's only clock: fault injection,
        # deadlines, and snapshot cadence all key on it (wall clock is
        # lint-banned from serving/ — repo-tick-wallclock).
        self.ticks += 1
        with self.tracer.span("admit"):
            self._admit()
        self._prefill_tick()
        active = [i for i, st in enumerate(self.slots)
                  if st is not None and st.prefilled()]
        if not active:
            return bool(self.queue
                        or any(st is not None for st in self.slots))
        self._step += 1
        # Materialize the block behind each decoding row's next write
        # position up front: under oversubscription this claim may preempt
        # a victim — possibly one of this tick's own rows, which then drops
        # out of `active` (it is requeued, not lost).  Mandatory claims
        # happen here, before any speculative drafting, so a spec tick
        # never preempts for optional draft blocks.
        with self.tracer.span("decode"):
            for i in active:
                st = self.slots[i]
                if st is None or not st.prefilled():
                    continue                  # preempted by an earlier claim
                j = int(self.lengths[i]) // self._page
                if self.table[i, j] == 0:
                    self._claim_block(i, j)
        active = [i for i in active if self.slots[i] is not None
                  and self.slots[i].prefilled()]
        if not active:
            return True
        if self._drafter is not None:
            self._spec_decode_tick(active)
        else:
            self._plain_decode_tick(active)
        return True

    # ------------------------------------------------------------------
    # JetStream-style engine API: prefill -> insert -> generate_step
    # (serving/frontdoor builds the async door and the prefill/decode
    # disaggregation on exactly this surface; docs/serving.md)
    # ------------------------------------------------------------------

    def prefill(self, req: Request) -> Prefix:
        """Engine API step 1: prefill a fresh request's prompt to
        completion and hand back a :class:`Prefix` — the prompt's paged
        blocks (ownership transferred, refs held by the Prefix) plus the
        first sampled token.  Runs through the ordinary chunked-prefill
        path (prefix-registry CoW hits, block publication, the standard
        first-token sample), so a later ``insert()`` + decode is
        bit-identical to serving the request through ``submit()``.

        The slot used for prefilling frees on return; only the blocks
        stay live.  Raises :class:`InsufficientBlocks` (retryable) when
        the pool cannot cover the prompt right now."""
        if req.generated:
            raise ValueError(
                "prefill() takes a fresh request; preemption resume runs "
                "through the scheduler (submit()/step())")
        self._validate_request(req)
        if None not in self.slots:
            raise RuntimeError("prefill() needs a free slot")
        self._register(req)
        req.submitted_tick = self.ticks
        ctx = np.asarray(req.prompt, np.int32)
        L = len(ctx)
        n_ctx = -(-L // self._page)
        matched = self._match_prefix(ctx, keep_last=True)
        need = n_ctx - len(matched)
        if need > self.pool.available():
            for bid in matched:
                self.pool.decref(bid)
            raise InsufficientBlocks(
                f"prompt needs {need} blocks beyond its prefix hits, pool "
                f"has {self.pool.available()}")
        self.pool.reserve(need)
        slot = self.slots.index(None)
        row = np.zeros((self._mb,), np.int32)
        row[:len(matched)] = matched
        for j in range(len(matched), n_ctx):
            row[j] = self.pool.alloc(reserved=True)
        cached = len(matched) * self._page
        self.table[slot] = row
        self.lengths[slot] = cached
        # blocks_reserved=0: prefill writes only context blocks, all
        # allocated above — the decode tail is reserved at insert() time
        # against the DECODE engine's pool.
        self.slots[slot] = _PagedSlot(req, next_prefill=cached,
                                      blocks_reserved=0, ctx=ctx,
                                      seq=self._admit_seq)
        self._admit_seq += 1
        req.prefill_len = L
        req.admitted_step = self._step
        self.counters["prefix_hit_tokens"] += cached
        # keep_last guarantees >= 1 token left to prefill, so the loop
        # always runs and the first token samples through _prefill_tick.
        self._prefill_fifo.appendleft(slot)
        while (self.slots[slot] is not None
               and not self.slots[slot].prefilled()):
            self.ticks += 1
            self._prefill_tick()
        if self.slots[slot] is None:
            # Finished during prefill (max_new_tokens == 1, eos, or a
            # deadline): _maybe_evict released every block already and the
            # tokens are in req.generated — nothing to hand off.
            last = int(req.generated[-1]) if req.generated else 0
            return Prefix(req=req, chain=ctx, length=0, last_token=last,
                          blocks=[], pool=self.pool, finished=True)
        # Detach: block ownership moves from the slot to the Prefix (the
        # refs taken above are NOT dropped); the slot frees.
        bids = [int(self.table[slot, j]) for j in range(n_ctx)]
        last = int(self.last_token[slot])
        self.table[slot] = 0
        self.lengths[slot] = 0
        self.last_token[slot] = 0
        self.slots[slot] = None
        self.counters["prefixes_prefilled"] += 1
        return Prefix(req=req, chain=ctx, length=L, last_token=last,
                      blocks=bids, pool=self.pool)

    def extract(self, prefix: Prefix) -> Prefix:
        """Detach a prefix from this engine: serialize its blocks' K/V/pos
        rows (plus the pool-wide quant scales) through the pool to host
        arrays, then drop the block refs.  The result is pool-layout
        independent — a decode engine with its own pool and block
        numbering can ``insert()`` it: the disaggregation handoff.
        Registered source blocks park in the LRU on decref, so the
        prefill engine's prefix cache stays warm for repeat prompts."""
        if prefix.finished or prefix.payload is not None:
            return prefix
        if prefix.pool is not self.pool:
            raise ValueError(
                "extract() must run on the engine owning the prefix")
        layers = extract_block_rows(self.caches, prefix.blocks)
        amax = [np.asarray(a, np.float32) for a in _amax_leaves(self.caches)]
        for bid in prefix.blocks:
            self.pool.decref(bid)
        self.counters["prefix_transfers"] += 1
        return dataclasses.replace(prefix, blocks=[], pool=None,
                                   payload={"layers": layers, "amax": amax})

    def release(self, prefix: Prefix) -> None:
        """Drop an attached prefix without inserting it (client went away
        between prefill and insert).  Detached/finished prefixes hold no
        pool state — nothing to do."""
        if prefix.pool is not self.pool or not prefix.blocks:
            return
        for bid in prefix.blocks:
            self.pool.decref(bid)
        prefix.blocks = []
        prefix.pool = None

    def insert(self, prefix: Prefix, slot: int) -> None:
        """Engine API step 2: mount a prefilled context into a free slot
        and arm it for decode.  Attached (same-pool) prefixes splice by
        block handle — no KV moves; detached ones CoW-match against this
        pool's own registry first and scatter only unmatched blocks from
        the payload, merging the source's quant scales (elementwise max —
        amax is monotone, so the merged grid is the union trajectory) and
        rebuilding the packed plane pools so every resident page means
        the same integers under it.

        The slot state is exactly the post-preemption resume contract
        (``resumed=True``, next decode input = ``prefix.last_token``), so
        decode, speculation, oversubscription and deadlines behave as if
        the request had always lived here."""
        if not 0 <= slot < len(self.slots):
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {len(self.slots)})")
        if self.slots[slot] is not None:
            raise RuntimeError(
                f"insert into occupied slot {slot} (rid "
                f"{self.slots[slot].req.rid} is live there)")
        if prefix.finished:
            raise ValueError(
                "prefix finished during prefill — nothing to insert")
        req = prefix.req
        if req.rid < 0:
            raise ValueError("prefix carries an unregistered request")
        chain = np.asarray(prefix.chain, np.int32)
        n_ctx = -(-prefix.length // self._page)
        total = self._blocks_for(req)
        goal = self._reserve_goal(total, n_ctx)
        if prefix.pool is self.pool:
            # Attached handoff: a pure block-table splice — the refs taken
            # at prefill() transfer to this slot.
            need = goal - n_ctx
            if need > self.pool.available():
                raise InsufficientBlocks(
                    f"decode tail needs {need} reserved blocks, pool has "
                    f"{self.pool.available()}")
            self.pool.reserve(need)
            row_bids = [int(b) for b in prefix.blocks]
            prefix.blocks = []
            prefix.pool = None
            cached_hit = 0
        else:
            if prefix.payload is None:
                raise ValueError(
                    "cross-engine insert needs a detached prefix: call "
                    "extract() on the source engine first")
            matched = self._match_prefix(chain, keep_last=False)
            need = goal - len(matched)
            if need > self.pool.available():
                for bid in matched:
                    self.pool.decref(bid)
                raise InsufficientBlocks(
                    f"prefix needs {need} blocks beyond its local CoW "
                    f"hits, pool has {self.pool.available()}")
            self.pool.reserve(need)
            sel = list(range(len(matched), n_ctx))
            fresh = [self.pool.alloc(reserved=True) for _ in sel]
            row_bids = [int(b) for b in matched] + fresh
            if fresh:
                self.caches = splice_block_rows(
                    self.caches, fresh, prefix.payload["layers"], sel)
            self._merge_amax(prefix.payload["amax"])
            # Publish transferred FULL blocks for CoW under their chain
            # keys (the partial tail block stays exclusively owned and
            # unregistered — repo invariant).
            for j in range(len(matched), prefix.length // self._page):
                key = tuple(int(t) for t in chain[:(j + 1) * self._page])
                self.pool.register(key, row_bids[j])
            if self._rules is not None:
                from repro.sharding.rules import cache_shardings
                self.caches = jax.device_put(
                    self.caches, cache_shardings(self._rules, self.caches))
            cached_hit = len(matched) * self._page
        self._register(req)
        # Deadlines re-anchor at insert: in disaggregated mode the
        # prefill and decode engines' tick clocks are unrelated, so
        # ``deadline_ticks`` bounds decode-side service from here.
        req.submitted_tick = self.ticks
        row = np.zeros((self._mb,), np.int32)
        row[:n_ctx] = row_bids
        self.table[slot] = row
        self.lengths[slot] = prefix.length
        self.last_token[slot] = int(prefix.last_token)
        self.slots[slot] = _PagedSlot(req, next_prefill=prefix.length,
                                      blocks_reserved=goal - n_ctx,
                                      ctx=chain, resumed=True,
                                      seq=self._admit_seq)
        self._admit_seq += 1
        self.counters["prefix_hit_tokens"] += cached_hit
        self.counters["prefixes_inserted"] += 1

    def _merge_amax(self, incoming: list) -> None:
        """Fold another engine's quant-scale leaves into this one's
        (elementwise max) and rebuild the packed plane pools.  Runs on
        every detached insert even when nothing grew: the freshly
        spliced pages carry no plane rows until the requant writes
        them."""
        cur = _amax_leaves(self.caches)
        if len(cur) != len(incoming):
            raise ValueError(
                f"prefix payload carries {len(incoming)} quant-scale "
                f"leaves, cache has {len(cur)}")
        if not cur:
            return
        merged = []
        for c, p in zip(cur, incoming):
            cn = np.asarray(c, np.float32)
            merged.append(np.maximum(cn,
                                     np.asarray(p,
                                                np.float32).reshape(cn.shape)))
        self.caches = _set_amax_leaves(self.caches, merged)
        self.caches = requant_plane_pools(self.caches)

    def generate_step(self) -> list[dict]:
        """Engine API step 3: one scheduler tick, returning the tokens it
        committed as per-request events ``{"rid", "slot", "tokens",
        "finished"}`` (sorted by rid; ``slot`` is -1 once the request has
        left its slot).  A preemption emits no event — the requeued
        request's tokens stand; an expiry/shed emits a terminal event
        with no tokens.  Token content is exactly ``step()``'s: this is a
        diff of the request registry, not a different decode path."""
        before = {rid: (len(r.generated),
                        r.finished_step >= 0 or r.shed_reason is not None)
                  for rid, r in self.requests.items()}
        self.step()
        slot_of = {st.req.rid: i for i, st in enumerate(self.slots)
                   if st is not None}
        events = []
        for rid in sorted(before):
            n0, was_done = before[rid]
            req = self.requests[rid]
            done = req.finished_step >= 0 or req.shed_reason is not None
            toks = [int(t) for t in req.generated[n0:]]
            if toks or (done and not was_done):
                events.append({"rid": rid, "slot": slot_of.get(rid, -1),
                               "tokens": toks, "finished": done})
        return events

    def free_slots(self) -> list[int]:
        """Indices of currently unoccupied slots (insert targets)."""
        return [i for i, st in enumerate(self.slots) if st is None]

    # ------------------------------------------------------------------
    # crash-consistent snapshot / restore (docs/robustness.md)
    # ------------------------------------------------------------------

    def snapshot(self) -> dict:
        """Crash-consistent engine state, fully JSON-serializable.

        Persists every piece of host-side truth: the request registry
        (prompts, generated tokens, QoS/accounting fields), queue and slot
        occupancy, scheduler counters, tick counter, sampling seed, the
        pool's allocator state (free list, refcounts, registry,
        reservations — for fidelity and offline inspection), and the
        pool-wide quant-scale leaves (``k_amax``/``v_amax``).

        Deliberately NOT persisted: device KV.  Restore re-materializes it
        through the PR-5 lossless-resume path — in-flight requests requeue
        with their generated tokens and recompute their context via
        chunked prefill (re-sharing prefix blocks across each other as
        they go), which is bit-identical because K/V written for (token,
        position) is schedule-invariant and the restored quant scales
        make the recompute's rescale trajectory match the undisturbed
        run's exactly."""
        active = sorted(
            (st.seq, st.req.rid) for st in self.slots if st is not None)
        reqs = []
        for rid in sorted(self.requests):
            r = self.requests[rid]
            reqs.append({
                "rid": rid,
                "prompt": [int(t) for t in r.prompt],
                "max_new_tokens": int(r.max_new_tokens),
                "generated": [int(t) for t in r.generated],
                "deadline_ticks": r.deadline_ticks,
                "slo": r.slo,
                "prefill_len": int(r.prefill_len),
                "admitted_step": int(r.admitted_step),
                "finished_step": int(r.finished_step),
                "preemptions": int(r.preemptions),
                "submitted_tick": int(r.submitted_tick),
                "shed_reason": r.shed_reason,
                "deadline_hit": bool(r.deadline_hit),
            })
        return {
            "version": 1,
            "ticks": int(self.ticks),
            "step": int(self._step),
            "seed": self._seed,
            "next_rid": int(self._next_rid),
            "admit_seq": int(self._admit_seq),
            "counters": {k: int(v) for k, v in self.counters.items()},
            "requests": reqs,
            "queue": [r.rid for r in self.queue],
            "active": [rid for _, rid in active],
            "amax": [np.asarray(a, np.float32).tolist()
                     for a in _amax_leaves(self.caches)],
            "pool": self.pool.snapshot(),
        }

    def restore(self, state: dict) -> None:
        """Rebuild scheduler truth from a snapshot into THIS engine, which
        must be freshly constructed (a host crash destroyed the old
        process — device KV included — so restore starts from a clean pool
        and empty caches, not from snapshot-era block ids).

        In-flight requests requeue exactly like preemption victims
        (admission order first, then the snapshot's queue order): the
        ordinary ``_admit`` resume path recomputes each context and
        replays the last sampled token, so the continuation's tokens are
        bit-identical to an undisturbed run.  The quant-scale leaves are
        written back *before* any recompute — see :func:`_set_amax_leaves`
        for why that pins the BitStopper scale trajectory."""
        if self.requests or self.ticks or self.pool.live_blocks():
            raise RuntimeError(
                "restore() needs a freshly constructed engine (the crash "
                "destroyed the old one; device KV is recomputed, not "
                "re-mapped)")
        if state.get("version") != 1:
            raise ValueError(f"unknown snapshot version "
                             f"{state.get('version')!r}")
        self.ticks = int(state["ticks"])
        self._step = int(state["step"])
        self._next_rid = int(state["next_rid"])
        self._admit_seq = int(state["admit_seq"])
        if state["seed"] is not None:
            self.begin(int(state["seed"]))
        self.counters.update(state["counters"])
        # Pool bookkeeping counters carry across the crash so benchmark
        # accounting stays cumulative; the allocator itself restarts empty
        # (every restored block is re-claimed through the resume path).
        self.pool.peak_live_blocks = int(state["pool"]["peak_live_blocks"])
        self.pool.alloc_count = int(state["pool"]["alloc_count"])
        self.requests = {}
        for d in state["requests"]:
            self.requests[d["rid"]] = Request(
                prompt=np.asarray(d["prompt"], np.int32),
                max_new_tokens=d["max_new_tokens"],
                generated=list(d["generated"]),
                rid=d["rid"],
                deadline_ticks=d["deadline_ticks"],
                slo=d["slo"],
                prefill_len=d["prefill_len"],
                admitted_step=d["admitted_step"],
                finished_step=d["finished_step"],
                preemptions=d["preemptions"],
                submitted_tick=d["submitted_tick"],
                shed_reason=d["shed_reason"],
                deadline_hit=d["deadline_hit"],
            )
        self.caches = _set_amax_leaves(self.caches, state["amax"])
        if self._rules is not None:
            # Re-commit the restored leaves to their mesh placement: the
            # scale injection above rebuilt host-side arrays.
            from repro.sharding.rules import cache_shardings
            self.caches = jax.device_put(
                self.caches, cache_shardings(self._rules, self.caches))
        # Crash-time slot occupants re-admit first (they were admitted
        # before anything still queued), in admission order; then the
        # queue in its snapshot order.  ``_admit`` distinguishes fresh
        # vs resumed requests by ``len(generated)`` as usual.
        for rid in list(state["active"]) + list(state["queue"]):
            self.queue.append(self.requests[rid])

    # ------------------------------------------------------------------
    # KV memory hierarchy: swap-to-host preemption + persistent prefix
    # store (docs/serving.md "Memory hierarchy")
    # ------------------------------------------------------------------

    def _swap_out(self, slot: int, exclusive: list[int], L: int,
                  req: Request) -> None:
        """Device→host copy of a preemption victim's exclusively-owned
        blocks into the swap pool, keyed by rid.  The record carries the
        f32 K/V/pos rows, the packed ``kq`` plane rows, and the
        swap-time quant-scale leaves — enough for :meth:`_swap_in` to
        re-materialize by splice with zero recompute.  Any failure
        (injected swap_fail, a non-contiguous exclusive run, budget
        refusal) just skips the record: the recompute-resume path is
        always the correct fallback."""
        if (self.chaos is not None
                and self.chaos.fire("swap_fail", self.ticks)):
            # The device→host copy died mid-flight: the partial record
            # is discarded and the victim resumes by recompute.
            self.counters["swap_fallbacks"] += 1
            return
        excl = set(exclusive)
        n_used = -(-L // self._page)
        pairs = [(j, int(self.table[slot, j])) for j in range(n_used)
                 if int(self.table[slot, j]) in excl]
        if not pairs:
            return        # every token-bearing block is shared: resume
                          # re-maps them from the registry for free
        js = [j for j, _ in pairs]
        if js != list(range(js[0], n_used)):
            # Shared blocks interleaved past the first exclusive one —
            # the record could not splice to a contiguous tail.
            self.counters["swap_fallbacks"] += 1
            return
        bids = [b for _, b in pairs]
        layers = extract_block_rows(self.caches, bids, planes=True)
        amax = [np.asarray(a, np.float32) for a in _amax_leaves(self.caches)]
        rec = {"js": js, "length": int(L), "layers": layers, "amax": amax}
        nbytes = (sum(int(a.nbytes) for lay in layers for a in lay.values())
                  + sum(int(a.nbytes) for a in amax))
        if self._swap.put(req.rid, rec, nbytes):
            self.counters["swap_outs"] += 1
        else:
            self.counters["swap_fallbacks"] += 1

    def _rehydrate(self, req: Request, row: np.ndarray, ctx: np.ndarray,
                   m: int, cached_len: int, resumed: bool) -> int:
        """Admission-time hierarchy lookup: after the context blocks are
        claimed, try to fill them from a host swap record (exact resume),
        else from the host/disk prefix store.  Returns the new cached
        length (``cached_len`` unchanged when nothing applies)."""
        new_len = None
        if self._swap is not None:
            new_len = self._swap_in(req, row, ctx, m, resumed)
        if new_len is None and (self._prefix_host is not None
                                or self.scfg.prefix_store_dir is not None):
            new_len = self._store_inject(req, row, ctx, m, resumed)
        if new_len is None:
            return cached_len
        if self._rules is not None:
            # Re-commit: the host-side splice rebuilt pool leaves.
            from repro.sharding.rules import cache_shardings
            self.caches = jax.device_put(
                self.caches, cache_shardings(self._rules, self.caches))
        return new_len

    def _swap_in(self, req: Request, row: np.ndarray, ctx: np.ndarray,
                 m: int, resumed: bool) -> int | None:
        """Re-materialize a swapped-out victim by scattering its host
        record into the freshly claimed blocks.

        Bit-identity argument: every swapped value was previously written
        by THIS engine, so the current (monotone) quant scales already
        cover it — the recompute reference would trigger no scale growth,
        and the swap-in must not apply the scale rule at all.  For the
        packed planes: if no scale grew since swap-out, the stored ``kq``
        rows splice verbatim (they ARE what incremental maintenance
        holds); if a scale did grow, the reference's growth event
        whole-pool-requanted, so repacking just the spliced blocks under
        the current scales reproduces its bytes exactly."""
        rec = self._swap.take(req.rid)
        if rec is None:
            return None
        L, js = rec["length"], rec["js"]
        if not resumed or js[0] != m or L > len(ctx):
            # The registry shifted under the record (prefix blocks it
            # relied on were evicted), or the record predates a state
            # this admission no longer matches: recompute instead.
            self.counters["swap_fallbacks"] += 1
            return None
        bids = [int(row[j]) for j in range(m, m + len(js))]
        layers = rec["layers"]
        cur = _amax_leaves(self.caches)
        same_scales = (len(cur) == len(rec["amax"]) and all(
            np.array_equal(np.asarray(a, np.float32), b)
            for a, b in zip(cur, rec["amax"])))
        if same_scales:
            self.caches = splice_block_rows(self.caches, bids, layers)
        else:
            stripped = [{k: v for k, v in lay.items() if k != "kq"}
                        for lay in layers]
            self.caches = splice_block_rows(self.caches, bids, stripped)
            self.caches = repack_block_planes(self.caches, bids)
        # Registration parity with the recompute reference: full blocks
        # publish under their chain keys exactly as _prefill_tick would
        # have while recomputing [m*page, L).
        bs = self._page
        for j in range(m, L // bs):
            key = tuple(int(t) for t in ctx[:(j + 1) * bs])
            self.pool.register(key, int(row[j]))
        self.counters["swap_ins"] += 1
        self.counters["swap_in_tokens"] += L - m * bs
        return L

    def _store_inject(self, req: Request, row: np.ndarray, ctx: np.ndarray,
                      m: int, resumed: bool) -> int | None:
        """Warm a request's context from the prefix store: walk the chain
        of full context blocks past the device-registry match, fetching
        host-tier records then disk records, splice the covered rows and
        replay the quant-scale rule host-side with chunk-group boundaries
        exactly matching the chunked-prefill recompute reference
        (``docs/serving.md`` has the losslessness argument).  Injection
        stops at the largest chunk boundary fully covered by stored
        blocks; a fresh request always leaves >= 1 token to prefill (its
        forward samples the first new token)."""
        bs = self._page
        Lc = len(ctx)
        tier, sdir = self._prefix_host, self.scfg.prefix_store_dir
        recs = []
        j = m
        while (j + 1) * bs <= Lc:
            key = tuple(int(t) for t in ctx[:(j + 1) * bs])
            rec = None
            if tier is not None:
                got = tier.get(key)
                if got is not None:
                    rec = got["layers"]
            if rec is None and sdir is not None:
                from repro.checkpoint.store import load_prefix_record
                rec = load_prefix_record(sdir, key)
            if rec is None:
                break
            recs.append(rec)
            j += 1
        if not recs:
            return None
        base = m * bs
        cov = (m + len(recs)) * bs
        # Largest admissible chunk-group boundary e_k = min(base +
        # k*chunk, Lc) covered by the stored blocks; resumed requests may
        # reach Lc (zero prefill chunks), fresh ones must stop short.
        if resumed and cov >= Lc:
            inject_end = Lc
        else:
            hi = min(cov, Lc - 1)
            inject_end = base + ((hi - base) // self._chunk) * self._chunk
        if inject_end <= base:
            return None
        jend = -(-inject_end // bs)
        recs = recs[:jend - m]
        # Merge the per-block records into one extract_block_rows-shaped
        # layer list (rows axis: 1 for stacked layers, else 0; k/v rows
        # are [.., bs, Hkv*D], the pos plane [.., bs]).
        merged = []
        for li in range(len(recs[0])):
            merged.append({
                f: np.concatenate(
                    [np.asarray(r[li][f]) for r in recs],
                    axis=np.asarray(recs[0][li][f]).ndim
                    - (2 if f == "pos" else 3))
                for f in ("k", "v", "pos")})
        bids = [int(row[j]) for j in range(m, jend)]
        self.caches = splice_block_rows(self.caches, bids, merged)
        # Replay the scale rule per chunk group — the stored values may
        # be new to THIS engine (cold start), and growth is trajectory-
        # dependent, so the groups mirror the recompute chunks exactly.
        groups = []
        s = base
        while s < inject_end:
            e = min(s + self._chunk, inject_end)
            wins = []
            for jj in range(s // bs, -(-e // bs)):
                wins.append((jj - m, max(s, jj * bs) - jj * bs,
                             min(e, (jj + 1) * bs) - jj * bs))
            groups.append(wins)
            s = e
        self.caches, k_grew = apply_inject_amax_rule(self.caches, merged,
                                                     groups)
        if k_grew:
            # The reference's last growth event whole-pool-requants.
            self.caches = requant_plane_pools(self.caches)
        else:
            self.caches = repack_block_planes(self.caches, bids)
        for jj in range(m, inject_end // bs):
            key = tuple(int(t) for t in ctx[:(jj + 1) * bs])
            self.pool.register(key, int(row[jj]))
        self.counters["prefix_store_hits"] += len(recs)
        self.counters["prefix_store_tokens"] += inject_end - base
        return inject_end

    def _on_prefix_evict(self, key: tuple, bid: int) -> None:
        """KVBlockPool evict hook: a parked registered block is being
        stolen for reuse — copy its rows down the hierarchy (host tier,
        cascading to disk) before the new owner overwrites them."""
        if getattr(self, "caches", None) is None:
            return
        layers = extract_block_rows(self.caches, [bid])
        rec = {"chain": key, "layers": layers}
        nbytes = sum(int(a.nbytes) for lay in layers for a in lay.values())
        self.counters["prefix_spills"] += 1
        if self._prefix_host is not None:
            self._prefix_host.put(key, rec, nbytes)
        else:
            self._spill_prefix_record(key, rec, nbytes)

    def _spill_prefix_record(self, key, rec, nbytes) -> None:
        """Bottom of the cascade: persist a prefix record to the disk
        store (atomic stage-then-promote; an injected
        ``checkpoint_interrupt`` drops the record, leaving a GC-able
        staging orphan and the store's previous contents intact)."""
        sdir = self.scfg.prefix_store_dir
        if sdir is None:
            return
        from repro.checkpoint.store import save_prefix_record
        try:
            save_prefix_record(sdir, list(key), rec["layers"],
                               interrupt=self._store_interrupt)
        except CheckpointInterrupted:
            self.counters["prefix_store_interrupts"] += 1

    def _store_interrupt(self) -> None:
        if (self.chaos is not None
                and self.chaos.fire("checkpoint_interrupt", self.ticks)):
            raise CheckpointInterrupted(
                f"prefix-store write killed at tick {self.ticks}")

    def flush_prefixes(self) -> int:
        """Persist every registered prefix block (and every host-tier
        record) to the prefix store — the graceful-shutdown half of
        cross-restart warm starts.  First-writer-wins: chains already in
        the store are no-ops.  Returns the number of records written or
        confirmed present."""
        sdir = self.scfg.prefix_store_dir
        if sdir is None:
            raise RuntimeError(
                "flush_prefixes() needs ServeConfig.prefix_store_dir")
        from repro.checkpoint.store import save_prefix_record
        n = 0
        for key, bid in self.pool.registered_items():
            layers = extract_block_rows(self.caches, [bid])
            try:
                save_prefix_record(sdir, list(key), layers,
                                   interrupt=self._store_interrupt)
                n += 1
            except CheckpointInterrupted:
                self.counters["prefix_store_interrupts"] += 1
        if self._prefix_host is not None:
            for key, rec in self._prefix_host.items():
                try:
                    save_prefix_record(sdir, list(key), rec["layers"],
                                       interrupt=self._store_interrupt)
                    n += 1
                except CheckpointInterrupted:
                    self.counters["prefix_store_interrupts"] += 1
        return n

    def memory_report(self) -> dict:
        """Bytes resident at every tier of the KV memory hierarchy.
        :meth:`kv_bytes_resident` stays device-only by contract; host and
        disk tiers report separately so no token's bytes are ever
        double-counted across tiers (the sanitizer cross-checks each
        host tier's internal ledger)."""
        rep = {
            "device_bytes": int(self.kv_bytes_resident(peak=False)),
            "device_bytes_peak": int(self.kv_bytes_resident(peak=True)),
            "host_swap_bytes": (int(self._swap.bytes_used)
                                if self._swap is not None else 0),
            "host_swap_bytes_peak": (int(self._swap.peak_bytes)
                                     if self._swap is not None else 0),
            "host_prefix_bytes": (int(self._prefix_host.bytes_used)
                                  if self._prefix_host is not None else 0),
            "host_prefix_bytes_peak": (int(self._prefix_host.peak_bytes)
                                       if self._prefix_host is not None
                                       else 0),
            "disk_prefix_bytes": 0,
        }
        if self.scfg.prefix_store_dir is not None:
            from repro.checkpoint.store import prefix_store_bytes
            rep["disk_prefix_bytes"] = int(
                prefix_store_bytes(self.scfg.prefix_store_dir))
        return rep

    # ------------------------------------------------------------------
    # oversubscription: victim preemption + lossless requeue
    # ------------------------------------------------------------------

    def _freeable_blocks(self, slot: int) -> int:
        """Pool capacity preempting this slot would release: exclusively-
        held table entries (refcount-1 blocks free outright or park in the
        evictable LRU) plus its un-materialized reservation units.  Entries
        another table also maps (refcount > 1) only drop a reference."""
        st = self.slots[slot]
        n = st.blocks_reserved
        for j in range(self._mb):
            bid = int(self.table[slot, j])
            if bid and self.pool.refcount(bid) == 1:
                n += 1
        return n

    _SLO_RANK = {"besteffort": 0, "standard": 1, "strict": 2}

    def _select_victim(self, needy: int) -> int | None:
        """Pick the slot to preempt so ``needy`` can claim a block.

        SLO class and deadline slack dominate: besteffort slots are
        victimized before standard before strict, and within a class the
        request with the MOST ticks of deadline slack is victimized first
        (it can best afford the resume recompute).  With neither SLO
        classes nor deadlines in play those keys are constant and the
        policy reduces to its pre-QoS behavior: ``fewest_tokens``
        victimizes the request with the least generated output (cheapest
        recompute, closest to vLLM's default); ``lifo`` victimizes the
        newest admission (oldest requests never starve).  Slots whose
        preemption would free nothing are never chosen."""
        cands = [i for i, st in enumerate(self.slots)
                 if st is not None and i != needy
                 and self._freeable_blocks(i) > 0]
        if not cands:
            return None

        def vkey(i):
            st = self.slots[i]
            req = st.req
            ddl = self._deadline_of(req)
            slack = (float("inf") if ddl is None
                     else req.submitted_tick + ddl - self.ticks)
            if self.scfg.preempt_policy == "lifo":
                pol = (-st.seq,)
            else:
                pol = (len(req.generated), -st.seq)
            return (self._SLO_RANK.get(req.slo, 1), -slack) + pol

        return min(cands, key=vkey)

    def _preempt(self, slot: int) -> None:
        """Evict a running request to reclaim its blocks, requeueing it for
        a lossless resume.  Exclusively-owned blocks free outright
        (``KVBlockPool.preempt``); shared/registered prefix blocks drop one
        reference — they stay live under other tables or park resurrectable
        in the LRU, so the resume re-maps them for free and recomputes only
        the unshared tail via chunked prefill.  The request's ``generated``
        tokens are kept: sampling keys are a pure function of (seed, rid,
        token index), so the resumed continuation is bit-identical to an
        uncontended run."""
        st = self.slots[slot]
        req = st.req
        L = int(self.lengths[slot])
        exclusive, shared, dropped = [], [], 0
        for j in range(self._mb):
            bid = int(self.table[slot, j])
            if not bid:
                continue
            if (self.pool.refcount(bid) == 1
                    and not self.pool.is_registered(bid)):
                exclusive.append(bid)
                # Only tokens in forcibly-freed blocks are dropped from
                # cache; tokens in shared/registered blocks stay resident
                # (or parked) and re-map for free on resume.
                dropped += max(0, min(L - j * self._page, self._page))
            else:
                shared.append(bid)
        # Swap-to-host: copy the victim's exclusive blocks to a host
        # record BEFORE they free (and before the sanitizer poisons
        # them), so resume can splice instead of recompute.  Victims
        # still mid-first-prefill (nothing generated) resume as fresh
        # admissions and need no record.
        if self._swap is not None and req.generated and exclusive:
            self._swap_out(slot, exclusive, L, req)
        self.pool.preempt(exclusive)
        for bid in shared:
            self.pool.decref(bid)
        self.pool.cancel_reservation(st.blocks_reserved)
        self.table[slot] = 0
        self.counters["preempt_dropped_tokens"] += dropped
        self.lengths[slot] = 0
        self.last_token[slot] = 0
        self.slots[slot] = None
        if slot in self._prefill_fifo:
            self._prefill_fifo.remove(slot)
        req.preemptions += 1
        self.counters["preemptions"] += 1
        self.counters["preempt_freed_blocks"] += len(exclusive)
        # Preempted requests resume ahead of never-admitted arrivals (they
        # were admitted first), ordered by submission among themselves —
        # a later-preempted request must not jump an earlier one already
        # waiting at the head.
        pos = 0
        for r in self.queue:
            if r.preemptions > 0 and r.rid < req.rid:
                pos += 1
            else:
                break
        self.queue.insert(pos, req)

    def _claim_block(self, slot: int, j: int, optional: bool = False) -> int:
        """Materialize the physical block behind table entry j — out of the
        slot's admission reservation when one remains, else (oversubscribed
        admission only) from the pool's spare capacity, preempting victims
        until a block is claimable.

        ``optional`` marks a speculative draft-block claim: those never
        preempt (the caller pre-checks spare capacity and truncates the
        draft when there is none), so the injected pool-dry consult is
        skipped here — the spec tick consults it itself and answers with a
        draft truncation, exactly what real dryness does at that point."""
        st = self.slots[slot]
        if st.blocks_reserved > 0:
            bid = self.pool.alloc(reserved=True)
            st.blocks_reserved -= 1
        else:
            if not self.scfg.oversubscribe:
                raise RuntimeError(
                    "paged scheduler invariant violated: slot "
                    f"{slot} needs a decode block but has no reservation")
            # Injected pool-dry (serving/chaos.py): force one preemption
            # cycle even though the pool is not actually exhausted —
            # exercises the lossless preempt/resume machinery at scripted
            # points.  If no victim exists the forced dryness is dropped
            # rather than wedging a healthy pool.
            if (not optional and self.chaos is not None
                    and self.chaos.fire("pool_dry", self.ticks)):
                victim = self._select_victim(needy=slot)
                if victim is not None:
                    self._preempt(victim)
                    self.counters["forced_preemptions"] += 1
            while self.pool.available() < 1:
                victim = self._select_victim(needy=slot)
                if victim is None:
                    raise RuntimeError(
                        "oversubscribed pool wedged: no preemptable victim "
                        f"can free a block for slot {slot}")
                self._preempt(victim)
            bid = self.pool.alloc()
        self.table[slot, j] = bid
        return bid

    def _plain_decode_tick(self, active: list[int]) -> None:
        """One non-speculative decode step over every prefilled slot.

        Precondition: every active row's next-write block was already
        materialized by ``step()`` (also true on the speculative bailout
        replay — the mandatory claims precede the table snapshot and only
        optional draft blocks roll back).  Claiming here instead could
        preempt mid-tick, which the spec path must never do."""
        for i in active:
            assert self.table[i, int(self.lengths[i]) // self._page] != 0, \
                f"slot {i} reached decode without its next-write block"
        with self.tracer.span("decode"):
            logits, self.caches = self._guarded_decode(
                self.params, *self.decode_inputs(active))
        rids = [st.req.rid if st is not None else 0 for st in self.slots]
        counts = [len(st.req.generated) if st is not None else 0
                  for st in self.slots]
        toks = self._sample_rows(logits, rids, counts)
        self.counters["decode_steps"] += 1
        self._count_kv([int(self.lengths[i]) + 1 for i in active])
        self._count_pages(self._decode_positions(active))
        with self.tracer.span("emit"):
            for i in active:
                req = self.slots[i].req
                req.generated.append(int(toks[i]))
                self.counters["decode_tokens"] += 1
                self.lengths[i] += 1
                self.last_token[i] = toks[i]
                self._maybe_evict(i, int(toks[i]))

    def decode_inputs(self, active: list[int] | None = None):
        """``(tokens, caches, positions)`` of the next plain decode tick
        over ``active`` rows (default: every prefilled slot) — what the
        jitted decode forward consumes.  Reads engine state, changes none
        of it.  Rows still prefilling (or empty) decode at the pad
        sentinel: their q/k/v are zeroed and the cache write is dropped."""
        if active is None:
            active = [i for i, st in enumerate(self.slots)
                      if st is not None and st.prefilled()]
        tokens = jnp.asarray(self.last_token[:, None])
        caches = _attach_tables(self.caches, self.table, self.lengths)
        return tokens, caches, jnp.asarray(self._decode_positions(active))

    def _decode_positions(self, active: list[int]) -> np.ndarray:
        """``[max_slots, 1]`` query positions of a plain decode tick over
        ``active``; every other row at the pad sentinel."""
        positions = np.full((len(self.slots), 1), POS_SENTINEL, np.int32)
        positions[active, 0] = self.lengths[active]
        return positions

    def _count_kv(self, spans: list[int]) -> None:
        """Add one decode step's attended tokens, each active row's span:
        all of it in a layer without a window (``decode_kv_tokens``), at
        most the window in a window layer (``decode_window_kv_tokens``)."""
        self.counters["decode_kv_tokens"] += sum(spans)
        if self._window:
            self.counters["decode_window_kv_tokens"] += sum(
                min(n, self._window) for n in spans)

    def _count_pages(self, positions: np.ndarray) -> None:
        """Add one decode step's paged BESF kernel grid, ``max_slots x
        max_blocks_per_req`` steps a layer, and the steps inside each
        slot's page range (``kernels/paged_decode.py:page_bounds``) of a
        layer without a window and, where the model has them, of a window
        layer, from the step's query positions as the attention layer
        turns them into fill levels: position + 1, or 0 for a padding
        query."""
        real = positions != POS_SENTINEL
        q_pos = np.where(real, positions, 0)
        fill = np.where(real, q_pos + 1, 0)
        lo, hi = page_bounds(fill, q_pos, self._page, xp=np)
        self.counters["decode_page_slots"] += self.table.size
        self.counters["decode_pages_reached"] += int((hi - lo).sum())
        if self._window:
            lo, hi = page_bounds(fill, q_pos, self._page, self._window,
                                 xp=np)
            self.counters["decode_window_pages_reached"] += int(
                (hi - lo).sum())

    def programs(self) -> dict:
        """The jitted forwards this engine serves with, each with
        arguments of a shape it runs it at: ``{"decode_fn": (jitted,
        args), "prefill_fn@<width>": ...}``, one prefill entry per prompt
        chunk width.  ``jitted.lower(*args).compile()`` gives the program
        the device runs (from the compile cache once served), whose HLO
        names the ops of a profile and their ``named_scope``s."""
        out = {"decode_fn": (self._decode,
                             (self.params, *self.decode_inputs()))}
        caches = _attach_tables(self.caches, self.table[:1],
                                self.lengths[:1])
        b = self.scfg.prefill_bucket
        for w in range(b, self._chunk + 1, b):
            zeros = jnp.zeros((1, w), jnp.int32)
            out[f"prefill_fn@{w}"] = (self._prefill, (
                self.params, zeros, caches, zeros,
                jnp.asarray(0, jnp.int32)))
        return out

    def _return_draft_blocks(self, slot: int,
                             blocks: list[tuple[int, int, bool]]) -> None:
        """Return unused speculative blocks to the pool the way they came:
        a block claimed from the slot's admission reservation rolls back
        WITH its reservation unit restored (and re-credited to the slot);
        a block claimed from oversubscribed *spare* capacity frees outright
        — re-reserving it would earmark shared spare capacity to this slot
        and push other slots into needless preemptions."""
        reserved = [bid for _, bid, r in blocks if r]
        spare = [bid for _, bid, r in blocks if not r]
        if reserved:
            self.pool.rollback(reserved)
            self.slots[slot].blocks_reserved += len(reserved)
        if spare:
            self.pool.rollback(spare, reserve=False)

    # ------------------------------------------------------------------
    # speculative decode: propose -> one Sq=k+1 verify -> accept/rollback
    # ------------------------------------------------------------------

    def _spec_decode_tick(self, active: list[int]) -> None:
        """One speculative decode step: draft, verify, accept, roll back.

        Losslessness argument, in scheduler terms: query i of slot b runs
        at absolute position ``lengths[b] + i`` against exactly the KV set
        (and quant scales — see the growth bailout) the non-speculative
        engine would have at that point, and its token is sampled under
        the same (seed, rid, token-index) key.  Token i+1 is only kept if
        draft i+1 *equals* the token the target just sampled, i.e. iff the
        non-speculative engine would have fed the same input — so the
        first divergence truncates acceptance and everything after it is
        rolled back untouched."""
        k = self._spec_k
        # Injected (or real) drafter death degrades the tick, never the
        # trace: an empty draft set falls through to the plain decode
        # below — speculation only ever changes forward count, so a dead
        # drafter costs throughput, not tokens.
        drafter_down = (self.chaos is not None
                        and self.chaos.fire("drafter_fail", self.ticks))
        if drafter_down:
            self.counters["drafter_failures"] += 1
        drafts: dict[int, list[int]] = {}
        for i in active:
            req = self.slots[i].req
            # A draft beyond the request's remaining budget could out-run
            # the admission reservation; cap so written positions stay
            # within the non-speculative worst case.
            cap = min(k, req.max_new_tokens - len(req.generated) - 1)
            if drafter_down or cap <= 0:
                drafts[i] = []
                continue
            ctx = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.generated, np.int32)])
            try:
                drafts[i] = [int(t)
                             for t in self._drafter.propose(ctx, cap)][:cap]
            except Exception:
                # A real drafter exception: pluggable drafters are allowed
                # to die without taking the engine down — this slot just
                # decodes plain this tick.
                self.counters["drafter_failures"] += 1
                drafts[i] = []
        if not any(drafts[i] for i in active):
            # Nothing proposed anywhere (cold n-gram cache, budget tails):
            # a verify pass would just be a slow plain tick.
            self._plain_decode_tick(active)
            return
        self.counters["spec_ticks"] += 1

        # Snapshot for the growth bailout: jax caches are immutable, so
        # keeping the references IS the device-state snapshot; the host
        # table is copied before speculative block materialization.
        caches_snap = self.caches
        table_snap = self.table.copy()

        Sq = k + 1
        B = len(self.slots)
        tokens = np.zeros((B, Sq), np.int32)
        positions = np.full((B, Sq), POS_SENTINEL, np.int32)
        # (table index, block, claimed-from-reservation) per slot — the
        # reservation flag decides how an unused block returns to the pool.
        new_blocks: dict[int, list[tuple[int, int, bool]]] = {}
        for i in active:
            st = self.slots[i]
            row = [int(self.last_token[i])] + drafts[i]
            base = int(self.lengths[i])
            new_blocks[i] = []
            # The row's first block was claimed in step(); blocks past it
            # are *optional* — they only hold draft tokens.  Claim them
            # from the slot's reservation or the pool's spare capacity,
            # NEVER by preemption (evicting a live request for tokens that
            # may be rejected is a losing trade): when the pool is tight
            # the draft is truncated to the blocks it could get.  An
            # injected pool-dry on an unreserved claim here takes the
            # real-dryness path — the draft truncates; forcing a
            # preemption at this point would evict an *active* slot in
            # the middle of its own speculative tick.
            for j in range(base // self._page + 1,
                           (base + len(row) - 1) // self._page + 1):
                if self.table[i, j] != 0:
                    continue
                reserved = st.blocks_reserved > 0
                forced_dry = (not reserved and self.chaos is not None
                              and self.chaos.fire("pool_dry", self.ticks))
                if not forced_dry and (
                        reserved or (self.scfg.oversubscribe
                                     and self.pool.available() >= 1)):
                    new_blocks[i].append(
                        (j, self._claim_block(i, j, optional=True),
                         reserved))
                else:
                    keep = j * self._page - base
                    row = row[:keep]
                    drafts[i] = drafts[i][:keep - 1]
                    break
            tokens[i, :len(row)] = row
            positions[i, :len(row)] = base + np.arange(len(row))

        caches = _attach_tables(self.caches, self.table, self.lengths)
        logits, new_caches, grew = self._guarded_verify(
            self.params, jnp.asarray(tokens), caches,
            jnp.asarray(positions))

        if bool(grew):
            # A draft-block token grew a pool-wide quant scale: earlier
            # queries were scored under a scale the non-speculative engine
            # would not have had yet.  Discard the whole speculative step
            # and replay it plain (which handles growth natively).
            self.caches = caches_snap
            self.table = table_snap
            for i in active:
                self._return_draft_blocks(i, new_blocks[i])
            self.counters["spec_bailouts"] += 1
            self._plain_decode_tick(active)
            return

        self.caches = new_caches
        # Sample every query position under its non-speculative key:
        # row (i, x) uses token index len(generated_i) + x.
        rids = np.zeros((B, Sq), np.int32)
        counts = np.zeros((B, Sq), np.int32)
        for i in active:
            rids[i, :] = self.slots[i].req.rid
            counts[i, :] = len(self.slots[i].req.generated) + np.arange(Sq)
        toks = self._sample_rows(logits.reshape(B * Sq, -1),
                                 rids.reshape(-1),
                                 counts.reshape(-1)).reshape(B, Sq)
        self.counters["decode_steps"] += 1
        self._count_kv([int(self.lengths[i]) + 1 + len(drafts[i])
                        for i in active])
        self._count_pages(positions)

        for i in active:
            st = self.slots[i]
            req = st.req
            d = drafts[i]
            t = toks[i]
            a = 0
            while a < len(d) and d[a] == int(t[a]):
                a += 1
            emitted = [int(t[x]) for x in range(a + 1)]
            if self.scfg.eos_id is not None and self.scfg.eos_id in emitted:
                emitted = emitted[:emitted.index(self.scfg.eos_id) + 1]
            emitted = emitted[:req.max_new_tokens - len(req.generated)]
            req.generated.extend(emitted)
            self.counters["decode_tokens"] += len(emitted)
            self.counters["spec_proposed"] += len(d)
            self.counters["spec_accepted"] += a
            self.lengths[i] += len(emitted)
            self.last_token[i] = emitted[-1]
            # Roll back the rejected tail: blocks whose every slot is past
            # the new fill level hold no live token — return them to the
            # pool and restore the reservation they were claimed from.
            # Only this tick's allocations can sit past the fill level,
            # so prompt/prefix-shared blocks are structurally out of reach
            # (kv_pool.rollback additionally enforces it).
            last_j = (int(self.lengths[i]) - 1) // self._page
            stale = [blk for blk in new_blocks[i] if blk[0] > last_j]
            for j, _, _ in stale:
                self.table[i, j] = 0
            self._return_draft_blocks(i, stale)
            self._maybe_evict(i, emitted[-1])


# Public name: the paged continuous batcher IS the serving engine.
ServingEngine = PagedEngine


class StaticBucketEngine:
    """The previous engine: one same-length batch at a time, re-padded per
    batch, shared cursor.  Kept as the baseline for
    ``benchmarks/serve_throughput.py`` and for A/B-ing the scheduler."""

    def __init__(self, cfg: ModelConfig, params,
                 scfg: ServeConfig = ServeConfig()):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg

        def prefill_fn(params, tokens, caches):
            S = tokens.shape[1]
            logits, caches, _ = T.forward(params, tokens, cfg, caches=caches,
                                          positions=jnp.arange(S))
            return logits[:, -1], caches

        def decode_fn(params, token, caches, pos):
            logits, caches, _ = T.forward(
                params, token, cfg, caches=caches, positions=pos[None])
            return logits[:, -1], caches

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn)

    def init_caches(self, batch: int):
        dt = (jnp.bfloat16 if self.scfg.cache_dtype == "bfloat16"
              else jnp.float32)
        return T.init_caches(self.cfg, batch, self.scfg.max_len, dt)

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1)

    def generate(self, requests: list[Request], seed: int = 0):
        """Serve requests bucketed by prompt length, one batch at a time."""
        base_key = jax.random.PRNGKey(seed)
        buckets: dict[int, list[Request]] = {}
        for r in requests:
            buckets.setdefault(len(r.prompt), []).append(r)
        for bi, (_, batch) in enumerate(sorted(buckets.items())):
            self._generate_batch(batch, jax.random.fold_in(base_key, bi))
        return requests

    def _generate_batch(self, requests: list[Request], key):
        prompts = jnp.asarray(np.stack([r.prompt for r in requests]))
        B, S = prompts.shape
        caches = self.init_caches(B)
        logits, caches = self._prefill(self.params, prompts, caches)
        max_new = max(r.max_new_tokens for r in requests)
        token = self._sample(logits, jax.random.fold_in(key, 0))
        for r, t in zip(requests, np.asarray(token)):
            r.generated.append(int(t))
        for i in range(1, max_new):
            logits, caches = self._decode(
                self.params, token[:, None], caches,
                jnp.asarray(S + i - 1, jnp.int32))
            token = self._sample(logits, jax.random.fold_in(key, i))
            for r, t in zip(requests, np.asarray(token)):
                if len(r.generated) < r.max_new_tokens:
                    r.generated.append(int(t))
        return requests


# ---------------------------------------------------------------------------
# measured sparsity of one prompt's prefill attention (layer 0)
# ---------------------------------------------------------------------------


def _prompt_sparsity(cfg: ModelConfig, params, prompt: np.ndarray):
    from repro.core.block_adaptation import block_bitstopper_attention
    from repro.models import layers as L
    from repro.models.attention import attention_block_shape

    x = L.embed(params["embed"], jnp.asarray(prompt)[None]).astype(
        cfg.activation_dtype)
    p0 = _first_attn_params(params, cfg)
    if p0 is None:
        return {}
    from repro.models.layers import linear, rope
    acfg = cfg.attn_config(False)
    pos = jnp.arange(x.shape[1])
    q = rope(linear(p0["wq"], x), pos[None], acfg.rope_theta)
    k = rope(linear(p0["wk"], x), pos[None], acfg.rope_theta)
    v = linear(p0["wv"], x)
    G = acfg.n_heads // acfg.n_kv_heads
    kr = jnp.repeat(k, G, axis=2).swapaxes(1, 2)
    vr = jnp.repeat(v, G, axis=2).swapaxes(1, 2)
    qt = q.swapaxes(1, 2)
    S = qt.shape[-2]
    # Small q-tiles: a kv block stops fetching planes only when EVERY
    # query row in the tile agrees, so tall tiles can't terminate.  The
    # same pad-to-tile-multiple rule as the serving forward path (public
    # helper) — padding is fully masked, and blocks with no unmasked pair
    # are excluded from the traffic means rather than counted as free.
    bq, pad_q = attention_block_shape(S, 8)
    bk, pad_k = attention_block_shape(S, 16)
    mask2d = jnp.tril(jnp.ones((S, S), bool))
    if pad_q or pad_k:
        mask2d = jnp.pad(mask2d, ((0, pad_q), (0, pad_k)))
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        kr = jnp.pad(kr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    res = block_bitstopper_attention(
        qt, kr, vr, cfg=cfg.bitstopper, block_q=bq, block_k=bk, mask=mask2d)
    rounds = np.asarray(res.stats.rounds_per_block, np.float64)
    alive = np.asarray(res.stats.block_alive)
    surv = np.asarray(res.stats.survivors)[..., :S, :S]
    n_qt, n_kb = rounds.shape[-2], rounds.shape[-1]
    live = np.asarray(mask2d).reshape(n_qt, bq, n_kb, bk).any((1, 3))
    live = np.broadcast_to(live, rounds.shape)
    return {
        "prompt_len": int(prompt.shape[0]),
        "mean_rounds": float(rounds[live].mean()),
        "plane_fraction": float(rounds[live].mean() / cfg.bitstopper.bits),
        "block_alive_fraction": float(alive[live].mean()),
        "survivor_fraction": float(surv.mean()),
        "n_blocks": int(live.sum()),
        "n_pairs": int(surv.size),
    }


def _first_attn_params(params, cfg: ModelConfig):
    for si, (unit, reps) in enumerate(cfg.segments):
        for i, spec in enumerate(unit):
            if spec.mixer in ("attn", "local_attn"):
                seg = params[f"seg{si}"]
                blk = seg[f"b{i}"] if isinstance(seg, dict) else seg[0][f"b{i}"]
                p = blk["attn"]
                if cfg.scan_layers and reps > 1 and isinstance(seg, dict):
                    p = jax.tree_util.tree_map(lambda a: a[0], p)
                return p
    return None
