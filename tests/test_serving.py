"""Serving-engine tests: continuous batching, decode/prefill parity,
deterministic sampling, the per-slot and paged KV caches, block/slot
lifecycle, prefix sharing, and the decode-specialized BitStopper path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.besf import BitStopperConfig, besf_attention, \
    besf_attention_decode
from repro.models import transformer as T
from repro.serving import (
    ContinuousBatchingEngine,
    PagedEngine,
    Request,
    ServeConfig,
    StaticBucketEngine,
)


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("stablelm-1.6b")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, lens, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, L, dtype=np.int32),
                    max_new_tokens=max_new)
            for L in lens]


def _engine(cfg, params, **kw):
    scfg = ServeConfig(max_len=kw.pop("max_len", 64),
                       max_slots=kw.pop("max_slots", 2),
                       prefill_bucket=kw.pop("prefill_bucket", 8), **kw)
    return ContinuousBatchingEngine(cfg, params, scfg)


# ---------------------------------------------------------------------------
# decode/prefill parity through the continuous-batching engine
# ---------------------------------------------------------------------------


def test_decode_matches_prefill_bitexact_xla(model):
    """A sequence decoded token-by-token through the engine must follow the
    same greedy path as a one-shot (cache-free) prefill forward pass."""
    cfg, params = model
    eng = _engine(cfg, params)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 9, dtype=np.int32)
    req = Request(prompt=prompt, max_new_tokens=8)
    eng.generate([req], seed=0)

    seq = np.concatenate([prompt, np.asarray(req.generated[:-1], np.int32)])
    logits, _, _ = T.forward(params, jnp.asarray(seq)[None], cfg)
    greedy = np.asarray(jnp.argmax(logits[0], -1))[len(prompt) - 1:]
    assert req.generated == [int(t) for t in greedy]


def test_decode_matches_prefill_bitstopper(model):
    """Same parity on the sparse path, within tolerance: block-granular
    prefill and the per-token decode fast path may disagree on pruned
    (near-zero-mass) candidates, so compare next-token logits loosely and
    the greedy path exactly."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.8))
    eng = _engine(cfgb, params)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfgb.vocab, 9, dtype=np.int32)
    req = Request(prompt=prompt, max_new_tokens=5)
    eng.generate([req], seed=0)
    assert len(req.generated) == 5

    # Dense one-shot forward: the sparse serve must track it closely.
    seq = np.concatenate([prompt, np.asarray(req.generated[:-1], np.int32)])
    logits, _, _ = T.forward(params, jnp.asarray(seq)[None],
                             cfg.replace(attn_impl="xla"))
    greedy = [int(t) for t in
              np.asarray(jnp.argmax(logits[0], -1))[len(prompt) - 1:]]
    assert req.generated == greedy


# ---------------------------------------------------------------------------
# continuous batching semantics
# ---------------------------------------------------------------------------


def test_mixed_lengths_isolated_slots(model):
    """Requests of different lengths served together (queue > slots) must
    each produce exactly what they produce when served alone — slot caches
    are isolated and masks respect per-slot fill levels."""
    cfg, params = model
    lens = (5, 11, 17)
    together = _reqs(cfg, lens)
    _engine(cfg, params).generate(together, seed=0)
    assert all(len(r.generated) == 6 for r in together)

    for i, L in enumerate(lens):
        alone = _reqs(cfg, lens)[i]          # same prompts (same seed)
        _engine(cfg, params).generate([alone], seed=0)
        assert alone.generated == together[i].generated, f"slot {i} differs"


def test_queue_admission_and_eviction(model):
    """More requests than slots: all finish, and the engine interleaves
    prefill with in-flight decode (scheduler actually continuous)."""
    cfg, params = model
    reqs = _reqs(cfg, (5, 7, 9, 11, 13), max_new=4)
    eng = _engine(cfg, params, max_slots=2)
    eng.generate(reqs, seed=0)
    assert all(len(r.generated) == 4 for r in reqs)
    assert eng.counters["requests_finished"] == 5
    # with 2 slots and 5 requests, at least one admission must have
    # happened after decoding started (interleaving, not phases)
    assert max(r.admitted_step for r in reqs) > 0
    assert all(r is None for r in eng.slots)


def test_eos_eviction(model):
    cfg, params = model
    eng = _engine(cfg, params)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 9, dtype=np.int32)
    free_run = Request(prompt=prompt.copy(), max_new_tokens=8)
    eng.generate([free_run], seed=0)
    eos = free_run.generated[2]              # force a stop at step 3

    eng2 = ContinuousBatchingEngine(cfg, params, ServeConfig(
        max_len=64, max_slots=2, prefill_bucket=8, eos_id=int(eos)))
    stopped = Request(prompt=prompt.copy(), max_new_tokens=8)
    eng2.generate([stopped], seed=0)
    assert stopped.generated == free_run.generated[:3]


def test_max_len_rejection(model):
    cfg, params = model
    eng = _engine(cfg, params, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.zeros(12, np.int32), max_new_tokens=8))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.zeros(4, np.int32), max_new_tokens=0))


def test_prefill_bucket_invariance(model):
    """Bucket padding must not change served tokens: pad rows are zeroed
    before attention, so the BitStopper per-tensor quant scale (and hence
    every threshold decision) is independent of the bucket size."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.6))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfgb.vocab, 9, dtype=np.int32)
    outs = []
    for bucket in (1, 8, 16):
        eng = _engine(cfgb, params, prefill_bucket=bucket)
        req = Request(prompt=prompt.copy(), max_new_tokens=5)
        eng.generate([req], seed=0)
        outs.append(req.generated)
    assert outs[0] == outs[1] == outs[2], outs


# ---------------------------------------------------------------------------
# paged engine: parity, block/slot lifecycle, prefix sharing
# ---------------------------------------------------------------------------


def _paged(cfg, params, **kw):
    scfg = ServeConfig(max_len=kw.pop("max_len", 64),
                       max_slots=kw.pop("max_slots", 2),
                       prefill_bucket=kw.pop("prefill_bucket", 8),
                       page_size=kw.pop("page_size", 8), **kw)
    return PagedEngine(cfg, params, scfg)


def test_paged_matches_contiguous_bitexact_greedy(model):
    """Acceptance: the paged engine's served tokens are bit-identical to
    the contiguous ContinuousBatchingEngine on the same trace and seed
    (dense path: per-query attention sees the same KV set, masked paged
    view slots are exact zeros)."""
    cfg, params = model
    a = _reqs(cfg, (5, 11, 17, 9))
    _engine(cfg, params).generate(a, seed=0)
    b = _reqs(cfg, (5, 11, 17, 9))
    _paged(cfg, params).generate(b, seed=0)
    assert [r.generated for r in a] == [r.generated for r in b]


def test_paged_matches_contiguous_bitexact_sampled(model):
    """Same trace, seeded sampling: per-request sampling keys are a pure
    function of (seed, rid, token index), so chunked-prefill scheduling
    differences cannot shift the sampled trace."""
    cfg, params = model
    a = _reqs(cfg, (5, 11, 17), max_new=5)
    _engine(cfg, params, temperature=1.0).generate(a, seed=7)
    b = _reqs(cfg, (5, 11, 17), max_new=5)
    _paged(cfg, params, temperature=1.0).generate(b, seed=7)
    assert [r.generated for r in a] == [r.generated for r in b]


def test_paged_bitstopper_decode_greedy_parity(model):
    """The sparse path through the paged cache: the Sq=1 BESF decode walks
    the block-table view and must still follow the dense greedy path."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.8))
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfgb.vocab, 9, dtype=np.int32)
    req = Request(prompt=prompt, max_new_tokens=5)
    _paged(cfgb, params).generate([req], seed=0)

    seq = np.concatenate([prompt, np.asarray(req.generated[:-1], np.int32)])
    logits, _, _ = T.forward(params, jnp.asarray(seq)[None],
                             cfg.replace(attn_impl="xla"))
    greedy = [int(t) for t in
              np.asarray(jnp.argmax(logits[0], -1))[len(prompt) - 1:]]
    assert req.generated == greedy


def test_paged_decode_page_counters(model):
    """``decode_page_slots`` / ``decode_pages_reached`` against a hand
    count from the slots' fill levels: every decode step walks max_slots x
    max_blocks_per_req grid steps, of which a decoding slot of fill level
    n (its new token included) reaches ceil(n / page); an empty slot and
    an idle engine reach nothing."""
    cfg, params = model
    eng = _paged(cfg, params, max_slots=3, page_size=8, max_len=64)
    assert eng.table.shape == (3, 8)
    idle = dict(eng.counters)
    assert not eng.step()
    assert eng.counters == idle
    assert idle["decode_page_slots"] == idle["decode_pages_reached"] == 0

    for r in _reqs(cfg, (6, 15), max_new=12):
        eng.submit(r)
    while not all(st is not None and st.prefilled()
                  for st in eng.slots[:2]):
        eng.step()
    start = dict(eng.counters)
    slots = reached = 0
    for _ in range(6):
        active = [i for i, st in enumerate(eng.slots) if st is not None]
        assert active == [0, 1]
        slots += 3 * 8
        reached += sum(-(-(int(eng.lengths[i]) + 1) // 8) for i in active)
        eng.step()
    assert eng.counters["decode_steps"] - start["decode_steps"] == 6
    assert (eng.counters["decode_page_slots"]
            - start["decode_page_slots"]) == slots
    assert (eng.counters["decode_pages_reached"]
            - start["decode_pages_reached"]) == reached
    assert reached < slots

    while eng.step():
        pass
    done = dict(eng.counters)
    assert not eng.step()
    assert eng.counters == done


def test_paged_chunked_prefill_invariance(model):
    """Chunk size must not change served tokens on the dense path, and a
    long prompt must actually take several prefill ticks."""
    cfg, params = model
    outs, chunks = [], []
    for chunk in (8, 16, 32):
        eng = _paged(cfg, params, prefill_chunk=chunk)
        req = _reqs(cfg, (37,), max_new=4)[0]
        eng.generate([req], seed=0)
        outs.append(req.generated)
        chunks.append(eng.counters["prefill_chunks"])
    assert outs[0] == outs[1] == outs[2], outs
    assert chunks[0] == 5                     # ceil(37 / 8)


def test_paged_long_generation_beyond_max_len(model):
    """Admission is bounded by pool capacity, not max_len: a request whose
    prompt + max_new_tokens exceed max_len serves once the table/pool
    allow it (the contiguous engine must still reject it)."""
    cfg, params = model
    with pytest.raises(ValueError):
        _engine(cfg, params, max_len=16).submit(
            Request(prompt=np.zeros(10, np.int32), max_new_tokens=20))

    eng = _paged(cfg, params, max_len=16, max_blocks_per_req=8,
                 pool_blocks=17)
    req = _reqs(cfg, (10,), max_new=20)[0]
    eng.generate([req], seed=0)
    assert len(req.generated) == 20
    # ...but a request that cannot ever fit is rejected up front.
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.zeros(10, np.int32),
                           max_new_tokens=200))


def test_paged_eviction_returns_all_blocks(model):
    """EOS/finish eviction drains every table reference and reservation:
    after the trace completes the pool is back to full capacity."""
    cfg, params = model
    eng = _paged(cfg, params, max_slots=2, prefix_sharing=False)
    eng.generate(_reqs(cfg, (5, 11, 17, 9, 13), max_new=4), seed=0)
    assert all(s is None for s in eng.slots)
    assert eng.pool.live_blocks() == 0
    assert eng.pool.available() == eng.pool.capacity
    assert (eng.table == 0).all()


def test_paged_recycled_blocks_no_stale_kv(model):
    """A request admitted onto recycled physical blocks must not read the
    previous owner's KV: output equals a fresh-engine run bit for bit.
    The pool is sized so the second batch MUST reuse the first's blocks."""
    cfg, params = model
    # 2 slots, <=2 blocks per request, null block -> 5-block pool is snug.
    eng = _paged(cfg, params, max_slots=2, page_size=8, pool_blocks=5,
                 prefix_sharing=False)
    eng.generate(_reqs(cfg, (12, 9), max_new=4, seed=3), seed=0)
    assert eng.pool.alloc_count >= 4
    reused = _reqs(cfg, (11, 7), max_new=4, seed=4)
    eng.generate(reused, seed=0)

    fresh = _reqs(cfg, (11, 7), max_new=4, seed=4)
    _paged(cfg, params, max_slots=2, page_size=8, pool_blocks=5,
           prefix_sharing=False).generate(fresh, seed=0)
    assert [r.generated for r in reused] == [r.generated for r in fresh]


def test_paged_admission_blocks_on_pool_capacity(model):
    """A free slot is not enough: the head of line waits until evictions
    return blocks, then serves — and still matches an uncontended run."""
    cfg, params = model
    # Each request needs 2 blocks (12+4-1 tokens, page 8); capacity 3, so
    # only one request fits at a time even though 2 slots are free.
    eng = _paged(cfg, params, max_slots=2, page_size=8, pool_blocks=4,
                 prefix_sharing=False)
    tight = _reqs(cfg, (12, 12, 12), max_new=4, seed=6)
    eng.generate(tight, seed=0)
    assert all(len(r.generated) == 4 for r in tight)
    assert eng.pool.available() == eng.pool.capacity

    for i in range(3):
        alone = _reqs(cfg, (12, 12, 12), max_new=4, seed=6)[i]
        _paged(cfg, params, max_slots=2, page_size=8,
               prefix_sharing=False).generate([alone], seed=0)
        assert alone.generated == tight[i].generated, f"request {i} differs"


def test_paged_prefix_sharing_bitident_and_saves_blocks(model):
    """Requests with a common system prompt: shared serving produces
    bit-identical tokens to unshared serving, actually hits the prefix
    cache, and keeps fewer blocks live."""
    cfg, params = model
    sys_prompt = np.random.default_rng(42).integers(
        0, cfg.vocab, 24, dtype=np.int32)

    def reqs(seed=1):
        r = np.random.default_rng(seed)
        return [Request(prompt=np.concatenate(
                            [sys_prompt,
                             r.integers(0, cfg.vocab, L, dtype=np.int32)]),
                        max_new_tokens=4)
                for L in (3, 7, 5, 9)]

    es = _paged(cfg, params, max_slots=2)
    eu = _paged(cfg, params, max_slots=2, prefix_sharing=False)
    # Publish the system prompt once (steady-state serving), then measure
    # the batch: every request should map the shared blocks.
    for eng in (es, eu):
        eng.generate([Request(prompt=sys_prompt.copy(), max_new_tokens=1)],
                     seed=0)
        eng.pool.peak_live_blocks = 0
    shared = reqs()
    es.generate(shared, seed=0)
    unshared = reqs()
    eu.generate(unshared, seed=0)

    assert [r.generated for r in shared] == [r.generated for r in unshared]
    assert es.counters["prefix_hit_tokens"] >= 24 * 3
    assert es.pool.peak_live_blocks < eu.pool.peak_live_blocks
    assert es.kv_bytes_resident() < eu.kv_bytes_resident()
    # shared blocks are refcounted back to zero at the end
    assert es.pool.live_blocks() == 0


def _fused_vs_fallback(cfg, params, make_reqs, seed=0, **kw):
    """Serve the same trace through the fused Pallas kernel (interpret on
    CPU) and the pure-JAX gather fallback; return both token lists."""
    outs = []
    for fused in (True, False):
        eng = _paged(cfg, params, fused_decode=fused, **kw)
        reqs = make_reqs()
        eng.generate(reqs, seed=seed)
        outs.append([r.generated for r in reqs])
    return outs


def test_fused_decode_matches_gather_fallback_greedy(model):
    """Acceptance: flipping ServeConfig.fused_decode never changes served
    tokens — the kernel is bit-identical to the paged oracle."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.8))
    fused, fallback = _fused_vs_fallback(
        cfgb, params, lambda: _reqs(cfgb, (5, 11, 17, 9)))
    assert fused == fallback


def test_fused_decode_matches_gather_fallback_sampled(model):
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.6))
    fused, fallback = _fused_vs_fallback(
        cfgb, params, lambda: _reqs(cfgb, (5, 11, 17), max_new=5),
        seed=7, temperature=1.0)
    assert fused == fallback


def test_fused_decode_matches_fallback_shared_prefix_and_recycled(model):
    """The hard pool states: refcount>1 prefix blocks mapped by several
    tables at once, and a pool snug enough that physical blocks recycle
    mid-trace — the fused walk must still match the fallback token for
    token."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.8))
    sys_prompt = np.random.default_rng(42).integers(
        0, cfgb.vocab, 16, dtype=np.int32)

    def reqs():
        r = np.random.default_rng(9)
        return [Request(prompt=np.concatenate(
                            [sys_prompt,
                             r.integers(0, cfgb.vocab, L, dtype=np.int32)]),
                        max_new_tokens=4)
                for L in (3, 7, 5, 9, 6)]

    # pool snug: 5 requests x ~4 blocks, 2 slots, 9 allocatable blocks
    fused, fallback = _fused_vs_fallback(cfgb, params, reqs,
                                         pool_blocks=10)
    assert fused == fallback
    # and the whole thing still follows the dense greedy path per request
    for i, toks in enumerate(fused):
        assert len(toks) == 4


def test_kv_bytes_resident_counts_plane_pool(model):
    """Honest memory accounting: with the fused kernel on, every live
    block also carries its packed bit-plane pool (bits x Hkv x D bits per
    token) plus the static amax scale state — resident bytes must reflect
    it, not just the f32 K/V rows."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.8))
    engines = {}
    for fused in (True, False):
        eng = _paged(cfgb, params, fused_decode=fused)
        reqs = _reqs(cfgb, (9, 14), max_new=4)
        eng.generate(reqs, seed=0)
        engines[fused] = eng
    assert (engines[True].pool.peak_live_blocks
            == engines[False].pool.peak_live_blocks)
    with_planes = engines[True].kv_bytes_resident()
    without = engines[False].kv_bytes_resident()
    assert with_planes > without
    # the gap is exactly the plane pool: bits/8 bytes per (token, kv-head,
    # dim) per BitStopper layer, over peak live tokens
    acfg = cfgb.attn_config(False)
    per_tok_planes = (cfgb.n_layers * acfg.bitstopper.bits
                      * acfg.n_kv_heads * acfg.head_dim) // 8
    blocks = engines[True].pool.peak_live_blocks
    page = engines[True].scfg.page_size
    assert with_planes - without == blocks * page * per_tok_planes
    # amax scale state is charged on both bitstopper engines
    dense = _paged(cfg, params)
    dense.generate(_reqs(cfg, (9, 14), max_new=4), seed=0)
    assert dense.pool.peak_live_blocks == blocks
    amax_bytes = cfgb.n_layers * 2 * acfg.n_kv_heads * 4
    assert without - dense.kv_bytes_resident() == amax_bytes


def test_fused_decode_page_size_validation():
    with pytest.raises(ValueError):
        ServeConfig(fused_decode=True, page_size=12)
    # planeless pools (page % 8 != 0) still serve through the dense gather
    scfg = ServeConfig(page_size=12)
    assert scfg.fused_decode is None


@pytest.mark.parametrize("fused_decode", [None, False])
def test_tpu_auto_fused_decode_never_falls_back_silently(
        model, monkeypatch, fused_decode):
    """On TPU, fused_decode=None resolves to the kernel; a page size it
    cannot pack is an error, never a quiet switch to the gather oracle.
    fused_decode=False still asks for the oracle explicitly.  The TPU is
    steered here through the backend query the engine consults."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if fused_decode is None:
        with pytest.raises(ValueError, match="page_size % 8"):
            _paged(cfgb, params, page_size=12, fused_decode=None)
    else:
        eng = _paged(cfgb, params, page_size=12, fused_decode=False)
        assert eng.cfg.fused_decode is False


def test_paged_bitstopper_window_layer_fused():
    """local_attn layers decode through the paged path with window
    masking (position-masked, no ring); fused and fallback must agree
    there too."""
    from repro.models.config import BlockSpec, ModelConfig
    cfgw = ModelConfig(
        name="win-test", family="dense", d_model=64, vocab=256,
        segments=(((BlockSpec("local_attn"),), 2),),
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, window=8,
        attn_impl="bitstopper_xla", bitstopper=BitStopperConfig(alpha=0.8))
    params = T.init_model(jax.random.PRNGKey(1), cfgw)
    fused, fallback = _fused_vs_fallback(
        cfgw, params, lambda: _reqs(cfgw, (9, 13), max_new=4))
    assert fused == fallback


# ---------------------------------------------------------------------------
# oversubscription: victim preemption + lossless resume
# ---------------------------------------------------------------------------

# Pool sized so worst-case reservations of the three requests (4 blocks
# each at page 8) cannot coexist, but their *actual* footprints can — the
# shape oversubscription exists for.  max_new is large enough that decode
# outgrows the prompt-sized reservations and a mid-decode claim must
# preempt.
_OS = dict(max_slots=3, page_size=8, pool_blocks=10, oversubscribe=True)


def _os_reqs(cfg, max_new=16, seed=0):
    return _reqs(cfg, (12, 9, 11), max_new=max_new, seed=seed)


def test_oversubscribed_preemption_bitident_greedy(model):
    """Acceptance: an oversubscribed trace completes with >=1 observed
    preemption and its token streams are bit-identical to an uncontended
    (worst-case-reserved, ample pool) run — the preempted request resumes
    via chunked-prefill recompute without perturbing a single token."""
    cfg, params = model
    a = _os_reqs(cfg)
    _paged(cfg, params, max_slots=3).generate(a, seed=0)
    eng = _paged(cfg, params, **_OS)
    b = _os_reqs(cfg)
    eng.generate(b, seed=0)
    assert eng.counters["preemptions"] >= 1
    assert [r.generated for r in a] == [r.generated for r in b]
    assert sum(r.preemptions for r in b) == eng.counters["preemptions"]
    # full cleanup: no leaked blocks or reservations after the trace
    assert eng.pool.available() == eng.pool.capacity
    assert (eng.table == 0).all()


def test_oversubscribed_preemption_bitident_sampled(model):
    """Seeded sampling: keys are (seed, rid, token index), so preemption
    and resume cannot shift the sampled trace either."""
    cfg, params = model
    a = _os_reqs(cfg)
    _paged(cfg, params, max_slots=3, temperature=1.0).generate(a, seed=7)
    eng = _paged(cfg, params, temperature=1.0, **_OS)
    b = _os_reqs(cfg)
    eng.generate(b, seed=7)
    assert eng.counters["preemptions"] >= 1
    assert [r.generated for r in a] == [r.generated for r in b]


def test_oversubscribed_lifo_policy_bitident(model):
    """The victim-choice policy changes WHO recomputes, never WHAT is
    served."""
    cfg, params = model
    a = _os_reqs(cfg)
    _paged(cfg, params, max_slots=3).generate(a, seed=0)
    eng = _paged(cfg, params, preempt_policy="lifo", **_OS)
    b = _os_reqs(cfg)
    eng.generate(b, seed=0)
    assert eng.counters["preemptions"] >= 1
    assert [r.generated for r in a] == [r.generated for r in b]


def test_oversubscribed_prefix_sharing_resumes_shared_blocks(model):
    """With a common system prompt, preemption decrefs the shared prefix
    blocks (they stay registered) and resume re-maps them for free — and
    the served tokens still match the uncontended unshared run."""
    cfg, params = model
    sys_prompt = np.random.default_rng(42).integers(
        0, cfg.vocab, 16, dtype=np.int32)

    def reqs():
        r = np.random.default_rng(5)
        return [Request(prompt=np.concatenate(
                            [sys_prompt,
                             r.integers(0, cfg.vocab, L, dtype=np.int32)]),
                        max_new_tokens=16)
                for L in (3, 7, 5)]

    a = reqs()
    _paged(cfg, params, max_slots=3, prefix_sharing=False).generate(
        a, seed=0)
    eng = _paged(cfg, params, pool_blocks=11, max_slots=3, page_size=8,
                 oversubscribe=True)
    b = reqs()
    eng.generate(b, seed=0)
    assert eng.counters["preemptions"] >= 1
    assert eng.counters["prefix_hit_tokens"] > 0
    assert [r.generated for r in a] == [r.generated for r in b]
    assert eng.pool.available() == eng.pool.capacity


def test_oversubscribed_speculative_bitident(model):
    """Speculative decoding under oversubscription: draft blocks are never
    worth a preemption (drafts truncate instead), and the combined
    spec+preemption trace still equals plain uncontended serving."""
    cfg, params = model
    a = _os_reqs(cfg)
    _paged(cfg, params, max_slots=3).generate(a, seed=0)
    eng = _paged(cfg, params, speculative="ngram", draft_k=3, **_OS)
    b = _os_reqs(cfg)
    eng.generate(b, seed=0)
    assert eng.counters["preemptions"] >= 1
    assert [r.generated for r in a] == [r.generated for r in b]
    assert eng.pool.available() == eng.pool.capacity


def test_oversubscribed_spec_rollback_spare_capacity(model):
    """Adversarial drafter: every draft is (almost always) rejected, so
    draft-tail blocks — claimed from the admission reservation AND from
    oversubscribed spare capacity — are constantly rolled back.  Spare
    claims must free outright (no phantom reservations earmarking shared
    capacity), the pool must drain clean, and the trace stays lossless."""
    cfg, params = model

    class RepeatDrafter:
        def propose(self, ctx, k):
            return [int(ctx[-1])] * k

    a = _os_reqs(cfg, max_new=24)
    _paged(cfg, params, max_slots=3).generate(a, seed=0)
    eng = _paged(cfg, params, speculative="ngram", draft_k=6,
                 **_OS)
    eng._drafter = RepeatDrafter()
    b = _os_reqs(cfg, max_new=24)
    eng.generate(b, seed=0)
    assert [r.generated for r in a] == [r.generated for r in b]
    assert eng.counters["spec_proposed"] > eng.counters["spec_accepted"]
    assert eng.pool.available() == eng.pool.capacity
    assert eng.pool._reserved == 0


def test_oversubscribed_bitstopper_greedy_parity(model):
    """The sparse serving path preempts and resumes too: BitStopper greedy
    under an oversubscribed pool matches its own uncontended run (the
    rewritten KV rows are recomputed from the same hidden states)."""
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla",
                       bitstopper=BitStopperConfig(alpha=0.8))
    a = _os_reqs(cfgb)
    _paged(cfgb, params, max_slots=3).generate(a, seed=0)
    eng = _paged(cfgb, params, **_OS)
    b = _os_reqs(cfgb)
    eng.generate(b, seed=0)
    assert eng.counters["preemptions"] >= 1
    assert [r.generated for r in a] == [r.generated for r in b]


def test_oversubscribe_requires_paged_engine(model):
    cfg, params = model
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(cfg, params,
                                 ServeConfig(oversubscribe=True))


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(max_slots=0)
    with pytest.raises(ValueError):
        ServeConfig(prefill_bucket=0)
    with pytest.raises(ValueError):
        ServeConfig(max_len=-1)
    with pytest.raises(ValueError):
        ServeConfig(page_size=0)
    with pytest.raises(ValueError):
        ServeConfig(pool_blocks=1)
    with pytest.raises(ValueError):
        ServeConfig(max_blocks_per_req=0)
    with pytest.raises(ValueError):
        ServeConfig(prefill_chunk=0)
    with pytest.raises(ValueError):
        ServeConfig(prefill_bucket=16, prefill_chunk=24)  # not a multiple
    with pytest.raises(ValueError):
        ServeConfig(temperature=-0.5)
    with pytest.raises(ValueError):
        ServeConfig(cache_dtype="float16")
    with pytest.raises(ValueError):
        ServeConfig(preempt_policy="roulette")
    # valid construction resolves defaults
    scfg = ServeConfig(max_len=64, page_size=16)
    assert scfg.resolved_max_blocks() == 4
    assert scfg.resolved_pool_blocks() == 1 + 4 * 4
    assert scfg.resolved_chunk() % scfg.prefill_bucket == 0


# ---------------------------------------------------------------------------
# deterministic sampling
# ---------------------------------------------------------------------------


def test_sampling_deterministic_under_seed(model):
    cfg, params = model
    outs = []
    for _ in range(2):
        reqs = _reqs(cfg, (5, 11), max_new=6)
        _engine(cfg, params, temperature=1.0).generate(reqs, seed=7)
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1], "same seed must reproduce every token"

    reqs = _reqs(cfg, (5, 11), max_new=6)
    _engine(cfg, params, temperature=1.0).generate(reqs, seed=8)
    assert [r.generated for r in reqs] != outs[0], \
        "different seed should change sampled tokens"


def test_greedy_ignores_seed(model):
    cfg, params = model
    a = _reqs(cfg, (9,), max_new=5)
    b = _reqs(cfg, (9,), max_new=5)
    _engine(cfg, params).generate(a, seed=0)
    _engine(cfg, params).generate(b, seed=123)
    assert a[0].generated == b[0].generated


def test_static_engine_deterministic(model):
    cfg, params = model
    scfg = ServeConfig(max_len=64, temperature=1.0)
    outs = []
    for _ in range(2):
        reqs = _reqs(cfg, (8, 8, 12), max_new=5)
        StaticBucketEngine(cfg, params, scfg).generate(reqs, seed=3)
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# per-slot cache + decode-specialized BESF internals
# ---------------------------------------------------------------------------


def test_per_slot_cache_layout(model):
    cfg, params = model
    caches = T.init_caches(cfg, 3, 32, per_slot=True)
    leaf = caches["seg0"]
    leaf = leaf[0] if isinstance(leaf, list) else \
        jax.tree_util.tree_map(lambda a: a[0], leaf)
    c = leaf["b0"]
    assert c["pos"].shape == (3, 32) and c["length"].shape == (3,)
    assert bool((c["pos"] >= 2 ** 30).all())


def test_per_slot_rejects_non_attention():
    cfg = reduced_config("mamba2-130m")
    with pytest.raises(NotImplementedError):
        T.init_caches(cfg, 2, 16, per_slot=True)


def test_besf_decode_bitexact_vs_reference():
    """The Sq=1 fast path (fused plane contraction + elementwise LATS)
    must reproduce the faithful per-round reference bit for bit —
    survivors, planes fetched, scores, and output."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(64, 16)) * 2, jnp.float32)
    v = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    mask = jnp.asarray(rng.random(64) > 0.2)[None]
    for alpha in (0.2, 0.6, 1.0):
        cfg = BitStopperConfig(alpha=alpha)
        ref = besf_attention(q, k, v, cfg, mask=mask)
        dec = besf_attention_decode(q, k, v, cfg, mask=mask)
        np.testing.assert_array_equal(np.asarray(ref.stats.survivors),
                                      np.asarray(dec.stats.survivors))
        np.testing.assert_array_equal(np.asarray(ref.stats.planes_fetched),
                                      np.asarray(dec.stats.planes_fetched))
        np.testing.assert_array_equal(np.asarray(ref.scores),
                                      np.asarray(dec.scores))
        np.testing.assert_array_equal(np.asarray(ref.out),
                                      np.asarray(dec.out))


def test_besf_decode_batched_per_example_masks():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(3, 4, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 4, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 4, 32, 16)), jnp.float32)
    m = jnp.asarray(rng.random((3, 1, 1, 32)) > 0.3)
    cfg = BitStopperConfig(alpha=0.6)
    ref = besf_attention(q, k, v, cfg, mask=m)
    dec = besf_attention_decode(q, k, v, cfg, mask=m)
    np.testing.assert_array_equal(np.asarray(ref.out), np.asarray(dec.out))


# ---------------------------------------------------------------------------
# served-traffic accounting
# ---------------------------------------------------------------------------


def test_sparsity_report_per_request(model):
    cfg, params = model
    cfgb = cfg.replace(attn_impl="bitstopper_xla")
    eng = _engine(cfgb, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfgb.vocab, L, dtype=np.int32)
               for L in (8, 16, 24)]
    rep = eng.sparsity_report(prompts)
    assert len(rep["per_request"]) == 3
    assert [r["prompt_len"] for r in rep["per_request"]] == [8, 16, 24]
    for r in rep["per_request"]:
        assert 0.0 < r["plane_fraction"] <= 1.0
        assert 0.0 < r["survivor_fraction"] <= 1.0
    # aggregate is the block-weighted mean (long prompts carry more units)
    w = np.array([r["n_blocks"] for r in rep["per_request"]], float)
    v = np.array([r["plane_fraction"] for r in rep["per_request"]])
    assert rep["plane_fraction"] == pytest.approx((v * w).sum() / w.sum())
