"""Speculative decoding: multi-query paged BESF verify (oracle + fused
Sq-tiled kernel), the PagedEngine draft-verify-accept loop, losslessness
against non-speculative traces, and block-table rollback invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import quantization as qlib
from repro.core.besf import (
    BitStopperConfig,
    besf_attention_decode_paged,
    besf_attention_verify_paged,
)
from repro.kernels.paged_decode import page_bounds
from repro.kernels.paged_verify import paged_bitstopper_verify
from repro.models import transformer as T
from repro.serving import (
    ContinuousBatchingEngine,
    DraftModelDrafter,
    NGramDrafter,
    PagedEngine,
    Request,
    ServeConfig,
)

BITS = 12


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("stablelm-1.6b").replace(
        attn_impl="bitstopper_xla", bitstopper=BitStopperConfig(alpha=0.8))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, lens, max_new=6, seed=0, prefix=None):
    rng = np.random.default_rng(seed)
    out = []
    for L in lens:
        p = rng.integers(0, cfg.vocab, L, dtype=np.int32)
        if prefix is not None:
            p = np.concatenate([prefix, p])
        out.append(Request(prompt=p, max_new_tokens=max_new))
    return out


def _scfg(**kw):
    return ServeConfig(max_len=kw.pop("max_len", 64),
                       max_slots=kw.pop("max_slots", 2),
                       prefill_bucket=kw.pop("prefill_bucket", 8),
                       page_size=kw.pop("page_size", 8), **kw)


# ---------------------------------------------------------------------------
# multi-query paged verify: oracle vs Sq=1 decode, kernel vs oracle
# ---------------------------------------------------------------------------


def _pool_state(seed, P=9, bs=16, Hkv=2, D=16):
    rng = np.random.default_rng(seed)
    k_pool = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)) * 2, jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), jnp.float32)
    # stale garbage in an unreferenced recycled block, louder than amax
    k_pool = k_pool.at[8].set(50.0)
    k_amax = jnp.max(jnp.abs(k_pool[:8]), axis=(0, 1, 3))
    v_amax = jnp.max(jnp.abs(v_pool), axis=(0, 1, 3))
    return k_pool, v_pool, k_amax, v_amax


def test_verify_oracle_matches_sq1_decode_per_query():
    """Losslessness foundation: every real (slot, query) row of the verify
    oracle is bit-identical to the Sq=1 paged decode at that position with
    that fill level — causal intra-draft masking IS the Sq=1 semantics."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(0)
    rng = np.random.default_rng(1)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    Sq, Hq, D = 3, 4, k_pool.shape[-1]
    q = jnp.asarray(rng.normal(size=(2, Sq, Hq, D)), jnp.float32)
    qpos = jnp.asarray([[17, 18, 19], [9, 10, 0]], jnp.int32)
    lengths = jnp.asarray([[18, 19, 20], [10, 11, 0]], jnp.int32)
    cfg = BitStopperConfig(alpha=0.6)
    ver = besf_attention_verify_paged(q, k_pool, v_pool, table, lengths,
                                      qpos, k_amax, v_amax, cfg=cfg)
    for b in range(2):
        for i in range(Sq):
            if int(lengths[b, i]) == 0:       # padding query: no work
                assert np.asarray(ver.rounds)[b, i].sum() == 0
                continue
            dec = besf_attention_decode_paged(
                q[b:b + 1, i], k_pool, v_pool, table[b:b + 1],
                lengths[b:b + 1, i], qpos[b:b + 1, i], k_amax, v_amax,
                cfg=cfg)
            np.testing.assert_array_equal(np.asarray(dec.out[0]),
                                          np.asarray(ver.out)[b, i])
            np.testing.assert_array_equal(np.asarray(dec.rounds[0]),
                                          np.asarray(ver.rounds)[b, i])
            np.testing.assert_array_equal(np.asarray(dec.survivors[0]),
                                          np.asarray(ver.survivors)[b, i])
            np.testing.assert_array_equal(np.asarray(dec.v_fetched[0]),
                                          np.asarray(ver.v_fetched)[b, i])


@pytest.mark.parametrize("alpha,window,G", [
    (0.2, None, 1),
    (0.6, None, 2),
    (0.8, 24, 2),
])
def test_verify_kernel_matches_oracle(alpha, window, G):
    """Bit-exact kernel/oracle parity on adversarial tables: a shared
    physical block mapped by two rows, recycled stale garbage, a row
    ending mid-page, and a padding (zero-length) query.  Per-query rounds,
    survivors and V-fetch decisions are bitwise; out agrees to f32
    epsilon (same contract as the Sq=1 decode kernel tests)."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(2)
    rng = np.random.default_rng(3)
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    Hq = Hkv * G
    kq_pool = qlib.pack_pool_planes(k_pool, k_amax, BITS)
    table = jnp.asarray([[1, 2, 3, 4], [1, 5, 6, 0], [7, 3, 0, 0]],
                        jnp.int32)
    Sq = 3
    q = jnp.asarray(rng.normal(size=(3, Sq, Hq, D)) * 2, jnp.float32)
    qpos = jnp.asarray([[61, 62, 63], [38, 39, 40], [17, 18, 0]], jnp.int32)
    lengths = jnp.asarray([[62, 63, 64], [39, 40, 41], [18, 19, 0]],
                          jnp.int32)
    cfg = BitStopperConfig(alpha=alpha)
    ora = besf_attention_verify_paged(q, k_pool, v_pool, table, lengths,
                                      qpos, k_amax, v_amax, cfg=cfg,
                                      window=window)
    ker = paged_bitstopper_verify(q, kq_pool, v_pool, table, lengths, qpos,
                                  k_amax, v_amax, cfg=cfg, window=window,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(ora.rounds),
                                  np.asarray(ker.rounds))
    np.testing.assert_array_equal(np.asarray(ora.survivors),
                                  np.asarray(ker.survivors))
    np.testing.assert_array_equal(np.asarray(ora.v_fetched),
                                  np.asarray(ker.v_fetched))
    np.testing.assert_allclose(np.asarray(ora.out), np.asarray(ker.out),
                               atol=1e-6, rtol=1e-6)
    # pages past a query's position/fill are never touched
    rounds = np.asarray(ora.rounds)
    assert (rounds[2, :, 2:] == 0).all()      # row 2 ends mid page 2
    assert (rounds[2, 2] == 0).all()          # padding query: nothing
    assert (rounds[1, 0, 3] == 0)             # null table entry


@pytest.mark.parametrize("window", [None, 16])
def test_verify_kernel_skips_unreachable_pages(window):
    """A Sq=4 draft block with one padding query over a wide table, beside
    an all-padding slot: each slot's page range is the union of its real
    queries' reach, and the kernel matches the oracle bit for bit on plane
    counts, survivors and V fetches while every skipped (slot, page)
    reads zero planes and zero survivors."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(8)
    rng = np.random.default_rng(9)
    bs, Hkv, D = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    kq_pool = qlib.pack_pool_planes(k_pool, k_amax, BITS)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 0, 0, 0, 0, 0]], jnp.int32)
    Sq = 4
    q = jnp.asarray(rng.normal(size=(2, Sq, 2 * Hkv, D)) * 2, jnp.float32)
    qpos = jnp.asarray([[46, 47, 48, 0], [0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([[47, 48, 49, 0], [0, 0, 0, 0]], jnp.int32)
    # Row 0 reaches tokens < 49 (pages 0-3); with the window its queries
    # see from token 31 on, so page 0 lies outside.  Row 1 reaches none.
    want = [(0 if window is None else 1, 4), (0, 0)]
    lo, hi = page_bounds(np.asarray(lengths), np.asarray(qpos), bs, window,
                         xp=np)
    assert list(zip(lo.tolist(), hi.tolist())) == want
    cfg = BitStopperConfig(alpha=0.6)
    ora = besf_attention_verify_paged(q, k_pool, v_pool, table, lengths,
                                      qpos, k_amax, v_amax, cfg=cfg,
                                      window=window)
    ker = paged_bitstopper_verify(q, kq_pool, v_pool, table, lengths, qpos,
                                  k_amax, v_amax, cfg=cfg, window=window,
                                  interpret=True)
    for name in ("rounds", "survivors", "v_fetched"):
        np.testing.assert_array_equal(np.asarray(getattr(ora, name)),
                                      np.asarray(getattr(ker, name)))
    np.testing.assert_allclose(np.asarray(ora.out), np.asarray(ker.out),
                               atol=1e-6, rtol=1e-6)
    rounds = np.asarray(ker.rounds)                       # [B, Sq, MB]
    surv = np.asarray(ker.survivors).reshape(2, Sq, 2 * Hkv, -1, bs)
    for b, (lo_b, hi_b) in enumerate(want):
        skipped = [j for j in range(table.shape[1]) if not lo_b <= j < hi_b]
        assert (rounds[b][:, skipped] == 0).all()
        assert not surv[b][:, :, skipped].any()
        assert not np.asarray(ker.v_fetched)[b][:, skipped].any()
    assert (rounds[0].max(axis=0)[want[0][0]:4] > 0).all()
    assert (rounds[0, 3] == 0).all()                      # padding query


def test_verify_kernel_amortizes_plane_fetches():
    """The fused kernel's union-liveness DMA sharing: per-query rounds
    match the oracle exactly, so the modeled plane traffic of the whole
    draft block (max over queries per page — one fetch serves all) is
    strictly less than the sum of per-query fetches."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(4)
    rng = np.random.default_rng(5)
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    kq_pool = qlib.pack_pool_planes(k_pool, k_amax, BITS)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    Sq = 4
    q = jnp.asarray(rng.normal(size=(1, Sq, Hkv, D)) * 2, jnp.float32)
    qpos = jnp.asarray([[60, 61, 62, 63]], jnp.int32)
    lengths = qpos + 1
    cfg = BitStopperConfig(alpha=0.4)
    ker = paged_bitstopper_verify(q, kq_pool, v_pool, table, lengths, qpos,
                                  k_amax, v_amax, cfg=cfg, interpret=True)
    rounds = np.asarray(ker.rounds)[0]                    # [Sq, MB]
    shared = rounds.max(axis=0).sum()                     # one DMA stream
    separate = rounds.sum()                               # Sq=1 x Sq cost
    assert shared < separate, (shared, separate)


def test_verify_kernel_stats_false_matches():
    k_pool, v_pool, k_amax, v_amax = _pool_state(6)
    rng = np.random.default_rng(7)
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    kq_pool = qlib.pack_pool_planes(k_pool, k_amax, BITS)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 2, Hkv, D)), jnp.float32)
    qpos = jnp.asarray([[40, 41], [20, 0]], jnp.int32)
    lengths = jnp.asarray([[41, 42], [21, 0]], jnp.int32)
    cfg = BitStopperConfig(alpha=0.6)
    a = paged_bitstopper_verify(q, kq_pool, v_pool, table, lengths, qpos,
                                k_amax, v_amax, cfg=cfg, interpret=True,
                                stats=False)
    b = paged_bitstopper_verify(q, kq_pool, v_pool, table, lengths, qpos,
                                k_amax, v_amax, cfg=cfg, interpret=True)
    assert a.survivors is None and a.v_fetched is None
    np.testing.assert_array_equal(np.asarray(a.out), np.asarray(b.out))
    np.testing.assert_array_equal(np.asarray(a.rounds), np.asarray(b.rounds))


# ---------------------------------------------------------------------------
# engine-level losslessness: speculative == non-speculative, bit for bit
# ---------------------------------------------------------------------------


def _serve(cfg, params, reqs, seed=0, drafter=None, **kw):
    eng = PagedEngine(cfg, params, _scfg(**kw), drafter=drafter)
    eng.generate(reqs, seed=seed)
    return eng


@pytest.mark.parametrize("speculative", ["ngram", "draft"])
def test_spec_trace_bitident_greedy(model, speculative):
    """Acceptance: speculative serving is lossless — greedy traces are
    bit-identical to non-speculative serving for both drafters (including
    cold-start scale-growth bailout ticks)."""
    cfg, params = model
    ref = _reqs(cfg, (5, 11, 17))
    _serve(cfg, params, ref)
    spec = _reqs(cfg, (5, 11, 17))
    eng = _serve(cfg, params, spec, speculative=speculative, draft_k=3)
    assert [r.generated for r in spec] == [r.generated for r in ref]
    assert eng.pool.live_blocks() == 0
    assert eng.pool.available() == eng.pool.capacity


@pytest.mark.parametrize("speculative", ["ngram", "draft"])
def test_spec_trace_bitident_sampled(model, speculative):
    """Seeded sampling: draft-block token n draws from the same
    fold_in(fold_in(seed, rid), n) key as non-speculative decode, so
    sampled traces are identical too."""
    cfg, params = model
    ref = _reqs(cfg, (5, 11), max_new=5)
    _serve(cfg, params, ref, seed=7, temperature=1.0)
    spec = _reqs(cfg, (5, 11), max_new=5)
    _serve(cfg, params, spec, seed=7, temperature=1.0,
           speculative=speculative, draft_k=3)
    assert [r.generated for r in spec] == [r.generated for r in ref]


def test_spec_fused_kernel_matches_fallback_and_accepts(model):
    """Self-drafting with the target model (acceptance 1.0 under greedy
    once quant scales warm up): the fused Sq-tiled verify kernel and the
    pure-JAX fallback serve identical tokens, actually accept drafts, and
    finish in fewer ticks than tokens emitted."""
    cfg, params = model
    outs, engines = [], []
    for fused in (True, False):
        eng = PagedEngine(cfg, params,
                          _scfg(speculative="draft", draft_k=3,
                                fused_decode=fused))
        # Warm the pool-wide quant scales so accept ticks dominate.
        _ = eng.generate(_reqs(cfg, (24,), max_new=8, seed=9), seed=0)
        reqs = _reqs(cfg, (5, 11), max_new=8)
        eng.generate(reqs, seed=0)
        outs.append([r.generated for r in reqs])
        engines.append(eng)
    assert outs[0] == outs[1]
    ref = _reqs(cfg, (5, 11), max_new=8)
    warm = PagedEngine(cfg, params, _scfg())
    warm.generate(_reqs(cfg, (24,), max_new=8, seed=9), seed=0)
    warm.generate(ref, seed=0)
    assert outs[0] == [r.generated for r in ref]
    for eng in engines:
        assert eng.counters["spec_accepted"] > 0
        assert eng.counters["spec_accepted"] == eng.counters["spec_proposed"]


def test_spec_with_chunked_prefill_and_shared_prefix(model):
    """Speculation composes with chunked prefill and prefix sharing:
    traces still match the non-speculative engine, prefix blocks still
    hit."""
    cfg, params = model
    sysp = np.random.default_rng(42).integers(0, cfg.vocab, 24,
                                              dtype=np.int32)
    kw = dict(prefill_chunk=8, max_len=96)
    ref = _reqs(cfg, (3, 7, 5), max_new=4, prefix=sysp)
    _serve(cfg, params, ref, **kw)
    spec = _reqs(cfg, (3, 7, 5), max_new=4, prefix=sysp)
    eng = _serve(cfg, params, spec, speculative="ngram", draft_k=4, **kw)
    assert [r.generated for r in spec] == [r.generated for r in ref]
    assert eng.counters["prefix_hit_tokens"] > 0
    assert eng.pool.live_blocks() == 0


def test_spec_snug_recycled_pool(model):
    """A pool snug enough that physical blocks recycle mid-trace: rolled-
    back draft-tail blocks re-enter circulation and must leak no stale KV
    into later requests — traces equal a fresh-pool run bit for bit."""
    cfg, params = model
    kw = dict(max_slots=2, pool_blocks=7, prefix_sharing=False,
              speculative="draft", draft_k=3)
    eng = PagedEngine(cfg, params, _scfg(**kw))
    eng.generate(_reqs(cfg, (12, 9), max_new=4, seed=3), seed=0)
    assert eng.pool.alloc_count >= 4
    reused = _reqs(cfg, (11, 7), max_new=4, seed=4)
    eng.generate(reused, seed=0)

    fresh = _reqs(cfg, (11, 7), max_new=4, seed=4)
    # Non-speculative, fresh pool — but same-engine amax warm-up matters
    # for bit-identity, so replay the same two batches without drafts.
    ref_eng = PagedEngine(cfg, params, _scfg(max_slots=2, pool_blocks=7,
                                             prefix_sharing=False))
    ref_eng.generate(_reqs(cfg, (12, 9), max_new=4, seed=3), seed=0)
    ref_eng.generate(fresh, seed=0)
    assert [r.generated for r in reused] == [r.generated for r in fresh]


def test_spec_eos_truncation(model):
    """EOS inside an accepted draft block truncates the emission exactly
    where non-speculative serving would have stopped."""
    cfg, params = model
    free = _reqs(cfg, (9,), max_new=8, seed=1)
    _serve(cfg, params, free)
    eos = free[0].generated[2]
    ref = _reqs(cfg, (9,), max_new=8, seed=1)
    _serve(cfg, params, ref, eos_id=int(eos))
    spec = _reqs(cfg, (9,), max_new=8, seed=1)
    _serve(cfg, params, spec, eos_id=int(eos), speculative="draft",
           draft_k=4)
    assert spec[0].generated == ref[0].generated == free[0].generated[:3]


# ---------------------------------------------------------------------------
# block-table rollback invariants
# ---------------------------------------------------------------------------


class _GarbageDrafter:
    """Adversarial drafter: always proposes k maximally wrong tokens so
    every tick allocates draft-tail blocks and rolls them all back."""

    def __init__(self, vocab):
        self.vocab = vocab

    def propose(self, context, k):
        return [(int(context[-1]) + 1 + i) % self.vocab for i in range(k)]


def test_spec_rollback_returns_tail_blocks(model):
    """Rejected draft tails: the tick's speculative blocks return to the
    free list with reservations restored (mid-trace the pool never leaks),
    and the served trace is still bit-identical to plain decode."""
    cfg, params = model
    ref = _reqs(cfg, (9, 14), max_new=6)
    _serve(cfg, params, ref)
    spec = _reqs(cfg, (9, 14), max_new=6)
    eng = PagedEngine(
        cfg, params,
        _scfg(speculative="ngram", draft_k=7, max_len=96),
        drafter=_GarbageDrafter(cfg.vocab))
    eng.generate(spec, seed=0)
    assert [r.generated for r in spec] == [r.generated for r in ref]
    # garbage drafts crossed page boundaries: speculative blocks were
    # materialized and rolled back (more allocs than plain serving needs)
    plain = PagedEngine(cfg, params, _scfg(max_len=96))
    plain.generate(_reqs(cfg, (9, 14), max_new=6), seed=0)
    assert eng.counters["spec_proposed"] > eng.counters["spec_accepted"]
    assert eng.pool.alloc_count > plain.pool.alloc_count
    assert eng.pool.live_blocks() == 0
    assert eng.pool.available() == eng.pool.capacity
    assert (eng.table == 0).all()


def test_spec_rollback_never_crosses_shared_prefix(model):
    """Prefix-shared blocks sit below the decode region; rollback frees
    only exclusively-owned draft-tail blocks (kv_pool.rollback enforces
    it), and the shared blocks stay published and resurrectable."""
    cfg, params = model
    sysp = np.random.default_rng(41).integers(0, cfg.vocab, 16,
                                              dtype=np.int32)
    eng = PagedEngine(
        cfg, params, _scfg(speculative="ngram", draft_k=6, max_len=96),
        drafter=_GarbageDrafter(cfg.vocab))
    eng.generate(_reqs(cfg, (4, 6), max_new=5, prefix=sysp), seed=0)
    assert eng.pool.live_blocks() == 0
    # the system-prompt blocks survived every rollback: a follow-up batch
    # still resurrects them from the LRU cache
    second = _reqs(cfg, (5,), max_new=4, seed=5, prefix=sysp)
    eng.generate(second, seed=0)
    assert eng.counters["prefix_hit_tokens"] >= 16


def test_spec_config_validation(model):
    cfg, params = model
    with pytest.raises(ValueError):
        ServeConfig(speculative="mtp")
    with pytest.raises(ValueError):
        ServeConfig(speculative="ngram", draft_k=0)
    with pytest.raises(ValueError):
        # bitstopper speculation needs the pool-wide quant state
        PagedEngine(cfg, params, _scfg(speculative="ngram", page_size=6))
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(cfg, params,
                                 ServeConfig(speculative="ngram"))
    with pytest.raises(ValueError):
        PagedEngine(cfg, params, _scfg(), drafter=NGramDrafter())


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_n=3, min_n=1)
    ctx = np.asarray([7, 1, 2, 3, 9, 1, 2, 3], np.int32)
    # suffix [1,2,3] matched at position 1 -> continuation [9, 1, 2]
    assert d.propose(ctx, 3) == [9, 1, 2]
    assert d.propose(ctx, 1) == [9]
    # no repeat anywhere -> nothing proposed
    assert d.propose(np.arange(10, dtype=np.int32), 4) == []
    # falls back to shorter n-grams
    assert d.propose(np.asarray([5, 9, 5], np.int32), 2) == [9, 5]


def test_draft_model_drafter_greedy(model):
    """Self-draft proposals equal the target's own greedy continuation
    (cache-free forward), for any context length bucket."""
    cfg, params = model
    d = DraftModelDrafter(cfg, params, bucket=8)
    rng = np.random.default_rng(0)
    ctx = rng.integers(0, cfg.vocab, 11, dtype=np.int32)
    got = d.propose(ctx, 3)
    seq = list(ctx)
    for _ in range(3):
        logits, _, _ = T.forward(params, jnp.asarray(seq)[None], cfg)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert got == seq[len(ctx):]
