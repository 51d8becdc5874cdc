"""Fused paged BESF decode: kernel-vs-oracle bit-exactness on adversarial
block tables (shared prefixes, recycled blocks, mid-page fills), parity with
the dense gather path, DMA-level early termination, and the incremental
bit-plane pool's write invariants (rescale-on-demand, free/realloc)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as qlib
from repro.core.besf import (
    BitStopperConfig,
    besf_attention_decode,
    besf_attention_decode_paged,
)
from repro.kernels import paged_decode
from repro.kernels.paged_decode import page_bounds, paged_bitstopper_decode
from repro.models.attention import (
    POS_SENTINEL,
    AttnConfig,
    PagedLayout,
    _update_paged_cache,
    gather_paged_view,
    init_cache,
)

BITS = 12


def _pack_pool(k_pool, k_amax, bits=BITS):
    """One-shot packing of the whole pool (the canonical shared layout).
    The independent check is `_assert_invariant`, which unpacks to bit
    level and compares the *incrementally written* pool against this
    one-shot requant — write-path vs reference, not copy vs copy."""
    return qlib.pack_pool_planes(k_pool, k_amax, bits)


def _unpack_pool(kq, bs, H):
    """Lane-dense uint8[P, bits, bs8*C, H*X] (row b*C + c holds byte group
    b's dims c*X .. c*X + X-1 of every head, head-major lanes) -> bit
    planes uint8[bits, P, bs, H, D]."""
    P, bits, R, L = kq.shape
    bs8 = bs // 8
    C, X = R // bs8, L // H
    D = C * X
    kq = kq.reshape(P, bits, bs8, C, H, X).transpose(0, 1, 2, 4, 3, 5)
    kq = kq.reshape(P, bits, bs8, H, D)
    shifts = jnp.arange(8, dtype=jnp.uint32).reshape(1, 1, 1, 8, 1, 1)
    u = (kq.astype(jnp.uint32)[:, :, :, None] >> shifts) & 1
    return u.reshape(P, bits, bs8 * 8, H, D).astype(jnp.uint8).transpose(
        1, 0, 2, 3, 4)


def _pool_state(seed, P=9, bs=16, Hkv=2, D=16, Dv=16, spiky=False):
    rng = np.random.default_rng(seed)
    k_pool = rng.normal(size=(P, bs, Hkv, D)) * 2
    v_pool = rng.normal(size=(P, bs, Hkv, Dv))
    if spiky:
        u = rng.normal(size=D)
        u /= np.linalg.norm(u)
        k_pool *= 0.02
        k_pool[1, :, :, :] += 8.0 * u            # hot page: physical block 1
    k_pool = jnp.asarray(k_pool, jnp.float32)
    v_pool = jnp.asarray(v_pool, jnp.float32)
    k_amax = jnp.max(jnp.abs(k_pool), axis=(0, 1, 3))
    v_amax = jnp.max(jnp.abs(v_pool), axis=(0, 1, 3))
    return k_pool, v_pool, k_amax, v_amax


# ---------------------------------------------------------------------------
# kernel (interpret mode) vs pure-JAX paged oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alpha,window,G", [
    (0.2, None, 1),
    (0.6, None, 2),
    (0.8, 24, 2),
    (1.0, 16, 1),
])
def test_paged_kernel_matches_oracle(alpha, window, G):
    """Bit-exact parity on a pool with a shared-prefix block (physical
    block 1 mapped by two tables), recycled/stale blocks (7, 8 hold
    garbage from a 'finished request', unreferenced), and rows ending
    mid-page."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(0)
    # Stale garbage in unreferenced blocks must be unobservable even
    # though it is LARGER than the pool amax (recycled after requant).
    k_pool = k_pool.at[8].set(50.0)
    rng = np.random.default_rng(1)
    Hkv = k_pool.shape[2]
    Hq = Hkv * G
    table = jnp.asarray([[1, 2, 3, 4], [1, 5, 6, 0], [7, 3, 0, 0]],
                        jnp.int32)
    lengths = jnp.asarray([64, 40, 19], jnp.int32)      # row 2 mid-page
    q_pos = lengths - 1
    q = jnp.asarray(rng.normal(size=(3, Hq, k_pool.shape[-1])) * 2,
                    jnp.float32)
    cfg = BitStopperConfig(alpha=alpha)
    kq_pool = _pack_pool(k_pool, k_amax)

    ora = besf_attention_decode_paged(q, k_pool, v_pool, table, lengths,
                                      q_pos, k_amax, v_amax, cfg=cfg,
                                      window=window)
    ker = paged_bitstopper_decode(q, kq_pool, v_pool, table, lengths,
                                  q_pos, k_amax, v_amax, cfg=cfg,
                                  window=window, interpret=True)
    np.testing.assert_array_equal(np.asarray(ora.rounds),
                                  np.asarray(ker.rounds))
    np.testing.assert_array_equal(np.asarray(ora.survivors),
                                  np.asarray(ker.survivors))
    np.testing.assert_array_equal(np.asarray(ora.v_fetched),
                                  np.asarray(ker.v_fetched))
    np.testing.assert_allclose(np.asarray(ora.out), np.asarray(ker.out),
                               atol=1e-6, rtol=1e-6)
    # pages past a row's fill level are never touched: zero planes fetched
    rounds = np.asarray(ora.rounds)
    assert rounds[1, 3] == 0 and (rounds[2, 2:] == 0).all()


# (block tables, fill levels, window, the hand-counted page range [lo, hi)
# of each slot): grid steps outside a slot's range are skipped whole.
SKIP_CASES = {
    "empty_slot": ([[1, 2, 3, 4], [5, 6, 0, 0], [0, 0, 0, 0]],
                   [64, 20, 0], None, [(0, 4), (0, 2), (0, 0)]),
    "first_page_of_wide_table": ([[1, 0, 0, 0, 0, 0, 0, 0],
                                  [2, 3, 4, 5, 6, 7, 0, 0]],
                                 [5, 90], None, [(0, 1), (0, 6)]),
    # row 0 at position 79 sees tokens 60..79: pages 0-2 lie outside.
    "windowed": ([[1, 2, 3, 4, 5], [6, 7, 0, 0, 0]],
                 [80, 30], 20, [(3, 5), (0, 2)]),
}


def _walk_every_page(monkeypatch):
    """Give every slot the page range [0, MB): the kernel then runs the
    whole per-page body on every grid step, reached or not."""
    def every_page(lengths, *_, **__):
        B = lengths.shape[0]
        return jnp.zeros((B,), jnp.int32), jnp.full((B,), 1 << 20, jnp.int32)

    monkeypatch.setattr(paged_decode, "page_bounds", every_page)


@pytest.mark.parametrize("case", sorted(SKIP_CASES))
def test_paged_kernel_skips_unreachable_pages(case, monkeypatch):
    """Grid steps that no query of their slot can reach run nothing, and
    change nothing: an empty slot, a row on the first page of a wide
    table, a window past a row's first pages.  Plane counts, survivors
    and V fetches match the oracle bit for bit, every output matches the
    kernel that walks every page bit for bit, and every skipped (slot,
    page) reads zero planes and zero survivors."""
    table, lengths, window, want = SKIP_CASES[case]
    k_pool, v_pool, k_amax, v_amax = _pool_state(6)
    bs = k_pool.shape[1]
    Hq, D = 2 * k_pool.shape[2], k_pool.shape[3]
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q_pos = jnp.maximum(lengths - 1, 0)
    lo, hi = page_bounds(np.asarray(lengths)[:, None],
                         np.asarray(q_pos)[:, None], bs, window, xp=np)
    assert list(zip(lo.tolist(), hi.tolist())) == want

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(table.shape[0], Hq, D)) * 2,
                    jnp.float32)
    cfg = BitStopperConfig(alpha=0.6)
    ora = besf_attention_decode_paged(q, k_pool, v_pool, table, lengths,
                                      q_pos, k_amax, v_amax, cfg=cfg,
                                      window=window)
    kq_pool = _pack_pool(k_pool, k_amax)
    ker = paged_bitstopper_decode(q, kq_pool, v_pool, table, lengths,
                                  q_pos, k_amax, v_amax, cfg=cfg,
                                  window=window, interpret=True)
    for name in ("rounds", "survivors", "v_fetched"):
        np.testing.assert_array_equal(np.asarray(getattr(ora, name)),
                                      np.asarray(getattr(ker, name)))
    np.testing.assert_allclose(np.asarray(ora.out), np.asarray(ker.out),
                               atol=1e-6, rtol=1e-6)
    _walk_every_page(monkeypatch)
    out, rounds, surv = paged_decode.paged_besf_pallas(
        q[:, None], kq_pool, v_pool, table, lengths[:, None],
        q_pos[:, None], k_amax, v_amax, cfg=cfg, window=window,
        interpret=True, stats=True)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(ker.out))
    np.testing.assert_array_equal(np.asarray(rounds[:, 0]),
                                  np.asarray(ker.rounds))
    np.testing.assert_array_equal(
        np.asarray(jnp.moveaxis(surv, 1, 2).reshape(ker.survivors.shape)),
        np.asarray(ker.survivors))
    rounds = np.asarray(ker.rounds)
    surv = np.asarray(ker.survivors).reshape(table.shape[0], Hq, -1, bs)
    for b, (lo_b, hi_b) in enumerate(want):
        skipped = [j for j in range(table.shape[1]) if not lo_b <= j < hi_b]
        assert (rounds[b, skipped] == 0).all()
        assert not surv[b, :, skipped].any()
        assert not np.asarray(ker.v_fetched)[b, skipped].any()
        assert (rounds[b, lo_b:hi_b] > 0).all()


def test_paged_oracle_matches_dense_gather_path():
    """Against the retained dense gather path (`besf_attention_decode` on
    the gathered logical view): with a single row the pool-wide scale
    equals the per-row view scale, so the ONLY semantic difference left is
    LATS granularity — page-sequential prefix-max thresholds keep a
    superset of the global per-round reference's survivors, and the extra
    tokens carry provably negligible softmax mass."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(2)
    bs = k_pool.shape[1]
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    lengths = jnp.asarray([3 * bs], jnp.int32)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, Hkv, D)), jnp.float32)
    # pool amax must equal the row's view amax for scale identity
    view = k_pool[table[0]].reshape(3 * bs, Hkv, D)
    k_amax = jnp.max(jnp.abs(view), axis=(0, 2))
    v_view = v_pool[table[0]].reshape(3 * bs, Hkv, D)
    v_amax = jnp.max(jnp.abs(v_view), axis=(0, 2))

    cfg = BitStopperConfig(alpha=1.0)
    paged = besf_attention_decode_paged(
        q, k_pool, v_pool, table, lengths, lengths - 1, k_amax, v_amax,
        cfg=cfg)
    # dense gather reference: head-major repeated-KV layout, per-(row,
    # head) view quantization — exactly what _cached_attention dispatches
    kr = view.transpose(1, 0, 2)[None]                  # [1, Hkv, Tv, D]
    vr = v_view.transpose(1, 0, 2)[None]
    ref = besf_attention_decode(q[:, :, None, :], kr, vr, cfg=cfg)
    # paged survivors must be a superset of the reference's (prefix-max
    # thresholds are conservative — they only ever keep MORE)
    s_paged = np.asarray(paged.survivors)[0]            # [Hq, Tv]
    s_ref = np.asarray(ref.stats.survivors)[0, :, 0]    # [Hq, Tv]
    assert (s_paged | ~s_ref.astype(bool)).all()
    # outputs agree up to the LATS guarantee: any survivor-set slack
    # carries softmax mass < e^{-alpha*radius} per token (~6.7e-3 here)
    np.testing.assert_allclose(np.asarray(paged.out)[0],
                               np.asarray(ref.out)[0, :, 0], atol=0.05)


def test_paged_decode_early_termination_skips_planes_and_v():
    """Spiky attention: one hot page dominates, so cold pages terminate
    after a few planes and their V is never fetched — the fused path's
    per-step traffic drops below the dense 12-plane/page floor."""
    k_pool, v_pool, k_amax, v_amax = _pool_state(4, spiky=True)
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    table = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    lengths = jnp.asarray([5 * k_pool.shape[1]], jnp.int32)
    rng = np.random.default_rng(5)
    u = np.asarray(k_pool[1, 0, 0] / jnp.linalg.norm(k_pool[1, 0, 0]))
    q = jnp.asarray(8.0 * u[None, None]
                    + 0.05 * rng.normal(size=(1, Hkv, D)), jnp.float32)
    cfg = BitStopperConfig(alpha=0.4)
    kq_pool = _pack_pool(k_pool, k_amax)
    ker = paged_bitstopper_decode(q, kq_pool, v_pool, table, lengths,
                                  lengths - 1, k_amax, v_amax, cfg=cfg,
                                  interpret=True)
    ora = besf_attention_decode_paged(q, k_pool, v_pool, table, lengths,
                                      lengths - 1, k_amax, v_amax, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(ora.rounds),
                                  np.asarray(ker.rounds))
    rounds = np.asarray(ker.rounds)[0]
    vf = np.asarray(ker.v_fetched)[0]
    assert rounds[0] == cfg.bits and vf[0]              # hot page completes
    assert rounds.sum() < cfg.bits * len(rounds), rounds
    assert not vf.all(), vf                             # some V never moved


# ---------------------------------------------------------------------------
# incremental bit-plane pool: write path invariants
# ---------------------------------------------------------------------------


def _acfg(Hkv=2, D=8):
    # fused_decode=True: the packed plane pool is only maintained when the
    # fused kernel will read it (fallback decode keeps scales only).
    return AttnConfig(d_model=Hkv * D, n_heads=Hkv, n_kv_heads=Hkv,
                      head_dim=D, impl="bitstopper_xla", fused_decode=True)


def _write(cache, k, v, positions):
    return _update_paged_cache(cache, jnp.asarray(k, jnp.float32),
                               jnp.asarray(v, jnp.float32),
                               jnp.asarray(positions, jnp.int32))


def _assert_invariant(cache):
    """Planes stored in kq must equal requantizing the f32 pool under the
    current running scale, for every slot written through any table row."""
    nb, bs = cache["pos"].shape
    H = cache["k_amax"].shape[0]
    table = np.asarray(cache["table"])
    length = np.asarray(cache["length"])
    live = np.zeros((nb, bs), bool)
    for b in range(table.shape[0]):
        for j in range(table.shape[1]):
            n = int(np.clip(length[b] - j * bs, 0, bs))
            if table[b, j] > 0 and n > 0:
                live[table[b, j], :n] = True
    got = np.asarray(_unpack_pool(cache["kq"], bs, H))  # [bits, P, bs, H, D]
    want = np.asarray(_unpack_pool(_pack_pool(cache["k"], cache["k_amax"]),
                                   bs, H))
    mask = live[None, :, :, None, None]
    np.testing.assert_array_equal(got * mask, want * mask)


def test_plane_pool_incremental_writes_and_rescale():
    """Appends keep the packed pool consistent with the f32 pool; a write
    that grows the running max-abs triggers the requant path and the
    invariant still holds (including previously written tokens)."""
    cfg = _acfg()
    cache = init_cache(cfg, batch=2, max_len=64, paged=PagedLayout(6, 8, 3))
    # page 8: one byte group cut into 8 rows of 2 lanes (1 dim x 2 heads)
    assert "kq" in cache and cache["kq"].shape == (6, 12, 8, 2)
    cache = dict(cache, table=jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32))
    rng = np.random.default_rng(0)

    def toks(B, S, scale=1.0):
        return (rng.normal(size=(B, S, 2, 8)) * scale,
                rng.normal(size=(B, S, 2, 8)) * scale)

    # row 0 writes 5 tokens (mid-page), row 1 idles at the sentinel
    k, v = toks(2, 5)
    pos = np.stack([np.arange(5), np.full(5, POS_SENTINEL)])
    cache = _write(cache, k, v, pos)
    assert cache["length"].tolist() == [5, 0]
    _assert_invariant(cache)
    amax0 = np.asarray(cache["k_amax"]).copy()

    # append 6 more to row 0 (crosses a page boundary), 3 to row 1
    k, v = toks(2, 6, scale=0.5)                        # no amax growth
    pos = np.stack([np.arange(5, 11),
                    np.concatenate([np.arange(3), [POS_SENTINEL] * 3])])
    cache = _write(cache, k, v, pos)
    assert cache["length"].tolist() == [11, 3]
    np.testing.assert_array_equal(np.asarray(cache["k_amax"]), amax0)
    _assert_invariant(cache)

    # a loud token grows the scale -> whole-pool requant, old tokens too
    k, v = toks(2, 1, scale=20.0)
    pos = np.asarray([[11], [POS_SENTINEL]])
    cache = _write(cache, k, v, pos)
    assert (np.asarray(cache["k_amax"]) > amax0).any()
    _assert_invariant(cache)


def test_plane_pool_survives_free_and_realloc():
    """A physical block freed by one request and reallocated to another
    must serve the NEW owner's planes: the write path fully overwrites the
    recycled page (low-mask merge starts at bit 0), and the paged decode
    of the new owner matches a pool that never saw the old content."""
    cfg = _acfg()
    layout = PagedLayout(4, 8, 2)
    cache = init_cache(cfg, batch=1, max_len=32, paged=layout)
    rng = np.random.default_rng(1)

    # request A fills physical blocks 1-2 through its table
    cache_a = dict(cache, table=jnp.asarray([[1, 2]], jnp.int32))
    kA = rng.normal(size=(1, 12, 2, 8))
    vA = rng.normal(size=(1, 12, 2, 8))
    cache_a = _write(cache_a, kA, vA, np.arange(12)[None])
    _assert_invariant(cache_a)

    # A finishes; B is admitted onto the SAME physical blocks (recycled),
    # with content quieter than A's (running amax must not shrink).
    cache_b = dict(cache_a, table=jnp.asarray([[2, 1]], jnp.int32),
                   length=jnp.zeros((1,), jnp.int32))
    kB = rng.normal(size=(1, 10, 2, 8)) * 0.5
    vB = rng.normal(size=(1, 10, 2, 8)) * 0.5
    cache_b = _write(cache_b, kB, vB, np.arange(10)[None])
    _assert_invariant(cache_b)

    # decode for B through the recycled pool == decode through a pristine
    # pool holding only B's content under the same running scales
    fresh = dict(init_cache(cfg, batch=1, max_len=32, paged=layout),
                 table=jnp.asarray([[2, 1]], jnp.int32),
                 k_amax=cache_a["k_amax"], v_amax=cache_a["v_amax"])
    fresh = _write(fresh, kB, vB, np.arange(10)[None])
    q = jnp.asarray(rng.normal(size=(1, 2, 8)), jnp.float32)
    args = (cache_b["table"], cache_b["length"], cache_b["length"] - 1,
            cache_b["k_amax"], cache_b["v_amax"])
    out_b = paged_bitstopper_decode(q, cache_b["kq"], cache_b["v"], *args,
                                    interpret=True)
    out_f = paged_bitstopper_decode(q, fresh["kq"], fresh["v"], *args)
    np.testing.assert_array_equal(np.asarray(out_b.out),
                                  np.asarray(out_f.out))
    np.testing.assert_array_equal(np.asarray(out_b.survivors),
                                  np.asarray(out_f.survivors))


def test_gather_view_gated_to_active_rows():
    """The on-demand gather masks inactive rows to the null block — their
    view is all-invalid — while active rows see exactly the old dense
    view semantics (zeroed past the fill level)."""
    cfg = _acfg()
    cache = init_cache(cfg, batch=2, max_len=32, paged=PagedLayout(4, 8, 2))
    cache = dict(cache, table=jnp.asarray([[1, 2], [3, 0]], jnp.int32))
    rng = np.random.default_rng(2)
    k = rng.normal(size=(2, 5, 2, 8))
    v = rng.normal(size=(2, 5, 2, 8))
    pos = np.stack([np.arange(5), np.arange(5)])
    cache = _write(cache, k, v, pos)

    kv_all = gather_paged_view(cache)
    kv_act = gather_paged_view(cache, jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(kv_all[0][0]),
                                  np.asarray(kv_act[0][0]))
    assert (np.asarray(kv_act[2][1]) == POS_SENTINEL).all()
    # fill-level masking: row 0 slots past length are zero
    assert (np.asarray(kv_all[0][0][5:]) == 0).all()
