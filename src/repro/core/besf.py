"""Bit-serial Enabled Stage Fusion (BESF) — the paper's core algorithm.

Faithful, per-token reference implementation (paper Fig. 5 + Section III).
Keys are INT12-quantized and consumed one bit plane at a time (MSB first).
After each round the LATS rule prunes candidates whose score interval can no
longer reach the adaptive threshold; pruned candidates stop fetching planes
(early termination).  Survivors of all rounds carry their *exact* INT12
scores — the prediction work IS the execution work (stage fusion) — and the
final output is softmax over survivors times V.

Integer partial scores are accumulated in int32 (exact: |A| <= 2048*2048*d),
so the interval property  lower <= exact <= upper  holds bit-for-bit; this is
what the hypothesis property tests check.

Complexity accounting (planes fetched per (i, j) pair, survivor counts) is
returned in a :class:`BESFStats` so benchmarks can derive traffic/compute
numbers without re-running the algorithm.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import margins as margins_lib
from repro.core import quantization as qlib
from repro.core.lats import NEG_INF, lats_keep, lats_threshold


@dataclasses.dataclass(frozen=True)
class BitStopperConfig:
    """Algorithm hyper-parameters (paper defaults)."""

    bits: int = 12
    alpha: float = 0.6
    radius: float = 5.0
    quantize_v: bool = True     # paper: S x V at 12-bit
    min_rounds: int = 1         # never prune before this many planes are seen

    def replace(self, **kw) -> "BitStopperConfig":
        return dataclasses.replace(self, **kw)


class BESFStats(NamedTuple):
    planes_fetched: jax.Array   # [Sq, Sk] int32 — bit planes consumed per pair
    survivors: jax.Array        # [Sq, Sk] bool  — alive after the last round
    valid: jax.Array            # [Sq, Sk] bool  — attention-mask validity


class BESFOutput(NamedTuple):
    out: jax.Array              # [Sq, dv]
    probs: jax.Array            # [Sq, Sk] — softmax over survivors (0 for pruned)
    scores: jax.Array           # [Sq, Sk] — final logits (NEG_INF for pruned)
    stats: BESFStats


def _besf_single(
    q: jax.Array,               # [Sq, d] float
    k: jax.Array,               # [Sk, d] float
    v: jax.Array,               # [Sk, dv] float
    mask: jax.Array | None,     # [Sq, Sk] bool or None
    cfg: BitStopperConfig,
) -> BESFOutput:
    Sq, d = q.shape
    Sk = k.shape[0]
    bits = cfg.bits
    sm_scale = 1.0 / (d ** 0.5)

    q_int, q_params = qlib.quantize(q, bits)
    k_int, k_params = qlib.quantize(k, bits)
    planes = qlib.to_bitplanes(k_int, bits)                     # [bits, Sk, d]
    w = (2 ** jnp.arange(bits - 1, -1, -1)).astype(jnp.int32)
    w = w * jnp.where(jnp.arange(bits) == 0, -1, 1)             # [bits]

    # Bit Margin Generator: [bits, Sq] margin pairs (int domain).
    m_min, m_max = margins_lib.bit_margins(q_int, bits)

    # alpha*radius expressed in the integer score domain.
    scale_total = q_params.scale * k_params.scale * sm_scale
    radius_int = cfg.radius / scale_total

    valid = jnp.ones((Sq, Sk), bool) if mask is None else mask.astype(bool)

    # Per-plane integer contributions: delta[r] = w_r * (q_int @ plane_r^T).
    # (Computed densely here for clarity; "fetch" accounting below records
    # what the accelerator would actually have loaded/computed.)
    def plane_score(r):
        return w[r] * (q_int @ planes[r].T.astype(jnp.int32))   # [Sq, Sk] int32

    def round_body(carry, r):
        partial, alive, fetched = carry
        # Every candidate alive entering round r fetches/computes plane r.
        fetched = fetched + alive.astype(jnp.int32)
        delta = plane_score(r)
        partial = partial + jnp.where(alive, delta, 0)

        lower = partial.astype(jnp.float32) + m_min[r][:, None]
        upper = partial.astype(jnp.float32) + m_max[r][:, None]
        eta = lats_threshold(lower, alive, cfg.alpha, radius_int)
        keep = lats_keep(upper, eta, alive)
        keep = jnp.where(r < cfg.min_rounds - 1, alive, keep)
        return (partial, keep, fetched), None

    partial0 = jnp.zeros((Sq, Sk), jnp.int32)
    fetched0 = jnp.zeros((Sq, Sk), jnp.int32)
    (partial, alive, fetched), _ = jax.lax.scan(
        round_body, (partial0, valid, fetched0), jnp.arange(bits)
    )

    # Formal stage epilogue: exact scores for survivors, softmax, S x V.
    logits = jnp.where(alive, partial.astype(jnp.float32) * scale_total, NEG_INF)
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(alive & valid, probs, 0.0)

    if cfg.quantize_v:
        v_int, v_params = qlib.quantize(v, bits)
        v_eff = qlib.dequantize(v_int, v_params)
    else:
        v_eff = v
    out = probs @ v_eff

    return BESFOutput(
        out=out,
        probs=probs,
        scores=logits,
        stats=BESFStats(planes_fetched=fetched, survivors=alive, valid=valid),
    )


def _besf_decode_single(
    q: jax.Array,               # [1, d] float — single decode query
    k: jax.Array,               # [Sk, d]
    v: jax.Array,               # [Sk, dv]
    mask: jax.Array | None,     # [1, Sk] bool or None
    cfg: BitStopperConfig,
) -> BESFOutput:
    """Sq=1 fast path: identical results to :func:`_besf_single`, different
    schedule.

    The reference issues one int matmul per bit round inside the LATS scan —
    the right shape for prefill, but at decode (one query) each round is a
    tiny matvec and the per-round setup dominates.  Here ALL plane
    contributions are computed in one fused integer contraction up front
    ([bits, Sk, d] x [d] -> [bits, Sk]) and prefix-summed; the remaining
    per-round scan is pure elementwise threshold logic.

    Bit-exactness vs the reference: a candidate alive at round r has, by
    definition, accumulated every plane 0..r — so its gated partial equals
    the ungated prefix sum.  Pruned candidates' partials diverge, but they
    contribute neither to eta (masked by `alive`) nor to the output
    (NEG_INF logits), so every observable — survivors, planes_fetched,
    scores, probs, out — matches the reference bit for bit.
    """
    _, d = q.shape
    Sk = k.shape[0]
    bits = cfg.bits
    sm_scale = 1.0 / (d ** 0.5)

    q_int, q_params = qlib.quantize(q, bits)
    k_int, k_params = qlib.quantize(k, bits)
    planes = qlib.to_bitplanes(k_int, bits)                     # [bits, Sk, d]
    w = (2 ** jnp.arange(bits - 1, -1, -1)).astype(jnp.int32)
    w = w * jnp.where(jnp.arange(bits) == 0, -1, 1)

    m_min, m_max = margins_lib.bit_margins(q_int, bits)         # [bits, 1]

    scale_total = q_params.scale * k_params.scale * sm_scale
    radius_int = cfg.radius / scale_total

    valid = jnp.ones((1, Sk), bool) if mask is None else mask.astype(bool)

    # One fused plane contraction + prefix sum replaces bits separate
    # matvecs: deltas[r] = w_r * (q_int @ plane_r^T), partials[r] = sum<=r.
    deltas = w[:, None, None] * jnp.einsum(
        "rkd,qd->rqk", planes.astype(jnp.int32), q_int)         # [bits, 1, Sk]
    partials = jnp.cumsum(deltas, axis=0)

    def round_body(carry, inp):
        alive, fetched = carry
        part, mn, mx, r = inp
        fetched = fetched + alive.astype(jnp.int32)
        lower = part.astype(jnp.float32) + mn[:, None]
        upper = part.astype(jnp.float32) + mx[:, None]
        eta = lats_threshold(lower, alive, cfg.alpha, radius_int)
        keep = lats_keep(upper, eta, alive)
        keep = jnp.where(r < cfg.min_rounds - 1, alive, keep)
        return (keep, fetched), None

    fetched0 = jnp.zeros((1, Sk), jnp.int32)
    (alive, fetched), _ = jax.lax.scan(
        round_body, (valid, fetched0),
        (partials, m_min, m_max, jnp.arange(bits)))

    final = partials[-1]
    logits = jnp.where(alive, final.astype(jnp.float32) * scale_total, NEG_INF)
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(alive & valid, probs, 0.0)

    if cfg.quantize_v:
        v_int, v_params = qlib.quantize(v, bits)
        v_eff = qlib.dequantize(v_int, v_params)
    else:
        v_eff = v
    out = probs @ v_eff

    return BESFOutput(
        out=out,
        probs=probs,
        scores=logits,
        stats=BESFStats(planes_fetched=fetched, survivors=alive, valid=valid),
    )


@partial(jax.jit, static_argnames=("cfg",))
def besf_attention_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: BitStopperConfig = BitStopperConfig(),
    mask: jax.Array | None = None,
) -> BESFOutput:
    """Decode-specialized BitStopper attention (Sq == 1 per leading index).

    q [..., 1, d], k [..., Sk, d], v [..., Sk, dv]; ``mask`` broadcastable
    to q.shape[:-2] + (1, Sk) — per-example masks (e.g. per serving slot)
    are supported, unlike the prefill entry point.
    """
    assert q.shape[-2] == 1, "decode path is single-query; use besf_attention"
    Sk = k.shape[-2]

    if q.ndim == 2:
        return _besf_decode_single(q, k, v, mask, cfg)

    flat_q = q.reshape((-1,) + q.shape[-2:])
    flat_k = k.reshape((-1,) + k.shape[-2:])
    flat_v = v.reshape((-1,) + v.shape[-2:])
    if mask is not None:
        flat_m = jnp.broadcast_to(mask, q.shape[:-2] + (1, Sk))
        flat_m = flat_m.reshape((-1, 1, Sk))
        res = jax.vmap(lambda a, b, c, m: _besf_decode_single(a, b, c, m, cfg))(
            flat_q, flat_k, flat_v, flat_m
        )
    else:
        res = jax.vmap(lambda a, b, c: _besf_decode_single(a, b, c, None, cfg))(
            flat_q, flat_k, flat_v
        )
    shape = q.shape[:-2]
    return jax.tree_util.tree_map(
        lambda x: x.reshape(shape + x.shape[1:]), res)


class PagedDecodeOutput(NamedTuple):
    out: jax.Array          # [B, Hq, dv] attention output
    rounds: jax.Array       # [B, n_blocks] int32 — bit planes fetched per page
    survivors: jax.Array    # [B, Hq, n_blocks*page_size] bool
    v_fetched: jax.Array    # [B, n_blocks] bool — V page actually read


def paged_decode_prep(q, k_amax, v_amax, n_kv_heads: int,
                      cfg: BitStopperConfig):
    """Shared host-side prep of the paged decode paths (oracle AND kernel —
    both must see bit-identical operands).

    q [B, Hq, d] (one decode query per serving slot, head-major);
    ``k_amax``/``v_amax`` [Hkv] are the pool-wide running max-abs per KV
    head maintained by the cache write path.  Returns
    ``(q_int, m_min, m_max, scale_total, alpha_radius, k_scale, v_scale)``
    with per-(slot, head) q quantization — identical to the dense decode
    path — but K/V scales shared by every slot, which is what makes one
    physical bit-plane pool valid under every block table."""
    B, Hq, d = q.shape
    bits = cfg.bits
    sm_scale = 1.0 / (d ** 0.5)
    G = Hq // n_kv_heads
    flat = q.reshape(B * Hq, d)
    q_scale = qlib.scale_from_amax(jnp.max(jnp.abs(flat), axis=1), bits)
    q_int = qlib.quantize_with_scale(flat, q_scale[:, None], bits)
    q_int = q_int.reshape(B, Hq, d)
    m_min, m_max = margins_lib.bit_margins(q_int, bits)       # [bits, B, Hq]
    k_scale = qlib.scale_from_amax(k_amax, bits)              # [Hkv]
    v_scale = qlib.scale_from_amax(v_amax, bits)
    k_scale_h = jnp.repeat(k_scale, G)                        # [Hq]
    scale_total = q_scale.reshape(B, Hq) * k_scale_h[None] * sm_scale
    alpha_radius = cfg.alpha * (cfg.radius / scale_total)
    return q_int, m_min, m_max, scale_total, alpha_radius, k_scale, v_scale


def _paged_decode_row(
    q_int,                  # [Hq, d] int32
    m_min, m_max,           # [bits, Hq] f32
    scale_total,            # [Hq] f32
    alpha_radius,           # [Hq] f32
    table,                  # [MB] int32 — logical block -> physical block
    length,                 # int32 — row fill level (tokens cached)
    q_pos,                  # int32 — absolute position of the query
    k_pool,                 # [P, bs, Hkv, d] or lane-dense [P, bs, Hkv*d]
    v_pool,                 # [P, bs, Hkv, dv] or lane-dense [P, bs, Hkv*dv]
    k_scale, v_scale,       # [Hkv] f32
    cfg: BitStopperConfig,
    window: int | None,
):
    """One slot's paged BESF decode — the semantic model of the fused
    kernel, walked in the exact same order so every observable matches.

    Pages are processed sequentially (logical block order).  LATS uses the
    **prefix max lower bound** across the pages seen so far (same
    conservative superset as the prefill kernel, ``block_adaptation.py``);
    a page whose every (head, token) candidate is pruned stops consuming
    planes, and its V page is counted un-fetched unless a token survives
    all rounds.  The softmax is the flash-style online rescale in page
    order — mirroring the kernel's epilogue op for op."""
    Hq, d = q_int.shape
    bs = v_pool.shape[1]
    Hkv = k_scale.shape[0]
    dv = math.prod(v_pool.shape[2:]) // Hkv
    MB = table.shape[0]
    bits = cfg.bits
    G = Hq // Hkv

    w = (2 ** jnp.arange(bits - 1, -1, -1)).astype(jnp.int32)
    w = w * jnp.where(jnp.arange(bits) == 0, -1, 1)
    qg = q_int.reshape(Hkv, G, d)

    def block_body(carry, j):
        t_pos = j * bs + jnp.arange(bs, dtype=jnp.int32)
        valid = (t_pos <= q_pos) & (t_pos < length)
        if window is not None:
            valid &= t_pos > q_pos - window
        # Runtime page gate (the oracle-side analogue of the kernel's
        # "no DMA past the fill level"): a page with no valid token costs
        # nothing — lax.cond stays a real branch because rows are mapped
        # sequentially (lax.map), not vmapped into a select.
        return jax.lax.cond(jnp.any(valid), _live_page, _dead_page,
                            carry, j, valid)

    def _dead_page(carry, j, valid):
        return carry, (jnp.zeros((), jnp.int32),
                       jnp.zeros((Hq, bs), bool), jnp.zeros((), bool))

    def _live_page(carry, j, valid):
        mlow, m_run, l_run, acc = carry
        phys = table[j]
        k_int = qlib.quantize_with_scale(
            k_pool[phys].reshape(bs, Hkv, d), k_scale[None, :, None],
            bits)                                             # [bs, Hkv, d]
        planes = qlib.to_bitplanes(k_int, bits)               # [bits,bs,Hkv,d]
        valid_b = jnp.broadcast_to(valid[None], (Hq, bs))

        def round_body(rc, r):
            partial, tok_alive, blk_live, rounds, mlow_in = rc
            rounds = rounds + blk_live.astype(jnp.int32)
            delta = w[r] * jnp.einsum(
                "kgd,tkd->kgt", qg, planes[r].astype(jnp.int32)
            ).reshape(Hq, bs)
            partial = jnp.where(blk_live, partial + delta, partial)
            lower = partial.astype(jnp.float32) + m_min[r][:, None]
            upper = partial.astype(jnp.float32) + m_max[r][:, None]
            low_here = jnp.max(
                jnp.where(valid_b & tok_alive, lower, NEG_INF), axis=-1)
            mlow_new = jnp.where(blk_live, jnp.maximum(mlow_in, low_here),
                                 mlow_in)
            eta = mlow_new - alpha_radius
            keep = tok_alive & (upper >= eta[:, None]) & valid_b
            keep = jnp.where(r < cfg.min_rounds - 1, tok_alive & valid_b,
                             keep)
            keep = jnp.where(blk_live, keep, tok_alive)
            blk_new = jnp.where(blk_live, jnp.any(keep), blk_live)
            return (partial, keep, blk_new, rounds, mlow_new), None

        init = (jnp.zeros((Hq, bs), jnp.int32), valid_b, jnp.any(valid),
                jnp.zeros((), jnp.int32), mlow)
        (partial, tok_alive, _, rounds, mlow), _ = jax.lax.scan(
            round_body, init, jnp.arange(bits))

        survived = tok_alive & (rounds == bits)
        any_surv = jnp.any(survived)
        logits = jnp.where(
            survived, partial.astype(jnp.float32) * scale_total[:, None],
            NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
        p = jnp.where(survived, jnp.exp(logits - m_new[:, None]), 0.0)
        corr = jnp.where(m_run == NEG_INF, 0.0, jnp.exp(m_run - m_new))
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        vblk = v_pool[phys].reshape(bs, Hkv, dv)
        if cfg.quantize_v:
            v_eff = (qlib.quantize_with_scale(
                vblk, v_scale[None, :, None], bits).astype(jnp.float32)
                * v_scale[None, :, None])
        else:
            v_eff = vblk.astype(jnp.float32)
        upd = jnp.einsum("kgt,tkd->kgd", p.reshape(Hkv, G, bs), v_eff)
        acc_new = acc * corr[:, None] + upd.reshape(Hq, dv)
        # The kernel's whole epilogue (including the V DMA) is predicated
        # on any_surv; a page with no survivor leaves the state untouched.
        m_run = jnp.where(any_surv, m_new, m_run)
        l_run = jnp.where(any_surv, l_new, l_run)
        acc = jnp.where(any_surv, acc_new, acc)
        return (mlow, m_run, l_run, acc), (rounds, survived, any_surv)

    init = (
        jnp.full((Hq,), NEG_INF, jnp.float32),
        jnp.full((Hq,), NEG_INF, jnp.float32),
        jnp.zeros((Hq,), jnp.float32),
        jnp.zeros((Hq, dv), jnp.float32),
    )
    (_, _, l_run, acc), (rounds, survived, v_fetched) = jax.lax.scan(
        block_body, init, jnp.arange(MB))
    out = acc / jnp.maximum(l_run, 1e-30)[:, None]
    survivors = jnp.moveaxis(survived, 0, 1).reshape(Hq, MB * bs)
    return out, rounds, survivors, v_fetched


@partial(jax.jit, static_argnames=("cfg", "window"))
def besf_attention_decode_paged(
    q: jax.Array,            # [B, Hq, d] — one decode query per slot
    k_pool: jax.Array,       # [P, page_size, Hkv*d] (or [.., Hkv, d]) pool
    v_pool: jax.Array,       # [P, page_size, Hkv*dv] (or [.., Hkv, dv])
    table: jax.Array,        # [B, MB] int32 block tables
    lengths: jax.Array,      # [B] int32 fill levels
    q_positions: jax.Array,  # [B] int32 absolute query positions
    k_amax: jax.Array,       # [Hkv] pool-wide running max|K|
    v_amax: jax.Array,       # [Hkv] pool-wide running max|V|
    cfg: BitStopperConfig = BitStopperConfig(),
    window: int | None = None,
) -> PagedDecodeOutput:
    """Paged BESF decode oracle: pure-JAX, gathers physical pages through
    the block table (this IS the retained gather fallback) while computing
    the exact page-sequential semantics of the fused Pallas kernel in
    ``repro/kernels/paged_decode.py`` — survivors, per-page plane counts,
    V-fetch decisions, and the online-softmax output all match the kernel
    bit for bit (tested).

    Quantization uses the cache's **pool-wide** running max-abs scales
    (``k_amax``/``v_amax``), not per-row view scales: a physical page
    shared by several block tables (prefix sharing) or recycled across
    requests must mean the same integers to every reader."""
    Hkv = k_amax.shape[0]
    prep = paged_decode_prep(q, k_amax, v_amax, Hkv, cfg)
    q_int, m_min, m_max, scale_total, alpha_radius, k_scale, v_scale = prep
    # lax.map (sequential over rows), NOT vmap: vmap would batch the
    # per-page lax.cond into a select that executes the dead-page work
    # anyway, and the whole point of the paged walk is that per-step cost
    # scales with each row's actual fill level.
    out, rounds, survivors, v_fetched = jax.lax.map(
        lambda xs: _paged_decode_row(
            xs[0], xs[1], xs[2], xs[3], xs[4], xs[5], xs[6], xs[7],
            k_pool, v_pool, k_scale, v_scale, cfg, window),
        (q_int, jnp.moveaxis(m_min, 1, 0), jnp.moveaxis(m_max, 1, 0),
         scale_total, alpha_radius, table, lengths, q_positions))
    return PagedDecodeOutput(out=out, rounds=rounds, survivors=survivors,
                             v_fetched=v_fetched)


class PagedVerifyOutput(NamedTuple):
    out: jax.Array          # [B, Sq, Hq, dv] attention output per draft query
    rounds: jax.Array       # [B, Sq, n_blocks] int32 planes fetched per page
    survivors: jax.Array    # [B, Sq, Hq, n_blocks*page_size] bool
    v_fetched: jax.Array    # [B, Sq, n_blocks] bool — V page read per query


@partial(jax.jit, static_argnames=("cfg", "window"))
def besf_attention_verify_paged(
    q: jax.Array,            # [B, Sq, Hq, d] — the draft block per slot
    k_pool: jax.Array,       # [P, page_size, Hkv*d] (or [.., Hkv, d]) pool
    v_pool: jax.Array,       # [P, page_size, Hkv*dv] (or [.., Hkv, dv])
    table: jax.Array,        # [B, MB] int32 block tables (shared by queries)
    lengths: jax.Array,      # [B, Sq] int32 per-QUERY fill levels
    q_positions: jax.Array,  # [B, Sq] int32 absolute query positions
    k_amax: jax.Array,       # [Hkv] pool-wide running max|K|
    v_amax: jax.Array,       # [Hkv] pool-wide running max|V|
    cfg: BitStopperConfig = BitStopperConfig(),
    window: int | None = None,
) -> PagedVerifyOutput:
    """Multi-query paged BESF verify oracle (speculative decoding).

    Scores an Sq-token draft block against a slot's paged KV in one pass.
    Every (slot, query) pair is treated as an independent row of the Sq=1
    paged decode: its own absolute position, its own fill level (causal
    intra-draft masking — query i at position p sees cached tokens
    ``t_pos <= p``, i.e. earlier draft tokens but not later ones), its own
    per-(query, head) INT quantization and LATS thresholds.  The rows are
    literally routed through :func:`_paged_decode_row`, so a real draft
    query is **bit-identical** to the Sq=1 decode the non-speculative
    engine would have run at that position — this is what makes
    speculative acceptance lossless.

    ``lengths`` is per query (normally ``q_positions + 1``); a padding
    query (slot proposed fewer than Sq drafts) is disabled with length 0 —
    every page is dead for it, it fetches nothing and costs nothing.

    This oracle is the gather fallback AND the semantic model of the fused
    Sq-tiled kernel ``repro/kernels/paged_verify.py``, which amortizes
    each page's plane DMAs across the whole draft block (fetched once if
    ANY query still needs them) while keeping per-query liveness for every
    observable."""
    B, Sq, Hq, d = q.shape
    Hkv = k_amax.shape[0]
    MB = table.shape[1]
    flat_q = q.reshape(B * Sq, Hq, d)
    prep = paged_decode_prep(flat_q, k_amax, v_amax, Hkv, cfg)
    q_int, m_min, m_max, scale_total, alpha_radius, k_scale, v_scale = prep
    # Each query row addresses the pool through its slot's table.
    flat_table = jnp.broadcast_to(table[:, None], (B, Sq, MB))
    out, rounds, survivors, v_fetched = jax.lax.map(
        lambda xs: _paged_decode_row(
            xs[0], xs[1], xs[2], xs[3], xs[4], xs[5], xs[6], xs[7],
            k_pool, v_pool, k_scale, v_scale, cfg, window),
        (q_int, jnp.moveaxis(m_min, 1, 0), jnp.moveaxis(m_max, 1, 0),
         scale_total, alpha_radius, flat_table.reshape(B * Sq, MB),
         lengths.reshape(B * Sq), q_positions.reshape(B * Sq)))
    return PagedVerifyOutput(
        out=out.reshape(B, Sq, Hq, -1),
        rounds=rounds.reshape(B, Sq, MB),
        survivors=survivors.reshape(B, Sq, Hq, -1),
        v_fetched=v_fetched.reshape(B, Sq, MB))


@partial(jax.jit, static_argnames=("cfg", "causal"))
def besf_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: BitStopperConfig = BitStopperConfig(),
    mask: jax.Array | None = None,
    causal: bool = False,
) -> BESFOutput:
    """BitStopper attention, faithful per-token reference.

    Supports arbitrary leading batch/head dims: q [..., Sq, d], k/v [..., Sk, *].
    """
    Sq, Sk = q.shape[-2], k.shape[-2]
    if causal:
        cmask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        mask = cmask if mask is None else (mask & cmask)

    if q.ndim == 2:
        return _besf_single(q, k, v, mask, cfg)

    flat_q = q.reshape((-1,) + q.shape[-2:])
    flat_k = k.reshape((-1,) + k.shape[-2:])
    flat_v = v.reshape((-1,) + v.shape[-2:])
    if mask is not None and mask.ndim > 2:
        flat_m = jnp.broadcast_to(mask, q.shape[:-2] + (Sq, Sk))
        flat_m = flat_m.reshape((-1, Sq, Sk))
        res = jax.vmap(lambda a, b, c, m: _besf_single(a, b, c, m, cfg))(
            flat_q, flat_k, flat_v, flat_m
        )
    else:
        res = jax.vmap(lambda a, b, c: _besf_single(a, b, c, mask, cfg))(
            flat_q, flat_k, flat_v
        )
    shape = q.shape[:-2]

    def unflat(x):
        return x.reshape(shape + x.shape[1:])

    return jax.tree_util.tree_map(unflat, res)
