"""Multi-head attention: MHA / GQA / MQA, RoPE, sliding windows, KV cache.

The *score path* is pluggable (``impl``):

* ``"xla"``            — chunked online-softmax attention in pure jnp (the
                         training / dry-run path; GSPMD-partitionable, peak
                         memory O(chunk^2) instead of O(S^2)).
* ``"bitstopper_xla"`` — the paper's predictor-free dynamic-sparse attention
                         (block-granular semantic model; serving path).
* ``"bitstopper"``     — fused Pallas kernel (interpret on CPU, compiled on TPU).
* ``"flash"``          — dense fused Pallas kernel.

GQA is computed *grouped* (no KV repetition) on the xla path; the BitStopper
paths repeat KV heads since the sparsity decision is per query head (each
query row owns its LATS threshold, exactly like a PE lane in the paper).

KV cache: slots carry their absolute position (``pos``); sliding-window
layers may use a **ring buffer** of ``window`` slots so ``long_500k`` decode
stays O(window) in memory.  Invalid slots hold the sentinel position 2^30,
which every causal/window test rejects.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import quantization as qlib
from repro.core.besf import BitStopperConfig, besf_attention_decode_paged, \
    besf_attention_verify_paged
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.sharding.api import constrain

NEG_INF = -1e30
POS_SENTINEL = 2 ** 30

# When a cache write grows a pool-wide running max-abs, overshoot the new
# max by this factor.  An exact running max creeps for the whole serve
# (P(new max per token) ~ 1/n), and every growth event is expensive: a
# whole-pool plane requant on the fused path, and a lossless-but-wasted
# bailout tick for speculative decoding.  With headroom, per-head growth
# events are O(log_headroom(dynamic range)) over the entire serve, at the
# cost of <= 25% coarser INT quantization right after a growth (still
# ~11.7 effective bits of the 12).
AMAX_HEADROOM = 1.25


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    yarn: L.Yarn | None = None         # YaRN-scaled RoPE (None = plain)
    qk_norm: bool = False              # RMSNorm over head_dim on q and k,
                                       # before RoPE
    window: int | None = None          # sliding-window size (None = global)
    causal: bool = True
    impl: str = "xla"
    bitstopper: BitStopperConfig = BitStopperConfig()
    chunk_q: int = 512
    chunk_k: int = 512
    # Paged serving decode: walk physical KV pages with the fused Pallas
    # kernel (kernels/paged_decode.py) instead of the pure-JAX gather
    # fallback.  Only consulted when the cache carries a bit-plane pool.
    fused_decode: bool = False
    # Speculative serving: this forward is a draft-block VERIFY — multi-
    # query BitStopper attention goes through the paged verify path (each
    # query bit-identical to the Sq=1 decode at its position) instead of
    # the block-prefill reference.
    spec_verify: bool = False


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": L.init_linear(kq, cfg.d_model, (cfg.n_heads, cfg.head_dim),
                            cfg.qkv_bias, dtype),
        "wk": L.init_linear(kk, cfg.d_model, (cfg.n_kv_heads, cfg.head_dim),
                            cfg.qkv_bias, dtype),
        "wv": L.init_linear(kv, cfg.d_model, (cfg.n_kv_heads, cfg.head_dim),
                            cfg.qkv_bias, dtype),
        "wo": L.init_linear(ko, cfg.n_heads * cfg.head_dim, cfg.d_model,
                            False, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(cfg.head_dim, dtype)
        p["k_norm"] = L.init_rmsnorm(cfg.head_dim, dtype)
    return p


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (jnp "flash"), grouped GQA.
# ---------------------------------------------------------------------------


def _mask_block(q_pos, k_pos, causal: bool, window: int | None):
    """[Bq, Bk] bool validity from absolute positions."""
    m = (k_pos[None, :] < POS_SENTINEL) & (q_pos[:, None] >= 0)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def _fwd_impl(q, k, v, q_pos, k_pos, causal, window, cq, ck):
    """Padded-shape forward.  q [B,Sq,Hkv,G,D] grouped; returns (out, lse).

    lse[b,h,g,i] = m_i + log l_i — the softmax normalizer saved for the
    manual backward (flash-attention style)."""
    B, Sq, Hkv, G, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    nq, nk = Sq // cq, Sk // ck
    sm_scale = 1.0 / D ** 0.5

    kb = k.reshape(B, nk, ck, Hkv, D)
    vb = v.reshape(B, nk, ck, Hkv, Dv)
    qp = q_pos.reshape(nq, cq)
    kp = k_pos.reshape(nk, ck)

    def q_chunk(qi_chunk, qpos):
        def kv_step(carry, inp):
            m_run, l_run, acc = carry
            kc, vc, kpos = inp
            logits = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi_chunk.astype(jnp.float32),
                kc.astype(jnp.float32)) * sm_scale
            mask = _mask_block(qpos, kpos, causal, window)
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(m_run == NEG_INF, 0.0, jnp.exp(m_run - m_new))
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            upd = jnp.einsum("bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
            acc_new = acc * corr[..., None] + upd
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G, cq), jnp.float32),
            jnp.zeros((B, Hkv, G, cq, Dv), jnp.float32),
        )
        (m_run, l_run, acc), _ = jax.lax.scan(
            kv_step, init, (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kp))
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        lse = jnp.where(l_run > 0, m_run + jnp.log(jnp.maximum(l_run, 1e-30)),
                        0.0)
        return jnp.einsum("bhgqd->bqhgd", out), lse      # lse [B,Hkv,G,cq]

    qg = q.reshape(B, nq, cq, Hkv, G, D)
    out, lse = jax.lax.map(lambda inp: q_chunk(*inp),
                           (qg.swapaxes(0, 1), qp))
    out = out.swapaxes(0, 1).reshape(B, Sq, Hkv, G, Dv)
    lse = jnp.moveaxis(lse, 0, 3).reshape(B, Hkv, G, Sq)
    return out, lse


def _make_chunked_attn(causal, window, cq, ck):
    """custom_vjp chunked attention with a MANUAL flash-style backward.

    Autodiff through the forward scans would save per-(q,kv)-tile softmax
    residuals — measured ~13 GB per layer at train_4k scale.  The manual
    backward recomputes each tile from (q, k, v, lse): residual memory is
    O(S·D), all tiles transient.
    """

    @jax.custom_vjp
    def attn(q, k, v, q_pos, k_pos):
        return _fwd_impl(q, k, v, q_pos, k_pos, causal, window, cq, ck)[0]

    def fwd(q, k, v, q_pos, k_pos):
        out, lse = _fwd_impl(q, k, v, q_pos, k_pos, causal, window, cq, ck)
        return out, (q, k, v, q_pos, k_pos, out, lse)

    def bwd(res, dout):
        q, k, v, q_pos, k_pos, out, lse = res
        B, Sq, Hkv, G, D = q.shape
        Sk, Dv = k.shape[1], v.shape[-1]
        nq, nk = Sq // cq, Sk // ck
        sm_scale = 1.0 / D ** 0.5

        dout = dout.astype(jnp.float32)
        # Per-row correction term D_i = sum_d dout_i · out_i.
        delta = jnp.einsum("bqhgd,bqhgd->bhgq", dout,
                           out.astype(jnp.float32))       # [B,Hkv,G,Sq]

        qg = q.reshape(B, nq, cq, Hkv, G, D).astype(jnp.float32)
        dog = dout.reshape(B, nq, cq, Hkv, G, Dv)
        kb = k.reshape(B, nk, ck, Hkv, D).astype(jnp.float32)
        vb = v.reshape(B, nk, ck, Hkv, Dv).astype(jnp.float32)
        lse_c = lse.reshape(B, Hkv, G, nq, cq)
        del_c = delta.reshape(B, Hkv, G, nq, cq)
        qp = q_pos.reshape(nq, cq)
        kp = k_pos.reshape(nk, ck)

        # Outer scan over KV chunks: emits (dk, dv) per chunk, carries the
        # full dq accumulator (O(S·D) f32).
        def kv_step(dq_acc, inp):
            kc, vc, kpos = inp                            # [B,ck,Hkv,D], ...

            def q_step(carry, qinp):
                dk_c, dv_c = carry
                qi, doi, lsei, deli, qpos = qinp
                logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kc) * sm_scale
                mask = _mask_block(qpos, kpos, causal, window)
                p = jnp.where(mask[None, None, None],
                              jnp.exp(logits - lsei[..., None]), 0.0)
                dv_c = dv_c + jnp.einsum("bhgqk,bqhgd->bkhd", p, doi)
                dp = jnp.einsum("bqhgd,bkhd->bhgqk", doi, vc)
                ds = p * (dp - deli[..., None]) * sm_scale
                dq_i = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kc)
                dk_c = dk_c + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qi)
                return (dk_c, dv_c), dq_i

            init = (jnp.zeros((B, ck, Hkv, D), jnp.float32),
                    jnp.zeros((B, ck, Hkv, Dv), jnp.float32))
            (dk_c, dv_c), dq_parts = jax.lax.scan(
                q_step, init,
                (qg.swapaxes(0, 1), dog.swapaxes(0, 1),
                 lse_c.transpose(3, 0, 1, 2, 4), del_c.transpose(3, 0, 1, 2, 4),
                 qp))
            dq_acc = dq_acc + jnp.moveaxis(dq_parts, 0, 1).reshape(
                B, Sq, Hkv, G, D)
            return dq_acc, (dk_c, dv_c)

        dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
        dq, (dk_parts, dv_parts) = jax.lax.scan(
            kv_step, dq0, (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kp))
        dk = jnp.moveaxis(dk_parts, 0, 1).reshape(B, Sk, Hkv, D)
        dv = jnp.moveaxis(dv_parts, 0, 1).reshape(B, Sk, Hkv, Dv)

        import numpy as _np
        zp = _np.zeros(q_pos.shape, jax.dtypes.float0)
        zk = _np.zeros(k_pos.shape, jax.dtypes.float0)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                zp, zk)

    attn.defvjp(fwd, bwd)
    return attn


def chunked_attention(
    q: jax.Array,              # [B, Sq, Hq, D]
    k: jax.Array,              # [B, Sk, Hkv, D]
    v: jax.Array,              # [B, Sk, Hkv, D]
    q_positions: jax.Array,    # [Sq] absolute positions of the queries
    k_positions: jax.Array,    # [Sk]
    causal: bool,
    window: int | None,
    chunk_q: int,
    chunk_k: int,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    pad_q, pad_k = (-Sq) % cq, (-Sk) % ck
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad_k),
                              constant_values=POS_SENTINEL)
    qg = q.reshape(B, q.shape[1], Hkv, G, D)
    attn = _make_chunked_attn(causal, window, cq, ck)
    out = attn(qg, k, v, q_positions, k_positions)       # [B,Sq',Hkv,G,Dv]
    out = out.reshape(B, q.shape[1], Hq, Dv)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# BitStopper score path: per-query-head dynamic sparsity.
# ---------------------------------------------------------------------------


def attention_block_shape(n: int, cap: int = 128) -> tuple[int, int]:
    """Public block-size helper for the block-granular BitStopper paths.

    Returns ``(block, pad)``: the tile size is ``min(cap, n)`` and the axis
    pads up to a multiple of it (padding must be fully masked, so dead tiles
    never fetch planes and zero pad rows don't move the per-tensor max-abs
    quant scale).  Padding — rather than shrinking the block to a divisor of
    ``n`` — keeps awkward (e.g. prime) lengths from degrading to 1-wide
    tiles."""
    b = min(cap, n)
    return b, (-n) % b


def _expand_gqa(q, k, v, G):
    """[B,S,H*,D] layout → head-major [B,Hq,S,D] with KV heads repeated
    (the BitStopper paths decide sparsity per query head)."""
    kr = jnp.repeat(k, G, axis=2).swapaxes(1, 2)      # [B, Hq, T, D]
    vr = jnp.repeat(v, G, axis=2).swapaxes(1, 2)
    return q.swapaxes(1, 2), kr, vr


def _bitstopper_full(q, k, v, cfg: AttnConfig, mask2d):
    """q [B,S,Hq,D], k/v [B,T,Hkv,D], mask2d [S,T] or None → [B,S,Hq,D]."""
    qt, kr, vr = _expand_gqa(q, k, v, cfg.n_heads // cfg.n_kv_heads)
    Sq = qt.shape[2]

    if cfg.impl == "bitstopper_xla" or mask2d is not None:
        from repro.core.block_adaptation import block_bitstopper_attention
        Sk = kr.shape[2]
        if mask2d is None and cfg.causal:
            mask2d = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        bq, pad_q = attention_block_shape(Sq)
        bk, pad_k = attention_block_shape(Sk)
        if pad_q or pad_k:
            if mask2d is None:
                mask2d = jnp.ones((Sq, Sk), bool)
            mask2d = jnp.pad(mask2d, ((0, pad_q), (0, pad_k)))
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
            kr = jnp.pad(kr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
            vr = jnp.pad(vr, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        res = jax.vmap(
            lambda a, b, c: block_bitstopper_attention(
                a, b, c, cfg=cfg.bitstopper, block_q=bq, block_k=bk,
                mask=mask2d)
        )(flat(qt), flat(kr), flat(vr))
        out = res.out.reshape(qt.shape[:2] + res.out.shape[1:])
    else:
        out = kops.attention(qt, kr, vr, impl=cfg.impl, causal=cfg.causal,
                             cfg=cfg.bitstopper)
    return out.swapaxes(1, 2)[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Shape of a paged (block-pool) KV cache.

    ``pool_blocks`` physical blocks of ``page_size`` token slots each are
    shared by every request; a request addresses them through a
    ``[batch, max_blocks_per_req]`` *block table* mapping logical block
    index (position // page_size) to physical block id.  Physical block 0
    is the **null block**: never written, it backs unused table entries so
    gathers stay in bounds."""
    pool_blocks: int
    page_size: int
    max_blocks_per_req: int

    def __post_init__(self):
        if self.pool_blocks < 2:
            raise ValueError("pool_blocks must be >= 2 (block 0 is the "
                             f"reserved null block), got {self.pool_blocks}")
        if self.page_size < 1 or self.max_blocks_per_req < 1:
            raise ValueError("page_size and max_blocks_per_req must be >= 1")


def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.float32,
               ring: bool = False, per_slot: bool = False,
               paged: PagedLayout | None = None):
    """With ``ring=True`` (sliding-window layers) only ``window`` slots are
    allocated and writes wrap — O(window) memory for long_500k decode.
    Ring-ness needs no flag at use time: writes always go to
    ``length mod n_slots``, which is the identity while length < n_slots.

    With ``per_slot=True`` (continuous-batching serving) every batch row is
    an independent *slot*: it carries its own write cursor (``length`` is
    [batch]) and its own slot->position map (``pos`` is [batch, n_slots]),
    so requests of different lengths share one decode batch without
    re-padding.  ``cache_is_per_slot`` distinguishes the two layouts.

    With ``paged=PagedLayout(...)`` the K/V storage loses its batch axis
    entirely: one lane-dense ``[pool_blocks, page_size, Hkv*D]`` pool (KV
    heads merged into the minor dim, so a page is whole (8, 128) tiles on
    a TPU with no padding of a 64-wide head dim) is shared by every slot,
    addressed through a per-slot block ``table`` (refcounted blocks can
    appear in several tables — copy-on-write prefix sharing).
    Sliding-window layers fall back to position masking (no ring): the
    logical index of a token is its absolute position.

    BitStopper layers additionally carry ``k_amax``/``v_amax`` — the
    monotone running max-abs per KV head defining the pool-wide quant
    scales both paged decode paths share — and, when ``cfg.fused_decode``,
    an **incremental bit-plane pool**: ``kq`` holds every page's K rows
    pre-quantized (INT-``bits``) and bit-packed 8 tokens/byte along the
    page axis in the lane-dense row layout of ``qlib.pack_pool_planes`` —
    ``uint8[pool_blocks, bits, R, Hkv*D/C]`` — written at cache write
    time so the fused kernel never re-derives planes from the f32 pool
    (see ``_update_plane_pool`` for the rescale-on-demand rule)."""
    if paged is not None:
        nb, bs = paged.pool_blocks, paged.page_size
        lanes = cfg.n_kv_heads * cfg.head_dim
        cache = {
            "k": jnp.zeros((nb, bs, lanes), dtype),
            "v": jnp.zeros((nb, bs, lanes), dtype),
            "pos": jnp.full((nb, bs), POS_SENTINEL, jnp.int32),
            "table": jnp.zeros((batch, paged.max_blocks_per_req), jnp.int32),
            "length": jnp.zeros((batch,), jnp.int32),
        }
        if cfg.impl in ("bitstopper", "bitstopper_xla") and bs % 8 == 0:
            # Pool-wide running quant scales: needed by BOTH paged decode
            # paths (the kernel and the pure-JAX fallback oracle).
            cache["k_amax"] = jnp.zeros((cfg.n_kv_heads,), jnp.float32)
            cache["v_amax"] = jnp.zeros((cfg.n_kv_heads,), jnp.float32)
            if cfg.fused_decode:
                # The packed plane pool is read only by the fused kernel;
                # the fallback re-derives planes from the f32 pool, so
                # don't pay write-time packing/requants it won't use.
                bits = cfg.bitstopper.bits
                cache["kq"] = jnp.zeros(
                    qlib.plane_pool_shape(nb, bits, bs, lanes), jnp.uint8)
        return cache
    n_slots = min(max_len, cfg.window) if (ring and cfg.window) else max_len
    if per_slot:
        pos = jnp.full((batch, n_slots), POS_SENTINEL, jnp.int32)
        length = jnp.zeros((batch,), jnp.int32)
    else:
        pos = jnp.full((n_slots,), POS_SENTINEL, jnp.int32)
        length = jnp.zeros((), jnp.int32)
    return {
        "k": jnp.zeros((batch, n_slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, n_slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": pos,
        "length": length,
    }


def cache_is_paged(cache) -> bool:
    return "table" in cache


def cache_is_per_slot(cache) -> bool:
    return cache_is_paged(cache) or cache["pos"].ndim == 2


def _update_cache(cache, k, v, positions):
    """Write the new token(s) into the cache.

    With active sharding rules and the cache's sequence axis sharded over
    "model", a plain dynamic-update-slice is decomposed by GSPMD into a
    masked SELECT over the whole local cache (full read+write of GiBs per
    layer per decoded token — measured as THE dominant decode traffic).
    The shard_map path does what serving systems do on real hardware: each
    shard tests whether the global slot lands in its range and performs an
    in-place LOCAL update of just that slot.
    """
    from repro.sharding.api import current_rules

    S = k.shape[1]
    n_slots = cache["k"].shape[1]
    kc = k.astype(cache["k"].dtype)
    vc = v.astype(cache["v"].dtype)
    pc = positions.astype(jnp.int32)

    if cache_is_per_slot(cache):
        # Per-slot layout: every batch row has its own cursor.  Writes are a
        # batched scatter at (length[b] + i) mod n_slots — rows at different
        # fill levels advance independently (continuous batching).  Tokens
        # whose position is the pad sentinel (bucketed prefill padding,
        # always trailing) are routed out of bounds and dropped, so pads
        # never consume ring slots or advance the cursor.
        B = kc.shape[0]
        pc2 = jnp.broadcast_to(pc, (B, S))
        real = pc2 != POS_SENTINEL
        idx = jax.lax.rem(
            cache["length"][:, None] + jnp.arange(S, dtype=jnp.int32)[None],
            n_slots)                                          # [B, S]
        idx = jnp.where(real, idx, n_slots)                   # OOB => dropped
        rows = jnp.arange(B)[:, None]
        ck = cache["k"].at[rows, idx].set(kc, mode="drop")
        cv = cache["v"].at[rows, idx].set(vc, mode="drop")
        cpos = cache["pos"].at[rows, idx].set(pc2, mode="drop")
        new = dict(cache, k=ck, v=cv, pos=cpos,
                   length=cache["length"] + real.sum(axis=1, dtype=jnp.int32))
        return ck, cv, cpos, new

    widx = jax.lax.rem(cache["length"], n_slots)
    rules = current_rules()
    use_shmap = (S == 1 and rules is not None
                 and "model" in rules.mesh.shape
                 and n_slots % rules.mesh.shape["model"] == 0)
    if not use_shmap:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, widx, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, widx, 1)
        cpos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], pc, widx, 0)
    else:
        from jax.sharding import PartitionSpec as P
        mesh = rules.mesh
        bspec = rules.pspec(("batch",), (cache["k"].shape[0],))[0]
        cache_spec = P(bspec, "model", None, None)
        new_spec = P(bspec, None, None, None)

        def body(ck_l, cv_l, pos_l, kn, vn, pn, wi):
            T_loc = ck_l.shape[1]
            local = wi[0] - jax.lax.axis_index("model") * T_loc
            in_rng = (local >= 0) & (local < T_loc)
            idx = jnp.clip(local, 0, T_loc - 1)
            cur_k = jax.lax.dynamic_slice_in_dim(ck_l, idx, 1, 1)
            cur_v = jax.lax.dynamic_slice_in_dim(cv_l, idx, 1, 1)
            cur_p = jax.lax.dynamic_slice_in_dim(pos_l, idx, 1, 0)
            ck_l = jax.lax.dynamic_update_slice_in_dim(
                ck_l, jnp.where(in_rng, kn, cur_k), idx, 1)
            cv_l = jax.lax.dynamic_update_slice_in_dim(
                cv_l, jnp.where(in_rng, vn, cur_v), idx, 1)
            pos_l = jax.lax.dynamic_update_slice_in_dim(
                pos_l, jnp.where(in_rng, pn, cur_p), idx, 0)
            return ck_l, cv_l, pos_l

        ck, cv, cpos = jax.shard_map(
            body, mesh=mesh,
            in_specs=(cache_spec, cache_spec, P("model"),
                      new_spec, new_spec, P(None), P(None)),
            out_specs=(cache_spec, cache_spec, P("model")),
            check_vma=False,
        )(cache["k"], cache["v"], cache["pos"], kc, vc, pc,
          widx[None])
    new = dict(cache, k=ck, v=cv, pos=cpos, length=cache["length"] + S)
    return ck, cv, cpos, new


def _update_plane_pool(cache, kc, vc, real, phys, p_safe, ok, k_pool_new):
    """Maintain the pool-wide quant scales — and, when the fused kernel is
    in play (``kq`` present), the incremental bit-plane pool — at cache
    write time.

    Scale policy (**rescale-on-demand**): ``k_amax``/``v_amax`` are the
    monotone running max-abs per KV head over every token ever written.
    While the max is stable, only the newly written tokens are quantized
    and their bits scattered into the packed pool (one byte column per
    token — O(written) traffic).  When a new token *grows* the max, every
    stored plane encodes integers under a stale scale, so the whole pool
    is requantized from the f32 K pool under the new scale — a rare,
    amortized event (max-abs growth is logarithmic in tokens served).

    Packing invariant: token at page offset ``t`` owns bit ``t % 8`` of
    byte ``t // 8`` (LSB-first, matching ``qlib.pack_planes_seq``).  Pages
    fill strictly front to back (allocator + append-only cursor), so a
    write to bit position ``b`` may clobber bits above ``b`` (never yet
    written, unreadable through the fill-level mask) but must preserve
    bits below ``b`` (earlier tokens) — hence the low-mask merge.
    """
    k_amax, v_amax = cache["k_amax"], cache["v_amax"]
    realm = real[..., None, None]
    kabs = jnp.abs(kc.astype(jnp.float32)) * realm
    vabs = jnp.abs(vc.astype(jnp.float32)) * realm
    k_hi = jnp.max(kabs, axis=(0, 1, 3))
    v_hi = jnp.max(vabs, axis=(0, 1, 3))
    # Growth overshoots by AMAX_HEADROOM so the running max settles after
    # a handful of events instead of creeping per token (each growth is a
    # whole-pool requant and/or a speculative bailout — see the constant).
    k_amax_new = jnp.where(k_hi > k_amax, k_hi * AMAX_HEADROOM, k_amax)
    v_amax_new = jnp.where(v_hi > v_amax, v_hi * AMAX_HEADROOM, v_amax)
    if "kq" not in cache:      # fallback decode: scales only, no packing
        return dict(k_amax=k_amax_new, v_amax=v_amax_new)
    kq = cache["kq"]
    nb, bits = kq.shape[:2]
    bs = cache["pos"].shape[1]
    C = qlib.plane_chunks(bs)
    grew = jnp.any(k_amax_new > k_amax)
    k_scale = qlib.scale_from_amax(k_amax_new, bits)          # [H]

    def requant(kq):
        return qlib.pack_pool_planes(k_pool_new, k_amax_new, bits)

    def incremental(kq):
        S = real.shape[1]
        k_int = qlib.quantize_with_scale(
            kc, k_scale[None, None, :, None], bits)           # [B,S,H,D]
        u = jnp.where(k_int < 0, k_int + (1 << bits), k_int).astype(jnp.uint32)
        shifts = jnp.arange(bits - 1, -1, -1,
                            dtype=jnp.uint32).reshape(1, bits, 1, 1)

        def write_one(s, kq):
            us = u[:, s]                                      # [B, H, D]
            tokbits = ((us[:, None] >> shifts) & 1).astype(jnp.int32)
            # The token's byte group is C consecutive rows of every plane.
            tokbits = qlib.to_plane_rows(tokbits, C)          # [B,bits,C,L]
            off = p_safe[:, s] % bs
            byte, bitpos = off // 8, off % 8                  # [B]
            # One dynamic slice per slot, never a scatter: the pool keeps
            # the row-major layout the kernel DMAs from.  A dropped token
            # rewrites its (null) target unchanged.
            for b in range(u.shape[0]):
                at = (jnp.where(ok[b, s], phys[b, s], 0), 0, byte[b] * C, 0)
                old = jax.lax.dynamic_slice(
                    kq, at, (1, bits, C, kq.shape[3])).astype(jnp.int32)
                lowmask = (1 << bitpos[b]) - 1
                new = (old & lowmask) | (tokbits[b] << bitpos[b])
                new = jnp.where(ok[b, s], new, old)
                kq = jax.lax.dynamic_update_slice(
                    kq, new.astype(jnp.uint8), at)
            return kq

        return jax.lax.fori_loop(0, S, write_one, kq)

    kq_new = jax.lax.cond(grew, requant, incremental, kq)
    return dict(kq=kq_new, k_amax=k_amax_new, v_amax=v_amax_new)


def _update_paged_cache(cache, k, v, positions):
    """Write new token(s) into the paged block-pool cache; returns ONLY the
    new cache — no logical view is materialized (callers that still need a
    dense gather ask :func:`gather_paged_view` explicitly).

    The K/V pool has no batch axis — every batch row (serving slot)
    scatters through its row of the block table.  A token at absolute
    position p lives in logical block p // bs at offset p % bs; the table
    maps logical -> physical block id.  Writes never target physical block
    0 (the null block backing unused table entries), and pad-sentinel
    tokens are routed out of bounds and dropped — exactly like the
    contiguous per-slot path."""
    nb, bs = cache["pos"].shape
    S = k.shape[1]
    kc = k.astype(cache["k"].dtype)
    vc = v.astype(cache["v"].dtype)
    pc = positions.astype(jnp.int32)
    B = kc.shape[0]
    table = cache["table"]                                    # [B, MB]
    MB = table.shape[1]
    pc2 = jnp.broadcast_to(pc, (B, S))
    real = pc2 != POS_SENTINEL
    p_safe = jnp.where(real, pc2, 0)
    logical = p_safe // bs
    phys = jnp.take_along_axis(table, jnp.clip(logical, 0, MB - 1),
                               axis=1)                        # [B, S]
    ok = real & (logical < MB) & (phys > 0)
    flat_idx = jnp.where(ok, phys * bs + p_safe % bs, nb * bs)
    kf = cache["k"].reshape(nb * bs, -1)
    vf = cache["v"].reshape(nb * bs, -1)
    pf = cache["pos"].reshape(nb * bs)
    fi = flat_idx.reshape(-1)
    kf = kf.at[fi].set(kc.reshape(B * S, -1), mode="drop")
    vf = vf.at[fi].set(vc.reshape(B * S, -1), mode="drop")
    pf = pf.at[fi].set(pc2.reshape(-1), mode="drop")
    new_len = cache["length"] + real.sum(axis=1, dtype=jnp.int32)
    new = dict(cache, k=kf.reshape(cache["k"].shape),
               v=vf.reshape(cache["v"].shape),
               pos=pf.reshape(nb, bs), length=new_len)
    if "k_amax" in cache:
        new.update(_update_plane_pool(cache, kc, vc, real, phys, p_safe, ok,
                                      new["k"]))
    return new


def gather_paged_view(cache, active=None):
    """Gather each row's dense logical view ``[B, MB*bs]`` from the pool
    (K/V lane-dense, ``[B, MB*bs, Hkv*D]``, like the pool).

    Only the first length[b] view slots were ever written by (or shared
    into) row b, so slots past the fill level are forced invalid and
    zeroed: a recycled physical block's stale K/V and positions are
    unobservable, and zeroed tails keep the BitStopper per-tensor max-abs
    quant scale identical to the contiguous layout.

    ``active`` ([B] bool) gates the gather to rows that actually attend
    this step: an inactive row's table is swapped for the null block, so
    its gather touches a single hot page instead of pulling
    ``max_blocks_per_req`` cold pages per layer.  (The fused decode path
    skips this gather entirely — it walks physical pages in the kernel.)
    """
    nb, bs = cache["pos"].shape
    table = cache["table"]
    if active is not None:
        table = jnp.where(active[:, None], table, 0)
    B, MB = table.shape
    Tv = MB * bs
    kf = cache["k"].reshape(nb * bs, -1)
    vf = cache["v"].reshape(nb * bs, -1)
    pf = cache["pos"].reshape(nb * bs)
    view = (table[..., None] * bs
            + jnp.arange(bs, dtype=jnp.int32)).reshape(B, Tv)
    k_view = kf[view]                                         # [B, Tv, H*D]
    v_view = vf[view]
    pos_view = pf[view]
    valid = jnp.arange(Tv, dtype=jnp.int32)[None] < cache["length"][:, None]
    pos_view = jnp.where(valid, pos_view, POS_SENTINEL)
    k_view = jnp.where(valid[..., None], k_view, 0)
    v_view = jnp.where(valid[..., None], v_view, 0)
    return k_view, v_view, pos_view


def _paged_shard_rules(cfg: AttnConfig):
    """Active mesh rules iff the paged pools are KV-head-sharded under them.

    Shardable iff a >1 "model" axis divides ``n_kv_heads`` — the same
    divisibility gate PAGED_CACHE_RULES applies to the pool placement, so
    this and the cache layout agree by construction.  When it fails (MQA's
    single KV head on a multi-way axis) the pools are replicated and the
    plain single-device call is already correct."""
    from repro.sharding.api import current_rules
    rules = current_rules()
    if rules is None:
        return None
    tp = rules.mesh.shape.get("model", 0)
    if tp <= 1 or cfg.n_kv_heads % tp != 0:
        return None
    return rules


def _shard_paged_attention(fn, rules, q, kpool, vpool, table, lengths,
                           q_pos, k_amax, v_amax):
    """Run a paged BESF entry point tensor-parallel over KV heads.

    Per-(slot, KV head) independence is what makes this exact: every BESF
    quantity — the LATS thresholds, bit-plane partial scores, the softmax
    normalizer, the V accumulation — reduces only within one (slot, KV
    head) pair, so splitting ``Hkv`` over "model" (grouped Q heads ride
    along: Q heads are KV-major, ``h -> h // G``) changes NO float
    reduction order.  Each shard runs the unmodified kernel/oracle at
    local geometry ``Hkv/tp`` against its slice of the bit-plane/V pools
    and amax scales (block table and fill levels replicated), and the
    trailing all-gather back to replicated heads is pure data movement —
    so the downstream ``wo`` matmul sums in single-device order and the
    output stays bit-identical to the unsharded run.  Slots shard over
    "data" the same way (batch rows are independent)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = rules.mesh
    verify = q.ndim == 4                                  # [B,Sq,Hq,D]
    bspec = rules.pspec(("batch",), (q.shape[0],))[0]
    qspec = (P(bspec, None, "model", None) if verify
             else P(bspec, "model", None))
    # Lane-dense pools: every KV head owns a contiguous lane range, so a
    # lane split is a head split (kq [P, bits, R, L], f32 [P, bs, Hkv*D]).
    kspec = P(*(None,) * (kpool.ndim - 1), "model")
    lspec = P(bspec, None) if verify else P(bspec)
    out = jax.shard_map(
        lambda *a: fn(*a).out, mesh=mesh,
        in_specs=(qspec, kspec, P(None, None, "model"),
                  P(bspec, None), lspec, lspec, P("model"), P("model")),
        out_specs=qspec, check_vma=False,
    )(q, kpool, vpool, table, lengths, q_pos, k_amax, v_amax)
    gspec = (P(bspec, None, None, None) if verify
             else P(bspec, None, None))
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, gspec))


def _paged_cached_attention(q, cache, positions, cfg: AttnConfig):
    """Attention against the (already updated) paged cache.

    The Sq == 1 BitStopper decode goes straight at the pool handles
    (block table + fill levels + bit-plane pool): the fused Pallas kernel
    when ``cfg.fused_decode``, else the pure-JAX paged oracle — the
    retained gather fallback with identical page-sequential semantics.
    Everything else (dense impl, prefill chunks, planeless pools) gathers
    the logical view, gated to active rows."""
    B, S = q.shape[:2]
    active = (positions != POS_SENTINEL).any(axis=1)
    if (cfg.spec_verify and cfg.impl in ("bitstopper", "bitstopper_xla")
            and "k_amax" in cache):
        # Speculative verify: score the whole draft block in one paged
        # multi-query BESF pass.  Every real query runs with its own fill
        # level (its position + 1 — the batched cache write has already
        # scattered the draft tokens, so query i sees exactly the KV set
        # the Sq=1 decode at that position would see: causal intra-draft
        # masking for free).  Padding queries (slot proposed fewer drafts,
        # or a row still prefilling) get fill level 0 and touch no pages.
        real = positions != POS_SENTINEL                      # [B, S]
        q_pos = jnp.where(real, positions, 0)
        lengths = jnp.where(real, q_pos + 1, 0)
        if cfg.fused_decode:
            from repro.kernels.paged_verify import paged_bitstopper_verify
            call = functools.partial(
                paged_bitstopper_verify,
                cfg=cfg.bitstopper, window=cfg.window, stats=False)
            pool = cache["kq"]
        else:
            call = functools.partial(
                besf_attention_verify_paged,
                cfg=cfg.bitstopper, window=cfg.window)
            pool = cache["k"]
        args = (q, pool, cache["v"], cache["table"], lengths, q_pos,
                cache["k_amax"], cache["v_amax"])
        rules = _paged_shard_rules(cfg)
        with jax.named_scope("attn"):
            if rules is not None:
                out = _shard_paged_attention(call, rules, *args)
            else:
                out = call(*args).out
        return out.astype(q.dtype)                            # [B,S,Hq,Dv]
    if (cfg.impl in ("bitstopper", "bitstopper_xla") and S == 1
            and "k_amax" in cache):
        qt = q[:, 0]                                          # [B, Hq, D]
        q_pos = positions[:, 0]
        # Gate to active rows: a slot still prefilling decodes at the pad
        # sentinel (its output is discarded by the engine) — zeroing its
        # fill level makes every page unreachable, so the kernel issues
        # ZERO DMAs for it instead of walking its blocks per layer.
        lengths = jnp.where(active, cache["length"], 0)
        if cfg.fused_decode:
            from repro.kernels.paged_decode import paged_bitstopper_decode
            call = functools.partial(
                paged_bitstopper_decode,
                cfg=cfg.bitstopper, window=cfg.window, stats=False)
            pool = cache["kq"]
        else:
            call = functools.partial(
                besf_attention_decode_paged,
                cfg=cfg.bitstopper, window=cfg.window)
            pool = cache["k"]
        args = (qt, pool, cache["v"], cache["table"], lengths, q_pos,
                cache["k_amax"], cache["v_amax"])
        rules = _paged_shard_rules(cfg)
        with jax.named_scope("attn"):
            if rules is not None:
                out = _shard_paged_attention(call, rules, *args)
            else:
                out = call(*args).out
        return out[:, None].astype(q.dtype)                   # [B,1,Hq,Dv]
    with jax.named_scope("kv_gather"):
        k_view, v_view, pos_view = gather_paged_view(cache, active)
    k_view = k_view.reshape(B, -1, cfg.n_kv_heads, cfg.head_dim)
    v_view = v_view.reshape(B, -1, cfg.n_kv_heads, cfg.head_dim)
    # Under the serving mesh the pools are KV-head sharded; the gathered
    # view follows the activations (replicated), so this attention runs at
    # its single-device shapes — bit-identical to unsharded serving.
    k_view = constrain(k_view, "batch", "seq", "kv_heads", None)
    v_view = constrain(v_view, "batch", "seq", "kv_heads", None)
    with jax.named_scope("attn"):
        return _cached_attention(q, k_view, v_view, positions, pos_view,
                                 cfg)


def _cached_attention(q, k_all, v_all, q_positions, k_positions,
                      cfg: AttnConfig):
    """Attention against the (padded/ring) cache, mask from slot positions.

    ``q_positions`` / ``k_positions`` may be 1-D (legacy shared-cursor
    cache: mask shared across the batch) or 2-D [B, ...] (per-slot cache:
    every batch row masks against its own fill level)."""
    B, Sq = q.shape[0], q.shape[1]
    per_slot = q_positions.ndim == 2
    if per_slot:
        mask = jax.vmap(
            lambda qp, kp: _mask_block(qp, kp, causal=True, window=cfg.window)
        )(q_positions, k_positions)                          # [B, Sq, T]
    else:
        mask = _mask_block(q_positions, k_positions, causal=True,
                           window=cfg.window)                # [Sq, T]
    bmask = mask if per_slot else jnp.broadcast_to(
        mask[None], (B,) + mask.shape)

    if cfg.impl in ("bitstopper_xla", "bitstopper"):
        if Sq == 1:
            # Decode fast path: single-query BESF with the per-round
            # threshold-scan setup amortized across planes (one fused int
            # plane contraction per head instead of one per bit round).
            from repro.core.besf import besf_attention_decode
            qt, kr, vr = _expand_gqa(q, k_all, v_all,
                                     cfg.n_heads // cfg.n_kv_heads)
            res = besf_attention_decode(
                qt, kr, vr, cfg=cfg.bitstopper, mask=bmask[:, None])
            return res.out.swapaxes(1, 2).astype(q.dtype)
        if not per_slot:
            return _bitstopper_full(q, k_all, v_all, cfg, mask)
        if B == 1:
            return _bitstopper_full(q, k_all, v_all, cfg, mask[0])
        # Per-slot multi-request prefill: per-token reference with
        # per-example masks (rare; the engine prefills one slot at a time).
        from repro.core.besf import besf_attention
        qt, kr, vr = _expand_gqa(q, k_all, v_all,
                                 cfg.n_heads // cfg.n_kv_heads)
        res = besf_attention(qt, kr, vr, cfg=cfg.bitstopper,
                             mask=bmask[:, None])
        return res.out.swapaxes(1, 2).astype(q.dtype)

    G = cfg.n_heads // cfg.n_kv_heads
    B, T, Hkv, D = k_all.shape
    qg = q.reshape(q.shape[0], q.shape[1], Hkv, G, D)
    # Mixed-dtype einsums with f32 accumulation: never materialize an f32
    # copy of the (multi-GiB) KV cache — reads stay bf16 (measured ~3x
    # decode HBM-traffic reduction vs .astype(f32) upcasting).
    logits = jnp.einsum("bqhgd,bthd->bhgqt", qg, k_all,
                        preferred_element_type=jnp.float32) / D ** 0.5
    logits = jnp.where(bmask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(bmask[:, None, None], p, 0.0)
    out = jnp.einsum("bhgqt,bthd->bqhgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------


def attention(
    p,
    x: jax.Array,                    # [B, S, d_model]
    positions: jax.Array,            # [S]
    cfg: AttnConfig,
    cache: dict[str, Any] | None = None,
):
    """Returns (out [B,S,d_model], new_cache).

    ``positions`` is [S] (shared across the batch) or, with a per-slot
    cache, [B, S] — each serving slot decodes at its own absolute position.
    """
    B, S, _ = x.shape
    if cache is not None and cache_is_per_slot(cache) and positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (B, S))
    q = L.linear(p["wq"], x)                         # [B, S, Hq, D]
    k = L.linear(p["wk"], x)                         # [B, S, Hkv, D]
    v = L.linear(p["wv"], x)
    if cfg.qk_norm:
        q = L.rms_norm(p["q_norm"], q)
        k = L.rms_norm(p["k_norm"], k)
    rope_pos = positions if positions.ndim == 2 else positions[None, :]
    q = L.rope(q, rope_pos, cfg.rope_theta, cfg.yarn)
    k = L.rope(k, rope_pos, cfg.rope_theta, cfg.yarn)
    if positions.ndim == 2:
        # Zero pad rows (bucketed-prefill sentinel positions): their k/v are
        # dropped by the cache scatter, and zero q rows keep the BitStopper
        # per-tensor max-abs quant scale independent of how much bucket
        # padding a request happened to get.
        real = (positions != POS_SENTINEL)[..., None, None]
        q, k, v = q * real, k * real, v * real
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)

    if cache is None:
        if positions.ndim == 2:
            # The cache-free (training/prefill) path is batch-uniform; 2-D
            # positions only arise from the per-slot serving cache.
            positions = positions[0]
        if cfg.impl in ("bitstopper_xla", "bitstopper"):
            mask2d = None
            if cfg.window is not None:
                mask2d = _mask_block(positions, positions, cfg.causal,
                                     cfg.window)
            out = _bitstopper_full(q, k, v, cfg, mask2d)
        elif cfg.impl == "flash" and cfg.window is None:
            qt, kr, vr = _expand_gqa(q, k, v, cfg.n_heads // cfg.n_kv_heads)
            out = kops.attention(qt, kr, vr, impl="flash",
                                 causal=cfg.causal).swapaxes(1, 2)
        else:
            out = chunked_attention(
                q, k, v, positions, positions,
                causal=cfg.causal, window=cfg.window,
                chunk_q=cfg.chunk_q, chunk_k=cfg.chunk_k,
            )
        new_cache = None
    elif cache_is_paged(cache):
        with jax.named_scope("kv_write"):
            new_cache = _update_paged_cache(cache, k, v, positions)
        out = _paged_cached_attention(q, new_cache, positions, cfg)
    else:
        k_all, v_all, k_pos, new_cache = _update_cache(cache, k, v, positions)
        out = _cached_attention(q, k_all, v_all, positions, k_pos, cfg)

    # Pin the head layout entering the wo contraction via the "heads_out"
    # logical axis.  Training rules map it to "model" (Megatron: partial
    # products + psum against the heads_flat-sharded wo).  Serving rules
    # map it to None: the all-gather back to replicated heads is pure data
    # movement, so the flattened-head matmul sums in single-device order —
    # the serving bit-identity invariant (docs/serving.md).
    out = constrain(out, "batch", "seq", "heads_out", None)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    y = L.linear(p["wo"], out)
    y = constrain(y, "batch", "seq", "embed")
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged block-row transport: the device<->host seam of the memory hierarchy.
#
# These helpers move whole physical blocks between the paged device pools
# and host arrays — the engine's Prefix.payload handoff (PR 9), swap-to-
# host preemption and the persistent prefix store are all built on them.
# They live here (not in serving/) because they encode the paged cache
# layout: which per-layer arrays exist, how stacked (scanned) layers carry
# a leading reps axis, and how the packed plane pool relates to the f32
# pool and the amax scales.
# ---------------------------------------------------------------------------


def iter_paged_layers(tree):
    """Yield every paged-layer cache dict in the pytree, in deterministic
    (sorted-dict-key / list-index) order.  All transport helpers below use
    this same traversal, so extracted layer lists and splice targets pair
    up positionally."""
    if isinstance(tree, dict):
        if cache_is_paged(tree):
            yield tree
        else:
            for key in sorted(tree):
                yield from iter_paged_layers(tree[key])
    elif isinstance(tree, (list, tuple)):
        for sub in tree:
            yield from iter_paged_layers(sub)


def map_paged_layers(tree, fn, _counter=None):
    """Rebuild the pytree with ``fn(layer_dict, layer_index)`` applied to
    every paged-layer cache dict (same order as :func:`iter_paged_layers`)."""
    if _counter is None:
        _counter = [0]
    if isinstance(tree, dict):
        if cache_is_paged(tree):
            i = _counter[0]
            _counter[0] += 1
            return fn(tree, i)
        return {k: map_paged_layers(tree[k], fn, _counter)
                for k in sorted(tree)}
    if isinstance(tree, (list, tuple)):
        mapped = [map_paged_layers(sub, fn, _counter) for sub in tree]
        return type(tree)(mapped) if isinstance(tree, tuple) else mapped
    return tree


def _rows_take(c, field, idx):
    a = c[field]
    return a[:, idx] if c["table"].ndim == 3 else a[idx]


def extract_block_rows(caches, bids, planes: bool = False):
    """Device→host copy of whole physical blocks ``bids`` from every paged
    layer.  Returns one dict per paged layer holding numpy ``k``/``v``
    rows and the ``pos`` plane (stacked layers keep their leading reps
    axis), plus the packed ``kq`` plane rows when ``planes=True`` and the
    layer maintains them.  Registered blocks are append-only and full
    blocks are never rewritten, so extracted rows stay valid until the
    block is freed and poisoned/reused."""
    import numpy as np
    idx = jnp.asarray(list(bids), jnp.int32)
    layers = []
    for c in iter_paged_layers(caches):
        rows = {"k": np.asarray(_rows_take(c, "k", idx)),
                "v": np.asarray(_rows_take(c, "v", idx)),
                "pos": np.asarray(_rows_take(c, "pos", idx))}
        if planes and "kq" in c:
            rows["kq"] = np.asarray(_rows_take(c, "kq", idx))
        layers.append(rows)
    return layers


def splice_block_rows(caches, bids, layers, sel=None):
    """Scatter rows from :func:`extract_block_rows` into physical blocks
    ``bids`` of every paged layer.  ``sel`` picks which record rows feed
    which bid (``bids[i] <- rows[sel[i]]``; default: all rows in order).
    ``kq`` rows are spliced only when both the record and the cache carry
    them — a caller whose scales moved since extraction must skip/repack
    instead (:func:`repack_block_planes`)."""
    idx = jnp.asarray(list(bids), jnp.int32)

    def put(c, i):
        rows = layers[i]
        stacked = c["table"].ndim == 3
        new = dict(c)
        for field in ("k", "v", "pos", "kq"):
            if field not in rows or field not in c:
                continue
            val = jnp.asarray(rows[field]).astype(c[field].dtype)
            if sel is not None:
                s = jnp.asarray(list(sel), jnp.int32)
                val = val[:, s] if stacked else val[s]
            new[field] = (c[field].at[:, idx].set(val) if stacked
                          else c[field].at[idx].set(val))
        return new

    return map_paged_layers(caches, put)


def requant_plane_pools(caches):
    """Rebuild every packed K bit-plane pool from its f32 pool under the
    current amax scales.  ``pack_pool_planes`` is a pure function of
    (f32 pool, amax), so the rebuilt planes are bit-identical to an
    incrementally maintained pool whose last requant happened at the
    current scales — the fix-up step after any operation that moves
    ``k_amax`` out from under stored planes (detached-prefix amax merge,
    store injection that grew the scale)."""
    def rq(c, _i):
        if "kq" not in c:
            return c
        stacked = c["table"].ndim == 3
        bits = c["kq"].shape[2] if stacked else c["kq"].shape[1]
        kf = c["k"].astype(jnp.float32)
        if stacked:
            kq = jax.vmap(
                lambda kp, am: qlib.pack_pool_planes(kp, am, bits)
            )(kf, c["k_amax"])
        else:
            kq = qlib.pack_pool_planes(kf, c["k_amax"], bits)
        return dict(c, kq=kq.astype(c["kq"].dtype))

    return map_paged_layers(caches, rq)


def repack_block_planes(caches, bids):
    """Rebuild the packed planes of just blocks ``bids`` from their (just
    spliced) f32 rows under the CURRENT scales — the no-growth injection
    path.  Bit-identical to the incremental write rule quantizing the
    same tokens under the same unchanged scale, at O(len(bids)) cost
    instead of a whole-pool requant."""
    idx = jnp.asarray(list(bids), jnp.int32)

    def rp(c, _i):
        if "kq" not in c:
            return c
        stacked = c["table"].ndim == 3
        bits = c["kq"].shape[2] if stacked else c["kq"].shape[1]
        if stacked:
            packed = jax.vmap(
                lambda kp, am: qlib.pack_pool_planes(kp, am, bits)
            )(c["k"][:, idx].astype(jnp.float32), c["k_amax"])
            return dict(c, kq=c["kq"].at[:, idx].set(
                packed.astype(c["kq"].dtype)))
        packed = qlib.pack_pool_planes(c["k"][idx].astype(jnp.float32),
                                       c["k_amax"], bits)
        return dict(c, kq=c["kq"].at[idx].set(packed.astype(c["kq"].dtype)))

    return map_paged_layers(caches, rp)


def apply_inject_amax_rule(caches, layers, groups):
    """Replay the cache-write scale rule for store-injected rows, one
    application per chunk group — exactly the trajectory chunked prefill
    of the same tokens would have produced.

    ``layers`` pairs with the paged layers (an :func:`extract_block_rows`
    result); ``groups`` is a list of chunk groups, each a list of
    ``(row, lo, hi)`` — a record row index plus the token-offset window
    within that block belonging to the group (chunk boundaries need not
    align with page boundaries).  Host-side numpy on purpose: ``abs`` and
    ``max`` are exact, and the float32 ``AMAX_HEADROOM`` multiply rounds
    identically to the device rule in ``_update_plane_pool``, so the
    resulting leaves are bit-identical to the recompute reference's.

    Returns ``(new_caches, k_grew)`` — ``k_grew`` True iff any K scale
    moved (the caller must then :func:`requant_plane_pools`, mirroring
    the reference's growth-triggered whole-pool requant; otherwise
    :func:`repack_block_planes` of the injected blocks suffices)."""
    import numpy as np
    headroom = np.float32(AMAX_HEADROOM)
    k_grew = [False]

    def window_hi(rows, stacked, row, lo, hi):
        if stacked:                       # [reps, nrows, bs, H, D]
            w = np.abs(rows[:, row, lo:hi])
            return w.max(axis=(1, 3), initial=np.float32(0.0))
        w = np.abs(rows[row, lo:hi])      # [hi-lo, H, D]
        return w.max(axis=(0, 2), initial=np.float32(0.0))

    def upd(c, i):
        if "k_amax" not in c:
            return c
        rows = layers[i]
        stacked = c["table"].ndim == 3
        H = c["k_amax"].shape[-1]         # lane-dense rows -> heads
        k_rows = np.asarray(rows["k"], np.float32)
        k_rows = k_rows.reshape(k_rows.shape[:-1] + (H, -1))
        v_rows = np.asarray(rows["v"], np.float32)
        v_rows = v_rows.reshape(v_rows.shape[:-1] + (H, -1))
        k_amax = np.asarray(c["k_amax"], np.float32).copy()
        v_amax = np.asarray(c["v_amax"], np.float32).copy()
        k0, v0 = k_amax.copy(), v_amax.copy()
        for group in groups:
            k_hi = np.zeros(k_amax.shape, np.float32)
            v_hi = np.zeros(v_amax.shape, np.float32)
            for row, lo, hi in group:
                k_hi = np.maximum(k_hi, window_hi(k_rows, stacked, row,
                                                  lo, hi))
                v_hi = np.maximum(v_hi, window_hi(v_rows, stacked, row,
                                                  lo, hi))
            k_new = np.where(k_hi > k_amax, k_hi * headroom, k_amax)
            v_new = np.where(v_hi > v_amax, v_hi * headroom, v_amax)
            if (k_new > k_amax).any():
                k_grew[0] = True
            k_amax = k_new.astype(np.float32)
            v_amax = v_new.astype(np.float32)
        if (k_amax == k0).all() and (v_amax == v0).all():
            return c
        return dict(c, k_amax=jnp.asarray(k_amax),
                    v_amax=jnp.asarray(v_amax))

    return map_paged_layers(caches, upd), k_grew[0]
