"""Fused paged BESF Pallas TPU kernel — serving's per-token hot path.

One kernel serves both paged BitStopper entry points: the Sq=1 decode
(:func:`paged_bitstopper_decode`, below) and the Sq-token speculative
verify (``kernels/paged_verify.py``), which scores a whole draft block
per slot.  Decode is the verify kernel at Sq=1: with one query per slot
the union liveness below *is* the query's own liveness.

DESIGN — mapping BitStopper (BESF / LATS / BAP) onto paged-DMA decode
=====================================================================

The serving KV cache is a batch-free block pool: ``[pool_blocks, ...]``
physical pages addressed through per-slot block tables.  The kernel walks
the *physical* pages directly; no dense per-slot view is materialized:

* **Paging via scalar prefetch.**  Block tables ride in SMEM
  (``PrefetchScalarGridSpec``), so the kernel computes every DMA address
  itself: grid ``(slot, kv_page)`` with the page axis innermost and
  sequential.  A page outside every query's reach runs **nothing** — no
  DMA and no compute: the wrapper passes each slot's page range
  ``[lo, hi)`` (:func:`page_bounds`, from the same fill levels, positions
  and window the kernel masks with) as two more scalar-prefetch operands,
  and the scalar core skips the whole per-page body of a grid step
  outside it.  Per-step traffic and time scale with actual fill, not with
  the padded table width; only the grid step's own overhead remains.
* **BESF at page granularity.**  K lives pre-quantized in the incremental
  bit-plane pool (``uint8[pool_blocks, bits, R, Hkv*D/C]``, packed 8
  tokens/byte at cache-write time under the pool-wide running per-KV-head
  scale, in the lane-dense row layout of
  ``core/quantization.py:pack_pool_planes`` — see
  ``models/attention.py:_update_plane_pool``).
  Planes are DMA'd **manually, one plane per round**, guarded by the LATS
  liveness predicate: once every (head, token) candidate of a page is
  pruned, the page's remaining planes are *never fetched*.  This is the
  paper's bit-serial early termination, enforced at the DMA level — with
  BlockSpec auto-pipelining the bytes would move regardless of ``pl.when``.
* **One plane DMA per (page, round) for the whole draft block.**  The
  plane is fetched when *any* query still wants it (union liveness) and
  consumed by all of them; liveness, margins, prefix-max lower bounds,
  plane counts and survivors stay per (query, head).  A query whose page
  died keeps receiving plane deltas into the shared partial-score scratch
  (the plane was fetched for a live neighbour) — unobservable: pruned
  candidates feed neither thresholds (frozen ``keep``), nor ``mlow``
  (gated on the query's own liveness), nor logits (survivors require all
  ``bits`` rounds, in which case every plane was accumulated anyway).
* **LATS.**  Per query head, the pruning threshold uses the **prefix max
  lower bound** over the pages seen so far (the same conservative superset
  of the paper's global max as the prefill kernel, oracle'd by
  ``core/block_adaptation.py``); margins come from the per-(query, head)
  INT12 query, computed host-side.
* **Early-terminated V.**  A page's V is fetched once, and only if at
  least one token of one query survives all rounds; each query's
  online-softmax update is predicated on its *own* survivors.
* **BAP.**  Bit-level asynchronous processing maps to DMA/compute overlap:
  plane r+1 of a live page is requested (double-buffered plane scratch)
  before round r's pruning math runs, and the whole epilogue (softmax
  rescale + V matmul) is predicated off for survivor-free pages.

Layout (what Mosaic accepts): every per-(query, head) quantity is a
**row** of a 2-D ``[Sq*Hq, n]`` array (row ``s*Hq + h``); per-row scalars
are ``[rows, 1]`` columns and the margin LUTs ``[rows, bits]`` tables read
one lane per round.  Every VMEM block spans the full extent of its last
two dims, the softmax state is 2-D scratch, and per-query plane counts
are written as lane ``j`` of a ``[Sq, MB]`` block resident across the page
axis — the kernel stores no scalar to VMEM.  The pools are stored
lane-dense, heads merged into the minor dim: a DMA slices only leading
dims and moves whole (8, 128) tiles, with no padding of a 64-wide head
dim.  So the plane matmul runs over all KV heads' lanes at once against a
**block-diagonal** query (row ``(s, h)`` is zero outside its own KV
head's lanes), and the V matmul yields every KV head's lanes per row, of
which the wrapper keeps the row's own head.

Numerics are exact: the INT12 query rides as two bf16 halves
(``q = 64 * hi + lo``, ``|hi| <= 32``, ``0 <= lo < 64``, both exact in
bf16), so each plane matmul is two one-pass bf16 MXU dots whose f32 sums
are integers < 2^24 (the zero lanes add exact zeros), accumulated into an
int32 partial-score scratch.  The pure-JAX
oracles this kernel must match bit for bit are
``core/besf.py:besf_attention_decode_paged`` / ``besf_attention_verify_
paged`` — same page order, same online-softmax op order, same pool-wide
quant scales, the same per-query einsums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantization as qlib
from repro.core.besf import BitStopperConfig, PagedDecodeOutput, \
    paged_decode_prep
from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _paged_besf_kernel(
    # scalar-prefetch (SMEM)
    tables_ref,             # [B, MB] int32 — logical -> physical page
    lo_ref,                 # [B] int32 — first page any query reaches
    hi_ref,                 # [B] int32 — one past the last such page
    # VMEM-blocked operands; rows are (query, head) pairs, row = s*Hq + h
    qpos_ref,               # [1, SH, 1] int32 — absolute query position
    len_ref,                # [1, SH, 1] int32 — query's fill level
    q_ref,                  # [1, 2, SH, C*L] bf16 — block-diagonal
                            # queries as q = 64 * q[0] + q[1]
    mmin_ref,               # [1, SH, bits] f32 — LATS margin LUT (min)
    mmax_ref,               # [1, SH, bits] f32 — LATS margin LUT (max)
    st_ref,                 # [1, SH, 1] f32 — scale_total
    ar_ref,                 # [1, SH, 1] f32 — alpha * radius_int
    vs_ref,                 # [1, Hkv*Dv] f32 — V quant scale per lane
    # HBM (manually DMA'd) lane-dense pools
    kq_hbm,                 # [P, bits, R, L] uint8 bit-plane pool
    v_hbm,                  # [P, bs, Hkv*Dv] V pool
    # outputs
    out_ref,                # [1, SH, Hkv*Dv] — every KV head's lanes
    rounds_ref,             # [1, Sq, MB] int32 — planes fetched per query
    *rest,                  # [surv_ref [1, 1, SH, bs] int8 if stats] +
                            # scratch: plane double buffer, V page,
                            # partial scores, LATS prefix max, m, l, acc,
                            # plane / V DMA semaphores
    bits: int,
    page_size: int,
    n_queries: int,
    chunks: int,
    min_rounds: int,
    quantize_v: bool,
    window: int | None,
    stats: bool,
):
    surv_ref = rest[0] if stats else None
    (plane_ref, v_ref, partial_ref, mlow_ref, m_ref, l_ref, acc_ref,
     plane_sem, v_sem) = rest[1:] if stats else rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    bs = page_size
    bs8 = bs // 8
    Sq = n_queries
    C = chunks
    SH = q_ref.shape[2]
    L = kq_hbm.shape[3]
    Hq = SH // Sq

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mlow_ref[...] = jnp.full_like(mlow_ref, NEG_INF)
        rounds_ref[...] = jnp.zeros_like(rounds_ref)

    # A page outside [lo, hi) holds no token any query of this slot can
    # reach: the step runs nothing — no DMA, no plane rounds — and leaves
    # every output as _init made it (its plane count lane stays 0).
    reached = (j >= lo_ref[b]) & (j < hi_ref[b])

    @pl.when(reached)
    def _page():
        partial_ref[...] = jnp.zeros_like(partial_ref)

        phys = tables_ref[b, j]

        # Per-row validity of this page's token slots: causal against the
        # query's own position AND its own fill level (padding queries carry
        # length 0, making every page dead for them).
        t_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (SH, bs), 1)
        q_pos = qpos_ref[0]                                       # [SH, 1]
        valid_b = (t_pos <= q_pos) & (t_pos < len_ref[0])         # [SH, bs]
        if window is not None:
            valid_b &= t_pos > q_pos - window

        def per_query(x):
            """[SH, n] bool -> [SH, 1] bool: any over the query's rows."""
            row = jnp.max(x.astype(jnp.int32), axis=1, keepdims=True)
            grp = jnp.max(row.reshape(Sq, Hq, 1), axis=1, keepdims=True)
            return jnp.broadcast_to(grp, (Sq, Hq, 1)).reshape(SH, 1) > 0

        blk0 = per_query(valid_b)                                 # [SH, 1]
        alpha_radius = ar_ref[0]                                  # [SH, 1]
        q_hi, q_lo = q_ref[0, 0], q_ref[0, 1]                     # [SH, C*L]
        shifts = jax.lax.broadcasted_iota(jnp.int32, (8, L), 0)
        lane_r = jax.lax.broadcasted_iota(jnp.int32, (SH, bits), 1)

        def plane_weight(r):
            mag = jax.lax.shift_left(jnp.int32(1),
                                     (bits - 1 - r).astype(jnp.int32))
            return jnp.where(r == 0, -mag, mag)

        def margin(lut_ref, r):
            # Lane r of the [SH, bits] LUT as a [SH, 1] column (exact: one
            # term plus zeros).
            return jnp.sum(jnp.where(lane_r == r, lut_ref[0], 0.0), axis=1,
                           keepdims=True)

        def start_plane_copy(r, slot):
            pltpu.make_async_copy(
                kq_hbm.at[phys, r], plane_ref.at[slot], plane_sem.at[slot],
            ).start()

        def wait_plane_copy(slot):
            pltpu.make_async_copy(
                kq_hbm.at[0, 0],                       # shape donor only
                plane_ref.at[slot], plane_sem.at[slot],
            ).wait()

        # BAP prefetch: plane 0 moves once if ANY query can reach this page.
        @pl.when(jnp.any(blk0))
        def _prefetch_first():
            start_plane_copy(0, 0)

        def round_body(r, carry):
            # Liveness rides the loop carry as int32 (Mosaic carries no i1
            # vectors through scf.for).
            tok_alive, blk_live, rounds, mlow = carry
            tok_alive, blk_live = tok_alive > 0, blk_live > 0
            slot = jax.lax.rem(r, 2)
            # Per-query plane accounting: only queries whose page is still
            # live consumed this plane (the DMA itself is shared).
            rounds_new = rounds + blk_live.astype(jnp.int32)

            @pl.when(jnp.any(blk_live))
            def _consume_plane():
                wait_plane_copy(slot)
                packed = plane_ref[slot].astype(jnp.int32)        # [R, L]
                # Row b*C + c holds byte group b's head-dim chunk c: unpacked,
                # byte groups stack into the chunk's [bs, L] 0/1 plane.  The
                # 12-bit query rides as two bf16 halves, each exact in bf16
                # (|q_hi| <= 32, 0 <= q_lo < 64), so each is one MXU pass
                # whose f32 sum of integers is exact, and 64 * hi + lo is the
                # exact integer score.
                delta = 0
                for c in range(C):
                    plane = jnp.concatenate(
                        [(packed[b * C + c:b * C + c + 1] >> shifts) & 1
                         for b in range(bs8)], axis=0
                    ).astype(jnp.float32).astype(jnp.bfloat16)

                    def dot(x):
                        return jax.lax.dot_general(
                            x[:, c * L:(c + 1) * L], plane,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [SH, bs]

                    delta = delta + (dot(q_hi) * 64 + dot(q_lo))
                partial_ref[...] += delta.astype(jnp.int32) * plane_weight(r)

            partial = partial_ref[...].astype(jnp.float32)
            lower = partial + margin(mmin_ref, r)
            upper = partial + margin(mmax_ref, r)
            low_here = jnp.max(jnp.where(valid_b & tok_alive, lower, NEG_INF),
                               axis=1, keepdims=True)
            mlow_new = jnp.where(blk_live, jnp.maximum(mlow, low_here), mlow)
            eta = mlow_new - alpha_radius
            # Boolean algebra, not jnp.where: Mosaic cannot select between i1
            # vectors.  Rounds before min_rounds keep every valid candidate; a
            # dead page's liveness stays frozen.
            keep = tok_alive & valid_b & ((upper >= eta)
                                          | (r < min_rounds - 1))
            keep = (blk_live & keep) | (~blk_live & tok_alive)
            blk_new = blk_live & per_query(keep)

            # BAP: next plane requested as soon as any query still wants it.
            @pl.when(jnp.any(blk_new) & (r + 1 < bits))
            def _prefetch_next():
                start_plane_copy(r + 1, 1 - slot)

            return (keep.astype(jnp.int32), blk_new.astype(jnp.int32),
                    rounds_new, mlow_new)

        tok_alive, _, rounds, mlow = jax.lax.fori_loop(
            0, bits, round_body,
            (valid_b.astype(jnp.int32), blk0.astype(jnp.int32),
             jnp.zeros((SH, 1), jnp.int32), mlow_ref[...]),
        )
        mlow_ref[...] = mlow
        rounds_q = jnp.max(rounds.reshape(Sq, Hq, 1), axis=1)     # [Sq, 1]
        lane_j = jax.lax.broadcasted_iota(jnp.int32, rounds_ref.shape[1:], 1)
        rounds_ref[0] = jnp.where(lane_j == j, rounds_q, rounds_ref[0])

        survived = (tok_alive > 0) & (rounds == bits)             # [SH, bs]
        if stats:
            surv_ref[0, 0] = survived.astype(jnp.int8)
        any_surv = per_query(survived)                            # [SH, 1]

        @pl.when(jnp.any(any_surv))
        def _epilogue():
            logits = jnp.where(
                survived, partial_ref[...].astype(jnp.float32) * st_ref[0],
                NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
            p = jnp.where(survived, jnp.exp(logits - m_new), 0.0)
            corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
            l_new = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            # One V DMA serves every query with survivors on this page.
            cp = pltpu.make_async_copy(v_hbm.at[phys], v_ref, v_sem)
            cp.start()
            cp.wait()
            v = v_ref[...].astype(jnp.float32)                # [bs, Hkv*Dv]
            if quantize_v:
                vs = vs_ref[...]
                v_eff = (qlib.quantize_with_scale(v, vs, bits)
                         .astype(jnp.float32) * vs)
            else:
                v_eff = v
            # Every row against every KV head's V lanes: row (s, h) is right
            # in its own head's lanes, and the wrapper keeps only those.
            upd = jnp.dot(p, v_eff, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
            acc_new = acc_ref[...] * corr + upd
            # Each query commits its softmax state only if IT had survivors —
            # the oracle's where(any_surv, new, old), per query.
            m_ref[...] = jnp.where(any_surv, m_new, m_prev)
            l_ref[...] = jnp.where(any_surv, l_new, l_ref[...])
            acc_ref[...] = jnp.where(any_surv, acc_new, acc_ref[...])

    if stats:
        @pl.when(jnp.logical_not(reached))
        def _no_survivors():
            surv_ref[...] = jnp.zeros_like(surv_ref)

    @pl.when(j == nj - 1)
    def _finalize():
        out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(out_ref.dtype)


def page_bounds(lengths, q_positions, page_size: int,
                window: int | None = None, xp=jnp):
    """Per-slot page range ``[lo, hi)`` holding every token some query of
    the slot attends, from the same bounds the kernel masks with: query
    ``s`` sees tokens ``max(0, pos - window + 1) <= t < min(len, pos + 1)``.
    ``lengths`` / ``q_positions`` are ``[B, Sq]``; a slot no query reaches
    (empty, still prefilling, padding only) gets ``lo == hi == 0``.
    ``xp`` is ``jnp`` inside the kernel wrapper, ``np`` on the host."""
    hi_tok = xp.minimum(lengths, q_positions + 1)
    lo_tok = (xp.zeros_like(hi_tok) if window is None
              else xp.maximum(q_positions - window + 1, 0))
    live = hi_tok > lo_tok
    hi = xp.where(live, -(-hi_tok // page_size), 0).max(axis=-1)
    lo = xp.where(live, lo_tok // page_size, hi[..., None]).min(axis=-1)
    return lo.astype(xp.int32), hi.astype(xp.int32)


def paged_besf_pallas(q, kq_pool, v_pool, table, lengths, q_positions,
                      k_amax, v_amax, *, cfg: BitStopperConfig,
                      window: int | None, interpret: bool, stats: bool):
    """Build and run the fused paged BESF kernel over a draft block per
    slot: q [B, Sq, Hq, D], lengths / q_positions [B, Sq].

    Returns ``(out [B, Sq, Hq, Dv], rounds [B, Sq, MB], surv)`` where
    ``surv`` is the int8 survivor map ``[B, MB, Sq*Hq, page]`` (None
    unless ``stats``).  ``interpret`` must already be resolved — the
    public entry points route it through ``resolve_interpret``."""
    B, Sq, Hq, D = q.shape
    P, bits, R, L = kq_pool.shape
    Hkv = k_amax.shape[0]
    bs = v_pool.shape[1]
    C = qlib.plane_chunks(bs)
    X = L // Hkv
    # Head-split [P, bs, Hkv, Dv] and lane-dense [P, bs, Hkv*Dv] V pools
    # are the same bytes; the kernel DMAs the lane-dense form.
    v_pool = v_pool.reshape(P, bs, -1)
    Dv = v_pool.shape[-1] // Hkv
    MB = table.shape[1]
    SH = Sq * Hq
    G = Hq // Hkv
    assert bits == cfg.bits and R == bs // 8 * C and C * X == D
    assert bits <= 15, "the query's high half (q >> 6) must be exact in bf16"

    # Shared host-side prep with the oracle: (slot, query) rows flatten to
    # B*Sq independent Sq=1 decodes as far as quantization is concerned.
    prep = paged_decode_prep(q.reshape(B * Sq, Hq, D), k_amax, v_amax,
                             Hkv, cfg)
    q_int, m_min, m_max, scale_total, alpha_radius, _, v_scale = prep
    # Block-diagonal queries in the plane pool's lane order: dim c*X + x
    # of head h sits at lane c*L + (h // G)*X + x, zero under every other
    # KV head (exact zeros in the integer plane scores).
    own = (jnp.arange(Hq) // G)[:, None] == jnp.arange(Hkv)[None]
    q_bd = jnp.where(own[None, :, None, :, None],
                     q_int.reshape(B * Sq, Hq, C, 1, X), 0)

    def rows(x):                                  # [B*Sq, Hq, ..] -> [B, SH, n]
        return x.reshape(B, SH, -1)

    # q = 64 * (q >> 6) + (q & 63): both halves are small integers (the
    # query is INT12), exact in bf16, for the kernel's one-pass MXU dots.
    q_split = jnp.stack([rows(q_bd >> 6), rows(q_bd & 63)],
                        axis=1).astype(jnp.bfloat16)      # [B, 2, SH, C*L]

    def lut(m):                                   # [bits, B*Sq, Hq]
        return jnp.moveaxis(m, 0, -1).reshape(B, SH, bits)

    def per_row(x):                               # [B, Sq] -> [B, SH, 1]
        return jnp.repeat(x.astype(jnp.int32), Hq, axis=1)[..., None]

    lo, hi = page_bounds(lengths.astype(jnp.int32),
                         q_positions.astype(jnp.int32), bs, window)
    kernel = functools.partial(
        _paged_besf_kernel,
        bits=bits, page_size=bs, n_queries=Sq, chunks=C,
        min_rounds=cfg.min_rounds, quantize_v=cfg.quantize_v,
        window=window, stats=stats,
    )

    def row_spec(n):
        return pl.BlockSpec((1, SH, n), lambda b, j, *_: (b, 0, 0))

    out_specs = [row_spec(Hkv * Dv),
                 pl.BlockSpec((1, Sq, MB), lambda b, j, *_: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, SH, Hkv * Dv), jnp.float32),
                 jax.ShapeDtypeStruct((B, Sq, MB), jnp.int32)]
    if stats:
        out_specs.append(
            pl.BlockSpec((1, 1, SH, bs), lambda b, j, *_: (b, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, MB, SH, bs), jnp.int8))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # tables, page range lo, hi
        grid=(B, MB),
        in_specs=[
            row_spec(1),                                         # q_pos
            row_spec(1),                                         # lengths
            pl.BlockSpec((1, 2, SH, C * L),                      # q_bd
                         lambda b, j, *_: (b, 0, 0, 0)),
            row_spec(bits),                                      # m_min
            row_spec(bits),                                      # m_max
            row_spec(1),                                         # scale_total
            row_spec(1),                                         # alpha*radius
            pl.BlockSpec((1, Hkv * Dv), lambda b, j, *_: (0, 0)),  # v_scale
            pl.BlockSpec(memory_space=pl.ANY),                   # kq pool
            pl.BlockSpec(memory_space=pl.ANY),                   # v pool
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, R, L), jnp.uint8),          # plane double buffer
            pltpu.VMEM((bs, Hkv * Dv), v_pool.dtype),  # v page
            pltpu.VMEM((SH, bs), jnp.int32),           # partial scores
            pltpu.VMEM((SH, 1), jnp.float32),          # LATS prefix max
            pltpu.VMEM((SH, 1), jnp.float32),          # m
            pltpu.VMEM((SH, 1), jnp.float32),          # l
            pltpu.VMEM((SH, Hkv * Dv), jnp.float32),   # acc
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(table.astype(jnp.int32), lo, hi, per_row(q_positions),
      per_row(lengths),
      q_split, lut(m_min), lut(m_max), rows(scale_total),
      rows(alpha_radius), jnp.repeat(v_scale, Dv)[None], kq_pool, v_pool)
    # Row (s, h) keeps its own KV head's lanes.
    out = res[0].reshape(B, Sq, Hq, Hkv, Dv)
    own_head = (jnp.arange(Hq) // G)[None, None, :, None, None]
    out = jnp.take_along_axis(out, own_head, axis=3)[:, :, :, 0]
    return out, res[1], (res[2] if stats else None)


@functools.partial(
    jax.jit, static_argnames=("cfg", "window", "interpret", "stats"))
def paged_bitstopper_decode(
    q: jax.Array,            # [B, Hq, D] — one decode query per slot
    kq_pool: jax.Array,      # [P, bits, R, L] uint8 lane-dense plane pool
    v_pool: jax.Array,       # [P, bs, Hkv*Dv] (or [P, bs, Hkv, Dv]) V pool
    table: jax.Array,        # [B, MB] int32 block tables
    lengths: jax.Array,      # [B] int32 fill levels
    q_positions: jax.Array,  # [B] int32 absolute query positions
    k_amax: jax.Array,       # [Hkv] pool-wide running max|K|
    v_amax: jax.Array,       # [Hkv] pool-wide running max|V|
    cfg: BitStopperConfig = BitStopperConfig(),
    window: int | None = None,
    interpret: bool | None = None,
    stats: bool = True,
) -> PagedDecodeOutput:
    """Run the fused paged BESF decode kernel over every serving slot.

    Bit-identical observables to ``besf_attention_decode_paged`` (the
    pure-JAX gather fallback): per-page plane counts, token survivors,
    V-fetch decisions, and the attention output.  ``interpret=None``
    auto-resolves per backend (compiled on TPU, interpreted elsewhere).

    ``stats=False`` (the serving hot path) drops the per-page survivor
    map from the kernel's outputs — no survivor bytes are stored to HBM —
    and returns ``survivors``/``v_fetched`` as None.  Tests and the
    traffic model use ``stats=True``."""
    interpret = resolve_interpret(interpret)
    B, Hq, _ = q.shape
    out, rounds, surv = paged_besf_pallas(
        q[:, None], kq_pool, v_pool, table, lengths[:, None],
        q_positions[:, None], k_amax, v_amax, cfg=cfg, window=window,
        interpret=interpret, stats=stats)
    if not stats:
        return PagedDecodeOutput(out=out[:, 0], rounds=rounds[:, 0],
                                 survivors=None, v_fetched=None)
    surv = surv.astype(bool)                                  # [B,MB,Hq,bs]
    survivors = jnp.moveaxis(surv, 1, 2).reshape(B, Hq, -1)
    v_fetched = surv.any(axis=(2, 3))
    return PagedDecodeOutput(out=out[:, 0], rounds=rounds[:, 0],
                             survivors=survivors, v_fetched=v_fetched)
