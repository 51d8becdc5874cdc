"""BitStopper fused attention Pallas TPU kernel.

One kernel fuses the paper's whole pipeline (stage fusion is the point):

  bit-plane QK score formation  +  LATS pruning  +  online-softmax * V

TPU adaptation of the ASIC design (see DESIGN.md section 2):

* K is stored as **bit-packed planes** ``uint8[bits, S/8, d]`` (8 tokens per
  byte along the sequence axis).  Planes live in HBM (``pl.ANY``) and are
  DMA'd **manually** per (kv-block, round) with ``pltpu.make_async_copy``
  guarded by the block-liveness predicate — a terminated block's remaining
  planes are *never fetched*.  This is the DMA-level analogue of the paper's
  early termination: with BlockSpec auto-pipelining the bytes would move
  regardless of ``pl.when``, so manual copies are essential, not stylistic.
* The V block is likewise fetched manually only if at least one token in the
  block survived all rounds (V-PU traffic early-terminated).
* The LATS running threshold uses the **prefix max lower bound** across the
  kv blocks seen so far (conservative superset of the paper's global max,
  see ``core/block_adaptation.py`` — the oracle this kernel must match).
* BAP (bit-level asynchronous processing) maps to DMA/compute overlap: the
  copy for plane r+1 of a *live* block is issued before plane r's matmul is
  consumed (double-buffered plane scratch), and the Pallas grid pipelines
  across q tiles.

Numerics are exact: plane matmuls are f32 (every intermediate an integer
< 2^24), accumulated into an int32 partial-score scratch — bit-identical to
the int32 oracle.

Grid: ``(n_q_tiles, n_kv_blocks)`` with kv innermost/sequential so the
online-softmax state and the LATS prefix max persist in VMEM scratch.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import margins as margins_lib
from repro.core import quantization as qlib
from repro.core.besf import BitStopperConfig
from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


class KernelOutput(NamedTuple):
    out: jax.Array          # [Sq, dv] attention output
    rounds: jax.Array       # [n_qt, n_kb] int32 — planes fetched per block
    survivors: jax.Array    # [Sq, Sk] int8 — token-level keep mask


def _bitstopper_kernel(
    # scalar-prefetch/SMEM operands
    scalar_ref,             # SMEM f32[2]: [scale_total, alpha*radius_int]
    # VMEM-blocked operands
    q_ref,                  # [block_q, d] int32
    mmin_ref,               # [bits, block_q] f32
    mmax_ref,               # [bits, block_q] f32
    # HBM (manually DMA'd) operands
    kp_hbm,                 # [bits, Sk//8, d] uint8 bit-packed planes
    v_hbm,                  # [Sk, dv] f32
    # outputs
    out_ref,                # [block_q, dv]
    rounds_ref,             # [1, 1, n_kb] int32 — this q tile's row
    surv_ref,               # [block_q, block_k] int8
    # scratch
    plane_ref,              # [2, block_k//8, d] uint8 (double buffer)
    v_ref,                  # [block_k, dv] f32
    partial_ref,            # [block_q, block_k] int32
    m_ref, l_ref, acc_ref,  # online softmax state
    mlow_ref,               # [block_q] f32 — LATS prefix max lower bound
    plane_sem,              # DMA semaphores [2]
    v_sem,
    *,
    bits: int,
    block_q: int,
    block_k: int,
    min_rounds: int,
    causal: bool,
    q_offset: int,
):
    qi = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    d = q_ref.shape[-1]
    bk8 = block_k // 8

    scale_total = scalar_ref[0]
    alpha_radius = scalar_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mlow_ref[...] = jnp.full_like(mlow_ref, NEG_INF)

    partial_ref[...] = jnp.zeros_like(partial_ref)

    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    # Validity mask of this tile (causal or full).
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        vmask = rows >= cols
        blk_reachable = k_start <= q_start + block_q - 1
    else:
        vmask = jnp.ones((block_q, block_k), bool)
        blk_reachable = ki >= 0  # trivially true, traced

    def plane_weight(r):
        # MSB(sign) first: w_0 = -2^(bits-1), w_r = 2^(bits-1-r).
        mag = jax.lax.shift_left(jnp.int32(1), (bits - 1 - r).astype(jnp.int32))
        return jnp.where(r == 0, -mag, mag)

    q_f32 = q_ref[...].astype(jnp.float32)

    def start_plane_copy(r, slot):
        pltpu.make_async_copy(
            kp_hbm.at[r, pl.ds(ki * bk8, bk8), :],
            plane_ref.at[slot],
            plane_sem.at[slot],
        ).start()

    def wait_plane_copy(slot):
        pltpu.make_async_copy(
            kp_hbm.at[0, pl.ds(ki * bk8, bk8), :],  # shape donor only
            plane_ref.at[slot],
            plane_sem.at[slot],
        ).wait()

    # BAP prefetch: plane 0 of a reachable block is requested up front.
    @pl.when(blk_reachable)
    def _prefetch_first():
        start_plane_copy(0, 0)

    def round_body(r, carry):
        tok_alive, blk_live, rounds, mlow = carry
        slot = jax.lax.rem(r, 2)

        @pl.when(blk_live)
        def _consume_plane():
            wait_plane_copy(slot)
            packed = plane_ref[slot].astype(jnp.int32)           # [bk8, d]
            shifts = jax.lax.broadcasted_iota(jnp.int32, (bk8, 8, d), 1)
            unpacked = (packed[:, None, :] >> shifts) & 1        # [bk8, 8, d]
            plane = unpacked.reshape(block_k, d).astype(jnp.float32)
            # f32 dot is exact here: every partial product is an integer
            # bounded by 2048 * d < 2^24.
            delta = jax.lax.dot_general(
                q_f32, plane, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            partial_ref[...] += delta.astype(jnp.int32) * plane_weight(r)

        # BAP: issue next plane's DMA as soon as this one is consumed, before
        # the pruning decision math (overlap fetch with LATS compute).
        partial = partial_ref[...].astype(jnp.float32)
        lower = partial + mmin_ref[r][:, None]
        upper = partial + mmax_ref[r][:, None]
        low_here = jnp.max(jnp.where(vmask & tok_alive, lower, NEG_INF), axis=-1)
        mlow_new = jnp.where(blk_live, jnp.maximum(mlow, low_here), mlow)
        eta = mlow_new - alpha_radius
        keep = tok_alive & (upper >= eta[:, None]) & vmask
        keep = jnp.where(r < min_rounds - 1, tok_alive & vmask, keep)
        keep = jnp.where(blk_live, keep, tok_alive)
        blk_new = jnp.where(blk_live, jnp.any(keep), blk_live)
        rounds_new = rounds + blk_live.astype(jnp.int32)

        @pl.when(blk_new & (r + 1 < bits))
        def _prefetch_next():
            start_plane_copy(r + 1, 1 - slot)

        return keep, blk_new, rounds_new, mlow_new

    tok0 = vmask
    blk0 = blk_reachable & jnp.any(vmask)
    tok_alive, blk_live, rounds, mlow = jax.lax.fori_loop(
        0, bits, round_body,
        (tok0, blk0, jnp.zeros((), jnp.int32), mlow_ref[...]),
    )
    mlow_ref[...] = mlow
    # Lane ki of the row resident across the kv axis: no scalar store to
    # VMEM (Mosaic refuses one) and no (1, 1) block of a taller array.
    @pl.when(ki == 0)
    def _clear_rounds():
        rounds_ref[...] = jnp.zeros_like(rounds_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, rounds_ref.shape[1:], 1)
    rounds_ref[0] = jnp.where(lane == ki, rounds, rounds_ref[0])

    # Survivors: alive tokens of a block that completed every round hold
    # their exact INT12 scores (stage fusion: prediction work == execution).
    survived = tok_alive & (rounds == bits)
    surv_ref[...] = survived.astype(jnp.int8)

    @pl.when(jnp.any(survived))
    def _epilogue():
        logits = jnp.where(
            survived, partial_ref[...].astype(jnp.float32) * scale_total, NEG_INF
        )
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.where(survived, jnp.exp(logits - m_new[:, None]), 0.0)
        corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        # V fetched only for blocks with at least one survivor.
        cp = pltpu.make_async_copy(
            v_hbm.at[pl.ds(ki * block_k, block_k), :], v_ref, v_sem
        )
        cp.start()
        cp.wait()
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v_ref[...], preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        out_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(out_ref.dtype)


def _bitstopper_single(
    q_int: jax.Array,        # [Sq, d] int32
    k_packed: jax.Array,     # [bits, Sk//8, d] uint8
    v_eff: jax.Array,        # [Sk, dv] f32
    m_min: jax.Array,        # [bits, Sq] f32
    m_max: jax.Array,        # [bits, Sq] f32
    scalars: jax.Array,      # f32[2]: [scale_total, alpha*radius_int]
    *,
    cfg: BitStopperConfig,
    block_q: int,
    block_k: int,
    causal: bool,
    interpret: bool,
) -> KernelOutput:
    Sq, d = q_int.shape
    bits = cfg.bits
    Sk = k_packed.shape[1] * 8
    dv = v_eff.shape[-1]
    assert Sq % block_q == 0 and Sk % block_k == 0 and block_k % 8 == 0
    n_qt, n_kb = Sq // block_q, Sk // block_k
    grid = (n_qt, n_kb)

    kernel = functools.partial(
        _bitstopper_kernel,
        bits=bits,
        block_q=block_q,
        block_k=block_k,
        min_rounds=cfg.min_rounds,
        causal=causal,
        q_offset=Sk - Sq if causal else 0,
    )
    out, rounds, surv = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                      # scalars
            pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),         # q
            pl.BlockSpec((bits, block_q), lambda qi, ki: (0, qi)),      # m_min
            pl.BlockSpec((bits, block_q), lambda qi, ki: (0, qi)),      # m_max
            pl.BlockSpec(memory_space=pl.ANY),                          # k planes
            pl.BlockSpec(memory_space=pl.ANY),                          # v
        ],
        out_specs=[
            pl.BlockSpec((block_q, dv), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((1, 1, n_kb), lambda qi, ki: (qi, 0, 0)),
            pl.BlockSpec((block_q, block_k), lambda qi, ki: (qi, ki)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Sq, dv), v_eff.dtype),
            jax.ShapeDtypeStruct((n_qt, 1, n_kb), jnp.int32),
            jax.ShapeDtypeStruct((Sq, Sk), jnp.int8),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_k // 8, d), jnp.uint8),    # plane double buffer
            pltpu.VMEM((block_k, dv), jnp.float32),         # v block
            pltpu.VMEM((block_q, block_k), jnp.int32),      # partial scores
            pltpu.VMEM((block_q,), jnp.float32),            # m
            pltpu.VMEM((block_q,), jnp.float32),            # l
            pltpu.VMEM((block_q, dv), jnp.float32),         # acc
            pltpu.VMEM((block_q,), jnp.float32),            # LATS prefix max
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(scalars, q_int, m_min, m_max, k_packed, v_eff)
    return KernelOutput(out=out, rounds=rounds[:, 0], survivors=surv)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_q", "block_k", "causal", "interpret"),
)
def bitstopper_attention_kernel(
    q: jax.Array,            # [..., Sq, d] float
    k: jax.Array,            # [..., Sk, d] float
    v: jax.Array,            # [..., Sk, dv] float
    cfg: BitStopperConfig = BitStopperConfig(),
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = False,
    interpret: bool | None = None,
) -> KernelOutput:
    """Quantize + pack + run the fused BitStopper kernel.

    Leading batch/head dims are vmapped.  ``interpret=None`` auto-resolves:
    compiled on TPU, interpreted (the CPU validation mode) everywhere else;
    an explicit bool forces either mode.
    """
    interpret = resolve_interpret(interpret)
    d = q.shape[-1]
    sm_scale = 1.0 / (d ** 0.5)
    bits = cfg.bits

    def prep_and_run(q2, k2, v2):
        q_int, qp = qlib.quantize(q2, bits)
        k_int, kp = qlib.quantize(k2, bits)
        planes = qlib.to_bitplanes(k_int, bits)
        k_packed = qlib.pack_planes_seq(planes)
        m_min, m_max = margins_lib.bit_margins(q_int, bits)
        scale_total = qp.scale * kp.scale * sm_scale
        radius_int = cfg.radius / scale_total
        scalars = jnp.stack([scale_total, cfg.alpha * radius_int]).astype(jnp.float32)
        if cfg.quantize_v:
            v_int, vp = qlib.quantize(v2, bits)
            v_eff = qlib.dequantize(v_int, vp)
        else:
            v_eff = v2.astype(jnp.float32)
        bq = min(block_q, q2.shape[0])
        return _bitstopper_single(
            q_int, k_packed, v_eff, m_min, m_max, scalars,
            cfg=cfg, block_q=bq, block_k=min(block_k, k2.shape[0]),
            causal=causal, interpret=interpret,
        )

    if q.ndim == 2:
        return prep_and_run(q, k, v)
    flat_q = q.reshape((-1,) + q.shape[-2:])
    flat_k = k.reshape((-1,) + k.shape[-2:])
    flat_v = v.reshape((-1,) + v.shape[-2:])
    res = jax.vmap(prep_and_run)(flat_q, flat_k, flat_v)
    shape = q.shape[:-2]
    return jax.tree_util.tree_map(
        lambda x: x.reshape(shape + x.shape[1:]), res
    )
