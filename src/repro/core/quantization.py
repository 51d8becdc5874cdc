"""INT12 post-training quantization and bit-plane decomposition.

The paper quantizes Q, K, V to 12-bit integers (per-tensor, symmetric, 2's
complement) and decomposes each Key vector into twelve 1-bit planes, most
significant (sign) plane first.  For an N-bit 2's-complement integer
``c_{N-1} c_{N-2} ... c_0`` the value is

    x = -c_{N-1} 2^{N-1} + sum_{i=0}^{N-2} c_i 2^i            (paper Eq. 4)

so *plane r* (r = 0 is the MSB) has weight  w_0 = -2^{N-1}  and
w_r = 2^{N-1-r}  for r >= 1.  Every bit except the sign bit contributes a
non-negative amount, which is what makes the bit-level uncertainty margin
(margins.py) a valid interval bound.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

DEFAULT_BITS = 12


@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Symmetric per-tensor quantization parameters."""

    scale: jax.Array  # scalar, float32:  x_float ~= x_int * scale
    bits: int = DEFAULT_BITS

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1

    @property
    def qmin(self) -> int:
        return -(2 ** (self.bits - 1))


def plane_weights(bits: int = DEFAULT_BITS, dtype=jnp.float32) -> jax.Array:
    """Weight of each bit plane, MSB (sign) first: [-2^(b-1), 2^(b-2), ..., 1]."""
    w = 2.0 ** jnp.arange(bits - 1, -1, -1)
    return (w * jnp.where(jnp.arange(bits) == 0, -1.0, 1.0)).astype(dtype)


def quantize(x: jax.Array, bits: int = DEFAULT_BITS) -> tuple[jax.Array, QuantParams]:
    """Symmetric per-tensor PTQ.  Returns (int32 values, params)."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(x / scale), -(qmax + 1), qmax).astype(jnp.int32)
    return q, QuantParams(scale=scale.astype(jnp.float32), bits=bits)


def dequantize(q: jax.Array, params: QuantParams) -> jax.Array:
    return q.astype(jnp.float32) * params.scale


def scale_from_amax(amax: jax.Array, bits: int = DEFAULT_BITS) -> jax.Array:
    """Symmetric quant scale for a given max-abs value (same epsilon floor
    as :func:`quantize`, so ``quantize_with_scale(x, max|x|)`` is
    bit-identical to ``quantize(x)``)."""
    qmax = 2 ** (bits - 1) - 1
    return jnp.maximum(amax, 1e-12).astype(jnp.float32) / qmax


def quantize_with_scale(x: jax.Array, scale: jax.Array,
                        bits: int = DEFAULT_BITS) -> jax.Array:
    """Quantize under an externally-maintained scale (broadcast against
    ``x``).  The paged serving cache uses this with a *pool-wide running*
    max-abs per KV head: every request quantizes against the same scale, so
    bit planes stored in the shared block pool are valid for every block
    table that maps them (per-request scales would make a prefix-shared
    block's planes wrong for all but one owner)."""
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -(qmax + 1), qmax).astype(jnp.int32)


def to_bitplanes(q: jax.Array, bits: int = DEFAULT_BITS) -> jax.Array:
    """Decompose int32 2's-complement values into bit planes.

    Returns uint8 array of shape ``(bits,) + q.shape`` with plane 0 = MSB
    (sign).  ``q`` must lie in [-2^(bits-1), 2^(bits-1)-1].
    """
    # Reinterpret as unsigned 'bits'-wide field: x mod 2^bits.
    u = jnp.where(q < 0, q + (1 << bits), q).astype(jnp.uint32)
    shifts = jnp.arange(bits - 1, -1, -1, dtype=jnp.uint32)  # MSB first
    planes = (u[None, ...] >> shifts.reshape((bits,) + (1,) * q.ndim)) & 1
    return planes.astype(jnp.uint8)


def from_bitplanes(planes: jax.Array) -> jax.Array:
    """Inverse of :func:`to_bitplanes` → int32 values."""
    bits = planes.shape[0]
    w = plane_weights(bits, dtype=jnp.int32 if bits < 31 else jnp.int64)
    # int32 weights: plane 0 weight is -2^(bits-1).
    w = (2 ** jnp.arange(bits - 1, -1, -1)).astype(jnp.int32)
    w = w * jnp.where(jnp.arange(bits) == 0, -1, 1)
    return jnp.tensordot(w, planes.astype(jnp.int32), axes=1)


def partial_value(planes: jax.Array, r: int) -> jax.Array:
    """Value reconstructed from planes 0..r inclusive (remaining bits = 0)."""
    bits = planes.shape[0]
    w = (2 ** jnp.arange(bits - 1, -1, -1)).astype(jnp.int32)
    w = w * jnp.where(jnp.arange(bits) == 0, -1, 1)
    mask = (jnp.arange(bits) <= r).astype(jnp.int32)
    return jnp.tensordot(w * mask, planes.astype(jnp.int32), axes=1)


# ---------------------------------------------------------------------------
# Sequence-axis bit packing (TPU kernel storage layout).
#
# Plane r of K (shape [S, d]) is stored packed 8-tokens-per-byte along the
# sequence axis: uint8[S//8, d].  Token s's bit lives in byte s//8 at bit
# position (s % 8) (LSB-first within the byte).  The d axis stays minor so a
# d=128 lane dimension tiles perfectly in VMEM.
# ---------------------------------------------------------------------------


def pack_planes_seq(planes: jax.Array) -> jax.Array:
    """Pack ``uint8[bits, S, d]`` planes → ``uint8[bits, S//8, d]`` (S % 8 == 0)."""
    bits, S, d = planes.shape
    assert S % 8 == 0, f"sequence length {S} not a multiple of 8"
    p = planes.reshape(bits, S // 8, 8, d).astype(jnp.uint32)
    weights = (1 << jnp.arange(8, dtype=jnp.uint32)).reshape(1, 1, 8, 1)
    return jnp.sum(p * weights, axis=2).astype(jnp.uint8)


def unpack_planes_seq(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_planes_seq` → ``uint8[bits, S, d]``."""
    bits, S8, d = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint32).reshape(1, 1, 8, 1)
    u = (packed.astype(jnp.uint32)[:, :, None, :] >> shifts) & 1
    return u.reshape(bits, S8 * 8, d).astype(jnp.uint8)


def plane_chunks(page_size: int) -> int:
    """Head-dim chunks per byte-group row of the lane-dense plane pool.

    One page's plane is ``page_size // 8`` byte groups (8 tokens per byte)
    of ``H * D`` bytes.  A manual DMA of it is a whole number of (8, 128)
    tiles only if it spans a multiple of 8 rows, so each byte group is cut
    into ``C`` head-dim chunks, one row each: ``lcm(page_size // 8, 8)``
    rows per plane (8 at page 16)."""
    bs8 = page_size // 8
    return math.lcm(bs8, 8) // bs8


def plane_pool_shape(pool_blocks: int, bits: int, page_size: int,
                     lanes: int) -> tuple[int, int, int, int]:
    """Shape of the lane-dense plane pool (:func:`pack_pool_planes`) of a
    ``[pool_blocks, page_size, lanes]`` K pool, ``lanes = H * D``."""
    C = plane_chunks(page_size)
    return (pool_blocks, bits, page_size // 8 * C, lanes // C)


def pack_pool_planes(pool: jax.Array, amax: jax.Array,
                     bits: int = DEFAULT_BITS) -> jax.Array:
    """Quantize + bit-plane-pack a whole paged K pool in one shot.

    ``pool`` f32 ``[P, page_size, H, D]`` or lane-dense ``[P, page_size,
    H*D]`` (page_size % 8 == 0), ``amax`` ``[H]`` pool-wide running
    max-abs.  Token t of a page owns bit ``t % 8`` of byte group ``t // 8``
    (LSB-first, the :func:`pack_planes_seq` layout).  The result is the
    serving cache's lane-dense plane pool ``uint8[P, bits, R, H*D/C]``
    (``C = plane_chunks(page_size)``, ``R = page_size//8 * C``): row
    ``b*C + c`` of a plane holds byte group ``b``'s dims ``d = c*X + x``
    (``X = D/C``) at lane ``h*X + x`` — each KV head owns a contiguous lane
    range of every row, so splitting the lanes splits heads.

    This is the canonical definition the incremental write path, the paged
    decode kernel, and the benchmarks all share — the rescale-on-demand
    requant rebuilds the serving plane pool with exactly this function."""
    P, bs = pool.shape[:2]
    H = amax.shape[0]
    assert bs % 8 == 0, f"page size {bs} not a multiple of 8"
    scale = scale_from_amax(amax, bits)
    q = quantize_with_scale(pool.reshape(P, bs, H, -1),
                            scale[None, None, :, None], bits)
    planes = to_bitplanes(q, bits)                  # [bits, P, bs, H, D]
    pk = planes.reshape(bits, P, bs // 8, 8, H, -1).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))
    packed = jnp.sum(pk * weights.reshape(1, 1, 1, 8, 1, 1), axis=3)
    rows = to_plane_rows(packed.astype(jnp.uint8), plane_chunks(bs))
    return rows.reshape(bits, P, -1, rows.shape[-1]).transpose(1, 0, 2, 3)


def to_plane_rows(x: jax.Array, chunks: int) -> jax.Array:
    """``[..., H, D]`` -> ``[..., C, H*D/C]``: one byte group's (or one
    token's) head-split plane values in the row/lane order of the
    lane-dense plane pool (see :func:`pack_pool_planes`)."""
    *lead, H, D = x.shape
    x = x.reshape(*lead, H, chunks, D // chunks)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, chunks, H * (D // chunks))


@partial(jax.jit, static_argnames=("bits",))
def quantize_and_pack(k: jax.Array, bits: int = DEFAULT_BITS):
    """Convenience: float K [S, d] → (packed planes uint8[bits, S//8, d], scale)."""
    q, params = quantize(k, bits)
    planes = to_bitplanes(q, bits)
    return pack_planes_seq(planes), params.scale
