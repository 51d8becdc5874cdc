"""Fused Sq-tiled paged BESF verify — speculative decoding's draft-block
scorer.

A slot brings an Sq-token **draft block** ([last sampled token, draft 1,
..., draft k]) and every query must be scored exactly as the Sq=1 decode
would have scored it at that position.  The payoff over running the
decode kernel Sq times is the paper's stage-fusion argument applied
across the draft block: one plane DMA per (page, round) serves every
query that still wants it (union liveness), and one V DMA per page serves
every query with survivors, while liveness, margins, plane counts and
survivors stay per (query, head) — bit for bit those of the pure-JAX
oracle ``core/besf.py:besf_attention_verify_paged``, which routes each
(slot, query) through the very ``_paged_decode_row`` the Sq=1 paths share.

Causal intra-draft masking comes from per-query fill levels: query i at
absolute position p sees cached tokens ``t_pos <= p`` — earlier draft
tokens (already scattered into the pool by the batched cache write) but
never later ones.  Padding queries (a slot that proposed fewer than k
drafts) ride along with fill level 0: every page is dead for them and
they fetch nothing.

The kernel itself is shared with the Sq=1 decode; its design notes live
in ``kernels/paged_decode.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.besf import BitStopperConfig, PagedVerifyOutput
from repro.kernels.paged_decode import paged_besf_pallas
from repro.kernels.runtime import resolve_interpret


@functools.partial(
    jax.jit, static_argnames=("cfg", "window", "interpret", "stats"))
def paged_bitstopper_verify(
    q: jax.Array,            # [B, Sq, Hq, D] — draft block per slot
    kq_pool: jax.Array,      # [P, bits, R, L] uint8 lane-dense plane pool
    v_pool: jax.Array,       # [P, bs, Hkv*Dv] (or [P, bs, Hkv, Dv]) V pool
    table: jax.Array,        # [B, MB] int32 block tables
    lengths: jax.Array,      # [B, Sq] int32 per-query fill levels
    q_positions: jax.Array,  # [B, Sq] int32 per-query absolute positions
    k_amax: jax.Array,       # [Hkv] pool-wide running max|K|
    v_amax: jax.Array,       # [Hkv] pool-wide running max|V|
    cfg: BitStopperConfig = BitStopperConfig(),
    window: int | None = None,
    interpret: bool | None = None,
    stats: bool = True,
) -> PagedVerifyOutput:
    """Run the fused Sq-tiled BESF verify kernel over every serving slot.

    Bit-identical observables to ``besf_attention_verify_paged`` (per-query
    plane counts, survivors, V-fetch decisions, attention output) while
    sharing each page's plane/V DMAs across the draft block.
    ``stats=False`` (the serving hot path) drops the survivor map and
    returns ``survivors``/``v_fetched`` as None, like the decode kernel."""
    interpret = resolve_interpret(interpret)
    B, Sq, Hq, _ = q.shape
    out, rounds, surv = paged_besf_pallas(
        q, kq_pool, v_pool, table, lengths, q_positions, k_amax, v_amax,
        cfg=cfg, window=window, interpret=interpret, stats=stats)
    if not stats:
        return PagedVerifyOutput(out=out, rounds=rounds, survivors=None,
                                 v_fetched=None)
    MB = surv.shape[1]
    surv = surv.astype(bool).reshape(B, MB, Sq, Hq, -1)
    survivors = jnp.moveaxis(surv, 1, 3).reshape(B, Sq, Hq, -1)
    v_fetched = jnp.moveaxis(surv.any(axis=(3, 4)), 1, 2)    # [B, Sq, MB]
    return PagedVerifyOutput(out=out, rounds=rounds, survivors=survivors,
                             v_fetched=v_fetched)
