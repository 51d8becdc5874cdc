"""The transformer stack: embedding → scanned block segments → head.

* **scan-over-layers** per segment (stacked params) keeps HLO size O(1) in
  depth — essential for compiling the 61-layer 671B dry-run — and lets the
  XLA latency-hiding scheduler pipeline per-layer collectives.
* **remat** policies: "none" | "dots" (save matmul outputs) | "full".
* Decode threads a per-layer cache pytree through the same scan.
* Optional **MTP** head (DeepSeek-style multi-token prediction): one extra
  block over [h_t ; embed(next_token)] predicting token t+2.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import attention, init_attention, init_cache
from repro.models.config import BlockSpec, ModelConfig
from repro.models.mla import init_mla, init_mla_cache, mla_attention
from repro.models.moe import init_moe, moe_ffn
from repro.models.rglru import init_rglru, init_rglru_cache, rglru_forward
from repro.models.ssm import init_ssm, init_ssm_cache, ssm_forward
from repro.sharding.api import constrain, current_rules


# ---------------------------------------------------------------------------
# Block init / forward
# ---------------------------------------------------------------------------


def init_block(key, spec: BlockSpec, cfg: ModelConfig):
    km, kf = jax.random.split(key)
    dt = cfg.parameter_dtype
    p: dict[str, Any] = {"norm1": L.init_norm(cfg.d_model, cfg.norm, dt)}
    if spec.mixer in ("attn", "local_attn"):
        p["attn"] = init_attention(km, cfg.attn_config(spec.mixer == "local_attn"), dt)
    elif spec.mixer == "mla":
        p["mla"] = init_mla(km, cfg.mla_config(), dt)
    elif spec.mixer == "ssm":
        p["ssm"] = init_ssm(km, cfg.ssm_config(), dt)
    elif spec.mixer == "rglru":
        p["rglru"] = init_rglru(km, cfg.rglru_config(), dt)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["norm2"] = L.init_norm(cfg.d_model, cfg.norm, dt)
        if spec.ffn == "moe":
            p["moe"] = init_moe(kf, cfg.moe_config(), dt)
        else:
            p["ffn"] = L.init_mlp(kf, cfg.d_model, cfg.d_ff, cfg.act, False, dt)
    return p


def block_forward(p, x, positions, spec: BlockSpec, cfg: ModelConfig,
                  cache=None):
    """Returns (x, new_cache, aux_loss)."""
    h = L.norm(p["norm1"], x)
    if spec.mixer in ("attn", "local_attn"):
        out, new_cache = attention(
            p["attn"], h, positions, cfg.attn_config(spec.mixer == "local_attn"),
            cache)
    elif spec.mixer == "mla":
        out, new_cache = mla_attention(p["mla"], h, positions, cfg.mla_config(),
                                       cache)
    elif spec.mixer == "ssm":
        out, new_cache = ssm_forward(p["ssm"], h, cfg.ssm_config(), cache)
    elif spec.mixer == "rglru":
        out, new_cache = rglru_forward(p["rglru"], h, cfg.rglru_config(), cache)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn != "none":
        h2 = L.norm(p["norm2"], x)
        if spec.ffn == "moe":
            rules = current_rules()
            mesh = rules.mesh if rules is not None else None
            y, aux = moe_ffn(p["moe"], h2, cfg.moe_config(), mesh=mesh)
        else:
            with jax.named_scope("mlp"):
                y = L.mlp(p["ffn"], h2, cfg.act)
        x = x + y
    # Scan-carry contract: blocks always emit the activation dtype, no
    # matter how param/activation dtypes promoted inside the mixers.
    x = constrain(x.astype(cfg.activation_dtype), "batch", "seq", "embed")
    return x, new_cache, aux


def init_block_cache(spec: BlockSpec, cfg: ModelConfig, batch: int,
                     max_len: int, dtype=jnp.float32, per_slot: bool = False,
                     paged=None):
    if spec.mixer == "attn":
        return init_cache(cfg.attn_config(False), batch, max_len, dtype,
                          per_slot=per_slot, paged=paged)
    if spec.mixer == "local_attn":
        return init_cache(cfg.attn_config(True), batch, max_len, dtype,
                          ring=True, per_slot=per_slot, paged=paged)
    if per_slot or paged is not None:
        raise NotImplementedError(
            f"per-slot/paged serving caches support attn/local_attn mixers "
            f"only, got {spec.mixer!r}")
    if spec.mixer == "mla":
        return init_mla_cache(cfg.mla_config(), batch, max_len, dtype)
    if spec.mixer == "ssm":
        return init_ssm_cache(cfg.ssm_config(), batch, dtype)
    if spec.mixer == "rglru":
        return init_rglru_cache(cfg.rglru_config(), batch, dtype)
    raise ValueError(spec.mixer)


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig):
    keys = jax.random.split(key, 3 + len(cfg.segments))
    dt = cfg.parameter_dtype
    params: dict[str, Any] = {
        "embed": L.init_embedding(keys[0], cfg.vocab, cfg.d_model, dt),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm, dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_linear(keys[1], cfg.d_model, cfg.vocab,
                                          False, dt)
    for si, (unit, reps) in enumerate(cfg.segments):
        seg_keys = jax.random.split(keys[2 + si], reps)

        def init_unit(k):
            uks = jax.random.split(k, len(unit))
            return {f"b{i}": init_block(uks[i], unit[i], cfg)
                    for i in range(len(unit))}

        if cfg.scan_layers and reps > 1:
            params[f"seg{si}"] = jax.vmap(init_unit)(seg_keys)
        else:
            params[f"seg{si}"] = [init_unit(k) for k in seg_keys]
    if cfg.mtp:
        km1, km2 = jax.random.split(keys[-1])
        params["mtp_block"] = init_block(km1, cfg.segments[-1][0][-1], cfg)
        params["mtp_proj"] = L.init_linear(km2, 2 * cfg.d_model, cfg.d_model,
                                           False, dt)
        params["mtp_norm"] = L.init_norm(cfg.d_model, cfg.norm, dt)
    return params


def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def run_segments(params, x, positions, cfg: ModelConfig, caches=None):
    """caches: None or {segN: stacked cache pytree (or list)}."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict[str, Any] = {}
    for si, (unit, reps) in enumerate(cfg.segments):
        seg_p = params[f"seg{si}"]
        seg_c = caches.get(f"seg{si}") if caches is not None else None

        def unit_fwd(x, p_unit, c_unit):
            aux = jnp.zeros((), jnp.float32)
            ncs = {}
            for i, spec in enumerate(unit):
                c = c_unit[f"b{i}"] if c_unit is not None else None
                x, nc, a = block_forward(p_unit[f"b{i}"], x, positions, spec,
                                         cfg, c)
                aux = aux + a
                if nc is not None:
                    ncs[f"b{i}"] = nc
            return x, (ncs or None), aux

        if cfg.scan_layers and reps > 1:
            body = _remat_wrap(
                lambda x, pc: (lambda r: (r[0], (r[1], r[2])))(
                    unit_fwd(x, pc[0], pc[1])),
                cfg.remat,
            )
            x, (ncs, auxs) = jax.lax.scan(
                body, x, (seg_p, seg_c) if seg_c is not None else (seg_p, None))
            aux_total = aux_total + jnp.sum(auxs)
            if ncs is not None:
                new_caches[f"seg{si}"] = ncs
        else:
            seg_new = []
            for li in range(reps):
                c_unit = seg_c[li] if seg_c is not None else None
                x, ncs, a = unit_fwd(x, seg_p[li], c_unit)
                aux_total = aux_total + a
                seg_new.append(ncs)
            if any(c is not None for c in seg_new):
                new_caches[f"seg{si}"] = seg_new
    return x, (new_caches or None), aux_total


def forward(params, tokens, cfg: ModelConfig, caches=None,
            positions=None, embeds=None):
    """tokens [B, S] int32 (or ``embeds`` [B, S, D] for stubbed frontends).

    Returns (logits [B,S,vocab] f32, new_caches, aux_loss).
    """
    if embeds is None:
        x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    else:
        x = embeds.astype(cfg.activation_dtype)
    x = constrain(x, "batch", "seq", "embed")
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    with jax.named_scope("layers"):         # the layer scan
        x, new_caches, aux = run_segments(params, x, positions, cfg, caches)
    with jax.named_scope("lm_head"):
        h = L.norm(params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = L.unembed(params["embed"], h)
        else:
            logits = L.linear(params["unembed"], h).astype(jnp.float32)
        logits = constrain(logits, "batch", "seq", "vocab")
    return logits, new_caches, aux


def mtp_logits(params, tokens, h, cfg: ModelConfig, positions):
    """DeepSeek-style MTP: predict token t+2 from [h_t ; emb(token_{t+1})]."""
    emb_next = L.embed(params["embed"], jnp.roll(tokens, -1, axis=1))
    cat = jnp.concatenate([L.norm(params["mtp_norm"], h),
                           emb_next.astype(h.dtype)], axis=-1)
    x = L.linear(params["mtp_proj"], cat)
    spec = cfg.segments[-1][0][-1]
    x, _, _ = block_forward(params["mtp_block"], x, positions, spec, cfg)
    return L.unembed(params["embed"], L.norm(params["final_norm"], x))


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.float32, per_slot: bool = False, paged=None):
    """Stacked (scan-compatible) cache pytree for decode.

    ``per_slot=True`` builds the continuous-batching layout: each batch row
    is an independent serving slot with its own write cursor and
    slot-position map (see :func:`repro.models.attention.init_cache`).

    ``paged=PagedLayout(...)`` builds the block-pool layout instead: one
    batch-free K/V pool per layer, addressed through per-slot block tables
    ([batch, max_blocks_per_req] int32) — the serving engine owns block
    allocation and rewrites the ``table``/``length`` leaves between
    forwards.  BitStopper layers additionally carry the incremental
    bit-plane pool (``kq`` + ``k_amax``/``v_amax`` leaves) that the fused
    paged decode kernel consumes; those leaves are maintained by the cache
    write path and pass through the engine's table attachment untouched."""
    caches: dict[str, Any] = {}
    for si, (unit, reps) in enumerate(cfg.segments):
        def unit_cache(_):
            return {f"b{i}": init_block_cache(unit[i], cfg, batch, max_len,
                                              dtype, per_slot=per_slot,
                                              paged=paged)
                    for i in range(len(unit))}
        if cfg.scan_layers and reps > 1:
            caches[f"seg{si}"] = jax.vmap(unit_cache)(jnp.arange(reps))
        else:
            caches[f"seg{si}"] = [unit_cache(None) for _ in range(reps)]
    return caches
