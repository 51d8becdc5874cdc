"""Mixture-of-Experts FFN: shared + routed experts, top-k, capacity-based.

Dispatch is **sort + capacity buffers** (the GShard/MaxText pattern, index
arithmetic instead of one-hot tensors): token replicas are bucketed into a
``[E_local, capacity, D]`` buffer by (expert, position-in-expert) scatter,
processed with one *batched* matmul per FFN weight (static shapes, MXU-
friendly), and gathered back.  FLOPs are ``capacity_factor ×`` the active
expert FLOPs — never E× dense compute — so the roofline "useful-FLOPs"
ratio stays honest.  (lax.ragged_dot was measured to lower dense-with-
group-dim on this backend: 100 GB temp / 15× FLOPs for ONE qwen-moe layer
backward — see EXPERIMENTS.md §Perf hillclimb log.)

Distribution (under ``shard_map`` over the full mesh; tokens are sharded
over the data axes and replicated over "model", which is how TP activations
already arrive):

* **EP** when ``n_routed % model_axis == 0`` (deepseek: 256/16 = 16 experts
  per shard).  Every shard routes its local tokens, scatters only rows
  bound for its own experts into its capacity buffer, and per-token outputs
  are ``psum``-combined over "model".  Expert weights are *stored* with the
  hidden dim FSDP-sharded over "data" (rules.py: ``expert_ffn → data``) and
  gathered just-in-time by the shard_map in_spec — ZeRO-3 for DeepSeek's
  1.3 TB of expert weights.
* **expert-TP** otherwise (qwen2-moe: 60 experts, 1408/16 = 88): all
  experts on every shard with the per-expert hidden dim split over "model"
  (stored that way, no gather), psum after ``wo``.

Tokens beyond an expert's capacity are dropped (standard; the router aux
loss keeps loads balanced, and capacity_factor=1.25 makes drops rare).
Serving sets ``dropless``: capacity is the token count, so a token's
output never depends on how its batch or prefill chunk was routed.

A layer may hold only a range of the experts, ``[offset, offset +
n_held)`` of the ``n_routed`` its router scores (a chip's share under
expert parallelism): it computes only those experts' part of each
token's output, and rows routed elsewhere contribute nothing.

The router computes in f32 at full precision, softmax over all experts
then top-k with the kept probabilities renormalized behind a flag,
Switch-style load-balance aux loss.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_routed: int
    top_k: int
    d_expert: int                  # per-expert ffn hidden dim
    n_shared: int = 0              # shared experts (always active)
    d_shared: int = 0              # shared-expert hidden (total)
    act: str = "swiglu"
    norm_topk: bool = True         # renormalize top-k probs to sum 1
    router_scale: float = 1.0
    aux_loss_coef: float = 0.001
    capacity_factor: float = 1.25  # per-expert token capacity multiplier
    resident: bool = False         # decode: experts sharded over the FULL
                                   # mesh (1/dev), tokens gathered — no
                                   # per-layer weight gathers
    dropless: bool = False         # capacity = the tokens routed (no row
                                   # is ever dropped: a token picks an
                                   # expert at most once)
    n_held: int = 0                # experts held here: [offset,
    offset: int = 0                # offset + n_held) of the n_routed the
                                   # router scores (0 = all of them)

    @property
    def held(self) -> int:
        return self.n_held or self.n_routed


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    E, D, H = cfg.held, cfg.d_model, cfg.d_expert
    p = {
        "router": L.init_linear(ks[0], D, cfg.n_routed, False, dtype),
        "wi_gate": L.truncated_normal_init(ks[1], (E, D, H), 1.0, dtype),
        "wi_up": L.truncated_normal_init(ks[2], (E, D, H), 1.0, dtype),
        "wo": L.truncated_normal_init(ks[3], (E, H, D), 1.0, dtype),
    }
    if cfg.n_shared:
        p["shared"] = L.init_mlp(ks[4], D, cfg.d_shared, cfg.act, False, dtype)
    return p


def _route(p, x2d, cfg: MoEConfig):
    """x2d [T, D] → (weights [T,k], expert_ids [T,k], aux_loss)."""
    logits = jnp.dot(x2d.astype(jnp.float32),
                     p["router"]["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST) * cfg.router_scale
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)              # [T, k]
    if cfg.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    T = x2d.shape[0]
    density = jnp.zeros((cfg.n_routed,)).at[top_i.reshape(-1)].add(1.0) / (
        T * cfg.top_k)
    mean_prob = probs.mean(axis=0)
    aux = cfg.n_routed * jnp.sum(density * mean_prob) * cfg.aux_loss_coef
    return top_p, top_i, aux


def _capacity(T: int, cfg: MoEConfig) -> int:
    """Rows per expert buffer for ``T`` tokens: every token where
    ``cfg.dropless``, else ``capacity_factor`` times the mean load."""
    c = T if cfg.dropless else int(
        cfg.capacity_factor * T * cfg.top_k / max(cfg.n_routed, 1))
    return max(8, -(-c // 8) * 8)          # round up to a multiple of 8


def _local_moe(p_w, x2d, top_p, top_i, cfg: MoEConfig,
               expert_offset, n_local: int, capacity: int):
    """One shard's routed-expert compute (also the single-device path).

    Scatter rows into [n_local, capacity, D] by (expert, slot), batched
    matmuls, gather back.  Rows for non-local experts (or beyond capacity)
    contribute nothing.
    """
    T, D = x2d.shape
    k = cfg.top_k
    R = T * k
    flat_eid = top_i.reshape(R)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    flat_w = top_p.reshape(R)

    local_eid = flat_eid - expert_offset
    is_local = (local_eid >= 0) & (local_eid < n_local)
    key = jnp.where(is_local, local_eid, n_local)
    # Slot within the expert bucket = running count of prior rows with the
    # same expert id (computed via sorted positions, no one-hot tensors).
    order = jnp.argsort(key)
    sorted_key = key[order]
    gs = jnp.bincount(key, length=n_local + 1)[:n_local]
    starts = jnp.concatenate([jnp.zeros((1,), gs.dtype), jnp.cumsum(gs)])[:-1]
    slot = jnp.arange(R) - starts[jnp.clip(sorted_key, 0, n_local - 1)]
    valid = (sorted_key < n_local) & (slot < capacity)
    e_idx = jnp.where(valid, sorted_key, 0)
    s_idx = jnp.where(valid, slot, 0)
    tok_sorted = flat_tok[order]

    rows = x2d[tok_sorted] * valid[:, None].astype(x2d.dtype)
    buf = jnp.zeros((n_local, capacity, D), x2d.dtype)
    buf = buf.at[e_idx, s_idx].add(rows, mode="drop")

    a = jnp.einsum("ecd,edh->ech", buf, p_w["wi_gate"],
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("ecd,edh->ech", buf, p_w["wi_up"],
                   preferred_element_type=jnp.float32)
    actf = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
    h = (actf(a) * b).astype(x2d.dtype)
    y_buf = jnp.einsum("ech,ehd->ecd", h, p_w["wo"],
                       preferred_element_type=jnp.float32)

    y_rows = y_buf[e_idx, s_idx] * valid[:, None]
    y_rows = y_rows * flat_w[order][:, None]
    out = jnp.zeros((T, D), y_rows.dtype).at[tok_sorted].add(
        y_rows, mode="drop")
    return out


def moe_ffn(p, x, cfg: MoEConfig, mesh=None, ep_axis: str = "model"):
    """Full MoE block.  x [B, S, D] → (out, aux_loss)."""
    with jax.named_scope("moe"):
        return _moe_ffn(p, x, cfg, mesh, ep_axis)


def _moe_ffn(p, x, cfg: MoEConfig, mesh, ep_axis: str):
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    top_p, top_i, aux = _route(p, x2d, cfg)

    tp = mesh.shape[ep_axis] if (mesh is not None and ep_axis in mesh.shape) else 1

    if tp == 1:
        # This device's experts, [offset, offset + held): rows routed
        # elsewhere contribute nothing here.
        out2d = _local_moe(p, x2d, top_p, top_i, cfg, cfg.offset, cfg.held,
                           _capacity(B * S, cfg))
    else:
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in mesh.axis_names if a != ep_axis)

        ep_pair = ("data", ep_axis)
        resident_ok = (cfg.resident and "data" in mesh.shape
                       and cfg.held % (mesh.shape["data"] * tp) == 0)
        if resident_ok:
            # ---- resident EP (decode): experts sharded over data×model —
            # weights stay put; the (tiny) decode token batch is gathered.
            n_grp = mesh.shape["data"] * tp
            n_local = cfg.held // n_grp
            tok_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

            def res_body(wig, wiu, wog, xs, tpp, tii):
                xg = jax.lax.all_gather(xs, tok_axes, axis=0, tiled=True)
                tpg = jax.lax.all_gather(tpp, tok_axes, axis=0, tiled=True)
                tig = jax.lax.all_gather(tii, tok_axes, axis=0, tiled=True)
                gidx = (jax.lax.axis_index("data") * tp
                        + jax.lax.axis_index(ep_axis))
                cap = _capacity(xg.shape[0], cfg)
                y = _local_moe({"wi_gate": wig, "wi_up": wiu, "wo": wog},
                               xg, tpg, tig, cfg,
                               cfg.offset + gidx * n_local, n_local,
                               cap)
                y = jax.lax.psum(y, ("data", ep_axis))
                # take back this shard's token slice
                T_loc = xs.shape[0]
                start = jax.lax.axis_index("data") * T_loc
                if "pod" in mesh.shape:
                    start = start + (jax.lax.axis_index("pod")
                                     * mesh.shape["data"] * T_loc)
                return jax.lax.dynamic_slice_in_dim(y, start, T_loc, 0)

            out2d = jax.shard_map(
                res_body, mesh=mesh,
                in_specs=(P(ep_pair), P(ep_pair), P(ep_pair),
                          P(dp), P(dp), P(dp)),
                out_specs=P(dp),
                check_vma=False,
            )(p["wi_gate"], p["wi_up"], p["wo"], x2d, top_p, top_i)
        elif cfg.held % tp == 0:
            # ---- EP: experts sharded over "model".
            n_local = cfg.held // tp

            def ep_body(wig, wiu, wog, xs, tpp, tii):
                idx = jax.lax.axis_index(ep_axis)
                cap = _capacity(xs.shape[0], cfg)
                y = _local_moe({"wi_gate": wig, "wi_up": wiu, "wo": wog},
                               xs, tpp, tii, cfg, cfg.offset + idx * n_local,
                               n_local, cap)
                return jax.lax.psum(y, ep_axis)

            out2d = jax.shard_map(
                ep_body, mesh=mesh,
                in_specs=(P(ep_axis), P(ep_axis), P(ep_axis),
                          P(dp), P(dp), P(dp)),
                out_specs=P(dp),
                check_vma=False,
            )(p["wi_gate"], p["wi_up"], p["wo"], x2d, top_p, top_i)
        else:
            # ---- expert-TP: every shard, all experts, 1/tp of hidden dim.
            def tpx_body(wig, wiu, wog, xs, tpp, tii):
                cap = _capacity(xs.shape[0], cfg)
                y = _local_moe({"wi_gate": wig, "wi_up": wiu, "wo": wog},
                               xs, tpp, tii, cfg, cfg.offset, cfg.held, cap)
                return jax.lax.psum(y, ep_axis)

            out2d = jax.shard_map(
                tpx_body, mesh=mesh,
                in_specs=(P(None, None, ep_axis), P(None, None, ep_axis),
                          P(None, ep_axis, None), P(dp), P(dp), P(dp)),
                out_specs=P(dp),
                check_vma=False,
            )(p["wi_gate"], p["wi_up"], p["wo"], x2d, top_p, top_i)

    out = out2d.reshape(B, S, D).astype(x.dtype)
    if "shared" in p:
        out = out + L.mlp(p["shared"], x, cfg.act)
    return out, aux
