"""The jit-compiled train step: loss, grads, microbatching, compression.

* **loss** — next-token cross-entropy (+ MoE aux + optional MTP at t+2).
* **grad accumulation** — ``microbatches > 1`` scans over batch slices,
  trading HBM for time (the dry-run's knob for fitting train_4k).
* **int8 gradient compression with error feedback** — per-leaf symmetric
  int8 quantization before the data-parallel all-reduce, with the
  quantization residual carried to the next step (error feedback keeps the
  noise unbiased over time).  Under GSPMD the all-reduce is implicit; the
  compression happens in a ``shard_map`` wrapper over the data axes so the
  reduced bytes really are int8 on the wire.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.train.optimizer import AdamWConfig, adamw_update, cosine_schedule


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    warmup_steps: int = 100
    total_steps: int = 1000
    microbatches: int = 1
    mtp_weight: float = 0.3
    grad_compression: str = "none"     # none | int8_ef


def lm_loss(logits, tokens, ignore_last: bool = True):
    """Next-token NLL.  logits [B,S,V] f32, tokens [B,S]."""
    tgt = jnp.roll(tokens, -1, axis=1)
    ll = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(ll, tgt[..., None], axis=-1)[..., 0]
    if ignore_last:
        w = jnp.ones_like(nll).at[:, -1].set(0.0)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
    return nll.mean()


def chunked_lm_loss(h, params, targets, cfg: ModelConfig,
                    chunk: int = 512, shift: int = 1):
    """Seq-chunked LM head + NLL — never materializes [B, S, vocab].

    Essential for big-vocab configs (deepseek 129k × 4k seq would be 34 GB
    of logits per device): each scan step computes one [B, chunk, V] slice
    (vocab-sharded under GSPMD) and reduces it immediately.  The target
    log-prob is taken with a one-hot einsum rather than take_along_axis so
    the vocab axis never needs gathering.
    """
    from repro.models import layers as Lyr

    B, S, _ = h.shape
    c = min(chunk, S)
    pad = (-S) % c
    tgt = jnp.roll(targets, -shift, axis=1)
    w = jnp.ones((B, S), jnp.float32)
    w = w.at[:, S - shift:].set(0.0)
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, 0), (0, pad)))
    n = h.shape[1] // c
    hc = h.reshape(B, n, c, -1).swapaxes(0, 1)
    tc = tgt.reshape(B, n, c).swapaxes(0, 1)
    wc = w.reshape(B, n, c).swapaxes(0, 1)
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["unembed"]["w"].T)

    @jax.checkpoint  # recompute per-chunk logits in backward: never keep
    def step(carry, inp):  # more than one [B, c, V] slice alive.
        hx, tx, wx = inp
        logits = jax.lax.dot_general(
            hx, table, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [B, c, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(tx, logits.shape[-1], dtype=logits.dtype)
        tgt_logit = jnp.einsum("bcv,bcv->bc", logits, onehot)
        nll = (lse - tgt_logit) * wx
        return (carry[0] + nll.sum(), carry[1] + wx.sum()), None

    (total, count), _ = jax.lax.scan(step, (0.0, 0.0), (hc, tc, wc))
    return total / jnp.maximum(count, 1.0)


def loss_fn(params, batch, cfg: ModelConfig, tcfg: TrainConfig):
    """batch: tokens [B,S] int32, or {"tokens": ..., "patches": [B,P,D]}
    for stubbed-frontend VLM archs (loss over the text positions)."""
    from repro.models import layers as Lyr
    from repro.sharding.api import constrain

    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    patches = batch.get("patches") if isinstance(batch, dict) else None
    n_patch = 0
    if patches is not None:
        n_patch = patches.shape[1]
        text = Lyr.embed(params["embed"], tokens)
        x = jnp.concatenate([patches.astype(text.dtype), text], axis=1)
        x = x.astype(cfg.activation_dtype)
    else:
        x = Lyr.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    x = constrain(x, "batch", "seq", "embed")
    positions = jnp.arange(x.shape[1])
    h, _, aux = T.run_segments(params, x, positions, cfg)
    if n_patch:
        h = h[:, n_patch:]                 # loss over the text positions
        positions = positions[: h.shape[1]]
    hn = Lyr.norm(params["final_norm"], h)
    loss = chunked_lm_loss(hn, params, tokens, cfg) + aux
    if cfg.mtp:
        # MTP shares the trunk: one extra block over [h_t ; emb(t+1)]
        # predicting token t+2 (chunked head again — no [B,S,V] tensor).
        emb_next = Lyr.embed(params["embed"], jnp.roll(tokens, -1, axis=1))
        cat = jnp.concatenate(
            [Lyr.norm(params["mtp_norm"], h), emb_next.astype(h.dtype)],
            axis=-1)
        xm = Lyr.linear(params["mtp_proj"], cat)
        spec = cfg.segments[-1][0][-1]
        xm, _, _ = T.block_forward(params["mtp_block"], xm, positions, spec,
                                   cfg)
        hm = Lyr.norm(params["final_norm"], xm)
        loss = loss + tcfg.mtp_weight * chunked_lm_loss(
            hm, params, tokens, cfg, shift=2)
    return loss


# ---------------------------------------------------------------------------
# int8 gradient compression with error feedback
# ---------------------------------------------------------------------------


def _compress_int8(g, err):
    gf = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return q, scale, gf - deq            # residual -> error feedback


def compress_grads(grads, err_state):
    """Returns (int8 tree, scale tree, new error state)."""
    qs = jax.tree_util.tree_map(_compress_int8, grads, err_state)
    q = jax.tree_util.tree_map(lambda t: t[0], qs,
                               is_leaf=lambda x: isinstance(x, tuple))
    s = jax.tree_util.tree_map(lambda t: t[1], qs,
                               is_leaf=lambda x: isinstance(x, tuple))
    e = jax.tree_util.tree_map(lambda t: t[2], qs,
                               is_leaf=lambda x: isinstance(x, tuple))
    return q, s, e


def decompress_grads(q, s):
    return jax.tree_util.tree_map(
        lambda qi, si: qi.astype(jnp.float32) * si, q, s)


def allreduce_int8_ef(grads, err_state, mesh, data_axes=("data",)):
    """shard_map int8 all-reduce over the data axes with error feedback.

    Grad leaves are assumed data-replicated per shard (GSPMD has already
    reduce-scattered FSDP shards); the wire format of the cross-replica sum
    becomes int8 + one f32 scale per leaf.
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(a for a in data_axes if a in mesh.shape)

    def body(g, e):
        q, s, e_new = compress_grads(g, e)
        q_sum = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x.astype(jnp.int32), axes), q)
        s_max = jax.tree_util.tree_map(lambda x: jax.lax.pmax(x, axes), s)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        g_new = jax.tree_util.tree_map(
            lambda qi, si: qi.astype(jnp.float32) * si / n, q_sum, s_max)
        return g_new, e_new

    specs = jax.tree_util.tree_map(lambda _: P(), grads)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, specs), out_specs=(specs, specs),
        check_vma=False,
    )(grads, err_state)


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns step(state, tokens) -> (state, metrics); jit at the call site
    (the launcher attaches in/out shardings)."""

    def grads_of(params, tokens):
        if tcfg.microbatches <= 1:
            return jax.value_and_grad(loss_fn)(params, tokens, cfg, tcfg)

        def slice_mb(x):
            mb = x.shape[0] // tcfg.microbatches
            return x.reshape((tcfg.microbatches, mb) + x.shape[1:])

        slices = jax.tree_util.tree_map(slice_mb, tokens)

        def acc_fn(carry, batch):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, batch, cfg, tcfg)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (loss_acc + l, g_acc), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, gsum), _ = jax.lax.scan(acc_fn, (0.0, zeros), slices)
        inv = 1.0 / tcfg.microbatches
        return loss * inv, jax.tree_util.tree_map(lambda g: g * inv, gsum)

    def step(state, tokens):
        from repro.sharding.api import current_rules
        params, opt, err = state["params"], state["opt"], state.get("err")
        loss, grads = grads_of(params, tokens)
        rules = current_rules()
        if rules is not None:
            # Pin gradient shardings to the parameter layout: without this
            # the backward scan emits *unsharded* stacked f32 grads
            # (measured +1.25 GiB/layer at 12B scale).
            grads = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads,
                rules.tree_shardings(grads))
        if tcfg.grad_compression == "int8_ef" and err is not None:
            from repro.sharding.api import current_rules
            rules = current_rules()
            if rules is not None:
                data_axes = tuple(a for a in ("pod", "data")
                                  if a in rules.mesh.shape)
                grads, err = allreduce_int8_ef(grads, err, rules.mesh,
                                               data_axes)
        lr_scale = cosine_schedule(
            opt["step"], warmup=tcfg.warmup_steps, total=tcfg.total_steps)
        params, opt, metrics = adamw_update(params, grads, opt,
                                            tcfg.optimizer, lr_scale)
        new_state = dict(state, params=params, opt=opt)
        if err is not None:
            new_state["err"] = err
        metrics = dict(metrics, loss=loss)
        return new_state, metrics

    return step


def init_train_state(key, cfg: ModelConfig, tcfg: TrainConfig):
    from repro.train.optimizer import init_opt_state
    params = T.init_model(key, cfg)
    state: dict[str, Any] = {
        "params": params,
        "opt": init_opt_state(params, tcfg.optimizer),
    }
    if tcfg.grad_compression == "int8_ef":
        state["err"] = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state
