"""Plain reference of the served model: a float32 forward pass, written
here from the published configuration, Qwen3-MoE's layer equations (whose
keys the configuration uses) and the BitStopper paper, that imports
nothing of the program.

Each layer is ``x + attn(rms(x))`` then ``x + moe(rms(x))``, with RMSNorm
scales and the file's ``rms_norm_eps``; a final RMSNorm and an untied
head give the logits.  The layer kinds repeat with the period of
``layer_types``.

* Attention: q, k and v projections without bias; q and k RMSNorm'd per
  head over ``head_dim``; rotary embedding on the two halves of each head
  (``rope_parameters``): plain at ``rope_theta`` on ``sliding_attention``
  layers, YaRN on ``full_attention`` layers (inverse frequencies blended
  between the original and the original over ``factor`` along a linear
  ramp between the truncated correction dims of ``beta_fast`` and
  ``beta_slow`` rotations over ``original_max_position_embeddings``, and
  cos and sin times ``attention_factor``).  Query head h reads KV head
  h // (heads / KV heads).  A window layer's query at position p sees the
  keys at p - sliding_window < t <= p.
* Experts: router logits over all of the deployment's ``num_experts``,
  softmax, the ``num_experts_per_tok`` largest, renormalized to sum 1
  (``norm_topk_prob``); a SwiGLU expert of width
  ``moe_intermediate_size`` per expert, weighted by its probability.

BitStopper's semantics are the ``dense_ln`` reference's, unchanged.  Q,
K and V are quantized to ``bits``-bit integers (Q per token and head, K
and V per head over the sequence), scores are the exact integer products
times their scales over sqrt(head_dim), and LATS keeps key t for a query
iff its score is at least the running maximum score over the keys the
query sees in the blocks up to and including t's block, less alpha *
radius (in logit units); a window layer's keys outside the window take no
part.  The block is ``lats_block_prefill`` keys for prompt positions and
``lats_block_decode`` keys for the served positions the program decodes
one at a time.  The program's quantization scales (a pool-wide running
maximum) are state this reference cannot see; they differ from these by
rounding far below bfloat16's.

Departures from the source, each also the program's:

* only the experts this chip holds (``deployment.expert_offset`` and the
  file's ``num_experts`` of them) are computed, and the others' part of
  each token's output is left out, as a chip of the stated expert-
  parallel deployment computes it before the exchange;
* BitStopper attention in place of exact softmax attention;
* no multi-token-prediction head (the configuration has none);
* q/k RMSNorm is taken from the Qwen3-MoE layout (no key states it);
* random weights.

``mode="float8"`` is the control: the same computation with the inputs
of every weight multiply (weights per tensor, per expert for the
experts, activations per row) rounded to float8 e4m3, the precision
below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import round_fp8

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
QUERY_BLOCK = 256
_KINDS = {"sliding_attention": True, "full_attention": False}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def inv_freq(dh: int, theta: float, yarn: tuple | None) -> np.ndarray:
    """RoPE's ``[dh/2]`` inverse frequencies; with ``yarn`` = (factor,
    original positions, beta_fast, beta_slow), YaRN's blend."""
    base = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    if yarn is None:
        return (1.0 / base).astype(np.float32)
    factor, orig, fast, slow = yarn

    def dim(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(fast)), 0), min(math.ceil(dim(slow)), dh - 1)
    ramp = np.clip((np.arange(dh // 2) - low) / max(high - low, 1e-3), 0, 1)
    return ((1.0 / (factor * base)) * ramp
            + (1.0 / base) * (1 - ramp)).astype(np.float32)


def _rope(x, pos, freqs, scale):
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _quant(x, amax, bits):
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax), scale


@functools.lru_cache(maxsize=8)
def _compiled(dims: tuple, t_pad: int, n_out: int, mode: str):
    (windowed, hq, hkv, dh, tied, eps, ropes, window, top_k, offset, held,
     bits, alpha, radius, g_pre, g_dec) = dims
    G = hq // hkv
    fp8 = mode == "float8"
    # (inverse frequencies, cos/sin scale) of full [0] and window [1] layers
    tables = [(jnp.asarray(inv_freq(dh, theta, yarn)), scale)
              for theta, yarn, scale in ropes]

    def rnd(a, axis):
        return round_fp8(a, axis) if fp8 else a

    def mm(a, w):
        """a [..., K] times w [K, N] in float32 (rounded through float8 in
        the control)."""
        return jnp.matmul(rnd(a, -1), rnd(w.astype(jnp.float32), None),
                          precision=HI)

    def attention(q, k, v, pos, t_real, n_prompt, win):
        kvalid = pos < t_real
        q_int, sq = _quant(q, jnp.max(jnp.abs(q), axis=-1, keepdims=True),
                           bits)                             # [T,Hq,D]
        kmax = jnp.max(jnp.where(kvalid[:, None, None], jnp.abs(k), 0),
                       axis=(0, 2))
        vmax = jnp.max(jnp.where(kvalid[:, None, None], jnp.abs(v), 0),
                       axis=(0, 2))
        k_int, sk = _quant(k, kmax[None, :, None], bits)
        v_int, sv = _quant(v, vmax[None, :, None], bits)
        k_int = jnp.repeat(k_int, G, axis=1)
        v_deq = jnp.repeat(v_int * sv, G, axis=1)
        sk_h = jnp.repeat(sk[0, :, 0], G)
        nb = t_pad // QUERY_BLOCK

        def block(args):
            qi, sqi, qpos = args                     # [QB,Hq,D] [QB,Hq] [QB]
            s = jnp.einsum("qhd,khd->hqk", qi, k_int, precision=HI)
            s = s * (sqi.T[:, :, None] * sk_h[:, None, None]) / np.sqrt(dh)
            valid = (pos[None, :] <= qpos[:, None]) & kvalid[None, :]
            if win is not None:
                valid &= pos[None, :] > qpos[:, None] - win
            sm = jnp.where(valid[None], s, NEG)

            def prefix_max(g):
                bm = sm.reshape(hq, QUERY_BLOCK, t_pad // g, g).max(-1)
                return jnp.repeat(jax.lax.cummax(bm, axis=2), g, axis=2)

            decode_row = (qpos >= n_prompt)[None, :, None]
            thr = jnp.where(decode_row, prefix_max(g_dec), prefix_max(g_pre))
            keep = valid[None] & (s >= thr - alpha * radius)
            m = jnp.max(jnp.where(keep, s, NEG), axis=-1, keepdims=True)
            e = jnp.where(keep, jnp.exp(s - m), 0.0)
            p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
            return jnp.einsum("hqk,khd->qhd", p, v_deq, precision=HI)

        out = jax.lax.map(block, (
            q_int.reshape(nb, QUERY_BLOCK, hq, dh),
            sq[..., 0].reshape(nb, QUERY_BLOCK, hq),
            pos.reshape(nb, QUERY_BLOCK)))
        return out.reshape(t_pad, hq * dh)

    def experts(h, m):
        probs = jax.nn.softmax(mm(h, m["router"]["w"]), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        mine = offset + jnp.arange(held)
        w = jnp.sum(jnp.where(top_i[:, :, None] == mine, top_p[..., None],
                              0.0), axis=1)                  # [T, held]
        hr = rnd(h, -1)

        def up(name):
            return jnp.einsum("td,edf->tef", hr, rnd(m[name], (1, 2)),
                              precision=HI)

        a = rnd(jax.nn.silu(up("wi_gate")) * up("wi_up"), -1)
        return jnp.einsum("tef,efd->td", a * w[..., None],
                          rnd(m["wo"], (1, 2)), precision=HI)

    def forward(params, tokens, t_real, n_prompt, out_pos):
        def f32(tree):
            return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                          tree)

        table = params["embed"]["table"]
        x = f32(table[tokens])
        pos = jnp.arange(t_pad, dtype=jnp.int32)
        d = x.shape[-1]

        def layer(x, p, win, rope):
            a = p["attn"]
            h = _rms(x, p["norm1"]["scale"], eps)

            def proj(w, heads):
                return mm(h, w["w"].reshape(d, heads * dh)).reshape(
                    t_pad, heads, dh)

            q = _rms(proj(a["wq"], hq), a["q_norm"]["scale"], eps)
            k = _rms(proj(a["wk"], hkv), a["k_norm"]["scale"], eps)
            q, k = _rope(q, pos, *rope), _rope(k, pos, *rope)
            v = proj(a["wv"], hkv)
            x = x + mm(attention(q, k, v, pos, t_real, n_prompt, win),
                       a["wo"]["w"])
            return x + experts(_rms(x, p["norm2"]["scale"], eps), p["moe"])

        def unit(x, pu):
            for i, w in enumerate(windowed):
                x = layer(x, f32(pu[f"b{i}"]), window if w else None,
                          tables[int(w)])
            return x, None

        seg = params["seg0"]
        if isinstance(seg, list):
            seg = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *seg)
        x, _ = jax.lax.scan(unit, x, seg)
        h = _rms(x[out_pos], f32(params["final_norm"]["scale"]), eps)
        head = table.T if tied else f32(params["unembed"]["w"])
        return mm(h, head)

    return jax.jit(forward)


def dims_of(conf: dict) -> tuple:
    b = conf["bitstopper"]
    kinds = [_KINDS[k] for k in conf["layer_types"]]
    p = next(n for n in range(1, len(kinds) + 1)
             if len(kinds) % n == 0
             and kinds == kinds[:n] * (len(kinds) // n))
    rp = conf["rope_parameters"]
    full, win = rp["full_attention"], rp["sliding_attention"]
    yarn = (float(full["factor"]), full["original_max_position_embeddings"],
            float(full["beta_fast"]), float(full["beta_slow"]))
    ropes = ((float(full["rope_theta"]), yarn,
              float(full["attention_factor"])),
             (float(win["rope_theta"]), None, 1.0))
    return (tuple(kinds[:p]), conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            bool(conf["tie_word_embeddings"]), float(conf["rms_norm_eps"]),
            ropes, conf["sliding_window"],
            conf["num_experts_per_tok"], conf["deployment"]["expert_offset"],
            conf["num_experts"], b["bits"], b["alpha"], b["radius"],
            b["lats_block_prefill"], b["lats_block_decode"])


def logits_at(conf: dict, params, prompt: np.ndarray, served: np.ndarray,
              t_pad: int, n_out: int, mode: str = "float32") -> np.ndarray:
    """Reference logits ``[len(served), vocab]`` at the positions whose
    output was each served token: the last prompt position, then every
    served token but the last fed back in turn."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    t_real, n = len(seq), len(served)
    if t_real > t_pad or n > n_out:
        raise ValueError(f"sequence of {t_real} tokens with {n} served does "
                         f"not fit the reference's {t_pad}/{n_out}")
    tokens = np.zeros((t_pad,), np.int32)
    tokens[:t_real] = seq
    out_pos = np.full((n_out,), t_real - 1, np.int32)
    out_pos[:n] = np.arange(len(prompt) - 1, t_real, dtype=np.int32)
    fn = _compiled(dims_of(conf), t_pad, n_out, mode)
    out = fn(params, jnp.asarray(tokens), jnp.int32(t_real),
             jnp.int32(len(prompt)), jnp.asarray(out_pos))
    return np.asarray(out)[:n]
