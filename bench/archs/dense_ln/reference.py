"""Plain reference of the served model: a float32 forward pass, written
here from the published architecture and the BitStopper paper, that
imports nothing of the program.

Attention follows BitStopper's semantics as the repo serves them.  Q, K
and V are quantized to ``bits``-bit integers (Q per token and head, K and
V per head over the sequence), scores are the exact integer products
times their scales over sqrt(head_dim), and LATS keeps key t for a query
iff its score is at least the running maximum score over the keys of the
blocks up to and including t's block, less alpha * radius (in logit
units).  That is where the bit-serial interval bounds of the paper end
after the last plane: a key pruned at an earlier plane also fails the
final rule, and the best key of the prefix is never pruned.  The
softmax and the weighted sum of V run over the keys kept.  The block is
``lats_block_prefill`` keys for prompt positions and
``lats_block_decode`` keys for the served positions the program decodes
one at a time.  The program's own quantization scales (a pool-wide
running maximum) are state this reference cannot see; its 12-bit scales
differ from the program's by rounding far below bfloat16's.

``mode="float8"`` is the control: the same computation with the inputs
of every weight multiply (weights per tensor, activations per row)
rounded to float8 e4m3, the precision below the bfloat16 the
configuration serves in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import round_fp8

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
QUERY_BLOCK = 256


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _quant(x, amax, bits):
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax), scale


@functools.lru_cache(maxsize=8)
def _compiled(dims: tuple, t_pad: int, n_out: int, mode: str):
    (layers, hq, hkv, dh, gated, tied, eps, theta, bits, alpha, radius,
     g_pre, g_dec) = dims
    G = hq // hkv
    fp8 = mode == "float8"

    def mm(a, w):
        """a [..., K] times w [K, N] in float32 (rounded through float8 in
        the control)."""
        w = w.astype(jnp.float32)
        if fp8:
            a = round_fp8(a, -1)
            w = round_fp8(w, None)
        return jnp.matmul(a, w, precision=HI)

    def attention(q, k, v, pos, t_real, n_prompt):
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

    def forward(params, tokens, t_real, n_prompt, out_pos):
        def f32(tree):
            return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                          tree)

        table = params["embed"]["table"]
        x = f32(table[tokens])
        pos = jnp.arange(t_pad, dtype=jnp.int32)
        d = x.shape[-1]

        def layer(x, p):
            p = f32(p)                  # one layer's weights at a time
            a = p["attn"]
            h = _layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)

            def proj(w, heads):
                y = mm(h, w["w"].reshape(d, heads * dh))
                y = y.reshape(t_pad, heads, dh)
                return y + w["b"] if "b" in w else y

            q = _rope(proj(a["wq"], hq), pos, theta)
            k = _rope(proj(a["wk"], hkv), pos, theta)
            v = proj(a["wv"], hkv)
            x = x + mm(attention(q, k, v, pos, t_real, n_prompt),
                       a["wo"]["w"])
            h = _layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
            f = p["ffn"]
            if gated:
                y = jax.nn.silu(mm(h, f["wi_gate"]["w"])) * mm(h, f["wi_up"]["w"])
            else:
                y = jax.nn.gelu(mm(h, f["wi"]["w"]), approximate=True)
            return x + mm(y, f["wo"]["w"]), None

        x, _ = jax.lax.scan(layer, x, params["seg0"]["b0"])
        fn = params["final_norm"]
        h = _layer_norm(x[out_pos], f32(fn["scale"]), f32(fn["bias"]), eps)
        head = table.T if tied else f32(params["unembed"]["w"])
        return mm(h, head)

    return jax.jit(forward)


def dims_of(conf: dict) -> tuple:
    b = conf["bitstopper"]
    return (conf["num_hidden_layers"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            bool(conf["ffn_gated"]), bool(conf["tie_word_embeddings"]),
            float(conf["layer_norm_eps"]), float(conf["rope_theta"]),
            b["bits"], b["alpha"], b["radius"], b["lats_block_prefill"],
            b["lats_block_decode"])


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
