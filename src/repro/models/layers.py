"""Shared building blocks: norms, RoPE, MLPs, embeddings, linear layers.

Params are plain nested dicts of jax.Arrays.  Sharding is attached later by
path-pattern rules (repro/sharding/rules.py), so layers stay mesh-agnostic.
All matmuls run in the array dtype with f32 accumulation via
``preferred_element_type``; norms/softmax always compute in f32.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal_init(key, shape, scale, dtype=jnp.float32):
    stddev = scale / max(1.0, math.sqrt(shape[0] if shape else 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * stddev).astype(dtype)


# ---------------------------------------------------------------------------
# Linear / embedding
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out, bias: bool = False, dtype=jnp.float32):
    """d_out may be an int or a tuple (fused multi-output heads)."""
    out_shape = (d_out,) if isinstance(d_out, int) else tuple(d_out)
    w = truncated_normal_init(key, (d_in, *out_shape), 1.0, dtype)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros(out_shape, dtype)
    return p


def linear(p, x):
    ndim_out = p["w"].ndim - 1
    y = jax.lax.dot_general(
        x, p["w"], (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if "b" in p:
        y = y + p["b"]
    return y.astype(x.dtype) if ndim_out else y.astype(x.dtype)


def init_embedding(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": truncated_normal_init(key, (vocab, d), math.sqrt(vocab), dtype)}


def embed(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def unembed(p, x):
    """Tied readout: logits = x @ table^T (f32)."""
    return jax.lax.dot_general(
        x, p["table"], (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm(p, x):
    return layer_norm(p, x) if "bias" in p else rms_norm(p, x)


def init_norm(d: int, kind: str = "rms", dtype=jnp.float32):
    return init_layernorm(d, dtype) if kind == "ln" else init_rmsnorm(d, dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN context extension of RoPE (arXiv:2309.00071): frequencies
    between the two correction dims ramp from the original (fast dims) to
    the original over ``factor`` (slow dims), and cos/sin are scaled by
    ``attention_factor``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def yarn_inv_freq(dh: int, theta: float, yarn: Yarn):
    """YaRN's ``[Dh/2]`` inverse frequencies (float32): RoPE's, blended
    with RoPE's over ``factor`` between the truncated correction dims."""
    base = theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)

    def corr_dim(rotations):
        return (dh * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrap = 1 - ramp
    inv = (1.0 / (yarn.factor * base)) * ramp + (1.0 / base) * extrap
    return jnp.asarray(inv, jnp.float32)


def rope(x, positions, theta: float = 10000.0, yarn: Yarn | None = None):
    """Rotary embedding.  x [..., S, H, Dh] (Dh even), positions [..., S].
    With ``yarn``, YaRN's frequencies, and cos/sin times its
    ``attention_factor``."""
    dh = x.shape[-1]
    freqs = (theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
             if yarn is None else yarn_inv_freq(dh, theta, yarn))       # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs          # [..., S, Dh/2]
    # broadcast over the heads axis
    angles = angles[..., None, :]                                      # [..., S, 1, Dh/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, d_ff: int, kind: str = "swiglu", bias: bool = False,
             dtype=jnp.float32, d_out: int | None = None):
    k1, k2, k3 = jax.random.split(key, 3)
    d_out = d_out or d
    if kind in ("swiglu", "geglu"):
        return {
            "wi_gate": init_linear(k1, d, d_ff, bias, dtype),
            "wi_up": init_linear(k2, d, d_ff, bias, dtype),
            "wo": init_linear(k3, d_ff, d_out, bias, dtype),
        }
    return {  # plain gelu MLP
        "wi": init_linear(k1, d, d_ff, bias, dtype),
        "wo": init_linear(k2, d_ff, d_out, bias, dtype),
    }


def mlp(p, x, kind: str = "swiglu"):
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        h = act(linear(p["wi_gate"], x)) * linear(p["wi_up"], x)
        return linear(p["wo"], h)
    return linear(p["wo"], jax.nn.gelu(linear(p["wi"], x)))
