"""Operations and bytes that the served model's calls need, from shapes.

Everything here is computed from the configuration file's published sizes
(``bench/configs/<config>.json``) and from the engine's counters over the
traced slice; nothing is read from the program.  Every layer attends the
same span, so two counters are enough: ``decode_tokens`` (tokens the
decode steps emitted) and ``decode_kv_tokens`` (cached tokens they
attended, summed over the rows of every step).  These are the numerators
of the step's share of the chip's peak (``decode_step_mfu``) and of the
fused decode kernel's roofline share (``besf_kernel_roofline``).
"""

from __future__ import annotations


def _dims(m: dict):
    layers = m["num_hidden_layers"]
    d = m["hidden_size"]
    hq = m["num_attention_heads"]
    hkv = m["num_key_value_heads"]
    dh = m["head_dim"]
    return layers, d, hq, hkv, dh


def matmul_params_per_token(m: dict) -> int:
    """Weights every token is multiplied through: the attention
    projections and the feed-forward of every layer, and the output head.
    The embedding is a lookup and costs no multiply."""
    layers, d, hq, hkv, dh = _dims(m)
    f = m["intermediate_size"]
    attn = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    ffn = (3 if m["ffn_gated"] else 2) * d * f
    return layers * (attn + ffn) + d * m["vocab_size"]


def decode_step_flops(m: dict, counters: dict) -> float:
    """Model FLOPs of decode steps that emit ``decode_tokens`` tokens while
    attending ``decode_kv_tokens`` cached tokens in all: 2 per weight per
    token, and per layer 2 * Hq * Dh for the scores and as much again for
    the weighted sum of V, per attended token.  This is the dense model's
    count, the same whatever the attention does with it."""
    layers, _, hq, _, dh = _dims(m)
    return (2.0 * matmul_params_per_token(m) * counters["decode_tokens"]
            + 4.0 * layers * hq * dh * counters["decode_kv_tokens"])


def besf_kernel_work(m: dict, counters: dict) -> tuple[float, float]:
    """``(flops, bytes)`` the fused paged BESF decode kernel needs over
    the ``decode_kv_tokens`` attended (row, token) pairs, summed over all
    layers, when no page terminates early: each attended token is scored
    against its ``bits`` K bit planes (2 * Hq * Dh operations per plane)
    and weighted into the output (2 * Hq * Dh), and its planes (``bits``
    bits per K element) and its V row (``kv_cache_bytes`` per element) are
    read once per KV head.  Where LATS terminates pages, fewer bytes are
    read and this count is then too high; see PERF.md."""
    layers, _, hq, hkv, dh = _dims(m)
    bits = m["bitstopper"]["bits"]
    kv_tokens = counters["decode_kv_tokens"]
    flops = layers * kv_tokens * (bits * 2.0 * hq * dh + 2.0 * hq * dh)
    per_token = bits * hkv * dh / 8 + hkv * dh * m["kv_cache_bytes"]
    return flops, layers * kv_tokens * per_token


def kv_bytes_per_token(m: dict) -> int:
    """Device bytes one cached token holds in the paged pool, all layers:
    K and V rows in the cache dtype plus the K bit planes."""
    layers, _, _, hkv, dh = _dims(m)
    bits = m["bitstopper"]["bits"]
    return layers * (2 * hkv * dh * m["kv_cache_bytes"] + bits * hkv * dh // 8)
