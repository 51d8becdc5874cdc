"""Operations and bytes that the served model's calls need, from shapes.

Everything here is computed from the configuration file's published sizes
(``bench/configs/<config>.json``) and from the engine's counters over the
traced slice; nothing is read from the program.  Full and window layers
attend different spans, so three counters are read: ``decode_tokens``
(tokens the decode steps emitted), ``decode_kv_tokens`` (cached tokens a
full layer attended, summed over the rows of every step) and
``decode_window_kv_tokens`` (the same for one window layer, at most the
window a row).  These are the numerators of the step's share of the
chip's peak (``decode_step_mfu``) and of the fused decode kernel's
roofline share (``besf_kernel_roofline``).
"""

from __future__ import annotations


def _dims(m: dict):
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"])


def layer_counts(m: dict) -> tuple[int, int]:
    """``(full, window)`` attention layers, from ``layer_types``."""
    kinds = m["layer_types"]
    n_window = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - n_window, n_window


def held_experts_per_token(m: dict) -> float:
    """Experts held here that a token is routed to, on average: its
    ``num_experts_per_tok`` picks spread evenly over the deployment's
    ``num_experts``, of which the file's ``num_experts`` are held."""
    return (m["num_experts_per_tok"] * m["num_experts"]
            / m["deployment"]["num_experts"])


def matmul_params_per_token(m: dict) -> float:
    """Weights every token is multiplied through: the attention
    projections, the router and the held experts it is routed to, in every
    layer, and the output head.  The embedding is a lookup and costs no
    multiply."""
    d, hq, hkv, dh = _dims(m)
    attn = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    router = d * m["deployment"]["num_experts"]
    expert = 3 * d * m["moe_intermediate_size"]
    layers = len(m["layer_types"])
    return (layers * (attn + router + held_experts_per_token(m) * expert)
            + d * m["vocab_size"])


def attended_tokens(m: dict, counters: dict) -> int:
    """(row, token) pairs the decode steps attended, summed over layers:
    every full layer ``decode_kv_tokens``, every window layer
    ``decode_window_kv_tokens``."""
    full, window = layer_counts(m)
    return (full * counters["decode_kv_tokens"]
            + window * counters["decode_window_kv_tokens"])


def decode_step_flops(m: dict, counters: dict) -> float:
    """Model FLOPs of decode steps that emit ``decode_tokens`` tokens: 2
    per weight per token, and per layer 2 * Hq * Dh for the scores and as
    much again for the weighted sum of V, per attended token."""
    _, hq, _, dh = _dims(m)
    return (2.0 * matmul_params_per_token(m) * counters["decode_tokens"]
            + 4.0 * hq * dh * attended_tokens(m, counters))


def besf_kernel_work(m: dict, counters: dict) -> tuple[float, float]:
    """``(flops, bytes)`` the fused paged BESF decode kernel needs over the
    attended (row, token) pairs of every layer, when no page terminates
    early: each is scored against its ``bits`` K bit planes (2 * Hq * Dh
    operations per plane) and weighted into the output (2 * Hq * Dh), and
    its planes (``bits`` bits per K element) and its V row
    (``kv_cache_bytes`` per element) are read once per KV head.  Where
    LATS terminates pages, fewer bytes are read and this count is then too
    high; see PERF.md."""
    _, hq, hkv, dh = _dims(m)
    bits = m["bitstopper"]["bits"]
    pairs = attended_tokens(m, counters)
    flops = pairs * (bits * 2.0 * hq * dh + 2.0 * hq * dh)
    per_token = bits * hkv * dh / 8 + hkv * dh * m["kv_cache_bytes"]
    return flops, pairs * per_token


def kv_bytes_per_token(m: dict) -> int:
    """Device bytes one cached token holds in the paged pool, all layers
    (a window layer keeps every page of its row): K and V rows in the
    cache dtype plus the K bit planes."""
    _, _, hkv, dh = _dims(m)
    bits = m["bitstopper"]["bits"]
    return len(m["layer_types"]) * (2 * hkv * dh * m["kv_cache_bytes"]
                                    + bits * hkv * dh // 8)
