"""KV pool: of the cached K/V tokens the decode steps held in all layers,
the share no layer could read: a window layer keeps every page of its
row but reads only the last ``sliding_window`` tokens (%).  Counted over
the window from the engine's ``decode_kv_tokens`` (a full layer's span)
and ``decode_window_kv_tokens`` (a window layer's), with the layer counts
from the configuration file's ``layer_types``; 0 for a model without
window layers, whose every held token is read."""


def read(rec):
    c, conf = rec["counters"], rec["conf"]
    if not c.get("decode_kv_tokens"):
        return None
    kinds = conf.get("layer_types") or []
    n_window = sum(k == "sliding_attention" for k in kinds)
    if not n_window:
        return 0.0
    if "decode_window_kv_tokens" not in c:
        return None
    idle = n_window * (c["decode_kv_tokens"] - c["decode_window_kv_tokens"])
    return 100.0 * idle / (len(kinds) * c["decode_kv_tokens"])
