"""A CPU-sized cell for the benchmark's tests: the stablelm architecture at
toy widths, served through the same harness path as a chip cell."""

from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

CONF = {
    "arch": "stablelm-1.6b", "arch_family": "dense_ln",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "rope_theta": 10000, "use_qkv_bias": True,
    "layer_norm_eps": 1e-05, "ffn_gated": True, "tie_word_embeddings": False,
    "param_dtype": "bfloat16", "activation_dtype": "bfloat16",
    "kv_cache_dtype": "float32", "kv_cache_bytes": 4,
    "page_size": 16, "pool_blocks": 17,
    "bitstopper": {"bits": 12, "alpha": 0.6, "radius": 5.0,
                   "lats_block_prefill": 128, "lats_block_decode": 16},
}

MIX = {
    "loop": "closed", "clients": 2,
    "requests_per_client": 4,
    "size_seed": 13,
    "prompt_tokens": {"dist": "uniform", "min": 20, "max": 60},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 10},
    "engine": {"max_slots": 2, "max_len": 128, "prefill_chunk": 32},
    "check": {"min_tokens": 64, "max_requests": 8},
}


def cell(mix=None, conf=None, limit=0.25):
    from bench.run import Cell
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return Cell(name="tiny", chips=1, conf=copy.deepcopy(conf or CONF),
                mix=copy.deepcopy(mix or MIX),
                limits={"served_logit_gap": limit},
                metrics={k: bench[k] for k in ("end_to_end", "per_layer")})
