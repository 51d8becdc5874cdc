"""``swa_moe``: pre-RMSNorm decoder blocks in a repeating period of
window and full attention layers (grouped KV, per-head q/k RMSNorm,
plain RoPE on window layers and YaRN on full ones) with a sparse
top-k expert feed-forward in every layer, of which the chip holds a
range of the experts: the Qwen3-MoE layer, as the
``mellum2-12b-a2.5b`` configuration states it."""
