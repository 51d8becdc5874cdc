"""Concrete parallelism layouts for the production meshes.

Parameter placement (path-pattern → logical axes; first match wins) and the
activation axis map, composing:

* **DP**    — batch over ("pod", "data")
* **FSDP**  — parameters' embed axis over "data" (ZeRO-3: jit inserts
              all-gathers before use, reduce-scatters after backward)
* **TP**    — heads / ffn / vocab / expert axes over "model" (Megatron split)
* **EP**    — MoE expert axis over "model" (divisibility decides EP vs
              expert-TP per config — see models/moe.py)
* **SP**    — optional: sequence axis over "model" between blocks (long ctx)

Indivisible dims fall back to replication automatically (api.MeshRules).
"""

from __future__ import annotations

from repro.sharding.api import MeshRules

# Path-pattern parameter rules.  Axis names refer to AXIS_MAP keys below.
PARAM_RULES: tuple[tuple[str, tuple], ...] = (
    # Embeddings / unembeddings: vocab sharded over model (TP), embed over fsdp.
    (r"embed/table$", ("vocab", "fsdp")),
    (r"unembed/w$", ("fsdp", "vocab")),
    (r"mtp_head/w$", ("fsdp", "vocab")),
    # Attention projections: heads over model, d_model over fsdp.
    (r"attn/wq/w$", ("fsdp", "heads", None)),
    (r"attn/wk/w$", ("fsdp", "kv_heads", None)),
    (r"attn/wv/w$", ("fsdp", "kv_heads", None)),
    (r"attn/wq/b$", ("heads", None)),
    (r"attn/w[kv]/b$", ("kv_heads", None)),
    (r"attn/wo/w$", ("heads_flat", "fsdp")),
    # MLA projections (deepseek): latent ranks replicated, heads over model.
    (r"mla/wq_a/w$", ("fsdp", None)),
    (r"mla/wq_b/w$", (None, "heads", None)),
    (r"mla/wkv_a/w$", ("fsdp", None)),
    (r"mla/wkv_b/w$", (None, "heads", None)),
    (r"mla/wo/w$", ("heads_flat", "fsdp")),
    # Dense MLPs: ffn over model (Megatron).
    (r"(mlp|ffn|shared)/wi(_gate|_up)?/w$", ("fsdp", "ffn")),
    (r"(mlp|ffn|shared)/wo/w$", ("ffn", "fsdp")),
    # MoE experts: expert axis over model (EP) when divisible, else the
    # per-expert ffn axis picks up "model" via moe.py's expert-TP path.
    (r"moe/router/w$", ("fsdp", None)),
    (r"moe/wi(_gate|_up)?$", ("expert", "expert_dmodel", "expert_ffn")),
    (r"moe/wo$", ("expert", "expert_ffn", "expert_dmodel")),
    # SSM (mamba2): inner channels over model.
    (r"ssm/in_proj/w$", ("fsdp", "ffn")),
    (r"ssm/out_proj/w$", ("ffn", "fsdp")),
    (r"ssm/(conv_w|conv_b|A_log|D|dt_bias)$", ("ffn",)),
    (r"ssm/norm/scale$", ("ffn",)),
    # RG-LRU (recurrentgemma): recurrent width over model.
    (r"rglru/(in_x|in_gate)/w$", ("fsdp", "ffn")),
    (r"rglru/out/w$", ("ffn", "fsdp")),
    (r"rglru/(a_param|conv_w|conv_b)$", ("ffn",)),
    (r"rglru/(rg|ig)/w$", (None, "ffn", None)),
    # Norm scales replicated.
    (r"(scale|bias)$", (None,)),
)

# Logical-axis → mesh-axis maps.
AXIS_MAP_1POD = {
    "batch": "data",
    "fsdp": "data",
    "embed": None,
    "seq": None,
    "heads": "model",
    "kv_heads": "model",
    "heads_flat": "model",
    # attention output entering wo: keep heads sharded so the wo matmul is
    # the Megatron partial-product + psum against the heads_flat-sharded
    # weight.  (The serving map replicates this axis instead — bit-identity.)
    "heads_out": "model",
    "ffn": "model",
    "vocab": "model",
    "expert": "model",
    "expert_ffn": None,
    "seq_sp": "model",
    # paged pools (PAGED_CACHE_RULES) shard their KV-head axis
    "kv_pool": "model",
    # decode KV caches are sharded along the *sequence* axis over "model"
    # (kv-head counts like 8 or 1 don't divide a 16-way axis; sequence
    # always does) — GSPMD turns the softmax/PV over the sharded axis into
    # small logit collectives instead of gathering the cache.
    "kv_seq": "model",
}

# Decode-cache leaf-name → logical axes (rank WITHOUT the scan-stack axis;
# a leading None is prepended automatically for stacked caches).
CACHE_RULES: dict[str, tuple] = {
    "k": ("batch", "kv_seq", None, None),
    "v": ("batch", "kv_seq", None, None),
    "c_kv": ("batch", "kv_seq", None),
    "k_pe": ("batch", "kv_seq", None),
    "conv": ("batch", None, "ffn"),
    "ssm": ("batch", None, None, None),
    "h": ("batch", "ffn"),
    "pos": ("kv_seq",),
    "length": (),
}

# Paged block-pool caches (models/attention.py init_cache(paged=...)) reuse
# some contiguous leaf names ("k", "v", "pos") at *pool* shapes, so they get
# their own rule table, selected by the presence of the "table" leaf in the
# same cache dict.  Pools are KV-head-sharded over "model" (per-head BESF
# attention needs no softmax collectives): they are lane-dense, each KV head
# owning a contiguous range of the minor dim, so splitting that dim splits
# heads — gated on the head count (the ``k_amax`` length) where the layer
# keeps one, so a head is never cut.  The page axis is replicated so the
# host-side KVBlockPool allocator stays device-agnostic — one logical block
# id space, block tables and fill levels replicated across "model".  Slots
# ("batch") shard over "data".  Indivisible dims (MQA's single KV head on a
# multi-way model axis) silently replicate via MeshRules.pspec.
PAGED_CACHE_RULES: dict[str, tuple] = {
    "k": (None, None, "kv_pool"),                 # [nb, bs, Hkv*D]
    "v": (None, None, "kv_pool"),
    "kq": (None, None, None, "kv_pool"),          # [nb, bits, R, Hkv*D/C]
    "k_amax": ("kv_pool",),                       # [Hkv]
    "v_amax": ("kv_pool",),
    "pos": (None, None),                          # [nb, bs] fill levels
    "table": ("batch", None),                     # [slots, MB] block tables
    "length": ("batch",),                         # [slots]
}


def cache_pspecs(rules: MeshRules, cache_tree):
    """PartitionSpec tree for a decode-cache pytree.

    Handles scan stacking (a leading axis is replicated) and routes paged
    cache dicts — recognised by their "table" leaf — through
    PAGED_CACHE_RULES, since paged pool leaves reuse contiguous leaf names
    at different geometries."""

    def spec_for(axes, leaf):
        if leaf.ndim == len(axes) + 1:           # scan-stacked
            axes = (None,) + axes
        return rules.pspec(axes, leaf.shape)

    def walk(node):
        if isinstance(node, dict):
            table = PAGED_CACHE_RULES if "table" in node else CACHE_RULES
            if "table" in node and "k_amax" in node and rules.pspec(
                    ("kv_pool",), node["k_amax"].shape[-1:])[0] is None:
                table = {name: tuple(None if a == "kv_pool" else a
                                     for a in axes)
                         for name, axes in table.items()}
            out = {}
            for name, sub in node.items():
                if isinstance(sub, (dict, list, tuple)):
                    out[name] = walk(sub)
                elif table.get(name) is None:
                    out[name] = rules.pspec([None] * sub.ndim, sub.shape)
                else:
                    out[name] = spec_for(table[name], sub)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(walk(sub) for sub in node)
        return rules.pspec([None] * node.ndim, node.shape)

    return walk(cache_tree)

def cache_shardings(rules: MeshRules, cache_tree):
    """NamedSharding tree (device_put-ready) for a decode-cache pytree."""
    from jax.sharding import NamedSharding
    specs = cache_pspecs(rules, cache_tree)

    def walk(spec_node, cache_node):
        if isinstance(cache_node, dict):
            return {k: walk(spec_node[k], cache_node[k]) for k in cache_node}
        if isinstance(cache_node, (list, tuple)):
            return type(cache_node)(
                walk(s, c) for s, c in zip(spec_node, cache_node))
        return NamedSharding(rules.mesh, spec_node)

    return walk(specs, cache_tree)


# Inference-only axis map for the mesh-sharded paged serving engine
# (ServeConfig.mesh).  Deliberately narrower than the training map: only
# slots ("batch") over "data" and the paged pools' KV heads ("kv_pool")
# over "model" — the per-head BESF attention reads its local heads inside
# a shard_map (models/attention.py:_shard_paged_attention).  Every
# activation stays replicated, so each device runs every matmul at the
# single-device shape: sharding a contraction dim makes GSPMD psum partial
# products, and even a head-sharded projection compiles on TPU to a
# different matmul (a per-device slice of the weight) whose rounding
# differs — either breaks the standing bit-identity invariant (sharded
# serving == single-device, docs/serving.md).
AXIS_MAP_SERVE = {
    "batch": "data",
    "fsdp": None,
    "embed": None,
    "seq": None,
    "heads": None,
    "kv_heads": None,
    "kv_pool": "model",
    "heads_flat": None,
    "heads_out": None,
    "ffn": None,
    "vocab": None,
    "expert": None,
    "expert_ffn": None,
    "expert_dmodel": None,
    "seq_sp": None,
    "kv_seq": None,
}


def make_serve_rules(mesh) -> MeshRules:
    """MeshRules for bit-identical mesh-sharded serving (PagedEngine).

    Parameters are replicated (``param_rules=()`` — serving has no
    optimizer state to shard, and replicated weights keep every matmul's
    contraction in single-device summation order).  Only the paged pools
    shard over "model" (by KV head, ``kv_pool``); slots shard over "data";
    every other activation stays replicated (see ``AXIS_MAP_SERVE``)."""
    return MeshRules(mesh=mesh, axis_map=dict(AXIS_MAP_SERVE),
                     param_rules=())


AXIS_MAP_MULTIPOD = dict(AXIS_MAP_1POD, batch=("pod", "data"))


def make_rules(mesh, *, sequence_parallel: bool = False,
               fsdp: bool = True, moe_ep: bool | None = None,
               n_routed: int = 0, moe_resident: bool = False) -> MeshRules:
    """``moe_ep``: EP-able configs store expert weights with the hidden dim
    FSDP-sharded over "data" (gathered just-in-time by the MoE shard_map);
    expert-TP configs store the hidden dim over "model" permanently.  When
    left None it is derived from ``n_routed`` divisibility."""
    multi_pod = "pod" in mesh.shape
    axis_map = dict(AXIS_MAP_MULTIPOD if multi_pod else AXIS_MAP_1POD)
    if sequence_parallel:
        axis_map["seq"] = "model"
    if not fsdp:
        axis_map["fsdp"] = None
    if moe_ep is None:
        tp = mesh.shape.get("model", 1)
        moe_ep = bool(n_routed) and n_routed % tp == 0
    # EP:        wi [E→model, D, H→data]   (H gathered just-in-time)
    # expert-TP: wi [E, D→data, H→model]   (D gathered just-in-time)
    # Either way expert weights/optimizer state are ~(data×model)-sharded.
    axis_map["expert_ffn"] = "data" if moe_ep else "model"
    axis_map["expert_dmodel"] = None if moe_ep else "data"
    if not moe_ep:
        axis_map["expert"] = None
    if moe_resident:
        # decode: experts fully sharded over data×model, weights resident
        # (storage layout == the resident shard_map's in_specs).
        axis_map["expert"] = ("data", "model")
        axis_map["expert_ffn"] = None
        axis_map["expert_dmodel"] = None
    return MeshRules(mesh=mesh, axis_map=axis_map, param_rules=PARAM_RULES)
