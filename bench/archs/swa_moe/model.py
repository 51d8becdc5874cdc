"""The served model as the benchmark builds it: the program's own
``ModelConfig`` for the configuration file's arch at the file's sizes
(its layer pattern read from ``layer_types``, its experts the range the
file's deployment puts on this chip), random weights made on the device
from the seed in the layout the program's transformer reads, and the
fused decode kernel's plane counts on the served caches, each window
layer with its window."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import jax_seed

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_MIXERS = {"sliding_attention": "local_attn", "full_attention": "attn"}
_FFNS = {"sparse": "moe"}


def period(kinds: list) -> int:
    """Length of the shortest unit whose repeats give ``kinds``."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def layer_unit(conf: dict) -> tuple[list, int]:
    """The repeating unit of ``(mixer, ffn)`` kinds, in the program's
    names, and its number of repeats."""
    kinds = [(_MIXERS[a], _FFNS[f]) for a, f in
             zip(conf["layer_types"], conf["mlp_layer_types"])]
    p = period(kinds)
    return kinds[:p], len(kinds) // p


def program_config(conf: dict):
    """The program's ``ModelConfig``: the registry entry of the file's
    arch (its layer unit, norms and expert layout, checked against the
    file's) at the file's sizes, RoPE and window, holding the file's
    experts, serving BitStopper attention in the file's dtypes."""
    from repro.configs import get_config
    from repro.core.besf import BitStopperConfig
    from repro.models.config import BlockSpec
    from repro.models.layers import Yarn

    bs = conf["bitstopper"]
    dep = conf["deployment"]
    full = conf["rope_parameters"]["full_attention"]
    window = conf["rope_parameters"]["sliding_attention"]
    unit, reps = layer_unit(conf)
    cfg = get_config(conf["arch"])
    want = {"unit": tuple(BlockSpec(m, f) for m, f in unit),
            "tie_embeddings": conf["tie_word_embeddings"],
            "qkv_bias": conf["attention_bias"],
            "act": {"silu": "swiglu"}[conf["hidden_act"]],
            "norm": "rms", "qk_norm": True, "n_shared": 0,
            "rope": ("yarn", "default")}
    got = {k: getattr(cfg, k) for k in want if hasattr(cfg, k)}
    got["unit"] = cfg.segments[0][0]
    got["rope"] = ("yarn" if cfg.rope_yarn else "default", "default")
    if (got != want or (full["rope_type"], window["rope_type"]) != want["rope"]
            or full["rope_theta"] != window["rope_theta"]
            or not conf["norm_topk_prob"] or conf["rms_norm_eps"] != 1e-6
            or not conf["use_sliding_window"]):
        raise ValueError(f"registry arch {conf['arch']!r} differs from its "
                         f"configuration file: {got} != {want}")
    return cfg.replace(
        segments=((want["unit"], reps),),
        d_model=conf["hidden_size"], vocab=conf["vocab_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        rope_theta=float(window["rope_theta"]),
        rope_yarn=Yarn(
            factor=float(full["factor"]),
            original_max_position=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=full["attention_factor"]),
        window=conf["sliding_window"],
        n_routed=dep["num_experts"], top_k=conf["num_experts_per_tok"],
        d_expert=conf["moe_intermediate_size"],
        n_experts_held=conf["num_experts"], expert_offset=dep["expert_offset"],
        dtype=conf["activation_dtype"], param_dtype=conf["param_dtype"],
        attn_impl="bitstopper_xla",
        bitstopper=BitStopperConfig(bits=bs["bits"], alpha=bs["alpha"],
                                    radius=bs["radius"]))


def param_shapes(conf: dict):
    """Leaf shapes of the parameter tree: one entry ``b<i>`` per block of
    the unit, each layer's weights stacked on axis 0 over the unit's
    repeats (a list of one unstacked unit where it repeats once)."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    hq, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    f, held = conf["moe_intermediate_size"], conf["num_experts"]
    unit, reps = layer_unit(conf)
    r = (reps,) if reps > 1 else ()

    def block():
        return {
            "norm1": {"scale": r + (d,)}, "norm2": {"scale": r + (d,)},
            "attn": {"wq": {"w": r + (d, hq, dh)},
                     "wk": {"w": r + (d, hkv, dh)},
                     "wv": {"w": r + (d, hkv, dh)},
                     "wo": {"w": r + (hq * dh, d)},
                     "q_norm": {"scale": r + (dh,)},
                     "k_norm": {"scale": r + (dh,)}},
            "moe": {"router": {"w": r + (d, conf["deployment"]["num_experts"])},
                    "wi_gate": r + (held, d, f), "wi_up": r + (held, d, f),
                    "wo": r + (held, f, d)},
        }

    seg = {f"b{i}": block() for i in range(len(unit))}
    tree = {"embed": {"table": (v, d)}, "final_norm": {"scale": (d,)},
            "seg0": seg if reps > 1 else [seg]}
    if not conf["tie_word_embeddings"]:
        tree["unembed"] = {"w": (d, v)}
    return tree


def _leaf_init(path: str, shape, stacked: bool, key):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name == "table":
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[1])
    # the head is [d, vocab]; a layer's weights are [fan_in, ...] after
    # the repeats axis (stacked) and the expert axis (experts)
    lead = 0 if path.startswith("unembed") else (
        int(stacked) + int(name in ("wi_gate", "wi_up")
                           or path.endswith("moe/wo")))
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[lead])


def make_params(conf: dict, seed: int):
    """Every weight in one jitted call on the default device, in the
    file's parameter dtype."""
    shapes = param_shapes(conf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in p) for p, _ in flat]
    stacked = layer_unit(conf)[1] > 1
    dtype = _DTYPES[conf["param_dtype"]]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf_init(p, s, stacked and p.startswith("seg"),
                             k).astype(dtype)
                  for p, (_, s), k in zip(paths, flat, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(jax.random.PRNGKey(jax_seed(seed)))


def plane_stats(engine, seed: int) -> dict | None:
    """The fused kernel's own plane counts (``stats=True``) on every
    layer's live cache, for random queries, each window layer with its
    window: planes fetched against ``bits`` for every page the live rows
    reach.  None where no row is live."""
    from repro.kernels.paged_decode import paged_bitstopper_decode
    active = [i for i, st in enumerate(engine.slots)
              if st is not None and st.prefilled()]
    if not active:
        return None
    cfg = engine.cfg
    _, caches, _ = engine.decode_inputs(active)
    seg = caches["seg0"]
    unit, reps = cfg.segments[0]
    rng = np.random.default_rng([seed, 11])
    lengths = np.zeros(len(engine.slots), np.int32)
    lengths[active] = engine.lengths[active]
    q = jnp.asarray(rng.standard_normal(
        (len(engine.slots), cfg.n_heads, cfg.head_dim)), cfg.activation_dtype)
    ln = jnp.asarray(lengths)
    fetched = dense = 0
    for r in range(reps):
        for i, spec in enumerate(unit):
            if isinstance(seg, list):
                c = seg[r][f"b{i}"]
            else:
                c = {k: seg[f"b{i}"][k][r]
                     for k in ("kq", "v", "table", "k_amax", "v_amax")}
            out = paged_bitstopper_decode(
                q, c["kq"], c["v"], c["table"], ln, jnp.maximum(ln - 1, 0),
                c["k_amax"], c["v_amax"], cfg=cfg.bitstopper,
                window=cfg.window if spec.mixer == "local_attn" else None,
                stats=True)
            rounds = np.asarray(out.rounds)
            fetched += int(rounds.sum())
            dense += int((rounds > 0).sum()) * cfg.bitstopper.bits
    return {"fetched": fetched, "dense": dense}
