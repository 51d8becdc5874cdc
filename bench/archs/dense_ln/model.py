"""The served model as the benchmark builds it: the program's own
``ModelConfig`` for the configuration file's arch at the file's sizes,
random weights made on the device from the seed in the layout the
program's transformer reads, and the fused decode kernel's plane counts
on the served caches."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import jax_seed

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(conf: dict):
    """The program's ``ModelConfig``: the registry entry of the file's
    arch (its block kinds, activation and norm, checked against the
    file's) at the file's sizes, serving BitStopper attention in the
    file's dtypes."""
    from repro.configs import get_config
    from repro.core.besf import BitStopperConfig
    from repro.models.config import uniform_segments

    bs = conf["bitstopper"]
    cfg = get_config(conf["arch"])
    want = {"tie_embeddings": conf["tie_word_embeddings"],
            "qkv_bias": conf["use_qkv_bias"],
            "act": "swiglu" if conf["ffn_gated"] else "gelu",
            "norm": "ln", "rope_theta": float(conf["rope_theta"])}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"registry arch {conf['arch']!r} differs from its "
                         f"configuration file: {got} != {want}")
    return cfg.replace(
        segments=uniform_segments(conf["num_hidden_layers"]),
        d_model=conf["hidden_size"], vocab=conf["vocab_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        dtype=conf["activation_dtype"], param_dtype=conf["param_dtype"],
        attn_impl="bitstopper_xla",
        bitstopper=BitStopperConfig(bits=bs["bits"], alpha=bs["alpha"],
                                    radius=bs["radius"]))


def param_shapes(conf: dict) -> dict:
    """Leaf shapes of the parameter tree, per layer stacked on axis 0."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    hq, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    f, v = conf["intermediate_size"], conf["vocab_size"]
    attn = {"wq": {"w": (L, d, hq, dh)}, "wk": {"w": (L, d, hkv, dh)},
            "wv": {"w": (L, d, hkv, dh)}, "wo": {"w": (L, hq * dh, d)}}
    if conf["use_qkv_bias"]:
        attn["wq"]["b"] = (L, hq, dh)
        attn["wk"]["b"] = (L, hkv, dh)
        attn["wv"]["b"] = (L, hkv, dh)
    if conf["ffn_gated"]:
        ffn = {"wi_gate": {"w": (L, d, f)}, "wi_up": {"w": (L, d, f)},
               "wo": {"w": (L, f, d)}}
    else:
        ffn = {"wi": {"w": (L, d, f)}, "wo": {"w": (L, f, d)}}
    norm = {"scale": (L, d), "bias": (L, d)}
    tree = {"embed": {"table": (v, d)},
            "final_norm": {"scale": (d,), "bias": (d,)},
            "seg0": {"b0": {"norm1": dict(norm), "norm2": dict(norm),
                            "attn": attn, "ffn": ffn}}}
    if not conf["tie_word_embeddings"]:
        tree["unembed"] = {"w": (d, v)}
    return tree


def _leaf_init(path: str, shape, key):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name in ("bias", "b"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name == "table":
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[1])
    # stacked layer weights are [layers, fan_in, ...]; the head [d, vocab]
    fan_in = shape[0] if path.startswith("unembed") else shape[1]
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def make_params(conf: dict, seed: int):
    """Every weight in one jitted call on the default device, in the
    file's parameter dtype."""
    shapes = param_shapes(conf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    dtype = _DTYPES[conf["param_dtype"]]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf_init(p, s, k).astype(dtype)
                  for p, (_, s), k in zip(paths, flat, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(jax.random.PRNGKey(jax_seed(seed)))


def plane_stats(engine, seed: int) -> dict | None:
    """The fused kernel's own plane counts (``stats=True``) on every
    layer's live cache, for random queries: planes fetched against
    ``bits`` for every page the live rows reach.  None where no row is
    live."""
    from repro.kernels.paged_decode import paged_bitstopper_decode
    active = [i for i, st in enumerate(engine.slots)
              if st is not None and st.prefilled()]
    if not active:
        return None
    cfg = engine.cfg
    _, caches, _ = engine.decode_inputs(active)
    c = caches["seg0"]["b0"]
    rng = np.random.default_rng([seed, 11])
    lengths = np.zeros(len(engine.slots), np.int32)
    lengths[active] = engine.lengths[active]
    q = jnp.asarray(rng.standard_normal(
        (len(engine.slots), cfg.n_heads, cfg.head_dim)), cfg.activation_dtype)
    ln = jnp.asarray(lengths)
    fetched = dense = 0
    for layer in range(cfg.n_layers):
        out = paged_bitstopper_decode(
            q, c["kq"][layer], c["v"][layer], c["table"][layer], ln,
            jnp.maximum(ln - 1, 0), c["k_amax"][layer], c["v_amax"][layer],
            cfg=cfg.bitstopper, stats=True)
        rounds = np.asarray(out.rounds)
        fetched += int(rounds.sum())
        dense += int((rounds > 0).sum()) * cfg.bitstopper.bits
    return {"fetched": fetched, "dense": dense}
