"""Each configuration file builds the program's model at its published
widths through its own arch family, and its pool holds the bytes its
family's reckoning says."""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench.archs import FUNCTIONS, family

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}
DENSE_LN = sorted(n for n, c in CONFIGS.items()
                  if c["arch_family"] == "dense_ln")
# A dense_ln configuration at these models' shapes: 24 layers x (f32 K
# 8 KiB + f32 V 8 KiB + 12-bit planes 3 KiB) per token.
PER_TOKEN = 466944


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_configuration_names_a_family_with_its_functions(name):
    fam = family(CONFIGS[name])
    assert fam.name == CONFIGS[name]["arch_family"]
    for module, functions in FUNCTIONS.items():
        assert all(callable(getattr(getattr(fam, module), f))
                   for f in functions)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_params_match_the_program_at_published_widths(name):
    from repro.models import transformer as T
    conf = CONFIGS[name]
    model = family(conf).model
    cfg = model.program_config(conf)
    got = jax.eval_shape(lambda k: T.init_model(k, cfg),
                         jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, got)
    want = model.param_shapes(conf)
    assert shapes == want
    assert cfg.n_layers == conf["num_hidden_layers"]
    assert cfg.d_model == conf["hidden_size"]
    assert cfg.vocab == conf["vocab_size"]
    assert all(l.dtype == jnp.dtype(conf["param_dtype"])
               for l in jax.tree_util.tree_leaves(got))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_pool_bytes_match_the_reckoning(name):
    from repro.models import transformer as T
    from repro.models.attention import PagedLayout
    conf = CONFIGS[name]
    fam = family(conf)
    cfg = fam.model.program_config(conf).replace(fused_decode=True)
    blocks, bs = conf["pool_blocks"], conf["page_size"]
    caches = jax.eval_shape(lambda: T.init_caches(
        cfg, 4, 2048, jnp.dtype(conf["kv_cache_dtype"]),
        paged=PagedLayout(blocks, bs, 128)))
    pool = sum(l.size * l.dtype.itemsize
               for p, l in jax.tree_util.tree_leaves_with_path(caches)
               if jax.tree_util.keystr(p).endswith(("['k']", "['v']",
                                                    "['kq']")))
    assert pool == blocks * bs * fam.work.kv_bytes_per_token(conf)


@pytest.mark.parametrize("name", DENSE_LN)
def test_dense_ln_bytes_per_token_by_hand(name):
    assert family(CONFIGS[name]).work.kv_bytes_per_token(CONFIGS[name]) \
        == PER_TOKEN


def test_every_cell_names_existing_files():
    names = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in names
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for conf in CONFIGS.values():
        assert (ROOT / "bench" / "archs" / conf["arch_family"]).is_dir()
