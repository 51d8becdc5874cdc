"""Mesh-sharded paged serving: the standing bit-identity invariant.

``ServeConfig.mesh`` shards the paged KV pools over KV heads ("model"
axis) and serving slots over "data", wrapping the four paged attention
calls in ``shard_map`` (docs/serving.md, "Multi-device serving").  The
invariant these tests pin: **the served token streams are bit-identical
to single-device serving on every path** — greedy, seeded sampling,
shared-prefix CoW, speculative, oversubscribed/preempting, through both
the fused kernel and the gather fallback.

Like test_sharding_multidev.py, each test spawns a fresh interpreter
with 8 forced host devices (the main pytest process must keep its single
CPU device); several serving configs share one subprocess to amortize
interpreter + compile startup.
"""

import os
import subprocess
import sys
import textwrap

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=_SRC)
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


_PRELUDE = """
    import numpy as np
    import jax

    from repro.configs import reduced_config
    from repro.core.besf import BitStopperConfig
    from repro.models import transformer as T
    from repro.serving import PagedEngine, Request, ServeConfig
    from repro.launch.mesh import make_debug_mesh

    def serve(mesh, fused, n_kv=None, speculative='off', temperature=0.0,
              oversub=False, shared_prefix=0):
        cfg = reduced_config('stablelm-1.6b').replace(
            attn_impl='bitstopper_xla',
            bitstopper=BitStopperConfig(alpha=0.85))
        if n_kv is not None:
            cfg = cfg.replace(n_kv_heads=n_kv)
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        kw = dict(max_len=64, max_slots=2, prefill_bucket=4, page_size=8,
                  fused_decode=fused, mesh=mesh, temperature=temperature,
                  speculative=speculative)
        if oversub:
            kw.update(pool_blocks=10, oversubscribe=True)
        eng = PagedEngine(cfg, params, ServeConfig(**kw))
        rng = np.random.default_rng(3)
        prefix = rng.integers(0, cfg.vocab, shared_prefix, dtype=np.int32)
        reqs = [Request(prompt=np.concatenate(
                            [prefix, rng.integers(0, cfg.vocab, L,
                                                  dtype=np.int32)]),
                        max_new_tokens=6) for L in (5, 9, 7)]
        eng.generate(reqs, seed=0)
        return [list(r.generated) for r in reqs], eng

    def check(name, mesh, **kw):
        ref, _ = serve(None, **kw)
        got, eng = serve(mesh, **kw)
        assert got == ref, (name, ref, got)
        assert all(ref), (name, 'empty generation proves nothing', ref)
        print(name, 'OK')
        return eng
"""


def test_sharded_tokens_bit_identical_decode_paths():
    """Greedy through both decode paths + seeded sampling: sharded (2,2)
    == single-device, token for token.  Also proves the plane pool is
    physically sharded (local Hkv == Hkv / tp on every device)."""
    _run(_PRELUDE + """
        mesh = make_debug_mesh(2, 2)
        check('greedy-fallback', mesh, fused=False)
        eng = check('greedy-fused', mesh, fused=True)
        check('seeded', mesh, fused=False, temperature=0.8)

        kq = eng.caches['seg0']['b0']['kq']
        for shard in kq.addressable_shards:
            assert shard.data.shape[-2] == kq.shape[-2] // 2, (
                kq.shape, shard.data.shape)
        print('POOL SHARDED: OK', kq.shape, '->', shard.data.shape)
    """)


def test_sharded_tokens_bit_identical_serving_features():
    """Shared-prefix CoW, speculative draft-verify, and oversubscribed
    preemption/resume all stay bit-identical under the mesh — the
    host-side block-table machinery is device-count-blind (tables and
    fill levels replicated over 'model', sharded only over 'data')."""
    _run(_PRELUDE + """
        mesh = make_debug_mesh(2, 2)
        eng = check('shared-prefix', mesh, fused=False, shared_prefix=12)
        assert eng.counters['prefix_hit_tokens'] > 0, eng.counters
        check('speculative', mesh, fused=False, speculative='ngram')
        eng = check('oversubscribed', mesh, fused=False, oversub=True)
        print('FEATURES OK', eng.counters['preemptions'], 'preemptions')
    """)


def test_mqa_indivisible_heads_fall_back_replicated():
    """n_kv_heads == 1 with tp == 2: heads are indivisible, the pools
    replicate over 'model' and attention runs unsharded — still
    bit-identical, and the kq leaf must NOT be head-split."""
    _run(_PRELUDE + """
        mesh = make_debug_mesh(2, 2)
        eng = check('mqa-fallback', mesh, fused=False, n_kv=1)
        kq = eng.caches['seg0']['b0']['kq']
        for shard in kq.addressable_shards:
            assert shard.data.shape == kq.shape, (
                kq.shape, shard.data.shape)
        print('MQA REPLICATED: OK')
    """)


def test_paged_cache_rules_cover_every_leaf():
    """Every leaf of the paged cache tree must be matched by an explicit
    PAGED_CACHE_RULES entry: pool leaves KV-head-sharded over 'model',
    per-slot leaves sharded over 'data' — a newly added leaf name that
    silently falls through to replicated fails here.  Runs on the single
    in-process CPU device (a 1x1 mesh exercises the same rule lookup)."""
    import jax
    from repro.configs import reduced_config
    from repro.models import transformer as T
    from repro.models.attention import PagedLayout
    from repro.sharding.rules import PAGED_CACHE_RULES, cache_pspecs, \
        make_serve_rules

    cfg = reduced_config("stablelm-1.6b").replace(
        attn_impl="bitstopper_xla", fused_decode=True)  # kq plane pool on
    layout = PagedLayout(pool_blocks=12, page_size=8, max_blocks_per_req=4)
    caches = T.init_caches(cfg, batch=2, max_len=32, paged=layout)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = cache_pspecs(make_serve_rules(mesh), caches)

    expect_axis = {"k": "model", "v": "model", "kq": "model",
                   "k_amax": "model", "v_amax": "model",
                   "table": "data", "length": "data", "pos": None}
    flat, _ = jax.tree_util.tree_flatten_with_path(caches)
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat) == len(flat_specs)
    seen = set()
    for (path, leaf), spec in zip(flat, flat_specs):
        name = path[-1].key
        assert name in PAGED_CACHE_RULES, f"unruled paged leaf {name!r}"
        assert len(spec) <= leaf.ndim, (name, spec, leaf.shape)
        want = expect_axis[name]
        assert (want in tuple(spec)) if want else all(
            s is None for s in tuple(spec)), (name, spec)
        seen.add(name)
    assert seen >= {"k", "v", "kq", "k_amax", "v_amax", "table", "length",
                    "pos"}, seen
