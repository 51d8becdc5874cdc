"""The pieces mellum2-12b-a2.5b brought into the program: an expert layer
told which experts it holds, dropless routing in serving, YaRN-scaled
RoPE on full-attention layers, and the engine's window-layer counters."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.moe import MoEConfig, init_moe, moe_ffn


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of a 16-expert layer (4 experts each), every
    share routing over all 16, add up to the layer that holds them all."""
    cfg = MoEConfig(d_model=32, n_routed=16, top_k=4, d_expert=16,
                    dropless=True)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    whole, _ = moe_ffn(p, x, cfg)
    parts = []
    for off in range(0, 16, 4):
        share = dict(p, **{k: p[k][off:off + 4]
                           for k in ("wi_gate", "wi_up", "wo")})
        y, _ = moe_ffn(share, x, dataclasses.replace(cfg, n_held=4,
                                                     offset=off))
        parts.append(y)
        assert float(jnp.abs(y).max()) > 0
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def _crowded(cfg, params):
    """Weights that send every token's first-layer routing to one expert:
    a constant direction added to every embedding row, which the first
    layer's router column of expert 0 reads strongly."""
    d = cfg.d_model
    u = jnp.ones((d,)) / math.sqrt(d)
    params = jax.tree_util.tree_map(lambda a: a, params)
    params["embed"]["table"] = params["embed"]["table"] + 4.0 * u
    seg = params["seg0"][0]["b0"]["moe"]["router"]
    seg["w"] = seg["w"].at[:, 0].add(8.0 * u)
    return params


def test_a_chunk_over_the_old_capacity_serves_as_one_token_at_a_time():
    """Dropless routing: a 24-token chunk whose first layer routes every
    token to one expert (three times the 1.25-factor capacity of 8) gives
    the logits that feeding the tokens one at a time through the cache
    gives.  With capacity buffers the same chunk drops tokens and does
    not."""
    cfg = reduced_config("mellum2-12b-a2.5b").replace(
        segments=(((T.BlockSpec("local_attn", "moe"),
                    T.BlockSpec("attn", "moe")), 1),),
        n_routed=8, top_k=2, window=8, moe_capacity_factor=1.25)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, cfg.vocab)
    params = _crowded(cfg, T.init_model(jax.random.PRNGKey(0), cfg))

    def chunk(c):
        return T.forward(params, tokens, c)[0][0]

    caches = T.init_caches(cfg, 1, 32)
    steps = []
    for t in range(24):
        y, caches, _ = T.forward(params, tokens[:, t:t + 1], cfg,
                                 caches=caches, positions=jnp.arange(t, t + 1))
        steps.append(y[0, 0])
    one_at_a_time = np.stack(steps)
    np.testing.assert_allclose(np.asarray(chunk(cfg.replace(
        moe_dropless=True))), one_at_a_time, rtol=2e-3, atol=2e-3)
    dropped = np.abs(np.asarray(chunk(cfg)) - one_at_a_time).max()
    assert dropped > 0.05


def test_yarn_frequencies_and_factor_by_hand():
    """Head 8, theta 10^4, factor 4 over 64 original positions, beta
    32/1: correction dims 8 ln(64 / (2 pi r)) / (2 ln 10^4) are -0.50 and
    1.01, truncated to 0 and 2, so the ramp over the 4 frequencies is
    0, 1/2, 1, 1 and each is (1 - ramp) f + ramp f / 4 of the plain
    1, 0.1, 0.01, 0.001."""
    yarn = L.Yarn(factor=4.0, original_max_position=64, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.5)
    np.testing.assert_allclose(np.asarray(L.yarn_inv_freq(8, 1e4, yarn)),
                               [1.0, 0.0625, 0.0025, 0.00025], rtol=1e-6)
    # one head at position 3: each pair (x_i, x_{i+4}) turns by 3 f_i and
    # scales by the attention factor
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 8)
    got = np.asarray(L.rope(x, jnp.array([3]), 1e4, yarn))[0, 0]
    ang = 3 * np.array([1.0, 0.0625, 0.0025, 0.00025])
    x1, x2 = np.arange(1.0, 5.0), np.arange(5.0, 9.0)
    want = 1.5 * np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                 x2 * np.cos(ang) + x1 * np.sin(ang)])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_mellum_full_layers_take_yarn_and_window_layers_plain_rope():
    cfg = get_config("mellum2-12b-a2.5b")
    full, window = cfg.attn_config(False), cfg.attn_config(True)
    assert window.yarn is None and window.window == 1024
    assert full.window is None and full.qk_norm and window.qk_norm
    # attention factor 0.1 ln 16 + 1; correction dims of 128 over 8192
    # positions at theta 5e5: 18.08 and 34.98, truncated to 18 and 35
    assert full.yarn.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    f = np.asarray(L.yarn_inv_freq(128, 5e5, full.yarn))
    plain = 5e5 ** -(np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(f[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(f[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all((f[19:35] < plain[19:35]) & (f[19:35] > plain[19:35] / 16))


def test_window_counters_by_hand():
    """One 40-token prompt, 4 new tokens (the first from prefill): three
    decode steps at positions 40, 41, 42.  A full layer attends 41 + 42 +
    43 tokens on 3 pages each; a layer with an 8-token window attends 8
    each, on the one page holding positions 33-42."""
    from repro.serving import PagedEngine, Request, ServeConfig
    cfg = reduced_config("mellum2-12b-a2.5b").replace(
        segments=(((T.BlockSpec("local_attn", "moe"),
                    T.BlockSpec("attn", "moe")), 1),), window=8)
    eng = PagedEngine(cfg, T.init_model(jax.random.PRNGKey(0), cfg),
                      ServeConfig(max_len=64, max_slots=2, page_size=16,
                                  fused_decode=False))
    eng.submit(Request(prompt=np.arange(40, dtype=np.int32),
                       max_new_tokens=4))
    while eng.pending():
        eng.step()
    c = eng.counters
    assert c["decode_steps"] == 3
    assert c["decode_kv_tokens"] == 41 + 42 + 43
    assert c["decode_window_kv_tokens"] == 3 * 8
    assert c["decode_pages_reached"] == 3 * 3
    assert c["decode_window_pages_reached"] == 3 * 1
