"""The ``swa_moe`` family (``mellum2-12b-a2.5b``): its pool's bytes and
its work by hand, the window metric by hand, and the paged engine's
served logits against the family's plain reference at a tiny size, with
the float8 control failing the same comparison."""

import copy
import importlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.archs import family

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "bench/configs/mellum2-12b-a2.5b.json").read_text())


def tiny_conf():
    """One period of the file's layers (3 window, 1 full) at toy widths:
    GQA 8/2 at head 16, window 8 (under one 16-token page), 16 experts
    of which this share holds 4 (experts 4-7), top-4."""
    c = copy.deepcopy(CONF)
    c.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
             head_dim=16, num_hidden_layers=4,
             layer_types=CONF["layer_types"][:4],
             mlp_layer_types=CONF["mlp_layer_types"][:4], sliding_window=8,
             vocab_size=256, moe_intermediate_size=32, num_experts=4,
             num_experts_per_tok=4, pool_blocks=17)
    c["deployment"] = dict(c["deployment"], num_experts=16, expert_offset=4)
    return c


def test_pool_bytes_by_hand():
    # 28 layers x (f32 K and V: 2 x 4 heads x 128 x 4 B, 12 planes of
    # 4 x 128 bits)
    per_token = 28 * (2 * 4 * 128 * 4 + 12 * 4 * 128 // 8)
    assert per_token == 136192
    assert family(CONF).work.kv_bytes_per_token(CONF) == per_token


def test_work_by_hand():
    work = family(CONF).work
    counters = {"decode_tokens": 4, "decode_kv_tokens": 10000,
                "decode_window_kv_tokens": 4000}
    # per layer: attention 2304 x 128 x (32 + 4 + 4 + 32), router 2304 x
    # 64, one held expert of 3 x 2304 x 896 (top-8 x 8 held of 64); head
    # 2304 x 98304
    attn, router, expert = 2304 * 128 * 72, 2304 * 64, 3 * 2304 * 896
    params = 28 * (attn + router + expert) + 2304 * 98304
    assert work.matmul_params_per_token(CONF) == params
    pairs = 7 * 10000 + 21 * 4000
    assert work.decode_step_flops(CONF, counters) == \
        2 * params * 4 + 4 * 32 * 128 * pairs
    flops, nbytes = work.besf_kernel_work(CONF, counters)
    assert flops == pairs * 13 * 2 * 32 * 128
    assert nbytes == pairs * (12 * 4 * 128 / 8 + 4 * 128 * 4)


def test_window_kv_idle_share_by_hand():
    read = importlib.import_module("bench.metrics.window_kv_idle_share").read
    rec = {"conf": CONF, "counters": {"decode_kv_tokens": 10000,
                                      "decode_window_kv_tokens": 4000}}
    # 21 of 28 layers hold 10000 tokens and read 4000
    assert read(rec) == pytest.approx(100 * 21 * 6000 / (28 * 10000))
    assert read({"conf": CONF, "counters": {"decode_kv_tokens": 10}}) is None
    rec["counters"]["decode_kv_tokens"] = 0
    assert read(rec) is None


def test_the_program_builds_the_files_model():
    cfg = family(CONF).model.program_config(CONF)
    assert cfg.n_layers == 28 and cfg.window == 1024
    assert (cfg.n_routed, cfg.n_experts_held, cfg.top_k) == (64, 8, 8)
    assert cfg.rope_yarn.factor == 16 and cfg.qk_norm
    assert [s.mixer for s in cfg.segments[0][0]] == \
        ["local_attn"] * 3 + ["attn"]


# Served logits against the reference.  LATS is set not to prune
# (radius 1e6) and the program runs in float32: LATS's keep rule is a
# hard threshold, and a key at it is kept by one side and pruned by the
# other wherever their quantization scales differ (the program's are a
# pool-wide running maximum with 1.25x headroom), which at this size
# (8-key windows, 4 of 16 experts) moves the logits by up to ~0.3 and,
# through a flipped top-4 choice, by more; the chip cell's limit is set
# from its seeds' spread instead.  What is left is 12-bit quantization of
# Q, K and V: over six seeds (CPU) the engine read 0.0028-0.0052 and the
# float8 control, every weight multiply's inputs in e4m3, 0.41-0.65.  The
# limit lies between them, near their geometric mean.
LIMIT = 0.04
SEED = 2 ** 31 + 17


@pytest.fixture(scope="module")
def served():
    from repro.serving import PagedEngine, Request, ServeConfig
    from repro.serving.engine import PagedEngine as _PE

    conf = tiny_conf()
    conf.update(param_dtype="float32", activation_dtype="float32")
    conf["bitstopper"] = dict(conf["bitstopper"], radius=1e6)
    fam = family(conf)
    params = fam.model.make_params(conf, SEED)
    logits = {}

    class Recording(_PE):
        """Keeps each request's logits by token index: a prefill samples
        one row (its first token); a decode tick samples every slot, and
        a slot still prefilling or empty shows an index its own request
        has not sampled yet, which that request's prefill then sets."""

        def _sample_rows(self, rows, rids, counts):
            for row, rid, n in zip(np.asarray(rows), rids, counts):
                if len(rids) == 1:
                    logits[(int(rid), int(n))] = row
                else:
                    logits.setdefault((int(rid), int(n)), row)
            return super()._sample_rows(rows, rids, counts)

    eng = Recording(fam.model.program_config(conf), params, ServeConfig(
        max_len=128, max_slots=2, prefill_chunk=32, page_size=16,
        pool_blocks=17, temperature=0.0, fused_decode=True))
    rng = np.random.default_rng(3)
    reqs = [eng.submit(Request(prompt=rng.integers(0, 256, n, dtype=np.int32),
                               max_new_tokens=6)) for n in (40, 57)]
    while eng.pending():
        eng.step()
    assert isinstance(eng, PagedEngine) and eng.cfg.fused_decode
    assert eng.counters["decode_window_pages_reached"] > 0
    return conf, fam, params, reqs, logits


@pytest.mark.parametrize("side", ["engine", "float8_control"])
def test_served_logits_agree_with_the_reference(served, side):
    conf, fam, params, reqs, logits = served
    widest = 0.0
    for r in reqs:
        prompt = np.asarray(r.prompt, np.int32)
        out = np.asarray(r.generated, np.int32)
        ref = fam.reference.logits_at(conf, params, prompt, out, 256, 8)
        got = (np.stack([logits[(r.rid, n)] for n in range(len(out))])
               if side == "engine" else
               fam.reference.logits_at(conf, params, prompt, out, 256, 8,
                                       mode="float8"))
        widest = max(widest, float(np.abs(got - ref).max()))
    if side == "engine":
        assert widest <= LIMIT
    else:
        assert widest > LIMIT

