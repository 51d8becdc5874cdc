"""Compile rehearsals: the paged BitStopper kernels, built natively
(``interpret=False``) for a described TPU v5e chip at stablelm-1.6b's
and mellum2-12b-a2.5b's serving shapes.  Nothing runs and no chip is
needed — the TPU compiler ships with jaxlib — but Mosaic refuses here
exactly what it would refuse on the chip (tile-misaligned blocks, scalar
VMEM stores, unsupported layouts), which interpret-mode tests cannot see.

The topology is described only inside the module fixture: loading the TPU
compiler at import time would make pytest-xdist workers collect different
test sets."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quantization as qlib
from repro.core.besf import BitStopperConfig
from repro.kernels.paged_decode import paged_bitstopper_decode
from repro.kernels.paged_verify import paged_bitstopper_verify

# stablelm-1.6b: 32 x 64 heads (MHA), 12 bit planes; the engine's paged
# layout for the chip smoke: page 16, 4 slots, 67-page block tables
# (max_len 1064), a full-capacity pool.
D, PAGE, SLOTS, MB, SQ = 64, 16, 4, 67, 5
# mellum2-12b-a2.5b: 32 query heads over 4 KV heads of 128 (G = 8, 512
# lanes), its long_decode cell's 240-page tables (max_len 3840), and its
# layers' 1024-token window.
GQA_D, GQA_MB, WINDOW = 128, 240, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operands(dev, Hq, Hkv, Sq, D=D, MB=MB):
    cfg = BitStopperConfig()
    pool = 1 + SLOTS * MB                 # a full-capacity pool

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    rows = (SLOTS,) if Sq is None else (SLOTS, Sq)
    q_shape = rows + (Hq, D)
    # the serving cache's lane-dense pools, as init_cache allocates them
    return cfg, (s(q_shape, jnp.float32),
                 s(qlib.plane_pool_shape(pool, cfg.bits, PAGE, Hkv * D),
                   jnp.uint8),
                 s((pool, PAGE, Hkv * D), jnp.float32),
                 s((SLOTS, MB), jnp.int32),
                 s(rows, jnp.int32), s(rows, jnp.int32),
                 s((Hkv,), jnp.float32), s((Hkv,), jnp.float32))


@pytest.mark.parametrize("kernel,heads,stats", [
    ("decode", 32, False),        # the serving hot path, one chip
    ("decode", 8, False),         # per-shard geometry under tp=4
    ("decode", 32, True),         # stats outputs (tests, traffic model)
    ("verify", 32, False),        # speculative draft block, k=4
    ("verify", 8, False),
])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, heads, stats):
    _compile_and_check(one_chip, kernel, heads, heads, stats=stats)


@pytest.mark.parametrize("kernel", ["decode", "verify"])
@pytest.mark.parametrize("window", [None, WINDOW])
def test_paged_kernel_compiles_for_v5e_at_gqa_head_128(one_chip, kernel,
                                                       window):
    """Grouped KV (Hq=32, Hkv=4, D=128): the block-diagonal query spans
    8 query heads per KV head's 128 lanes; full and window layers."""
    _compile_and_check(one_chip, kernel, 32, 4, stats=False, D=GQA_D,
                       MB=GQA_MB, window=window)


def _compile_and_check(dev, kernel, Hq, Hkv, stats, D=D, MB=MB,
                       window=None):
    entry = {"decode": paged_bitstopper_decode,
             "verify": paged_bitstopper_verify}[kernel]
    cfg, args = _operands(dev, Hq, Hkv, None if kernel == "decode" else SQ,
                          D=D, MB=MB)
    compiled = entry.lower(*args, cfg=cfg, window=window, interpret=False,
                           stats=stats).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The pools reach the kernel as the cache stores them: no copy, pad or
    # relayout of a pool-sized array in front of the call.
    pool = 1 + SLOTS * MB
    pool_ops = [line for line in text.splitlines()
                if f"[{pool}," in line
                and re.search(r" (copy|pad|transpose|fusion)\(", line)]
    assert not pool_ops, pool_ops
