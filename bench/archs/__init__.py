"""Architecture families: the code that knows a configuration's blocks.

A configuration file names its family under ``"arch_family"``, and the
family is the package ``bench/archs/<arch_family>/`` with three modules:

* ``model.py``: ``program_config(conf)`` (the program's ``ModelConfig`` at
  the file's sizes), ``param_shapes(conf)`` (leaf shapes of the parameter
  tree the program reads), ``make_params(conf, seed)`` (random weights
  made on the device from the seed) and ``plane_stats(engine, seed)`` (the
  fused decode kernel's own plane counts over the layers the family serves
  with it, or None where no row is live);
* ``reference.py``: ``logits_at(conf, params, prompt, served, t_pad,
  n_out, mode)``, the plain float32 forward pass (``mode="float8"`` the
  control); it imports nothing of the program;
* ``work.py``: ``decode_step_flops(conf, counters)``,
  ``besf_kernel_work(conf, counters)`` and ``kv_bytes_per_token(conf)``,
  where ``counters`` are the engine's counters over the traced slice.

``family(conf)`` is the one way the harness, the metric readers and the
tests reach that code.  The helpers below belong to no family.
"""

from __future__ import annotations

import importlib
import typing

import numpy as np

FUNCTIONS = {
    "model": ("program_config", "param_shapes", "make_params", "plane_stats"),
    "reference": ("logits_at",),
    "work": ("decode_step_flops", "besf_kernel_work", "kv_bytes_per_token"),
}


class Family(typing.NamedTuple):
    name: str
    model: typing.Any
    reference: typing.Any
    work: typing.Any


def family(conf: dict) -> Family:
    """The modules of the configuration's ``arch_family``."""
    name = conf["arch_family"]
    mods = {m: importlib.import_module(f"bench.archs.{name}.{m}")
            for m in FUNCTIONS}
    missing = [f"{m}.{f}" for m, fs in FUNCTIONS.items() for f in fs
               if not hasattr(mods[m], f)]
    if missing:
        raise AttributeError(f"arch family {name!r} lacks {missing}")
    return Family(name=name, **mods)


def jax_seed(seed: int) -> int:
    """A 31-bit key for JAX from a seed of any size (the driver's seeds
    exceed 32 signed bits)."""
    return int(np.random.default_rng(seed).integers(2 ** 31 - 1))


def round_fp8(x, axis):
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (None: one for the whole tensor), back in float32: the
    control's rounding of a weight multiply's inputs."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
