"""Traffic: a mix is a data file ``<mix>.json`` in this directory; its
``loop`` names the generator module here that turns it, with a seed, into
a request schedule."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(eq=False)
class Spec:
    """One request as the load generator sends it."""
    prompt: np.ndarray          # [S] int32 token ids
    max_new: int                # output tokens asked for (greedy)
    due: float | None = None    # open loop: seconds after the window opens
    client: int | None = None   # closed loop: the client that sends it


@dataclasses.dataclass
class Schedule:
    loop: str                   # "closed" | "open"
    clients: list | None = None     # closed: one list of Specs per client
    arrivals: list | None = None    # open: Specs in order of ``due``


def load_mix(name: str) -> dict:
    with open(HERE / f"{name}.json") as f:
        return json.load(f)


def make_schedule(mix: dict, seed: int, vocab: int) -> Schedule:
    gen = importlib.import_module(f"bench.traffic.{mix['loop']}")
    return gen.schedule(mix, seed, vocab)


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from a length distribution of a mix file."""
    if spec["dist"] == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    elif spec["dist"] == "lognormal":
        x = np.round(spec["median"] * np.exp(spec["sigma"]
                                             * rng.standard_normal(n)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def fixed_sizes(mix: dict, n: int, spec: dict, salt: int) -> np.ndarray:
    """``n`` lengths drawn from the mix's own ``size_seed``, the same for
    every run seed: a seed changes the tokens and the weights, never the
    amount or the order of the work, so runs on different seeds measure
    the same work."""
    return draw(spec, n, np.random.default_rng([mix["size_seed"], salt]))
