"""Closed loop: ``clients`` clients, each with one request outstanding,
sending its next the moment the last one finishes (no think time)."""

from __future__ import annotations

import numpy as np

from bench.traffic import Schedule, Spec, fixed_sizes


def schedule(mix: dict, seed: int, vocab: int) -> Schedule:
    c, per = mix["clients"], mix["requests_per_client"]
    n = c * per
    plen = fixed_sizes(mix, n, mix["prompt_tokens"], 1)
    olen = fixed_sizes(mix, n, mix["output_tokens"], 2)
    rng = np.random.default_rng([seed, 3])
    clients: list[list[Spec]] = [[] for _ in range(c)]
    for i in range(n):
        prompt = rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
        clients[i % c].append(Spec(prompt=prompt, max_new=int(olen[i]),
                                   client=i % c))
    return Schedule(loop="closed", clients=clients)
