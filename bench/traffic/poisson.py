"""Open loop: Poisson arrivals at ``rate_per_s``, each request one of a
few shared system prompts followed by its own user turn."""

from __future__ import annotations

import numpy as np

from bench.traffic import Schedule, Spec, fixed_sizes


def schedule(mix: dict, seed: int, vocab: int) -> Schedule:
    n = mix["requests"]
    fixed = np.random.default_rng([mix["size_seed"], 4])
    gaps = fixed.exponential(1.0 / mix["rate_per_s"], n)
    due = np.cumsum(gaps) - gaps[0]          # the first is due at once
    sp = mix["system_prompts"]
    which = fixed.choice(sp["count"], n, p=np.asarray(sp["weights"])
                         / np.sum(sp["weights"]))
    ulen = fixed_sizes(mix, n, mix["prompt_tokens"], 1)
    olen = fixed_sizes(mix, n, mix["output_tokens"], 2)
    rng = np.random.default_rng([seed, 3])
    systems = [rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
               for _ in range(sp["count"])]
    arrivals = []
    for i in range(n):
        user = rng.integers(0, vocab, int(ulen[i]), dtype=np.int32)
        arrivals.append(Spec(prompt=np.concatenate([systems[which[i]], user]),
                             max_new=int(olen[i]), due=float(due[i])))
    return Schedule(loop="open", arrivals=arrivals)
