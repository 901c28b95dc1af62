"""Open-loop arrival schedule: a copy of
``repro.serve.loadgen.poisson_arrivals`` with the duration fixed
rather than the count, and the schedule a run serves."""
from __future__ import annotations

import numpy as np


def poisson_arrivals(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from 0) of a Poisson process at ``rate``
    requests per second, all below ``seconds``."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0: {rate}")
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return t[t < seconds]


# every seed serves the same gaps and history lengths, in its own order,
# so that runs differ in what they ask and not in how much
SCHEDULE_SEED = 2312_06165


def schedule(rate: float, seconds: float, min_len: int, max_len: int,
             seed: int):
    """(due times [n], history lengths [n]) of one run: one fixed
    multiset of Poisson inter-arrival gaps and of lengths uniform in
    ``min_len..max_len``, each in an order drawn from ``seed``."""
    fixed = np.random.default_rng([SCHEDULE_SEED, int(rate * 1000),
                                   int(seconds * 1000)])
    due = poisson_arrivals(rate, seconds, fixed)
    gaps = np.diff(due, prepend=0.0)
    lengths = fixed.integers(min_len, max_len + 1, due.size)
    rng = np.random.default_rng([int(seed), 5])
    return (np.cumsum(gaps[rng.permutation(due.size)]),
            lengths[rng.permutation(due.size)])
