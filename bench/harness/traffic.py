"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) holds one block:

``refresh``  closed-loop solver clients::

    {"clients": 8, "ring": 2}

  The configuration's tenants are split into ``clients`` contiguous
  blocks; each client refreshes its block round-robin, each refresh
  with the next of the tenant's ``ring`` host grid sets.

``query``    open-loop point queries, tenants drawn uniformly::

    {"rate_per_s": 320, "points": 64, "deadline_ms": 10}

  Arrivals are a Poisson process of the given rate, conditioned on its
  count: ``round(rate * seconds)`` arrival times drawn independently and
  uniformly over the window, then sorted.  That is exactly how a Poisson
  process's arrivals lie once their number is known, so the stream
  keeps every swing of rate a Poisson stream has at every scale inside
  the window; only the total, and so the work, is the same for every
  seed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class QuerySchedule:
    offsets: np.ndarray        # (N,) seconds after the window opens
    tenants: np.ndarray        # (N,) tenant index
    points: np.ndarray         # (N, Q, d) float32 in [0, 1)
    deadline_ms: Optional[float]


def arrival_offsets(rate: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival times in [0, seconds) of a Poisson process of
    ``rate`` conditioned on ``round(rate * seconds)`` arrivals."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


#: the keys each block may hold; a mix that asks for anything else is
#: refused rather than run as something it does not say
BLOCK_KEYS = {"refresh": {"clients", "ring"},
              "query": {"rate_per_s", "points", "deadline_ms"}}


def block_of(mix: dict) -> str:
    """The one block a mix holds, its keys checked."""
    if len(mix) != 1 or next(iter(mix)) not in BLOCK_KEYS:
        raise ValueError(f"a mix holds one block of {sorted(BLOCK_KEYS)}, "
                         f"not {sorted(mix)}")
    kind = next(iter(mix))
    extra = set(mix[kind]) - BLOCK_KEYS[kind]
    if extra:
        raise ValueError(f"unknown {kind} keys {sorted(extra)}")
    return kind


def query_schedule(spec: dict, seconds: float, n_tenants: int, dim: int,
                   rng: np.random.Generator) -> QuerySchedule:
    offsets = arrival_offsets(float(spec["rate_per_s"]), seconds, rng)
    tenants = rng.integers(0, n_tenants, size=len(offsets))
    q = int(spec["points"])
    points = rng.random((len(offsets), q, dim), dtype=np.float32)
    return QuerySchedule(offsets, tenants, points, spec.get("deadline_ms"))


def client_blocks(n_tenants: int, clients: int) -> List[List[int]]:
    """Tenants of each closed-loop client: contiguous, near-equal blocks."""
    if not 1 <= clients <= n_tenants:
        raise ValueError(f"{clients} clients for {n_tenants} tenants")
    bounds = np.linspace(0, n_tenants, clients + 1).round().astype(int)
    return [list(range(a, b)) for a, b in zip(bounds[:-1], bounds[1:])]
