"""The one generator of traffic: a mix is a file of parameters
(``bench/traffic/<name>.json``) that this module reads.

``{"loop": "closed", "clients": C, "warm_waves": k}``
    C clients that each wait for their reply before sending again.
``{"loop": "open", "rate_rps": r, "warm_waves": k}``
    Independent clients: ``round(r * seconds)`` arrivals, placed as a
    Poisson process with that many arrivals in the window is, uniformly
    and independently (then sorted), from the seed.
"""
from __future__ import annotations

import hashlib
from typing import List, Mapping

import numpy as np


def sub_seed(seed: int, *tags) -> int:
    """A 63-bit seed for one part of a run (a wave, the arrivals), drawn
    from the run's seed; any whole number, however large, is taken."""
    text = ":".join(str(t) for t in (seed,) + tags)
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:8],
                          "little") >> 1


def is_open(traffic: Mapping) -> bool:
    loop = traffic["loop"]
    if loop not in ("open", "closed"):
        raise ValueError(f"traffic loop must be open or closed, got {loop!r}")
    return loop == "open"


def open_arrivals(traffic: Mapping, seed: int, seconds: float
                  ) -> List[float]:
    """Due times in seconds from the window's opening."""
    n = int(round(traffic["rate_rps"] * seconds))
    rng = np.random.default_rng(sub_seed(seed, "arrivals"))
    return sorted(float(t) for t in rng.uniform(0.0, seconds, n))
