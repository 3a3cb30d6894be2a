"""The arrival tape of an open-loop mix: when each request is due and which
mix entry it asks for.

The draw follows the program's ``serve/replay.py`` ``TrafficSpec.schedule``
(mix = shapes x kinds in rank order, Zipf weights ``k^-s``, exponential
gaps at ``rate_hz``), with one change that keeps runs comparable: the gaps
and the entries are drawn once from the mix file's own ``tape_seed``, so
every run offers the same requests with the same gaps, and ``--seed``
only permutes their order.
"""

from __future__ import annotations

import numpy as np

import inputs


def mix(traffic: dict) -> list[tuple[tuple[int, ...], str]]:
    """The ranked (extents, kind) entries, hottest first."""
    return [(inputs.parse_extents(e), k) for e in traffic["shapes"]
            for k in traffic["kinds"]]


def weights(n: int, zipf_s: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -zipf_s
    return w / w.sum()


def tape(traffic: dict, seconds: float, seed: int
         ) -> tuple[np.ndarray, np.ndarray]:
    """``(due_s, entry)`` arrays: due times from the window's start, all
    below ``seconds``, and the mix entry of each request."""
    g = np.random.default_rng(traffic["tape_seed"])
    rate = float(traffic["rate_hz"])
    gaps = []
    t = 0.0
    while True:
        gap = float(g.exponential(1.0 / rate))
        if t + gap >= seconds:
            break
        t += gap
        gaps.append(gap)
    n_mix = len(mix(traffic))
    entries = g.choice(n_mix, size=len(gaps),
                       p=weights(n_mix, traffic["zipf_s"]))
    order = inputs.rng(seed, 2).permutation(len(gaps))
    return np.cumsum(np.asarray(gaps)[order]), entries[order]
