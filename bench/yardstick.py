"""The benchmark's own arithmetic: peaks, least bytes, quantiles.

Kept with the benchmark so that every PR computes these numbers the same
way and no PR that claims a gain can change them.
"""

from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))

C64_BYTES = 8
F32_BYTES = 4


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")
          ) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def spectrum_extents(extents, real: bool) -> tuple[int, ...]:
    """Extents of a forward transform's output: R2C keeps n/2+1 bins of the
    last axis."""
    ext = tuple(int(v) for v in extents)
    return ext[:-1] + (ext[-1] // 2 + 1,) if real else ext


def least_bytes(extents, batch: int, real: bool) -> int:
    """Bytes one forward or one inverse transform has to move at the least:
    its input array read once plus its output array written once, at their
    dtypes (f32 real, c64 complex), whatever path implements it.  The same
    count holds for the inverse, which reads the spectrum and writes the
    signal."""
    signal = batch * math.prod(extents)
    spectrum = batch * math.prod(spectrum_extents(extents, real))
    return signal * (F32_BYTES if real else C64_BYTES) + spectrum * C64_BYTES


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between closest
    ranks (numpy's default); ``inf`` entries count as the largest values,
    so a missed request lands in the tail."""
    v = sorted(float(x) for x in values)
    if not v:
        return float("nan")
    pos = q * (len(v) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    if v[hi] == math.inf:
        return math.inf if pos > lo or v[lo] == math.inf else v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
