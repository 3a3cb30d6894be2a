"""Inputs made from ``--seed``: keys, device arrays and the rows a check
compares.  The same seed gives the same inputs on every run."""

from __future__ import annotations

import numpy as np


def parse_extents(text) -> tuple[int, ...]:
    """``"4096x4096"`` -> ``(4096, 4096)``."""
    return tuple(int(v) for v in str(text).split("x"))


def words(seed: int, *salt: int) -> np.ndarray:
    """Two 32-bit words from any whole ``seed`` and a salt (the index of a
    problem or a mix entry)."""
    return np.random.SeedSequence(
        [int(seed) % 2**64, *salt]).generate_state(2, dtype=np.uint32)


def key(seed: int, *salt: int):
    import jax

    return jax.random.wrap_key_data(words(seed, *salt), impl="threefry2x32")


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(words(seed, *salt))


def make(k, shape, complex_input: bool, sharding=None):
    """Standard normal data of ``shape`` (complex64 or float32), made on the
    device in one jitted call; ``sharding`` places it across a mesh."""
    import jax
    import jax.numpy as jnp

    def gen(kk):
        if not complex_input:
            return jax.random.normal(kk, shape, jnp.float32)
        kr, ki = jax.random.split(kk)
        return jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                               jax.random.normal(ki, shape, jnp.float32))

    return jax.jit(gen, out_shardings=sharding)(k)


def check_rows(seed: int, index: int, batch: int, row_elems: int,
               max_elems: int = 1 << 23, max_rows: int = 16) -> np.ndarray:
    """The batch rows a check compares: the first and the last, and more
    drawn from the seed, as many as keep the reference short."""
    want = min(batch, max(2, min(max_rows, max_elems // max(row_elems, 1))))
    rows = {0, batch - 1}
    g = rng(seed, index, 1)
    while len(rows) < want:
        rows.add(int(g.integers(batch)))
    return np.array(sorted(rows), dtype=np.int64)
