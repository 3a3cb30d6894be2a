"""Faults planted under the timed path, for the tests that see ``correct``
come out false (``rehearse.py --fault NAME``).  Never used by ``run.py``.

* ``unchanged`` — the forward transform returns its input (a step that
  returns its state unchanged);
* ``half_batch`` — only the first half of the batch is transformed, the
  rest of the rows come out zero;
* ``altered`` — one bin of every row's answer is negated where it is
  produced;
* ``no_exchange`` — every all-to-all between chips is left out: each chip
  keeps its own blocks where the exchange would have sent them away.
"""

from __future__ import annotations


def _break(name: str, x, y):
    import jax.numpy as jnp

    if name == "unchanged":
        if x.shape == y.shape:
            return x.astype(y.dtype)
        return x[..., :y.shape[-1]].astype(y.dtype)
    if name == "half_batch":
        keep = jnp.arange(y.shape[0]) < y.shape[0] // 2
        return jnp.where(keep.reshape((-1,) + (1,) * (y.ndim - 1)), y, 0)
    if name == "altered":
        flat = y.reshape(y.shape[0], -1)
        return flat.at[:, 1].multiply(-1).reshape(y.shape)
    raise ValueError(f"unknown fault {name!r}")


def _local_all_to_all(x, axis_name, split_axis, concat_axis, *,
                      axis_index_groups=None, tiled=False):
    import jax
    import jax.numpy as jnp

    if not tiled:
        raise NotImplementedError("the program calls tiled all-to-alls")
    n = jax.lax.axis_size(axis_name)
    return jnp.concatenate(jnp.split(x, n, axis=split_axis),
                           axis=concat_axis)


def install(name: str) -> None:
    """Plant fault ``name`` in the program (imported from this checkout)."""
    import jax

    if name == "no_exchange":
        jax.lax.all_to_all = _local_all_to_all
        return
    from repro.core.clients import dist_fft, jax_fft

    forward_fn = jax_fft._forward_fn

    def broken_forward_fn(problem, cand):
        f = forward_fn(problem, cand)
        return lambda x: _break(name, x, f(x))

    jax_fft._forward_fn = jax_fft.forward_fn = broken_forward_fn

    build_fn = dist_fft.DistFFTNDClient._build_fn

    def broken_build_fn(self, cand, direction):
        from jax.sharding import NamedSharding

        fn, mesh, in_spec, out_spec = build_fn(self, cand, direction)
        if direction == "forward":
            fn = jax.jit(lambda x, fn=fn: _break(name, x, fn(x)),
                         out_shardings=NamedSharding(mesh, out_spec))
        return fn, mesh, in_spec, out_spec

    dist_fft.DistFFTNDClient._build_fn = broken_build_fn
