"""The one platform decision: where the program runs, and what follows.

Every kernel call site, the planner's feasibility rules and the chirp-Z
engine choice ask this module instead of probing ``jax.devices()`` on
their own, so a test can steer all of them at once (patch
:func:`platform`) and no path can decide differently from another.

* :func:`platform` — the default device's JAX platform (``"tpu"``,
  ``"cpu"``, ...).
* :func:`interpret_mode` — whether a Pallas call runs in interpret mode:
  everywhere but the TPU, and never on it.
* :func:`setup_compile_cache` — JAX's persistent compilation cache at a
  fixed path; entry points call it at start, never at import.
"""

from __future__ import annotations

import os

#: The persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: not set: a fixed directory inside the checkout (the path is part of the
#: cache key, so a directory that moves never hits).  Ignored by git.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def platform() -> str:
    """JAX platform of the default device (``jax.devices()[0]``)."""
    import jax

    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


def interpret_mode(requested: bool | None = None) -> bool:
    """Resolve a Pallas ``interpret`` argument.

    ``None`` (every production call site) interprets off the TPU and
    compiles through Mosaic on it.  An explicit ``True`` is honoured off the
    TPU (tests run kernels interpreted on the CPU) and refused on it: a
    device run that interpreted its kernels would time the interpreter.
    """
    tpu = on_tpu()
    if requested is None:
        return not tpu
    if requested and tpu:
        raise ValueError("Pallas interpret mode requested on the TPU; "
                         "kernels there must compile through Mosaic")
    return bool(requested)


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
