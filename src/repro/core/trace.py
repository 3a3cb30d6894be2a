"""The program's tracing: named host spans and named executables.

Spans mark the boundaries of the clients-and-planner layer.  Each is a
``jax.profiler.TraceAnnotation``: a host event in a profiler trace, and
next to nothing when no profiler session is active.  The set-up spans
(``COUNTED``) also add their count and ``perf_counter`` seconds to a
process-wide table, so that set-up can be read without a trace; the
hot-path spans are trace events only.  Always on.

Executables are named rather than spanned: on a TPU the device trace holds
no scope names on its operations, only the name of the executable each ran
in (``jit_<name>``), so a deterministic name is the unit that ties device
time to a plan.  The name holds no id, hash or counter, so that every
process builds the same name and the persistent compile cache hits.
"""

from __future__ import annotations

import re
import threading
import time

import jax

from .backends import record

#: Set-up spans, also counted in the table: the plan's selection, and an
#: executable's build or compile-cache load.
COUNTED = ("fft.plan", "fft.build")
#: Every span the program emits: set-up, then the hot path (the call of a
#: compiled executable, and the wait for its result).
SPANS = COUNTED + ("fft.dispatch", "fft.sync")

_lock = threading.Lock()
_table: dict[str, list] = {}
_UNSAFE = re.compile(r"[^A-Za-z0-9_]")


class _Counted:
    __slots__ = ("name", "annotation", "t0")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        dt = time.perf_counter() - self.t0
        with _lock:
            entry = _table.setdefault(self.name, [0, 0.0])
            entry[0] += 1
            entry[1] += dt
        return False


def span(name: str, **attrs):
    """``with span("fft.dispatch", exe=name, seq=n): ...``; a name in
    ``COUNTED`` is also counted in the table."""
    if name in COUNTED:
        return _Counted(name, attrs)
    return jax.profiler.TraceAnnotation(name, **attrs)


def dispatch_and_sync(exe: str, seq: int, fn, arg):
    """``fn(arg)``, a compiled executable's call, in ``fft.dispatch``, and
    the wait for its result in ``fft.sync``, both tagged with the
    executable's name and the client's ordinal ``seq``; returns the
    result."""
    with jax.profiler.TraceAnnotation("fft.dispatch", exe=exe, seq=seq):
        out = fn(arg)
    with jax.profiler.TraceAnnotation("fft.sync", exe=exe, seq=seq):
        out.block_until_ready()
    return out


def counters() -> dict[str, tuple[int, float]]:
    """``{span name: (count, seconds)}`` of the ``COUNTED`` spans since the
    last reset."""
    with _lock:
        return {k: (v[0], v[1]) for k, v in _table.items()}


def reset_counters() -> None:
    with _lock:
        _table.clear()


def family(problem, cand) -> str:
    """``xla`` when XLA runs every axis, ``pallas`` when a Pallas kernel
    runs any axis, ``jnp`` otherwise (each backend's record gives its
    family)."""
    families = {record(c.backend).family
                for c in cand.per_axis(problem.rank)}
    if families == {"xla"}:
        return "xla"
    return "pallas" if "pallas" in families else "jnp"


def executable_name(problem, cand, direction: str) -> str:
    """``fft_<family>_<key>_<extents>_b<batch>_<c2c|r2c>_<f32|f64>_<op|ip>_<fwd|inv>``
    (``op`` out of place, ``ip`` in place)."""
    if direction not in ("fwd", "inv"):
        raise ValueError(f"direction is 'fwd' or 'inv', not {direction!r}")
    extents = "x".join(str(int(v)) for v in problem.extents)
    kind = "c2c" if problem.complex_input else "r2c"
    precision = "f64" if problem.precision == "double" else "f32"
    placement = "ip" if problem.inplace else "op"
    key = _UNSAFE.sub("_", cand.key())
    return (f"fft_{family(problem, cand)}_{key}_{extents}_b{problem.batch}"
            f"_{kind}_{precision}_{placement}_{direction}")


def named(fn, name: str):
    """``fn`` under ``name``, so that ``jax.jit`` names its module
    ``jit_<name>``."""
    def wrapper(*args, **kwargs):
        return fn(*args, **kwargs)

    wrapper.__name__ = wrapper.__qualname__ = name
    return wrapper
