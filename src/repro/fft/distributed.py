"""Distributed FFTs over a device mesh — the pod-scale extension of the paper.

gearshifft benchmarks single-device libraries; real HPC FFT workloads (the
paper's motivating image-reconstruction pipelines) outgrow one device.  We
add mesh-parallel transforms built from shard_map + all_to_all, the
TPU-native analogue of FFTW-MPI / cuFFTMp pencil decompositions:

1D ("four-step across the mesh"): view n = n1*n2 as an (n1, n2) matrix with
   rows sharded.  all_to_all transposes between the column pass and the row
   pass; twiddles are computed per-shard from the device's axis_index.
   Output in TRANSPOSED spectrum order (k = k1 + k2*n1), exactly like
   FFTW-MPI's `FFTW_MPI_TRANSPOSED_OUT` — callers either accept the layout
   (self-inverse round trips, spectral filtering) or pay one more all_to_all.

2D/3D pencil: shard the leading axes, FFT the local axis, all_to_all to
   rotate the next axis into locality, repeat.  Collective volume per device
   per rotation = local block size — the canonical pencil cost model used
   in EXPERIMENTS.md §Roofline.

Axis-name convention: collectives take mesh axis names (str or tuple); the
production mesh uses ('pod','data','model') so 3D transforms shard over
('pod','data') x 'model'.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.trace import named
from . import fourstep
from .nd import _apply_last


def _shard_map(body, mesh, in_specs, out_specs):
    """shard_map with varying-manual-axes checking off: pallas_call has no
    such rule, and the planned local engines are Pallas kernels.  Our
    bodies keep every output dim explicitly sharded or device-invariant,
    so the check adds nothing here."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def executable_name(decomp: str, mesh_shape, shape, inverse: bool,
                    natural: bool) -> str:
    """The executable's name: deterministic, so that the persistent compile
    cache hits in every process; the distributed clients tag their spans
    with it."""
    dims = lambda t: "x".join(str(int(v)) for v in t)
    return (f"fft_{decomp}{dims(mesh_shape)}_{dims(shape)}_"
            f"{'nat' if natural else 'tr'}_{'inv' if inverse else 'fwd'}")


#: A local engine: ``cfft(x, inverse=False)`` transforming the LAST axis —
#: the same contract ``nd.fftn`` consumes, so the per-shard transforms of a
#: distributed plan run through exactly the engines the planner picked
#: (stockham_pallas / fft mixed-radix / chirp-Z / ...), not a hard-coded
#: baseline.
Engine = "Callable[..., jnp.ndarray]"


def _engines_for(rank: int, engines) -> tuple:
    """Normalize ``engines`` to one local engine per global axis (default:
    the matmul four-step jnp baseline, the pre-planner behavior)."""
    if engines is None:
        return (fourstep.fft,) * rank
    if callable(engines):
        return (engines,) * rank
    fns = tuple(engines)
    if len(fns) != rank:
        raise ValueError(f"{len(fns)} local engines for rank {rank}")
    return fns


# ---------------------------------------------------------------------------
# 1D: distributed four-step
# ---------------------------------------------------------------------------
def _combined_index(axes: tuple[str, ...]):
    """Row-major device index over one or more mesh axes (static sizes)."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def fft1d_shard(x_block: jnp.ndarray, n1: int, n2: int, p: int,
                axes: tuple[str, ...], inverse: bool = False,
                engines=None) -> jnp.ndarray:
    """Per-shard body (call under shard_map). x_block: (n1/P, n2) complex,
    rows of the (n1, n2) four-step matrix view, row-sharded over ``axes``.

    Returns (n1/P, n2): block-row k1-slab of D[k1, k2] — flattening device-
    major gives the transposed spectrum X[k1 + k2*n1].

    Inverse note: the two sub-transform passes apply 1/n1 and 1/n2, so the
    global 1/n = 1/(n1*n2) normalization comes out exactly — no correction.
    """
    axis = axes if len(axes) > 1 else axes[0]
    eng1, eng2 = _engines_for(2, engines)   # column (n1) / row (n2) engines
    n = n1 * n2
    # transpose: rows sharded -> columns sharded, j1 fully local
    xt = jax.lax.all_to_all(x_block, axis, split_axis=1, concat_axis=0,
                            tiled=True)                    # (n1, n2/P)
    # column DFTs (over j1)
    xt = jnp.moveaxis(eng1(jnp.moveaxis(xt, 0, -1), inverse=inverse), -1, 0)
    # twiddle T[k1, j2_global] with j2_global = idx*(n2/P) + local
    idx = _combined_index(axes)
    k1 = jnp.arange(n1)
    j2 = idx * (n2 // p) + jnp.arange(n2 // p)
    sign = 2.0 if inverse else -2.0
    ang = (sign * jnp.pi / n) * (k1[:, None] * j2[None, :]).astype(jnp.float64)
    xt = xt * jnp.exp(1j * ang).astype(xt.dtype)
    # transpose back: k1 sharded, j2 local
    xb = jax.lax.all_to_all(xt, axis, split_axis=0, concat_axis=1,
                            tiled=True)                    # (n1/P, n2)
    # row DFTs (over j2)
    return eng2(xb, inverse=inverse)


def _choose_1d_factors(n: int, p: int) -> tuple[int, int]:
    """n = n1*n2 with p | n1 AND p | n2 (every tiled all_to_all in the
    pipeline — including the optional natural-order untranspose — splits one
    of the two factors over the p devices), both as square as possible."""
    best = None
    n1 = p
    while n1 <= n:
        if n % n1 == 0:
            n2 = n // n1
            if n2 % p == 0:
                score = abs(n1 - n2)
                if best is None or score < best[0]:
                    best = (score, n1, n2)
        n1 += p
    if best is None:
        raise ValueError(f"cannot shard n={n} over {p} devices")
    return best[1], best[2]


def can_shard_1d(n: int, p: int) -> bool:
    """Feasibility probe for the planner: does an (n1, n2) factorization
    with p | n1 and p | n2 exist?"""
    try:
        _choose_1d_factors(n, p)
        return True
    except ValueError:
        return False


def make_fft1d(mesh: Mesh, axis: str | tuple[str, ...], n: int,
               inverse: bool = False, natural: bool = False, engines=None):
    """Build a jit-able distributed 1D FFT over ``mesh[axis]``.

    Input: (n,) complex sharded contiguously over ``axis``; output: the
    spectrum with the same sharding — TRANSPOSED order (k = k1 + k2*n1
    block-cyclic, FFTW_MPI_TRANSPOSED_OUT) by default, or natural order for
    one extra all_to_all when ``natural=True``.  ``engines`` routes the two
    local sub-transform passes (lengths n1 and n2) through planner-selected
    engines.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    n1, n2 = _choose_1d_factors(n, p)
    spec_in = P(axes)
    a2a_axis = axes if len(axes) > 1 else axes[0]

    def body(xb):
        # xb arrives (n/P,) = (n1/P * n2,) row-major rows of the matrix view
        blk = xb.reshape(n1 // p, n2)
        out = fft1d_shard(blk, n1, n2, p, axes, inverse=inverse,
                          engines=engines)                 # (n1/P, n2)
        if natural:
            # untranspose: D[k1, k2] -> Y[k2, k1]; flattened device-major
            # this is exactly X[k1 + k2*n1] in contiguous natural order
            out = jax.lax.all_to_all(out, a2a_axis, split_axis=1,
                                     concat_axis=0, tiled=True)  # (n1, n2/P)
            out = out.T                                    # (n2/P, n1)
        return out.reshape(-1)

    fn = _shard_map(body, mesh, (spec_in,), spec_in)
    return jax.jit(named(fn, executable_name("dist1d", (p,), (n,), inverse,
                                             natural))), (n1, n2)


def transposed_to_natural(y: jnp.ndarray, n1: int, n2: int) -> jnp.ndarray:
    """Undo the transposed spectrum order (host-side/test helper)."""
    return y.reshape(n1, n2).T.reshape(-1)


def ifft1d_shard(y_block: jnp.ndarray, n1: int, n2: int, p: int,
                 axes: tuple[str, ...], engines=None) -> jnp.ndarray:
    """Inverse per-shard body consuming the TRANSPOSED spectrum produced by
    :func:`fft1d_shard` (FFTW_MPI_TRANSPOSED_IN analogue).

    y_block: (n1/P, n2) block-row k1-slab of Y[k1, k2] = X[k1 + k2*n1].
    Returns (n1/P, n2) rows of the natural-order signal x[j1*n2 + j2].

    Derivation (x[j] = 1/n sum_k X[k] W_n^{+jk}, j = j1*n2 + j2,
    k = k1 + k2*n1; the cross term W_n^{+ j1*n2*k2*n1} = 1):

        x[j1, j2] = 1/n1 sum_k1 W_{n1}^{+j1 k1} W_n^{+j2 k1}
                    (1/n2 sum_k2 W_{n2}^{+j2 k2} Y[k1, k2])

    i.e. the forward pipeline mirrored: row IDFTs (over k2, local) ->
    twiddle -> transpose -> column IDFTs (over k1).  The two sub-transform
    passes apply 1/n2 and 1/n1, so the global 1/n normalization comes out
    exactly.  Same collective count as forward: two all_to_alls.
    """
    axis = axes if len(axes) > 1 else axes[0]
    eng1, eng2 = _engines_for(2, engines)   # column (n1) / row (n2) engines
    n = n1 * n2
    # row IDFTs (over k2) — k2 is fully local, no communication
    b = eng2(y_block, inverse=True)                        # (n1/P, n2)
    # twiddle W_n^{+ k1_global j2} with k1_global = idx*(n1/P) + local
    idx = _combined_index(axes)
    k1 = idx * (n1 // p) + jnp.arange(n1 // p)
    j2 = jnp.arange(n2)
    ang = (2.0 * jnp.pi / n) * (k1[:, None] * j2[None, :]).astype(jnp.float64)
    b = b * jnp.exp(1j * ang).astype(b.dtype)
    # transpose: k1 sharded -> k1 fully local, j2 sharded
    bt = jax.lax.all_to_all(b, axis, split_axis=1, concat_axis=0,
                            tiled=True)                    # (n1, n2/P)
    # column IDFTs (over k1)
    bt = jnp.moveaxis(eng1(jnp.moveaxis(bt, 0, -1), inverse=True),
                      -1, 0)                               # x[j1, j2-slab]
    # transpose back: rows j1 sharded, j2 local -> natural row-major layout
    return jax.lax.all_to_all(bt, axis, split_axis=0, concat_axis=1,
                              tiled=True)                  # (n1/P, n2)


def make_ifft1d(mesh: Mesh, axis: str | tuple[str, ...], n: int,
                natural: bool = False, engines=None):
    """Build a jit-able inverse of :func:`make_fft1d`'s transform.

    Input: the (n,) spectrum sharded over ``axis`` exactly as ``make_fft1d``
    emitted it — transposed order by default, natural order when
    ``natural=True`` (matching a forward built with ``natural=True``);
    output: the natural-order signal with the same sharding — so
    ifft1d(fft1d(x)) == x without any host-side reordering in either mode.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    n1, n2 = _choose_1d_factors(n, p)
    spec = P(axes)
    a2a_axis = axes if len(axes) > 1 else axes[0]

    def body(yb):
        if natural:
            # mirror the forward's untranspose: natural block (n2/P, n1)
            # -> local transpose -> all_to_all back to (n1/P, n2) k1-slabs
            blk = yb.reshape(n2 // p, n1).T                # (n1, n2/P)
            blk = jax.lax.all_to_all(blk, a2a_axis, split_axis=0,
                                     concat_axis=1, tiled=True)  # (n1/P, n2)
        else:
            blk = yb.reshape(n1 // p, n2)
        out = ifft1d_shard(blk, n1, n2, p, axes, engines=engines)
        return out.reshape(-1)

    fn = _shard_map(body, mesh, (spec,), spec)
    return jax.jit(named(fn, executable_name("dist1d", (p,), (n,), True,
                                             natural))), (n1, n2)


# ---------------------------------------------------------------------------
# ND planned decompositions: slab (1D mesh) and pencil (2D mesh)
# ---------------------------------------------------------------------------
# Both builders take arrays shaped (batch, *shape) — the leading batch dim is
# always present (batch=1 for unbatched problems) and never sharded.  Local
# per-axis transforms run through planner-selected ``engines`` (one per
# global axis, same contract as nd.fftn's per-axis engine list).  Output is
# TRANSPOSED-sharded by default (the cheap layout); ``natural=True`` pays the
# restoring all_to_all(s) so the output sharding matches the input's.

def slab_divisible(shape: Sequence[int], p: int) -> bool:
    """Slab feasibility: p | d0 (input sharding) and p | d1 (the transpose
    all_to_all splits d1 over the mesh)."""
    shape = tuple(shape)
    return (len(shape) >= 2 and p >= 1
            and shape[0] % p == 0 and shape[1] % p == 0)


def pencil_divisible(shape: Sequence[int], pr: int, pc: int) -> bool:
    """Pencil feasibility over a (pr, pc) mesh for a rank-3 transform:
    pr | X, pc | Y (input sharding); pc | Z (first rotation splits Z);
    pr | Y (second rotation splits Y)."""
    shape = tuple(shape)
    if len(shape) != 3:
        return False
    X, Y, Z = shape
    return X % pr == 0 and Y % pc == 0 and Z % pc == 0 and Y % pr == 0


def make_slab_fftnd(mesh: Mesh, axis: str | tuple[str, ...],
                    shape: Sequence[int], *, inverse: bool = False,
                    natural: bool = False, engines=None):
    """Build a jit-able slab-decomposed ND FFT (rank 2 or 3, 1D mesh).

    Global array (batch, d0, d1[, d2]) with d0 sharded over ``axis``.  All
    inner axes (d1[, d2]) transform locally; ONE all_to_all rotates d0 into
    locality (splitting d1) for its transform.  Output sharding: d1-sharded
    TRANSPOSED layout by default, or the input's d0-sharded layout for one
    extra all_to_all when ``natural=True``.  ``inverse`` builds the matching
    inverse: it consumes whichever layout the forward with the same
    ``natural`` emitted and always returns the natural d0-sharded signal.

    Returns ``(fn, in_spec, out_spec)``.
    """
    shape = tuple(int(d) for d in shape)
    rank = len(shape)
    if rank not in (2, 3):
        raise ValueError(f"slab decomposition is rank-2/3 only, got {shape}")
    ax_t = axis if isinstance(axis, str) else tuple(axis)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    if not slab_divisible(shape, p):
        raise ValueError(f"slab: {p} devices must divide d0={shape[0]} "
                         f"and d1={shape[1]}")
    engs = _engines_for(rank, engines)
    tail = (None,) * (rank - 1)
    slab_spec = P(None, ax_t, *tail)                    # d0 sharded
    trans_spec = P(None, None, ax_t, *tail[1:])         # d1 sharded

    def run(x, block_ax, g):
        return _apply_last(x, block_ax,
                           functools.partial(engs[g], inverse=inverse))

    if not inverse or natural:
        # Forward pipeline.  Also the natural-in inverse: the transform is
        # fully separable (no cross-axis twiddle), so the inverse is the
        # same decomposition with inverse per-axis engines.
        def body(xb):                                   # (B, d0/P, d1[, d2])
            for g in range(rank - 1, 0, -1):            # inner axes, local
                xb = run(xb, g + 1, g)
            xb = jax.lax.all_to_all(xb, ax_t, split_axis=2, concat_axis=1,
                                    tiled=True)         # (B, d0, d1/P[, d2])
            xb = run(xb, 1, 0)                          # d0, now local
            if natural:
                xb = jax.lax.all_to_all(xb, ax_t, split_axis=1,
                                        concat_axis=2, tiled=True)
            return xb

        in_spec = slab_spec
        out_spec = slab_spec if natural else trans_spec
    else:
        # TRANSPOSED-in inverse: mirror of the forward, ending natural.
        def body(yb):                                   # (B, d0, d1/P[, d2])
            yb = run(yb, 1, 0)                          # d0, local
            yb = jax.lax.all_to_all(yb, ax_t, split_axis=1, concat_axis=2,
                                    tiled=True)         # (B, d0/P, d1[, d2])
            for g in range(1, rank):                    # inner axes, local
                yb = run(yb, g + 1, g)
            return yb

        in_spec = trans_spec
        out_spec = slab_spec

    fn = _shard_map(body, mesh, (in_spec,), out_spec)
    name = executable_name("slab", (p,), shape, inverse, natural)
    return jax.jit(named(fn, name)), in_spec, out_spec


def make_pencil_fftnd(mesh: Mesh, row_axis, col_axis, shape: Sequence[int],
                      *, inverse: bool = False, natural: bool = False,
                      engines=None):
    """Build a jit-able pencil-decomposed 3D FFT over a (Pr, Pc) mesh.

    Global array (batch, X, Y, Z) with X sharded over ``row_axis`` (Pr) and
    Y over ``col_axis`` (Pc).  Z transforms locally; each remaining axis is
    rotated into locality by one all_to_all (2 rotations total).  Output:
    (X, Y/Pr, Z/Pc)-sharded TRANSPOSED layout by default, or the input's
    pencil layout for two extra all_to_alls when ``natural=True``.
    ``inverse`` consumes whichever layout the matching forward emitted and
    returns the natural pencil-sharded signal.

    Returns ``(fn, in_spec, out_spec)``.
    """
    shape = tuple(int(d) for d in shape)
    if len(shape) != 3:
        raise ValueError(f"pencil decomposition is rank-3 only, got {shape}")
    row_t = row_axis if isinstance(row_axis, str) else tuple(row_axis)
    col_t = col_axis if isinstance(col_axis, str) else tuple(col_axis)
    rows = (row_axis,) if isinstance(row_axis, str) else tuple(row_axis)
    cols = (col_axis,) if isinstance(col_axis, str) else tuple(col_axis)
    pr = 1
    for a in rows:
        pr *= mesh.shape[a]
    pc = 1
    for a in cols:
        pc *= mesh.shape[a]
    if not pencil_divisible(shape, pr, pc):
        raise ValueError(f"pencil: mesh ({pr}x{pc}) incompatible with "
                         f"shape {shape} (need pr|X, pc|Y, pc|Z, pr|Y)")
    engs = _engines_for(3, engines)
    pencil_spec = P(None, row_t, col_t, None)           # (B, X/Pr, Y/Pc, Z)
    trans_spec = P(None, None, row_t, col_t)            # (B, X, Y/Pr, Z/Pc)

    def run(x, block_ax, g):
        return _apply_last(x, block_ax,
                           functools.partial(engs[g], inverse=inverse))

    if not inverse or natural:
        # Forward pipeline (and, separability again, the natural-in inverse).
        def body(xb):                                   # (B, X/Pr, Y/Pc, Z)
            xb = run(xb, 3, 2)                          # Z, local
            xb = jax.lax.all_to_all(xb, col_t, split_axis=3, concat_axis=2,
                                    tiled=True)         # (B, X/Pr, Y, Z/Pc)
            xb = run(xb, 2, 1)                          # Y, local
            xb = jax.lax.all_to_all(xb, row_t, split_axis=2, concat_axis=1,
                                    tiled=True)         # (B, X, Y/Pr, Z/Pc)
            xb = run(xb, 1, 0)                          # X, local
            if natural:
                xb = jax.lax.all_to_all(xb, row_t, split_axis=1,
                                        concat_axis=2, tiled=True)
                xb = jax.lax.all_to_all(xb, col_t, split_axis=2,
                                        concat_axis=3, tiled=True)
            return xb

        in_spec = pencil_spec
        out_spec = pencil_spec if natural else trans_spec
    else:
        # TRANSPOSED-in inverse: exact mirror, ending natural.
        def body(yb):                                   # (B, X, Y/Pr, Z/Pc)
            yb = run(yb, 1, 0)                          # X, local
            yb = jax.lax.all_to_all(yb, row_t, split_axis=1, concat_axis=2,
                                    tiled=True)         # (B, X/Pr, Y, Z/Pc)
            yb = run(yb, 2, 1)                          # Y, local
            yb = jax.lax.all_to_all(yb, col_t, split_axis=2, concat_axis=3,
                                    tiled=True)         # (B, X/Pr, Y/Pc, Z)
            yb = run(yb, 3, 2)                          # Z, local
            return yb

        in_spec = trans_spec
        out_spec = pencil_spec

    fn = _shard_map(body, mesh, (in_spec,), out_spec)
    name = executable_name("pencil", (pr, pc), shape, inverse, natural)
    return jax.jit(named(fn, name)), in_spec, out_spec


# ---------------------------------------------------------------------------
# 2D/3D: pencil decomposition
# ---------------------------------------------------------------------------
def fft3d_shard(x_block: jnp.ndarray, row_axis, col_axis,
                inverse: bool = False) -> jnp.ndarray:
    """Per-shard pencil 3D FFT body (call under shard_map).

    Global array (X, Y, Z); block (X/Pr, Y/Pc, Z) with X sharded over
    ``row_axis`` (size Pr), Y over ``col_axis`` (size Pc).  Returns block of
    the spectrum in (X/Pr, Y/Pc, Z) layout after full 3 axis transforms.
    """
    eng = functools.partial(fourstep.fft, inverse=inverse)
    # 1) FFT along Z (local)
    x = eng(x_block)
    # 2) rotate Y into locality: split Z over col_axis, gather Y
    x = jax.lax.all_to_all(x, col_axis, split_axis=2, concat_axis=1, tiled=True)
    #    now (X/Pr, Y, Z/Pc); FFT along Y
    x = jnp.moveaxis(eng(jnp.moveaxis(x, 1, -1)), -1, 1)
    # 3) rotate X into locality: split Y over row_axis, gather X
    x = jax.lax.all_to_all(x, row_axis, split_axis=1, concat_axis=0, tiled=True)
    #    now (X, Y/Pr, Z/Pc); FFT along X
    x = jnp.moveaxis(eng(jnp.moveaxis(x, 0, -1)), -1, 0)
    # 4) restore canonical sharding (X/Pr, Y/Pc, Z): undo both rotations
    x = jax.lax.all_to_all(x, row_axis, split_axis=0, concat_axis=1, tiled=True)
    x = jax.lax.all_to_all(x, col_axis, split_axis=1, concat_axis=2, tiled=True)
    return x


def make_fft3d(mesh: Mesh, row_axis, col_axis, shape: Sequence[int],
               inverse: bool = False, keep_transposed: bool = False):
    """Build a jit-able pencil 3D FFT.

    Input/output: (X, Y, Z) complex with sharding P(row_axis, col_axis, None).
    ``keep_transposed`` skips step 4 (output sharded (X, Y/Pr, Z/Pc)) —
    the cheaper layout when a roundtrip (e.g. spectral conv) follows.
    """
    row_t = row_axis if isinstance(row_axis, str) else tuple(row_axis)
    col_t = col_axis if isinstance(col_axis, str) else tuple(col_axis)

    def body(xb):
        if keep_transposed:
            eng = functools.partial(fourstep.fft, inverse=inverse)
            x = eng(xb)
            x = jax.lax.all_to_all(x, col_t, split_axis=2, concat_axis=1, tiled=True)
            x = jnp.moveaxis(eng(jnp.moveaxis(x, 1, -1)), -1, 1)
            x = jax.lax.all_to_all(x, row_t, split_axis=1, concat_axis=0, tiled=True)
            return jnp.moveaxis(eng(jnp.moveaxis(x, 0, -1)), -1, 0)
        return fft3d_shard(xb, row_t, col_t, inverse=inverse)

    in_spec = P(row_t, col_t, None)
    out_spec = P(None, row_t, col_t) if keep_transposed else in_spec
    fn = _shard_map(body, mesh, (in_spec,), out_spec)
    size = lambda t: math.prod(mesh.shape[a] for a in
                               ((t,) if isinstance(t, str) else t))
    name = executable_name("fft3d", (size(row_t), size(col_t)), shape,
                           inverse, not keep_transposed)
    return jax.jit(named(fn, name))


def sharding_for(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
