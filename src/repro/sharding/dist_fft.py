"""Pencil-decomposed distributed rFFT: whole fields stay sharded end to end.

The paper's GPU pipeline assumes one device sees the whole spectrum; our
``sharded`` engine backend (PR 2) only shards *pencil batches*, so a whole
field still had to fit one device's HBM before ``rfftn``.  This module is the
missing distributed transform: a 1-D slab decomposition over one mesh axis
(the field sharded along axis 0), local FFTs along unsharded axes, and
``all_to_all`` transposes under the version-portable ``shard_map`` shim.

Generalized (uneven, padded) slab decomposition: ANY axis lengths are
accepted.  Axis 0 decomposes into ``ceil(N0/D)``-row slabs — the global
device array is zero-padded at the tail of axis 0 to ``D * ceil(N0/D)`` so
``shard_map`` sees an evenly divisible layout; the transpose split axes
(axis 1 for 3-D, the rfft half axis for 2-D) are zero-padded *in transit*
around each ``all_to_all`` and sliced back to their true extent before the
per-axis FFT runs.  Pad rows are exactly zero and every transform pass is
linear, so they stay exactly zero through forward, inverse and the whole
POCS loop: clips are no-ops on zeros, displacement accumulators stay zero,
and the strict-inequality violation test can never fire on a zero component
— convergence counts and shell binning therefore need no explicit pad mask
in the loop body (consumers that *normalize* — e.g. the mean-fluctuation
step of the sharded power spectrum — do mask pad rows explicitly).

Bitwise discipline (the PR 2 parity bar, extended to whole fields): the
single-device ``jnp.fft.rfftn`` computes its passes in a fixed axis order —
r2c along the *last* axis, then c2c along axis 0, then axis 1 (verified
empirically on the CPU DUCC lowering; ``tests/test_dist_fft.py`` gates it).
On TPU the single-device whole-field path runs these passes explicitly
(:func:`repro.kernels.rfft.ops.field_rfftn`), since its C2R is inaccurate.
The distributed transform applies the *same per-axis passes in the same
order*, transposing between them, and each local pass is
batch-invariant (a slab's rows — or a chunk of its last axis — transform
identically whatever the slab or chunk count; the conformance suite gates
this).  ``all_to_all`` moves bits untouched, padding only ever inserts and
removes exact zeros, and the convergence-count collectives are integer
``psum``s, so the distributed POCS loop — and the FFCz blobs built from it —
are bitwise identical to the single-device path whenever the shape's parity
class is ``"bitwise"``.

Parity tri-state (:func:`classify_parity`): the *inverse* transform carries
a ``1/N`` normalization per c2c axis whose placement the fused kernel
chooses internally; splitting the axes into separate passes reproduces it
bit for bit exactly when each c2c-axis length is a power of two (``1/N`` is
then an exponent shift — placement-invariant; the c2r last axis is
unconstrained: its scale sits inside the same final pass either way).

  ``"bitwise"``  every c2c axis is a power of two: the distributed loop
                 trajectory, edit streams and blob payload reproduce the
                 single-device path bit for bit (uneven slabs included —
                 padding is bitwise-neutral).
  ``"bound"``    some c2c axis is not a power of two: blobs may diverge
                 from the single-device path at float32-rounding level, but
                 the dual-bound guarantee holds regardless (the float64
                 polish enforces the bounds on whatever trajectory the
                 float32 loop took).
  *error*        unsupported rank or degenerate extent —
                 :func:`classify_parity` raises ``ValueError``.

Overlapped (double-buffered) transposes: each 3-D ``all_to_all``+FFT pair is
split into ``overlap_chunks`` independent chunks along the last (half-
spectrum) axis — chunk ``i+1``'s ``all_to_all`` carries no data dependency
on chunk ``i``'s FFT, so XLA's async collectives can overlap communication
with compute on real meshes.  Chunking the last axis is bitwise-neutral
(per-line FFTs are batch-invariant; gated in tests).  2-D fields have no
free axis (the half axis is the transpose axis) and always run single-shot.

Data layout (D = mesh axis size, ``H = N_last // 2 + 1``, ``S0 =
ceil(N0/D)``, ``P0 = D * S0``):

  3-D field (N0, N1, N2), device array (P0, N1, N2), local slab (S0, N1, N2):
    rfft ax2 -> [pad ax1 | a2a(1->0) | slice ax0 to N0 | fft ax0]
             -> [pad ax0 | a2a(0->1) | slice ax1 to N1 | fft ax1]
    spectrum device array (P0, N1, H), local block (S0, N1, H): sharded
    along axis 0 like the field, pad rows exactly zero.
  2-D field (N0, N1), device array (P0, N1), local slab (S0, N1):
    rfft ax1 -> [pad ax1 to D*ceil(H/D) | a2a(1->0) | slice ax0 to N0 | fft ax0]
    spectrum device array (N0, D*ceil(H/D)): sharded along the half axis,
    pad columns exactly zero.

``*_local`` functions run *inside* a ``shard_map`` region on local blocks;
:func:`pencil_rfftn` / :func:`pencil_irfftn` are the global-array wrappers.
:class:`ShardedField` is the engine-facing handle (PLAN/EXECUTE accept it).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding.shardmap import shard_map

#: Default number of last-axis chunks each 3-D all_to_all+FFT pair is split
#: into so communication can overlap compute (1 = single-shot).
DEFAULT_OVERLAP_CHUNKS = 2

_PARITY_STATES = ("bitwise", "bound")


def ceil_div(n: int, d: int) -> int:
    return -(-n // d)


def slab_rows(n0: int, n_dev: int) -> int:
    """Rows of axis 0 each device holds (the padded slab height)."""
    return ceil_div(n0, n_dev)


def padded_extent(n: int, n_dev: int) -> int:
    """``n`` zero-padded up to the next multiple of ``n_dev``."""
    return n_dev * ceil_div(n, n_dev)


def classify_parity(shape: Tuple[int, ...], n_dev: int) -> str:
    """Tri-state parity class of a slab decomposition: value or ValueError.

    Returns ``"bitwise"`` when every c2c axis (all but the last for 3-D,
    axis 0 for 2-D) has power-of-two length — the distributed transforms
    then reproduce the fused single-device ``rfftn``/``irfftn`` bit for bit,
    whatever the slab unevenness.  Returns ``"bound"`` otherwise: results
    may differ from the single-device path at float32-rounding level, but
    the FFCz dual-bound guarantee is unconditional on parity.  Raises
    ``ValueError`` (the *error* state) for unsupported ranks or degenerate
    extents — the only shape restrictions left; divisibility by the mesh is
    handled by the padded decomposition and never an error.
    """
    if len(shape) not in (2, 3):
        raise ValueError(
            f"pencil-decomposed FFT supports 2-D and 3-D fields, got rank {len(shape)} "
            f"(shape {shape}); tile other ranks through the engine's pencil batches instead"
        )
    if any(int(n) < 1 for n in shape):
        raise ValueError(f"field shape {shape} has a degenerate (< 1) axis extent")
    if n_dev < 1:
        raise ValueError(f"mesh axis size must be >= 1, got {n_dev}")
    c2c = shape[:-1]
    if all((int(n) & (int(n) - 1)) == 0 for n in c2c):
        return "bitwise"
    return "bound"


def validate_pencil_shape(
    shape: Tuple[int, ...], n_dev: int, strict_bitwise: bool = True
) -> str:
    """Classify ``shape``'s parity; raise when bitwise is demanded but absent.

    The divisibility constraints of the pre-padded decomposition are gone:
    any 2-D/3-D shape slab-decomposes over any mesh size.  With
    ``strict_bitwise`` (the default), a ``"bound"``-class shape (some c2c
    axis not a power of two) raises instead of silently losing blob parity;
    ``strict_bitwise=False`` accepts it.  Returns the parity class.
    """
    parity = classify_parity(tuple(int(n) for n in shape), n_dev)
    if strict_bitwise and parity != "bitwise":
        bad = [(a, int(n)) for a, n in enumerate(shape[:-1]) if int(n) & (int(n) - 1)]
        a, n = bad[0]
        raise ValueError(
            f"axis {a} length {n} is not a power of two: the inverse FFT's "
            f"1/{n} normalization then rounds differently split per-axis "
            f"than fused, so blobs would not be bitwise identical to the "
            f"single-device path; request parity='auto' (strict_bitwise=False) "
            f"to accept float32-rounding-level divergence (bounds still hold)"
        )
    return parity


@dataclasses.dataclass(frozen=True)
class DistSpec:
    """Static description of one slab decomposition (hashable, jit-static).

    Carried by the ``dist`` mode of
    :func:`repro.core.pocs.alternating_projection` and by the ``*_local``
    transform bodies: the true global shape, the mesh axis, its size (needed
    to size transit padding — unknowable from a traced block alone), and the
    transpose overlap chunk count.
    """

    axis_name: str
    gshape: Tuple[int, ...]
    n_dev: int
    overlap_chunks: int = DEFAULT_OVERLAP_CHUNKS


def freq_partition_spec(ndim: int, axis_name: str) -> P:
    """PartitionSpec of the distributed half-spectrum for a rank-``ndim`` field."""
    return P(axis_name) if ndim == 3 else P(None, axis_name)


def local_freq_shape(gshape: Tuple[int, ...], n_dev: int) -> Tuple[int, ...]:
    """Local (per-device) half-spectrum block shape, pad rows/columns included."""
    h = gshape[-1] // 2 + 1
    if len(gshape) == 3:
        return (slab_rows(gshape[0], n_dev), gshape[1], h)
    return (gshape[0], ceil_div(h, n_dev))


def padded_freq_shape(gshape: Tuple[int, ...], n_dev: int) -> Tuple[int, ...]:
    """Global (device-array) half-spectrum shape, pad rows/columns included."""
    h = gshape[-1] // 2 + 1
    if len(gshape) == 3:
        return (padded_extent(gshape[0], n_dev), gshape[1], h)
    return (gshape[0], padded_extent(h, n_dev))


def padded_spatial_shape(gshape: Tuple[int, ...], n_dev: int) -> Tuple[int, ...]:
    """Global (device-array) spatial shape: axis 0 padded to a slab multiple."""
    return (padded_extent(gshape[0], n_dev),) + tuple(gshape[1:])


def local_pair_weights(
    gshape: Tuple[int, ...], freq_shape: Tuple[int, ...], axis_name: str
):
    """Conjugate-pair multiplicities for a *local* half-spectrum block.

    3-D blocks keep the whole half axis locally, so the static
    :func:`repro.core.cubes.rfft_pair_weights` plane broadcasts as-is (pad
    rows carry weights, but their components are exactly zero, so weighted
    reductions over them vanish).  2-D blocks shard the half axis, so global
    column indices come from ``axis_index`` (traced — call inside the
    ``shard_map`` region only); transit-pad columns beyond the true half
    extent get weight 0.
    """
    # deferred: importing repro.core at module scope would cycle through
    # repro.core.__init__ -> engine -> this module
    from repro.core.cubes import rfft_pair_weights

    if len(gshape) == 3:
        return rfft_pair_weights(gshape)
    n = gshape[-1]
    h = n // 2 + 1
    h_loc = freq_shape[-1]
    col = jax.lax.axis_index(axis_name) * h_loc + jnp.arange(h_loc)
    w = jnp.where(col == 0, 1, 2)
    if n % 2 == 0:
        w = jnp.where(col == h - 1, 1, w)
    w = jnp.where(col >= h, 0, w)  # transit-pad columns: not spectrum at all
    return w.astype(jnp.int32)[None, :]


def _pad_axis_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _transpose_apply(
    t: jnp.ndarray,
    spec: DistSpec,
    split_axis: int,
    concat_axis: int,
    keep: int,
    apply_fn,
) -> jnp.ndarray:
    """One transpose+FFT pair: pad -> all_to_all -> slice -> per-axis pass.

    Pads ``split_axis`` with zeros to a mesh-size multiple so the tiled
    ``all_to_all`` is well formed on any extent, slices ``concat_axis`` back
    to its true extent ``keep`` (dropping slab padding before the transform
    sees it), then runs ``apply_fn`` (the c2c/c2r pass along
    ``concat_axis``).  When the last axis is free (3-D) and
    ``spec.overlap_chunks > 1``, the pair is double-buffered: the block is
    split into independent last-axis chunks so chunk ``i+1``'s all_to_all
    can overlap chunk ``i``'s FFT on meshes with async collectives.
    Chunking is bitwise-neutral (per-line FFTs are batch-invariant).
    """

    def one(piece: jnp.ndarray) -> jnp.ndarray:
        piece = _pad_axis_to(piece, split_axis, spec.n_dev)
        piece = jax.lax.all_to_all(
            piece,
            spec.axis_name,
            split_axis=split_axis,
            concat_axis=concat_axis,
            tiled=True,
        )
        if piece.shape[concat_axis] != keep:
            piece = jax.lax.slice_in_dim(piece, 0, keep, axis=concat_axis)
        return apply_fn(piece)

    last = t.ndim - 1
    chunks = spec.overlap_chunks
    if chunks <= 1 or last in (split_axis, concat_axis) or t.shape[last] < chunks:
        return one(t)
    base, rem = divmod(t.shape[last], chunks)
    sizes = [base + (1 if i < rem else 0) for i in range(chunks)]
    pieces, off = [], 0
    for sz in sizes:
        pieces.append(jax.lax.slice_in_dim(t, off, off + sz, axis=last))
        off += sz
    return jnp.concatenate([one(p) for p in pieces], axis=last)


def rfftn_local(block: jnp.ndarray, spec: DistSpec) -> jnp.ndarray:
    """Distributed ``rfftn`` body: local passes + padded all_to_all transposes.

    The pass order (r2c last axis, then c2c axis 0, then axis 1) mirrors the
    fused single-device ``jnp.fft.rfftn`` exactly; slab/transit padding is
    sliced away before each c2c pass, so every transform runs at its true
    length (gated by tests/test_dist_fft.py and the conformance suite).
    """
    gshape = spec.gshape
    nd = len(gshape)
    r = jnp.fft.rfft(block, axis=nd - 1)
    t = _transpose_apply(
        r,
        spec,
        split_axis=1,
        concat_axis=0,
        keep=gshape[0],
        apply_fn=lambda p: jnp.fft.fft(p, axis=0),
    )
    if nd == 2:
        return t
    return _transpose_apply(
        t,
        spec,
        split_axis=0,
        concat_axis=1,
        keep=gshape[1],
        apply_fn=lambda p: jnp.fft.fft(p, axis=1),
    )


def _c2r_last(p: jnp.ndarray, n: int, fft_impl: str) -> jnp.ndarray:
    """The local last-axis C2R pass: :func:`repro.kernels.rfft.ops.c2r_last`.

    ``fft_impl="packed"`` swaps XLA's C2R custom call (the measured slow
    half of the loop) for the pack-trick pass — a per-line transform, so it
    composes with the pencil decomposition unchanged (every line it sees is
    a full half-spectrum of a real line).  On CPU the packed pass rounds
    differently from the fused single-device inverse, so distributed parity
    under it is ``"bound"``, never ``"bitwise"``.  Where XLA's C2R is
    inaccurate (TPU) ``"xla"`` takes the pack-trick pass too, exactly as the
    single-device per-axis inverse does.
    """
    from repro.kernels.rfft import ops as rfft_ops

    return rfft_ops.c2r_last(p, n, packed=fft_impl == "packed")


def irfftn_local(
    block: jnp.ndarray, spec: DistSpec, fft_impl: str = "xla"
) -> jnp.ndarray:
    """Distributed ``irfftn`` body (inverse pass order: axis 0, axis 1, c2r last).

    ``fft_impl="packed"`` runs the final local c2r pass through the
    pack-trick transform (see :func:`_c2r_last`).
    """
    gshape = spec.gshape
    nd = len(gshape)
    if nd == 2:
        t = jnp.fft.ifft(block, axis=0)
        return _transpose_apply(
            t,
            spec,
            split_axis=0,
            concat_axis=1,
            keep=gshape[-1] // 2 + 1,
            apply_fn=lambda p: _c2r_last(p, gshape[1], fft_impl),
        )
    t = _transpose_apply(
        block,
        spec,
        split_axis=1,
        concat_axis=0,
        keep=gshape[0],
        apply_fn=lambda p: jnp.fft.ifft(p, axis=0),
    )
    t = _transpose_apply(
        t,
        spec,
        split_axis=0,
        concat_axis=1,
        keep=gshape[1],
        apply_fn=lambda p: jnp.fft.ifft(p, axis=1),
    )
    return _c2r_last(t, gshape[2], fft_impl)


def _as_parity_request(parity, strict_bitwise) -> str:
    """Normalize the user's parity request; bools alias the legacy kwarg."""
    if strict_bitwise is not None:
        parity = strict_bitwise
    if parity is True:
        return "bitwise"
    if parity is False or parity is None or parity == "auto":
        return "auto"
    if parity in _PARITY_STATES:
        return parity
    raise ValueError(
        f"parity must be 'auto', 'bitwise' or 'bound' (or a legacy strict_bitwise "
        f"bool), got {parity!r}"
    )


class ShardedField:
    """A real 2-D/3-D field slab-sharded along axis 0 over one mesh axis.

    The engine-facing handle for distributed whole-field FFCz:
    ``CorrectionEngine.plan_field`` / ``execute_field`` and ``FFCz.compress``
    accept it, keeping field-sized device state sharded through the whole
    spectral pipeline.  ANY axis extents are accepted: the device array is
    the field zero-padded at the tail of axis 0 to an even slab multiple
    (``padded_shape``), while ``shape`` stays the true extent and every
    host-facing accessor (``to_host``, the engine's plan/encode staging)
    works on the unpadded field.

    ``parity`` is the requested parity class: ``"auto"`` (default) accepts
    whatever :func:`classify_parity` assigns the shape; ``"bitwise"``
    *requires* single-device blob parity and raises on a ``"bound"``-class
    shape; ``"bound"`` documents that the caller expects divergence.  The
    classification itself is always available as :attr:`parity`.  The
    legacy ``strict_bitwise`` bool is accepted as an alias
    (``True == "bitwise"``, ``False == "auto"``).

    ``to_host()`` is the explicit (cached) host staging used only at the
    base-compressor and edit-encode boundaries — the same host-RAM boundary
    the single-device pipeline has; device HBM never holds the gathered
    field.
    """

    def __init__(
        self,
        array,
        mesh,
        axis_name: str = "data",
        parity: Union[str, bool, None] = "auto",
        overlap_chunks: int = DEFAULT_OVERLAP_CHUNKS,
        strict_bitwise: Optional[bool] = None,
    ):
        shape = tuple(int(n) for n in array.shape)
        n_dev = mesh.shape[axis_name]
        self.parity_requested = _as_parity_request(parity, strict_bitwise)
        self.parity = classify_parity(shape, n_dev)
        if self.parity_requested == "bitwise" and self.parity != "bitwise":
            validate_pencil_shape(shape, n_dev, strict_bitwise=True)  # raises
        self.mesh = mesh
        self.axis_name = axis_name
        self.overlap_chunks = int(overlap_chunks)
        self.gshape = shape
        self.padded_shape = padded_spatial_shape(shape, n_dev)
        x32 = np.asarray(array, dtype=np.float32)
        pad0 = self.padded_shape[0] - shape[0]
        if pad0:
            x32 = np.pad(x32, [(0, pad0)] + [(0, 0)] * (len(shape) - 1))
        self.array = jax.device_put(x32, NamedSharding(mesh, self.spec))
        self._host: Optional[np.ndarray] = None

    @classmethod
    def shard(
        cls,
        x: np.ndarray,
        mesh=None,
        axis_name: str = "data",
        parity: Union[str, bool, None] = "auto",
        overlap_chunks: int = DEFAULT_OVERLAP_CHUNKS,
        strict_bitwise: Optional[bool] = None,
    ) -> "ShardedField":
        """Shard a host array over ``mesh[axis_name]`` (default: all devices)."""
        if mesh is None:
            mesh = make_mesh((len(jax.devices()),), (axis_name,))
        return cls(
            x, mesh, axis_name, parity, overlap_chunks, strict_bitwise=strict_bitwise
        )

    @property
    def spec(self) -> P:
        return P(self.axis_name)

    @property
    def freq_spec(self) -> P:
        return freq_partition_spec(self.ndim, self.axis_name)

    @property
    def shape(self) -> Tuple[int, ...]:
        """The TRUE (unpadded) global field shape."""
        return self.gshape

    @property
    def ndim(self) -> int:
        return len(self.gshape)

    @property
    def n_dev(self) -> int:
        return self.mesh.shape[self.axis_name]

    @property
    def padded_freq_shape(self) -> Tuple[int, ...]:
        return padded_freq_shape(self.gshape, self.n_dev)

    @property
    def freq_shape(self) -> Tuple[int, ...]:
        """The TRUE (unpadded) rfft half-spectrum shape."""
        return tuple(self.gshape[:-1]) + (self.gshape[-1] // 2 + 1,)

    @property
    def dist_spec(self) -> DistSpec:
        return DistSpec(self.axis_name, self.gshape, self.n_dev, self.overlap_chunks)

    def unpad_spatial(self, a):
        """Slice a padded (device-layout) spatial array to the true extents."""
        return a[: self.gshape[0]]

    def unpad_freq(self, a):
        """Slice a padded (device-layout) half-spectrum to the true extents."""
        if self.ndim == 3:
            return a[: self.gshape[0]]
        return a[:, : self.freq_shape[-1]]

    def pad_spatial_np(self, grid: np.ndarray, fill: float = 0.0) -> np.ndarray:
        """Pad a true-extent spatial grid to the device layout.

        ``fill`` sets the pad-row value — bound grids (ROI ``E_n``) pad with
        the background bound so the zero pad rows of the sharded field stay
        inside their cube (``clip(0, ±fill) == 0`` needs ``fill > 0``).
        """
        pad0 = self.padded_shape[0] - self.gshape[0]
        if pad0:
            widths = [(0, pad0)] + [(0, 0)] * (self.ndim - 1)
            return np.pad(grid, widths, constant_values=fill)
        return grid

    def pad_freq_np(self, grid: np.ndarray) -> np.ndarray:
        """Zero-pad a true-extent half-spectrum grid to the device layout."""
        pfs = self.padded_freq_shape
        widths = [(0, p - t) for p, t in zip(pfs, grid.shape)]
        if any(w != (0, 0) for w in widths):
            return np.pad(grid, widths)
        return grid

    def to_host(self) -> np.ndarray:
        """Gathered UNPADDED host copy (cached) — the codec staging buffer."""
        if self._host is None:
            self._host = np.asarray(self.unpad_spatial(self.array))
        return self._host


@functools.lru_cache(maxsize=None)
def _pencil_fft_fn(mesh, spec: DistSpec, inverse: bool):
    fspec = freq_partition_spec(len(spec.gshape), spec.axis_name)
    if inverse:
        fn = lambda b: irfftn_local(b, spec)  # noqa: E731
        in_spec, out_spec = fspec, P(spec.axis_name)
    else:
        fn = lambda b: rfftn_local(b, spec)  # noqa: E731
        in_spec, out_spec = P(spec.axis_name), fspec
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec))


def pencil_rfftn(field: ShardedField):
    """Distributed ``rfftn`` of a :class:`ShardedField` -> sharded half-spectrum.

    Returns a global complex array in the PADDED device layout
    (:attr:`ShardedField.padded_freq_shape`, laid out per
    :func:`freq_partition_spec`); pad rows/columns are exactly zero and
    ``field.unpad_freq`` slices them away.  The true-extent region is
    bitwise identical to ``jnp.fft.rfftn`` of the gathered field for
    ``"bitwise"``-class shapes.
    """
    return _pencil_fft_fn(field.mesh, field.dist_spec, False)(field.array)


def pencil_irfftn(
    spectrum,
    gshape: Tuple[int, ...],
    mesh,
    axis_name: str = "data",
    parity: Union[str, bool, None] = "auto",
    overlap_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    strict_bitwise: Optional[bool] = None,
):
    """Distributed ``irfftn`` -> real field sharded along axis 0.

    ``spectrum`` may be given in the padded device layout of ANY writer mesh
    (what :func:`pencil_rfftn` returns — pad rows/columns are zero and sit
    at the tail, so a foreign mesh's padding is sliced off) or at the true
    half-spectrum extents; either is re-padded to THIS mesh's layout on
    host.  Returns the UNPADDED global field.
    """
    gshape = tuple(int(n) for n in gshape)
    n_dev = mesh.shape[axis_name]
    if _as_parity_request(parity, strict_bitwise) == "bitwise":
        validate_pencil_shape(gshape, n_dev, strict_bitwise=True)
    else:
        classify_parity(gshape, n_dev)
    pfs = padded_freq_shape(gshape, n_dev)
    if tuple(spectrum.shape) != pfs:
        true_fs = tuple(gshape[:-1]) + (gshape[-1] // 2 + 1,)
        if any(s < t for s, t in zip(spectrum.shape, true_fs)):
            raise ValueError(
                f"spectrum shape {tuple(spectrum.shape)} is smaller than the "
                f"half-spectrum {true_fs} of field shape {gshape}; pass the "
                f"true-extent spectrum or a padded device layout"
            )
        spectrum = np.asarray(spectrum)[tuple(slice(0, t) for t in true_fs)]
        widths = [(0, p - t) for p, t in zip(pfs, true_fs)]
        spectrum = np.pad(spectrum, widths)
    spectrum = jax.device_put(
        spectrum, NamedSharding(mesh, freq_partition_spec(len(gshape), axis_name))
    )
    spec = DistSpec(axis_name, gshape, n_dev, int(overlap_chunks))
    out = _pencil_fft_fn(mesh, spec, True)(spectrum)
    return out[: gshape[0]]
