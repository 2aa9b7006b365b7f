"""Device-resident CorrectionEngine: the one FFCz pipeline every workload shares.

The paper's Alg. 1 is a single correction loop, but each integration
(whole-field codec, checkpoint batch codec, KV-cache compression, gradient
compression) needs the same scaffolding around it: bound resolution,
float32/quantization bound discipline, the jitted POCS program, pair-weighted
bit-width selection, and edit-stream serialization.  This module factors that
scaffolding into three explicit stages behind one engine object:

  PLAN     resolve user bounds to absolute dual bounds, apply the shared
           :func:`float32_bound_discipline`, pick whole-field vs pencil
           tiling, and fix quantization widths' base ``m``.  Spectra are
           computed on device and ONLY when a bound actually consumes them
           (``Delta_abs`` needs no forward FFT at all).
  EXECUTE  one jitted device program: FFT + POCS via
           :func:`repro.core.pocs.alternating_projection` (whole field) or
           the packed vmapped program of
           :func:`repro.core.blockwise.correct_batch` (pencils), plus the
           exact float64 polish.  Three pluggable backends:
             ``local``    single-device, one dispatch per tensor;
             ``batched``  donated, vmapped, one program per batch (default);
             ``sharded``  the batched program under ``jax.shard_map`` over a
                          mesh axis — a multi-device batch is corrected where
                          it lives, never gathered to one host.
  ENCODE   pair-weight accounting, :func:`adaptive_quant_bits`, and
           edit-stream serialization through :mod:`repro.core.edits`.

Clients hold no private copies of this math: :class:`repro.core.ffcz.FFCz`
is a thin plan/execute/encode client (plus base-compressor I/O and byte
assembly), and ``checkpoint/codec``, ``serving/kv_compress``,
``optim/grad_compress``, and the temporal stream codec
(:class:`repro.core.temporal.TemporalCodec`, which threads per-frame
``warm_freq`` spectra into EXECUTE) route their corrections through
:meth:`CorrectionEngine.correct` / :meth:`CorrectionEngine.execute_field`.
A new scenario is a new engine client, not a fifth pipeline.

The prose version of this page — stage diagram, backend matrix, parity
tri-state — is docs/architecture.md; keep the two in sync.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from scipy import fft as host_fft

from repro.coding.quantize import DEFAULT_QUANT_BITS
from repro.core import blockwise
from repro.core.bounds import power_spectrum_delta_rfft, resolve_bounds, resolve_roi_bound_grid
from repro.core.errors import FFCzError, InfeasibleBound, classify_exception
from repro.core.cubes import rfft_pair_weights
from repro.core.edits import EncodedEdits, encode_edits
from repro.core.pocs import (
    AlternatingProjectionResult,
    _alternating_projection,
    alternating_projection,
)
from repro.core.spans import span
from repro.kernels.rfft.ops import field_rfftn
from repro.launch.mesh import make_mesh
from repro.sharding import dist_fft
from repro.sharding.dist_fft import ShardedField
from repro.sharding.shardmap import shard_map

_BACKENDS = ("local", "batched", "sharded")


# ---------------------------------------------------------------------------
# shared guarantee math (one home; FFCz re-exports for backward compat)


#: Frequency excess, relative to the largest Delta, below which the float64
#: polish may stop once it no longer shrinks (float64 FFT round-off sits
#: near 1e-15 of Delta on 128^3 Nyx-like fields).
POLISH_FLOOR_REL = 1e-12

#: Elements below which a polish pass runs inline on the calling thread
#: (pencil buckets, checkpoint, KV and gradient batches); at or above it the
#: pass is split into ``_SLABS`` slabs along axis 0 that run on ``_POOL``
#: (whole fields).
_THREADED_MIN = 1 << 20


def _cores() -> int:
    """The cores this process may run on (``os.cpu_count()`` where the
    platform keeps no affinity mask)."""
    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0))
    return os.cpu_count() or 1


# two slabs a core: enough to even out the cores, few enough that a slab's
# numpy calls outweigh its dispatch
_SLABS = 2 * _cores()


def _new_pool():
    global _POOL
    _POOL = ThreadPoolExecutor(max_workers=_cores(), thread_name_prefix="ffcz-polish")


_new_pool()
# a forked child inherits the pool object but not its threads
os.register_at_fork(after_in_child=_new_pool)


def _slab_count(shape) -> int:
    """Slabs a polish pass over an input of ``shape`` is split into: one,
    run inline, below ``_THREADED_MIN`` elements."""
    if math.prod(shape) < _THREADED_MIN:
        return 1
    return min(shape[0], _SLABS)


def _slab_ranges(shape, k: int) -> list:
    """Flat ``[a, b)`` offsets of ``k`` (at most) even slabs along axis 0
    of a C-order array of ``shape``."""
    size = math.prod(shape)
    n0 = shape[0] if shape else 1
    k = max(1, min(k, n0))
    if k == 1:
        return [(0, size)]
    row = size // n0
    edges = [j * n0 // k * row for j in range(k + 1)]
    return list(zip(edges[:-1], edges[1:]))


def _each_slab(fn, ranges) -> list:
    """``fn(a, b)`` over ``ranges``, inline for one slab, else on ``_POOL``.

    numpy releases the GIL inside elementwise loops, so the slabs run on
    every core; each slab sees the same operations whichever thread runs it.
    """
    if len(ranges) == 1:
        return [fn(*ranges[0])]
    return list(_POOL.map(lambda r: fn(*r), ranges))


def _cube(b, shape):
    """``(-b, b)``: floats for a scalar bound, flat C-order float64 arrays
    over ``shape`` for a bound grid."""
    b = np.asarray(b, dtype=np.float64)
    if b.ndim == 0:
        return -float(b), float(b)
    hi = np.ascontiguousarray(np.broadcast_to(b, shape)).reshape(-1)
    return -hi, hi


def _copy_f(x, dtype, k: int):
    """A C-order ``dtype`` copy of ``x``, in ``k`` slabs."""
    out = np.empty(np.shape(x), dtype=dtype)
    src = np.ascontiguousarray(x).reshape(-1)
    dst = out.reshape(-1)

    def one(a, b):
        dst[a:b] = src[a:b]

    _each_slab(one, _slab_ranges(out.shape, k))
    return out


def _clip_spectrum(d, cube, k: int):
    """Clip the C-order spectrum ``d`` in place to the f-cube ``(lo, hi)``,
    in ``k`` slabs.

    Returns ``(hits, excess)``: per slab with a clipped component, the flat
    indices of those components and their displacement ``clip(d) - d``; and
    the largest ``|displacement|`` (0.0 when nothing is clipped).  Only the
    clipped components are written: every other one would move by ``+ 0``.
    """
    lo, hi = cube
    flat = d.reshape(-1)
    grid = not isinstance(hi, float)

    def one(a, b):
        v = flat[a:b]
        w = v.view(np.float64).reshape(-1, 2)  # (re, im) per component
        lo_v, hi_v = (lo[a:b, None], hi[a:b, None]) if grid else (lo, hi)
        out = w > hi_v
        out |= w < lo_v
        i = np.unique(np.flatnonzero(out) >> 1)
        if not i.size:
            return i, None, 0.0
        x = v[i]
        lo_i, hi_i = (lo[a:b][i], hi[a:b][i]) if grid else (lo, hi)
        re = np.clip(x.real, lo_i, hi_i)
        im = np.clip(x.imag, lo_i, hi_i)
        disp = (re - x.real) + 1j * (im - x.imag)
        v[i] = re + 1j * im
        return i + a, disp, float(np.max(np.abs(disp)))

    out = _each_slab(one, _slab_ranges(d.shape, k))
    hits = [(i, disp) for i, disp, _m in out if i.size]
    return hits, max(m for _i, _d, m in out)


def _clip_spatial(eps, spat, cube, k: int) -> int:
    """Clip the C-order ``eps`` in place to the s-cube ``(lo, hi)``, in
    ``k`` slabs, adding each clipped point's displacement to ``spat`` (in
    place); returns the number of points clipped."""
    lo, hi = cube
    ef, sf = eps.reshape(-1), spat.reshape(-1)
    grid = not isinstance(hi, float)

    def one(a, b):
        x = ef[a:b]
        lo_v, hi_v = (lo[a:b], hi[a:b]) if grid else (lo, hi)
        out = x > hi_v
        out |= x < lo_v
        i = np.flatnonzero(out)
        if i.size:
            xi = x[i]
            c = np.clip(xi, lo_v[i], hi_v[i]) if grid else np.clip(xi, lo, hi)
            s = sf[a:b]
            s[i] = s[i] + (c - xi)
            x[i] = c
        return i.size

    return sum(_each_slab(one, _slab_ranges(eps.shape, k)))


def polish_pocs_float64(eps, spat, freq, E, Delta, axes=None, max_iters: int = 30):
    """Exact (float64) POCS iterations to absorb float32 FFT round-off.

    Runs on the rfft half-spectrum over ``axes`` (default: all axes —
    whole-field polish; the pencil path passes the pencil axis), with
    ``freq`` the matching half-spectrum accumulator.  Residual violations
    after the float32 loop are O(eps32 * ||delta||_inf), orders of magnitude
    below the bounds, so this converges in a handful of iterations and
    contributes negligibly to the edit payload.

    It stops at the exact fixed point (no component outside the f-cube), or
    once the largest frequency excess is below ``POLISH_FLOOR_REL * max
    Delta`` and no longer shrinking: each round trip cuts the excess about
    100x until the FFT's own float64 rounding sets a floor, and iterations
    past that point only cycle on it.  The state it returns is the previous
    iteration's, which ends on the s-cube projection, so the spatial bound
    holds exactly either way.

    Returns ``(eps, spat, freq, settled)``; ``settled`` is False when the
    ``max_iters`` cap left a frequency excess above that floor.  The
    arguments are not modified.

    The transforms are scipy's pocketfft on every host core (``workers=-1``):
    each line is transformed alone, so the result does not depend on the
    thread count.  The clips between them write only the components and
    points that lie outside their cube (a few hundred of a field's spectrum,
    about 1% of its points), in place, slab by slab along axis 0: inline
    below ``_THREADED_MIN`` elements, else on every core.  An untouched
    value keeps its bits where a dense pass would add ``0.0`` to it, so the
    result equals the dense clip-and-add loop's bit for bit (up to the sign
    of a zero).  Each round trip past the check (the ``freq`` update, one
    ``irfftn`` and its clips) is one ``ffcz.polish.round`` span carrying
    ``f_clipped`` (components clipped) and ``s_clipped`` (points clipped).
    """
    axes = tuple(range(np.ndim(eps))) if axes is None else tuple(axes)
    eps = np.asarray(eps, dtype=np.float64)
    s = [eps.shape[a] for a in axes]
    half = list(eps.shape)
    half[axes[-1]] = half[axes[-1]] // 2 + 1
    floor = POLISH_FLOOR_REL * float(np.max(Delta)) if np.size(Delta) else 0.0
    k = _slab_count(eps.shape)
    s_cube = _cube(E, eps.shape)
    f_cube = _cube(Delta, tuple(half))
    owned = False
    prev = np.inf
    for it in range(max_iters + 1):
        d = np.ascontiguousarray(host_fft.rfftn(eps, axes=axes, workers=-1))
        hits, excess = _clip_spectrum(d, f_cube, k)
        if excess == 0.0 or prev <= excess <= floor or it == max_iters:
            break
        prev = excess
        with span("ffcz.polish.round", f_clipped=sum(i.size for i, _disp in hits)) as sp:
            if not owned:
                spat, freq = _copy_f(spat, np.float64, k), _copy_f(freq, np.complex128, k)
                owned = True
            ff = freq.reshape(-1)
            for i, disp in hits:
                ff[i] += disp
            eps_f = np.ascontiguousarray(host_fft.irfftn(d, s=s, axes=axes, workers=-1))
            sp.set_metadata(s_clipped=_clip_spatial(eps_f, spat, s_cube, k))
            eps = eps_f
    return eps, spat, freq, excess <= floor


def _rebuild_f64(eps0, eps_freq, spat):
    """``eps0 + (eps_freq + spat)`` in float64, the polish's start state,
    summed slab by slab into ``eps_freq`` (a float64 array it may write)."""
    eps_freq = np.ascontiguousarray(eps_freq)
    out = eps_freq.reshape(-1)
    e0 = np.ascontiguousarray(eps0).reshape(-1)
    sf = np.ascontiguousarray(spat).reshape(-1)

    def one(a, b):
        seg = out[a:b]
        seg += sf[a:b]
        seg += e0[a:b]  # widened exactly; a + b == b + a in IEEE

    _each_slab(one, _slab_ranges(eps_freq.shape, _slab_count(eps_freq.shape)))
    return eps_freq


def _host_l2_norm(x32: np.ndarray) -> float:
    """Sharding-invariant l2 norm feeding the cast-noise slack.

    Computed as a float64 numpy pairwise sum on the host staging copy, so
    the single-device and sharded plans resolve bitwise-identical bounds (an
    on-device XLA reduction would re-order — and so re-round — with the
    sharding; every other plan reduction is a max/min, which is exact in any
    order).
    """
    if not x32.size:
        return 0.0
    x64 = np.asarray(x32, dtype=np.float64)
    return float(np.sqrt(np.sum(x64 * x64)))


def float32_bound_discipline(E, Delta, m: int, l2_norm: float, abs_max: float):
    """Shrink user bounds for quantization + float32-storage round-off.

    Reserves 2x the direct quantization term (one for the stream's own
    noise, one for the other stream's cross-domain leakage — matched by
    :func:`adaptive_quant_bits`), subtracts the absolute float32 slack
    (casting the reconstruction perturbs each frequency component by
    ~u32*l2_norm, 4-sigma statistical budget, and each point by
    u32*abs_max), and clamps Delta at 4x the frequency slack so the bound
    stays representable.  ``Delta`` may be a scalar or a pointwise grid.
    Shared by every engine plan (whole-field and pencil), so the guarantee
    math lives in one place.

    Returns ``(E_proj, Delta_proj, Delta_floored, slack_f)``.
    """
    u32 = float(np.finfo(np.float32).eps)
    shrink = 1.0 - 2.0 ** (-m) - 2.0 ** (-m)
    slack_f = 4.0 * u32 * float(l2_norm)
    slack_s = u32 * float(abs_max)
    Delta = np.maximum(Delta, 4.0 * slack_f)
    return E * shrink - slack_s, Delta * shrink - slack_f, Delta, slack_f


def adaptive_quant_bits(m: int, k_s: int, E: float, min_delta: float, sum_w_delta: float, n: int, cap: int = 48):
    """Closed-form edit-stream bit-widths covering cross-domain quant leakage.

    The base width ``m`` covers each stream's *direct* quantization term;
    the widened widths also fit the cross terms inside the same reserved
    margin: ``k_s`` quantized spatial edits perturb every frequency
    component by up to ``k_s * E * 2^-m_s`` after the FFT (kept under
    ``min_delta * 2^-m``), and the active frequency edits — ``sum_w_delta``
    being their conjugate-pair-weighted Delta sum — perturb every spatial
    point by up to ``(sqrt2/n) * sum_w_delta * 2^-m_f`` after the IFFT
    (kept under ``E * 2^-m``).  Shared by the engine's whole-field and
    pencil encode stages, so the guarantee math lives in one place.
    """
    m_s = m
    if k_s > 0 and min_delta > 0 and E > 0:
        m_s = m + max(0, int(np.ceil(np.log2(max(k_s * E / min_delta, 1.0)))))
    m_f = m
    if sum_w_delta > 0 and E > 0 and n > 0:
        ratio = np.sqrt(2.0) * sum_w_delta / (n * E)
        m_f = m + max(0, int(np.ceil(np.log2(max(ratio, 1.0)))))
    return min(m_s, cap), min(m_f, cap)


# ---------------------------------------------------------------------------
# plan objects


@dataclasses.dataclass(frozen=True)
class FieldPlan:
    """PLAN-stage output for one whole-field correction.

    ``Delta`` is the representability-floored bound the edits are encoded
    against (scalar, or a float32 half-spectrum ``Delta_k`` grid in
    ``pspec`` mode); ``E_proj``/``Delta_proj`` are the shrunk bounds the
    projection actually runs with (see :func:`float32_bound_discipline`).
    """

    shape: Tuple[int, ...]
    E: float
    Delta: Union[float, np.ndarray]
    E_proj: float
    Delta_proj: Union[float, np.ndarray]
    slack_f: float
    pointwise: bool
    quant_bits: int
    max_iters: int
    relax: float
    use_kernels: bool
    codec: str
    # POCS loop transform selector ("xla" | "packed" | "pallas") and
    # convergence-check cadence — see repro.core.pocs.  Defaults preserve the
    # legacy trajectory (and blob bytes) exactly.
    fft_impl: str = "xla"
    check_every: int = 1
    # Temporal warm start (ISSUE 8): when True, execute_field applies a
    # caller-supplied warm_freq spectrum as the loop's initial freq_edits
    # state (see repro.core.pocs).  False ignores any warm_freq — the
    # bitwise-identical cold start.
    warm_start: bool = False
    # ROI bounds (ISSUE 9): per-point spatial bound grid resolved from
    # FFCzConfig.E_roi (float32, field-shaped, every entry <= E) and its
    # disciplined projection twin.  None keeps the uniform-E paths (and
    # blob bytes) exactly as before.
    E_grid: Optional[np.ndarray] = None
    E_grid_proj: Optional[np.ndarray] = None

    @property
    def roi(self) -> bool:
        """True when the plan carries a per-point spatial bound grid."""
        return self.E_grid is not None

    @property
    def delta_scalar(self) -> float:
        """Scalar Delta for the blob header (nan when pointwise)."""
        return float("nan") if self.pointwise else float(self.Delta)

    def pointwise_bytes(self) -> Optional[bytes]:
        """float32 half-spectrum Delta_k grid for the blob, or None."""
        if not self.pointwise:
            return None
        return np.asarray(self.Delta, dtype=np.float32).tobytes()

    def roi_bytes(self) -> Optional[bytes]:
        """float32 spatial E_n grid for the blob's FFCR section, or None."""
        if self.E_grid is None:
            return None
        return np.asarray(self.E_grid, dtype=np.float32).tobytes()


@dataclasses.dataclass(frozen=True)
class PencilPlan:
    """PLAN-stage output for one tensor's pencil-tiled correction.

    The frequency bound applies to each ``block``-length pencil's local
    rfft spectrum: ``Delta = Delta_rel * max_k |RFFT(pencil of x)_k|``.
    """

    block: int
    quant_bits: int
    E: float
    Delta: float
    E_proj: float
    Delta_proj: float


@dataclasses.dataclass
class FieldResult:
    """EXECUTE-stage output: float64-exact loop state ready to encode.

    ``converged`` is the device loop's flag; when it is False,
    ``final_violations`` is the pair-weighted full-spectrum count of
    frequency components still outside the (shrunk) f-cube *after* the
    float64 polish — the number a caller needs to decide whether to retry
    with relaxed knobs, reject, or encode-with-warning.  Encoding a
    non-converged result is safe for the spatial bound (the final state is
    inside the s-cube by construction) but the frequency bound may be
    violated at exactly these components.
    """

    eps: np.ndarray  # final error vector (float64, inside the s-cube)
    spat: np.ndarray  # spatial edit accumulator (float64)
    freq: np.ndarray  # frequency edit accumulator (complex128, rfft layout)
    iterations: int
    converged: bool
    final_violations: int = 0


# ---------------------------------------------------------------------------
# async EXECUTE handles (pipelined serving, ISSUE 7)
#
# JAX dispatch is asynchronous: a jitted call returns in-flight device arrays
# before the program finishes.  The engine exposes that seam explicitly so a
# serving loop can overlap batch i's host ENCODE with batch i+1's device
# EXECUTE: the *_async entry points dispatch and return a handle immediately
# (classifying dispatch-time failures), and ``handle.result()`` is the
# ``jax.block_until_ready`` fence plus every host-side completion step (state
# staging, float64 polish, violation recount) — classified again, because an
# async device failure surfaces at the fence, possibly on another thread.


class FieldExecuteHandle:
    """One in-flight whole-field EXECUTE; ``result()`` fences and polishes.

    ``result()`` is idempotent (the finalized :class:`FieldResult` — or the
    classified error — is cached) and may be called from a different thread
    than the dispatching one: every failure re-raises as the same classified
    :class:`~repro.core.errors.FFCzError` on every caller.
    """

    def __init__(self, engine: "CorrectionEngine", raw, eps0, plan: FieldPlan):
        self._engine = engine
        self._raw = raw  # AlternatingProjectionResult of in-flight device arrays
        self._eps0 = eps0  # the initial error (host array or ShardedField)
        self._plan = plan
        self._value: Optional[FieldResult] = None
        self._exc: Optional[FFCzError] = None

    def result(self) -> FieldResult:
        if self._exc is not None:
            raise self._exc
        if self._value is None:
            try:
                self._value = self._engine._finalize_field(self._raw, self._eps0, self._plan)
            except FFCzError as err:
                self._exc = err
                raise
            finally:
                self._raw = None  # drop the device references either way
        return self._value


class PencilBatchHandle:
    """One in-flight fused pencil EXECUTE over a packed ``(B, block)`` buffer.

    ``result()`` fences the device program and returns the same
    ``(corrected, edits, stats)`` tuple :meth:`CorrectionEngine.correct`
    produces, with per-tensor slices of the packed outputs.  Idempotent and
    thread-agnostic, like :class:`FieldExecuteHandle`.
    """

    def __init__(self, raw, stats, specs, counts, pads, block, return_edits, return_corrected):
        self._raw = raw
        self._stats = stats
        self._specs = specs  # [(shape, dtype)] per tensor
        self._counts = counts
        self._pads = pads
        self._block = block
        self._return_edits = return_edits
        self._return_corrected = return_corrected
        self._value = None
        self._exc: Optional[FFCzError] = None

    def result(self):
        if self._exc is not None:
            raise self._exc
        if self._value is None:
            try:
                with span("ffcz.fence"):
                    res, stats = jax.block_until_ready((self._raw, self._stats))
                corrected, edits = [], []
                offset = 0
                for (shape, dtype), nb, pad in zip(self._specs, self._counts, self._pads):
                    sl = slice(offset, offset + nb)
                    if self._return_corrected:
                        corrected.append(
                            blockwise.untile_1d(res.eps[sl], shape, pad).astype(dtype)
                        )
                    if self._return_edits:
                        edits.append((res.spat_edits[sl], res.freq_edits[sl]))
                    offset += nb
                if self._return_edits:
                    self._value = (corrected, edits, stats)
                else:
                    self._value = (corrected, stats)
            except FFCzError as err:
                self._exc = err
                raise
            except (RuntimeError, MemoryError) as e:
                self._exc = classify_exception(e, "execute")
                raise self._exc from e
            finally:
                self._raw = self._stats = None
        return self._value


class _FenceHandle:
    """Generic handle over already-structured (but still in-flight) outputs:
    ``result()`` is just the classified ``block_until_ready`` fence.  Used by
    the ``local`` backend, whose per-tensor dispatches happen eagerly."""

    def __init__(self, value):
        self._value = value
        self._fenced = False
        self._exc: Optional[FFCzError] = None

    def result(self):
        if self._exc is not None:
            raise self._exc
        if not self._fenced:
            try:
                with span("ffcz.fence"):
                    jax.block_until_ready(self._value)
                self._fenced = True
            except (RuntimeError, MemoryError) as e:
                self._exc = classify_exception(e, "execute")
                self._value = None
                raise self._exc from e
        return self._value


# ---------------------------------------------------------------------------
# the engine


@functools.lru_cache(maxsize=None)
def _sharded_field_pocs_fn(
    mesh,
    spec,
    pointwise: bool,
    max_iters: int,
    relax: float,
    fft_impl: str = "xla",
    check_every: int = 1,
    warm: bool = False,
    roi: bool = False,
):
    """Compiled sharded whole-field POCS program, cached per (mesh, DistSpec).

    Scalar bounds enter as replicated operands so re-planning the same field
    shape (or a new field of the same shape) reuses the compiled while_loop
    instead of retracing — the whole-field analogue of ``_pencil_fft_fn``.
    Arrays cross the boundary in the PADDED device layout; slab-pad rows are
    exactly zero and stay zero through the loop (see
    :mod:`repro.sharding.dist_fft`).  ``roi`` switches the spatial bound
    operand from a replicated scalar to a slab-sharded per-point grid (padded
    with the background bound so pad rows stay at zero through the clip).
    """
    ax = spec.axis_name
    fspec = dist_fft.freq_partition_spec(len(spec.gshape), ax)
    d_spec = fspec if pointwise else P()
    e_spec = P(ax) if roi else P()

    if warm:
        # the warm spectrum enters as a local half-spectrum block in the
        # padded device layout (pad rows zero), like a pointwise Delta grid
        def run(e_loc, d_loc, E, slack, w_loc):
            return _alternating_projection(
                e_loc,
                E,
                d_loc,
                max_iters=max_iters,
                relax=relax,
                check_slack=slack,
                dist=spec,
                fft_impl=fft_impl,
                check_every=check_every,
                warm_freq=w_loc,
            )

        in_specs = (P(ax), d_spec, e_spec, P(), fspec)
    else:

        def run(e_loc, d_loc, E, slack):
            return _alternating_projection(
                e_loc,
                E,
                d_loc,
                max_iters=max_iters,
                relax=relax,
                check_slack=slack,
                dist=spec,
                fft_impl=fft_impl,
                check_every=check_every,
            )

        in_specs = (P(ax), d_spec, e_spec, P())

    out_specs = AlternatingProjectionResult(
        eps=P(ax),
        spat_edits=P(ax),
        freq_edits=fspec,
        iterations=P(),
        converged=P(),
        final_violations=P(),
    )
    return jax.jit(
        shard_map(run, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


class CorrectionEngine:
    """Plan / execute / encode FFCz corrections on a pluggable backend.

    Args:
      backend: ``"local"`` (one dispatch per tensor), ``"batched"`` (one
        donated vmapped program per batch; the default), or ``"sharded"``
        (the batched program under ``shard_map`` over ``mesh[axis]``).
      mesh: device mesh for the sharded backend.  Defaults to a 1-D mesh
        over all local devices, built lazily on first use so engine
        construction never touches jax device state.
      axis: mesh axis name the packed block buffer is sharded over.
      fft_impl: default POCS transform selector for the *pencil* paths
        (``"xla"`` | ``"packed"`` | ``"pallas"``, see
        :mod:`repro.core.pocs`); whole-field corrections take theirs from
        ``FFCzConfig.fft_impl`` via the plan.  All three backends thread it
        into the loop — the packed/pallas transforms are vmap-safe, so the
        batched and sharded programs lift them unchanged.
    """

    def __init__(
        self,
        backend: str = "batched",
        mesh: Optional[Any] = None,
        axis: str = "data",
        fft_impl: str = "xla",
    ):
        if backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
        if fft_impl not in ("xla", "packed", "pallas"):
            raise ValueError(f"fft_impl must be 'xla', 'packed' or 'pallas', got {fft_impl!r}")
        self.backend = backend
        self.axis = axis
        self.fft_impl = fft_impl
        self._mesh = mesh

    # Engines compare by configuration, not identity, so jitted functions
    # taking an engine as a static argument (e.g. compress_kv_tensor) hit
    # one cache entry for equivalent engines instead of retracing per
    # instance.  A lazily-built default mesh changes the key once on first
    # sharded use (one extra retrace), never corrupts a cache.
    def _key(self):
        return (self.backend, self.axis, self.fft_impl, self._mesh)

    def __eq__(self, other):
        return isinstance(other, CorrectionEngine) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_mesh((len(jax.devices()),), (self.axis,))
        return self._mesh

    # -- PLAN --------------------------------------------------------------

    def plan_field(self, x: Union[np.ndarray, ShardedField], cfg) -> FieldPlan:
        """Resolve one whole field's bounds on device (cfg: FFCzConfig).

        The forward spectrum is computed (as a device rfft) only when a
        bound consumes it: ``pspec_rel`` needs the pointwise grid,
        ``Delta_rel`` needs ``max_k |X_k|``, and ``Delta_abs`` needs no
        forward FFT at all.

        A :class:`repro.sharding.dist_fft.ShardedField` keeps the spectrum
        sharded: the forward transform is the pencil-decomposed distributed
        rfftn and the bound grid is built on the sharded half-spectrum.  All
        plan reductions are sharding-invariant (max/min, or the host-staged
        :func:`_host_l2_norm`), so the resulting :class:`FieldPlan` is
        bitwise identical to planning the gathered field on one device.

        Precision note: the device rfft runs in float32, so relative bounds
        resolved from it (``Delta_rel`` / ``pspec_rel``) can differ from a
        host-float64 resolution — and across device backends — at float32
        rounding level (~1e-7 relative).  The blob stores the resolved
        values it was built with and all guarantees are verified against
        those stored values, so the bound contract is unaffected; byte
        identity of blobs only holds within one backend.  (The pencil path
        keeps host-float64 resolution — see :meth:`plan_pencils` — because
        its per-pencil Delta is a convention external tools recompute.)
        """
        sharded = isinstance(x, ShardedField)
        E_abs_eff, E_rel_eff = cfg.E_abs, cfg.E_rel
        if sharded:
            x32, x_dev = x.to_host(), x.array
            rfftn = lambda _dev: dist_fft.pencil_rfftn(x)  # noqa: E731
            if E_abs_eff is None and E_rel_eff is not None:
                # The device array carries zero slab-pad rows, which would
                # corrupt the E_rel range reduction (min picks up the pad).
                # max/min/subtract/multiply are all single correctly-rounded
                # float32 ops, so the host staging copy reproduces the
                # on-device reduction of the unpadded field bitwise.
                rng32 = np.max(x32) - np.min(x32)
                if float(rng32) == 0.0:
                    # mirror resolve_bounds' constant-field diagnosis — the
                    # sharded branch resolves E before ever reaching it
                    raise InfeasibleBound(
                        f"E_rel={float(cfg.E_rel):g} on a constant field: range(x) == 0 "
                        "resolves the spatial bound to E = 0 (an empty s-cube); pass "
                        "E_abs for constant fields",
                        stage="plan",
                    )
                E_abs_eff, E_rel_eff = np.float32(cfg.E_rel) * np.float32(rng32), None
        else:
            x32 = np.asarray(x, dtype=np.float32)
            x_dev = jnp.asarray(x32)
            rfftn = field_rfftn
        if cfg.pspec_rel is not None:
            # the padded sharded spectrum's pad rows are exactly zero, so the
            # grid max / floor / DC reductions below see the same values as
            # the single-device path; the stored grid is sliced to the true
            # half-spectrum extents
            X = rfftn(x_dev)
            grid = power_spectrum_delta_rfft(X, cfg.pspec_rel)
            gmax = float(jnp.max(grid))
            if gmax <= 0:
                # grid = t*|X|/sqrt(2) with floor 0, so gmax == 0 iff the
                # field is all-zero: every Delta_k resolves to 0 and any
                # published "pspec_rel" guarantee would be meaningless
                raise InfeasibleBound(
                    f"pspec_rel={float(cfg.pspec_rel):g} on an all-zero field: every "
                    "Delta_k resolves to 0 (no spectrum to preserve); use Delta_abs "
                    "for zero fields",
                    stage="plan",
                )
            floor = gmax * cfg.pspec_floor_rel
            Delta_user = np.asarray(jnp.maximum(grid, floor), dtype=np.float32)
            if sharded:
                Delta_user = x.unpad_freq(Delta_user)
            bounds = resolve_bounds(x_dev, E_abs=E_abs_eff, E_rel=E_rel_eff, Delta_abs=1.0)
            pointwise = True
        elif cfg.Delta_abs is not None:
            bounds = resolve_bounds(x_dev, E_abs=E_abs_eff, E_rel=E_rel_eff, Delta_abs=cfg.Delta_abs)
            Delta_user = float(bounds.Delta)
            pointwise = False
        else:
            # Delta_rel needs max_k |X_k|: zero pad rows never raise a max
            X = rfftn(x_dev)
            bounds = resolve_bounds(x_dev, E_abs=E_abs_eff, E_rel=E_rel_eff, Delta_rel=cfg.Delta_rel, X=X)
            Delta_user = float(bounds.Delta)
            pointwise = False
        E = float(bounds.E)
        l2_norm = _host_l2_norm(x32)
        abs_max = float(jnp.max(jnp.abs(x_dev))) if x32.size else 0.0
        E_proj, Delta_proj, Delta, slack_f = float32_bound_discipline(
            E, Delta_user, cfg.quant_bits, l2_norm, abs_max
        )
        # ROI bounds (ISSUE 9): resolve the user's mask / per-point grid into
        # the float32 E_n grid the blob stores, then re-run the (elementwise)
        # discipline on it so every point gets its own shrunk projection
        # bound — exactly how the pointwise Delta_k grid is treated.
        E_grid = E_grid_proj = None
        E_roi = getattr(cfg, "E_roi", None)
        if E_roi is not None:
            E_grid = resolve_roi_bound_grid(
                E_roi, E, tuple(x32.shape), scale=getattr(cfg, "E_roi_scale", 0.1)
            )
            E_grid_proj, _, _, _ = float32_bound_discipline(
                E_grid, Delta_user, cfg.quant_bits, l2_norm, abs_max
            )
            E_grid_proj = np.asarray(E_grid_proj, dtype=np.float32)
            if float(np.min(E_grid_proj)) <= 0:
                raise InfeasibleBound(
                    f"tightest ROI bound E_n={float(np.min(E_grid)):g} below float32 "
                    "representability for this data",
                    stage="plan",
                )
        if not pointwise:
            Delta_proj = float(Delta_proj)
            Delta = float(Delta)
        # Infeasible spatial∩frequency intersection is a *request* property:
        # reject structurally (stage + disposition) instead of letting a bare
        # exception escape the engine into a serving loop.
        if E_proj <= 0:
            raise InfeasibleBound(
                f"spatial bound E={E:g} below float32 representability for this data",
                stage="plan",
            )
        if float(np.min(Delta_proj)) <= 0:
            raise InfeasibleBound(
                f"frequency bound Delta={float(np.min(np.asarray(Delta_user))):g} below float32 "
                f"representability after the quantization shrink (quant_bits={cfg.quant_bits})",
                stage="plan",
            )
        return FieldPlan(
            shape=tuple(x32.shape),
            E=E,
            Delta=Delta,
            E_proj=float(E_proj),
            Delta_proj=Delta_proj,
            slack_f=float(slack_f),
            pointwise=pointwise,
            quant_bits=cfg.quant_bits,
            max_iters=cfg.max_iters,
            relax=cfg.relax,
            use_kernels=cfg.use_kernels,
            codec=cfg.codec,
            fft_impl=getattr(cfg, "fft_impl", "xla"),
            check_every=getattr(cfg, "check_every", 1),
            warm_start=getattr(cfg, "warm_start", False),
            E_grid=E_grid,
            E_grid_proj=E_grid_proj,
        )

    def plan_pencils(
        self,
        x32: np.ndarray,
        *,
        E_rel: Optional[float] = None,
        Delta_rel: Optional[float] = None,
        block: int,
        quant_bits: int = DEFAULT_QUANT_BITS,
        E_abs: Optional[float] = None,
        Delta_abs: Optional[float] = None,
        E_roi=None,
        E_roi_scale: float = 0.1,
    ) -> Optional[PencilPlan]:
        """Resolve one tensor's pencil-tiled bounds; None if E underflows.

        Bound resolution here stays in host float64 (``np.fft.rfft``): the
        per-pencil ``Delta`` is the published guarantee other tools
        recompute exactly, so it must not pick up float32-FFT jitter.  The
        cast-noise slack uses per-pencil norms (the noise lands on each
        pencil's local spectrum).

        ``E_abs``/``Delta_abs`` override the relative resolution with
        already-absolute bounds (each independently): temporal residual
        frames carry bounds resolved once on the stream's first frame, so
        re-deriving them from each residual's own range would drift.  An
        absolute Delta needs no forward FFT at all.

        ``E_roi`` (mask or per-point grid, see
        :func:`repro.core.bounds.resolve_roi_bound_grid`) collapses to the
        *tightest* resolved bound as the effective uniform ``E``: pencil
        tiling scrambles spatial adjacency across blocks, so a per-point
        grid cannot ride the tiled streams — the whole-field path
        (:meth:`plan_field`) keeps the full grid.
        """
        flat = x32.reshape(-1)
        tiles = np.pad(flat, (0, (-flat.size) % block)).reshape(-1, block)
        if E_abs is not None:
            E = float(E_abs)
        else:
            if E_rel is None:
                raise ValueError("plan_pencils needs E_rel or E_abs")
            E = E_rel * float(np.ptp(x32))
        if E_roi is not None:
            grid = resolve_roi_bound_grid(E_roi, E, tuple(x32.shape), scale=E_roi_scale)
            E = float(np.min(grid))
        if Delta_abs is not None:
            Delta = float(Delta_abs)
        else:
            if Delta_rel is None:
                raise ValueError("plan_pencils needs Delta_rel or Delta_abs")
            Delta = Delta_rel * float(np.abs(np.fft.rfft(tiles, axis=-1)).max())
        E_proj, Delta_proj, Delta, _slack_f = float32_bound_discipline(
            E,
            Delta,
            quant_bits,
            np.sqrt((tiles.astype(np.float64) ** 2).sum(axis=-1).max()),
            np.max(np.abs(x32)) if x32.size else 0.0,
        )
        if E_proj <= 0:
            return None
        return PencilPlan(
            block=block,
            quant_bits=quant_bits,
            E=E,
            Delta=float(Delta),
            E_proj=float(E_proj),
            Delta_proj=float(Delta_proj),
        )

    @staticmethod
    def tile_f64(eps0: np.ndarray, block: int) -> np.ndarray:
        """Float64 (n_blocks, block) tiling of an error tensor — the exact
        loop state the pencil polish rebuilds from, captured up front so the
        float32 original need not outlive the batched device call."""
        flat = np.asarray(eps0, dtype=np.float64).reshape(-1)
        return np.pad(flat, (0, (-flat.size) % block)).reshape(-1, block)

    # -- EXECUTE -----------------------------------------------------------

    def execute_field(
        self,
        eps0: Union[np.ndarray, ShardedField],
        plan: FieldPlan,
        warm_freq: Optional[np.ndarray] = None,
    ) -> FieldResult:
        """One jitted device POCS program + the exact float64 polish.

        The jitted loop runs in float32 (the TPU perf path, as the paper
        runs FP32 on A100); its convergence check is therefore only
        float32-exact.  A few exact host-side POCS iterations absorb the
        FFT round-off so the *shrunk* bounds hold in float64, leaving the
        full quantization margin intact.

        A :class:`ShardedField` ``eps0`` runs the same while_loop on local
        slabs inside ``shard_map``, with the pencil-decomposed distributed
        transforms in the loop body — the field-sized float32 state never
        gathers to one device.  The loop trajectory is bitwise identical to
        the single-device program (see :mod:`repro.sharding.dist_fft`), so
        the edit streams — and the blobs built from them — match exactly.

        ``warm_freq`` (complex half-spectrum, the previous stream frame's
        converged ``FieldResult.freq``) seeds the loop's ``freq_edits``
        accumulator — consumed only when ``plan.warm_start`` is True, so a
        cold-configured plan stays bitwise identical whatever the caller
        passes (the temporal neutrality switch).
        """
        return self.execute_field_async(eps0, plan, warm_freq=warm_freq).result()

    def execute_field_async(
        self,
        eps0: Union[np.ndarray, ShardedField],
        plan: FieldPlan,
        warm_freq: Optional[np.ndarray] = None,
    ) -> FieldExecuteHandle:
        """Dispatch the whole-field POCS program; return before the fence.

        The returned :class:`FieldExecuteHandle` owns the in-flight device
        arrays; ``handle.result()`` runs ``jax.block_until_ready`` plus the
        host half of :meth:`execute_field` (state staging, float64 polish,
        violation recount) and may run on a different thread — the pipelined
        service fences batch *i* on its encode worker while this thread
        dispatches batch *i+1*.  Dispatch-time device failures classify and
        raise here; fence-time failures classify inside ``result()``.
        """
        sharded = isinstance(eps0, ShardedField)
        if not plan.warm_start:
            warm_freq = None  # neutrality: cold plans never see a warm state
        try:
            if sharded:
                res = self._pocs_field_sharded(eps0, plan, warm_freq)
            else:
                E_op = (
                    plan.E_proj
                    if plan.E_grid_proj is None
                    else jnp.asarray(plan.E_grid_proj)
                )
                res = alternating_projection(
                    jnp.asarray(eps0, dtype=jnp.float32),
                    E_op,
                    jnp.asarray(plan.Delta_proj),
                    max_iters=plan.max_iters,
                    use_kernels=plan.use_kernels,
                    relax=plan.relax,
                    check_slack=0.5 * plan.slack_f,
                    fft_impl=plan.fft_impl,
                    check_every=plan.check_every,
                    warm_freq=None if warm_freq is None
                    else jnp.asarray(warm_freq, dtype=jnp.complex64),
                )
        except (RuntimeError, MemoryError) as e:
            # device dispatch / allocation failures carry stage + disposition
            # (OOM -> "bisect") so serving loops can act without string-matching
            raise classify_exception(e, "execute") from e
        return FieldExecuteHandle(self, res, eps0, plan)

    def _finalize_field(self, res, eps0, plan: FieldPlan) -> FieldResult:
        """The fence + host half of EXECUTE (see :meth:`execute_field_async`):
        spans ``ffcz.fence``, ``ffcz.fetch`` and ``ffcz.polish``."""
        # edit state -> host: this is the encode/serialization staging (the
        # single-device path stages identically); the float64 polish is a
        # handful of host FFT round trips on the O(residual) edit state.
        # Sharded state arrives in the padded device layout — slab-pad
        # rows/columns are exactly zero; slicing them away here restores the
        # single-device shapes (and values, bitwise on "bitwise"-parity
        # shapes) before the polish and encode stages.
        try:
            with span("ffcz.fence"):
                jax.block_until_ready(res)
        except (RuntimeError, MemoryError) as e:
            # an async device failure surfaces at the fence, not at dispatch
            raise classify_exception(e, "execute") from e
        with span("ffcz.fetch"):
            try:
                spat = np.asarray(res.spat_edits, dtype=np.float64)
                freq = np.asarray(res.freq_edits, dtype=np.complex128)
            except (RuntimeError, MemoryError) as e:
                raise classify_exception(e, "execute") from e
            if isinstance(eps0, ShardedField):
                spat = eps0.unpad_spatial(spat)
                freq = eps0.unpad_freq(freq)
                eps0 = eps0.to_host()
        with span("ffcz.polish", slabs=_slab_count(plan.shape)):
            # The polish starts from the state the edit streams encode,
            # rebuilt in float64: the device loop keeps eps == eps0 +
            # IFFT(freq) + spat only as far as its own float32 transforms are
            # accurate, and the decoder reconstructs from the edits, not from
            # the device's eps.
            eps_f = _rebuild_f64(
                eps0,
                host_fft.irfftn(freq, s=plan.shape, axes=tuple(range(len(plan.shape))), workers=-1),
                spat,
            )
            E_pol = (
                plan.E_proj
                if plan.E_grid_proj is None
                else np.asarray(plan.E_grid_proj, dtype=np.float64)
            )
            eps_f, spat, freq, settled = polish_pocs_float64(
                eps_f, spat, freq, E_pol, np.asarray(plan.Delta_proj, dtype=np.float64)
            )
            converged = bool(res.converged) and settled
            final_violations = 0
            if not converged:
                # Surface non-convergence with an exact post-polish count: the
                # float32 loop's exit count may overstate what the float64
                # polish could not absorb.  Pair weights keep full-spectrum
                # semantics, matching the loop's own violation accounting.
                # (Converged runs skip the extra host rfftn — the default path
                # pays nothing.)
                d = np.fft.rfftn(eps_f)
                tol = np.asarray(plan.Delta_proj, dtype=np.float64)
                bad = (np.abs(d.real) > tol) | (np.abs(d.imag) > tol)
                w = np.broadcast_to(np.asarray(rfft_pair_weights(plan.shape)), bad.shape)
                final_violations = int(np.sum(w * bad))
        return FieldResult(
            eps=eps_f,
            spat=spat,
            freq=freq,
            iterations=int(res.iterations),
            converged=converged,
            final_violations=final_violations,
        )

    def _pocs_field_sharded(self, eps0: ShardedField, plan: FieldPlan, warm_freq=None):
        """The whole-field POCS while_loop under ``shard_map`` (dist mode)."""
        if plan.use_kernels:
            raise ValueError("use_kernels is not supported for sharded whole fields")
        if plan.fft_impl == "pallas":
            raise ValueError(
                "fft_impl='pallas' is not supported for sharded whole fields "
                "(the fused epilogues assume the whole spectrum; use 'packed')"
            )
        if plan.fft_impl != "xla" and eps0.parity_requested == "bitwise":
            # honest tri-state: the packed inverse places its roundings
            # differently from the fused single-device irfftn, so blobs can
            # only be bound-parity whatever the shape class
            raise ValueError(
                "parity='bitwise' requires fft_impl='xla': packed transforms "
                "diverge from the single-device path at float32-rounding "
                "level (bounds still hold; request parity='auto')"
            )
        mesh = eps0.mesh
        if plan.pointwise:
            # pre-round the float64 plan grid to float32 on host (the same
            # IEEE rounding jnp.asarray applies on the single-device path),
            # zero-pad it to the device layout (pad components are exactly
            # zero in the loop, so their bound value is inert), then scatter
            # straight into the frequency layout
            delta_op = jax.device_put(
                eps0.pad_freq_np(np.asarray(plan.Delta_proj, dtype=np.float32)),
                NamedSharding(mesh, eps0.freq_spec),
            )
        else:
            delta_op = jnp.float32(plan.Delta_proj)
        if plan.E_grid_proj is not None:
            # ROI grid enters as a slab-sharded spatial operand; pad rows
            # carry the (positive) background projection bound so the zero
            # pad rows of the field stay exactly zero through the clip
            e_op = jax.device_put(
                eps0.pad_spatial_np(
                    np.asarray(plan.E_grid_proj, dtype=np.float32),
                    fill=np.float32(plan.E_proj),
                ),
                NamedSharding(mesh, eps0.spec),
            )
        else:
            e_op = np.float32(plan.E_proj)
        warm_op = None
        if warm_freq is not None:
            # same device layout as a pointwise Delta grid: zero-padded to
            # the local half-spectrum blocks (pad rows stay zero in the loop)
            warm_op = jax.device_put(
                eps0.pad_freq_np(np.asarray(warm_freq, dtype=np.complex64)),
                NamedSharding(mesh, eps0.freq_spec),
            )
        fn = _sharded_field_pocs_fn(
            mesh,
            eps0.dist_spec,
            plan.pointwise,
            plan.max_iters,
            plan.relax,
            plan.fft_impl,
            plan.check_every,
            warm_op is not None,
            plan.E_grid_proj is not None,
        )
        # scalar bounds ride as replicated operands (pre-rounded to the f32
        # values the single-device trace uses), so same-shape fields with
        # different bounds share one compiled program
        args = (eps0.array, delta_op, e_op, np.float32(0.5 * plan.slack_f))
        if warm_op is not None:
            args = args + (warm_op,)
        return fn(*args)

    def correct(
        self,
        tensors: Sequence[Any],
        E,
        Delta,
        block: int = 4096,
        max_iters: int = 50,
        return_edits: bool = False,
        return_corrected: bool = True,
        fft_impl: Optional[str] = None,
        warm_freq: Optional[Sequence[Any]] = None,
    ):
        """Pencil-tiled correction of a heterogeneous batch on this backend.

        Same contract as :func:`repro.core.blockwise.correct_batch` (which
        implements the ``batched`` and ``sharded`` backends); the ``local``
        backend dispatches one program per tensor.  Jit-safe on the batched
        backend, so jitted integrations can call through unchanged.
        ``fft_impl`` overrides the engine default for this call;
        ``warm_freq`` optionally seeds each tensor's blocks with prior edit
        spectra (``(n_blocks_i, block//2+1)`` per tensor — the temporal
        stream path).
        """
        fft_impl = self.fft_impl if fft_impl is None else fft_impl
        try:
            if self.backend == "local":
                return self._correct_local(
                    tensors, E, Delta, block, max_iters, return_edits, return_corrected,
                    fft_impl, warm_freq,
                )
            return blockwise.correct_batch(
                tensors,
                E,
                Delta,
                block=block,
                max_iters=max_iters,
                return_edits=return_edits,
                return_corrected=return_corrected,
                backend=self.backend,
                mesh=self.mesh if self.backend == "sharded" else None,
                axis=self.axis,
                fft_impl=fft_impl,
                warm_freq=warm_freq,
            )
        except (RuntimeError, MemoryError) as e:
            raise classify_exception(e, "execute") from e

    def correct_async(
        self,
        tensors: Sequence[Any],
        E,
        Delta,
        block: int = 4096,
        max_iters: int = 50,
        return_edits: bool = False,
        return_corrected: bool = True,
        fft_impl: Optional[str] = None,
        staging: Optional[np.ndarray] = None,
        warm_freq: Optional[Sequence[Any]] = None,
    ):
        """Dispatch a pencil-batch correction; return a handle before the fence.

        The async twin of :meth:`correct`: packing happens on host
        (:func:`repro.core.blockwise.pack_batch` — ``staging`` optionally
        reuses a caller-cached ``(B, block)`` buffer so steady-state serving
        buckets stop reallocating it), the packed POCS program is dispatched
        with the device buffer DONATED, and the returned
        :class:`PencilBatchHandle`'s ``result()`` fences + slices per tensor,
        yielding exactly :meth:`correct`'s return structure.  The packed
        values, the vmapped while_loop and the stat reductions are the same
        program as :meth:`correct`'s, so results are interchangeable.

        Dispatch-time failures (including allocation failure on the packed
        buffer) classify and raise here; async failures classify inside
        ``result()``, which may run on another thread.
        """
        fft_impl = self.fft_impl if fft_impl is None else fft_impl
        if len(tensors) == 0:
            empty = blockwise.BatchCorrectionStats(
                iterations=jnp.zeros((0,), jnp.int32),
                converged=jnp.zeros((0,), bool),
                block_iterations=jnp.zeros((0,), jnp.int32),
                block_converged=jnp.zeros((0,), bool),
            )
            return _FenceHandle(([], [], empty) if return_edits else ([], empty))
        if self.backend == "local":
            # per-tensor dispatches happen eagerly; the handle is just the fence
            try:
                return _FenceHandle(
                    self._correct_local(
                        tensors, E, Delta, block, max_iters, return_edits,
                        return_corrected, fft_impl, warm_freq,
                    )
                )
            except (RuntimeError, MemoryError) as e:
                raise classify_exception(e, "execute") from e
        specs = [(np.asarray(t).shape, np.asarray(t).dtype) for t in tensors]
        try:
            packed, counts, pads = blockwise.pack_batch(tensors, block, out=staging)
            warm = None
            if warm_freq is not None:
                warm = np.concatenate(
                    [np.asarray(w, dtype=np.complex64) for w in warm_freq], axis=0
                )
            res, stats = blockwise.correct_packed(
                packed,
                counts,
                E,
                Delta,
                max_iters=max_iters,
                backend=self.backend,
                mesh=self.mesh if self.backend == "sharded" else None,
                axis=self.axis,
                fft_impl=fft_impl,
                warm=warm,
            )
        except (RuntimeError, MemoryError) as e:
            raise classify_exception(e, "execute") from e
        return PencilBatchHandle(
            res, stats, specs, counts, pads, block, return_edits, return_corrected
        )

    def _correct_local(
        self, tensors, E, Delta, block, max_iters, return_edits, return_corrected,
        fft_impl="xla", warm_freq=None,
    ):
        """Per-tensor dispatch (the pre-batching behaviour, kept for
        comparison benches and single-tensor calls).  Bounds go through the
        same resolver as the batched/sharded backends so the scalar-vs-
        per-tensor contract cannot diverge."""
        n = len(tensors)
        Es = blockwise._as_bound_array(E, n)
        Ds = blockwise._as_bound_array(Delta, n)
        warms = [None] * n if warm_freq is None else list(warm_freq)
        if len(warms) != n:
            raise ValueError(f"expected {n} per-tensor warm spectra, got {len(warms)}")
        corrected, edits, it_blocks, conv_blocks, it_t, conv_t = [], [], [], [], [], []
        for t, e, d, w in zip(tensors, Es, Ds, warms):
            t = jnp.asarray(t)
            corr, spat, freq, iters, conv = blockwise.blockwise_correct_with_edits(
                t, e, d, block=block, max_iters=max_iters, fft_impl=fft_impl,
                warm=None if w is None else jnp.asarray(w),
            )
            if return_corrected:
                corrected.append(corr.astype(t.dtype))
            if return_edits:
                edits.append((spat, freq))
            it_blocks.append(iters)
            conv_blocks.append(conv)
            it_t.append(jnp.max(iters))
            conv_t.append(jnp.all(conv))
        stats = blockwise.BatchCorrectionStats(
            iterations=jnp.stack(it_t) if n else jnp.zeros((0,), jnp.int32),
            converged=jnp.stack(conv_t) if n else jnp.zeros((0,), bool),
            block_iterations=jnp.concatenate(it_blocks) if n else jnp.zeros((0,), jnp.int32),
            block_converged=jnp.concatenate(conv_blocks) if n else jnp.zeros((0,), bool),
        )
        if return_edits:
            return corrected, edits, stats
        return corrected, stats

    # -- ENCODE ------------------------------------------------------------

    def encode_field(self, result: FieldResult, plan: FieldPlan) -> Tuple[EncodedEdits, EncodedEdits]:
        """Serialize a whole field's edit streams with adaptive bit-widths.

        K_s and the active pair-weighted Delta sum are known exactly
        post-projection, so the widths come from the closed form in
        :func:`adaptive_quant_bits` (beyond-paper; the paper fixes m = 16
        which covers only the direct term).  The Delta sum runs over the
        *full* spectrum, so each active half-spectrum edit contributes with
        its conjugate-pair multiplicity.
        """
        k_s = int(np.count_nonzero(result.spat))
        shape_f = np.shape(result.freq)
        # the active components alone, in C order: the same terms, summed
        # in the same order, as over the dense mask
        at = np.unravel_index(np.flatnonzero(result.freq), shape_f)
        pair_w = np.broadcast_to(np.asarray(rfft_pair_weights(plan.shape)), shape_f)[at]
        delta_b = np.broadcast_to(np.asarray(plan.Delta), shape_f)[at]
        sum_active_delta = float(np.sum(pair_w * delta_b))
        n = int(np.prod(plan.shape)) if plan.shape else 1
        m_s, m_f = adaptive_quant_bits(
            plan.quant_bits,
            k_s,
            plan.E,
            float(np.min(plan.Delta)),
            sum_active_delta,
            n,
        )
        if plan.roi:
            # Per-point spatial bounds split the cross-term accounting:
            # m_s stays from the call above (spatial edits are bounded by
            # their own per-point bound <= E, so the global-E width covers
            # the FFT leakage of the quantized stream), while m_f must keep
            # the IFFT leakage of the frequency stream under the *tightest*
            # point's reserved margin — rerun with E_min for that side.
            _, m_f = adaptive_quant_bits(
                plan.quant_bits,
                k_s,
                float(np.min(plan.E_grid)),
                float(np.min(plan.Delta)),
                sum_active_delta,
                n,
            )
        try:
            se = encode_edits(
                result.spat, plan.E_grid if plan.roi else plan.E, m=m_s, codec=plan.codec
            )
            fe = encode_edits(result.freq, plan.Delta, m=m_f, codec=plan.codec, half_spectrum=True)
        except (RuntimeError, MemoryError, OSError) as e:
            raise classify_exception(e, "encode") from e
        return se, fe

    def encode_pencils(
        self,
        spat_t: Any,
        freq_t: Any,
        tiles0: np.ndarray,
        plan: PencilPlan,
        codec: str = "zlib",
    ) -> Tuple[EncodedEdits, EncodedEdits, bool]:
        """Polish + serialize one tensor's pencil edit streams.

        ``spat_t``/``freq_t`` are the device edit tiles from
        :meth:`correct`; ``tiles0`` the float64 tiling of the *initial*
        error (:meth:`tile_f64`).  The float64 polish reruns on the
        reconstructed loop state, then adaptive bit-widths are chosen per
        worst-case pencil.  The third value is the polish's ``settled`` flag
        (see :func:`polish_pocs_float64`): False means the tensor is not
        converged, whatever the device loop reported.
        """
        with span("ffcz.fetch"):
            spat = np.asarray(spat_t, dtype=np.float64)
            freq = np.asarray(freq_t, dtype=np.complex128)
        with span("ffcz.polish", slabs=_slab_count(np.shape(tiles0))):
            eps_now = tiles0 + np.fft.irfft(freq, n=plan.block, axis=-1) + spat
            _eps, spat, freq, settled = polish_pocs_float64(
                eps_now, spat, freq, plan.E_proj, plan.Delta_proj, axes=(1,)
            )
        pair_w = np.asarray(rfft_pair_weights((plan.block,))).reshape(-1)
        k_s_max = int(np.count_nonzero(spat, axis=1).max()) if spat.size else 0
        wsum_max = float(((freq != 0) * pair_w).sum(axis=1).max()) if freq.size else 0.0
        m_s, m_f = adaptive_quant_bits(
            plan.quant_bits, k_s_max, plan.E, plan.Delta, wsum_max * plan.Delta, plan.block, cap=40
        )
        try:
            se = encode_edits(spat, plan.E, m=m_s, codec=codec)
            fe = encode_edits(freq, plan.Delta, m=m_f, codec=codec, half_spectrum=True)
        except (RuntimeError, MemoryError, OSError) as e:
            raise classify_exception(e, "encode") from e
        return se, fe, settled


@functools.lru_cache(maxsize=None)
def default_engine() -> CorrectionEngine:
    """Process-wide batched engine the framework integrations share."""
    return CorrectionEngine(backend="batched")
