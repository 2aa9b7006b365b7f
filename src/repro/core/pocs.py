"""Alternating projection-correction (paper Alg. 1) as one jitted while_loop.

The paper's CUDA pipeline launches per-iteration kernels from the host
(cuFFT -> CheckConvergence -> ProjectOntoFCube -> cuFFT -> ProjectOntoSCube)
with device<->host synchronization on the convergence flag.  On TPU/JAX the
whole loop is a single ``jax.lax.while_loop`` resident in HBM: no launch
overhead, no host sync, and XLA fuses the clip/accumulate stages around the
FFTs.  The convergence check is *fused into* the f-cube projection (one pass
over delta instead of the paper's two kernels) — a beyond-paper optimization
mirrored in the Pallas kernel (:mod:`repro.kernels.fcube`).

Hermitian rFFT fast path (default, ``use_rfft=True``): the error vector is
real, so its spectrum is Hermitian-symmetric and the full complex ``fftn`` is
redundant.  The loop state, the f-cube projection, the convergence check and
the ``freq_edits`` accumulator all live on the ``rfftn`` half-spectrum (last
axis ``N//2 + 1``), halving FFT flops and frequency-state HBM traffic per
iteration.  Violation counts weight each half-spectrum component by its
conjugate-pair multiplicity (:func:`repro.core.cubes.rfft_pair_weights`), so
``final_violations`` keeps full-spectrum semantics.  ``use_rfft=False``
retains the complex-FFT path as the oracle (tests bit-compare the two).

Semantics match Alg. 1 exactly:

  eps <- x_hat - x                       (inside the s-cube by construction)
  loop:
    delta <- FFT(eps)
    if delta inside f-cube: stop          (CheckConvergence)
    delta' <- clip(delta, +-Delta)        (ProjectOntoFCube)
    freq_edits += delta' - delta
    eps <- IFFT(delta')
    eps' <- clip(eps, +-E)                (ProjectOntoSCube)
    spat_edits += eps' - eps
    eps <- eps'

Both cubes are closed convex sets with (generically) non-empty intersection,
so POCS converges; ``max_iters`` guards the tangential-intersection slow case
(paper §III), after which a final s-cube projection guarantees the spatial
bound and the residual frequency excess is reported.

Transform selector (``fft_impl``): XLA's C2R inverse is the slow half of the
loop (~2.1x the R2C forward on the CI CPU), so the loop's transforms are
pluggable through :mod:`repro.kernels.rfft`:

  ``"xla"``     ``jnp.fft.rfftn``/``irfftn`` (the default; blobs stay
                byte-identical to earlier writers).
  ``"packed"``  XLA's forward r2c (DUCC is already pack-trick fast) + the
                pure-XLA pack-trick C2R inverse (``packed_irfftn``: one
                Hermitian-mirror gather, twiddle recombination, half-length
                complex ``ifftn``, de-interleave) — 1.2-1.3x per iteration
                on CPU.  Composes with ``dist`` mode, where it swaps the
                local last-axis c2r pass.
  ``"pallas"``  the packed transforms with fused Pallas epilogues: the
                forward epilogue performs the f-cube clip, the pair-weighted
                violation count AND the inverse pack twiddle in one VMEM
                pass; the inverse epilogue fuses the s-cube clip into the
                de-interleave — one pass over the data instead of
                FFT-then-clip (interpret mode on CPU, Mosaic on TPU).

On TPU, whose C2R is inaccurate
(:func:`repro.kernels.rfft.ops.c2r_is_accurate`), ``"xla"`` runs XLA's
transforms as separate per-axis passes — the passes of the slab-sharded
``dist`` mode, in its order — with the last-axis C2R pass swapped for the
pack trick between ``optimization_barrier`` s; ``"packed"`` keeps its
whole-field inverse, also between barriers there.

Packed/pallas trajectories differ from ``"xla"`` at float32-rounding level
(the 1/N normalization and twiddle roundings sit elsewhere), so distributed
parity for them is ``"bound"``, never ``"bitwise"`` — the dual-bound
guarantee is unconditional either way (float64 polish).  Shapes with an odd
last axis fall back statically: ``"packed"`` to the XLA transforms,
``"pallas"`` to XLA transforms + the fused fcube/scube projection kernels.

Convergence-check cadence (``check_every``): the violation-count reduction
(and its integer ``psum`` in dist mode) can run every K-th iteration instead
of every iteration — extra POCS iterations are always safe (projections are
no-ops once feasible), so the only cost is declaring convergence up to K-1
iterations late.  The final iteration before ``max_iters`` always checks, so
``final_violations`` stays meaningful.  Opt-in via the plan knob
(``FFCzConfig.check_every``); bound-conformance gated.

Warm start (``warm_freq``, ISSUE 8): a temporal stream's consecutive frames
produce highly correlated edit spectra, so the loop can seed its
``freq_edits`` accumulator from the PREVIOUS frame's converged spectrum
instead of zero.  The warm state is constructed to preserve the loop
invariant ``eps == eps0 + IFFT(freq_edits) + spat_edits`` exactly: the warm
spectrum is applied through the loop's own inverse transform and the result
is re-projected onto the s-cube (accumulating into ``spat_edits``) before
iteration 0, so a warm-started loop that converges immediately still
satisfies BOTH bounds by construction.  ``warm_freq=None`` (the default)
builds the exact legacy zero state — the trajectory, and therefore the edit
streams and blob bytes, are bitwise identical to pre-warm-start writers
(gated by tests/test_temporal.py).  See docs/streaming.md.

Distributed pencil mode (``dist=DistSpec(...)``): the loop body runs on a
*local slab* inside a ``shard_map`` region, with the FFT pair replaced by
the pencil-decomposed transforms of :mod:`repro.sharding.dist_fft`
(zero-padded all_to_all transposes between per-axis passes — any axis
extents, uneven slabs included) and the convergence count reduced with an
integer ``psum``.  Slab-pad rows of the local state are exactly zero and
stay exactly zero through the loop (clips and FFTs are zero-preserving, the
strict-inequality violation test never fires on zeros), so no pad masking
is needed in the body.  The per-axis pass order matches the fused
single-device transform bitwise, so a sharded whole-field loop reproduces
the single-device trajectory exactly on ``"bitwise"``-parity shapes — the
whole-field analogue of the PR 2 batched-vs-sharded parity bar.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.cubes import (
    project_box_relaxed,
    project_fcube,
    project_fcube_relaxed,
    project_scube,
    rfft_pair_weights,
    rfft_shape,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AlternatingProjectionResult:
    eps: Any  # final spatial error vector (inside s-cube; inside f-cube if converged)
    spat_edits: Any  # accumulated displacement along the spatial basis (real)
    # accumulated displacement along the frequency basis (complex); rfft
    # half-spectrum layout (last axis N//2+1) when use_rfft, else full spectrum
    freq_edits: Any
    iterations: Any  # int32 iteration count
    converged: Any  # bool: inside both cubes
    final_violations: Any  # int32: f-cube violations at exit (0 if converged)


_FFT_IMPLS = ("xla", "packed", "pallas")


def _alternating_projection(
    eps0: jnp.ndarray,
    E,
    Delta,
    max_iters: int = 1000,
    use_kernels: bool = False,
    relax: float = 1.0,
    check_slack=0.0,
    use_rfft: bool = True,
    dist: Optional[Any] = None,
    fft_impl: str = "xla",
    check_every: int = 1,
    warm_freq: Optional[Any] = None,
) -> AlternatingProjectionResult:
    """Run Alg. 1 from an initial spatial error vector ``eps0``.

    Args:
      eps0: x_hat - x from the base compressor (any rank, real dtype).
      E, Delta: scalar or broadcastable pointwise bounds (see core.bounds).
        Under ``use_rfft`` a pointwise ``Delta`` may be given either on the
        half-spectrum (``rfft_shape(eps0.shape)``) or on the full spectrum
        (``eps0.shape`` — sliced to the half-spectrum, exact for the
        Hermitian-symmetric grids ``core.bounds`` produces).
      max_iters: POCS iteration cap.
      use_kernels: route projections through the Pallas TPU kernels
        (``repro.kernels``) instead of the pure-jnp oracles.
      relax: over-relaxation factor (beyond-paper, addresses the paper's
        noted slow nearly-tangential convergence): the f-cube step moves
        ``relax`` times the projection displacement, then re-projects, i.e.
        relaxed POCS x <- P(x + (relax-1)(P(x) - x)).  1.0 is the
        paper-faithful plain alternating projection; 1.0 < relax < 2.0
        preserves Fejer monotonicity (convergence) for convex sets.  The
        final iterate is still an exact f-cube projection, so feasibility
        guarantees are unchanged.  For a box both projections collapse into
        the closed-form one-clip pass of ``project_box_relaxed``.
      use_rfft: run the loop on the Hermitian half-spectrum (the fast path;
        ``freq_edits`` then has rfft layout).  False keeps the full
        complex-FFT oracle.
      dist: a :class:`repro.sharding.dist_fft.DistSpec` — run the loop on a
        local slab inside a ``shard_map`` region with the pencil-decomposed
        distributed transforms (``eps0`` is then the local block — slab-pad
        rows zero, ``freq_edits`` the local half-spectrum block, and a
        pointwise ``Delta`` must already be the local frequency block,
        zero-padded to it).  Callers inside ``shard_map`` use the
        undecorated :func:`_alternating_projection` under the region's
        outer jit.
      fft_impl: loop transform selector — ``"xla"`` (default),
        ``"packed"`` (pack-trick C2R inverse, pure XLA, also composes with
        ``dist`` mode's local last-axis pass) or ``"pallas"`` (packed
        transforms with the fused clip/count epilogue kernels; requires the
        rfft path, ``relax == 1.0``, no ``dist``).  See the module
        docstring; shapes with an odd last axis fall back statically.
      check_every: run the convergence-check reduction every K-th iteration
        (and on the final one) instead of every iteration; 1 (default)
        preserves the exact legacy trajectory.
      warm_freq: optional complex seed for the ``freq_edits`` accumulator
        (``freq_shape`` layout: the rfft half-spectrum, the full spectrum
        when ``use_rfft=False``, or the local half-spectrum block in dist
        mode).  Applied through the loop's own inverse transform and
        s-cube-projected before iteration 0 so the loop invariant
        ``eps == eps0 + IFFT(freq_edits) + spat_edits`` holds exactly (see
        module docstring).  ``None`` (default) is the bitwise-identical
        legacy cold start.

    Returns an :class:`AlternatingProjectionResult` pytree.
    """
    if fft_impl not in _FFT_IMPLS:
        raise ValueError(f"fft_impl must be one of {_FFT_IMPLS}, got {fft_impl!r}")
    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    if fft_impl != "xla" and not use_rfft:
        raise ValueError("fft_impl='packed'/'pallas' require the rfft path (use_rfft=True)")
    if fft_impl == "pallas":
        if use_kernels:
            raise ValueError(
                "fft_impl='pallas' already fuses the projections into its "
                "epilogue kernels; drop use_kernels"
            )
        if relax != 1.0:
            raise ValueError("fft_impl='pallas' supports only relax == 1.0")
        if dist is not None:
            raise ValueError("dist mode supports fft_impl 'xla' or 'packed' only")
    eps0 = jnp.asarray(eps0)
    cdtype = jnp.complex64 if eps0.dtype != jnp.float64 else jnp.complex128
    E = jnp.asarray(E, dtype=eps0.dtype)
    Delta_r = jnp.asarray(Delta, dtype=eps0.real.dtype)

    shape = eps0.shape
    # Packed/pallas transforms need an even last axis; fall back statically
    # otherwise ("packed" -> the XLA transforms, "pallas" -> XLA transforms
    # + the fused fcube/scube projection kernels of the use_kernels path).
    from repro.kernels.rfft import ops as _rfft_ops

    if fft_impl != "xla":
        _packed_ok = _rfft_ops.supports_packed(dist.gshape if dist is not None else shape)
    else:
        _packed_ok = False
    pallas_fused = fft_impl == "pallas" and _packed_ok
    if fft_impl == "pallas" and not _packed_ok:
        use_kernels = True
    if dist is not None:
        if use_kernels or not use_rfft:
            raise ValueError("dist mode supports only the pure-jnp rfft path")
        from repro.sharding import dist_fft as _dfft

        axis_name, gshape = dist.axis_name, dist.gshape
        weights = None
        freq_shape = _dfft.local_freq_shape(gshape, dist.n_dev)
        if Delta_r.ndim and Delta_r.shape != freq_shape:
            raise ValueError(
                f"dist mode needs a scalar Delta or the local half-spectrum block "
                f"{freq_shape}, got {Delta_r.shape}"
            )
        if E.ndim and E.shape != eps0.shape:
            # pointwise spatial bounds (ROI grids) must arrive pre-sharded in
            # the padded local layout, exactly like a pointwise Delta grid
            raise ValueError(
                f"dist mode needs a scalar E or the local spatial block "
                f"{eps0.shape}, got {E.shape}"
            )
        inv_impl = "packed" if _packed_ok else "xla"
        fwd = lambda e: _dfft.rfftn_local(e, dist).astype(cdtype)  # noqa: E731
        inv = lambda d: _dfft.irfftn_local(d, dist, fft_impl=inv_impl).astype(eps0.dtype)  # noqa: E731
    elif use_rfft:
        # pair weights are only consumed by the fused kernels' reductions;
        # the jnp branch uses the cheaper 2*sum - self-conjugate-planes form
        weights = rfft_pair_weights(shape) if (use_kernels or pallas_fused) else None
        if Delta_r.ndim and Delta_r.shape == shape:
            # full-spectrum pointwise grid: Hermitian-symmetric by contract,
            # so the rfft half-plane slice is exact
            Delta_r = Delta_r[..., : shape[-1] // 2 + 1]
        freq_shape = rfft_shape(shape)
        if _packed_ok:
            # the measured gap is the C2R inverse; the XLA forward (DUCC r2c,
            # already pack-trick fast) stays
            fwd = lambda e: jnp.fft.rfftn(e).astype(cdtype)  # noqa: E731
            if _rfft_ops.c2r_is_accurate():
                inv = lambda d: _rfft_ops.packed_irfftn(d, shape).astype(eps0.dtype)  # noqa: E731
            else:
                # isolated from the loop body, as in rfft ops.c2r_last
                barrier = jax.lax.optimization_barrier
                inv = lambda d: barrier(  # noqa: E731
                    _rfft_ops.packed_irfftn(barrier(d), shape)
                ).astype(eps0.dtype)
        else:
            fwd = lambda e: _rfft_ops.field_rfftn(e).astype(cdtype)  # noqa: E731
            inv = lambda d: _rfft_ops.field_irfftn(d, shape).astype(eps0.dtype)  # noqa: E731
    else:
        weights = None
        freq_shape = shape
        fwd = lambda e: jnp.fft.fftn(e).astype(cdtype)  # noqa: E731
        inv = lambda d: jnp.real(jnp.fft.ifftn(d)).astype(eps0.dtype)  # noqa: E731

    # Convergence test uses a float32-resolution tolerance: below
    # ~1e-5 relative the float32 FFT round-trip oscillates and cannot
    # make progress; the exact float64 polish in FFCz.compress owns the
    # last digits (its workload is O(tolerance), i.e. negligible).
    _CHECK_TOL = 1e-5

    if use_kernels:
        from repro.kernels.fcube import ops as fcube_ops
        from repro.kernels.scube import ops as scube_ops

        def f_project(delta, Delta):
            clipped, disp, viol = fcube_ops.project_fcube_fused(
                delta, Delta, weight=weights, check_tol=_CHECK_TOL, check_slack=check_slack
            )
            if relax != 1.0:
                clipped, _ = project_fcube(delta + relax * disp, Delta)
                disp = clipped - delta
            return clipped, disp, viol

        def s_project(eps, E):
            clipped, disp = scube_ops.project_scube_fused(eps, E)
            if relax != 1.0:
                clipped = jnp.clip(eps + relax * disp, -E, E)
                disp = clipped - eps
            return clipped, disp
    elif not pallas_fused:

        # Static layout facts for the cheap half-spectrum count below: the
        # last-axis k=0 plane (and the Nyquist plane for even N) is
        # self-conjugate and counts once; every other component stands for a
        # conjugate pair and counts twice.
        has_nyquist = use_rfft and shape and shape[-1] % 2 == 0 and shape[-1] // 2 + 1 > 1

        def _count_violations(delta):
            # check_slack: absolute float32-noise allowance for tiny
            # pointwise Delta_k (the caller reserves >= 2x this in its
            # bound shrink, and the float64 polish closes the gap exactly)
            dt = Delta_r * (1.0 + _CHECK_TOL) + check_slack
            vb = (jnp.abs(delta.real) > dt) | (jnp.abs(delta.imag) > dt)
            if dist is not None:
                # integer psum of pair-weighted local counts == the
                # single-device full-spectrum count, exactly
                w = _dfft.local_pair_weights(gshape, freq_shape, axis_name)
                viol = jax.lax.psum(jnp.sum(vb.astype(jnp.int32) * w), axis_name)
            elif use_rfft:
                # full-spectrum count without a weight-plane multiply:
                # 2 * total - (self-conjugate planes counted twice in it)
                viol = 2 * jnp.sum(vb) - jnp.sum(vb[..., 0])
                if has_nyquist:
                    viol = viol - jnp.sum(vb[..., -1])
            else:
                viol = jnp.sum(vb)
            return viol.astype(jnp.int32)

        def f_project(delta, Delta):
            if relax == 1.0:
                return project_fcube(delta, Delta)
            clipped = project_fcube_relaxed(delta, Delta, relax)
            return clipped, clipped - delta

        def s_project(eps, E):
            if relax == 1.0:
                return project_scube(eps, E)
            clipped = project_box_relaxed(eps, E, relax)
            return clipped, clipped - eps

    # Loop-invariant Hermitian-mirrored pointwise bound for the fused forward
    # epilogue (mirroring inside the body would re-gather every iteration).
    Delta_m = None
    if pallas_fused and Delta_r.ndim:
        Delta_m = _rfft_ops.mirror_half_spectrum(jnp.broadcast_to(Delta_r, freq_shape))

    def cond(state):
        _eps, _se, _fe, it, done, _viol = state
        return jnp.logical_and(~done, it < max_iters)

    def body(state):
        eps, spat_edits, freq_edits, it, _done, _viol = state
        delta = fwd(eps)
        if pallas_fused:
            # one VMEM pass: f-clip + edit displacement + pair-weighted
            # violation count + the inverse pack twiddle feeding ifftn
            clipped, f_disp, Z, viol = _rfft_ops.fwd_epilogue_fused(
                delta,
                Delta_r,
                Delta_m=Delta_m,
                weight=weights,
                check_tol=_CHECK_TOL,
                check_slack=check_slack,
            )
        elif use_kernels:
            clipped, f_disp, viol = f_project(delta, Delta_r)
        else:
            clipped, f_disp = f_project(delta, Delta_r)
            viol = None
        if check_every == 1:
            if viol is None:
                viol = _count_violations(delta)
            done = viol == 0
        else:
            # cadenced CheckConvergence: the reduction (and its psum in dist
            # mode) runs every K-th iteration and on the final one, so the
            # exit count is never stale; extra iterations are always safe
            # (projections are no-ops once feasible)
            do_check = jnp.logical_or(it % check_every == 0, it == max_iters - 1)
            if viol is None:
                viol = jax.lax.cond(
                    do_check, lambda: _count_violations(delta), lambda: jnp.int32(-1)
                )
            done = jnp.logical_and(do_check, viol == 0)
        # When already inside the f-cube, the displacement is zero and the
        # projections below are no-ops; masking keeps the loop branch-free
        # (matches the GPU implementation, which exits before projecting).
        freq_edits = freq_edits + jnp.where(done, 0, 1) * f_disp
        if pallas_fused:
            z = jnp.fft.ifftn(Z[..., : shape[-1] // 2])
            eps_s, s_disp = _rfft_ops.unpack_sclip_fused(z, E, shape)
            eps_s = eps_s.astype(eps0.dtype)
            s_disp = s_disp.astype(eps0.dtype)
        else:
            eps_f = inv(clipped)
            eps_s, s_disp = s_project(eps_f, E)
        spat_edits = spat_edits + jnp.where(done, 0, 1) * s_disp
        eps_next = jnp.where(done, eps, eps_s)
        return (eps_next, spat_edits, freq_edits, it + 1, done, viol)

    if warm_freq is None:
        eps_init, spat0 = eps0, jnp.zeros_like(eps0)
        if jnp.ndim(E) > 0:
            # Pointwise spatial bounds (ROI grids): the base compressor only
            # guarantees the *global* bound, so eps0 may already violate the
            # tighter per-point cube — and a trivially-converged loop (f-cube
            # satisfied at iteration 0) would return it unclipped.  Restore
            # the "state inside the s-cube" invariant before iteration 0,
            # same construction as the warm seed below.  Scalar E keeps the
            # exact legacy state (eps0 is inside the global cube by contract).
            eps_init, spat0 = project_scube(eps0, E)
            eps_init = eps_init.astype(eps0.dtype)
            spat0 = spat0.astype(eps0.dtype)
        state0 = (
            eps_init,
            spat0,
            jnp.zeros(freq_shape, dtype=cdtype),
            jnp.int32(0),
            jnp.bool_(False),
            jnp.int32(-1),
        )
    else:
        warm = jnp.asarray(warm_freq).astype(cdtype)
        if warm.shape != freq_shape:
            raise ValueError(
                f"warm_freq must have the loop's frequency-state shape "
                f"{freq_shape}, got {warm.shape}"
            )
        # Seed freq_edits with the previous frame's converged spectrum, then
        # restore the loop invariant: eps must equal
        # eps0 + IFFT(freq_edits) + spat_edits AND sit inside the s-cube
        # (the loop's convergence check only tests the f-cube, so skipping
        # this projection could declare a warm start converged with eps
        # outside the spatial bound).  `inv` is the loop's own inverse, so
        # this composes with packed/pallas transforms and dist-mode local
        # blocks (zero pad rows map to zero: linearity + clip(0) == 0).
        eps_w = eps0 + inv(warm)
        eps_s0, s_disp0 = project_scube(eps_w, E)
        state0 = (
            eps_s0.astype(eps0.dtype),
            s_disp0.astype(eps0.dtype),
            warm,
            jnp.int32(0),
            jnp.bool_(False),
            jnp.int32(-1),
        )
    eps, spat_edits, freq_edits, it, done, viol = jax.lax.while_loop(cond, body, state0)
    # Iteration accounting matches Table III: the terminating convergence
    # check counts as an iteration (pure-containment cases report 1).
    return AlternatingProjectionResult(
        eps=eps,
        spat_edits=spat_edits,
        freq_edits=freq_edits,
        iterations=it,
        converged=done,
        final_violations=jnp.where(done, 0, viol),
    )


# Public jitted entry point.  ``shard_map`` regions call the undecorated
# :func:`_alternating_projection` instead (the region's outer jit compiles it;
# a nested jit under manual collectives buys nothing and muddies the trace).
alternating_projection = functools.partial(
    jax.jit,
    static_argnames=(
        "max_iters", "use_kernels", "relax", "use_rfft", "dist", "fft_impl", "check_every",
    ),
)(_alternating_projection)
