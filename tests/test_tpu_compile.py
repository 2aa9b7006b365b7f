"""Compile the main-path kernels and the whole-field POCS program for a
described TPU v5e at Nyx scale (512^3), without a chip.

The TPU compiler refuses what interpret mode accepts (block shapes its
tiling cannot lay out, programs that do not fit HBM), so these compiles
guard every change to the kernels and the loop.  Nothing runs: the
programs are compiled from shapes against ``v5e:2x2``'s first device.  The
topology is described inside a module fixture, never at import, so only the
worker that runs this file loads the TPU library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import pocs
from repro.core.cubes import rfft_shape
from repro.kernels.fcube.kernel import fcube_pallas
from repro.kernels.rfft.kernel import BLOCK_ROWS, rfft_fwd_epilogue_pallas, unpack_sclip_pallas
from repro.kernels.scube.kernel import scube_pallas
from repro.kernels.tiling import LANES

#: SDRBench's Nyx fields are 512^3 float32.
NYX = (512, 512, 512)
#: One v5e chip's HBM (Google Cloud, "TPU v5e": 16 GB).
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile written to the persistent cache cannot be read
    # back without the chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _plane_rows(shape) -> int:
    """Rows of the (rows, 128) planes that tile a 512^3 half-spectrum."""
    n = int(np.prod(rfft_shape(shape)))
    rows = -(-n // LANES)
    return rows + (-rows) % BLOCK_ROWS


def _compile(fn, args, one_chip, **static):
    sds = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    return jax.jit(lambda *a: fn(*a, interpret=False, **static)).lower(*sds).compile()


@pytest.mark.parametrize("pointwise", [False, True], ids=["scalar", "pointwise"])
@pytest.mark.parametrize("kernel", ["fcube", "scube", "rfft_fwd_epilogue", "unpack_sclip"])
def test_kernel_compiles_for_v5e(kernel, pointwise, one_chip):
    plane = ((_plane_rows(NYX), LANES), jnp.float32)
    weights = ((_plane_rows(NYX), LANES), jnp.int32)
    scalar = ((1, 1), jnp.float32)
    bound = plane if pointwise else scalar
    fn, args, static = {
        "fcube": (fcube_pallas, [plane, plane, bound, weights, scalar],
                  dict(pointwise=pointwise, weighted=True)),
        "scube": (scube_pallas, [plane, bound], dict(pointwise=pointwise)),
        "rfft_fwd_epilogue": (rfft_fwd_epilogue_pallas,
                              [plane] * 4 + [bound, bound] + [plane] * 2 + [weights, scalar],
                              dict(pointwise=pointwise)),
        "unpack_sclip": (unpack_sclip_pallas, [plane, plane, bound, bound],
                         dict(pointwise=pointwise)),
    }[kernel]
    compiled = _compile(fn, args, one_chip, **static)
    # a Mosaic kernel, not the interpreter's emulation
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fft_impl", ["xla", "pallas"])
def test_whole_field_pocs_fits_one_v5e(fft_impl, one_chip, monkeypatch):
    # the loop picks its inverse and the kernels' interpret mode from the
    # process's own (CPU) backend; the program compiled here is the one the
    # chip runs
    from repro.kernels.rfft import ops as rfft_ops

    monkeypatch.setattr(rfft_ops, "_is_cpu", lambda: False)
    monkeypatch.setattr(rfft_ops, "c2r_is_accurate", lambda: False)
    field = jax.ShapeDtypeStruct(NYX, jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = pocs.alternating_projection.lower(
        field, scalar, scalar, max_iters=1000, fft_impl=fft_impl
    ).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB does not fit one v5e"
    kernels = compiled.as_text().count("tpu_custom_call")
    assert kernels == (2 if fft_impl == "pallas" else 0)
