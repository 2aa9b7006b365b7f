"""The float64 polish's sparse, slab-threaded rounds against the dense loop.

``_dense_polish`` is the polish as it was before its clips went sparse: a
whole-array clip, displacement and sum each round.  The engine's
``polish_pocs_float64`` must give the same ``eps``, ``spat`` and ``freq``
(``np.array_equal``: zeros may differ only in sign), the same ``settled``
flag and the same number of round trips, on the inline path and on the
threaded one; and a whole compression must give the same blob bytes.
"""

import importlib.util
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest
from scipy import fft as host_fft

from repro.compressors import get_compressor
from repro.core import engine as engine_mod
from repro.core.ffcz import FFCz, FFCzConfig

pytestmark = pytest.mark.timeout(300)

_NYX = Path(__file__).resolve().parents[1] / "perfbench" / "configs"


def _dense_polish(eps, spat, freq, E, Delta, axes=None, max_iters: int = 30, rounds=None):
    """The dense loop, verbatim but for the ``rounds`` counter."""
    axes = tuple(range(eps.ndim)) if axes is None else tuple(axes)
    s = [eps.shape[a] for a in axes]
    floor = engine_mod.POLISH_FLOOR_REL * float(np.max(Delta)) if np.size(Delta) else 0.0
    prev = np.inf
    for it in range(max_iters + 1):
        delta = host_fft.rfftn(eps, axes=axes, workers=-1)
        re = np.clip(delta.real, -Delta, Delta)
        im = np.clip(delta.imag, -Delta, Delta)
        disp = (re - delta.real) + 1j * (im - delta.imag)
        excess = float(np.max(np.abs(disp))) if disp.size else 0.0
        if excess == 0.0 or prev <= excess <= floor or it == max_iters:
            break
        prev = excess
        if rounds is not None:
            rounds.append(1)
        freq = freq + disp
        clipped = re + 1j * im
        eps_f = host_fft.irfftn(clipped, s=s, axes=axes, workers=-1)
        eps_s = np.clip(eps_f, -E, E)
        spat = spat + (eps_s - eps_f)
        eps = eps_s
    return eps, spat, freq, excess <= floor


def _state(shape, axes, pointwise, roi, seed=0):
    """A polish start state just outside both cubes: a field whose spectrum
    pokes out of the f-cube at a few components and whose points poke out
    of the s-cube at about 1%, as the float32 loop leaves it."""
    rng = np.random.default_rng(seed)
    eps = rng.uniform(-1.0, 1.0, shape)
    E = 0.99
    d = host_fft.rfftn(eps, axes=axes)
    mag = np.maximum(np.abs(d.real), np.abs(d.imag))
    if pointwise:
        Delta = mag * (1.0 + rng.uniform(-1e-4, 0.05, mag.shape))
    else:
        Delta = float(np.quantile(mag, 0.999))
    if roi:
        E = np.full(shape, E)
        E.reshape(-1)[:: 7] = 0.8
    spat = rng.standard_normal(shape) * 1e-3
    freq = d * 1e-3
    return eps, spat, freq, E, Delta


CASES = {
    "3d-scalar": ((32, 32, 32), None, False, False),
    "3d-pspec": ((32, 32, 32), None, True, False),
    "3d-roi": ((32, 32, 32), None, False, True),
    "pencils": ((64, 512), (1,), False, False),
}


@pytest.mark.parametrize("threaded", [False, True], ids=["inline", "threaded"])
@pytest.mark.parametrize("case", list(CASES))
def test_sparse_rounds_equal_the_dense_loop(case, threaded, monkeypatch):
    shape, axes, pointwise, roi = CASES[case]
    eps, spat, freq, E, Delta = _state(shape, axes, pointwise, roi)
    size = int(np.prod(shape))
    monkeypatch.setattr(engine_mod, "_THREADED_MIN", size if threaded else size + 1)
    assert (engine_mod._slab_count(shape) > 1) == threaded

    ref_rounds, rounds = [], []
    ref = _dense_polish(eps, spat, freq, E, Delta, axes=axes, rounds=ref_rounds)
    irfftn = engine_mod.host_fft.irfftn
    monkeypatch.setattr(
        engine_mod.host_fft, "irfftn", lambda *a, **k: rounds.append(1) or irfftn(*a, **k)
    )
    args = tuple(np.copy(a) for a in (eps, spat, freq))
    got = engine_mod.polish_pocs_float64(*args, E, Delta, axes)
    assert len(ref_rounds) >= 2 and len(rounds) == len(ref_rounds)
    for a, b in zip(got[:3], ref[:3]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert got[3] == ref[3]
    # the arguments are left as they were
    assert all(np.array_equal(a, b) for a, b in zip(args, (eps, spat, freq)))


@pytest.mark.parametrize("threaded", [False, True], ids=["inline", "threaded"])
def test_rebuild_equals_the_dense_sum(threaded, monkeypatch):
    """The polish's float64 start state, ``eps0 + (IFFT(freq) + spat)``,
    summed slab by slab in place."""
    rng = np.random.default_rng(1)
    eps0 = rng.standard_normal((32, 32, 32)).astype(np.float32)
    ifft, spat = rng.standard_normal((2, 32, 32, 32))
    monkeypatch.setattr(engine_mod, "_THREADED_MIN", eps0.size if threaded else eps0.size + 1)
    assert (engine_mod._slab_count(eps0.shape) > 1) == threaded
    want = np.asarray(eps0, dtype=np.float64) + (ifft + spat)
    assert np.array_equal(engine_mod._rebuild_f64(eps0, ifft.copy(), spat), want)


def test_compress_blob_bytes_equal_the_dense_loop(monkeypatch):
    """A 64^3 Nyx-like field through ``FFCz.compress``, threaded: the blob
    is the dense loop's byte for byte."""
    cfg = json.loads((_NYX / "nyx.json").read_text())
    spec = importlib.util.spec_from_file_location("nyx_gen", _NYX / "nyx.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    x = gen.make({**cfg, "edge": 64}, "field", 0, 5200000001)
    monkeypatch.setattr(engine_mod, "_THREADED_MIN", x.size // 4)
    ffcz = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, verify=False))
    new = ffcz.compress(x).to_bytes()
    monkeypatch.setattr(engine_mod, "polish_pocs_float64", _dense_polish)
    old = ffcz.compress(x).to_bytes()
    assert new == old


def test_concurrent_polishes_share_the_pool(monkeypatch):
    """More threaded polishes at once than there are cores, on the one
    pool, with the interpreter switching threads often: each equals the
    dense loop."""
    monkeypatch.setattr(engine_mod, "_THREADED_MIN", 1)
    n = (os.cpu_count() or 1) + 2
    states = [_state((32, 32, 32), None, False, seed % 2 == 1, seed) for seed in range(n)]
    refs = [_dense_polish(*st) for st in states]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(max_workers=n) as ex:
            futs = [ex.submit(engine_mod.polish_pocs_float64, *st) for st in states]
            got = [f.result(timeout=120) for f in futs]
    finally:
        sys.setswitchinterval(interval)
    for g, r in zip(got, refs):
        assert all(np.array_equal(a, b) for a, b in zip(g[:3], r[:3])) and g[3] == r[3]
