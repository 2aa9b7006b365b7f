"""The check catches a broken timed path, and the control fails it.

Each test skips the look for a chip (``tiny.run`` passes the CPU device),
drives the rest of a run at a tiny size with one fault planted under the
service, and sees ``correct`` come out false: the correction step returning
its state unchanged, half of each batch left out, and an answer altered
where the service produces it.  (A cell on one chip has no exchange between
chips to leave out.)  The control, the reference's bound resolution
carried in bfloat16 in place of the program's, must fail too.
"""

import dataclasses
import struct
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.tests import tiny
from repro.core import blockwise, engine
from repro.core.ffcz import FFCzBlob
from repro.core.pocs import AlternatingProjectionResult
from repro.serving import ffcz_service


def _unchanged_state(monkeypatch):
    """EXECUTE hands back the state it was given: no device projection and
    no float64 polish."""

    def field(eps0, E, Delta, **_kw):
        eps0 = jnp.asarray(eps0)
        half = eps0.shape[:-1] + (eps0.shape[-1] // 2 + 1,)
        return AlternatingProjectionResult(
            eps=eps0, spat_edits=jnp.zeros_like(eps0), freq_edits=jnp.zeros(half, jnp.complex64),
            iterations=jnp.int32(1), converged=jnp.bool_(True), final_violations=jnp.int32(0))

    def packed(packed, counts, E, Delta, **_kw):
        b, block = np.shape(packed)
        n = len(counts)
        res = AlternatingProjectionResult(
            eps=jnp.asarray(packed), spat_edits=jnp.zeros((b, block), jnp.float32),
            freq_edits=jnp.zeros((b, block // 2 + 1), jnp.complex64),
            iterations=jnp.ones((b,), jnp.int32), converged=jnp.ones((b,), bool),
            final_violations=jnp.zeros((b,), jnp.int32))
        stats = blockwise.BatchCorrectionStats(
            iterations=jnp.ones((n,), jnp.int32), converged=jnp.ones((n,), bool),
            block_iterations=res.iterations, block_converged=res.converged)
        return res, stats

    monkeypatch.setattr(engine, "alternating_projection", field)
    monkeypatch.setattr(blockwise, "correct_packed", packed)
    monkeypatch.setattr(engine, "polish_pocs_float64",
                        lambda eps, spat, freq, E, Delta, axes=None, max_iters=30:
                        (eps, spat, freq, True))


def _half_batch_left_out(monkeypatch):
    """The scheduler drops the second half of every bucket it pops."""
    pop = ffcz_service.FFCzService._pop_unit

    def half(self):
        unit = pop(self)
        return unit[: -(-len(unit) // 2)]

    monkeypatch.setattr(ffcz_service.FFCzService, "_pop_unit", half)


def _answer_altered(monkeypatch):
    """Every blob leaves the service with its spatial bound halved."""
    complete = ffcz_service.FFCzService._complete

    def altered(self, req, payload):
        if payload[:4] == b"FFSB":
            E, = struct.unpack_from("<d", payload, 5)
            body = bytearray(payload[:-4])
            struct.pack_into("<d", body, 5, E / 2)
            payload = bytes(body) + struct.pack("<I", zlib.crc32(bytes(body)))
        else:
            blob = FFCzBlob.from_bytes(payload)
            payload = dataclasses.replace(blob, E=blob.E / 2).to_bytes()
        return complete(self, req, payload)

    monkeypatch.setattr(ffcz_service.FFCzService, "_complete", altered)


FAULTS = {
    (tiny.NYX, "unchanged"): _unchanged_state,
    (tiny.NYX, "altered"): _answer_altered,
    (tiny.EEG, "unchanged"): _unchanged_state,
    (tiny.EEG, "half_batch"): _half_batch_left_out,
    (tiny.EEG, "altered"): _answer_altered,
}
#: the compared number each fault must push over its limit
CAUGHT_BY = {"unchanged": "spectral", "half_batch": "not_clean", "altered": "E_gap"}


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(monkeypatch, cell, fault):
    FAULTS[cell, fault](monkeypatch)
    # a fast mix, so that the half-batch fault finds buckets of two
    traffic = {"rate_per_s": 200.0} if cell == tiny.EEG else None
    _run, line = tiny.run(cell, traffic=traffic)
    assert line["correct"] is False
    caught = line["checks"][CAUGHT_BY[fault]]
    assert caught["value"] > caught["limit"], line["checks"]


@pytest.mark.parametrize("cell", [tiny.NYX, tiny.EEG])
def test_the_bfloat16_control_is_not_correct(cell):
    run, line = tiny.run(cell)
    assert line["correct"] is True
    checks = run.check(control=True)
    assert any(c["control"] > c["limit"] for c in checks.values() if "control" in c)
    assert checks["E_gap"]["control"] > 3 * checks["E_gap"]["value"]
