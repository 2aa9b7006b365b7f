"""The service's profiler spans and per-request wait counters.

A whole field and a two-request pencil bucket run through ``FFCzService``
at ``pipeline_depth`` 2 under ``jax.profiler.trace``; the trace is read back
with ``jax.profiler.ProfileData``.  Every stage span appears once per unit
(PLAN and the base codec once per request), the stages nest in their
unit's ``ffcz.front`` / ``ffcz.back`` on the scheduler and the encode worker
respectively, the ``uid`` stats join a unit's spans across the two threads,
and each ``ffcz.polish.round`` is one float64 ``irfftn`` of the polish and
carries its clip counts.

``queue_s`` / ``handoff_s`` are checked exactly on a clock that only the
stages advance: the base codec costs 1 s of FRONT, the encoder 10 s of BACK.
"""

import glob
from collections import Counter

import jax
import numpy as np
import pytest

from repro.compressors import get_compressor
from repro.core import engine as engine_mod
from repro.core.engine import CorrectionEngine
from repro.core.ffcz import FFCzConfig
from repro.serving.ffcz_service import FFCzService, ServiceConfig

pytestmark = pytest.mark.timeout(300)

FRONT_STAGES = ("ffcz.plan", "ffcz.base", "ffcz.dispatch")
BACK_STAGES = ("ffcz.fence", "ffcz.fetch", "ffcz.polish", "ffcz.encode")


def _cfg():
    return FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=300, verify=False)


def _read_spans(log_dir):
    """``ffcz.*`` host events: ``(name, line, start, end, stats)``, where
    ``line`` tells the profiler's per-thread lines apart."""
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ffcz."):
                    s = int(e.start_ns)
                    stats = {k: v for k, v in e.stats}
                    out.append((e.name, (plane.name, i), s, s + int(e.duration_ns), stats))
    return out


def _inside(spans, outer):
    """The spans on ``outer``'s thread that lie within it."""
    _name, line, s, e, _st = outer
    return [sp for sp in spans if sp is not outer and sp[1] == line and s <= sp[2] and sp[3] <= e]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run: a 16^3 field, then two pencil tensors (one bucket).
    Counts the float64 ``irfftn`` calls made inside the polish."""
    log_dir = tmp_path_factory.mktemp("trace")
    rounds = []
    inside = []
    real_polish = engine_mod.polish_pocs_float64
    real_irfftn = engine_mod.host_fft.irfftn

    def polish(*a, **k):
        inside.append(1)
        try:
            return real_polish(*a, **k)
        finally:
            inside.pop()

    def irfftn(*a, **k):
        if inside:
            rounds.append(1)
        return real_irfftn(*a, **k)

    rng = np.random.default_rng(11)
    svc = FFCzService(
        get_compressor("szlike"), config=ServiceConfig(max_batch=4, block=64, pipeline_depth=2)
    )
    field = svc.submit_compress(rng.standard_normal((16, 16, 16)).astype(np.float32), _cfg())
    pencils = [
        svc.submit_pencils(rng.standard_normal(n).astype(np.float32), 1e-3, 1e-4)
        for n in (200, 130)
    ]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "polish_pocs_float64", polish)
        mp.setattr(engine_mod.host_fft, "irfftn", irfftn)
        with jax.profiler.trace(str(log_dir)):
            res = svc.drain()
    svc.close()
    return {
        "spans": _read_spans(log_dir),
        "res": res,
        "field": field,
        "pencils": pencils,
        "rounds": len(rounds),
        "counters": dict(svc.counters),
    }


def _unit(spans, outer_name, uid):
    (outer,) = [sp for sp in spans if sp[0] == outer_name and sp[4].get("uid") == uid]
    return outer, Counter(sp[0] for sp in _inside(spans, outer))


def test_every_response_completed(traced):
    assert all(r.ok for r in traced["res"].values())
    assert list(traced["res"]) == [traced["field"], *traced["pencils"]]


def test_field_stages_once_each_nested_on_two_threads(traced):
    spans, uid = traced["spans"], traced["field"]
    front, inner_front = _unit(spans, "ffcz.front", uid)
    back, inner_back = _unit(spans, "ffcz.back", uid)
    assert front[4] == {"uid": uid, "kind": "field", "n": 1}
    assert back[4] == {"uid": uid, "kind": "field", "n": 1}
    assert front[1] != back[1]  # scheduler and encode worker
    assert {k: inner_front[k] for k in FRONT_STAGES} == dict.fromkeys(FRONT_STAGES, 1)
    assert {k: inner_back[k] for k in BACK_STAGES} == dict.fromkeys(BACK_STAGES, 1)
    assert not set(inner_front) & set(BACK_STAGES)
    assert not set(inner_back) & set(FRONT_STAGES)
    # the polish's rounds lie inside the polish span
    (polish,) = [sp for sp in _inside(spans, back) if sp[0] == "ffcz.polish"]
    assert all(sp[0] == "ffcz.polish.round" for sp in _inside(spans, polish))


def test_pencil_bucket_stages(traced):
    spans, lead = traced["spans"], traced["pencils"][0]
    front, inner_front = _unit(spans, "ffcz.front", lead)
    back, inner_back = _unit(spans, "ffcz.back", lead)
    assert front[4] == {"uid": lead, "kind": "pencils", "n": 2}
    assert front[1] != back[1]
    # PLAN and the base codec per request, one fused dispatch per bucket
    assert [inner_front[k] for k in FRONT_STAGES] == [2, 2, 1]
    # one fence, one encode loop holding each tensor's fetch and polish
    assert [inner_back[k] for k in BACK_STAGES] == [1, 2, 2, 1]
    (encode,) = [sp for sp in _inside(spans, back) if sp[0] == "ffcz.encode"]
    assert Counter(sp[0] for sp in _inside(spans, encode))["ffcz.polish"] == 2


def test_waits_carry_the_uid_of_each_unit_in_retirement_order(traced):
    waits = sorted((sp for sp in traced["spans"] if sp[0] == "ffcz.wait"), key=lambda sp: sp[2])
    assert [sp[4]["uid"] for sp in waits] == [traced["field"], traced["pencils"][0]]
    fronts = [sp for sp in traced["spans"] if sp[0] == "ffcz.front"]
    assert {sp[1] for sp in waits} == {sp[1] for sp in fronts}  # the scheduler's line
    uids = {sp[4]["uid"] for sp in traced["spans"] if "uid" in sp[4]}
    assert uids == {traced["field"], traced["pencils"][0]} <= set(traced["res"])


def test_polish_rounds_are_the_polish_irfftn_calls(traced):
    n = sum(sp[0] == "ffcz.polish.round" for sp in traced["spans"])
    assert traced["rounds"] > 0
    assert n == traced["rounds"]


def test_polish_spans_carry_slabs_and_clip_counts(traced):
    """``ffcz.polish`` says how many slabs its passes ran in (1: a 16^3
    field and a pencil bucket run inline); each ``ffcz.polish.round`` says
    how many components and points it clipped, and a round runs only when
    some component lies outside the f-cube."""
    spans = traced["spans"]
    polishes = [sp for sp in spans if sp[0] == "ffcz.polish"]
    assert len(polishes) == 3 and all(sp[4] == {"slabs": 1} for sp in polishes)
    back, _inner = _unit(spans, "ffcz.back", traced["field"])
    (polish,) = [sp for sp in _inside(spans, back) if sp[0] == "ffcz.polish"]
    rounds = sorted(
        (sp for sp in _inside(spans, polish) if sp[0] == "ffcz.polish.round"), key=lambda sp: sp[2]
    )
    assert rounds and rounds[0][4]["f_clipped"] > 0
    for sp in spans:
        if sp[0] == "ffcz.polish.round":
            assert set(sp[4]) == {"f_clipped", "s_clipped"}
            assert sp[4]["f_clipped"] > 0 and sp[4]["s_clipped"] >= 0


def test_quantize_spans_inside_encode(traced):
    """The field's ENCODE quantizes two streams, each in one ``ffcz.quantize``
    that says how many components it holds, how many are nonzero and how
    many of those are active (their codes are not all zero)."""
    from repro.core.ffcz import FFCzBlob

    spans, uid = traced["spans"], traced["field"]
    back, _inner = _unit(spans, "ffcz.back", uid)
    (encode,) = [sp for sp in _inside(spans, back) if sp[0] == "ffcz.encode"]
    quant = sorted((sp for sp in _inside(spans, encode) if sp[0] == "ffcz.quantize"), key=lambda sp: sp[2])
    assert [sp[4]["n"] for sp in quant] == [16**3, 16 * 16 * 9]  # spatial, then half spectrum
    blob = FFCzBlob.from_bytes(traced["res"][uid].payload)
    assert [sp[4]["active"] for sp in quant] == [blob.spat_edits.n_active, blob.freq_edits.n_active]
    for sp in quant:
        assert set(sp[4]) == {"n", "nonzero", "active"}
        assert 0 < sp[4]["active"] <= sp[4]["nonzero"] <= sp[4]["n"]


def test_base_pass_keeps_the_reconstruction(traced):
    """szlike hands back the reconstruction it quantised against: every
    request's ``ffcz.base`` says so, and each (one field, two pencil
    tensors) counts ``base_recon_kept`` once."""
    bases = [sp for sp in traced["spans"] if sp[0] == "ffcz.base"]
    assert len(bases) == 3 and all(sp[4] == {"recon": "kept"} for sp in bases)
    assert traced["counters"]["base_recon_kept"] == 3
    assert traced["counters"]["base_recon_decoded"] == 0


def test_deflate_spans_count_their_chunks(traced, tmp_path):
    """Each pencil's base codec deflates its small code stream in one chunk
    (``zlib.compress``); a 128^3 field's ~4 MiB stream is deflated in
    chunks, and ``ffcz.deflate`` says how many and over how many bytes."""
    from repro.coding import lossless
    from repro.data.fields import make_field

    spans, lead = traced["spans"], traced["pencils"][0]
    front, _inner = _unit(spans, "ffcz.front", lead)
    bases = [sp for sp in _inside(spans, front) if sp[0] == "ffcz.base"]
    assert len(bases) == 2
    for base in bases:
        (deflate,) = [sp for sp in _inside(spans, base) if sp[0] == "ffcz.deflate"]
        assert deflate[4]["chunks"] == 1 and 0 < deflate[4]["bytes"] <= lossless.DEFLATE_CHUNK_BYTES

    x = make_field("nyx-like-128")
    with jax.profiler.trace(str(tmp_path)):
        get_compressor("szlike").compress(x, 1e-3 * float(np.ptp(x)))
    (deflate,) = [sp for sp in _read_spans(tmp_path) if sp[0] == "ffcz.deflate"]
    stats = deflate[4]
    assert stats["chunks"] == -(-stats["bytes"] // lossless.DEFLATE_CHUNK_BYTES) > 1


# -- wait counters on a clock only the stages advance ----------------------


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class _CostlyBase:
    """The szlike codec, with each compression charged 1 s of FRONT."""

    def __init__(self, clock):
        self._inner = get_compressor("szlike")
        self._clock = clock

    def compress(self, x, E):
        self._clock.now += 1.0
        return self._inner.compress(x, E)

    def decompress(self, blob):
        return self._inner.decompress(blob)


class _Deferred:
    """An executor that runs a submitted BACK half when its result is
    awaited, on the awaiting thread: the order of stages is fixed."""

    class _Future:
        def __init__(self, fn, args):
            self._fn, self._args = fn, args

        def result(self):
            return self._fn(*self._args)

        def cancel(self):
            return True

    def submit(self, fn, *args):
        return self._Future(fn, args)

    def shutdown(self, wait=True):
        pass


def _timed_service(depth, monkeypatch):
    clock = _Clock()
    engine = CorrectionEngine(backend="batched")
    real_encode = engine.encode_field

    def encode_field(*a, **k):
        clock.now += 10.0
        return real_encode(*a, **k)

    monkeypatch.setattr(engine, "encode_field", encode_field)
    svc = FFCzService(
        _CostlyBase(clock),
        engine=engine,
        config=ServiceConfig(pipeline_depth=depth, deadline_s=1e9),
        clock=clock,
        sleep=lambda s: None,
    )
    svc._worker = _Deferred()
    rng = np.random.default_rng(3)
    uids = [
        svc.submit_compress(rng.standard_normal((8, 8)).astype(np.float32), _cfg())
        for _ in range(3)
    ]
    return svc, uids


def _waits(svc, uids):
    res = svc.drain()
    svc.close()
    assert all(res[u].ok for u in uids)
    return [(res[u].stats.queue_s, res[u].stats.handoff_s) for u in uids]


def test_queue_and_handoff_over_depth_two(monkeypatch):
    """All three admitted at 0.  step 1: FRONT A [0, 1], FRONT B [1, 2],
    BACK A from 2 to 12.  step 2: FRONT C [12, 13], BACK B from 13.
    step 3: BACK C from 23."""
    svc, uids = _timed_service(2, monkeypatch)
    assert _waits(svc, uids) == [(0.0, 1.0), (1.0, 11.0), (12.0, 10.0)]


def test_a_codec_without_a_reconstruction_decodes(monkeypatch):
    """A base codec with only compress and decompress takes the decode
    path: each of the three fields counts ``base_recon_decoded`` once."""
    svc, uids = _timed_service(2, monkeypatch)
    _waits(svc, uids)
    assert svc.counters["base_recon_decoded"] == 3
    assert svc.counters["base_recon_kept"] == 0


def test_no_handoff_at_depth_one(monkeypatch):
    """Inline BACK starts where FRONT ends: each field takes 11 s, and the
    next one waits for it."""
    svc, uids = _timed_service(1, monkeypatch)
    assert _waits(svc, uids) == [(0.0, 0.0), (11.0, 0.0), (22.0, 0.0)]
