"""Stream a multi-channel EEG recording through the temporal codec.

    PYTHONPATH=src:. python examples/stream_eeg.py
    PYTHONPATH=src:. python examples/stream_eeg.py --quick   # tiny, CI docs leg

EEG is the forcing scenario for the pencil path (docs/streaming.md): each
frame is ``(channels, samples)`` — 1-D-per-channel x time — so the stream
routes through ``correct_batch`` with one pencil per channel row, not the
whole-field rfftn.  The demo:

1. synthesizes a slowly evolving multi-channel recording (per-channel 1/f
   "pink" EEG character + a drifting shared component — the temporal
   coherence the predictor and the POCS warm start exploit),
2. compresses it with ``TemporalCodec`` (linear predictor, keyframe every 8
   frames, ``warm_start=True``),
3. re-verifies BOTH claimed bounds on every decoded frame — keyframes and
   residual frames alike — against the stream header's (E, Delta),
4. seeks to an arbitrary frame via the FFCS index and checks the
   seek-decode is bitwise identical to the sequential decode,
5. prints per-frame POCS iteration counts (residual frames warm-start from
   the previous frame's edit spectrum; the controlled warm-vs-cold
   iteration check is ``tests/test_temporal.py``'s
   ``test_warm_chain_cuts_pocs_iterations``).
"""

import argparse

import numpy as np

from repro.compressors import get_compressor
from repro.configs.ffcz_fields import FieldConfig
from repro.core.ffcz import FFCzConfig
from repro.core.temporal import TemporalCodec, TemporalConfig, TemporalStream
from repro.data.fields import make_field


def make_eeg_frames(n_frames: int, channels: int, samples: int, seed: int = 0):
    """Coherent synthetic EEG: per-channel pink noise + drifting shared mode."""
    rng = np.random.default_rng(seed)
    chans = np.stack([
        make_field(FieldConfig(f"ch{c}", (samples,), "pink", alpha=1.0, seed=seed + c))
        for c in range(channels)
    ])
    shared = make_field(FieldConfig("shared", (samples,), "pink", alpha=1.0, seed=seed + 999))
    drift = 0.03 * rng.standard_normal((channels, 1)).astype(np.float32)
    frames = []
    for t in range(n_frames):
        wobble = 0.01 * rng.standard_normal((channels, samples)).astype(np.float32)
        frames.append((chans + (t * drift) * shared + wobble).astype(np.float32))
    return frames


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="tiny stream (the CI docs leg)")
    ap.add_argument("--frames", type=int, default=None)
    ap.add_argument("--channels", type=int, default=None)
    ap.add_argument("--samples", type=int, default=None)
    args = ap.parse_args()

    n_frames = args.frames or (6 if args.quick else 24)
    channels = args.channels or (4 if args.quick else 16)
    samples = args.samples or (64 if args.quick else 512)

    frames = make_eeg_frames(n_frames, channels, samples)
    raw_bytes = sum(f.nbytes for f in frames)
    print(f"stream: {n_frames} frames x ({channels} ch, {samples} samples) "
          f"= {raw_bytes/1e3:.1f} kB float32")

    codec = TemporalCodec(
        get_compressor("szlike"),
        FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=300, warm_start=True),
        # block=0 -> one pencil per channel row (the EEG routing)
        stream=TemporalConfig(mode="pencils", predictor="linear", keyframe_interval=8),
    )

    enc = codec.open_stream()
    for f in frames:
        enc.add_frame(f)
    blob = enc.finish()
    print(f"FFCS container: {len(blob)} bytes ({raw_bytes/len(blob):.1f}x)")

    # 3. per-frame dual-bound verification against the stream-level claim
    stream = TemporalStream.from_bytes(blob)
    E0, D0 = stream.E, stream.Delta
    decoded = codec.decompress_stream(blob)
    worst_e = worst_d = 0.0
    for t, (x, xh) in enumerate(zip(frames, decoded)):
        eps = xh.astype(np.float64) - x.astype(np.float64)
        flat = eps.reshape(-1)
        tiles = np.pad(flat, (0, (-flat.size) % stream.block)).reshape(-1, stream.block)
        d = np.fft.rfft(tiles, axis=-1)
        e, dm = np.abs(eps).max(), max(np.abs(d.real).max(), np.abs(d.imag).max())
        worst_e, worst_d = max(worst_e, e), max(worst_d, dm)
        kind = "KEY" if stream.is_keyframe(t) else "res"
        st = enc.frame_stats[t]
        print(f"  frame {t:2d} [{kind}]  pocs_iters={st['iterations']:3d}  "
              f"|eps|={e:.3e}  |dhat|={dm:.3e}")
        assert e <= E0 and dm <= D0, f"frame {t} violated the stream bound"
    print(f"bounds held on every frame: worst |eps|={worst_e:.3e} <= E={E0:.3e}, "
          f"worst |dhat|={worst_d:.3e} <= Delta={D0:.3e}")

    # 4. seek: decode one frame via the index, compare to sequential decode
    t_seek = n_frames - 2
    k = stream.latest_keyframe(t_seek)
    x_seek = codec.decode_frame(blob, t_seek)
    assert np.array_equal(x_seek, decoded[t_seek])
    print(f"seek to frame {t_seek}: decoded {t_seek - k + 1} frames "
          f"(keyframe {k} -> {t_seek}), bitwise == sequential decode")

    # 5. warm start: residual frames vs cold keyframes
    cold = [s["iterations"] for s in enc.frame_stats if s["keyframe"]]
    warm = [s["iterations"] for s in enc.frame_stats if not s["keyframe"]]
    if warm:
        print(f"POCS iterations: keyframes (cold) mean {np.mean(cold):.1f}, "
              f"residuals (warm) mean {np.mean(warm):.1f}")


if __name__ == "__main__":
    main()
