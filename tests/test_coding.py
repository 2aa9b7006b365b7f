"""Unit + property tests for the entropy-coding layer."""

import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-stubs (requirements-dev.txt)

from repro.coding import (
    huffman_decode,
    huffman_encode,
    lossless,
    lossless_compress,
    lossless_decompress,
    pack_bits,
    unpack_bits,
)
from repro.coding.quantize import bound_shrink, dequantize_uniform, quantize_uniform


class TestBitpack:
    def test_roundtrip(self, rng):
        flags = rng.random(1000) < 0.1
        assert (unpack_bits(pack_bits(flags), 1000) == flags).all()

    def test_empty(self):
        assert unpack_bits(pack_bits(np.zeros(0, bool)), 0).size == 0

    @given(st.lists(st.booleans(), max_size=200))
    @settings(max_examples=50, deadline=None)
    def test_roundtrip_property(self, bits):
        arr = np.array(bits, dtype=bool)
        assert (unpack_bits(pack_bits(arr), len(bits)) == arr).all()


class TestHuffman:
    def test_roundtrip_uniform(self, rng):
        s = rng.integers(-100, 100, 5000)
        assert (huffman_decode(huffman_encode(s)) == s).all()

    def test_roundtrip_skewed(self, rng):
        s = np.rint(rng.standard_normal(5000) * 2).astype(np.int64)
        enc = huffman_encode(s)
        assert (huffman_decode(enc) == s).all()
        # skewed stream must compress below 8 bytes/sym baseline
        assert len(enc) < s.size * 8

    def test_single_symbol(self):
        s = np.zeros(100, dtype=np.int64)
        assert (huffman_decode(huffman_encode(s)) == s).all()

    def test_empty(self):
        assert huffman_decode(huffman_encode(np.zeros(0))).size == 0

    @given(st.lists(st.integers(-(2**40), 2**40), max_size=300))
    @settings(max_examples=50, deadline=None)
    def test_roundtrip_property(self, vals):
        s = np.array(vals, dtype=np.int64)
        assert (huffman_decode(huffman_encode(s)) == s).all()


def _decode_walk_reference(data: bytes) -> np.ndarray:
    """The pre-vectorization per-symbol LUT walk (ISSUE 5 regression oracle)."""
    import struct

    from repro.coding.huffman import _canonical_codes

    (n_alpha,) = struct.unpack_from("<I", data, 0)
    off = 4
    if n_alpha == 0:
        return np.zeros(0, dtype=np.int64)
    alphabet = np.frombuffer(data, dtype="<i8", count=n_alpha, offset=off).copy()
    off += 8 * n_alpha
    lengths = np.frombuffer(data, dtype=np.uint8, count=n_alpha, offset=off).copy()
    off += n_alpha
    n_syms, n_bits = struct.unpack_from("<QQ", data, off)
    off += 16
    bits = np.unpackbits(np.frombuffer(data, dtype=np.uint8, offset=off), count=n_bits)
    codes = _canonical_codes(lengths)
    max_len = int(lengths.max())
    table_sym = np.zeros(1 << max_len, dtype=np.int64)
    table_len = np.zeros(1 << max_len, dtype=np.int64)
    for sym in range(n_alpha):
        ln = int(lengths[sym])
        base = int(codes[sym]) << (max_len - ln)
        table_sym[base : base + (1 << (max_len - ln))] = sym
        table_len[base : base + (1 << (max_len - ln))] = ln
    padded = np.concatenate([bits, np.zeros(max_len, dtype=np.uint8)])
    weights = (1 << np.arange(max_len - 1, -1, -1)).astype(np.int64)
    out = np.empty(n_syms, dtype=np.int64)
    pos = 0
    for i in range(int(n_syms)):
        window = int(padded[pos : pos + max_len] @ weights)
        out[i] = table_sym[window]
        pos += int(table_len[window])
    return alphabet[out]


def _heap_code_lengths(freqs):
    """Code lengths by the heap merge: pop the two least ``(freq, tiebreak)``
    entries (a leaf's tiebreak is its index, an internal node's its creation
    order after every leaf) and deepen every symbol under both."""
    import heapq

    n = len(freqs)
    if n == 1:
        return np.array([1], dtype=np.uint8)
    heap = [(int(f), i, [i]) for i, f in enumerate(freqs)]
    heapq.heapify(heap)
    lengths = np.zeros(n, dtype=np.int64)
    tiebreak = n
    while len(heap) > 1:
        fa, _, sa = heapq.heappop(heap)
        fb, _, sb = heapq.heappop(heap)
        lengths[sa + sb] += 1
        heapq.heappush(heap, (fa + fb, tiebreak, sa + sb))
        tiebreak += 1
    return lengths.astype(np.uint8)


def _sequential_canonical_codes(lengths):
    """Canonical codes by the rank-order walk: each code is the one before
    plus one, shifted left by the growth in length."""
    order = np.lexsort((np.arange(len(lengths)), lengths))
    codes = np.zeros(len(lengths), dtype=np.uint64)
    code, prev = 0, int(lengths[order[0]])
    for rank, sym in enumerate(order):
        ln = int(lengths[sym])
        if rank:
            code = (code + 1) << (ln - prev)
        codes[sym], prev = code, ln
    return codes


COUNT_TABLES = {
    "random": lambda rng: rng.integers(1, 5000, 700),
    "tied": lambda rng: rng.integers(1, 4, 900),
    "all-equal": lambda rng: np.full(64, 7),
    "skewed": lambda rng: np.concatenate([[10**9], rng.integers(1, 3, 300), [10**6, 10**3]]),
    "geometric": lambda rng: 2 ** np.arange(40),
    "one-symbol": lambda rng: np.array([5]),
    "two-symbol": lambda rng: np.array([3, 3]),
    "two-symbol-uneven": lambda rng: np.array([1, 10**6]),
    "large-alphabet": lambda rng: np.concatenate(
        [rng.integers(1, 3, 60_000), (rng.pareto(0.6, 2_000) * 50 + 1).astype(np.int64)]
    ),
}


class TestLinearCodeLengths:
    """The two-queue code lengths and the canonical codes equal the heap
    merge's and the rank-order walk's, so the encoded bytes are unchanged."""

    @pytest.mark.parametrize("table", sorted(COUNT_TABLES))
    def test_lengths_and_codes_match_the_heap(self, table, rng):
        from repro.coding.huffman import _canonical_codes, _code_lengths

        counts = np.asarray(COUNT_TABLES[table](rng), dtype=np.int64)
        lengths = _code_lengths(counts)
        assert lengths.dtype == np.uint8
        assert np.array_equal(lengths, _heap_code_lengths(counts))
        assert np.array_equal(_canonical_codes(lengths), _sequential_canonical_codes(lengths))

    @given(st.lists(st.integers(1, 50), min_size=1, max_size=120))
    @settings(max_examples=60, deadline=None)
    def test_lengths_match_the_heap_property(self, counts):
        from repro.coding.huffman import _canonical_codes, _code_lengths

        counts = np.array(counts, dtype=np.int64)
        lengths = _code_lengths(counts)
        assert np.array_equal(lengths, _heap_code_lengths(counts))
        assert np.array_equal(_canonical_codes(lengths), _sequential_canonical_codes(lengths))

    def test_codes_of_any_length_table(self, rng):
        """The decoder builds codes from lengths it reads, any table at all."""
        from repro.coding.huffman import _canonical_codes

        for n in (1, 2, 17, 300):
            lengths = rng.integers(0, 20, n).astype(np.uint8)
            assert np.array_equal(_canonical_codes(lengths), _sequential_canonical_codes(lengths))


class TestHuffmanVectorizedDecode:
    """ISSUE 5 satellite: the decode LUT walk is numpy-vectorized
    (windowed u32 reads + pointer-doubling chain) and byte-exact."""

    @pytest.mark.parametrize(
        "make",
        [
            lambda rng: rng.geometric(0.3, 20000) - 1,
            lambda rng: rng.integers(-5, 6, 20000),
            lambda rng: np.where(rng.random(20000) < 0.97, 0, rng.integers(-999, 999, 20000)),
            lambda rng: rng.integers(0, 5000, 20000),  # wide alphabet, long codes
            lambda rng: np.array([42]),
            lambda rng: np.zeros(7, dtype=np.int64),  # single-symbol alphabet
        ],
    )
    def test_matches_reference_walk(self, make, rng):
        s = np.asarray(make(rng), dtype=np.int64)
        enc = huffman_encode(s)
        got = huffman_decode(enc)
        assert np.array_equal(got, s)
        assert np.array_equal(got, _decode_walk_reference(enc))

    def test_chunked_decode_crosses_boundaries(self, rng, monkeypatch):
        """The decoder's temporaries are bounded by DECODE_CHUNK_BITS; a
        tiny odd chunk forces many boundary crossings (codes straddling the
        chunk edge seed the next chunk with their exact start bit)."""
        import repro.coding.huffman as hm

        s = np.where(rng.random(20000) < 0.9, 0, rng.integers(-500, 500, 20000))
        enc = huffman_encode(s)
        want = huffman_decode(enc)
        for chunk in (1, 7, 257):
            monkeypatch.setattr(hm, "DECODE_CHUNK_BITS", chunk)
            assert np.array_equal(huffman_decode(enc), want)

    def test_truncated_stream_raises(self, rng):
        """The vectorized path keeps the old unpackbits length guard: a
        truncated payload fails loudly instead of decoding missing bits as
        zeros."""
        s = rng.integers(-50, 50, 5000)
        enc = huffman_encode(s)
        for cut in (1, 3, 16):
            with pytest.raises(ValueError, match="[Tt]runcated"):
                huffman_decode(enc[:-cut])

    def test_faster_than_reference_walk(self, rng):
        """Regression-timed: the vectorized walk must beat the per-symbol
        Python loop it replaced (>10x in practice; assert 2x to stay robust
        to CI noise)."""
        import time

        s = rng.geometric(0.25, 200000) - 1
        enc = huffman_encode(s)
        huffman_decode(enc)  # warm caches / allocator
        t0 = time.perf_counter()
        got = huffman_decode(enc)
        t_vec = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = _decode_walk_reference(enc)
        t_ref = time.perf_counter() - t0
        assert np.array_equal(got, want)
        assert t_vec < t_ref / 2, f"vectorized {t_vec:.3f}s vs loop {t_ref:.3f}s"


class TestLossless:
    @pytest.mark.parametrize("codec", ["huffman+zlib", "zlib"])
    def test_roundtrip(self, codec, rng):
        s = rng.integers(-1000, 1000, 3000)
        assert (lossless_decompress(lossless_compress(s, codec=codec)) == s).all()

    def test_bad_codec(self):
        with pytest.raises(ValueError):
            lossless_compress(np.zeros(3), codec="nope")


CHUNK = lossless.DEFLATE_CHUNK_BYTES
# streams the serial coder wrote: arange(-3, 4) as "zlib", [5, 5, 5, -7, 300]
# as "huffman+zlib"
OLD_ZLIB = bytes.fromhex("4652789c4b62678080bffffe333032310300187d036a")
OLD_HUFF = bytes.fromhex("4648789c63666060f8f91f0258192040871142333132c184d8a1b41800492b084f")


def _int8_symbols(body_len, rng, period=20_000):
    """Symbols whose ``codec="zlib"`` body (9-byte header, then int8) is
    ``body_len`` bytes: random bytes repeating every ``period``, so past the
    first period every match reaches back across chunk boundaries."""
    block = rng.integers(-128, 128, period)
    return np.resize(block, body_len - 9)


def _body(s):
    return struct.pack("<cQ", b"b", s.size) + s.astype(np.int8).tobytes()


class TestChunkedDeflate:
    """Bodies longer than one chunk are deflated in chunks on a pool and
    joined into one zlib stream; shorter ones keep ``zlib.compress``."""

    @pytest.mark.parametrize(
        "body_len, chunk",
        [
            (CHUNK, CHUNK),  # exactly one chunk: serial
            (CHUNK + 1, CHUNK),  # one byte over: a 1-byte last chunk
            (3 * CHUNK + 4321, CHUNK),  # several chunks
            (50_000, 1000),  # chunks far shorter than the 32 KiB dictionary
        ],
        ids=["one_chunk", "one_byte_over", "several", "tiny_chunks"],
    )
    def test_roundtrip_one_zlib_stream(self, body_len, chunk, rng, monkeypatch):
        monkeypatch.setattr(lossless, "DEFLATE_CHUNK_BYTES", chunk)
        s = _int8_symbols(body_len, rng)
        body = _body(s)
        assert len(body) == body_len
        out = lossless_compress(s, codec="zlib")
        serial = b"FR" + zlib.compress(body, 6)
        assert np.array_equal(lossless_decompress(out), s)
        assert zlib.decompress(out[2:]) == body
        assert (out == serial) == (body_len <= chunk)
        # each chunk is primed with the window before it, so the matches
        # that span a boundary cost what they cost in the serial stream; a
        # chunk adds its flush marker and block headers
        assert len(out) <= len(serial) * 1.002 + 16 * -(-body_len // chunk)

    @pytest.mark.parametrize("chunk", [CHUNK, 4096])
    def test_bytes_do_not_depend_on_the_pool(self, chunk, rng, monkeypatch):
        monkeypatch.setattr(lossless, "DEFLATE_CHUNK_BYTES", chunk)
        s = rng.integers(-300, 300, 3 * CHUNK // 2)  # int16, ~3 MiB of body
        outs = []
        for workers in (1, 4):
            with ThreadPoolExecutor(workers) as pool:
                monkeypatch.setattr(lossless, "_POOL", pool)
                outs.append(lossless_compress(s, codec="zlib"))
        assert outs[0] == outs[1]
        assert np.array_equal(lossless_decompress(outs[0]), s)

    @pytest.mark.parametrize("n", [0, 1, 3000, CHUNK // 2 - 9])
    def test_below_two_chunks_is_the_serial_stream(self, n, rng):
        s = rng.integers(-1000, 1000, n)
        s[:1] = 999  # an int16 body; an empty stream is an int8 one
        body = struct.pack("<cQ", b"h" if n else b"b", n) + s.astype(np.int16).tobytes()
        assert lossless_compress(s, codec="zlib") == b"FR" + zlib.compress(body, 6)
        assert lossless_compress(s) == b"FH" + zlib.compress(huffman_encode(s), 6)

    def test_streams_written_before_still_decode(self, rng):
        assert np.array_equal(lossless_decompress(OLD_ZLIB), np.arange(-3, 4))
        assert np.array_equal(lossless_decompress(OLD_HUFF), [5, 5, 5, -7, 300])
        assert lossless_compress(np.arange(-3, 4), codec="zlib") == OLD_ZLIB
        assert lossless_compress(np.array([5, 5, 5, -7, 300])) == OLD_HUFF
        # a multi-chunk body as the serial coder deflated it
        s = _int8_symbols(2 * CHUNK + 99, rng)
        assert np.array_equal(lossless_decompress(b"FR" + zlib.compress(_body(s), 6)), s)


class TestQuantize:
    @given(
        st.floats(1e-6, 1e6),
        st.integers(4, 24),
        st.lists(st.floats(-100, 100), min_size=1, max_size=100),
    )
    @settings(max_examples=100, deadline=None)
    def test_error_bound(self, bound, m, vals):
        v = np.array(vals)
        codes = quantize_uniform(v, bound, m)
        back = dequantize_uniform(codes, bound, m)
        # round-to-nearest: |err| <= step/2 = bound * 2^-m, plus the float64
        # resolution of v/step itself (binds when |v|/bound ~ 2^52-ish —
        # found by hypothesis at bound=1e-6, m=23, v=33.7)
        tol = bound * 2.0 ** (-m) * (1 + 1e-12) + 8 * np.finfo(np.float64).eps * np.abs(v)
        assert np.all(np.abs(back - v) <= tol)

    def test_pointwise_bound_array(self, rng):
        v = rng.standard_normal(64)
        b = np.abs(rng.standard_normal(64)) + 0.1
        back = dequantize_uniform(quantize_uniform(v, b, 8), b, 8)
        assert np.all(np.abs(back - v) <= b * 2.0**-8 * (1 + 1e-12))

    def test_zero_bound_is_zero_codes(self):
        codes = quantize_uniform(np.ones(4), 0.0, 16)
        assert (codes == 0).all()

    def test_bound_shrink(self):
        assert bound_shrink(1.0, 16) == 1.0 - 2.0**-16
