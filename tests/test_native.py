"""Native wire codec (C++ via ctypes, numpy fallback) — the in-repo
replacement for the reference's blosc binding (``mpi_comms.py:18-30``).
Round-trips, cross-checks native vs fallback, and compression-ratio
sanity on float and sparse data."""

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.utils import native


def test_native_lib_builds():
    # the environment ships g++; the build must succeed here
    assert native.get_lib() is not None


def test_native_build_keyed_on_content_not_mtime(tmp_path, monkeypatch,
                                                capsys):
    """Staleness is a hash of source + flags recorded beside the .so: an
    old file time does not rebuild, a changed source does, and a failed
    build says so on stderr once (then stays latched)."""
    import os

    (tmp_path / "native").mkdir()
    src = tmp_path / "native" / "probe.cpp"
    src.write_text('extern "C" int probe() { return 1; }\n')
    monkeypatch.setattr(native, "_repo_root", lambda: str(tmp_path))
    monkeypatch.setattr(native, "_BUILD_FAILURES", set())
    so = tmp_path / "native" / "_build" / "libprobe.so"

    assert native.build_and_load("probe.cpp").probe() == 1
    built = so.stat().st_ino
    os.utime(so, (1, 1))  # older than the source: mtimes say "stale"
    assert native.build_and_load("probe.cpp").probe() == 1
    assert so.stat().st_ino == built  # not rebuilt

    src.write_text('extern "C" int probe() { return 2; }\n')
    native.build_and_load("probe.cpp")
    assert so.stat().st_ino != built  # the content changed: rebuilt

    src.write_text("this is not C++\n")
    assert native.build_and_load("probe.cpp") is None
    assert native.build_and_load("probe.cpp") is None
    err = capsys.readouterr().err
    assert err.count("libprobe.so unavailable") == 1


def test_shuffle_roundtrip_native_and_fallback():
    rng = np.random.RandomState(0)
    data = rng.bytes(4 * 100)
    arr = np.frombuffer(data, np.uint8)
    shuf = native.shuffle(arr, 4)
    out = native.unshuffle(shuf, 4)
    np.testing.assert_array_equal(out, arr)
    # fallback path computes the identical permutation
    np.testing.assert_array_equal(
        shuf, arr.reshape(-1, 4).T.reshape(-1)
    )


@pytest.mark.parametrize("data", [
    b"",
    b"\x00" * 1000,
    b"hello world" * 50,
    bytes(range(256)) * 4,
    b"\x00\x01" * 500,
])
def test_rle0_roundtrip(data):
    arr = np.frombuffer(data, np.uint8)
    enc = native.rle0_encode(arr)
    dec = native.rle0_decode(enc, arr.size)
    np.testing.assert_array_equal(dec, arr)


def test_rle0_native_matches_numpy_fallback():
    rng = np.random.RandomState(1)
    raw = rng.randint(0, 4, 2000).astype(np.uint8)  # lots of zeros
    raw[rng.rand(2000) < 0.7] = 0
    native_enc = native.rle0_encode(raw)
    np_enc = native._rle0_encode_np(raw)
    assert native_enc == np_enc
    np.testing.assert_array_equal(
        native._rle0_decode_np(native_enc, raw.size),
        native.rle0_decode(np_enc, raw.size),
    )


def test_compress_structured_floats():
    # integer-valued float32 (quantized grads, step counters, masks):
    # shuffle exposes the constant low-mantissa bytes as zero runs
    rng = np.random.RandomState(2)
    data = rng.randint(0, 100, 4096).astype(np.float32).tobytes()
    blob = native.compress(data, elem_size=4)
    assert len(blob) < len(data) * 0.55  # ~2x: half the shuffled bytes are 0
    assert native.decompress(blob) == data


def test_compress_sparse_payload():
    # top-k style: 99% zeros -> big ratio
    rng = np.random.RandomState(3)
    arr = np.zeros(10000, np.float32)
    idx = rng.choice(10000, 100, replace=False)
    arr[idx] = rng.randn(100)
    data = arr.tobytes()
    blob = native.compress(data, elem_size=4)
    assert len(blob) < len(data) // 10
    assert native.decompress(blob) == data


def test_compress_incompressible_stores():
    rng = np.random.RandomState(4)
    data = rng.bytes(1024)
    blob = native.compress(data, elem_size=1)
    assert len(blob) <= len(data) + 18  # header only
    assert native.decompress(blob) == data


def test_decompress_garbage_raises():
    with pytest.raises(ValueError):
        native.decompress(b"XXXX" + b"\x00" * 20)


def test_corrupt_payload_fails_crc():
    data = np.arange(100, dtype=np.float32).tobytes()
    blob = native.compress(data, elem_size=4)
    bad = blob[:20] + bytes([blob[20] ^ 0xFF]) + blob[21:]
    with pytest.raises(ValueError):
        native.decompress(bad)
