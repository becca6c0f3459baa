"""Native C++ library (string packing, Spark-exact hash oracle, xxhash64
frame checksum) + the Pallas murmur3 kernel in interpret mode.  The C++
hashes serve as an INDEPENDENT oracle for the device kernels — three
implementations (C++, jnp, Pallas) must agree bit-for-bit."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import native as N
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.pallas_kernels import murmur3_long_pallas


def test_native_library_builds():
    assert N.available(), "g++ toolchain present but native build failed"


def test_pack_unpack_strings_roundtrip(rng):
    strs = ["", "a", "hello world", "x" * 63, "é中ñ", "tab\there"] * 50
    flat = b"".join(s.encode() for s in strs)
    lens = [len(s.encode()) for s in strs]
    offsets = np.zeros(len(strs) + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    width = 64
    cap = 512
    packed = N.pack_strings(np.frombuffer(flat, np.uint8), offsets, width,
                            cap)
    assert packed is not None
    matrix, lens_out = packed
    assert matrix.shape == (cap, width)
    assert list(lens_out[:len(strs)]) == lens
    flat2, offs2 = N.unpack_strings(matrix, lens_out, len(strs))
    assert bytes(flat2) == flat
    assert list(offs2) == list(offsets)


def test_native_pack_matches_python_path(rng):
    """arrow_to_device must produce identical matrices with and without
    the native fast path."""
    from spark_rapids_tpu.columnar import convert as C
    strs = [None, "", "abc", "x" * 30, "é中", "end"] * 20
    arr = pa.array(strs, type=pa.string())
    native = C._strings_to_matrix(arr, 256)
    lib = N._lib
    try:
        N._lib = None  # force the numpy fallback
        fallback = C._strings_to_matrix(arr, 256)
    finally:
        N._lib = lib
    assert np.array_equal(native[0], fallback[0])
    assert np.array_equal(native[1], fallback[1])


def test_cpp_murmur3_matches_device_kernel(rng):
    vals = np.concatenate([
        rng.integers(-(1 << 62), 1 << 62, 1000),
        np.array([0, 1, -1, (1 << 63) - 1, -(1 << 63), 42])]).astype(np.int64)
    cpp = N.murmur3_i64(vals, 42)
    assert cpp is not None
    dev = np.asarray(H.murmur3_long(np, vals, np.uint32(42)))
    assert np.array_equal(cpp, dev), "C++ oracle disagrees with jnp kernel"


def test_cpp_murmur3_i32_matches(rng):
    vals = rng.integers(-(1 << 31), 1 << 31, 500).astype(np.int32)
    cpp = N.murmur3_i32(vals, 42)
    dev = np.asarray(H.murmur3_int(np, vals, np.uint32(42)))
    assert np.array_equal(cpp, dev)


def test_pallas_murmur3_interpret_matches(rng):
    import jax.numpy as jnp
    vals = rng.integers(-(1 << 62), 1 << 62, 3000).astype(np.int64)
    pal = np.asarray(murmur3_long_pallas(jnp.asarray(vals), 42,
                                         interpret=True))
    ref = np.asarray(H.murmur3_long(jnp, jnp.asarray(vals), jnp.uint32(42)))
    cpp = N.murmur3_i64(vals, 42)
    assert np.array_equal(pal, ref)
    assert np.array_equal(pal, cpp)


def test_xxhash64_native_matches_python():
    for data in (b"", b"a", b"hello", b"x" * 31, b"y" * 32, b"z" * 100,
                 bytes(range(256)) * 5):
        lib = N._lib if N.available() else None
        native = N.xxhash64_bytes(data, seed=7)
        py = N._xxhash64_py(data, 7)
        assert native == py, data[:10]


def test_serializer_checksum_detects_corruption():
    from spark_rapids_tpu.columnar.convert import arrow_to_device
    from spark_rapids_tpu.shuffle.serializer import (deserialize_batch,
                                                     serialize_batch)
    t = pa.table({"x": list(range(100)), "s": [f"v{i}" for i in range(100)]})
    frame = serialize_batch(arrow_to_device(t))
    # round-trip intact
    out = deserialize_batch(frame)
    assert out.num_rows_int == 100
    # flip a payload byte -> loud failure
    bad = bytearray(frame)
    bad[len(bad) // 2] ^= 0xFF
    with pytest.raises(ValueError, match="checksum"):
        deserialize_batch(bytes(bad))


def test_pallas_seg_sum_interpret_matches(rng):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.pallas_kernels import seg_sum_f32_pallas
    n, s, out = 10_000, 4, 37
    vals = rng.random((s, n)).astype(np.float32)
    rank = rng.integers(0, out + 5, n).astype(np.int32)  # incl. dead ranks
    got = np.asarray(seg_sum_f32_pallas(jnp.asarray(vals),
                                        jnp.asarray(rank), out,
                                        interpret=True))
    exp = np.zeros((s, out), np.float64)
    live = rank < out
    for i in range(s):
        np.add.at(exp[i], rank[live], vals[i][live].astype(np.float64))
    assert got.shape == (s, out)
    assert np.allclose(got, exp, rtol=1e-5)


def test_pallas_seg_sum_single_slot_and_tiny(rng):
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.pallas_kernels import seg_sum_f32_pallas
    vals = np.asarray([[1.0, 2.0, 4.0]], np.float32)
    rank = np.asarray([0, 1, 0], np.int32)
    got = np.asarray(seg_sum_f32_pallas(jnp.asarray(vals),
                                        jnp.asarray(rank), 2,
                                        interpret=True))
    assert np.allclose(got, [[5.0, 2.0]])


# --------------------------------------------------------------------------
# built from what git commits; the compile cache where it is told to be
# --------------------------------------------------------------------------

def test_loader_builds_both_libraries_from_source(tmp_path, monkeypatch):
    """A fresh clone holds only the .cpp: both libraries build from it,
    next to the source, under the content-tagged name."""
    import os
    import shutil

    from spark_rapids_tpu.native import _loader
    repo_native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    for src in ("srt_native.cpp", "srt_transport.cpp"):
        shutil.copy(os.path.join(repo_native, src), tmp_path / src)
    monkeypatch.setattr(_loader, "_candidate_dirs", lambda: [str(tmp_path)])
    monkeypatch.setattr(_loader, "LOADED", {})
    for lib, src, flags in (("libsrt_native.so", "srt_native.cpp", ()),
                            ("libsrt_transport.so", "srt_transport.cpp",
                             ("-pthread",))):
        so = _loader.find_or_build(lib, src, extra_flags=flags)
        tag = _loader._src_tag(str(tmp_path / src))
        assert so == str(tmp_path / f"{lib[:-3]}-{tag}.so")
        assert os.path.exists(so)
        assert _loader.LOADED[lib] == {"path": so, "error": None}
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_loader_records_why_a_library_is_missing(tmp_path, monkeypatch):
    from spark_rapids_tpu.native import _loader
    (tmp_path / "broken.cpp").write_text("this is not C++\n")
    monkeypatch.setattr(_loader, "_candidate_dirs", lambda: [str(tmp_path)])
    monkeypatch.setattr(_loader, "LOADED", {})
    assert _loader.load("libbroken.so", "broken.cpp") is None
    assert "building" in _loader.LOADED["libbroken.so"]["error"]
    assert _loader.load("libabsent.so", "absent.cpp") is None
    assert "not found" in _loader.LOADED["libabsent.so"]["error"]


@pytest.mark.parametrize("given", [None, "outside-cache"])
def test_compile_cache_placement(tmp_path, given):
    """JAX_COMPILATION_CACHE_DIR set: the package touches no cache setting;
    unset: ``<checkout>/.jax_cache``, flat.  (A child process: the choice
    is made once, at import; importing initializes no backend.)"""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = repo
    if given:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / given)
    out = subprocess.run(
        [sys.executable, "-c",
         "import spark_rapids_tpu as s; print(s.compile_cache_dir())"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    want = str(tmp_path / given) if given else os.path.join(repo,
                                                            ".jax_cache")
    assert out.stdout.strip().splitlines()[-1] == want
    if given:   # the package made no directory of its own there either
        assert not os.path.exists(want)
