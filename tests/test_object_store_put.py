"""The object store's put: one pass per byte. Each chunk of a leaf is
copied into the shadow region and folded into the leaf's CRC from the
source, with no staging copy of the leaf."""
import tracemalloc
import zlib

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.core import object_store
from repro.core.object_store import PMemObjectStore
from repro.core.pmem import PMemPool

CHUNK = 4096

SIZES = {
    "empty": lambda it: (0,),
    "scalar": lambda it: (),
    "under_chunk": lambda it: (CHUNK // 2 // it,),
    "one_chunk": lambda it: (CHUNK // it,),
    "chunk_plus_one": lambda it: (CHUNK // it + 1,),
    "two_and_a_half": lambda it: (5 * CHUNK // 2 // it,),
}
DTYPES = (np.float32, ml_dtypes.bfloat16, np.int8)


def _leaf(shape, dtype, seed=0):
    r = np.random.RandomState(seed)
    if np.dtype(dtype).kind == "i":
        return r.randint(-128, 128, shape).astype(dtype)
    return np.asarray(r.standard_normal(shape)).astype(dtype)


def _store(tmp_path):
    return PMemObjectStore(PMemPool(tmp_path, "n0"))


def _crc_of(arr) -> int:
    """The leaf CRC as the store always computed it: over a staged
    ``tobytes()`` copy of the whole leaf."""
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("size", list(SIZES))
def test_put_crc_and_bytes_per_leaf_size(tmp_path, monkeypatch, size,
                                         dtype):
    monkeypatch.setattr(object_store, "DEFAULT_CHUNK_BYTES", CHUNK)
    arr = _leaf(SIZES[size](np.dtype(dtype).itemsize), dtype)
    st = _store(tmp_path)
    man = st.put("obj", {"x": arr, "tail": np.arange(3, dtype=np.int32)})
    ent = man["leaves"]["x"]
    assert ent["crc"] == _crc_of(arr)
    assert ent["nbytes"] == arr.nbytes and ent["shape"] == list(arr.shape)
    if arr.nbytes == 0:
        assert ent["crc"] == zlib.crc32(b"") == 0
    out = st.get("obj", verify=True)
    assert out["x"].dtype == arr.dtype and out["x"].shape == arr.shape
    assert out["x"].tobytes() == arr.tobytes()
    np.testing.assert_array_equal(out["tail"], np.arange(3))


def test_put_manifest_matches_the_staged_layout(tmp_path, monkeypatch):
    """Offsets and CRCs are those of the old put, which laid the leaves
    end to end in flattened order and CRC'd each leaf's ``tobytes()``:
    chunks that split an element, a transposed (non-contiguous) leaf and
    a jax array change none of it."""
    monkeypatch.setattr(object_store, "DEFAULT_CHUNK_BYTES", 1001)
    r = np.random.RandomState(3)
    tree = {"b": {"w": r.randn(37, 29).astype(np.float32).T,
                  "s": np.float32(2.5)},
            "a": jnp.arange(1200, dtype=jnp.bfloat16),
            "c": r.randint(0, 9, (3, 700)).astype(np.int8),
            "e": np.zeros((0, 4), np.float32)}
    man = _store(tmp_path).put("obj", tree, meta={"step": 1})
    off, want = 0, {}
    for path, arr in object_store._flatten(tree):
        want[path] = {"shape": list(arr.shape), "dtype": str(arr.dtype),
                      "offset": off, "nbytes": arr.nbytes,
                      "crc": _crc_of(arr)}
        off += arr.nbytes
    assert man["leaves"] == want
    assert man["nbytes"] == off


def test_put_makes_no_staging_copy(tmp_path):
    arr = np.arange(32 << 20, dtype=np.uint8)  # 32 MiB, four chunks
    st = _store(tmp_path)
    tracemalloc.start()
    try:
        man = st.put("big", {"x": arr})
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < object_store.DEFAULT_CHUNK_BYTES + (1 << 20), peak
    assert man["leaves"]["x"]["crc"] == _crc_of(arr)
