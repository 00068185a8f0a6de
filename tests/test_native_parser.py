"""Native C++ libsvm tokenizer: equivalence with the pure-Python parser."""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data import native
from sagemaker_xgboost_container_tpu.data.readers import parse_libsvm_text
from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc
from tests.reference_fixtures import resources

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="no C++ toolchain"
)

SAMPLE = """\
1 2:1 5:0.5
0 0:3.5 2:-1
2.5:0.25 1:7
# a comment line
-1 qid:3 4:1e-3
"""


def _python_parse(text, num_col=None):
    from sagemaker_xgboost_container_tpu.data import readers

    native._lib = None
    native._tried = True  # force fallback
    try:
        return readers.parse_libsvm_text(text, num_col)
    finally:
        native._tried = False


def test_equivalence_on_sample():
    native._tried = False
    got = parse_libsvm_text(SAMPLE)
    want = _python_parse(SAMPLE)
    native._tried = False
    assert got[0].shape == want[0].shape
    np.testing.assert_allclose(got[0].toarray(), want[0].toarray())
    np.testing.assert_allclose(got[1], want[1])  # labels
    np.testing.assert_allclose(got[2], want[2])  # weights (one line has one)


def test_equivalence_on_abalone():
    with open(resources() + "/abalone/data/train/abalone.train_0") as f:
        text = f.read()
    native._tried = False
    got = parse_libsvm_text(text)
    want = _python_parse(text)
    native._tried = False
    np.testing.assert_allclose(got[0].toarray(), want[0].toarray())
    np.testing.assert_allclose(got[1], want[1])


def test_malformed_raises_usererror():
    native._tried = False
    with pytest.raises(exc.UserError):
        parse_libsvm_text("1 2:abc\n")
    with pytest.raises(exc.UserError):
        parse_libsvm_text("1 nocolon\n")


def test_throughput_not_slower_than_python():
    import time

    rng = np.random.RandomState(0)
    lines = []
    for _ in range(20000):
        idx = np.sort(rng.choice(50, size=10, replace=False))
        lines.append(
            "{:.3f} ".format(rng.randn())
            + " ".join("{}:{:.4f}".format(i, rng.randn()) for i in idx)
        )
    text = "\n".join(lines)

    native._tried = False
    t0 = time.perf_counter()
    parse_libsvm_text(text)
    t_native = time.perf_counter() - t0

    t0 = time.perf_counter()
    _python_parse(text)
    t_python = time.perf_counter() - t0
    native._tried = False
    # the native path should be dramatically faster; assert a loose bound so
    # CI noise can't flake it
    assert t_native < t_python, (t_native, t_python)


def test_multithreaded_parse_matches_single(monkeypatch):
    """The chunked parallel parse (libsvm_count_mt/fill_mt) must produce
    byte-identical CSR pieces to the single-threaded path — newline-aligned
    chunking, prefix-summed row/nnz bases, no indptr boundary overlap.
    (This container has 1 CPU, so the MT path only engages via the
    GRAFT_PARSE_THREADS override; multi-core training hosts take it
    automatically for multi-MB payloads.)"""
    rng = np.random.RandomState(5)
    lines = []
    for i in range(5000):
        idx = np.sort(rng.choice(40, size=rng.randint(1, 12), replace=False))
        feats = " ".join("{}:{:.4f}".format(j, rng.randn()) for j in idx)
        w = ":{:.2f}".format(rng.rand()) if i % 3 == 0 else ""
        lines.append("{:.3f}{} qid:{} {}".format(rng.randn(), w, i // 50, feats))
    blob = ("\n".join(lines) + "\n").encode()

    if not native.native_available():
        pytest.skip("no compiler")
    monkeypatch.setenv("GRAFT_PARSE_THREADS", "1")
    ref = native.parse_libsvm_native(blob)
    monkeypatch.setenv("GRAFT_PARSE_THREADS", "5")  # uneven chunking
    mt = native.parse_libsvm_native(blob)
    (v0, i0, p0), l0, w0, q0 = ref
    (v1, i1, p1), l1, w1, q1 = mt
    np.testing.assert_array_equal(p0, p1)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(v0, v1)
    np.testing.assert_array_equal(l0, l1)
    np.testing.assert_array_equal(w0, w1)
    np.testing.assert_array_equal(q0, q1)

    # malformed input under MT still reports the exact global line number
    bad = blob + b"7 3:oops 4:x\n"
    with pytest.raises(ValueError, match=str(len(lines) + 1)):
        native.parse_libsvm_native(bad)
