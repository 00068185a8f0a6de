"""Data-layer tests against files of the reference's fixture layout, made
from a seed (tests/reference_fixtures.py).

Coverage model: test/unit/test_data_utils.py (content types, format
validation, loaders over test/resources/data/*) — but asserting on DataMatrix
instead of DMatrix.
"""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data import binning, content_types as ct, readers
from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.data.recordio import (
    read_recordio_protobuf,
    write_recordio_protobuf,
)
from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc
from tests.reference_fixtures import resources

FIXTURES = resources() + "/data"
ABALONE = resources() + "/abalone/data"


def test_get_content_type_aliases():
    assert ct.get_content_type(None) == "libsvm"
    assert ct.get_content_type("csv") == "csv"
    assert ct.get_content_type("text/csv") == "csv"
    assert ct.get_content_type("text/csv; label_size=1") == "csv"
    assert ct.get_content_type("text/CSV;charset=utf8") == "csv"
    assert ct.get_content_type("text/x-libsvm") == "libsvm"
    assert ct.get_content_type("application/x-parquet") == "parquet"
    assert ct.get_content_type("application/x-recordio-protobuf") == "recordio-protobuf"


def test_get_content_type_bad_label_size():
    with pytest.raises(exc.UserError, match="label_size"):
        ct.get_content_type("text/csv; label_size=5")


def test_get_content_type_invalid():
    with pytest.raises(exc.UserError, match="not an accepted ContentType"):
        ct.get_content_type("application/json")


def test_load_csv_fixture():
    dm = readers.get_data_matrix(FIXTURES + "/csv/train.csv", "text/csv")
    assert dm.num_row > 0 and dm.num_col == 5
    assert dm.labels.shape == (dm.num_row,)


def test_load_csv_directory_of_files():
    dm = readers.get_data_matrix(FIXTURES + "/csv/csv_files", "csv")
    assert dm.num_row > 0


def test_load_libsvm_fixture():
    dm = readers.get_data_matrix(FIXTURES + "/libsvm/train.libsvm", "text/libsvm")
    assert dm.num_row > 0
    # absent entries are missing (NaN), not zero
    assert np.isnan(dm.features).any()


def test_load_abalone_train_dir():
    dm = readers.get_data_matrix(ABALONE + "/train", "text/libsvm")
    assert dm.num_row > 2000
    assert dm.num_col == 9  # indices 0..8 (libsvm file uses 1..8)
    assert np.isfinite(dm.labels).all()


def test_load_parquet_fixture():
    dm = readers.get_data_matrix(FIXTURES + "/parquet", "application/x-parquet")
    assert dm.num_row > 0 and dm.labels is not None


def test_load_recordio_fixture():
    dm = readers.get_data_matrix(
        FIXTURES + "/recordio_protobuf/train.pb", "application/x-recordio-protobuf"
    )
    assert dm.num_row > 0 and dm.labels is not None


def test_recordio_sparse_edge_cases():
    import glob
    import os

    for pb in glob.glob(FIXTURES + "/recordio_protobuf/sparse_edge_cases/*.pbr"):
        with open(pb, "rb") as f:
            features, labels = read_recordio_protobuf(f.read())
        assert features.shape[0] > 0, os.path.basename(pb)


def test_recordio_roundtrip():
    feats = np.array([[1.0, 2.0], [3.0, 4.0]], dtype=np.float32)
    labels = np.array([0.0, 1.0], dtype=np.float32)
    buf = write_recordio_protobuf(feats, labels)
    f2, l2 = read_recordio_protobuf(buf)
    np.testing.assert_allclose(f2, feats)
    np.testing.assert_allclose(l2, labels)


def test_no_label_error(tmp_path):
    p2 = tmp_path / "single.csv"
    p2.write_text("1\n2\n")
    with pytest.raises(exc.UserError):
        readers.get_data_matrix(str(p2), "csv")


def test_missing_path_returns_none(tmp_path):
    assert readers.get_data_matrix(str(tmp_path / "nope"), "csv") is None


def test_validate_libsvm_rejects_csv(tmp_path):
    p = tmp_path / "x.libsvm"
    p.write_text("1.0,2.0,3.0\n")
    with pytest.raises(exc.UserError, match="LIBSVM"):
        readers.validate_data_file_path(str(p), "libsvm")


def test_nested_dir_staging():
    dm = readers.get_data_matrix(
        resources() + "/abalone-subdirs/train", "libsvm"
    )
    assert dm is not None and dm.num_row > 0


def test_staging_depth_cap_warns_but_loads_nothing_deeper(caplog):
    # dir1/dir2/dir3/dir4/abalone.train_0 sits at depth 4 > MAX_FOLDER_DEPTH
    staged = readers.stage_input_files(resources() + "/abalone-subdirs/dir1")
    import os

    assert staged is not None
    assert os.listdir(staged) == []


def test_csv_weights():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = d + "/w.csv"
        with open(path, "w") as f:
            f.write("1.0,0.5,7.0,8.0\n0.0,2.0,9.0,1.0\n")
        dm = readers.get_data_matrix(path, "csv", csv_weights=1)
        np.testing.assert_allclose(dm.weights, [0.5, 2.0])
        assert dm.num_col == 2


def test_get_size_and_hidden_file(tmp_path):
    (tmp_path / "a.csv").write_text("1,2\n")
    assert readers.get_size(str(tmp_path)) == 4
    (tmp_path / ".hidden").write_text("x")
    with pytest.raises(exc.UserError, match="Hidden"):
        readers.get_size(str(tmp_path))


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------


def test_binning_roundtrip_decisions():
    rng = np.random.RandomState(0)
    feats = rng.randn(500, 4).astype(np.float32)
    feats[rng.rand(500, 4) < 0.1] = np.nan
    dm = DataMatrix(feats, labels=np.zeros(500, np.float32))
    bm = binning.bin_matrix(dm, max_bin=64)
    assert bm.bins.dtype == np.uint8
    # missing marker
    assert (bm.bins[np.isnan(feats)] == 64).all()
    # bin(v) <= b  <=>  v < cut[b] for every cut of every feature
    for f in range(4):
        cuts = bm.cut_points[f]
        col = feats[:, f]
        valid = ~np.isnan(col)
        for b in range(0, len(cuts), max(1, len(cuts) // 5)):
            lhs = bm.bins[valid, f] <= b
            rhs = col[valid] < cuts[b]
            assert (lhs == rhs).all()


def test_binning_exact_when_few_distinct():
    col = np.array([1.0, 1.0, 2.0, 3.0, 3.0, 5.0], dtype=np.float32).reshape(-1, 1)
    dm = DataMatrix(col, labels=np.zeros(6, np.float32))
    bm = binning.bin_matrix(dm, max_bin=256)
    np.testing.assert_allclose(bm.cut_points[0], [1.5, 2.5, 4.0])
    assert set(bm.bins[:, 0].tolist()) == {0, 1, 2, 3}


def test_binning_respects_max_bin():
    rng = np.random.RandomState(1)
    col = rng.randn(10000, 1).astype(np.float32)
    dm = DataMatrix(col, labels=np.zeros(10000, np.float32))
    bm = binning.bin_matrix(dm, max_bin=16)
    assert len(bm.cut_points[0]) <= 15
    assert bm.bins.max() <= 15


def test_matrix_slice_and_concat():
    feats = np.arange(20, dtype=np.float32).reshape(10, 2)
    dm = DataMatrix(feats, labels=np.arange(10, dtype=np.float32))
    sl = dm.slice([0, 2, 4])
    assert sl.num_row == 3
    np.testing.assert_allclose(sl.labels, [0, 2, 4])
    cat = sl.concat(dm.slice([1, 3]))
    assert cat.num_row == 5


def test_fixture_sweep_all_reference_data_dirs():
    """Every remaining reference data fixture loads into a DataMatrix."""
    cases = [
        (FIXTURES + "/csv/multiple_files", "csv"),
        (FIXTURES + "/csv/weighted_csv_files", "csv"),
        (FIXTURES + "/recordio_protobuf/pb_files", "application/x-recordio-protobuf"),
        (FIXTURES + "/recordio_protobuf/sparse", "application/x-recordio-protobuf"),
        (FIXTURES + "/libsvm/libsvm_files", "libsvm"),
    ]
    for path, content_type in cases:
        dm = readers.get_data_matrix(path, content_type)
        assert dm is not None and dm.num_row > 0, path


def test_abalone_binary_and_multiclass_train():
    from sagemaker_xgboost_container_tpu.models import train

    dm_bin = readers.get_data_matrix(
        resources() + "/abalone-binary/data/train", "libsvm"
    )
    assert set(np.unique(dm_bin.labels)) <= {0.0, 1.0}
    forest = train(
        {"objective": "binary:logistic", "max_depth": 3}, dm_bin, num_boost_round=5
    )
    p = forest.predict(dm_bin.features)
    assert ((p > 0.5) == dm_bin.labels).mean() > 0.7

    dm_multi = readers.get_data_matrix(
        resources() + "/abalone-multiclass/data/train", "libsvm"
    )
    n_class = int(dm_multi.labels.max()) + 1
    forest = train(
        {"objective": "multi:softprob", "num_class": n_class, "max_depth": 3},
        dm_multi,
        num_boost_round=4,
    )
    prob = forest.predict(dm_multi.features)
    assert prob.shape == (dm_multi.num_row, n_class)


def test_check_data_redundancy(tmp_path, caplog):
    """Reference data_utils.py:631-660: same-named same-size files across
    train/validation warn (duplicate data impairs the validation score);
    missing dirs raise UserError."""
    import logging

    from sagemaker_xgboost_container_tpu.data import readers
    from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc

    train = tmp_path / "train"
    val = tmp_path / "validation"
    train.mkdir()
    val.mkdir()
    (train / "part0").write_text("abcdef")
    (val / "part0").write_text("uvwxyz")   # same name + size -> suspected dup
    (train / "part1").write_text("123")
    (val / "part1").write_text("12345")    # same name, size differs -> quiet
    with caplog.at_level(logging.WARNING):
        readers.check_data_redundancy(str(train), str(val))
    assert "Suspected identical files" in caplog.text
    assert "part0" in caplog.text and "part1" not in caplog.text

    import pytest as _pytest

    with _pytest.raises(exc.UserError, match="training data's path"):
        readers.check_data_redundancy(str(tmp_path / "absent"), str(val))
    with _pytest.raises(exc.UserError, match="validation data's path"):
        readers.check_data_redundancy(str(train), str(tmp_path / "absent"))
