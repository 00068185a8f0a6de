"""Seeded stand-ins for the reference container's ``test/resources`` tree.

The tests of the data layer, the serving endpoints and the training job were
written against the reference repository's own fixture files, which are not
part of this one (ROADMAP D9). ``resources()`` writes, once a process and from
a fixed seed, a directory of the same layout and shapes into a temporary
directory: Abalone-shaped libsvm channels (8 features, labels 1 to 29) and the
csv, libsvm, Parquet and RecordIO-protobuf files the data-layer tests name.
What a seed cannot make (models pickled or saved by real xgboost, the
reference's hand-made ``.pbr`` edge cases) stays under ``REFERENCE_RESOURCES``
and its tests carry ``needs_reference_artifacts``.
"""

import atexit
import functools
import os
import shutil
import tempfile

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.recordio import write_recordio_protobuf

SEED = 20260928

REFERENCE_RESOURCES = "/root/reference/test/resources"

needs_reference_artifacts = pytest.mark.skipif(
    not os.path.isdir(REFERENCE_RESOURCES),
    reason="reads the reference repository's own binary artefacts under "
    "{} (models made by real xgboost), which a seed cannot make and this "
    "repository does not carry (ROADMAP D9)".format(REFERENCE_RESOURCES),
)


def _abalone(rng, n):
    """Rows shaped like UCI Abalone: sex in {1, 2, 3}, seven sizes and
    weights that grow with the animal, rings (the label) 1 to 29."""
    length = rng.uniform(0.075, 0.815, size=n)

    def noisy(scale):
        return 1.0 + scale * rng.randn(n)

    features = np.column_stack(
        [
            rng.randint(1, 4, size=n),
            length,
            0.8 * length * noisy(0.03),
            0.28 * length * noisy(0.08),
            3.6 * length**3 * noisy(0.08),
            1.55 * length**3 * noisy(0.10),
            0.78 * length**3 * noisy(0.10),
            1.05 * length**3 * noisy(0.10),
        ]
    )
    rings = np.clip(np.rint(2.5 + 14.0 * length + 1.5 * rng.randn(n)), 1, 29)
    return features, rings.astype(int)


def _write_libsvm(path, features, labels, keep=None):
    """One ``label 1:v ... d:v`` line a row (1-based indices, as the Abalone
    files have them); ``keep`` [n, d] drops entries to make rows sparse."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for i, (row, label) in enumerate(zip(features, labels)):
            tokens = [
                "{}:{:g}".format(j + 1, round(float(v), 4))
                for j, v in enumerate(row)
                if keep is None or keep[i, j]
            ]
            f.write("{:g} {}\n".format(label, " ".join(tokens)))


def _write_csv(path, columns):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    np.savetxt(path, columns, delimiter=",", fmt="%.6g")


def _tabular(rng, n, d=5):
    x = rng.randn(n, d)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(float)
    return x, y


def _write_all(root):
    rng = np.random.RandomState(SEED)
    join = functools.partial(os.path.join, root)

    # ---- Abalone-shaped libsvm channels (4,177 rows as the data set has)
    features, rings = _abalone(rng, 4177)
    train, validation = slice(0, 2924), slice(2924, 4177)
    halves = (slice(0, 1462), slice(1462, 2924))
    for k, part in enumerate(halves):
        _write_libsvm(
            join("abalone/data/train/abalone.train_{}".format(k)),
            features[part], rings[part],
        )
    _write_libsvm(
        join("abalone/data/validation/abalone.validation_0"),
        features[validation], rings[validation],
    )
    # the same files under nested directories: staging flattens up to a depth
    for k, part in enumerate(halves):
        _write_libsvm(
            join("abalone-subdirs/train/part{}/abalone.train_{}".format(k, k)),
            features[part], rings[part],
        )
    _write_libsvm(
        join("abalone-subdirs/dir1/dir2/dir3/dir4/abalone.train_0"),
        features[halves[0]], rings[halves[0]],
    )
    median = np.median(rings[train])
    _write_libsvm(
        join("abalone-binary/data/train/abalone.train_0"),
        features[train], (rings[train] > median).astype(int),
    )
    terciles = np.quantile(rings[train], [1 / 3, 2 / 3])
    _write_libsvm(
        join("abalone-multiclass/data/train/abalone.train_0"),
        features[train], np.searchsorted(terciles, rings[train]),
    )

    # ---- data/: one small table a content type, label first
    x, y = _tabular(rng, 240)
    table = np.column_stack([y, x])
    _write_csv(join("data/csv/train.csv"), table)
    for name in ("csv_files", "multiple_files"):
        for k in range(3):
            _write_csv(
                join("data/csv/{}/train_{}.csv".format(name, k)),
                table[80 * k : 80 * (k + 1)],
            )
    weighted = np.column_stack([y, rng.uniform(0.5, 2.0, size=len(y)), x])
    for k in range(2):
        _write_csv(
            join("data/csv/weighted_csv_files/train_{}.csv".format(k)),
            weighted[120 * k : 120 * (k + 1)],
        )

    keep = rng.rand(*x.shape) < 0.7
    keep[:, 0] = True  # every row has an entry; the last column appears
    keep[0, -1] = True
    _write_libsvm(join("data/libsvm/train.libsvm"), x, y, keep=keep)
    for k in range(2):
        part = slice(120 * k, 120 * (k + 1))
        _write_libsvm(
            join("data/libsvm/libsvm_files/train_{}.libsvm".format(k)),
            x[part], y[part], keep=keep[part],
        )

    import pandas as pd

    os.makedirs(join("data/parquet"))
    pd.DataFrame(
        table.astype(np.float32),
        columns=["label"] + ["f{}".format(j) for j in range(x.shape[1])],
    ).to_parquet(join("data/parquet/train.parquet"), index=False)

    import scipy.sparse as sp

    def write_pb(path, feats, labels):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(write_recordio_protobuf(feats, labels.astype(np.float32)))

    dense = x.astype(np.float32)
    write_pb(join("data/recordio_protobuf/train.pb"), dense, y)
    for k in range(2):
        part = slice(120 * k, 120 * (k + 1))
        write_pb(
            join("data/recordio_protobuf/pb_files/train_{}.pb".format(k)),
            dense[part], y[part],
        )
    write_pb(
        join("data/recordio_protobuf/sparse/train.pb"),
        sp.csr_matrix(np.where(keep, dense, 0.0)), y,
    )


@functools.lru_cache(maxsize=None)
def resources():
    """The root of the seeded tree, written on first use and removed when
    the process exits. Children of the test process (``training.entry`` in
    ``tests/test_training_e2e.py``) read it by path."""
    root = tempfile.mkdtemp(prefix="graft-test-resources-")
    atexit.register(shutil.rmtree, root, ignore_errors=True)
    _write_all(root)
    return root
