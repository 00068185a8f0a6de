"""Seeded stand-in for the Allstate Claim Prediction Challenge table as
LightGBM's comparison ran it: 4,228 columns, one-hot encoded, as scipy CSR.

A row holds 15 numeric columns (columns 0 to 14; each absent in its own share
of the rows, ``NUMERIC_ABSENT``) and one value of each of 17 categoricals,
one-hot encoded into the 4,213 columns that follow: a categorical of ``g``
values owns ``g`` consecutive columns, a row holds 1.0 in the column of its
value and nothing in the others, and in ``GROUP_UNKNOWN`` of the rows the
value is unknown and the row holds none of the group's columns. The group
sizes (``GROUP_SIZES``) sum to 4,213 with three large ones (75, 1,300 and
2,700 values: a car's make, model and sub-model in the source's table) and
fourteen small ones. A value's popularity inside its group is Zipf-like
(``floor((g + 1) ** u) - 1`` from one uniform: value 0 the commonest). About
31 cells of a row are present (0.74 % of 4,228). The label (a claim was paid)
is a noisy function of a few numeric columns, of the values of six groups
(effects drawn once, the same for every seed) and of *whether* two columns
are absent, so that a split's default direction carries signal; positives
near 1 %. Every seed draws from the same distribution: only the rows differ.
Every statistic here is the generator's own: the configuration's ``assumed``
says so.

Nothing dense of rows x 4,228 is ever made: a chunk of rows is drawn as a
``[32, rows]`` table of (column, value, present) and compressed to CSR.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

NUM_NUMERIC = 15
NUMERIC_ABSENT = (
    0.0, 0.0, 0.0, 0.0, 0.0, 0.002, 0.005, 0.01, 0.01, 0.02, 0.03, 0.05, 0.08, 0.12, 0.2,
)
GROUP_SIZES = (2, 2, 3, 3, 4, 5, 6, 8, 10, 12, 14, 18, 23, 28, 75, 1300, 2700)
GROUP_UNKNOWN = (
    0.0, 0.01, 0.0, 0.02, 0.05, 0.0, 0.01, 0.03, 0.1, 0.02, 0.0, 0.05, 0.01, 0.04,
    0.02, 0.06, 0.1,
)
NUM_FEATURE = NUM_NUMERIC + sum(GROUP_SIZES)  # 4228
GROUP_START = np.cumsum((NUM_NUMERIC,) + GROUP_SIZES[:-1])
SLOTS = NUM_NUMERIC + len(GROUP_SIZES)  # cells a row can hold: 32
PRESENT_A_ROW = float(
    NUM_NUMERIC - sum(NUMERIC_ABSENT) + len(GROUP_SIZES) - sum(GROUP_UNKNOWN)
)  # 31.18
POSITIVE_RATE = 0.01  # what the label's intercept is set for
INTERCEPT = -7.05

# which groups carry signal, and their values' effects: drawn once, whatever
# the seed (a value's effect shrinks with its rank, so that rare values of the
# large groups are weak and many)
SIGNAL_GROUPS = (4, 9, 13, 14, 15, 16)
_effects = np.random.default_rng(0x416C6C7374617465)
GROUP_EFFECT = {
    g: (
        _effects.normal(size=GROUP_SIZES[g])
        * (0.9 / np.sqrt(1.0 + np.arange(GROUP_SIZES[g]) / 40.0))
    ).astype(np.float32)
    for g in SIGNAL_GROUPS
}

ROW_CHUNK = 1 << 20
THREADS = 12


def _rows(seed, chunk, n):
    """CSR pieces and labels of ``n`` rows, drawn from a stream of their own:
    (indices, data, cells a row, labels)."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x416C6C7374, chunk])
    u = rng.random((SLOTS, n), dtype=np.float32)
    absent = np.asarray(NUMERIC_ABSENT + GROUP_UNKNOWN, np.float32)[:, None]
    present = u >= absent
    # the rest of the unit interval, stretched back to [0, 1), is the value's
    u -= absent
    u /= 1.0 - absent
    np.clip(u, 0.0, np.float32(1.0 - 2.0**-24), out=u)
    column = np.empty((SLOTS, n), np.int32)
    value = np.ones((SLOTS, n), np.float32)
    z = rng.standard_normal((NUM_NUMERIC, n), dtype=np.float32)
    for c in range(NUM_NUMERIC):
        column[c] = c
    value[0:5] = z[0:5]                                        # gaussians
    value[5:8] = np.exp(np.float32(0.8) * z[5:8])              # skewed amounts
    value[8:11] = np.floor(np.float32(12.0) * u[8:11] ** 2)    # small counts, 12 values
    value[11:13] = np.round(z[11:13] * np.float32(4.0)) / np.float32(4.0)  # quarter steps
    value[13] = np.floor(np.float32(1990.0) + np.float32(24.0) * u[13])    # a year
    value[14] = np.abs(z[14]) * np.float32(1000.0)                         # a price
    score = np.full(n, INTERCEPT, np.float32)
    score += np.float32(0.5) * np.where(present[0], value[0], 0)
    score += np.float32(0.6) * np.where(present[6] & (value[6] > 1.5), 1, 0)
    score -= np.float32(0.4) * np.where(present[13], (value[13] - 2002.0) / 12.0, 0)
    score += np.float32(0.7) * ~present[14] + np.float32(0.5) * ~present[12]
    for g, size in enumerate(GROUP_SIZES):
        s = NUM_NUMERIC + g
        # Zipf-like: P(value = k) is log((k + 2) / (k + 1)) / log(size + 1)
        code = np.floor(np.power(np.float32(size + 1), u[s])).astype(np.int32) - 1
        np.clip(code, 0, size - 1, out=code)
        column[s] = GROUP_START[g] + code
        if g in GROUP_EFFECT:
            score += np.where(present[s], GROUP_EFFECT[g][code], np.float32(0.3))
    score += np.float32(0.5) * rng.standard_normal(n, dtype=np.float32)
    label = (rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-score))).astype(np.float32)
    keep = present.T  # [n, SLOTS]: row-major selection is CSR order, columns ascending
    return (
        np.ascontiguousarray(column.T)[keep],
        np.ascontiguousarray(value.T)[keep],
        keep.sum(axis=1),
        label,
    )


def _matrix(seed, first_chunk, rows):
    """``rows`` rows as CSR, from chunks ``first_chunk`` onwards."""
    sizes = [min(ROW_CHUNK, rows - lo) for lo in range(0, rows, ROW_CHUNK)]
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        parts = list(pool.map(lambda a: _rows(seed, first_chunk + a[0], a[1]), enumerate(sizes)))
    indptr = np.zeros(rows + 1, np.int64)
    np.cumsum(np.concatenate([p[2] for p in parts]), out=indptr[1:])
    x = sp.csr_matrix(
        (
            np.concatenate([p[1] for p in parts]),
            np.concatenate([p[0] for p in parts]),
            indptr if indptr[-1] >= 1 << 31 else indptr.astype(np.int32),
        ),
        shape=(rows, NUM_FEATURE),
    )
    return x, np.concatenate([p[3] for p in parts])


def make(config, seed):
    """{"train": (CSR, y), "validation": (CSR, y)}: the published split, the
    last ``validation_rows`` held out; every chunk of ``ROW_CHUNK`` rows is
    drawn from a stream of its own, so the chunks are made side by side."""
    if int(config["num_feature"]) != NUM_FEATURE:
        raise ValueError("allstate_like makes {} columns".format(NUM_FEATURE))
    n_train, n_valid = int(config["train_rows"]), int(config["validation_rows"])
    return {
        "train": _matrix(seed, 0, n_train),
        "validation": _matrix(seed, 1 << 20, n_valid),
    }
