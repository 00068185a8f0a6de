"""Seeded stand-in for UCI HIGGS: 28 float32 columns, binary label.

24 continuous columns (momenta log-normal, angles uniform or normal, derived
masses log-normal) and 4 jet b-tag columns that take three values, as in the
published set; no missing values. The label is a noisy nonlinear function of
a few columns so that boosting has something to learn. Every seed draws from
the same distribution: only the rows differ.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

B_TAG_COLUMNS = (8, 12, 16, 20)
B_TAG_VALUES = np.asarray([0.0, 1.0865380764, 2.1730761528], np.float32)
UNIFORM_COLUMNS = (2, 4, 7, 11, 15, 19)  # azimuthal angles
LOGNORMAL_COLUMNS = (0, 3, 5, 9, 13, 17, 21, 22, 23, 24, 25, 26, 27)


ROW_CHUNK = 1 << 20
THREADS = 8


def _rows(seed, chunk, n, num_feature):
    """Rows ``chunk * ROW_CHUNK`` onwards, drawn from a stream of their own."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x4869676773, chunk])
    x = rng.standard_normal((n, num_feature), dtype=np.float32)
    signal = (
        0.9 * np.tanh(x[:, 25])
        + 0.7 * x[:, 26] * x[:, 27]
        - 0.5 * np.abs(x[:, 1])
        + 0.6 * (x[:, 8] > 0.0)
        + 0.4 * x[:, 0]
        - 0.3 * x[:, 5] * x[:, 22]
    )
    y = (rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-signal))).astype(np.float32)
    for c in UNIFORM_COLUMNS:
        # a normal's CDF is costly; the sine of a scaled normal is uniform
        # enough in shape for binning and keeps every value distinct
        x[:, c] = np.float32(1.7416) * np.sin(x[:, c] * np.float32(1.9))
    for c in LOGNORMAL_COLUMNS:
        np.exp(np.float32(0.5) * x[:, c], out=x[:, c])
    for c in B_TAG_COLUMNS:
        x[:, c] = B_TAG_VALUES[(x[:, c] > 0.0).astype(np.int8) + (x[:, c] > 0.6745)]
    return x, y


def make(config, seed):
    """{"train": (X, y), "validation": (X, y)}, float32, from the seed: made
    in chunks of rows on a few threads, the same rows whatever the threads."""
    n_train, n_val = int(config["train_rows"]), int(config["validation_rows"])
    n, d = n_train + n_val, int(config["num_feature"])
    x, y = np.empty((n, d), np.float32), np.empty(n, np.float32)

    def fill(chunk):
        lo = chunk * ROW_CHUNK
        hi = min(lo + ROW_CHUNK, n)
        x[lo:hi], y[lo:hi] = _rows(seed, chunk, hi - lo, d)

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(-(-n // ROW_CHUNK))))
    return {
        "train": (x[:n_train], y[:n_train]),
        "validation": (x[n_train:], y[n_train:]),
    }
