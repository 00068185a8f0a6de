"""Seeded stand-in for the Criteo Terabyte click logs: 39 float32 columns
(13 counts, then 26 label-encoded categoricals), a click label.

Counts are non-negative integers with a spike at 0 and 1 and a heavy tail
(a floored Lomax draw), NaN at the log's missing rates. Codes are
frequency-ranked integers (code 0 the commonest, NVTabular ``Categorify``
order) over the log's cardinalities, Zipf-like (``floor((card + 1) ** u) - 1`` from
one uniform), NaN at the log's missing rates; a code over 2**24 rounds to a
float32 neighbour. About 14 % of all cells are missing. The label is a noisy
function of a few counts, a few low-cardinality codes and of *whether* a
column is missing, so that a split's default direction carries signal. Every
seed draws from the same distribution: only the rows differ. The rates,
cardinalities and the click rate are from memory of the public log (no
network here): the configuration's ``assumed`` says so.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

NUM_COUNTS = 13
COUNT_MISSING = (0.45, 0.0, 0.21, 0.22, 0.026, 0.22, 0.043, 0.0005, 0.043, 0.45, 0.043, 0.765, 0.22)
# Lomax scale and tail index of each count column: small scales put most of
# the mass on 0 and 1, tail indices near 1 reach six digits in 16M rows
COUNT_SCALE = (1.2, 8.0, 3.0, 4.0, 900.0, 30.0, 5.0, 9.0, 40.0, 0.5, 1.5, 0.4, 4.0)
COUNT_TAIL = (1.4, 1.2, 1.2, 1.8, 1.5, 1.2, 1.3, 2.2, 1.3, 3.0, 1.6, 1.5, 1.9)
CARDINALITIES = (
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951, 2953546, 403346, 10,
    2208, 11938, 155, 4, 976, 14, 39979771, 25641295, 39664984, 585935, 12972, 108, 36,
)
CODE_MISSING = (
    0.0, 0.0, 0.0, 0.0, 0.0, 0.12, 0.0, 0.0, 0.0, 0.0, 0.0, 0.034, 0.0,
    0.0, 0.0, 0.034, 0.0, 0.0, 0.44, 0.44, 0.034, 0.76, 0.0, 0.034, 0.44, 0.44,
)
MISSING = np.asarray(COUNT_MISSING + CODE_MISSING, np.float32)
MISSING_SHARE = float(MISSING.mean())  # of all cells: 0.1402
CLICK_RATE = 0.03  # what the label's intercept is set for (measured 0.0300 over 4M rows)

ROW_CHUNK = 1 << 20
THREADS = 12


def _rows(seed, chunk, n):
    """Rows ``chunk * ROW_CHUNK`` onwards, drawn from a stream of their own."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x43726974656F, chunk])
    d = len(MISSING)
    # one uniform a cell decides both: missing below the column's rate, and
    # the rest of the unit interval, stretched back to [0, 1), is the value's
    u = rng.random((d, n), dtype=np.float32)
    missing = u < MISSING[:, None]
    u -= MISSING[:, None]
    u /= 1.0 - MISSING[:, None]
    np.clip(u, 0.0, np.float32(1.0 - 2.0**-24), out=u)
    x = np.empty((d, n), np.float32)
    for c in range(NUM_COUNTS):
        # floored Lomax: scale * ((1 - u) ** (-1 / tail) - 1)
        tail = np.power(1.0 - u[c], np.float32(-1.0 / COUNT_TAIL[c]))
        np.floor(np.float32(COUNT_SCALE[c]) * (tail - 1.0), out=x[c])
    for c, card in enumerate(CARDINALITIES, start=NUM_COUNTS):
        # frequency-ranked codes: P(code = k) is log((k + 2) / (k + 1)) / log(card + 1)
        np.exp(u[c] * np.float32(np.log(card + 1.0)), out=x[c])
        np.floor(x[c], out=x[c])
        x[c] -= 1.0
        np.minimum(x[c], np.float32(card - 1), out=x[c])
    np.copyto(x, np.float32(np.nan), where=missing)

    def count(c):  # log1p of a count column, 0 where it is missing
        return np.log1p(np.where(missing[c], np.float32(0.0), x[c]))

    def code(c):
        return x[NUM_COUNTS + c]

    signal = (
        np.float32(-4.21)
        + 0.45 * count(0) - 0.6 * missing[0]          # I1, and whether it is there
        + 0.30 * count(10) - 0.20 * count(4) / 4.0    # I11, I5
        + 0.9 * ~missing[11]                          # I12 is there in a quarter of the rows
        + 0.5 * missing[NUM_COUNTS + 21]              # C22 absent
        - 0.4 * missing[NUM_COUNTS + 18]              # C19 absent
        + 0.5 * (code(5) == 0) - 0.5 * (code(5) == 2)  # C6: three values and NaN
        + 0.4 * (code(16) >= 2)                       # C17: four values
        - 0.3 * (code(8) < 4)                         # C9: 63 values
        + 0.3 * (code(12) >= 5)                       # C13: ten values
    ).astype(np.float32)
    y = (rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-signal))).astype(np.float32)
    return x, y


def make(config, seed):
    """{"train": (X, y), "validation": (X, y)}, float32, from the seed: made
    in chunks of rows on a few threads, the same rows whatever the threads."""
    n_train, n_val = int(config["train_rows"]), int(config["validation_rows"])
    n, d = n_train + n_val, int(config["num_feature"])
    if d != len(MISSING):
        raise ValueError("criteo_like makes {} columns, not {}".format(len(MISSING), d))
    x, y = np.empty((n, d), np.float32), np.empty(n, np.float32)

    def fill(chunk):
        lo = chunk * ROW_CHUNK
        hi = min(lo + ROW_CHUNK, n)
        cols, y[lo:hi] = _rows(seed, chunk, hi - lo)
        x[lo:hi] = cols.T

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(-(-n // ROW_CHUNK))))
    return {
        "train": (x[:n_train], y[:n_train]),
        "validation": (x[n_train:], y[n_train:]),
    }
