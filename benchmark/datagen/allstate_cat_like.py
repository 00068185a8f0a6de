"""The table of ``allstate_like`` *before* its one-hot encoding: 32 float
columns, a categorical's value given as its code.

Same seed, same table: the rows are ``allstate_like``'s own draws (its
``_rows`` is called, nothing of it is copied), each row's cells put back into
the 32 slots they were drawn in. Columns 0 to 14 are the numeric columns,
absent cells NaN; columns 15 to 31 are the 17 categoricals (``GROUP_SIZES``
values each, 2 to 2,700), a row holding its value's code ``0 .. size - 1`` as
a float and NaN where the value is unknown. One-hot encoding column
``15 + g`` at ``GROUP_START[g] + code`` gives ``allstate_like``'s CSR back
(``tests/benchmark/test_categorical_cell.py`` holds the two together).
``FEATURE_TYPES`` is what the trainer is told: ``q`` for the numeric columns,
``c`` for the categoricals.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.datagen import allstate_like as onehot

NUM_FEATURE = onehot.SLOTS  # 32
FEATURE_TYPES = ["q"] * onehot.NUM_NUMERIC + ["c"] * len(onehot.GROUP_SIZES)
CARDINALITIES = [0] * onehot.NUM_NUMERIC + list(onehot.GROUP_SIZES)


def _rows(seed, chunk, n):
    """``n`` rows of chunk ``chunk`` as float32 ``[n, 32]`` and their labels."""
    column, value, cells, label = onehot._rows(seed, chunk, n)
    group = np.searchsorted(onehot.GROUP_START, column, side="right") - 1
    numeric = column < onehot.NUM_NUMERIC
    slot = np.where(numeric, column, onehot.NUM_NUMERIC + group)
    held = np.where(numeric, value, (column - onehot.GROUP_START[group]).astype(np.float32))
    x = np.full((n, NUM_FEATURE), np.nan, np.float32)
    x[np.repeat(np.arange(n), cells), slot] = held
    return x, label


def _matrix(seed, first_chunk, rows):
    sizes = [min(onehot.ROW_CHUNK, rows - lo) for lo in range(0, rows, onehot.ROW_CHUNK)]
    x = np.empty((rows, NUM_FEATURE), np.float32)
    y = np.empty(rows, np.float32)

    def fill(a):
        i, n = a
        lo = i * onehot.ROW_CHUNK
        x[lo:lo + n], y[lo:lo + n] = _rows(seed, first_chunk + i, n)

    with ThreadPoolExecutor(max_workers=onehot.THREADS) as pool:
        list(pool.map(fill, enumerate(sizes)))
    return x, y


def make(config, seed):
    """{"train": (x, y), "validation": (x, y)}: ``allstate_like.make``'s rows,
    chunk for chunk."""
    if int(config["num_feature"]) != NUM_FEATURE:
        raise ValueError("allstate_cat_like makes {} columns".format(NUM_FEATURE))
    if list(config["feature_types"]) != FEATURE_TYPES:
        raise ValueError("allstate_cat_like's columns are {}".format(FEATURE_TYPES))
    n_train, n_valid = int(config["train_rows"]), int(config["validation_rows"])
    return {
        "train": _matrix(seed, 0, n_train),
        "validation": _matrix(seed, 1 << 20, n_valid),
    }
