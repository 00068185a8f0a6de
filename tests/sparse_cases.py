"""Seeded sparse matrices for the bundled-layout tests: one-hot groups with
Zipf-like popularity and a share of unknown values, numeric columns with a
share of absent cells, as scipy CSR; and the dense float form (NaN = absent)
the densified path takes."""

import numpy as np
import scipy.sparse as sp

GROUPS = (3, 5, 9, 40, 300)
NUMERIC = 3


def one_hot_csr(rows, seed, groups=GROUPS, numeric=NUMERIC, unknown=0.1, absent=0.05):
    """(CSR [rows, numeric + sum(groups)], binary labels)."""
    rng = np.random.default_rng(seed)
    d = numeric + sum(groups)
    at, columns, values = [], [], []
    score = np.zeros(rows)
    for j in range(numeric):
        held = np.flatnonzero(rng.random(rows) > absent)
        v = rng.normal(size=len(held)).astype(np.float32)
        at.append(held), columns.append(np.full(len(held), j)), values.append(v)
        if j == 0:
            score[held] += np.where(v > 0.3, 1.0, -0.5)
    first = numeric
    effects = np.random.default_rng(99)
    for size in groups:
        p = 1.0 / np.arange(1, size + 1)
        code = rng.choice(size, size=rows, p=p / p.sum())
        held = np.flatnonzero(rng.random(rows) > unknown)
        at.append(held), columns.append(first + code[held])
        values.append(np.ones(len(held), np.float32))
        score[held] += effects.normal(size=size)[code[held]]
        first += size
    x = sp.csr_matrix(
        (np.concatenate(values), (np.concatenate(at), np.concatenate(columns))),
        shape=(rows, d),
    )
    y = (score + rng.normal(size=rows) > 0.5).astype(np.float32)
    return x, y


def densified(x):
    out = np.full(x.shape, np.nan, np.float32)
    coo = x.tocoo()
    out[coo.row, coo.col] = coo.data
    return out
