"""Seeded stand-in for MSLR-WEB30K (Fold1): 136 float32 columns, graded
relevance labels 0-4, documents in query groups of ragged size.

Columns follow the published layout: 25 kinds of feature over five streams
(body, anchor, title, url, whole document) in columns 0-124, then eleven
document features (url shape, link counts, page and site rank, quality
scores, click counts, dwell time). The shapes are this generator's own
(``assumed`` in the configuration): small integer counts with few distinct
values, ratios on a coarse grid, heavy-tailed integer lengths and counts
(many of them zero), BM25-like positive scores, language-model scores below
zero, two-valued columns. No missing values, as in the published set.

The label is a noisy function of a few columns, cut at fixed thresholds into
grades 0-4 with about MSLR's shares (52 / 32 / 13 / 2 / 1 %), so that
boosting has splits to find at depth 8. Group sizes are log-normal with the
published mean, at least 1 and at most ``max_group_size``, each of which one
group is exactly; the sizes are then adjusted to the published totals, the
last groups taking what is left. Every seed draws from the same
distribution: only the documents and the order of the sizes differ.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROW_CHUNK = 1 << 19
THREADS = 8
SIZE_SIGMA = 0.78  # log-normal spread of the group sizes: real pairs about 0.5 G
# latent relevance = signal + unit noise; its grades' lower edges, read once
# from 4M draws so that the shares are about 52 / 32 / 13 / 2 / 1 %
GRADE_EDGES = np.asarray([-0.096, 1.282, 2.570, 3.215], np.float32)

# (first column, columns, kind) in the published order; kinds are below
COLUMN_BLOCKS = (
    (0, 5, "small_count"),      # covered query term number
    (5, 5, "grid_ratio"),       # covered query term ratio
    (10, 5, "length"),          # stream length
    (15, 5, "positive"),        # IDF
    (20, 25, "zero_count"),     # sum, min, max, mean, variance of term frequency
    (45, 25, "positive"),       # the same of stream-length normalised tf
    (70, 25, "zero_positive"),  # the same of tf*idf
    (95, 5, "two_valued"),      # boolean model
    (100, 5, "unit"),           # vector space model
    (105, 5, "zero_positive"),  # BM25
    (110, 15, "negative"),      # LMIR.ABS, LMIR.DIR, LMIR.JM
    (125, 1, "small_count"),    # slashes in the url
    (126, 1, "length"),         # length of the url
    (127, 2, "zero_count"),     # inlinks, outlinks
    (129, 2, "length"),         # PageRank, SiteRank
    (131, 2, "byte"),           # QualityScore, QualityScore2
    (133, 3, "zero_count"),     # query-url clicks, url clicks, dwell time
)


def _transform(block, kind):
    """Standard normals -> one kind of column, in place."""
    if kind == "small_count":
        np.multiply(block, np.float32(1.8), out=block)
        np.add(block, np.float32(2.5), out=block)
        np.clip(np.floor(block, out=block), 0.0, 12.0, out=block)
    elif kind == "grid_ratio":
        np.multiply(block, np.float32(1.2), out=block)
        np.add(block, np.float32(2.0), out=block)
        np.clip(np.rint(block, out=block), 0.0, 4.0, out=block)
        np.multiply(block, np.float32(0.25), out=block)
    elif kind == "length":
        np.multiply(block, np.float32(1.2), out=block)
        np.add(block, np.float32(4.5), out=block)
        np.floor(np.exp(block, out=block), out=block)
    elif kind == "positive":
        np.exp(np.multiply(block, np.float32(0.5), out=block), out=block)
    elif kind == "zero_count":
        np.multiply(block, np.float32(1.4), out=block)
        np.floor(np.exp(block, out=block), out=block)  # about half are zero
    elif kind == "zero_positive":
        np.multiply(block, np.float32(3.0), out=block)
        np.maximum(block, 0.0, out=block)
    elif kind == "two_valued":
        block[...] = block > np.float32(0.3)
    elif kind == "unit":
        np.multiply(np.tanh(block, out=block), np.float32(0.5), out=block)
        np.add(block, np.float32(0.5), out=block)
    elif kind == "negative":
        np.multiply(np.exp(np.multiply(block, np.float32(0.4), out=block), out=block),
                    np.float32(-8.0), out=block)
    elif kind == "byte":
        np.multiply(block, np.float32(40.0), out=block)
        np.add(block, np.float32(128.0), out=block)
        np.clip(np.rint(block, out=block), 0.0, 255.0, out=block)
    else:
        raise ValueError(kind)


def _rows(seed, chunk, n, num_feature):
    """Rows ``chunk * ROW_CHUNK`` onwards, drawn from a stream of their own."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x4D534C52, chunk])
    x = rng.standard_normal((n, num_feature), dtype=np.float32)
    signal = (
        0.9 * np.tanh(x[:, 105])           # BM25 of the body
        + 0.6 * x[:, 107] * (x[:, 95] > 0.3)  # BM25 of the title where all terms match
        + 0.5 * x[:, 0]                     # covered query terms in the body
        - 0.4 * np.abs(x[:, 112])           # a language-model score
        + 0.5 * x[:, 133]                   # query-url clicks
        + 0.3 * x[:, 129] * x[:, 131]       # rank and quality together
        + 0.3 * (x[:, 7] > 0.0)             # the title covers most terms
    )
    latent = signal + rng.standard_normal(n, dtype=np.float32)
    y = np.searchsorted(GRADE_EDGES, latent).astype(np.float32)
    for first, count, kind in COLUMN_BLOCKS:
        _transform(x[:, first:first + count], kind)
    return x, y


def group_sizes(n_groups, n_docs, max_size, seed, stream):
    """``n_groups`` sizes that sum to ``n_docs`` exactly: each at least 1 and
    at most ``max_size``, one of them 1 and one ``max_size`` exactly (the
    published set's smallest and largest query).

    The published set has one list of sizes, so the sizes are one fixed draw
    for each (``n_groups``, ``n_docs``, ``max_size``, ``stream``) and the
    seed decides their order: every run then lays its groups out in arrays
    of the same shapes and loads the same compiled program, as every job
    over the published set would."""
    if n_groups < 2 or not n_groups - 1 <= n_docs - max_size <= (n_groups - 2) * max_size + 1:
        raise ValueError("no such sizes: {} groups, {} documents".format(n_groups, n_docs))
    rng = np.random.default_rng([0x47525053, stream, n_groups, n_docs, max_size])
    mean = n_docs / n_groups
    sizes = np.exp(rng.normal(np.log(mean) - SIZE_SIGMA ** 2 / 2, SIZE_SIGMA, n_groups))
    sizes = np.clip(np.rint(sizes * (n_docs / sizes.sum())), 1, max_size).astype(np.int64)
    sizes[0], sizes[1] = 1, max_size
    # the last groups take what is left, one document each, as far as needed
    movable = np.arange(2, n_groups)[::-1]
    while True:
        left = n_docs - int(sizes.sum())
        if left == 0:
            break
        room = movable[(sizes[movable] < max_size) if left > 0 else (sizes[movable] > 1)]
        take = room[: abs(left)]
        sizes[take] += 1 if left > 0 else -1
    order = np.random.default_rng([int(seed) % (1 << 63), 0x47525053, stream])
    return order.permutation(sizes)


def make(config, seed):
    """{"train": (X, y, groups), "validation": (X, y, groups)}, float32 and
    int64 sizes, from the seed: rows made in chunks on a few threads, the
    same rows whatever the threads."""
    n_train, n_val = int(config["train_rows"]), int(config["validation_rows"])
    n, d = n_train + n_val, int(config["num_feature"])
    x, y = np.empty((n, d), np.float32), np.empty(n, np.float32)

    def fill(chunk):
        lo = chunk * ROW_CHUNK
        hi = min(lo + ROW_CHUNK, n)
        x[lo:hi], y[lo:hi] = _rows(seed, chunk, hi - lo, d)

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(-(-n // ROW_CHUNK))))
    largest = int(config["max_group_size"])
    return {
        "train": (
            x[:n_train], y[:n_train],
            group_sizes(int(config["train_groups"]), n_train, largest, seed, 0),
        ),
        "validation": (
            x[n_train:], y[n_train:],
            group_sizes(int(config["validation_groups"]), n_val, largest, seed, 1),
        ),
    }
