"""Seeded stand-in for MNIST8M ("infinite MNIST"): 784 float32 pixel columns
of whole numbers 0 to 255 on a 28 x 28 grid, ten classes, nothing missing.

A row is one of ten fixed glyphs (the seven-segment figures 0 to 9, strokes
three pixels thick with soft edges, in a box of 16 x 20 pixels in the middle
of the grid, as MNIST centres its digits), moved by a seeded shift of up to
four pixels across and three down, sheared by up to two pixels (MNIST8M's
rows are such small deformations of MNIST's), with one of its seven strokes
flipped in a tenth of the rows (so that classes overlap: a 0 with its middle
stroke lit is an 8's picture under a 0's label, and the loss has a floor),
scaled by a seeded gain a row and a seeded factor a pixel, floored to whole
numbers. Strokes shared between glyphs and the shifts spread every class
over several columns, so a depth-5 tree has something to find and something
left over. About 19 % of the cells are non-zero (MNIST's share); the outer
columns are zero in nearly every row, and about an eighth of the columns
(102 to 104 of 784 at the cell's size) in every row: one value each, which
no split can use. Classes are drawn at the frequencies of MNIST's 60,000
training labels. Every seed draws from the same
distribution: only the rows differ. The frequencies and the pixel statistics
are from memory of the public set (no network here): the configuration's
``assumed`` says so.

``make`` asks the program one thing first: the configuration's ``gamma`` is 4
and the plain reference recomputes a split's stored gain from its own sums,
so a program that stores the gain less ``gamma`` (this one before PR 39)
cannot be judged ``correct`` here whatever it computes. ``make`` grows one
eight-row tree through ``ops/tree_build.py::build_tree`` and leaves at once,
with a message and no data made, where the stored gain is not the split's own.

Memory and time: the float matrix is 1.59 GB at 506,875 rows (the cell's
train and validation rows together); the table of pictures (128 stroke sets
x 315 placements x 784 bytes) is 31.6 MB and takes 0.1 s to draw; rows are made
in chunks of 16,384 on a few threads (two float32 temporaries of 51 MB a
thread), the same rows whatever the threads: 2.0 s on the chip's host (my
chip runs, PR 39), 1.1 to 5.5 s on 8 cores here, most of it the first touch
of the matrix's pages.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SIDE = 28
# MNIST's 60,000 training labels by class, 0 to 9 (from memory)
CLASS_COUNTS = (5923, 6742, 5958, 6131, 5842, 5421, 5918, 6265, 5851, 5949)
# strokes a (top), b (top right), c (bottom right), d (bottom), e (bottom
# left), f (top left), g (middle) as bits 0 to 6; the figures 0 to 9
STROKES = 7
GLYPHS = tuple(
    sum(1 << "abcdefg".index(s) for s in strokes)
    for strokes in (
        "abcdef", "bc", "abged", "abgcd", "fgbc", "afgcd", "afgedc", "abc", "abcdefg", "abcdfg",
    )
)
# the glyph's box: columns 6 to 21, rows 4 to 23; (row0, row1, col0, col1) a stroke
LEFT, RIGHT, TOP, MIDDLE, BOTTOM, THICK = 6, 22, 4, 12, 20, 3
STROKE_BOXES = (
    (TOP, TOP + THICK, LEFT, RIGHT),                 # a
    (TOP, MIDDLE + THICK, RIGHT - THICK, RIGHT),     # b
    (MIDDLE, BOTTOM + THICK, RIGHT - THICK, RIGHT),  # c
    (BOTTOM, BOTTOM + THICK, LEFT, RIGHT),           # d
    (MIDDLE, BOTTOM + THICK, LEFT, LEFT + THICK),    # e
    (TOP, MIDDLE + THICK, LEFT, LEFT + THICK),       # f
    (MIDDLE, MIDDLE + THICK, LEFT, RIGHT),           # g
)
EDGE, CORE = 110, 255  # a stroke's outer pixels and its middle, before gain
SHIFT_X = (-4, -3, -2, -1, 0, 1, 2, 3, 4)
SHIFT_X_P = (0.004, 0.016, 0.08, 0.2, 0.4, 0.2, 0.08, 0.016, 0.004)
SHIFT_Y = (-3, -2, -1, 0, 1, 2, 3)
SHIFT_Y_P = (0.01, 0.09, 0.2, 0.4, 0.2, 0.09, 0.01)
SHEAR = (-2, -1, 0, 1, 2)  # columns the top row leans right, the bottom row left
SHEAR_P = (0.05, 0.25, 0.4, 0.25, 0.05)
PLACEMENTS = len(SHIFT_X) * len(SHIFT_Y) * len(SHEAR)
FLIP_RATE = 0.1   # rows with one stroke flipped
GAIN = (0.55, 1.0)    # a row's gain, uniform
PIXEL = (0.6, 1.25)   # a pixel's factor, uniform; the product is cut off at 255

ROW_CHUNK = 1 << 14
THREADS = 8


def _stroke_pictures():
    """[STROKES, PLACEMENTS, 784] uint8: every stroke alone, at every placement."""
    out = np.zeros((STROKES, PLACEMENTS, SIDE, SIDE), np.uint8)
    lean = (np.arange(SIDE) - (SIDE - 1) / 2.0) / ((BOTTOM + THICK - TOP) / 2.0)
    for s, (r0, r1, c0, c1) in enumerate(STROKE_BOXES):
        plain = np.zeros((SIDE, SIDE), np.uint8)
        plain[r0:r1, c0:c1] = EDGE
        plain[r0 + 1 : r1 - 1, c0 + 1 : c1 - 1] = CORE
        p = 0
        for dx in SHIFT_X:
            for dy in SHIFT_Y:
                for shear in SHEAR:
                    pic = out[s, p]
                    for r in range(r0, r1):
                        to = r + dy
                        by = dx - int(np.rint(shear * lean[r]))
                        pic[to, c0 + by : c1 + by] = plain[r, c0:c1]
                    p += 1
    return out.reshape(STROKES, PLACEMENTS, SIDE * SIDE)


def _picture_table():
    """[128 stroke sets, PLACEMENTS, 784] uint8: strokes overlaid by maximum."""
    strokes = _stroke_pictures()
    table = np.zeros((1 << STROKES,) + strokes.shape[1:], np.uint8)
    for mask in range(1, 1 << STROKES):
        low = mask & -mask
        np.maximum(table[mask ^ low], strokes[low.bit_length() - 1], out=table[mask])
    return table.reshape(-1, SIDE * SIDE)


def _rows(table, seed, chunk, n):
    """Rows ``chunk * ROW_CHUNK`` onwards, drawn from a stream of their own."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x4D4E495354384D, chunk])
    freq = np.asarray(CLASS_COUNTS, np.float64)
    cls = rng.choice(len(GLYPHS), size=n, p=freq / freq.sum())
    mask = np.asarray(GLYPHS)[cls]
    flipped = rng.random(n) < FLIP_RATE
    mask = np.where(flipped, mask ^ (1 << rng.integers(0, STROKES, size=n)), mask)
    place = (
        rng.choice(len(SHIFT_X), size=n, p=SHIFT_X_P) * len(SHIFT_Y)
        + rng.choice(len(SHIFT_Y), size=n, p=SHIFT_Y_P)
    ) * len(SHEAR) + rng.choice(len(SHEAR), size=n, p=SHEAR_P)
    gain = rng.uniform(*GAIN, size=n).astype(np.float32)
    x = table.take(mask * PLACEMENTS + place, axis=0).astype(np.float32)
    x *= gain[:, None]
    pixel = rng.random(x.shape, dtype=np.float32)
    pixel *= np.float32(PIXEL[1] - PIXEL[0])
    pixel += np.float32(PIXEL[0])
    x *= pixel
    np.minimum(x, np.float32(255.0), out=x)
    np.floor(x, out=x)
    return x, cls.astype(np.float32)


def _stored_gain_of_a_known_split():
    """The program's stored gain of the one split of eight rows (gradients
    -1 and +1 four each, hessians 1, lambda 1) under ``gamma`` 0.5: the
    split's own loss change is 0.5 * (16/5 + 16/5 - 0) = 3.2."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
    from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree

    knobs = resolve_hist_knobs()._replace(backend="cpu")  # eight rows need no kernel

    @jax.jit
    def root_gain(bins, grad, hess, num_cuts):
        tree, _row_out = build_tree(
            bins, grad, hess, num_cuts, 1, 3,
            reg_lambda=1.0, gamma=0.5, min_child_weight=0.0, knobs=knobs,
        )
        return tree["gain"][0]

    half = np.repeat(np.asarray([0, 1], np.int32), 4)
    return float(
        root_gain(
            jnp.asarray(half[:, None]), jnp.asarray(2.0 * half - 1.0, jnp.float32),
            jnp.ones(8, jnp.float32), jnp.asarray([1], jnp.int32),
        )
    )


def make(config, seed):
    """{"train": (X, y), "validation": (X, y)}, float32, from the seed."""
    n_train, n_val = int(config["train_rows"]), int(config["validation_rows"])
    n, d = n_train + n_val, int(config["num_feature"])
    if d != SIDE * SIDE or int(config["params"]["num_class"]) != len(GLYPHS):
        raise ValueError(
            "mnist8m_like makes {} columns and {} classes".format(SIDE * SIDE, len(GLYPHS))
        )
    stored = _stored_gain_of_a_known_split()
    if abs(stored - 3.2) > 0.05:
        raise SystemExit(
            "benchmark: this program stores a split's gain as {:.3f} where its loss change is "
            "3.2 (gamma taken off?): the reference cannot judge its trees under gamma {}".format(
                stored, config["params"].get("gamma")
            )
        )
    table = _picture_table()
    x, y = np.empty((n, d), np.float32), np.empty(n, np.float32)

    def fill(chunk):
        lo = chunk * ROW_CHUNK
        hi = min(lo + ROW_CHUNK, n)
        x[lo:hi], y[lo:hi] = _rows(table, seed, chunk, hi - lo)

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(-(-n // ROW_CHUNK))))
    return {
        "train": (x[:n_train], y[:n_train]),
        "validation": (x[n_train:], y[n_train:]),
    }
