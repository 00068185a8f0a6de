"""Seeded stand-in for UCI Covertype: 54 columns, 7 classes.

10 integer-valued quantitative columns, then a 4-way and a 40-way one-hot
group (exactly one column set in each group per row), classes drawn at the
published frequencies; no missing values. Class-dependent means and group
probabilities come from a fixed table, so every seed draws from the same
distribution: only the rows differ.
"""

import numpy as np

# (mean, sd, low, high) of the quantitative columns, in the published order:
# elevation, aspect, slope, horizontal / vertical distance to hydrology,
# horizontal distance to roadways, hillshade 9am / noon / 3pm, horizontal
# distance to fire points.
QUANT = (
    (2959.0, 280.0, 1859, 3858),
    (155.0, 112.0, 0, 360),
    (14.0, 7.5, 0, 66),
    (269.0, 212.0, 0, 1397),
    (46.0, 58.0, -173, 601),
    (2350.0, 1559.0, 0, 7117),
    (212.0, 27.0, 0, 254),
    (223.0, 20.0, 0, 254),
    (142.0, 38.0, 0, 254),
    (1980.0, 1324.0, 0, 7173),
)
TABLE_SEED = 0x436F7674  # the fixed table of class effects, not the run's seed


def _class_tables(num_class):
    rng = np.random.default_rng(TABLE_SEED)
    shift = rng.normal(0.0, 0.8, size=(num_class, len(QUANT)))  # in sd units
    wild = rng.dirichlet(np.full(4, 0.6), size=num_class)
    soil = rng.dirichlet(np.full(40, 0.15), size=num_class)
    return shift, wild, soil


def _one_hot_group(rng, cum_probs, cls, out):
    """Set exactly one column of ``out`` per row, by the row's class."""
    u = rng.random(len(cls))
    pick = (u[:, None] >= cum_probs[cls][:, :-1]).sum(axis=1)
    out[np.arange(len(cls)), pick] = 1.0


def make(config, seed):
    n_train, n_val = int(config["train_rows"]), int(config["validation_rows"])
    n = n_train + n_val
    num_class = int(config["params"]["num_class"])
    freq = np.asarray(config["class_frequencies"], np.float64)
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x436F7674797065])
    cls = rng.choice(num_class, size=n, p=freq / freq.sum())
    shift, wild, soil = _class_tables(num_class)
    x = np.zeros((n, int(config["num_feature"])), np.float32)
    for j, (mean, sd, low, high) in enumerate(QUANT):
        col = mean + sd * (0.75 * rng.standard_normal(n) + shift[cls, j])
        x[:, j] = np.clip(np.rint(col), low, high)
    _one_hot_group(rng, np.cumsum(wild, axis=1), cls, x[:, 10:14])
    _one_hot_group(rng, np.cumsum(soil, axis=1), cls, x[:, 14:54])
    y = cls.astype(np.float32)
    return {
        "train": (x[:n_train], y[:n_train]),
        "validation": (x[n_train:], y[n_train:]),
    }
