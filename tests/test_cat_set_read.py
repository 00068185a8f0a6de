"""The set table's read as a matrix product (PR 51,
``ops/categorical.py::_set_read_fn``, kernel ``graft_cat_set_read``): the
kernel, interpreted on the CPU, against the indexed gather bit for bit over
level widths, words a node and row counts; ``go_right`` over either word;
whole builds and evaluation walks on the chip's lowerings against the gather's;
and what the lowering follows.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix  # noqa: E402
from tests.test_categorical_training import PARAMS, TYPES, KeepLog, table  # noqa: E402


def read_case(W, words, n, seed):
    """A level's set table whose words hold every bit (bit 31 set in half of
    them), per-row nodes, and per-row values: missing (0), no category (-1),
    the first and the last code of the widest column, a code in every byte
    of a word, and seeded ones."""
    rng = np.random.default_rng(seed)
    sets = rng.integers(-(2**31), 2**31, size=(W, words), dtype=np.int64).astype(np.int32)
    sets[:, 0] |= np.int32(-(2**31))
    node = rng.integers(0, W, n).astype(np.int32)
    value = rng.integers(-1, 32 * words + 1, n).astype(np.int32)
    fixed = [0, -1, 1, 32 * words] + [1 + 8 * byte + 3 for byte in range(4 * min(words, 2))]
    value[: len(fixed)] = fixed[:n]
    node[: len(fixed)] = W - 1
    return sets, node, value


@pytest.mark.parametrize("n", [1024, 1537])  # whole lane tiles, and not
@pytest.mark.parametrize("words", [1, 3, 85])
@pytest.mark.parametrize("W", [1, 2, 8, 64, 128])
def test_the_product_reads_the_gathers_word_bit_for_bit(W, words, n):
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.data.categorical import CatLayout
    from sagemaker_xgboost_container_tpu.ops import categorical

    sets, node, value = read_case(W, words, n, seed=W * 1000 + words)
    want = sets[node, np.minimum(np.maximum(value - 1, 0) >> 5, words - 1)]
    assert (want < 0).any()  # bit 31: a negative word comes back as its 32 bits
    args = (jnp.asarray(sets), jnp.asarray(node), jnp.asarray(value))
    for impl in ("gather", "select", "product"):
        got = np.asarray(categorical.set_table_lookup(*args, impl))
        assert got.dtype == np.int32 and np.array_equal(got, want), impl
    # a column of 32 * words categories makes a node's set `words` words
    tables = categorical.CatTables(CatLayout(["c"], [32 * words], 256))
    assert tables.words == words
    on_chip = np.asarray(tables.set_word(*args, "tpu"))
    assert np.array_equal(on_chip, want)
    assert np.array_equal(np.asarray(tables.set_word(*args, "cpu")), want)


def test_rows_go_the_same_way_over_the_products_word_and_the_gathers():
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.data.categorical import CatLayout
    from sagemaker_xgboost_container_tpu.ops import categorical

    W, words, n = 128, 85, 5000
    sets, node, value = read_case(W, words, n, seed=7)
    tables = categorical.CatTables(CatLayout(["c"], [32 * words], 256))
    rng = np.random.default_rng(8)
    split_bin = jnp.asarray(np.where(rng.random(n) < 0.7, tables.num_bins - 1, 100), jnp.int32)
    default_left = jnp.asarray(rng.random(n) < 0.5)
    args = (jnp.asarray(sets), jnp.asarray(node), jnp.asarray(value))
    ways = {
        impl: np.asarray(tables.go_right(
            args[2], split_bin, default_left, categorical.set_table_lookup(*args, impl)
        ))
        for impl in ("gather", "product")
    }
    assert np.array_equal(ways["gather"], ways["product"])
    assert ways["gather"].any() and not ways["gather"].all()
    # and it is the bit of the row's code in its node's set
    code = value - 1
    bit = (sets[node, np.maximum(code, 0) >> 5] >> (code & 31)) & 1
    at_set = (np.asarray(split_bin) == tables.num_bins - 1) & (code >= 0)
    assert np.array_equal(ways["product"][at_set], bit[at_set] == 1)


def test_an_empty_level_a_level_deeper_than_one_node_tile_and_a_wide_set():
    """No rows: no grid step, zeros of the right shape. Over 128 nodes (a
    level deeper than 7) the node tiles' products add up. A set of more
    words than the probe's takes fewer rows a grid step, in proportion."""
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import categorical

    none = categorical.set_table_lookup(
        jnp.zeros((4, 3), jnp.int32), jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
        "product",
    )
    assert none.shape == (0,) and none.dtype == jnp.int32
    sets, node, value = read_case(512, 19, 3000, seed=11)
    got = categorical.set_table_lookup(
        jnp.asarray(sets), jnp.asarray(node), jnp.asarray(value), "product"
    )
    want = sets[node, np.minimum(np.maximum(value - 1, 0) >> 5, 18)]
    assert np.array_equal(np.asarray(got), want)
    block = categorical.SET_READ_ROW_BLOCK
    assert categorical._set_read_rows(12_184_290, 85) == block
    assert categorical._set_read_rows(12_184_290, 1) == block
    assert categorical._set_read_rows(700, 85) == 768
    assert categorical._set_read_rows(12_184_290, 2048) == 256
    assert categorical._set_read_rows(12_184_290, 16384) == 128
    sets, node, value = read_case(8, 2048, 700, seed=12)
    got = categorical.set_table_lookup(
        jnp.asarray(sets), jnp.asarray(node), jnp.asarray(value), "product"
    )
    want = sets[node, np.minimum(np.maximum(value - 1, 0) >> 5, 2047)]
    assert np.array_equal(np.asarray(got), want)


# -------------------------------------------------------------- whole builds
def forest_arrays(forest):
    out = []
    for tree in forest.trees:
        out.append({
            field: np.asarray(getattr(tree, field))
            for field in ("feature", "threshold", "default_left", "left", "right", "value",
                          "base_weight", "gain", "sum_hess")
        })
        out[-1]["categories"] = {
            int(node): np.asarray(cats) for node, cats in sorted(tree.categories.items())
        }
    return out


def same_forest(a, b):
    a, b = forest_arrays(a), forest_arrays(b)
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        assert sorted(ta["categories"]) == sorted(tb["categories"])
        for node, cats in ta.pop("categories").items():
            assert np.array_equal(cats, tb["categories"][node])
        tb.pop("categories")
        for field, value in ta.items():
            assert np.array_equal(value, tb[field], equal_nan=value.dtype.kind == "f"), field


def chip_program_session(monkeypatch, lowering, **params):
    """``test_categorical_training``'s table trained and evaluated with every
    lowering the chip's (the kernels interpreted), the set table read by
    ``lowering`` at every level: returns the forest, the log, the margins."""
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.ops import categorical
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs

    reads = []

    def choose(backend, entries):
        reads.append((backend, entries))
        return lowering

    monkeypatch.setattr(categorical, "choose_set_read_impl", choose)
    x, y = table(0, n=1200)
    ex, ey = table(1, n=700)
    ex[:300, 4] = np.repeat([-1.0, 600.0, 5000.0, -0.5, 1e9, np.nan], 50)
    ex[300:400, 2] = np.repeat([12.0, 31.0, 32.0, 255.0, -3.0], 20)
    if params.get("num_class"):
        y = (y + (x[:, 0] > 0.5)).astype(np.float32)
        ey = (ey + (ex[:, 0] > 0.5)).astype(np.float32)
    keep = KeepLog()
    dtrain = DataMatrix(x, labels=y, feature_types=TYPES)
    forest = models.train(
        dict(PARAMS, **params), dtrain, num_boost_round=2,
        evals=[(dtrain, "train"), (DataMatrix(ex, labels=ey, feature_types=TYPES), "validation")],
        callbacks=[keep], verbose_eval=False,
        hist_knobs=resolve_hist_knobs()._replace(backend="tpu"),
    )
    assert reads and all(backend == "tpu" for backend, _entries in reads)
    return forest, keep.evals_log, forest.predict_margin(ex)


@pytest.mark.parametrize(
    "params",
    [{}, {"objective": "multi:softmax", "num_class": 3, "eval_metric": "mlogloss"}],
    ids=["one_tree", "three_class_trees"],
)
def test_a_whole_build_and_walk_on_the_product_are_the_gathers(monkeypatch, params):
    """``build_tree`` (the training rows' margins, so the logged loss) and
    ``predict_binned_levels`` (the validation rows') read the set table
    through the product at every level, under the class ``vmap`` too: the
    same trees, the same sets, the same logged losses as through the gather."""
    product = chip_program_session(monkeypatch, "product", **params)
    gather = chip_program_session(monkeypatch, "gather", **params)
    assert sum(len(t.categories) for t in product[0].trees) > 3
    same_forest(product[0], gather[0])
    assert product[1] == gather[1]
    assert np.array_equal(product[2], gather[2])


# ------------------------------------------------- what the lowering follows
def test_the_lowering_follows_the_backend_and_the_tables_entries_alone(monkeypatch):
    import jax

    from sagemaker_xgboost_container_tpu.data.categorical import CatLayout
    from sagemaker_xgboost_container_tpu.ops import categorical

    limit = categorical.SET_READ_SELECT_MAX_ENTRIES
    shapes = [(1, 85), (2, 85), (8, 85), (128, 85), (64, 3), (128, 1), (512, 19)]

    def chosen():
        return {
            (backend, W, words): categorical.choose_set_read_impl(backend, W * words)
            for backend in ("tpu", "cpu", "gpu") for W, words in shapes
        }

    def traced(backend, W, words):
        tables = categorical.CatTables(CatLayout(["c"], [32 * words], 256))
        return str(jax.make_jaxpr(lambda s, n, v: tables.set_word(s, n, v, backend))(
            np.zeros((W, words), np.int32), np.zeros(600, np.int32), np.ones(600, np.int32)
        ))

    before = chosen()
    for (backend, W, words), impl in before.items():
        if backend != "tpu":
            assert impl == "gather"
        else:
            assert impl == ("select" if W * words <= limit else "product")
    assert before[("tpu", 128, 85)] == "product"  # the benchmark cell's widest level
    texts = {key: traced(*key) for key in (("tpu", 128, 85), ("tpu", 1, 85), ("cpu", 128, 85))}
    assert "graft_cat_set_read" in texts[("tpu", 128, 85)]
    assert "gather" not in texts[("tpu", 128, 85)] and "gather" not in texts[("tpu", 1, 85)]
    assert "pallas_call" not in texts[("cpu", 128, 85)]

    # no environment variable is read: every GRAFT_* name the package knows, set
    names = set()
    package = os.path.join(ROOT, "sagemaker_xgboost_container_tpu")
    for folder, _dirs, files in os.walk(package):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    names.update(re.findall(r"\bGRAFT_[A-Z0-9_]+\b", f.read()))
    assert "GRAFT_HIST_MM_PREC" in names
    for name in sorted(names) + ["GRAFT_CAT_SET_READ", "GRAFT_SET_READ_IMPL"]:
        monkeypatch.setenv(name, "bf16")
    assert chosen() == before
    assert {key: traced(*key) for key in texts} == texts
