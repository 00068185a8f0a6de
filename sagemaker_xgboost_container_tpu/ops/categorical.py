"""The device half of categorical training: the partition scan over a level
histogram and the set test in row routing.

``data/categorical.py`` lays a categorical column's codes out as positions of
its own bin columns (its *chunks*), so a level histogram ``[W, bin columns,
B]`` holds every category's gradient sums at a static place. What changes is
what a split is:

* **the scan** (``CatTables.find_best_splits``) runs ``ops.split``'s scan for
  the numeric columns (a chunk has no legal threshold: its count of cuts is
  given as 0) and, under the stage ``cat_scan``, a partition scan for the
  categorical ones. A column of fewer than ``max_cat_to_onehot`` categories
  tries each category alone (one against the rest). Any other orders its
  categories by ``g / (h + lambda)`` and tries the first ``k`` of the order
  as a set, from both ends of the order, ``k`` up to the categories the node
  holds less one and at most ``max_cat_threshold``; the scanned set goes
  **right**, a row whose value is missing is tried on both sides, both
  children hold ``min_child_weight``. Score and stored gain are
  ``ops.split``'s. The best candidate over all columns wins the node.
  A category's place in its node's order is the count of the categories in
  front of it (``_rank``: a fused compare-and-count, no sort, no gather and
  no scatter), a candidate's sums a masked sum over the places, and the
  winner's set is read back off the places.
* **the routing** (``row_value``, ``set_word``, ``go_right``): a row reads
  its value of the node's split column in one dense pass over its bin
  columns (the numeric bin, or the code its chunk and position give), the
  word of the node's set that holds its code from the level's
  ``[2**level, words]`` table, and goes right iff its bit is set; a missing
  value follows ``default_left``, a value that is no category goes left.

A tree such a build makes holds the *original* column in ``feature``, the
marker ``num_bins - 1`` in ``bin`` where the split is a set, and the set's
words in ``cat_words``. A session without a categorical column never traces
any of this: ``ops/tree_build.py`` takes these branches only where it is
handed a ``CatTables``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.device import STAGE_CAT_SCAN, stage
from .split import _score, find_best_splits

# Widest set table (a level's nodes x words) the TPU reads, by the select pass
# (`node_table_lookup`; this cell's widest is 128 x 85 = 10,880 entries, 313 ms
# a round). A wider one would need a row-length gather of the table, which no
# chip run has measured or soaked for this table, so the session refuses the
# job (`set_table_fits`) where the backend is the TPU. The CPU reads any
# width by the gather.
SET_TABLE_SELECT_MAX_ENTRIES = 1 << 14


def set_table_fits(words, max_depth, backend):
    """Whether the widest level's set table (``2**(max_depth - 1)`` nodes of
    ``words`` words) is one ``set_word`` reads on ``backend``."""
    return backend != "tpu" or (words << max(max_depth - 1, 0)) <= SET_TABLE_SELECT_MAX_ENTRIES


class CatTables:
    """What the traced build reads of a ``data.categorical.CatLayout``, as
    static numpy tables, and the two parameters of the partition scan."""

    def __init__(self, layout, max_cat_to_onehot=4, max_cat_threshold=64):
        self.col_feature = layout.col_feature
        self.col_first = layout.col_first
        self.col_count = layout.col_count
        self.col_is_cat = layout.col_is_cat
        self.num_bins = layout.max_bin + 1
        self.words = layout.set_words
        self.cap = int(max_cat_threshold)
        # a chunk's highest position a training row can sit in; 0 for a
        # numeric column, whose reach is its traced count of cuts
        self.chunk_reach = np.where(
            layout.col_is_cat, np.maximum(layout.col_count - 1, 0), 0
        ).astype(np.int32)
        # (column, categories, chunks) of the columns that can split at all
        able = [
            (f, layout.cardinalities[f], tuple(int(c) for c in layout.chunks(f)))
            for f in range(layout.num_col)
            if layout.feature_types[f] == "c" and layout.cardinalities[f] >= 2
        ]
        self.onehot = [a for a in able if a[1] < int(max_cat_to_onehot)]
        self.sorted = [a for a in able if a[1] >= int(max_cat_to_onehot)]
        # candidates a row of the scan holds: a prefix length or a lone category
        self.candidates = max([self.cap] + [c for _f, c, _ in self.onehot])

    @property
    def has_candidates(self):
        return bool(self.onehot or self.sorted)

    # ------------------------------------------------------------- the scan
    def _category_sums(self, X, chunks):
        """[W, C]: a column's per-category sums, its chunks side by side."""
        return jnp.concatenate([X[:, c, : self.col_count[c]] for c in chunks], axis=-1)

    def _missing_sums(self, X, total, chunks, by_category):
        """[W]: the sums of the rows that hold no value of the column: the
        missing slot of a column of one chunk, read as a numeric column's is;
        of several chunks, the node's total less the categories' own."""
        if len(chunks) == 1:
            return X[:, chunks[0], self.num_bins - 1]
        return total - by_category.sum(axis=-1)

    def find_best_splits(
        self,
        G,
        H,
        num_cuts,
        reg_lambda=1.0,
        alpha=0.0,
        gamma=0.0,
        min_child_weight=1.0,
        feature_mask=None,
        monotone=None,
        gathers=True,
    ):
        """``ops.split.find_best_splits`` over numeric and categorical
        columns: G, H f32 ``[W, bin columns, B]``; ``num_cuts`` i32 ``[bin
        columns]`` with 0 at every chunk. Returns the same dict with
        ``feature`` the *original* column, ``bin`` = ``num_bins - 1`` where
        the split is a set, and ``cat_words`` i32 ``[W, words]``, the set that
        goes right (zeros at a threshold split)."""
        best = find_best_splits(
            G, H, num_cuts, reg_lambda=reg_lambda, alpha=alpha, gamma=gamma,
            min_child_weight=min_child_weight, feature_mask=feature_mask,
            monotone=monotone, gathers=gathers,
        )
        columns = jnp.asarray(self.col_feature)
        best["feature"] = _pick(columns, best["feature"])
        W = G.shape[0]
        best["cat_words"] = jnp.zeros((W, self.words), jnp.int32)
        if not self.has_candidates:
            return best
        with stage(STAGE_CAT_SCAN):
            cat = self._partition_scan(
                G, H, best["g_total"], best["h_total"], reg_lambda, alpha, gamma,
                min_child_weight,
            )
            # a tie stays with the threshold split
            use = cat["gain"] > best["gain"]
            return {
                "gain": jnp.where(use, cat["gain"], best["gain"]),
                "feature": jnp.where(use, cat["feature"], best["feature"]),
                "bin": jnp.where(use, self.num_bins - 1, best["bin"]),
                "default_left": jnp.where(use, cat["default_left"], best["default_left"]),
                "g_total": best["g_total"],
                "h_total": best["h_total"],
                "cat_words": jnp.where(use[:, None], cat["words"], 0),
            }

    def _partition_scan(self, G, H, g_total, h_total, reg_lambda, alpha, gamma, min_child_weight):
        W = G.shape[0]
        L = self.candidates
        k = jnp.arange(1, L + 1, dtype=jnp.int32)

        def pad(x):  # [W, <= L] -> [W, L]
            return jnp.pad(x, ((0, 0), (0, L - x.shape[1])))

        # rows of the scan, (column, the sets' g, the sets' h, legal, missing g,
        # missing h) each: a sorted column takes two (the order's low end, its
        # high end), a one-against-the-rest column one
        rows = []
        ranks = []  # per sorted column: (present, rank, held), what its sets are read from
        for f, C, chunks in self.sorted:
            g, h = self._category_sums(G, chunks), self._category_sums(H, chunks)
            present = h > 0
            rank = _rank(jnp.where(present, g / (h + reg_lambda), jnp.inf))
            held = present.sum(axis=1, dtype=jnp.int32)   # categories the node holds
            ranks.append((present, rank, held))
            g_m = self._missing_sums(G, g_total, chunks, g)
            h_m = self._missing_sums(H, h_total, chunks, h)
            # the first k of the order, k <= held - 1 (a proper set) and <= cap
            fits = (k[None, :] <= held[:, None] - 1) & (k[None, :] <= self.cap)
            for low_end in (True, False):
                inside = _prefix(
                    present[:, :, None], rank[:, :, None], held[:, None, None], k, low_end
                )
                rows.append((
                    f,
                    jnp.sum(jnp.where(inside, g[:, :, None], 0.0), axis=1),
                    jnp.sum(jnp.where(inside, h[:, :, None], 0.0), axis=1),
                    fits, g_m, h_m,
                ))
        for f, C, chunks in self.onehot:
            g, h = self._category_sums(G, chunks), self._category_sums(H, chunks)
            rows.append((
                f, pad(g), pad(h), jnp.broadcast_to(k[None, :] <= C, (W, L)),
                self._missing_sums(G, g_total, chunks, g),
                self._missing_sums(H, h_total, chunks, h),
            ))

        row_feature, g_set, h_set, ok, g_miss, h_miss = (
            column if i == 0 else jnp.stack(column, axis=1) for i, column in enumerate(zip(*rows))
        )                                                   # [W, R, L], missing [W, R]
        g_miss, h_miss = g_miss[..., None], h_miss[..., None]
        R = g_set.shape[1]
        parent = _score(g_total, h_total, reg_lambda, alpha)[:, None, None]

        def _gain(gr, hr):
            gl = g_total[:, None, None] - gr
            hl = h_total[:, None, None] - hr
            fits = ok & (hl >= min_child_weight) & (hr >= min_child_weight)
            raw = 0.5 * (
                _score(gl, hl, reg_lambda, alpha) + _score(gr, hr, reg_lambda, alpha) - parent
            ) - gamma
            return jnp.where(fits, raw, -jnp.inf)

        gain_left = _gain(g_set, h_set)                      # missing -> left
        gain_right = _gain(g_set + g_miss, h_set + h_miss)   # missing -> right, with the set
        take_left = gain_left > gain_right
        flat = jnp.where(take_left, gain_left, gain_right).reshape(W, R * L)
        best_idx = jnp.argmax(flat, axis=1).astype(jnp.int32)
        best_gain = flat.max(axis=1)  # argmax is the first maximum: the value there
        at_best = jnp.arange(R * L, dtype=jnp.int32)[None, :] == best_idx[:, None]
        default_left = (take_left.reshape(W, R * L) & at_best).any(axis=1)
        row, size = best_idx // L, best_idx % L + 1

        # the winner's set, read back off its column's order
        words = jnp.zeros((W, self.words), jnp.int32)
        for j, (present, rank, held) in enumerate(ranks):
            inside = jnp.where(
                (row == 2 * j)[:, None],
                _prefix(present, rank, held[:, None], size[:, None], True),
                _prefix(present, rank, held[:, None], size[:, None], False),
            )
            won = (row == 2 * j) | (row == 2 * j + 1)
            words = jnp.where(won[:, None], _pack_bits(inside, self.words), words)
        for j, (_f, C, _chunks) in enumerate(self.onehot):
            # the lone category is the candidate's own position
            inside = jnp.arange(1, C + 1, dtype=jnp.int32)[None, :] == size[:, None]
            won = row == 2 * len(ranks) + j
            words = jnp.where(won[:, None], _pack_bits(inside, self.words), words)
        return {
            "gain": jnp.where(jnp.isfinite(best_gain), best_gain, -jnp.inf),
            "feature": _pick(jnp.asarray(np.asarray(row_feature, np.int32)), row),
            "default_left": default_left,
            "words": words,
        }

    # ---------------------------------------------------------- the routing
    def row_value(self, bins, split_feat):
        """What each row holds of its split column (``split_feat`` ``[n]``
        i32, an original column), ``[n]`` i32: 0 where it is missing, -1
        where it is a number but no category of the column, else one more
        than the numeric bin or the category's code. One dense pass over the
        row's bin columns, as ``row_bin_lookup``'s: at most one chunk of a
        column holds the row."""
        feature = jnp.asarray(self.col_feature)[None, :]
        first = jnp.asarray(self.col_first + 1)[None, :]
        count = jnp.asarray(self.col_count)[None, :]
        b = bins.astype(jnp.int32)
        held = jnp.where(b < count, first + b, -1)
        mine = (split_feat[:, None] == feature) & (b != self.num_bins - 1)
        return jnp.sum(jnp.where(mine, held, 0), axis=1)

    def set_word(self, words, local_safe, value, backend):
        """The word of each row's node's set that holds the row's code:
        ``words`` i32 ``[W, words]`` read flat by ``node * words + code // 32``
        (a row without a code reads word 0, which ``go_right`` never asks)."""
        from .tree_build import node_table_lookup

        at = jnp.minimum(jnp.maximum(value - 1, 0) >> 5, self.words - 1)
        # one lowering a backend: the session has refused a table the TPU's
        # select pass does not hold (`set_table_fits`)
        impl = "select" if backend == "tpu" else "gather"
        return node_table_lookup(words.reshape(-1), local_safe * self.words + at, impl=impl)

    def go_right(self, value, split_bin, default_left, word):
        """Where a row goes: missing, where ``default_left`` says; at a set
        split, right iff its code's bit is set (no category: left); at a
        threshold split, by its bin against the split's."""
        code = value - 1
        in_set = (code >= 0) & (((word >> (code & 31)) & 1) == 1)
        by_split = jnp.where(split_bin == self.num_bins - 1, in_set, code > split_bin)
        return jnp.where(value == 0, ~default_left, by_split)


def _rank(key):
    """f32 ``[W, C]`` -> i32 ``[W, C]``: each category's place in its node's
    ascending order of (key, code), by counting the categories in front of
    it: C * C compares a node in one fused reduce, no sort (a multi-operand
    sort a level compiles for a minute on the chip's compiler) and no
    gather. A node's absent categories (key +inf) come last."""
    code = jnp.arange(key.shape[1], dtype=jnp.int32)
    ahead, behind = key[:, :, None], key[:, None, :]
    before = (ahead < behind) | ((ahead == behind) & (code[None, :, None] < code[None, None, :]))
    return before.sum(axis=1, dtype=jnp.int32)


def _prefix(present, rank, held, k, low_end):
    """Whether each category is among the ``k`` of its node's order's low end
    (the first ``k``) or high end (the last ``k`` of the held ones); the
    arguments broadcast against each other."""
    return present & (rank < k if low_end else rank >= held - k)


def _pick(table, index):
    """``table[index]`` of a short static table, as a compare-select-reduce
    (``ops.split.find_best_splits`` says why no gather)."""
    at = index[..., None] == jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(at, table, 0), axis=-1).astype(table.dtype)


def _pack_bits(inside, words):
    """bool ``[W, C]`` -> i32 ``[W, words]``, bit ``c % 32`` of word ``c // 32``."""
    W, C = inside.shape
    bits = jnp.pad(inside, ((0, 0), (0, words * 32 - C))).reshape(W, words, 32)
    packed = jnp.sum(
        bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32), axis=-1, dtype=jnp.uint32
    )
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


# ------------------------------------------------------- the packed tree
def pack_set_words(words):
    """A tree's ``cat_words`` i32 ``[..., nodes, words]`` as rows of the
    round's one packed f32 array, ``[2 * words, ..., nodes]``: each word's
    low and high 16 bits, which a float holds exactly."""
    rows = jnp.moveaxis(words, -1, 0)
    return jnp.concatenate([rows & 0xFFFF, (rows >> 16) & 0xFFFF]).astype(jnp.float32)


def unpack_set_words(rows, xp=jnp):
    """``pack_set_words`` back: f32 ``[2 * words, ..., nodes]`` -> i32
    ``[..., nodes, words]`` (``xp``: numpy on the host)."""
    half = rows.shape[0] // 2
    low, high = rows[:half].astype(xp.int32), rows[half:].astype(xp.int32)
    return xp.moveaxis(low | (high << 16), 0, -1)
