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
  The key of that read is (node, word): on the TPU it is a matrix product,
  the one-hot of the row's node times the table as bytes, then a pick of the
  row's own word (``set_table_lookup``, the kernel ``graft_cat_set_read``),
  where a select pass over the flattened table pays for every entry.

A tree such a build makes holds the *original* column in ``feature``, the
marker ``num_bins - 1`` in ``bin`` where the split is a set, and the set's
words in ``cat_words``. A session without a categorical column never traces
any of this: ``ops/tree_build.py`` takes these branches only where it is
handed a ``CatTables``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.device import STAGE_CAT_SCAN, stage
from . import histogram
from .split import _score, find_best_splits

# Widest set table (a level's nodes x words) the TPU reads (this cell's widest
# is 128 x 85 = 10,880 entries). The product below would hold a wider one, but
# no chip run has measured or soaked a wider table, so the session refuses the
# job (`set_table_fits`) where the backend is the TPU. The CPU reads any width
# by the gather.
SET_TABLE_SELECT_MAX_ENTRIES = 1 << 14

# Widest set table the TPU still reads by the select pass over the flat table
# (`node_table_lookup`); a wider one is read as a matrix product
# (`_set_read_fn`). ns a row and level on one v5e (scripts/dissect.py
# --cat-set-read: `allstate-cat-d8`'s 12,184,290 train rows and 1,000,000
# evaluation rows, 85 words a node, every pair bit-equal; PR 51, PERF.md
# section 6):
#
#   level  entries    gather         select           product
#     0        85    8.24 / 8.32    0.12 / 0.19    0.55 / 0.68
#     1       170    8.59 / 8.67    0.19 / 0.29    0.55 / 0.68
#     2       340    8.58 / 8.67    0.32 / 0.35    0.55 / 0.68
#     3       680    5.62 / 5.71    0.68 / 0.64    0.55 / 0.68   <- the product from here
#     4     1,360    7.50 / 7.58    1.35 / 1.18    0.55 / 0.68
#     5     2,720    7.50 / 7.57    2.08 / 2.04    0.55 / 0.68
#     6     5,440    7.50 / 7.58    6.56 / 3.99    0.55 / 0.68
#     7    10,880    7.50 / 7.57   11.63 / 14.39   0.55 / 0.68
#
# The select pass costs per entry (22.9 ns a row over the eight levels, 279 ms
# a round of 12.18M rows); the product costs the same at every level, because
# a narrower table comes padded to the 128 nodes the MXU contracts anyway.
SET_READ_SELECT_MAX_ENTRIES = 512

# Rows a grid step of the product takes (lanes of its blocks) where a node's
# set holds at most 88 words, fewer in proportion for a wider set (the step's
# f32 product is ``[4 x words, rows]``: 11.5 MB of VMEM here); nodes a product
# contracts (the MXU's depth). ms a level over 12,184,290 rows by rows a grid
# step and rows a product inside the step (the same probe's tables, levels 0
# and 7 alike; my chip runs, PR 51): (512, 512) 11.29, (2,048, 512) 10.12,
# (8,192, 512) 9.72, (8,192, 1,024) 8.18, (2,048, 2,048) 7.76, (8,192, 2,048)
# 7.34, (4,096, 4,096) 6.93, (8,192, 4,096) 6.87, **(8,192, 8,192) 6.67**,
# (16,384, 8,192) 6.63, (16,384, 16,384) 6.60: one product a step, no loop
# inside it.
SET_READ_ROW_BLOCK = 8192
SET_READ_NODE_TILE = 128


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
        ``words`` i32 ``[W, words]`` at ``[node, code // 32]`` (a row without
        a code reads word 0, which ``go_right`` never asks), in the lowering
        ``choose_set_read_impl`` picks from the backend and the table's
        static shape."""
        impl = choose_set_read_impl(backend, words.shape[0] * words.shape[1])
        return set_table_lookup(words, local_safe, value, impl)

    def go_right(self, value, split_bin, default_left, word):
        """Where a row goes: missing, where ``default_left`` says; at a set
        split, right iff its code's bit is set (no category: left); at a
        threshold split, by its bin against the split's."""
        code = value - 1
        in_set = (code >= 0) & (((word >> (code & 31)) & 1) == 1)
        by_split = jnp.where(split_bin == self.num_bins - 1, in_set, code > split_bin)
        return jnp.where(value == 0, ~default_left, by_split)


def choose_set_read_impl(backend, entries):
    """The lowering ``CatTables.set_word`` takes for a level's set table of
    ``entries`` = nodes x words entries (static at trace time) on
    ``backend``: the select pass costs rows x entries and the product rows x
    128 x words whatever the level, and only the TPU serializes gathers."""
    if backend != "tpu":
        return "gather"
    return "select" if entries <= SET_READ_SELECT_MAX_ENTRIES else "product"


def _word_of(value, words):
    """The word of a set that holds a row's code (``value`` - 1); a row
    without a code reads word 0."""
    return jnp.minimum(jnp.maximum(value - 1, 0) >> 5, words - 1)


def _byte_planes(words):
    """A level's set table i32 ``[W, words]`` as the product's left operand,
    bf16 ``[4 * plane, W padded to whole node tiles]``, ``plane`` = ``words``
    padded to whole sublane tiles: row ``k * plane + w`` holds byte ``k`` of
    word ``w`` of every node's set, 0..255, which bf16 holds exactly."""
    W, count = words.shape
    shifts = jnp.arange(0, 32, 8, dtype=jnp.int32)[:, None, None]
    planes = (words.T[None, :, :] >> shifts) & 0xFF            # [4, words, W]
    plane = histogram._round_up(count, 8)
    nodes = histogram._round_up(W, SET_READ_NODE_TILE)
    planes = jnp.pad(planes, ((0, 0), (0, plane - count), (0, nodes - W)))
    return planes.reshape(4 * plane, nodes).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _set_read_fn(n, words, nodes, block, interpret):
    """Compiled read of a set table as a matrix product, rows on the lanes:
    (table bf16 ``[4 * plane, nodes]`` (``_byte_planes``), node i32 ``[1,
    n]``, value i32 ``[1, n]``) -> i32 ``[1, n]``, each row's ``words[node,
    (value - 1) // 32]``.

    A grid step takes ``block`` rows: the one-hot of the rows' nodes
    (``[128, block]``, 0/1 in bf16) times the table gives every byte of the
    node's set (``[4 * plane, block]`` in f32: one non-zero term a sum, so
    exact), the row's own word is picked off each byte plane by a
    compare-select-reduce over ``plane`` sublanes, and the four bytes are
    put back at their places. ``nodes`` over 128 (a level deeper than 7)
    adds the products of its node tiles. Nothing of the body follows the
    level: a narrower table comes padded to one node tile, so the levels of
    a build share one Mosaic body and the evaluation rows' count makes a
    second. The last step's rows past ``n`` read what lies there and are
    not written back."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    plane = histogram._round_up(words, 8)
    tile = SET_READ_NODE_TILE
    node_tiles = nodes // tile

    def kernel(table_ref, node_ref, value_ref, out_ref):
        node = node_ref[...]                                   # [1, block]
        node_iota = jax.lax.broadcasted_iota(jnp.int32, (tile, block), 0)

        def product(table, first):          # a node tile of the table, its first node
            onehot = (node_iota == node - first).astype(jnp.bfloat16)
            return jnp.dot(table, onehot, preferred_element_type=jnp.float32)

        if node_tiles == 1:
            held = product(table_ref[...], 0)                  # [4 * plane, block]
        else:
            held = jax.lax.fori_loop(
                0, node_tiles,
                lambda k, acc: acc + product(
                    table_ref[:, pl.ds(pl.multiple_of(k * tile, tile), tile)], k * tile
                ),
                jnp.zeros((4 * plane, block), jnp.float32),
            )
        at = _word_of(value_ref[...], words)
        mine = jax.lax.broadcasted_iota(jnp.int32, (plane, block), 0) == at
        word = jnp.zeros((1, block), jnp.int32)
        for k in range(4):
            byte = jnp.sum(
                jnp.where(mine, held[k * plane:(k + 1) * plane], 0.0), axis=0, keepdims=True
            )
            word = word | (byte.astype(jnp.int32) << (8 * k))
        out_ref[...] = word

    rows = pl.BlockSpec((1, block), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block),),
        in_specs=[pl.BlockSpec((4 * plane, nodes), lambda i: (0, 0)), rows, rows],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a step's one-hot and its product, in f32 and with room to spare
            vmem_limit_bytes=min(
                16 * (tile + 4 * plane) * block + 16 * 1024 * 1024, 100 * 1024 * 1024
            ),
        ),
        interpret=interpret,
        name="graft_cat_set_read",
    )


def _set_read_rows(n, words):
    """Rows a grid step of ``_set_read_fn`` takes: ``SET_READ_ROW_BLOCK``
    up to 88 words a node, fewer in proportion beyond, whole lane tiles and
    no more than the rows there are."""
    plane = histogram._round_up(words, 8)
    fit = SET_READ_ROW_BLOCK * min(plane, 88) // plane
    return min(max(fit // 128, 1) * 128, histogram._round_up(n, 128))


def set_table_lookup(words, node, value, impl):
    """``words[node[i], (value[i] - 1) // 32]`` of a level's set table i32
    ``[W, words]``, ``node`` inside the table. Three lowerings, the same 32
    bits (a word whose bit 31 is set stays negative):

    * ``gather``: the indexed gather of the flat table.
    * ``select``: ``node_table_lookup``'s select pass over the flat table,
      rows x W x words compare-select-adds.
    * ``product``: ``_set_read_fn``, rows x 128 x 4 x words multiply-adds on
      the MXU and a pick over ``words``, whatever the level.

    ``impl``: a lowering by name, as ``choose_set_read_impl`` gives it."""
    count = words.shape[1]
    if impl == "product":
        n = node.shape[0]
        if n == 0:  # a grid of no step would hand back an unwritten buffer
            return jnp.zeros((0,), jnp.int32)
        table = _byte_planes(words)
        fn = _set_read_fn(
            n, count, table.shape[1], _set_read_rows(n, count), histogram.pallas_interpret()
        )
        return fn(table, node[None, :], value[None, :])[0]
    from .tree_build import node_table_lookup

    at = _word_of(value, count)
    return node_table_lookup(words.reshape(-1), node * count + at, impl=impl)


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
