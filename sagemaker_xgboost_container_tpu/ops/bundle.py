"""The device half of the bundled layout: split scan and row routing over
bin columns that hold several original columns each.

``data/bundling.py`` lays mutually exclusive columns of a sparse matrix side
by side in one dense bin column (a *bundle*): every member keeps its own
range ``[lo, hi)`` of the bundle's bin positions, a row sits at the position
of the one member it holds a value of, and in the bundle's missing slot where
it holds none. The level histogram kernel reads such a matrix as any other.
What changes is what a position means:

* **the scan** (``BundleTables.find_best_splits``) runs over positions and
  judges each *original* column: a split at position ``p`` of a member sends
  left the member's positions up to ``p``; the member's *missing* sums are the
  bundle's missing slot plus the sums over the other members' ranges (a row
  absent from this column sits in one or the other), added up where a dense
  session reads the missing slot alone: a bundle of one reads the same sums;
* **the routing** (``absent``) is a range test: a row whose bin lies outside
  the split member's range is absent from that column and follows the split's
  ``default_left``; inside it the bin is compared with the split position.

The trees a bundled build makes hold ``(bundle, position)`` in ``feature`` and
``bin``; ``data/bundling.py::BundlePlan.original_splits`` turns them into
original column ids and the column's own bin on the host.

A dense session never traces any of this: ``ops/tree_build.py`` takes these
branches only where it is handed a ``BundleTables``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from .split import _score

RANGE_BITS = 9  # a range end is at most 256 (257 bins, the last one missing)


class BundleTables:
    """What the traced build reads of a bundle plan, as static numpy tables
    over ``[bundles, positions]`` (positions: the data bins, the missing slot
    left out): the member's range ``lo`` / ``hi`` of every position, whether a
    split may sit there (``legal``: the member has a cut there and a position
    above or the missing sums to send right), and the original ``column``
    (-1: no member holds the position)."""

    def __init__(self, lo, hi, legal, column):
        self.lo = np.asarray(lo, np.int32)
        self.hi = np.asarray(hi, np.int32)
        self.legal = np.asarray(legal, bool)
        self.column = np.asarray(column, np.int32)
        if self.hi.max(initial=0) >= 1 << RANGE_BITS:
            raise ValueError("a bundle's positions do not fit the range word")

    @property
    def reach(self):
        """[bundles] int32: a bundle's highest position, what a dense
        column's count of cuts is to the level histogram (the bins its rows
        can sit in, the missing slot apart)."""
        return self.hi.max(axis=1, initial=1) - 1

    @property
    def range_words(self):
        """[bundles, positions] int32: ``lo`` and ``hi`` of a position's
        member as one word, what a row reads of its node beside the split."""
        return (self.lo << RANGE_BITS) | self.hi

    @staticmethod
    def absent(row_bin, range_word):
        """A row whose bin lies outside the member's range holds no value of
        the split's column (the bundle's missing slot lies above every range)."""
        lo = range_word >> RANGE_BITS
        hi = range_word & ((1 << RANGE_BITS) - 1)
        return (row_bin < lo) | (row_bin >= hi)

    @classmethod
    def go_right(cls, row_bin, range_word, split_bin, default_left):
        """Where a row goes at a split of the member whose range the word
        holds: absent from the column, where the split's ``default_left``
        says; else by its position against the split's."""
        return jnp.where(cls.absent(row_bin, range_word), ~default_left, row_bin > split_bin)

    def node_ranges(self, feature, position, gathers=True):
        """The range word of each node's split, looked up per node (a node
        table long, never a row's read). ``gathers`` False, for a build or a
        walk mapped over class trees: a compare-select-reduce over the table,
        the same words (``ops.split.find_best_splits`` says why)."""
        words = jnp.asarray(self.range_words)
        if gathers:
            return words[feature, position]
        flat = words.reshape(-1)
        wanted = (feature * words.shape[1] + position)[..., None] == jnp.arange(
            flat.shape[0], dtype=jnp.int32
        )
        return jnp.sum(jnp.where(wanted, flat, 0), axis=-1)

    def find_best_splits(
        self,
        G,
        H,
        num_cuts=None,
        reg_lambda=1.0,
        alpha=0.0,
        gamma=0.0,
        min_child_weight=1.0,
        feature_mask=None,
        monotone=None,
        gathers=True,
    ):
        """``ops.split.find_best_splits`` over bundles: G, H f32
        ``[W, bundles, B]`` (B includes the missing slot). ``num_cuts`` is
        not read (the plan's ``legal`` holds it); ``feature_mask``: f32
        ``[original columns]``, 1 = usable. Returns the same dict with
        ``feature`` = bundle, ``bin`` = position, and ``range``, the member's
        range word a node."""
        if monotone is not None:
            raise ValueError("the bundled scan takes no monotone constraints")
        W, d, B = G.shape
        nbins = B - 1
        # node totals: every row sits in exactly one slot of bundle 0
        g_total = G[:, 0, :].sum(axis=-1)
        h_total = H[:, 0, :].sum(axis=-1)

        lo = jnp.asarray(self.lo)[:, None, :]            # [d, 1, p]
        hi = jnp.asarray(self.hi)[:, None, :]
        q = jnp.arange(nbins, dtype=jnp.int32)[None, :, None]
        p = jnp.arange(nbins, dtype=jnp.int32)[None, None, :]
        in_range = (q >= lo) & (q < hi)                  # [d, q, p]
        up_to = (in_range & (q <= p)).astype(jnp.float32)
        # the other members' positions: rows that hold one of them are absent
        # from this one (positions no member holds are empty: hi is 0 there)
        others = (~in_range & (hi > 0)).astype(jnp.float32)

        def sums(X, mask):
            # 0/1 weights: each product is exact, the sums are f32
            return jnp.einsum(
                "wdq,dqp->wdp", X[:, :, :nbins], mask,
                precision=jax.lax.Precision.HIGHEST,
            )

        g_left, h_left = sums(G, up_to), sums(H, up_to)
        # absent from the member: the bundle's missing slot and the other
        # members' rows, added up (a bundle of one reads its slot alone, as a
        # dense column does; nothing is got by subtraction)
        g_miss = G[:, :, nbins, None] + sums(G, others)
        h_miss = H[:, :, nbins, None] + sums(H, others)

        parent = _score(g_total, h_total, reg_lambda, alpha)[:, None, None]

        def _gain(gl, hl):
            gr = g_total[:, None, None] - gl
            hr = h_total[:, None, None] - hl
            ok = (hl >= min_child_weight) & (hr >= min_child_weight)
            raw = 0.5 * (
                _score(gl, hl, reg_lambda, alpha)
                + _score(gr, hr, reg_lambda, alpha)
                - parent
            ) - gamma
            return jnp.where(ok, raw, -jnp.inf)

        gain_right = _gain(g_left, h_left)               # absent -> right
        gain_left = _gain(g_left + g_miss, h_left + h_miss)

        legal = jnp.asarray(self.legal)
        if feature_mask is not None:
            if feature_mask.ndim != 1:
                raise ValueError("the bundled scan takes a per-column mask only")
            usable = feature_mask[jnp.asarray(np.maximum(self.column, 0))] > 0
            legal = legal & usable
        legal = legal[None, :, :]
        gain_right = jnp.where(legal, gain_right, -jnp.inf)
        gain_left = jnp.where(legal, gain_left, -jnp.inf)

        take_left = gain_left > gain_right
        gain = jnp.where(take_left, gain_left, gain_right)

        flat = gain.reshape(W, d * nbins)
        best_idx = jnp.argmax(flat, axis=1)
        best_feature = (best_idx // nbins).astype(jnp.int32)
        best_bin = (best_idx % nbins).astype(jnp.int32)
        if gathers:
            best_gain = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
            best_default_left = jnp.take_along_axis(
                take_left.reshape(W, d * nbins), best_idx[:, None], axis=1
            )[:, 0]
        else:  # mapped over class trees: ops.split.find_best_splits says why
            best_gain = flat.max(axis=1)
            at_best = (
                jnp.arange(d * nbins, dtype=best_idx.dtype)[None, :] == best_idx[:, None]
            )
            best_default_left = (take_left.reshape(W, d * nbins) & at_best).any(axis=1)

        return {
            "gain": jnp.where(jnp.isfinite(best_gain), best_gain, -jnp.inf),
            "feature": best_feature,
            "bin": best_bin,
            "default_left": best_default_left,
            "g_total": g_total,
            "h_total": h_total,
            "range": self.node_ranges(best_feature, best_bin, gathers=gathers),
        }
