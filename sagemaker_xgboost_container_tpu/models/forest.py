"""Forest: the trained model — host representation + xgboost JSON codec.

The model artifact stays **xgboost-compatible** (SURVEY.md §7 layer 3): we
serialize to the public xgboost JSON schema so (a) the serving contract keeps
the ``xgboost-model`` file name/format (reference xgb_constants.py:96), and
(b) models trained elsewhere with real xgboost load into our XLA predictor.

Host side each tree is compact arrays (left/right children, split feature,
float threshold, default_left, values); for inference the forest stacks into
padded [T, N] device arrays consumed by ops.predict. Trees coming out of the
trainer arrive in the padded full-binary layout with *bin* splits and are
compacted here, converting bins to float thresholds via the binning cuts
(bin(v) <= b  <=>  v < cuts[b] by construction — data/binning.py).
"""

import json

import numpy as np

from ..ops.predict import forest_predict_margin, host_predict_margin
from ..toolkit import exceptions as exc
from . import objectives as objectives_mod


def predict_bucket(n):
    """Power-of-two row bucket the device predict path pads to — the single
    source of truth shared by predict_margin and the serving warmup (which
    pre-compiles exactly these buckets)."""
    return max(8, 1 << (int(n - 1).bit_length())) if n else 8


def _host_predict_rows():
    """Row-count cutover below which prediction runs the numpy host path
    instead of the compiled device kernel (0 disables). Default 32: host
    traversal of a few rows costs microseconds while any device dispatch
    pays a host<->device round trip. The crossover is not measured on this
    chip (chip_smoke.py's serve phase drives both sides of the cutover; a
    serving cell of benchmark/run.py would measure it: PERF.md section 7)."""
    from ..utils.envconfig import env_int

    return env_int("GRAFT_HOST_PREDICT_ROWS", 32)


class Tree:
    """One decision tree, compact arrays, xgboost node ordering (root = 0).

    ``categories``: optional dict {node_id: int array} for partition-based
    categorical splits (xgboost ``enable_categorical``). Stored categories
    are the set that routes to the RIGHT child (xgboost
    common::Decision semantics: category in set -> not default-left branch
    decision -> right); invalid/missing categories follow ``default_left``.
    The trainer makes them for columns given as categories
    (``DataMatrix(feature_types=...)``, ``ops/categorical.py``), and BYO
    xgboost models loaded for serving carry them (reference
    serve_utils.py:171-197 loads any customer model through libxgboost, which
    handles categorical nodes natively).
    """

    def __init__(self, feature, threshold, default_left, left, right, value,
                 base_weight=None, gain=None, sum_hess=None, parent=None,
                 categories=None):
        self.feature = np.asarray(feature, np.int32)
        self.threshold = np.asarray(threshold, np.float32)
        self.default_left = np.asarray(default_left, np.bool_)
        self.left = np.asarray(left, np.int32)
        self.right = np.asarray(right, np.int32)
        self.value = np.asarray(value, np.float32)  # leaf value at leaves
        n = len(self.feature)
        self.base_weight = np.asarray(
            base_weight if base_weight is not None else np.zeros(n), np.float32
        )
        self.gain = np.asarray(gain if gain is not None else np.zeros(n), np.float32)
        self.sum_hess = np.asarray(sum_hess if sum_hess is not None else np.zeros(n), np.float32)
        self.parent = np.asarray(
            parent if parent is not None else _parents_from_children(self.left, self.right),
            np.int32,
        )
        self.categories = {
            int(k): np.asarray(v, np.int64) for k, v in (categories or {}).items()
        }

    @property
    def num_nodes(self):
        return len(self.feature)

    @property
    def has_categorical(self):
        return bool(self.categories)

    def max_category(self):
        return max(
            (int(v.max()) for v in self.categories.values() if len(v)), default=-1
        )

    @property
    def is_leaf(self):
        return self.left < 0

    def depth(self):
        """Max root->leaf depth (host-side, for kernel iteration count). A
        level at a time, in numpy: the training loop asks it of every tree it
        commits (``models/booster.py::note_committed_trees``), inside the
        host's turnaround between two dispatches."""
        depth = 0
        nodes = np.zeros(1, np.int64)
        while True:
            left = self.left[nodes]
            internal = left >= 0
            if not internal.any():
                return depth
            nodes = np.concatenate([left[internal], self.right[nodes][internal]])
            depth += 1


def _parents_from_children(left, right):
    parent = np.full(len(left), 2147483647, np.int32)  # xgboost root parent marker
    for i, (l, r) in enumerate(zip(left, right)):
        if l >= 0:
            parent[l] = i
            parent[r] = i
    return parent


def compact_padded_tree(padded, cut_points):
    """Trainer's padded arrays (numpy) -> compact Tree.

    Keeps only the nodes reachable from the root through the explicit child
    indices and numbers them in increasing padded slot: a parent's slot is
    below its children's in both builders' layouts, so parents come first.
    For ``build_tree``'s heap that is breadth-first order; for a loss-guided
    tree (``ops/lossguide.py``: split step t makes slots 2t+1 and 2t+2) it is
    the order of expansion, as xgboost's own loss-guided updater numbers its
    nodes. Split bin indices become float thresholds via the feature's cut
    array. A categorical build's arrays (``cat_words``: i32 ``[nodes, words]``)
    hold a set split where ``bin`` is past every cut of any column (the
    missing bin's index, ``ops/categorical.py``): such a node keeps the codes
    of its set's bits as its ``categories`` and no threshold.
    """
    is_leaf = np.asarray(padded["is_leaf"])
    feature = np.asarray(padded["feature"])
    bin_idx = np.asarray(padded["bin"])
    default_left = np.asarray(padded["default_left"])
    leaf_value = np.asarray(padded["leaf_value"])
    base_weight = np.asarray(padded["base_weight"])
    gain = np.asarray(padded["gain"])
    sum_hess = np.asarray(padded["sum_hess"])
    set_words = padded.get("cat_words")
    categories = {}
    if set_words is not None:
        from ..data.categorical import words_to_categories
    if "left" in padded:
        child_left = np.asarray(padded["left"])
        child_right = np.asarray(padded["right"])
    else:  # legacy full-binary layout
        ids = np.arange(len(is_leaf), dtype=np.int32)
        child_left, child_right = 2 * ids + 1, 2 * ids + 2

    # reachable slots, then compact ids in increasing slot order
    order = [0]
    for node in order:
        if not is_leaf[node]:
            order += [int(child_left[node]), int(child_right[node])]
    order.sort()
    compact_id = {node: cid for cid, node in enumerate(order)}

    k = len(order)
    out = {
        "feature": np.zeros(k, np.int32),
        "threshold": np.zeros(k, np.float32),
        "default_left": np.zeros(k, np.bool_),
        "left": np.full(k, -1, np.int32),
        "right": np.full(k, -1, np.int32),
        "value": np.zeros(k, np.float32),
        "base_weight": np.zeros(k, np.float32),
        "gain": np.zeros(k, np.float32),
        "sum_hess": np.zeros(k, np.float32),
    }
    for node in order:
        cid = compact_id[node]
        out["base_weight"][cid] = base_weight[node]
        out["sum_hess"][cid] = sum_hess[node]
        if is_leaf[node]:
            out["value"][cid] = leaf_value[node]
        else:
            f = int(feature[node])
            out["feature"][cid] = f
            if set_words is not None and (
                cut_points[f] is None or int(bin_idx[node]) >= len(cut_points[f])
            ):
                categories[cid] = words_to_categories(set_words[node])
            else:
                out["threshold"][cid] = cut_points[f][int(bin_idx[node])]
            out["default_left"][cid] = default_left[node]
            out["left"][cid] = compact_id[int(child_left[node])]
            out["right"][cid] = compact_id[int(child_right[node])]
            out["gain"][cid] = gain[node]
    return Tree(categories=categories, **out)


def _parse_base_score(value):
    """xgboost >= 2.x may store base_score as a vector literal '[5E-1]'."""
    if isinstance(value, str):
        value = value.strip()
        if value.startswith("["):
            value = value.strip("[]").split(",")[0]
    return float(value)


class Forest:
    """The model: trees + objective metadata + prediction entry points."""

    def __init__(self, objective_name="reg:squarederror", objective_params=None,
                 base_score=0.5, num_feature=0, num_class=0, feature_names=None,
                 feature_types=None):
        self.trees = []
        self.tree_info = []  # class id per tree (0 for single-output)
        self.iteration_indptr = [0]
        self.objective_name = objective_name
        self.objective_params = dict(objective_params or {})
        self.base_score = float(base_score)
        self.num_feature = int(num_feature)
        self.num_class = int(num_class)  # 0 = not multiclass (xgboost convention)
        self.feature_names = feature_names
        # xgboost's spelling a column ("c": a category's code), None: all numbers
        self.feature_types = list(feature_types) if feature_types else None
        self.attributes = {}
        self._stacked_cache = None

    # ------------------------------------------------------------------ meta
    @property
    def num_output_group(self):
        return max(1, self.num_class)

    @property
    def num_boosted_rounds(self):
        return len(self.iteration_indptr) - 1

    def objective(self):
        params = dict(self.objective_params)
        if self.num_class:
            params.setdefault("num_class", self.num_class)
        return objectives_mod.create_objective(self.objective_name, params)

    # ------------------------------------------------------------- mutation
    def append_round(self, trees, tree_info):
        """Add one boosting round's trees (list[Tree], list[int] class ids)."""
        self.trees.extend(trees)
        self.tree_info.extend(int(c) for c in tree_info)
        self.iteration_indptr.append(len(self.trees))
        self._stacked_cache = None

    # ------------------------------------------------------------ prediction
    def _stack(self, tree_slice):
        # memoized per (start, stop): serving calls predict per request and a
        # rebuild of the padded [T, N] arrays (a Python loop over every tree)
        # costs ~5ms on a 100-tree forest — dominating small-payload latency
        key = (tree_slice.start, tree_slice.stop)
        if self._stacked_cache is None:
            self._stacked_cache = {}
        if key in self._stacked_cache:
            return self._stacked_cache[key]
        stacked = self._stack_uncached(tree_slice)
        self._stacked_cache[key] = stacked
        return stacked

    def _stack_uncached(self, tree_slice):
        trees = self.trees[tree_slice]
        if not trees:
            return None
        N = max(t.num_nodes for t in trees)
        T = len(trees)

        def pad(getter, dtype, fill=0):
            out = np.full((T, N), fill, dtype)
            for i, t in enumerate(trees):
                out[i, : t.num_nodes] = getter(t)
            return out

        self_idx = np.arange(N, dtype=np.int32)[None, :].repeat(T, axis=0)
        left = pad(lambda t: t.left, np.int32, -1)
        right = pad(lambda t: t.right, np.int32, -1)
        is_leaf = left < 0
        left = np.where(is_leaf, self_idx, left)
        right = np.where(is_leaf, self_idx, right)
        stacked = {
            "feature": pad(lambda t: t.feature, np.int32),
            "threshold": pad(lambda t: t.threshold, np.float32),
            "default_left": pad(lambda t: t.default_left, np.bool_),
            "left": left,
            "right": right,
            "is_leaf": is_leaf,
            "leaf_value": pad(lambda t: t.value, np.float32),
            "depth": max(t.depth() for t in trees),
        }
        max_cat = max((t.max_category() for t in trees), default=-1)
        if max_cat >= 0:
            # bitmask of right-branch categories per node: [T, N, W] u32
            W = (max_cat >> 5) + 1
            cat_split = np.zeros((T, N), np.bool_)
            cat_mask = np.zeros((T, N, W), np.uint32)
            for i, t in enumerate(trees):
                for node, cats in t.categories.items():
                    cat_split[i, node] = True
                    for c in cats:
                        cat_mask[i, node, c >> 5] |= np.uint32(1) << np.uint32(c & 31)
            stacked["cat_split"] = cat_split
            stacked["cat_mask"] = cat_mask
        return stacked

    def predict_margin(self, features, iteration_range=None):
        """features: np [n, d] float32 with NaN missing -> margins."""
        obj = self.objective()
        base = obj.base_margin(self.base_score)
        if iteration_range is None:
            lo, hi = 0, self.num_boosted_rounds
        else:
            lo, hi = iteration_range
            hi = hi or self.num_boosted_rounds
        tree_lo, tree_hi = self.iteration_indptr[lo], self.iteration_indptr[hi]
        if features.shape[1] < self.num_feature:
            raise exc.UserError(
                "feature_names mismatch: model expects {} features, data has {}".format(
                    self.num_feature, features.shape[1]
                )
            )
        stacked = self._stack(slice(tree_lo, tree_hi))
        n = features.shape[0]
        if stacked is None:
            if self.num_output_group == 1:
                return np.full(n, base, np.float32)
            return np.full((n, self.num_output_group), base, np.float32)
        if 0 < n <= _host_predict_rows():
            # tiny payloads skip the device entirely: the per-dispatch floor
            # (host<->device transfer and launch) dwarfs microseconds of
            # traversal. Threshold: GRAFT_HOST_PREDICT_ROWS.
            return host_predict_margin(
                stacked,
                np.ascontiguousarray(features, np.float32),
                num_output_group=self.num_output_group,
                base_margin=base,
                tree_info=self.tree_info[tree_lo:tree_hi],
            )
        # bucket the row count to a power of two so serving payloads of
        # varying size share jit-compiled kernels instead of recompiling
        n_pad = predict_bucket(n)
        if n_pad != n:
            features = np.concatenate(
                [features, np.zeros((n_pad - n, features.shape[1]), np.float32)], axis=0
            )
        out = forest_predict_margin(
            stacked,
            features,
            num_output_group=self.num_output_group,
            base_margin=base,
            tree_info=self.tree_info[tree_lo:tree_hi],
        )
        return out[:n]

    def predict(self, features, output_margin=False, iteration_range=None, pred_leaf=False):
        if pred_leaf:
            return self.predict_leaf(features, iteration_range=iteration_range)
        margin = self.predict_margin(features, iteration_range=iteration_range)
        if output_margin:
            return margin
        return self.objective().margin_to_prediction(margin)

    def predict_leaf(self, features, iteration_range=None):
        """Leaf index per (row, tree) — xgboost ``predict(pred_leaf=True)``."""
        from ..ops.predict import forest_leaf_nodes

        if iteration_range is None:
            lo, hi = 0, self.num_boosted_rounds
        else:
            lo, hi = iteration_range
            hi = hi or self.num_boosted_rounds
        stacked = self._stack(
            slice(self.iteration_indptr[lo], self.iteration_indptr[hi])
        )
        features = np.asarray(features, np.float32)
        if stacked is None:
            return np.zeros((features.shape[0], 0), np.int32)
        return np.asarray(forest_leaf_nodes(stacked, features))

    # ------------------------------------------------------------ attributes
    def attr(self, key):
        """xgboost Booster.attr: stored attribute or None."""
        return self.attributes.get(key)

    def set_attr(self, **kwargs):
        """xgboost Booster.set_attr: set (or delete with None) attributes."""
        for key, value in kwargs.items():
            if value is None:
                self.attributes.pop(key, None)
            else:
                self.attributes[key] = str(value)

    # ------------------------------------------------------------ importance
    def get_score(self, importance_type="weight"):
        """Feature importances (xgboost Booster.get_score semantics).

        weight: split counts; [total_]gain / [total_]cover: summed loss change
        / summed hessian at splits, averaged for the non-total variants. Keys
        are feature names when known, else ``f<index>``.
        """
        valid = ("weight", "gain", "cover", "total_gain", "total_cover")
        if importance_type not in valid:
            raise exc.UserError(
                "importance_type must be one of {}".format(", ".join(valid))
            )
        counts = {}
        gains = {}
        covers = {}
        for tree in self.trees:
            split_mask = ~tree.is_leaf
            for f, g, c in zip(
                tree.feature[split_mask], tree.gain[split_mask], tree.sum_hess[split_mask]
            ):
                f = int(f)
                counts[f] = counts.get(f, 0) + 1
                gains[f] = gains.get(f, 0.0) + float(g)
                covers[f] = covers.get(f, 0.0) + float(c)

        def name(f):
            if self.feature_names and f < len(self.feature_names):
                return self.feature_names[f]
            return "f{}".format(f)

        if importance_type == "weight":
            return {name(f): v for f, v in counts.items()}
        if importance_type == "total_gain":
            return {name(f): v for f, v in gains.items()}
        if importance_type == "total_cover":
            return {name(f): v for f, v in covers.items()}
        if importance_type == "gain":
            return {name(f): gains[f] / counts[f] for f in counts}
        return {name(f): covers[f] / counts[f] for f in counts}

    def get_fscore(self):
        return self.get_score("weight")

    def get_dump(self, with_stats=False):
        """Text dump of every tree (xgboost ``Booster.get_dump`` format)."""

        def name(f):
            if self.feature_names and f < len(self.feature_names):
                return self.feature_names[f]
            return "f{}".format(f)

        dumps = []
        for tree in self.trees:
            lines = {}

            def walk(node, depth):
                indent = "\t" * depth
                if tree.is_leaf[node]:
                    line = "{}{}:leaf={:.9g}".format(indent, node, float(tree.value[node]))
                    if with_stats:
                        line += ",cover={:.9g}".format(float(tree.sum_hess[node]))
                else:
                    left, right = int(tree.left[node]), int(tree.right[node])
                    missing = left if tree.default_left[node] else right
                    if node in tree.categories:
                        # xgboost categorical dump: the right-branch set,
                        # with yes/no swapped (in-set routes right)
                        cond = "{}:{{{}}}".format(
                            name(int(tree.feature[node])),
                            ",".join(str(int(c)) for c in tree.categories[node]),
                        )
                        line = "{}{}:[{}] yes={},no={},missing={}".format(
                            indent, node, cond, right, left, missing
                        )
                    else:
                        line = "{}{}:[{}<{:.9g}] yes={},no={},missing={}".format(
                            indent,
                            node,
                            name(int(tree.feature[node])),
                            float(tree.threshold[node]),
                            left,
                            right,
                            missing,
                        )
                    if with_stats:
                        line += ",gain={:.9g},cover={:.9g}".format(
                            float(tree.gain[node]), float(tree.sum_hess[node])
                        )
                lines[node] = line
                if not tree.is_leaf[node]:
                    walk(int(tree.left[node]), depth + 1)
                    walk(int(tree.right[node]), depth + 1)

            walk(0, 0)
            dumps.append("\n".join(lines[k] for k in sorted(lines)) + "\n")
        return dumps

    # ----------------------------------------------------------------- json
    _OBJECTIVE_PARAM_BLOCKS = {
        "reg:squarederror": ("reg_loss_param", {"scale_pos_weight": "1"}),
        "reg:squaredlogerror": ("reg_loss_param", {"scale_pos_weight": "1"}),
        "reg:logistic": ("reg_loss_param", {"scale_pos_weight": "1"}),
        "binary:logistic": ("reg_loss_param", {"scale_pos_weight": "1"}),
        "binary:logitraw": ("reg_loss_param", {"scale_pos_weight": "1"}),
        "count:poisson": ("poisson_regression_param", {"max_delta_step": "0.7"}),
        "reg:tweedie": ("tweedie_regression_param", {"tweedie_variance_power": "1.5"}),
        "reg:pseudohubererror": ("pseudo_huber_param", {"huber_slope": "1"}),
        "multi:softmax": ("softmax_multiclass_param", {"num_class": "0"}),
        "multi:softprob": ("softmax_multiclass_param", {"num_class": "0"}),
        "rank:pairwise": ("lambdarank_param", {}),
        "rank:ndcg": ("lambdarank_param", {}),
        "rank:map": ("lambdarank_param", {}),
    }

    def _tree_to_json(self, tree, tree_id):
        is_leaf = tree.is_leaf
        # xgboost: split_conditions holds the threshold for splits, the leaf
        # value for leaves; split_indices is 0 at leaves.
        split_conditions = np.where(is_leaf, tree.value, tree.threshold)
        cats, cat_nodes, cat_segs, cat_sizes = [], [], [], []
        split_type = [0] * tree.num_nodes
        for node in sorted(tree.categories):
            node_cats = tree.categories[node]
            cat_nodes.append(int(node))
            cat_segs.append(len(cats))
            cat_sizes.append(len(node_cats))
            cats.extend(int(c) for c in node_cats)
            split_type[node] = 1
        return {
            "base_weights": [float(v) for v in tree.base_weight],
            "categories": cats,
            "categories_nodes": cat_nodes,
            "categories_segments": cat_segs,
            "categories_sizes": cat_sizes,
            "default_left": [int(b) for b in tree.default_left],
            "id": tree_id,
            "left_children": [int(v) for v in tree.left],
            "right_children": [int(v) for v in tree.right],
            "loss_changes": [float(v) for v in tree.gain],
            "parents": [int(v) for v in tree.parent],
            "split_conditions": [float(v) for v in split_conditions],
            "split_indices": [int(v) for v in tree.feature],
            "split_type": split_type,
            "sum_hessian": [float(v) for v in tree.sum_hess],
            "tree_param": {
                "num_deleted": "0",
                "num_feature": str(self.num_feature),
                "num_nodes": str(tree.num_nodes),
                "size_leaf_vector": "1",
            },
        }

    @staticmethod
    def _tree_from_json(blob):
        categories = None
        if blob.get("categories_nodes"):
            # xgboost stores all categorical nodes' right-branch category
            # sets in one flat list with per-node segments
            flat = np.asarray(blob.get("categories", []), np.int64)
            nodes = blob["categories_nodes"]
            segs = blob.get("categories_segments", [])
            sizes = blob.get("categories_sizes", [])
            categories = {
                int(node): flat[int(segs[j]) : int(segs[j]) + int(sizes[j])]
                for j, node in enumerate(nodes)
            }
        left = np.asarray(blob["left_children"], np.int32)
        is_leaf = left < 0
        cond = np.asarray(blob["split_conditions"], np.float32)
        return Tree(
            feature=blob["split_indices"],
            threshold=np.where(is_leaf, 0.0, cond),
            default_left=np.asarray(blob["default_left"], bool),
            left=left,
            right=blob["right_children"],
            value=np.where(is_leaf, cond, 0.0),
            base_weight=blob.get("base_weights"),
            gain=blob.get("loss_changes"),
            sum_hess=blob.get("sum_hessian"),
            parent=blob.get("parents"),
            categories=categories,
        )

    def save_json(self):
        block_name, defaults = self._OBJECTIVE_PARAM_BLOCKS.get(
            self.objective_name, ("reg_loss_param", {"scale_pos_weight": "1"})
        )
        block = dict(defaults)
        for key in list(block):
            if key in self.objective_params:
                block[key] = str(self.objective_params[key])
        if "num_class" in block:
            block["num_class"] = str(self.num_class)
        doc = {
            "version": [3, 0, 0],
            "learner": {
                "attributes": self.attributes,
                "feature_names": self.feature_names or [],
                "feature_types": [
                    "c" if t == "c" else "float" for t in self.feature_types or []
                ],
                "gradient_booster": {
                    "model": {
                        "gbtree_model_param": {
                            "num_trees": str(len(self.trees)),
                            "num_parallel_tree": "1",
                        },
                        "iteration_indptr": list(self.iteration_indptr),
                        "tree_info": list(self.tree_info),
                        "trees": [
                            self._tree_to_json(t, i) for i, t in enumerate(self.trees)
                        ],
                    },
                    "name": "gbtree",
                },
                "learner_model_param": {
                    "base_score": repr(self.base_score),
                    "boost_from_average": "1",
                    "num_class": str(self.num_class),
                    "num_feature": str(self.num_feature),
                    "num_target": "1",
                },
                "objective": {"name": self.objective_name, block_name: block},
            },
        }
        return json.dumps(doc)

    @classmethod
    def load_json(cls, text):
        try:
            doc = json.loads(text)
        except (ValueError, TypeError) as e:
            raise exc.UserError("Not a valid xgboost JSON model", caused_by=e)
        return cls.from_dict(doc)

    @classmethod
    def from_dict(cls, doc):
        try:
            learner = doc["learner"]
            gb = learner["gradient_booster"]
            weight_drop = None
            if gb.get("name") == "dart" or "gbtree" in gb:
                # dart nests the tree model under "gbtree" and carries
                # per-tree dropout scale factors in "weight_drop"
                weight_drop = gb.get("weight_drop")
                gb = gb["gbtree"]
            model = gb["model"]
            lmp = learner["learner_model_param"]
            objective = learner["objective"]
        except (KeyError, ValueError, TypeError) as e:
            raise exc.UserError("Not a valid xgboost JSON model", caused_by=e)
        params = {}
        for block in objective.values():
            if isinstance(block, dict):
                params.update(block)
        forest = cls(
            objective_name=objective["name"],
            objective_params=params,
            base_score=_parse_base_score(lmp.get("base_score", 0.5)),
            num_feature=int(lmp.get("num_feature", 0)),
            num_class=int(lmp.get("num_class", 0)),
            feature_names=learner.get("feature_names") or None,
            feature_types=learner.get("feature_types") or None,
        )
        forest.attributes = learner.get("attributes", {})
        forest.trees = [cls._tree_from_json(t) for t in model["trees"]]
        if weight_drop:
            for tree, scale in zip(forest.trees, weight_drop):
                tree.value = tree.value * np.float32(scale)
        forest.tree_info = [int(v) for v in model.get("tree_info", [0] * len(forest.trees))]
        indptr = model.get("iteration_indptr")
        if indptr:
            forest.iteration_indptr = [int(v) for v in indptr]
        else:
            per_round = max(1, forest.num_output_group)
            forest.iteration_indptr = list(
                range(0, len(forest.trees) + 1, per_round)
            )
        return forest

    def save_model(self, path, model_format=None):
        """Write the model; format by explicit arg or .ubj extension
        (mirrors xgboost's extension-driven choice), JSON otherwise."""
        if model_format is None:
            model_format = "ubj" if str(path).endswith(".ubj") else "json"
        if model_format == "ubj":
            import json as json_mod

            from .compat import encode_ubjson

            with open(path, "wb") as f:
                f.write(encode_ubjson(json_mod.loads(self.save_json())))
            return
        with open(path, "w") as f:
            f.write(self.save_json())

    @classmethod
    def load_model(cls, path):
        with open(path, "rb") as f:
            raw = f.read()
        return cls.load_json(raw.decode("utf-8"))
