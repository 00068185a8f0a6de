#!/usr/bin/env python
"""Per-stage timing dissection of one boosting round on the current backend.

Times each stage of one configuration (1M x 28, depth 8, max_bin 256,
binary:logistic) in isolation under jit, so a round's time on
the device can be attributed: grad/hess, per-level histograms (with the sibling
subtraction that the real build does), node totals, split scan, row routing,
eval prediction, and the full fused tree build.

Prints one "stage: ms" line per stage plus a JSON summary line at the end.
``--hist-impl flat|pallas`` names the level histogram's builder (default:
what ``ops/histogram.choose_hist_impl`` picks here; the full tree build always
takes the backend's); GRAFT_HIST_MM_PREC is honored through the session
snapshot. ``--route-impl gather|dense`` forces the lowering of the routing
stage's bin fetch (default: what ``ops/tree_build.choose_route_impl`` picks
here). ``--route-widths`` runs the
width probe instead: both lowerings of ``row_bin_lookup`` at each feature
width and bins dtype, the table beside ``ROUTE_DENSE_MAX_WIDTH``.
``--node-table-widths`` runs the node-table probe: both lowerings of
``node_table_lookup`` at each table width, the table beside
``NODE_TABLE_SELECT_MAX_WIDTH``, then the two traversals of one depth-8 tree
over the same rows, then the build's own read set at its two widest levels
(gathers, one select a field, the packed word) at the cells' train row
counts. ``--hist-levels`` runs the level-histogram probe: the
Pallas kernel builder called directly at the two cells' shapes, ms a call at
every level's node count W under the operand-row rule
(``ops/histogram._operand_rows``), the bin fold (``_bin_fold``: each folded
level also at ``fold`` 1, with the one-hot tiles a call latches) and the tile
pack (``_tile_pack``: each packed level beside the shipped fold), then at
W = 1 with the operand padded to more rows: the tables the two rules were
read from; with ``--trees T`` the call of T class trees in one operand
(``_class_groups``) at every W, and with ``--narrow-every K`` every K-th
column at 127 cuts, whose second one-hot tile the calls that do not fold
leave out (``_live_tiles``). ``--cat-set-read`` runs the set-table read probe:
the three lowerings of ``ops.categorical.set_table_lookup`` at every level's
table of a depth-8 build, ``allstate-cat-d8``'s rows and 85 words a node, the
table beside ``SET_READ_SELECT_MAX_ENTRIES``. Run under an external timeout,
like anything that holds a device.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

N_ROWS = int(os.getenv("DISSECT_ROWS", "1000000"))
N_FEATURES = int(os.getenv("DISSECT_FEATURES", "28"))
MAX_DEPTH = int(os.getenv("DISSECT_MAX_DEPTH", "8"))
MAX_BIN = int(os.getenv("DISSECT_MAX_BIN", "256"))
REPS = int(os.getenv("DISSECT_REPS", "5"))


def _time(fn, *args):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


ROUTE_PROBE_WIDTHS = (28, 54, 136, 512, 1024, 2048)
ROUTE_PROBE_CALLS = 8  # one per level: enqueued back to back, one wait


def _time_a_call(fn, *args):
    """ms a call of ``fn`` over ``ROUTE_PROBE_CALLS`` calls enqueued back to
    back, as a round's levels are."""

    def calls(*a):
        out = None
        for _ in range(ROUTE_PROBE_CALLS):
            out = fn(*a)
        return out

    return _time(calls, *args) / ROUTE_PROBE_CALLS


def route_width_probe(widths, n_rows):
    """ms per call of ``row_bin_lookup`` under each lowering, by feature width
    and bins dtype, as a level of the round program calls it (per-row feature
    ids, the result compared with a per-row split bin). Bins are made on the
    device, in the layout a ``device_put`` matrix of that shape has."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import tree_build as TB

    rows = []
    for width in widths:
        n = min(n_rows, (1 << 30) // width)  # under 2**30 bins: a few GB at the widest
        for dtype, num_bins in ((jnp.uint8, 256), (jnp.uint16, 257)):
            k_bins, k_feat, k_split = jax.random.split(jax.random.PRNGKey(width), 3)
            bins = jax.jit(
                lambda k, dtype=dtype, num_bins=num_bins, n=n: (
                    jax.random.bits(k, (n, width), dtype) % num_bins
                ).astype(dtype)
            )(k_bins)
            feat = jax.random.randint(k_feat, (n,), 0, width, jnp.int32)
            split_bin = jax.random.randint(k_split, (n,), 0, num_bins - 1, jnp.int32)
            jax.block_until_ready((bins, feat, split_bin))
            row = {"width": width, "dtype": jnp.dtype(dtype).name, "rows": n}
            outs = {}
            for impl in ("gather", "dense"):
                fn = jax.jit(
                    lambda b, f, sb, impl=impl: TB.row_bin_lookup(b, f, impl=impl) > sb
                )
                ms = _time_a_call(fn, bins, feat, split_bin)
                row[impl + "_ms"] = ms
                row[impl + "_ns_per_row"] = ms * 1e6 / n
                outs[impl] = fn(bins, feat, split_bin)
            row["equal"] = bool(jnp.array_equal(outs["gather"], outs["dense"]))
            row["chosen"] = TB.choose_route_impl(jax.default_backend(), width)
            print(json.dumps(row), flush=True)
            rows.append(row)
            del bins, outs
    return rows


NODE_TABLE_PROBE_WIDTHS = tuple(2**k for k in range(14))
NODE_TABLE_PROBE_ROWS = 2_200_000  # higgs-d8's validation rows
EVAL_WALK_GAMMA = 4.0  # leafs some of the probe tree's branches above the last level


def node_table_probe(widths, n_rows):
    """ns a row and lookup of ``node_table_lookup`` under each lowering, by
    table width and dtype, as a level of the evaluation walk calls it (a
    per-row index into the level's own table)."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import tree_build as TB

    rows = []
    for width in widths:
        k_tab, k_idx = jax.random.split(jax.random.PRNGKey(width))
        idx = jax.random.randint(k_idx, (n_rows,), 0, width, jnp.int32)
        ints = jax.random.randint(k_tab, (width,), 0, 257, jnp.int32)
        tables = {
            "int32": ints,
            "bool": ints % 2 == 0,
            "float32": jax.random.normal(k_tab, (width,), jnp.float32),
        }
        jax.block_until_ready((idx, tables))
        for name, table in tables.items():
            row = {"width": width, "dtype": name, "rows": n_rows}
            outs = {}
            for impl in ("gather", "select"):
                fn = jax.jit(
                    lambda t, i, impl=impl: TB.node_table_lookup(t, i, impl=impl)
                )
                row[impl + "_ns_per_row"] = _time_a_call(fn, table, idx) * 1e6 / n_rows
                outs[impl] = fn(table, idx)
            row["equal"] = bool(
                jnp.array_equal(
                    *(
                        jax.lax.bitcast_convert_type(outs[k], jnp.int32)
                        if name == "float32"
                        else outs[k]
                        for k in ("gather", "select")
                    )
                )
            )
            row["chosen"] = TB.choose_table_impl(jax.default_backend(), width)
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


# the cells' train rows whose levels 7 and 8 are real gathers (benchmark/configs)
BUILD_READ_PROBE_ROWS = (8_800_000, 16_387_491)


def build_read_probe(row_counts, d=N_FEATURES, num_bins=MAX_BIN + 1):
    """ns a row of what ``build_tree`` reads of its node tables at a depth-8
    tree's two widest levels: the four split fields and the leaf weight by one
    index at 128 entries, the leaf weight at 256. As gathers, as one select a
    field, and as the packed word's select beside the two weights'; every
    variant bit-equal to the gathers."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import tree_build as TB

    bin_bits = TB.split_word_bin_bits(d, num_bins)
    keys = jax.random.split(jax.random.PRNGKey(33), 8)
    feature = jax.random.randint(keys[0], (128,), 0, d, jnp.int32)
    split_bin = jax.random.randint(keys[1], (128,), 0, num_bins, jnp.int32)
    default_left = jax.random.bernoulli(keys[2], 0.5, (128,))
    becomes_leaf = jax.random.bernoulli(keys[3], 0.2, (128,))
    weight7 = jax.random.normal(keys[4], (128,), jnp.float32)
    weight8 = jax.random.normal(keys[5], (256,), jnp.float32)

    def reads(idx7, idx8, variant):
        impl = "gather" if variant == "gather" else "select"
        fields = (feature, split_bin, default_left, becomes_leaf)
        if variant == "packed":
            feat, sbin, left, leaf = TB.unpack_split_word(
                TB.node_table_lookup(
                    TB.pack_split_word(*fields, bin_bits), idx7, impl=impl
                ),
                bin_bits,
            )
        else:
            feat, sbin, left, leaf = (
                TB.node_table_lookup(f, idx7, impl=impl) for f in fields
            )
        # consumed as the build consumes them: a node id and a margin a row
        node = jnp.where(leaf, -1, feat * num_bins * 2 + sbin * 2 + left)
        margin = jnp.where(
            leaf, TB.node_table_lookup(weight7, idx7, impl=impl),
            TB.node_table_lookup(weight8, idx8, impl=impl),
        )
        return node, jax.lax.bitcast_convert_type(margin, jnp.int32)

    rows = []
    for n in row_counts:
        idx7 = jax.random.randint(keys[6], (n,), 0, 128, jnp.int32)
        idx8 = jax.random.randint(keys[7], (n,), 0, 256, jnp.int32)
        jax.block_until_ready((idx7, idx8))
        row = {"rows": n, "features": d, "num_bins": num_bins, "bin_bits": bin_bits}
        want = None
        for variant in ("gather", "selects", "packed"):
            fn = jax.jit(lambda a, b, variant=variant: reads(a, b, variant))
            row[variant + "_ns_per_row"] = _time_a_call(fn, idx7, idx8) * 1e6 / n
            got = fn(idx7, idx8)
            want = got if want is None else want
            row[variant + "_equal"] = all(
                bool(jnp.array_equal(g, w)) for g, w in zip(got, want)
            )
        print(json.dumps(row), flush=True)
        rows.append(row)
        del idx7, idx8
    return rows


def eval_walk_probe(n_rows, d=N_FEATURES, depth=MAX_DEPTH, max_bin=MAX_BIN):
    """ms of one depth-wise tree applied to ``n_rows`` unseen rows under each
    traversal and node-table lowering, every result bit-equal to the pointer
    traversal's. The tree is built here from seeded rows, with a ``gamma``
    that leafs some branches early."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import tree_build as TB

    B = max_bin + 1
    dtype = jnp.uint8 if B <= 256 else jnp.uint16
    k_train, k_eval, k_g = jax.random.split(jax.random.PRNGKey(29), 3)
    make = jax.jit(
        lambda k, n: (jax.random.bits(k, (n, d), jnp.uint16) % B).astype(dtype),
        static_argnums=1,
    )
    train, rows = make(k_train, 200_000), make(k_eval, n_rows)
    grad = jax.random.normal(k_g, (train.shape[0],), jnp.float32) + (
        train[:, 0] > B // 2
    ) * 0.5
    tree, _ = jax.jit(
        lambda b, g: TB.build_tree(
            b, g, jnp.ones_like(g), jnp.full((d,), max_bin - 1, jnp.int32), depth, B,
            gamma=EVAL_WALK_GAMMA,
        )
    )(train, grad)
    route_impl = TB.choose_route_impl(jax.default_backend(), d)
    fns = {
        "pointer": lambda t, b: TB.predict_binned(t, b, depth, B, route_impl=route_impl),
        "level_gather": lambda t, b: TB.predict_binned_levels(
            t, b, depth, B, route_impl=route_impl, table_backend="cpu"
        ),
        "level_select": lambda t, b: TB.predict_binned_levels(
            t, b, depth, B, route_impl=route_impl, table_backend="tpu"
        ),
    }
    out = {"rows": n_rows, "depth": depth, "width": d}
    want = None
    for name, fn in fns.items():
        fn = jax.jit(fn)
        out[name + "_ms"] = _time(fn, tree, rows)
        got = jax.lax.bitcast_convert_type(fn(tree, rows), jnp.int32)
        want = got if want is None else want
        out[name + "_equal"] = bool(jnp.array_equal(got, want))
    out["leaves_reached"] = int(jnp.unique(want).size)
    print(json.dumps(out), flush=True)
    return out


# allstate-cat-d8's train rows and evaluation rows, and the words of a node's
# set there (2,700 categories)
CAT_SET_READ_ROWS = (12_184_290, 1_000_000)
CAT_SET_READ_WORDS = 85


def cat_set_read_probe(row_counts, words, depth=MAX_DEPTH):
    """ns a row and level of ``ops.categorical.set_table_lookup`` under each
    lowering, at every level of a depth-wise build (a ``[2**level, words]``
    set table whose words hold every bit, bit 31 too; per-row nodes and
    values, values that are missing or no category among them), every
    result bit-equal to the gather's."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import categorical as C

    rows = []
    for n in row_counts:
        for level in range(depth):
            W = 1 << level
            k_tab, k_node, k_val = jax.random.split(jax.random.PRNGKey(n % 1000 + level), 3)
            table = jax.lax.bitcast_convert_type(
                jax.random.bits(k_tab, (W, words), jnp.uint32), jnp.int32
            )
            node = jax.random.randint(k_node, (n,), 0, W, jnp.int32)
            value = jax.random.randint(k_val, (n,), -1, 32 * words + 1, jnp.int32)
            jax.block_until_ready((table, node, value))
            row = {"rows": n, "level": level, "nodes": W, "words": words, "entries": W * words}
            want = None
            for impl in ("gather", "select", "product"):
                fn = jax.jit(lambda t, i, v, impl=impl: C.set_table_lookup(t, i, v, impl))
                row[impl + "_ns_per_row"] = _time_a_call(fn, table, node, value) * 1e6 / n
                got = fn(table, node, value)
                want = got if want is None else want
                row[impl + "_equal"] = bool(jnp.array_equal(got, want))
            row["chosen"] = C.choose_set_read_impl(jax.default_backend(), W * words)
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


# the cells' train matrices (benchmark/configs): rows, features; 257 bins, u16
HIST_PROBE_SHAPES = {"higgs-d8": (8_800_000, 28), "mslr-ndcg": (2_270_296, 136)}
HIST_PROBE_LEVELS = (1, 2, 4, 8, 16, 32, 64)  # a depth-8 tree with subtraction
HIST_PROBE_ROWS = (16, 32, 64, 128)           # operand rows at W = 1


def hist_level_probe(shapes, num_bins=MAX_BIN + 1, trees=1, narrow_every=0):
    """ms a call of the level histogram kernel by node count W under the
    shipped operand-row, chunk, fold and pack rules (a folded level also at
    ``fold`` 1, a packed one also unpacked at the shipped fold), then by
    operand rows at W = 1 in both precisions, with the
    rows the MXU streams against one latched one-hot tile (both halves of
    the split operand, every folded copy), the tiles a call latches and the
    share of the MXU's peak that the issued flops make. Bins and gradients
    are made on the device. With ``trees`` > 1 the call is that of a round's
    class trees in one operand (``ops/histogram._class_groups``: the trees
    of a group share their latches, the groups are a grid axis), by node
    count alone. With ``narrow_every`` = K every K-th column holds 127 cuts
    (bins 0 to 127 and the missing bin), so the calls that do not fold leave
    its second one-hot tile out (``ops/histogram._live_tiles``; but for what
    fills a list's last block: ``dead_bin_tiles``); the other columns hold
    255, and at K = 0 all do."""
    import jax
    import jax.numpy as jnp

    from benchmark.peaks import PEAKS
    from sagemaker_xgboost_container_tpu.ops import histogram as H

    block, B = H.PALLAS_ROW_BLOCK, num_bins
    split_missing = H._mxu_split_missing(B)
    bin_lanes = H._bin_lanes(B)
    # no published peak for this device kind: the share is left out
    peak = PEAKS.get(jax.devices()[0].device_kind, {}).get("flops_bf16")
    out = []
    for name, (n, d) in shapes.items():
        dtype = jnp.uint8 if B <= 256 else jnp.uint16
        fg = H._pallas_feature_group(d, dtype)
        d_pad = -(-d // fg) * fg
        cap = H._chunk_cap(-(-n // block))
        n_pad = -(-n // (block * cap)) * block * cap
        k_bins, k_gh, k_node = jax.random.split(jax.random.PRNGKey(31), 3)
        narrow = jnp.arange(d_pad) % max(narrow_every, 1) == narrow_every - 1
        bins = jax.jit(
            lambda k: jnp.where(
                (jnp.arange(d_pad) < d)[:, None],
                _probe_bins(jax.random.bits(k, (d_pad, n_pad), jnp.uint16), B, narrow), 0,
            ).astype(dtype)
        )(k_bins)
        gh = jax.random.normal(k_gh, (2, n_pad), jnp.float32)
        # the unfolded body's operand: a column's live one-hot tiles
        reach = jnp.where(narrow[:d], 127, B - 2)
        live = H._live_tiles(reach, d, fg, B)
        dead_tiles = H.dead_bin_tiles(np.asarray(reach), B, dtype)
        jax.block_until_ready((bins, gh, live))

        def call(W, rows, chunks, prec="bf16x2", fold=1, class_groups=None, pack=1):
            size, groups = class_groups or (1, 1)
            node = jax.random.randint(k_node, (groups * size, n_pad), 0, W, jnp.int32)
            operands = (gh, node)
            if class_groups:  # [groups, 2 * size, n] and [groups, size, n]
                operands = (
                    jax.random.normal(k_gh, (groups, 2 * size, n_pad), jnp.float32),
                    node.reshape(groups, size, n_pad),
                )
            if pack > 1:  # ``pack`` features a latched tile, the level's real rows
                rows, fold = H._slot_rows(W, bin_lanes, pack), bin_lanes * pack // 128
                fn = jax.jit(H._pallas_hist_packed_fn(
                    n_pad, d, fg, W, B, block, prec, H.pallas_interpret(),
                    split_missing, chunks, pack,
                ))
            else:
                fn = jax.jit(H._pallas_hist_fn(
                    n_pad, d, fg, W, B, block, prec, H.pallas_interpret(),
                    split_missing, rows, chunks, fold, class_groups,
                ))
            skipped = 0
            if pack == 1 and fold == 1:
                operands += (live,)
                skipped = dead_tiles
            ms = _time(fn, bins, *operands)
            # what the MXU is handed: for every tile of ``pack`` features
            # ``fold`` copies of each one's operand rows (both halves of
            # bf16x2 in one dot) against a [block, pack * bin_lanes / fold]
            # one-hot, two flops a multiply-add, once a class group; a tile is
            # [128 rows, 128 lanes] of it, a tile with one real feature whole
            streamed = pack * fold * rows * (2 if prec == "bf16x2" else 1)
            lane_tiles = -(-d // pack) * (pack * bin_lanes // fold // 128) - skipped
            flops = 2.0 * groups * n_pad * streamed * lane_tiles * 128
            row = {
                "shape": name, "W": W, "prec": prec, "trees": trees,
                "tree_groups": groups, "operand_rows": rows,
                "chunks": chunks, "fold": fold, "pack": pack,
                "streamed_rows_a_tile": streamed,
                "tiles_latched": groups * (n_pad // 128) * lane_tiles,
                "tiles_skipped_a_row_tile": skipped,
                "ms": ms,
                "mxu_share_of_peak": flops / (ms * 1e-3) / peak if peak else None,
            }
            print(json.dumps(row), flush=True)
            out.append(row)

        for W in HIST_PROBE_LEVELS:
            class_groups = H._class_groups(W, trees) if trees > 1 else None
            rows = H._operand_rows(W, class_groups[0] if class_groups else 1)
            # the shipped fold, and the unfolded kernel beside it
            for fold in sorted({1, H._bin_fold(rows, bin_lanes, "bf16x2")}):
                call(W, rows, H._row_chunks(W, cap), fold=fold,
                     class_groups=class_groups)
            # the shipped pack (one tree), beside the shipped fold above
            pack = H._tile_pack(W, bin_lanes, "bf16x2") if trees == 1 else 1
            if pack > 1:
                call(W, rows, H._row_chunks(W, cap), pack=pack)
        # the rule's table: one latched one-hot tile costs what 64 streamed
        # rows cost; the one-pass control reaches 16 rows a tile
        for prec in H.HIST_PRECISIONS if trees == 1 else ():
            for rows in HIST_PROBE_ROWS:
                for fold in sorted({1, H._bin_fold(rows, bin_lanes, prec)}):
                    call(1, rows, 1, prec, fold)
            # and the control's packed call: half the streamed rows a tile
            pack = H._tile_pack(1, bin_lanes, prec)
            if prec != "bf16x2" and pack > 1:
                call(1, None, H._row_chunks(1, cap), prec, pack=pack)
        del bins, gh
    return out


def _probe_bins(raw, B, narrow):
    """Bins of the level-histogram probe from random 16-bit words [d, n]:
    uniform over the B bins, the last one missing; a ``narrow`` column (bool
    [d]) keeps its missing share and spreads the rest over bins 0 to 127."""
    import jax.numpy as jnp

    wide = raw % B
    return jnp.where(narrow[:, None] & (wide != B - 1), raw % 128, wide)


def _emit(summary, out_path):
    line = json.dumps(summary)
    print(line)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            f.write(line + "\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hist-impl", choices=("flat", "pallas"), default=None)
    ap.add_argument("--route-impl", choices=("gather", "dense"), default=None)
    ap.add_argument(
        "--route-widths", nargs="*", type=int, default=None, metavar="D",
        help="run only the width probe (default widths: {})".format(
            " ".join(map(str, ROUTE_PROBE_WIDTHS))
        ),
    )
    ap.add_argument(
        "--node-table-widths", nargs="*", type=int, default=None, metavar="W",
        help="run only the node-table probe (default widths: 1 to 8192 doubling)",
    )
    ap.add_argument(
        "--hist-levels", action="store_true",
        help="run only the level-histogram probe (both cells' shapes)",
    )
    ap.add_argument(
        "--trees", type=int, default=1, metavar="T",
        help="with --hist-levels: the call of T class trees in one operand",
    )
    ap.add_argument(
        "--narrow-every", type=int, default=0, metavar="K",
        help="with --hist-levels: every K-th column holds 127 cuts, one bin tile",
    )
    ap.add_argument(
        "--cat-set-read", action="store_true",
        help="run only the set-table read probe (allstate-cat-d8's rows and words)",
    )
    ap.add_argument("--out", default=None, help="also write the JSON summary here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import histogram as H
    from sagemaker_xgboost_container_tpu.ops import tree_build as TB
    from sagemaker_xgboost_container_tpu.ops.split import find_best_splits

    print("backend:", jax.default_backend(), flush=True)
    if args.route_widths is not None:
        summary = {
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "route_dense_max_width": TB.ROUTE_DENSE_MAX_WIDTH,
            "route_width_probe": route_width_probe(
                args.route_widths or ROUTE_PROBE_WIDTHS, N_ROWS
            ),
        }
        _emit(summary, args.out)
        return
    if args.node_table_widths is not None:
        summary = {
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "node_table_select_max_width": TB.NODE_TABLE_SELECT_MAX_WIDTH,
            "node_table_probe": node_table_probe(
                args.node_table_widths or NODE_TABLE_PROBE_WIDTHS,
                NODE_TABLE_PROBE_ROWS,
            ),
            "eval_walk_probe": eval_walk_probe(NODE_TABLE_PROBE_ROWS),
            "build_read_probe": build_read_probe(
                (N_ROWS,) if os.getenv("DISSECT_ROWS") else BUILD_READ_PROBE_ROWS
            ),
        }
        _emit(summary, args.out)
        return
    if args.cat_set_read:
        from sagemaker_xgboost_container_tpu.ops import categorical as C

        summary = {
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "set_read_select_max_entries": C.SET_READ_SELECT_MAX_ENTRIES,
            "cat_set_read_probe": cat_set_read_probe(
                (N_ROWS,) if os.getenv("DISSECT_ROWS") else CAT_SET_READ_ROWS,
                CAT_SET_READ_WORDS,
            ),
        }
        _emit(summary, args.out)
        return
    if args.hist_levels:
        shapes = HIST_PROBE_SHAPES
        if os.getenv("DISSECT_ROWS"):  # a rehearsal's size, one shape
            shapes = {"rehearsal": (N_ROWS, N_FEATURES)}
        summary = {
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "hist_level_probe": hist_level_probe(
                shapes, trees=args.trees, narrow_every=args.narrow_every
            ),
        }
        _emit(summary, args.out)
        return
    route_impl = args.route_impl or TB.choose_route_impl(
        jax.default_backend(), N_FEATURES
    )
    knobs = H.resolve_hist_knobs()
    hist_impl = args.hist_impl or H.choose_hist_impl(knobs.backend)
    print(
        "impl={} prec={} route={}".format(hist_impl, knobs.precision, route_impl),
        flush=True,
    )

    rng = np.random.RandomState(0)
    n, d, B = N_ROWS, N_FEATURES, MAX_BIN + 1
    bin_dtype = np.uint8 if B <= 256 else np.uint16  # match binning storage
    bins = jnp.asarray(rng.randint(0, MAX_BIN, size=(n, d)).astype(bin_dtype))
    margins = jnp.asarray(rng.randn(n).astype(np.float32) * 0.3)
    labels = jnp.asarray((rng.rand(n) > 0.5).astype(np.float32))
    jax.block_until_ready((bins, margins, labels))

    timings = {}

    # --- grad/hess (binary:logistic) ------------------------------------
    @jax.jit
    def gradhess(m, y):
        p = jax.nn.sigmoid(m)
        return p - y, p * (1.0 - p)

    timings["grad_hess"] = _time(gradhess, margins, labels)

    # --- per-level histogram cost, as the real build pays it ------------
    # level 0: full width-1 histogram. levels 1..max_depth-1 with
    # subtraction: only the left-child half is histogrammed (width/2
    # output), over ~all rows. last level: node_totals only.
    node_fns = {}

    def hist_at(width_out):
        key = ("hist", width_out)
        if key not in node_fns:
            node_fns[key] = jax.jit(
                lambda b, g, h, nl: H.level_histogram(
                    b, g, h, nl, width_out, B, knobs=knobs, impl=hist_impl
                )
            )
        return node_fns[key]

    grad, hess = gradhess(margins, labels)
    jax.block_until_ready((grad, hess))

    hist_total = 0.0
    for level in range(MAX_DEPTH):
        if level == 0:
            width_out = 1
        else:
            width_out = 2 ** (level - 1)  # subtraction: left children only
        nl = jnp.asarray(rng.randint(0, width_out, size=n).astype(np.int32))
        ms = _time(hist_at(width_out), bins, grad, hess, nl)
        timings["hist_L{}[{}]".format(level, width_out)] = ms
        hist_total += ms
    timings["hist_all_levels"] = hist_total

    # --- last-level node totals -----------------------------------------
    W_last = 2**MAX_DEPTH
    nl = jnp.asarray(rng.randint(0, W_last, size=n).astype(np.int32))
    fn_tot = jax.jit(lambda g, h, x: H.node_totals(g, h, x, W_last, knobs=knobs))
    timings["node_totals[{}]".format(W_last)] = _time(fn_tot, grad, hess, nl)

    # --- split scan across all levels -----------------------------------
    num_cuts = jnp.full((d,), MAX_BIN - 1, jnp.int32)
    split_total = 0.0
    for level in range(MAX_DEPTH):
        W = 2**level
        Gl = jnp.asarray(rng.rand(W, d, B).astype(np.float32))
        Hl = jnp.asarray(np.abs(rng.rand(W, d, B)).astype(np.float32))
        fn = jax.jit(lambda G, Hh: find_best_splits(G, Hh, num_cuts))
        ms = _time(fn, Gl, Hl)
        split_total += ms
    timings["split_scan_all_levels"] = split_total

    # --- routing (one level at full width) ------------------------------
    split_feat = jnp.asarray(rng.randint(0, d, size=n).astype(np.int32))

    @jax.jit
    def route(b, sf):
        row_bin = TB.row_bin_lookup(b, sf, impl=route_impl)
        return row_bin > 128

    timings["route_lookup[n]"] = _time(route, bins, split_feat) * MAX_DEPTH
    timings["route_one_level"] = timings["route_lookup[n]"] / MAX_DEPTH

    # --- full tree build (the real fused program) -----------------------
    @jax.jit
    def full_tree(b, g, h):
        tree, row_out = TB.build_tree(
            b, g, h, num_cuts, MAX_DEPTH, B, eta=0.2, knobs=knobs
        )
        return TB.pack_tree(tree), row_out

    timings["full_tree_build"] = _time(full_tree, bins, grad, hess)

    # --- full round incl. grad/hess + margin update ---------------------
    @jax.jit
    def full_round(b, m, y):
        g, h = gradhess(m, y)
        tree, row_out = TB.build_tree(
            b, g, h, num_cuts, MAX_DEPTH, B, eta=0.2, knobs=knobs
        )
        return TB.pack_tree(tree), m + row_out

    timings["full_round"] = _time(full_round, bins, margins, labels)

    for k, v in timings.items():
        print("{:28s} {:9.2f} ms".format(k, v), flush=True)
    _emit(
        {
            "backend": jax.default_backend(),
            "hist_impl": hist_impl,
            "route_impl": route_impl,
            "timings_ms": timings,
        },
        args.out,
    )


if __name__ == "__main__":
    main()
