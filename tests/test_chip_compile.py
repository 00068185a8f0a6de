"""The tree build compiled for the chip, without the chip.

The TPU's compiler is installed beside the CPU's and compiles for a v5e that
is described and not attached, so what it makes of ``build_tree`` can be read
here: the per-row reads of a level's node tables have to stay one
compare-select-reduce fusion each. A ``[rows, table width]`` intermediate
would not fit the chip at a cell's size (16,387,491 rows x 256 entries of
``int32`` are 16.8 GB), and a row-length gather is the serialised read that
cost 8 ns a row and lookup (PERF.md section 6, PR 33). Nothing runs, so
nothing here is a time.

Every test of this file describes the topology through the fixture below, in
the test's own process: only one process at a time may load the TPU's library,
and this file is the only one that does.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree, pack_tree

ROWS, FEATURES, NUM_BINS, DEPTH = 2_000_000, 39, 257, 8


@pytest.fixture(scope="module")
def described_chips():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: {}".format(e))
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(described_chips):
    return SingleDeviceSharding(described_chips[0])


@pytest.fixture()
def no_compile_cache():
    """A program compiled for a described chip cannot be read back from the
    persistent cache without one: keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


_COMPUTATION = r"\n(?=(?:ENTRY |%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{))"
_INSTRUCTION = r"\s+(?:ROOT )?%?[\w.\-]+ = (\(?\S.*?) ([\w\-]+)\("
_ARRAY = r"\b(pred|[suf]\d+|bf16)\[([0-9,]*)\]"


def _results(hlo, fused):
    """(opcode, dtype, dims) of every instruction's results: inside the fused
    computations (what a fusion computes on the way), or outside them (the
    buffers the program holds between its fusions). Reducers are neither."""
    out = []
    for comp in re.split(_COMPUTATION, hlo):
        head = comp.split("\n", 1)[0].lstrip("%")
        if head.startswith("region") or head.startswith("fused_computation") != fused:
            continue
        for line in comp.split("\n"):
            m = re.match(_INSTRUCTION, line)
            if not m:
                continue
            for dtype, dims in re.findall(_ARRAY, m.group(1)):
                out.append((m.group(2), dtype, tuple(int(x) for x in dims.split(",") if x)))
    return out


@pytest.mark.parametrize(
    "classes, class_vmap",
    [(0, False), (3, False), (3, True)],
    # a mapped build under Pallas's own batching rule (DART, the CV folds, a
    # loss-guided multi-class job), and the round program's class branch
    ids=["one_tree", "class_vmap", "class_operand"],
)
def test_build_tree_holds_no_rows_by_width_buffer_and_no_row_gather(
    one_chip, no_compile_cache, classes, class_vmap
):
    knobs = resolve_hist_knobs()._replace(backend="tpu")

    def build(bins, grad, hess, num_cuts):
        def one(g, h):
            tree, row_out = build_tree(
                bins, g, h, num_cuts, DEPTH, NUM_BINS, eta=0.1, knobs=knobs,
                class_vmap=class_vmap,
            )
            return pack_tree(tree), row_out

        return jax.vmap(one)(grad, hess) if classes else one(grad, hess)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    per_row = (classes, ROWS) if classes else (ROWS,)
    compiled = (
        jax.jit(build)
        .lower(
            shape((ROWS, FEATURES), jnp.uint16),
            shape(per_row, jnp.float32),
            shape(per_row, jnp.float32),
            shape((FEATURES,), jnp.int32),
        )
        .compile()
    )
    hlo = compiled.as_text()
    held = _results(hlo, fused=False)
    assert any(op == "fusion" and ROWS in dims for op, _dtype, dims in held)
    # the bins and the histogram's padded copies of them are the widest
    # buffers a build may hold: rows x features of u16 and a few per-row
    # vectors a class, never rows x the 128 or 256 entries of a node table
    widest = max(1, classes) * 4 * ROWS
    for op, dtype, dims in held:
        elements = 1
        for x in dims:
            elements *= x
        if dtype != "u16" and elements > widest:
            assert max(dims) < ROWS, (op, dtype, dims)
    # every gather left, fused or not, is the split scan's: a node long
    gathers = [
        dims for op, _dtype, dims in held + _results(hlo, fused=True) if op == "gather"
    ]
    if class_vmap:
        # the round program's class branch has none: mapped, the split scan's
        # reads of its winners were one gather over a [T, W, d * bins] mask
        # that XLA kept in VMEM, where a v5e stopped in it (PERF.md section 6,
        # PR 41); `find_best_splits(gathers=False)` reads them without one
        assert not gathers, gathers
    else:
        assert gathers and max(max(dims, default=1) for dims in gathers) <= 2**DEPTH, gathers


@pytest.mark.parametrize("W", [1, 8, 16, 64])
def test_ten_class_trees_in_one_operand_compile_for_the_chip(
    one_chip, no_compile_cache, monkeypatch, W
):
    """`mnist8m-mc10`'s level (506,250 x 784, 257 bins in u16, ten class
    trees) through the chip's own kernel compiler, which the interpreter on
    the CPU is not: 32 operand rows in one group at W = 1, 160 at W = 8, two
    groups of 160 at W = 16, a group a tree at W = 64."""
    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod

    monkeypatch.setattr(hist_mod, "pallas_interpret", lambda: False)
    n, d, T = 506_250, 784, 10

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = (
        jax.jit(lambda b, g, h, node: hist_mod._hist_pallas(b, g, h, node, W, NUM_BINS))
        .lower(
            shape((n, d), jnp.uint16), shape((T, n), jnp.float32),
            shape((T, n), jnp.float32), shape((T, n), jnp.int32),
        )
        .compile()
    )
    assert compiled.as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize(
    "n, d, B, dtype, W, prec, members",
    [
        (8_800_000, 28, 257, jnp.uint16, 1, "bf16x2", 0),      # higgs-d8's root
        (16_387_491, 39, 257, jnp.uint16, 2, "bf16x2", 0),     # criteo-tb-d8: an odd width
        (2_270_296, 136, 256, jnp.uint8, 2, "bf16x2", 0),      # u8 bins, five feature groups
        (8_800_000, 28, 257, jnp.uint16, 4, "bf16", 0),        # the one-pass control packs W = 4
        (1_000_000, 28, 128, jnp.uint8, 1, "bf16", 0),         # and four features at one bin tile
        (1_000_000, 28, 257, jnp.uint16, 2, "bf16x2", 3),      # DART, the CV folds: Pallas's own rule
    ],
)
def test_packed_level_kernel_compiles_for_the_chip(
    one_chip, no_compile_cache, monkeypatch, n, d, B, dtype, W, prec, members
):
    """The narrow levels' packed body (``ops/histogram.py::_tile_pack``, PR
    47: two features a latched tile, operand and one-hot built as 32-bit
    words and bitcast to bf16) through the chip's own kernel compiler, which
    refuses what the interpreter takes: a slice off the tiling, a bitcast it
    cannot lay out."""
    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod

    monkeypatch.setattr(hist_mod, "pallas_interpret", lambda: False)
    assert hist_mod._tile_pack(W, hist_mod._bin_lanes(B), prec) > 1
    lead = (members,) if members else ()

    def shape(dims, kind):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one_chip)

    def level(b, g, h, node):
        return hist_mod._hist_pallas(b, g, h, node, W, B, prec=prec)

    if members:  # mapped over the members' gradients, the bins the one matrix
        level = jax.vmap(level, in_axes=(None, 0, 0, 0))
    compiled = (
        jax.jit(level)
        .lower(
            shape((n, d), dtype), shape(lead + (n,), jnp.float32),
            shape(lead + (n,), jnp.float32), shape(lead + (n,), jnp.int32),
        )
        .compile()
    )
    assert compiled.as_text().count("tpu_custom_call") >= 1


# ------------------------------------------------- a ranking round's gathers
def _computations(hlo):
    """name -> text of every computation of an HLO module."""
    out = {}
    for comp in re.split(_COMPUTATION, hlo):
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", comp)
        if head:
            out[head.group(1)] = comp
    return out


def _opcodes_under(comps, name, seen=None):
    """Opcodes of computation ``name`` and of every computation it calls
    (fusions, loop bodies and conditions, a conditional's branches,
    reducers)."""
    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return []
    seen.add(name)
    body = comps[name]
    ops = [m.group(2) for m in (re.match(_INSTRUCTION, line) for line in body.split("\n")) if m]
    called = re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", body)
    for name in called + _conditional_branches(body):
        ops += _opcodes_under(comps, name, seen)
    return ops


def _conditional_branches(body):
    """Names of the branch computations of every conditional in ``body``
    (HLO spells two branches ``true_computation=`` / ``false_computation=``
    and more ``branch_computations={...}``)."""
    out = []
    for line in body.split("\n"):
        if " conditional(" not in line:
            continue
        out += re.findall(r"(?:true|false)_computation=%?([\w.\-]+)", line)
        for branches in re.findall(r"branch_computations=\{([^}]*)\}", line):
            out += [b.strip().lstrip("%") for b in branches.split(",")]
    return out


def _computations_under(comps, name):
    """``name`` and every computation it calls."""
    seen = set()
    _opcodes_under(comps, name, seen)
    return sorted(seen)


@pytest.mark.parametrize("scheme", ["pairwise", "ndcg"])
def test_a_ranking_rounds_loop_gathers_the_margins_and_nothing_else(
    one_chip, no_compile_cache, scheme
):
    """K rounds under ``lax.scan``: the loop's body holds one row-to-slot
    gather a bucket (the margins) and the two of ``rank_scatter``; the
    compiler lifted none of the three a bucket the round used to hold out of
    the loop (PERF.md section 6, PR 38), so what is not in the body now is
    not run a round."""
    import numpy as np

    from sagemaker_xgboost_container_tpu.ops import ranking

    rng = np.random.default_rng(0)
    sizes = np.concatenate([rng.integers(1, 33, 3000), rng.integers(33, 65, 1500),
                            rng.integers(65, 129, 800)])
    host = ranking.build_group_layout(sizes)
    buckets = len(host.indices)
    assert buckets == 3
    n = int(sizes.sum())

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    rows = jax.ShapeDtypeStruct((n,), jnp.float32)
    layout = jax.tree_util.tree_map(
        shape, jax.eval_shape(lambda y, w: ranking.with_slot_columns(host, y, w), rows, rows)
    )

    def rounds(margins, layout):
        def body(m, _):
            g, h = ranking.lambdarank_grad_hess(m, layout, scheme)
            return m - 0.1 * g / (h + 1.0), None

        return jax.lax.scan(body, margins, None, length=8)[0]

    hlo = jax.jit(rounds).lower(shape(rows), layout).compile().as_text()
    comps = _computations(hlo)
    entry = next(name for name, text in comps.items() if text.startswith("ENTRY"))
    loops = re.findall(r" while\(.*?body=%?([\w.\-]+)", comps[entry])
    assert len(loops) == 1, loops
    in_loop = _opcodes_under(comps, loops[0])
    assert in_loop.count("gather") == buckets + 2
    # and none was moved in front of the loop
    outside = _opcodes_under(comps, entry, seen={loops[0]})
    assert outside.count("gather") == 0


# --------------------------------------------- a loss-guided round, rolled
LEAFWISE_ROWS, LEAFWISE_VALIDATION, LEAFWISE_FEATURES, LEAVES = 10_500_000, 500_000, 28, 255


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "data_mesh_of_4"])
def test_rolled_loss_guided_round_compiles_for_the_chip(
    described_chips, one_chip, no_compile_cache, monkeypatch, chips
):
    """`higgs-leafwise-l255`'s round (10.5M x 28 in u8, 255 leaves, the step
    replay over 500,000 validation rows) through the chip's own compilers, the
    kernel's too: two kernel call sites whatever `max_leaves` (the root's and
    a pass's) where the unrolled loop held 255, the step loop one `while`
    whose body holds a conditional with the kernel inside it (PR 43: a pass
    runs only for a pick without a histogram), and on a `data` mesh the
    histogram's all-reduce inside that branch too. The replay (PR 44) is a
    second `while`, a column slice a step: no gather and no collective in its
    body, and no row-length gather anywhere in the walk."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
    from sagemaker_xgboost_container_tpu.ops.lossguide import build_tree_lossguide
    from sagemaker_xgboost_container_tpu.ops.tree_build import (
        predict_binned_steps,
        tree_from_packed,
    )
    from sagemaker_xgboost_container_tpu.telemetry.device import STAGE_EVAL_APPLY, stage

    monkeypatch.setattr(hist_mod, "pallas_interpret", lambda: False)
    knobs = resolve_hist_knobs()._replace(backend="tpu")
    axis = "data" if chips > 1 else None

    def one_round(bins, grad, hess, num_cuts, validation_bins):
        tree, row_out = build_tree_lossguide(
            bins, grad, hess, num_cuts, max_leaves=LEAVES, num_bins=NUM_BINS,
            min_child_weight=100.0, eta=0.1, knobs=knobs, axis_name=axis,
        )
        packed = pack_tree(tree)
        with stage(STAGE_EVAL_APPLY):
            walked = predict_binned_steps(
                tree_from_packed(packed), validation_bins, NUM_BINS, table_backend="tpu"
            )
        return packed, row_out, walked

    if chips == 1:
        fn, rows, whole = one_round, one_chip, one_chip
    else:
        mesh = Mesh(np.array(described_chips[:chips]), ("data",))
        fn = jax.shard_map(
            one_round, mesh=mesh,
            in_specs=(P("data", None), P("data"), P("data"), P(), P("data", None)),
            out_specs=(P(), P("data"), P("data")), check_vma=False,
        )
        rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def shape(dims, dtype, sharding):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    n, v = LEAFWISE_ROWS, LEAFWISE_VALIDATION
    hlo = (
        jax.jit(fn)
        .lower(
            shape((n, LEAFWISE_FEATURES), jnp.uint8, rows), shape((n,), jnp.float32, rows),
            shape((n,), jnp.float32, rows), shape((LEAFWISE_FEATURES,), jnp.int32, whole),
            shape((v, LEAFWISE_FEATURES), jnp.uint8, rows),
        )
        .compile()
        .as_text()
    )
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    comps = _computations(hlo)
    step_loops = [
        body for name, text in comps.items()
        for body in re.findall(r" while\([^\n]*?body=%?([\w.\-]+)[^\n]*step_pick", text)
    ]
    assert len(step_loops) == 1, step_loops
    in_loop = _opcodes_under(comps, step_loops[0])
    assert in_loop.count("custom-call") >= 1
    # the kernel is in a branch of the step's conditional, not in the step
    passes = [
        _opcodes_under(comps, branch)
        for name in _computations_under(comps, step_loops[0])
        for branch in _conditional_branches(comps[name])
    ]
    with_kernel = [ops for ops in passes if "custom-call" in ops]
    assert len(with_kernel) == 1 and len(passes) >= 2
    if chips > 1:
        assert "all-reduce" in with_kernel[0] or "all-reduce-start" in with_kernel[0]
    replays = [
        body for name, text in comps.items()
        for body in re.findall(r" while\([^\n]*?body=%?([\w.\-]+)[^\n]*eval_apply", text)
    ]
    assert len(replays) == 1, replays
    in_replay = _opcodes_under(comps, replays[0])
    assert "dynamic-slice" in in_replay
    assert not {"gather", "while", "conditional", "all-reduce", "all-reduce-start"} & set(in_replay)
    walk = [line for line in hlo.split("\n") if "eval_apply" in line and " gather(" in line]
    assert not walk, walk


# ------------------------------------------- a bundled round (sparse input)
SPARSE_ROWS, SPARSE_VALIDATION, SPARSE_BUNDLES = 12_184_290, 1_000_000, 47


def _seeded_tables(bundles, max_bin, seed=0):
    """Bundle tables of ``bundles`` bin columns: three bundles of one (a dense
    column of 200 cuts each), the rest filled with one-position members."""
    import numpy as np

    from sagemaker_xgboost_container_tpu.ops.bundle import BundleTables

    lo = np.zeros((bundles, max_bin), np.int32)
    hi = np.zeros((bundles, max_bin), np.int32)
    legal = np.zeros((bundles, max_bin), bool)
    column = np.full((bundles, max_bin), -1, np.int32)
    rng = np.random.default_rng(seed)
    next_column = 0
    for b in range(bundles):
        if b < 3:
            hi[b, :201], legal[b, :200], column[b, :201] = 201, True, next_column
            next_column += 1
            continue
        used = int(rng.integers(max_bin // 2, max_bin + 1))
        lo[b, :used] = np.arange(used)
        hi[b, :used] = np.arange(used) + 1
        legal[b, :used] = True
        column[b, :used] = next_column + np.arange(used)
        next_column += used
    return BundleTables(lo, hi, legal, column), next_column


def test_bundled_round_compiles_for_the_chip(one_chip, no_compile_cache, monkeypatch):
    """`allstate-onehot-d8`'s round (12,184,290 rows x 47 bundle columns in
    u16, depth 8, the level walk's range test over 1,000,000 validation rows)
    through the chip's own compilers, the kernel's too: the same eight kernel
    call sites as a dense depth-8 build, the bundled scan's masked sums as
    dots, and no row-length gather in the build or in the walk."""
    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
    from sagemaker_xgboost_container_tpu.ops.tree_build import (
        choose_route_impl,
        predict_binned_levels,
        tree_from_packed,
    )

    monkeypatch.setattr(hist_mod, "pallas_interpret", lambda: False)
    knobs = resolve_hist_knobs()._replace(backend="tpu")
    tables, columns = _seeded_tables(SPARSE_BUNDLES, NUM_BINS - 1)

    def one_round(bins, grad, hess, num_cuts, validation_bins):
        tree, row_out = build_tree(
            bins, grad, hess, num_cuts, max_depth=DEPTH, num_bins=NUM_BINS,
            min_child_weight=100.0, eta=0.1, knobs=knobs, bundle=tables,
            feature_mask=jnp.ones(columns, jnp.float32),
        )
        packed = pack_tree(tree)
        walked = predict_binned_levels(
            tree_from_packed(packed), validation_bins, DEPTH, NUM_BINS,
            route_impl=choose_route_impl("tpu", SPARSE_BUNDLES), table_backend="tpu",
            bundle=tables,
        )
        return packed, row_out, walked

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    n, v = SPARSE_ROWS, SPARSE_VALIDATION
    compiled = (
        jax.jit(one_round)
        .lower(
            shape((n, SPARSE_BUNDLES), jnp.uint16), shape((n,), jnp.float32),
            shape((n,), jnp.float32), shape((columns,), jnp.int32),
            shape((v, SPARSE_BUNDLES), jnp.uint16),
        )
        .compile()
    )
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == DEPTH
    # no per-row gather: a gather's result is never a row long
    for dims in re.findall(r" = \S+\[([0-9,]*)\][^\n]* gather\(", hlo):
        assert str(n) not in dims.split(",") and str(v) not in dims.split(","), dims
    # the program's own arguments and scratch fit the chip beside the bins
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 6 << 30, memory


# ------------------------------ a categorical round (columns as categories)
CAT_SIZES = (2, 2, 3, 3, 4, 5, 6, 8, 10, 12, 14, 18, 23, 28, 75, 1300, 2700)


def test_categorical_round_compiles_for_the_chip(one_chip, no_compile_cache, monkeypatch):
    """`allstate-cat-d8`'s round (12,184,290 rows x 47 bin columns in u16: 15
    numeric columns and the chunks of 17 categorical ones; depth 8; the level
    walk's set test over 1,000,000 validation rows) through the chip's own
    compilers: the eight kernel call sites of a dense depth-8 build, the
    partition scan without a sort, and no row-length gather in the build or
    in the walk: the level tables (at most 128 x 85 words) are read by the
    select pass or, from ``SET_READ_SELECT_MAX_ENTRIES`` up, by the product
    kernel ``graft_cat_set_read`` (PR 51), one call a level of the build and
    of the walk, and no ``[rows, 4 * words]`` product is ever written out."""
    from sagemaker_xgboost_container_tpu.data.categorical import CatLayout
    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
    from sagemaker_xgboost_container_tpu.ops.categorical import CatTables, choose_set_read_impl
    from sagemaker_xgboost_container_tpu.ops.tree_build import (
        choose_route_impl,
        pack_round_trees,
        predict_binned_levels,
        round_tree_from_packed,
    )

    monkeypatch.setattr(hist_mod, "pallas_interpret", lambda: False)
    knobs = resolve_hist_knobs()._replace(backend="tpu")
    layout = CatLayout(["q"] * 15 + ["c"] * 17, [0] * 15 + list(CAT_SIZES), NUM_BINS - 1)
    tables = CatTables(layout, 4, 64)
    columns = layout.num_bin_columns
    assert (columns, tables.words) == (47, 85)

    def one_round(bins, grad, hess, num_cuts, validation_bins):
        tree, row_out = build_tree(
            bins, grad, hess, num_cuts, max_depth=DEPTH, num_bins=NUM_BINS,
            min_child_weight=100.0, eta=0.1, knobs=knobs, cat=tables,
            feature_mask=jnp.ones(columns, jnp.float32),
        )
        packed = pack_round_trees(tree)
        walked = predict_binned_levels(
            round_tree_from_packed(packed, tables.words), validation_bins, DEPTH, NUM_BINS,
            route_impl=choose_route_impl("tpu", columns), table_backend="tpu", cat=tables,
        )
        return packed, row_out, walked

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    n, v = SPARSE_ROWS, SPARSE_VALIDATION
    compiled = (
        jax.jit(one_round)
        .lower(
            shape((n, columns), jnp.uint16), shape((n,), jnp.float32),
            shape((n,), jnp.float32), shape((columns,), jnp.int32),
            shape((v, columns), jnp.uint16),
        )
        .compile()
    )
    hlo = compiled.as_text()
    products = sum(
        choose_set_read_impl("tpu", tables.words << level) == "product" for level in range(DEPTH)
    )
    assert products >= 4  # the wide levels, whose select pass cost 150 ms a round
    assert hlo.count('custom_call_target="tpu_custom_call"') == DEPTH + 2 * products
    assert len(re.findall(r"custom-call\([^\n]*graft_cat_set_read", hlo)) == 2 * products
    assert not re.findall(r" sort\(", hlo)
    # the packed tree: ten rows and the sets' two halves a word
    assert "f32[{},511]".format(10 + 2 * tables.words) in hlo
    for dims in re.findall(r" = \S+\[([0-9,]*)\][^\n]* gather\(", hlo):
        assert str(n) not in dims.split(",") and str(v) not in dims.split(","), dims
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 6 << 30, memory
