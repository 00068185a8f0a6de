"""Trace-safety pass: what must never happen inside a jitted closure.

The jitted round path is the product (PAPER.md: >=5 rounds/sec needs a
round program that never silently recompiles or syncs to host), and both
bug classes have shipped before: the PR-4 per-round re-sketch recompile
(``jax.jit`` constructed per call) and assorted trace-time knob reads that
PR 4 had to hoist to session build (the histogram collective's name, gone
since PR 45; ``ops/histogram.resolve_hist_knobs`` is the pattern). This
pass makes the policy mechanical.

**Reachability.** Roots are functions handed to ``jax.jit``/``pjit``/
``shard_map`` (as arguments, through ``functools.partial``, through simple
local aliases/ternaries, or as decorators, ``@partial(jax.jit, ...)``
included). From the roots, a name-based call graph follows: direct calls,
``self.method`` calls, imported names (absolute and relative), module
attribute calls, and bare *references* (a function passed to
``lax.scan``/``vmap``/a callback slot is treated as called). Nested
functions resolve through their lexical scope chain. The graph
over-approximates on purpose: a function that *might* run under trace is
held to trace rules.

Rules:

* ``trace-env-read`` — ``os.environ``/``os.getenv``/``env_int``-family
  reads inside a reachable function. Knobs are resolved once at session
  build time and threaded in (the ``resolve_hist_knobs`` pattern): a
  trace-time read bakes whatever the env said at first trace into the
  compiled program, so mid-job changes silently do nothing and two shards
  tracing at different times can disagree. The ``env_int``-family helper
  *definitions* in an ``envconfig`` module are exempt: the call sites are
  the policy surface, and each suppressed caller would otherwise drag the
  helper body back into the reachable set as a duplicate finding.
* ``trace-uncached-jit`` — ``jax.jit(...)`` constructed inside a function
  not decorated with ``functools.lru_cache``/``cache``. Every call makes a
  fresh wrapper with a fresh (empty) compile cache — the re-sketch
  recompile class. Module-level jit, decorator jit, and jit inside
  ``lru_cache``'d factories are fine. Applies to every function, reachable
  or not (hot-path callers are exactly the ones a reachability analysis
  can miss).
* ``trace-host-sync`` — ``.item()``/``.tolist()``, ``np.asarray``/
  ``np.array`` on values flowing through a reachable function,
  ``jax.device_get``, ``print``, and ``float()``/``int()``/``bool()``
  applied directly to a root function's parameter: each forces a device
  sync (or fails to trace) in code meant to stay on-device.
"""

import ast

from ..core import Finding, PACKAGE
from ..astutil import (
    ImportMap,
    decorator_names,
    dotted_name,
    iter_own_nodes,
    module_str_constants,
    str_const,
)

_JIT_LEAVES = {"jit", "pjit"}
_WRAPPER_LEAVES = {"partial", "jit", "pjit", "shard_map", "vmap", "checkpoint", "remat"}
_ENV_CALLS = {"os.getenv", "os.environ.get", "environ.get", "getenv"}
_ENVCONFIG_HELPERS = {"env_int", "env_float", "env_bool", "env_port"}
_CACHE_DECORATORS = {"lru_cache", "cache", "functools.lru_cache", "functools.cache"}
_NUMPY_SYNC_LEAVES = {"asarray", "array", "ascontiguousarray"}


class FuncInfo(object):
    __slots__ = (
        "qual", "node", "sf", "parent", "class_name", "assigns", "own_defs",
        "is_cached", "params",
    )

    def __init__(self, qual, node, sf, parent, class_name):
        self.qual = qual
        self.node = node
        self.sf = sf
        self.parent = parent
        self.class_name = class_name
        self.assigns = {}
        self.own_defs = {}
        self.is_cached = any(
            d in _CACHE_DECORATORS for d in decorator_names(node)
        )
        args = node.args
        self.params = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]

    def cached_anywhere(self):
        cur = self
        while cur is not None:
            if cur.is_cached:
                return True
            cur = cur.parent
        return False


class _ModuleIndex(object):
    def __init__(self, sf):
        self.sf = sf
        self.imports = ImportMap(sf.tree, sf.module)
        self.funcs = {}          # id(node) -> FuncInfo
        self.toplevel = {}       # name -> FuncInfo
        self.methods = {}        # (class, name) -> FuncInfo
        self.module_assigns = {}  # top-level name aliases
        self.constants = module_str_constants(sf.tree)
        self._collect(sf.tree, parent=None, class_name=None, prefix="")
        for node in sf.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    self.module_assigns.setdefault(t.id, []).append(node.value)

    def _collect(self, node, parent, class_name, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = prefix + child.name
                info = FuncInfo(qual, child, self.sf, parent, class_name)
                self.funcs[id(child)] = info
                if parent is None and class_name is None:
                    self.toplevel[child.name] = info
                if class_name is not None and parent is None:
                    self.methods[(class_name, child.name)] = info
                if parent is not None:
                    parent.own_defs[child.name] = info
                self._collect(child, info, class_name, qual + ".")
                self._collect_assigns(child, info)
            elif isinstance(child, ast.ClassDef):
                self._collect(child, None, child.name, prefix + child.name + ".")
            else:
                self._collect(child, parent, class_name, prefix)

    def _collect_assigns(self, func_node, info):
        for n in iter_own_nodes(func_node):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        info.assigns.setdefault(t.id, []).append(n.value)


class TraceSafetyPass(object):
    rules = {
        "trace-env-read": "env knob read inside a jit/shard_map-reachable function",
        "trace-uncached-jit": "jax.jit constructed inside a non-cached function",
        "trace-host-sync": "host-sync call inside a jit/shard_map-reachable function",
    }

    # ------------------------------------------------------------ resolution
    def _resolve_name(self, name, info, index, _visited=None):
        """A bare name in function ``info`` -> [FuncInfo] candidates.

        ``_visited`` guards assignment cycles (``x = x or default``) and
        mutually-aliasing names.
        """
        if _visited is None:
            _visited = set()
        key = (id(info), id(index), name)
        if key in _visited:
            return []
        _visited.add(key)
        cur = info
        while cur is not None:
            if name in cur.own_defs:
                return [cur.own_defs[name]]
            if name in cur.assigns:
                out = []
                for expr in cur.assigns[name]:
                    out.extend(
                        self._resolve_callable(expr, cur, index, depth=0,
                                               _visited=_visited)
                    )
                if out:
                    return out
            cur = cur.parent
        if name in index.toplevel:
            return [index.toplevel[name]]
        if name in index.module_assigns:
            out = []
            for expr in index.module_assigns[name]:
                out.extend(
                    self._resolve_callable(expr, None, index, depth=0,
                                           _visited=_visited)
                )
            if out:
                return out
        if name in index.imports.names:
            mod, orig = index.imports.names[name]
            target = self._lookup(mod)
            if target is not None and orig in target.toplevel:
                return [target.toplevel[orig]]
        return []

    def _resolve_attr(self, expr, info, index):
        """self.x / module.attr -> [FuncInfo]."""
        name = dotted_name(expr)
        if not name:
            return []
        parts = name.split(".")
        if parts[0] == "self" and len(parts) == 2 and info is not None:
            cls = info.class_name
            # walk up: nested functions keep the defining class
            cur = info
            while cls is None and cur is not None:
                cls = cur.class_name
                cur = cur.parent
            hit = index.methods.get((cls, parts[1]))
            return [hit] if hit else []
        if len(parts) == 2:
            base, attr = parts
            mod_path = None
            if base in index.imports.modules:
                mod_path = index.imports.modules[base]
            elif base in index.imports.names:
                src, orig = index.imports.names[base]
                mod_path = src + "." + orig
            if mod_path:
                target = self._lookup(mod_path)
                if target is not None and attr in target.toplevel:
                    return [target.toplevel[attr]]
        return []

    def _resolve_callable(self, expr, info, index, depth, _visited=None):
        """An expression in callable position -> [FuncInfo]."""
        if depth > 6:
            return []
        if _visited is None:
            _visited = set()
        if isinstance(expr, ast.Name):
            return self._resolve_name(expr.id, info, index, _visited=_visited)
        if isinstance(expr, ast.Attribute):
            return self._resolve_attr(expr, info, index)
        if isinstance(expr, ast.IfExp):
            return self._resolve_callable(
                expr.body, info, index, depth + 1, _visited=_visited
            ) + self._resolve_callable(expr.orelse, info, index, depth + 1,
                                       _visited=_visited)
        if isinstance(expr, ast.Call):
            callee = dotted_name(expr.func) or ""
            leaf = callee.rsplit(".", 1)[-1]
            if leaf in _WRAPPER_LEAVES and expr.args:
                return self._resolve_callable(expr.args[0], info, index,
                                              depth + 1, _visited=_visited)
        if isinstance(expr, ast.Lambda):
            # a lambda body runs in the enclosing trace: resolve every name
            # it references
            out = []
            for n in ast.walk(expr.body):
                if isinstance(n, ast.Name):
                    out.extend(
                        self._resolve_name(n.id, info, index, _visited=_visited)
                    )
            return out
        return []

    # --------------------------------------------------------------- graph
    def _owning_info(self, node, index, parents):
        cur = parents.get(node)
        while cur is not None:
            if id(cur) in index.funcs:
                return index.funcs[id(cur)]
            cur = parents.get(cur)
        return None

    def _lookup(self, mod):
        """Module index for a dotted import path, tolerant of the package
        prefix: scanned modules are keyed by path relative to the scan root,
        so when the root is the repo they carry the ``PACKAGE.`` prefix but
        an absolute import in a fixture tree may not (and vice versa when
        the scan root is the package dir itself)."""
        hit = self._indices.get(mod)
        if hit is not None:
            return hit
        prefix = PACKAGE + "."
        if mod.startswith(prefix):
            return self._indices.get(mod[len(prefix):])
        return self._indices.get(prefix + mod)

    def _build(self, project):
        from ..astutil import enclosing_map

        self._indices = {}
        self._parents = {}
        for sf in project.files:
            if sf.tree is None:
                continue
            self._indices[sf.module] = _ModuleIndex(sf)
            self._parents[sf.module] = enclosing_map(sf.tree)
        roots = set()
        edges = {}
        for mod, index in list(self._indices.items()):
            parents = self._parents[mod]
            for node in ast.walk(index.sf.tree):
                if isinstance(node, ast.Call):
                    callee = dotted_name(node.func) or ""
                    leaf = callee.rsplit(".", 1)[-1]
                    if leaf in _JIT_LEAVES or leaf == "shard_map":
                        owner = self._owning_info(node, index, parents)
                        if node.args:
                            for target in self._resolve_callable(
                                node.args[0], owner, index, depth=0
                            ):
                                roots.add(id(target.node))
                                self._root_infos[id(target.node)] = target
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    decs = decorator_names(node)
                    if any(d.rsplit(".", 1)[-1] in _JIT_LEAVES for d in decs):
                        info = index.funcs.get(id(node))
                        if info is not None:
                            roots.add(id(node))
                            self._root_infos[id(node)] = info

            # reference edges
            for fid, info in index.funcs.items():
                targets = edges.setdefault(fid, set())
                for n in iter_own_nodes(info.node):
                    cands = []
                    if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                        cands = self._resolve_name(n.id, info, index)
                    elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                        cands = self._resolve_attr(n.func, info, index)
                    for cand in cands:
                        if id(cand.node) != fid:
                            targets.add(id(cand.node))
                            self._root_infos[id(cand.node)] = cand

        reachable = set(roots)
        frontier = list(roots)
        while frontier:
            cur = frontier.pop()
            for nxt in edges.get(cur, ()):
                if nxt not in reachable:
                    reachable.add(nxt)
                    frontier.append(nxt)
        return roots, reachable

    # ----------------------------------------------------------------- run
    def run(self, project):
        self._root_infos = {}
        roots, reachable = self._build(project)

        for mod, index in self._indices.items():
            sf = index.sf
            for fid, info in index.funcs.items():
                # uncached-jit applies to every function
                for finding in self._check_uncached_jit(sf, info):
                    yield finding
                if fid not in reachable:
                    continue
                is_root = fid in roots
                for finding in self._check_env_reads(sf, info, index):
                    yield finding
                for finding in self._check_host_sync(sf, info, index, is_root):
                    yield finding

    def _check_uncached_jit(self, sf, info):
        if info.cached_anywhere():
            return
        for n in iter_own_nodes(info.node):
            if not isinstance(n, ast.Call):
                continue
            callee = dotted_name(n.func) or ""
            leaf = callee.rsplit(".", 1)[-1]
            if leaf in _JIT_LEAVES and (
                "." in callee or self._is_jax_import(callee, info)
            ):
                yield Finding(
                    "trace-uncached-jit",
                    sf.relpath,
                    n.lineno,
                    "jax.jit constructed inside '{}' — every call builds a "
                    "fresh wrapper with an empty compile cache (the PR-4 "
                    "re-sketch recompile class); hoist to module level or an "
                    "lru_cache'd factory".format(info.qual),
                )

    def _is_jax_import(self, name, info):
        index = self._indices.get(info.sf.module)
        if index is None:
            return False
        src = index.imports.names.get(name)
        return bool(src and src[0].split(".")[0] == "jax")

    def _env_name_of(self, call, index):
        if call.args:
            lit = str_const(call.args[0])
            if lit:
                return lit
            if isinstance(call.args[0], ast.Name):
                return index.constants.get(call.args[0].id)
        return None

    def _check_env_reads(self, sf, info, index):
        if (
            info.qual in _ENVCONFIG_HELPERS
            and sf.module.rsplit(".", 1)[-1] == "envconfig"
        ):
            # the helper bodies ARE the env read; policy is enforced at their
            # call sites (calls to the env_int family are themselves findings),
            # so flagging the definition would re-report every justified
            # caller one level down
            return
        for n in iter_own_nodes(info.node):
            hit = None
            if isinstance(n, ast.Call):
                callee = dotted_name(n.func) or ""
                if callee in _ENV_CALLS or (
                    callee in _ENVCONFIG_HELPERS
                ):
                    hit = self._env_name_of(n, index)
                    hit = hit or "<dynamic>"
            elif isinstance(n, ast.Subscript):
                base = dotted_name(n.value) or ""
                if base in ("os.environ", "environ"):
                    hit = str_const(n.slice) or "<dynamic>"
            if hit is not None:
                yield Finding(
                    "trace-env-read",
                    sf.relpath,
                    n.lineno,
                    "env read ({}) inside jit-reachable '{}' — resolve the "
                    "knob at session build time and thread it in (the "
                    "resolve_hist_knobs pattern, docs/static-analysis.md)".format(
                        hit, info.qual
                    ),
                )

    def _check_host_sync(self, sf, info, index, is_root):
        numpy_aliases = {
            alias
            for alias, target in index.imports.modules.items()
            if target == "numpy"
        }
        for n in iter_own_nodes(info.node):
            if not isinstance(n, ast.Call):
                continue
            callee = dotted_name(n.func) or ""
            leaf = callee.rsplit(".", 1)[-1]
            reason = None
            if (
                isinstance(n.func, ast.Attribute)
                and n.func.attr in ("item", "tolist")
                and not n.args
            ):
                # checked off the attribute itself, not the dotted chain:
                # `x.sum().item()` has no resolvable dotted name but syncs
                # all the same
                reason = ".{}() forces a device->host sync".format(n.func.attr)
            elif leaf in _NUMPY_SYNC_LEAVES and "." in callee and (
                callee.split(".")[0] in numpy_aliases
            ):
                reason = "{} materializes a device value on host".format(callee)
            elif callee in ("jax.device_get",) or leaf == "device_get":
                reason = "device_get forces a device->host sync"
            elif callee == "print":
                reason = "print() inside traced code runs at trace time only " \
                         "(and syncs when given device values)"
            elif (
                is_root
                and callee in ("float", "int", "bool")
                and len(n.args) == 1
                and isinstance(n.args[0], ast.Name)
                and n.args[0].id in info.params
            ):
                reason = "{}() on traced argument '{}' forces a host sync".format(
                    callee, n.args[0].id
                )
            if reason:
                yield Finding(
                    "trace-host-sync",
                    sf.relpath,
                    n.lineno,
                    "{} inside jit-reachable '{}' — keep the round path "
                    "on-device (docs/static-analysis.md)".format(reason, info.qual),
                )
