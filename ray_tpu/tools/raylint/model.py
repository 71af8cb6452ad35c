"""The raylint project model: ONE parse of the whole package.

Every rule runs against this shared index instead of re-walking files:

- module index: dotted module name -> parsed AST + source lines
- function table: qualified name ("pkg.mod:Cls.meth") -> FuncInfo
- class table: lock/condition attributes (assignments of
  ``threading.Lock/RLock/Condition``), method sets, base names
- call graph: conservative name-based resolution (self-methods,
  module-local functions, imported symbols, project classes ->
  ``__init__``, plus a unique-method-name fallback for cross-class
  edges) — enough to chase ``blocking-under-lock`` transitively
- suppressions: ``# raylint: disable=<rule>[,<rule>] -- reason``
  parsed out of the raw source (AST drops comments)

The model is deliberately approximate where Python is dynamic: rules
prefer a small number of explainable false positives (silenced with a
reasoned ``disable``) over silent false negatives in the invariants
this framework actually depends on.
"""

from __future__ import annotations

import ast
import hashlib
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

# disable comment syntax: "raylint: disable=<rules> -- <why>"
_SUPPRESS_RE = re.compile(
    r"#\s*raylint:\s*disable=([a-zA-Z0-9_,\- ]+?)"
    r"(?:\s*--\s*(?P<reason>\S.*))?\s*$")

_LOCK_FACTORIES = {"Lock", "RLock"}
_COND_FACTORIES = {"Condition"}


@dataclass
class Suppression:
    line: int
    rules: Set[str]
    reason: Optional[str]
    comment_only: bool  # whole line is the comment -> guards line+1


@dataclass
class ModuleInfo:
    name: str                      # dotted ("ray_tpu.cluster.head")
    path: str                      # absolute
    relpath: str                   # project-root relative
    tree: ast.Module
    lines: List[str]
    is_package: bool = False       # an __init__.py (relative imports
    #                                anchor at the package ITSELF)
    suppressions: List[Suppression] = field(default_factory=list)
    imports: Dict[str, str] = field(default_factory=dict)
    # module-level names bound to threading.Lock()/RLock()/Condition()
    locks: Set[str] = field(default_factory=set)
    conds: Set[str] = field(default_factory=set)


@dataclass
class ClassInfo:
    qualname: str                  # "pkg.mod:Cls"
    module: str
    name: str
    node: ast.ClassDef
    bases: List[str] = field(default_factory=list)
    methods: Dict[str, str] = field(default_factory=dict)  # name->func qn
    lock_attrs: Set[str] = field(default_factory=set)
    cond_attrs: Set[str] = field(default_factory=set)
    # cond attr -> the lock attr it WRAPS ("self._cond =
    # threading.Condition(self._lock)"): the condition IS that lock
    # for ordering purposes — acquiring one while holding the other
    # is reentrant, not an inversion.
    cond_alias: Dict[str, str] = field(default_factory=dict)


@dataclass
class CallEdge:
    """One call-graph edge with its resolution confidence.  ``kind``:
    "self" (self.method), "local" (sibling/nested def), "module"
    (module-local function or alias.func into a project module),
    "import" (imported project symbol), "init" (class -> __init__),
    "fallback" (unique-method-name guess — class-blind, the edge the
    lock-set propagation must NOT trust)."""
    target: str
    line: int
    via: str
    kind: str


@dataclass
class FuncInfo:
    qualname: str                  # "pkg.mod:Cls.meth" / "pkg.mod:fn"
    module: str
    cls: Optional[str]             # enclosing class simple name
    name: str
    node: ast.AST                  # FunctionDef | AsyncFunctionDef
    line: int


class ProjectModel:
    """Parse ``root`` (a package directory) once and index it."""

    def __init__(self, root: str, package: Optional[str] = None):
        self.root = os.path.abspath(root)
        self.project_dir = os.path.dirname(self.root) or "."
        self.package = package or os.path.basename(self.root.rstrip("/"))
        self.modules: Dict[str, ModuleInfo] = {}
        self.classes: Dict[str, ClassInfo] = {}
        self.functions: Dict[str, FuncInfo] = {}
        # bare function/method name -> qualnames defining it
        self.by_name: Dict[str, List[str]] = {}
        # call graph: func qualname -> [(callee qualname, line, via)]
        # (legacy 3-tuple view; call_edges carries the resolution kind)
        self.calls: Dict[str, List[Tuple[str, int, str]]] = {}
        self.call_edges: Dict[str, List[CallEdge]] = {}
        self.parse_errors: List[Tuple[str, str]] = []
        self._own_cache: Dict[int, List[ast.AST]] = {}
        # (call-node id, enclosing fn qualname) -> resolved
        # (target, kind) | None.  Resolution (inheritance walks,
        # import chasing) is re-requested for the same Call node by
        # the call-graph build, the lock-set scan, the raise
        # inference, and the try indexing — memoize it.  Node ids
        # stay valid for the model's lifetime (ModuleInfo pins every
        # tree); the qualname qualifier matters because the parse
        # memo SHARES one AST between byte-identical files, so the
        # same node resolves under different modules' import/class
        # contexts.
        self._edge_cache: Dict[Tuple[int, str],
                               Optional[Tuple[str, str]]] = {}
        self._locks: Optional[LockAnalysis] = None
        self._flow: Optional[DeviceFlow] = None
        self._load()
        self._index()
        self._build_call_graph()

    def lock_analysis(self) -> "LockAnalysis":
        """The interprocedural lock-set model, built once on demand
        (the lock-order and wait rules share it, and the CLI dumps
        its graph)."""
        if self._locks is None:
            self._locks = LockAnalysis(self)
        return self._locks

    def device_flow(self) -> "DeviceFlow":
        """The traced-value (device-plane) dataflow model, built once
        on demand — the host-device-sync / recompile-hazard /
        missing-donation rules all read it."""
        if self._flow is None:
            self._flow = DeviceFlow(self)
        return self._flow

    # ------------------------------------------------------------ loading
    def _load(self) -> None:
        cache = _ParseCache.open(self.project_dir)
        for dirpath, dirnames, filenames in os.walk(self.root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ("__pycache__",))
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, self.project_dir)
                modname = self._modname(path)
                try:
                    with open(path, "rb") as f:
                        raw = f.read()
                    src = raw.decode("utf-8")
                    tree = cache.get(raw)
                    if tree is None:
                        tree = ast.parse(src, filename=path)
                        cache.put(raw, tree)
                except (SyntaxError, UnicodeDecodeError, OSError) as e:
                    self.parse_errors.append((rel, str(e)))
                    continue
                info = ModuleInfo(name=modname, path=path, relpath=rel,
                                  tree=tree, lines=src.splitlines(),
                                  is_package=fn == "__init__.py")
                self._scan_suppressions(info)
                self._scan_imports(info)
                self.modules[modname] = info
        cache.save()

    def _modname(self, path: str) -> str:
        rel = os.path.relpath(path, os.path.dirname(self.root))
        rel = rel[:-3] if rel.endswith(".py") else rel
        parts = rel.split(os.sep)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)

    def _scan_suppressions(self, info: ModuleInfo) -> None:
        for i, line in enumerate(info.lines, start=1):
            if "raylint" not in line:
                continue
            m = _SUPPRESS_RE.search(line)
            if m is None:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            info.suppressions.append(Suppression(
                line=i, rules=rules, reason=m.group("reason"),
                comment_only=line.strip().startswith("#")))

    def _scan_imports(self, info: ModuleInfo) -> None:
        """name -> fully-qualified target ("pkg.mod" or "pkg.mod.sym")."""
        for node in ast.walk(info.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    info.imports[alias.asname or
                                 alias.name.split(".")[0]] = alias.name
            elif isinstance(node, ast.ImportFrom):
                base = self._resolve_from(info, node)
                if base is None:
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    info.imports[alias.asname or alias.name] = \
                        f"{base}.{alias.name}"

    def _resolve_from(self, info: ModuleInfo,
                      node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module
        parts = info.name.split(".")
        # "from . import x" in a plain module drops the module's own
        # leaf; in a package __init__ the single dot IS the package
        # (its dotted name already lacks the "__init__" leaf), so a
        # package strips one level fewer.  Each extra dot climbs one
        # more package either way.
        drop = node.level - (1 if info.is_package else 0)
        if drop > len(parts):
            return None
        anchor = parts[:-drop] if drop else list(parts)
        if node.module:
            anchor = anchor + node.module.split(".")
        return ".".join(anchor) if anchor else None

    # ----------------------------------------------------------- indexing
    def _index(self) -> None:
        for info in self.modules.values():
            self._index_module_locks(info)
            for node in info.tree.body:
                if isinstance(node, ast.ClassDef):
                    self._index_class(info, node)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    self._index_func(info, node, cls=None)

    def _is_factory(self, info: ModuleInfo, call: ast.AST,
                    names: Set[str]) -> bool:
        """``threading.Lock()`` / ``Lock()`` (imported) value?"""
        if not isinstance(call, ast.Call):
            return False
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr in names and \
                isinstance(f.value, ast.Name) and \
                info.imports.get(f.value.id, f.value.id) == "threading":
            return True
        if isinstance(f, ast.Name) and f.id in names and \
                info.imports.get(f.id, "").startswith("threading."):
            return True
        return False

    def _index_module_locks(self, info: ModuleInfo) -> None:
        for node in info.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                if self._is_factory(info, node.value, _LOCK_FACTORIES):
                    info.locks.add(name)
                elif self._is_factory(info, node.value, _COND_FACTORIES):
                    info.conds.add(name)

    def _index_class(self, info: ModuleInfo, node: ast.ClassDef) -> None:
        qn = f"{info.name}:{node.name}"
        ci = ClassInfo(qualname=qn, module=info.name, name=node.name,
                       node=node,
                       bases=[b.id for b in node.bases
                              if isinstance(b, ast.Name)])
        self.classes[qn] = ci
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = self._index_func(info, item, cls=node.name)
                ci.methods[item.name] = fi.qualname
        # lock attributes: "self.X = threading.Lock()" anywhere in the
        # class body (usually __init__, but not only)
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                t = sub.targets[0]
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self":
                    if self._is_factory(info, sub.value, _LOCK_FACTORIES):
                        ci.lock_attrs.add(t.attr)
                    elif self._is_factory(info, sub.value,
                                          _COND_FACTORIES):
                        ci.cond_attrs.add(t.attr)
                        arg = (sub.value.args[0]
                               if sub.value.args else None)
                        if isinstance(arg, ast.Attribute) and \
                                isinstance(arg.value, ast.Name) and \
                                arg.value.id == "self":
                            ci.cond_alias[t.attr] = arg.attr

    def _index_func(self, info: ModuleInfo, node, cls: Optional[str],
                    prefix: str = "") -> FuncInfo:
        base = f"{cls}." if cls else ""
        qn = f"{info.name}:{prefix}{base}{node.name}"
        fi = FuncInfo(qualname=qn, module=info.name, cls=cls,
                      name=node.name, node=node, line=node.lineno)
        self.functions[qn] = fi
        self.by_name.setdefault(node.name, []).append(qn)
        # nested defs become their own nodes (resolved by local name)
        self._index_nested(info, node, cls,
                           prefix=f"{prefix}{base}{node.name}.")
        return fi

    def _index_nested(self, info: ModuleInfo, func_node, cls,
                      prefix) -> None:
        """Index the defs DIRECTLY nested in ``func_node``; each level
        recurses with its own prefix, so ``outer.a.helper`` and
        ``outer.b.helper`` never collide (a collision would silently
        drop the second body from every rule's scan)."""
        for sub in self._direct_child_defs(func_node):
            qn = f"{info.name}:{prefix}{sub.name}"
            if qn in self.functions:
                # same name re-bound within one scope (rare):
                # disambiguate by line rather than drop the body
                qn = f"{qn}@{sub.lineno}"
            fi = FuncInfo(qualname=qn, module=info.name, cls=cls,
                          name=sub.name, node=sub, line=sub.lineno)
            self.functions[qn] = fi
            self.by_name.setdefault(sub.name, []).append(qn)
            self._index_nested(info, sub, cls,
                               prefix=f"{prefix}{sub.name}.")

    @staticmethod
    def _direct_child_defs(func_node):
        """FunctionDefs nested in ``func_node`` without crossing
        another function boundary (does descend into if/try/with/
        loops and class bodies)."""
        out = []
        stack = list(ast.iter_child_nodes(func_node))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                out.append(node)
                continue
            if isinstance(node, ast.Lambda):
                continue
            stack.extend(ast.iter_child_nodes(node))
        return out

    # --------------------------------------------------------- call graph
    def _build_call_graph(self) -> None:
        for fi in list(self.functions.values()):
            edges: List[CallEdge] = []
            info = self.modules[fi.module]
            for node in self.walk_own(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                hit = self._resolve_call_edge(info, fi, node)
                if hit is not None:
                    target, kind = hit
                    edges.append(CallEdge(target, node.lineno,
                                          call_desc(node), kind))
            self.call_edges[fi.qualname] = edges
            self.calls[fi.qualname] = [(e.target, e.line, e.via)
                                       for e in edges]

    def walk_own(self, func_node):
        """All nodes of a function body WITHOUT descending into nested
        function definitions (they execute elsewhere) or lambdas.
        Cached per node: every rule re-walks every function, and the
        traversal dominates the whole lint wall-clock otherwise."""
        cached = self._own_cache.get(id(func_node))
        if cached is not None:
            return cached
        out = []
        stack = list(ast.iter_child_nodes(func_node))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            out.append(node)
            stack.extend(ast.iter_child_nodes(node))
        self._own_cache[id(func_node)] = out
        return out

    def _resolve_call(self, info: ModuleInfo, fi: FuncInfo,
                      call: ast.Call) -> Optional[str]:
        hit = self._resolve_call_edge(info, fi, call)
        return hit[0] if hit is not None else None

    def _resolve_call_edge(self, info: ModuleInfo, fi: FuncInfo,
                           call: ast.Call
                           ) -> Optional[Tuple[str, str]]:
        """(callee qualname, edge kind) — see CallEdge for kinds."""
        key = (id(call), fi.qualname)
        if key in self._edge_cache:
            return self._edge_cache[key]
        out = self._resolve_call_edge_uncached(info, fi, call)
        self._edge_cache[key] = out
        return out

    def _resolve_call_edge_uncached(self, info: ModuleInfo,
                                    fi: FuncInfo, call: ast.Call
                                    ) -> Optional[Tuple[str, str]]:
        f = call.func
        if isinstance(f, ast.Name):
            return self._resolve_name_kind(info, fi, f.id)
        if isinstance(f, ast.Attribute):
            # self.method(...)
            if isinstance(f.value, ast.Name) and f.value.id == "self" \
                    and fi.cls is not None:
                qn = self._method_on(info.name, fi.cls, f.attr)
                if qn is not None:
                    return qn, "self"
            # module_alias.func(...)
            if isinstance(f.value, ast.Name):
                target = info.imports.get(f.value.id)
                if target in self.modules:
                    mod = self.modules[target]
                    qn = f"{mod.name}:{f.attr}"
                    if qn in self.functions:
                        return qn, "module"
            # unique-method fallback: exactly one project definition of
            # this name -> conservative (class-blind) edge
            cands = self.by_name.get(f.attr, ())
            if len(cands) == 1:
                return cands[0], "fallback"
        return None

    def _method_on(self, module: str, cls: str,
                   name: str) -> Optional[str]:
        """Method lookup on a class, following project-local bases."""
        seen: Set[str] = set()
        stack = [f"{module}:{cls}"]
        while stack:
            key = stack.pop()
            if key in seen:
                continue
            seen.add(key)
            ci = self.classes.get(key)
            if ci is None:
                continue
            if name in ci.methods:
                return ci.methods[name]
            for base in ci.bases:
                # same module first, else any project class of the name
                if f"{ci.module}:{base}" in self.classes:
                    stack.append(f"{ci.module}:{base}")
                else:
                    stack.extend(k for k in self.classes
                                 if k.endswith(f":{base}"))
        return None

    def _resolve_name(self, info: ModuleInfo, fi: FuncInfo,
                      name: str) -> Optional[str]:
        hit = self._resolve_name_kind(info, fi, name)
        return hit[0] if hit is not None else None

    def _resolve_name_kind(self, info: ModuleInfo, fi: FuncInfo,
                           name: str) -> Optional[Tuple[str, str]]:
        # sibling nested function first (shares the enclosing prefix)
        prefix = fi.qualname.rsplit(".", 1)[0]
        for cand, kind in ((f"{prefix}.{name}", "local"),
                           (f"{fi.qualname}.{name}", "local"),
                           (f"{info.name}:{name}", "module")):
            if cand in self.functions:
                return cand, kind
        imported = info.imports.get(name)
        if imported:
            # imported function...
            mod, _, sym = imported.rpartition(".")
            qn = f"{mod}:{sym}"
            if qn in self.functions:
                return qn, "import"
            # ...or imported project class -> its __init__
            ci = self.classes.get(qn)
            if ci and "__init__" in ci.methods:
                return ci.methods["__init__"], "init"
        # class defined in this module -> __init__
        ci = self.classes.get(f"{info.name}:{name}")
        if ci and "__init__" in ci.methods:
            return ci.methods["__init__"], "init"
        return None

    # --------------------------------------------------------- utilities
    def lock_context(self, info: ModuleInfo, fi: FuncInfo,
                     expr: ast.AST) -> Optional[Tuple[str, bool]]:
        """(lock name, is_condition) when ``expr`` (a with-item) is a
        known lock/condition object, else None.  Falls back to a name
        heuristic (``*_lock`` / ``*mutex*`` / ``*_cond``) for locks
        passed in from elsewhere."""
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self" and fi.cls is not None:
            ci = self.classes.get(f"{fi.module}:{fi.cls}")
            if ci is not None:
                if expr.attr in ci.lock_attrs:
                    return expr.attr, False
                if expr.attr in ci.cond_attrs:
                    return expr.attr, True
            return _lock_by_name(expr.attr)
        if isinstance(expr, ast.Name):
            if expr.id in info.locks:
                return expr.id, False
            if expr.id in info.conds:
                return expr.id, True
            return _lock_by_name(expr.id)
        return None


def _lock_by_name(name: str) -> Optional[Tuple[str, bool]]:
    low = name.lower()
    if low.endswith("_cond") or low.endswith("cond"):
        return name, True
    if low.endswith("lock") or "mutex" in low:
        return name, False
    return None


def call_desc(call: ast.Call) -> str:
    """Short printable form of a call target ("self.head.call")."""
    try:
        return ast.unparse(call.func)
    except Exception:
        return "<call>"


# --------------------------------------------------------------------------
# parse cache: content-hash-keyed ASTs
# --------------------------------------------------------------------------

class _ParseCache:
    """Content-hash-keyed AST memo, PROCESS-LOCAL by design.

    ``ast.parse`` dominates a cold model build, and the tier-1 lint
    gate builds the model repeatedly in one process (fixture corpora,
    the whole-package self-lint, the model unit tests): an unchanged
    file re-parses identically every time, so trees are memoized by
    ``sha1(file bytes)`` — an edit anywhere in a file misses only that
    file.  Sharing tree objects across ProjectModel instances is safe:
    nothing mutates them, and the per-model node caches key by id().

    Deliberately NOT persisted to disk: pickling ASTs was measured
    SLOWER to load than re-parsing (~1.6 s pickle.loads vs ~1.1 s
    ast.parse for the whole package on CPython 3.10 — generic
    attribute-by-attribute object reconstruction loses to the C
    parser), so a cross-process cache would be a pessimization
    wearing a cache's name.  ``RAY_TPU_RAYLINT_CACHE=0`` disables the
    memo (debugging, memory-constrained runs)."""

    _memo: Dict[str, ast.Module] = {}
    _MAX_ENTRIES = 4096  # ~40 MiB worst case; clear-all on overflow

    def __init__(self, enabled: bool):
        self._enabled = enabled

    @classmethod
    def open(cls, root: str) -> "_ParseCache":
        return cls(os.environ.get("RAY_TPU_RAYLINT_CACHE", "") != "0")

    @staticmethod
    def _key(raw: bytes) -> str:
        return hashlib.sha1(raw).hexdigest()

    def get(self, raw: bytes) -> Optional[ast.Module]:
        if not self._enabled:
            return None
        return self._memo.get(self._key(raw))

    def put(self, raw: bytes, tree: ast.Module) -> None:
        if not self._enabled:
            return
        if len(self._memo) >= self._MAX_ENTRIES:
            self._memo.clear()
        self._memo[self._key(raw)] = tree

    def save(self) -> None:
        pass  # process-local: nothing to flush


# --------------------------------------------------------------------------
# interprocedural lock-set analysis
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class LockToken:
    """Canonical lock identity.  ``key`` merges aliases (a
    ``Condition(self._lock)`` IS its lock for ordering); ``is_cond``
    remembers the syntactic shape for the wait rules; ``global_`` is
    False for bare-name locals/params whose identity can't be
    canonicalized across functions (they stay out of the global
    graph)."""
    key: str
    is_cond: bool
    global_: bool

    def short(self) -> str:
        mod, _, rest = self.key.partition(":")
        return f"{mod.rsplit('.', 1)[-1]}.{rest}"


@dataclass
class LockAcquire:
    token: LockToken
    line: int
    held: Tuple[LockToken, ...]    # locks already held at this site


@dataclass
class LockWait:
    token: LockToken               # the lock/condition being waited on
    line: int
    held: Tuple[LockToken, ...]
    timeouted: bool
    desc: str


@dataclass
class FuncLockFacts:
    acquires: List[LockAcquire] = field(default_factory=list)
    # (callee qualname, line, edge kind, held tokens at the call)
    calls: List[Tuple[str, int, str, Tuple[LockToken, ...]]] = \
        field(default_factory=list)
    waits: List[LockWait] = field(default_factory=list)


class LockAnalysis:
    """For every function: which locks may be HELD when it runs —
    locally (enclosing ``with`` regions) and interprocedurally (the
    union over callers, propagated to a fixpoint over the call graph's
    confident edges; the class-blind unique-name fallback edges are
    excluded so one guessed edge can't smear a lock set across the
    package).  From the per-function facts it assembles the global
    lock-acquisition-order graph: an edge A -> B for every site that
    acquires B while A may be held, each edge carrying witnesses
    (function, file, line, whether A came in through the entry set).
    Cycles in that graph are the ABBA deadlock candidates
    ``lock-order-inversion`` reports."""

    _PROPAGATE_KINDS = ("self", "local", "module", "import", "init")
    _MAX_WITNESSES = 3

    def __init__(self, model: ProjectModel):
        self.model = model
        self.facts: Dict[str, FuncLockFacts] = {}
        # fn qualname -> tokens possibly held on entry (strings = keys)
        self.entry: Dict[str, Set[str]] = {}
        # (fn, token key) -> (caller, line, caller_held_locally)
        self.entry_why: Dict[Tuple[str, str],
                             Tuple[str, int, bool]] = {}
        # (held key, acquired key) -> [(fn, relpath, line, via_entry)]
        self.edges: Dict[Tuple[str, str],
                         List[Tuple[str, str, int, bool]]] = {}
        self._token_cache: Dict[Tuple[str, str, str],
                                Optional[LockToken]] = {}
        for qn in sorted(model.functions):
            fi = model.functions[qn]
            info = model.modules[fi.module]
            self.facts[qn] = self._scan_func(info, fi)
        self._propagate()
        self._build_graph()

    # ------------------------------------------------- token resolution
    def _class_lock_owner(self, module: str, cls: str,
                          attr: str) -> Optional[Tuple[str, str, bool]]:
        """(owner class qualname, canonical attr, is_cond) for a
        ``self.<attr>`` lock/condition, following project-local bases
        and the Condition->lock alias chain."""
        seen: Set[str] = set()
        stack = [f"{module}:{cls}"]
        while stack:
            key = stack.pop()
            if key in seen:
                continue
            seen.add(key)
            ci = self.model.classes.get(key)
            if ci is None:
                continue
            if attr in ci.cond_attrs:
                canon = attr
                hops = 0
                while canon in ci.cond_alias and hops < 4:
                    canon = ci.cond_alias[canon]
                    hops += 1
                return ci.qualname, canon, True
            if attr in ci.lock_attrs:
                return ci.qualname, attr, False
            for base in ci.bases:
                if f"{ci.module}:{base}" in self.model.classes:
                    stack.append(f"{ci.module}:{base}")
                else:
                    stack.extend(k for k in self.model.classes
                                 if k.endswith(f":{base}"))
        return None

    def token_for(self, info: ModuleInfo, fi: FuncInfo,
                  expr: ast.AST) -> Optional[LockToken]:
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self" and fi.cls is not None:
            ck = (fi.module, fi.cls, expr.attr)
            if ck in self._token_cache:
                return self._token_cache[ck]
            owner = self._class_lock_owner(fi.module, fi.cls, expr.attr)
            if owner is not None:
                cls_qn, canon, is_cond = owner
                tok = LockToken(f"{cls_qn}.{canon}", is_cond, True)
            else:
                hit = _lock_by_name(expr.attr)
                tok = None
                if hit is not None:
                    # Heuristic self-attr: same class + attr is the
                    # same lock in practice, so it joins the graph.
                    tok = LockToken(f"{fi.module}:{fi.cls}.{expr.attr}",
                                    hit[1], True)
            self._token_cache[ck] = tok
            return tok
        if isinstance(expr, ast.Name):
            if expr.id in info.locks:
                return LockToken(f"{info.name}:{expr.id}", False, True)
            if expr.id in info.conds:
                return LockToken(f"{info.name}:{expr.id}", True, True)
            hit = _lock_by_name(expr.id)
            if hit is not None:
                # A local/parameter lock: real for THIS function's
                # waits, meaningless as a global identity.
                return LockToken(f"{fi.qualname}:{expr.id}",
                                 hit[1], False)
        return None

    # ----------------------------------------------------- local facts
    def _scan_func(self, info: ModuleInfo,
                   fi: FuncInfo) -> FuncLockFacts:
        # Fast path: no with-statements and no .wait() calls means no
        # acquisitions, no waits, and an empty held-set at every call
        # — take the calls straight from the prebuilt graph instead
        # of re-walking the body (the vast majority of functions).
        interesting = False
        for node in self.model.walk_own(fi.node):
            if isinstance(node, (ast.With, ast.AsyncWith)) or (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "wait"):
                interesting = True
                break
        if not interesting:
            return FuncLockFacts(calls=[
                (e.target, e.line, e.kind, ())
                for e in self.model.call_edges.get(fi.qualname, ())])
        facts = FuncLockFacts()
        self._scan_stmts(info, fi, fi.node.body, (), facts)
        return facts

    def _scan_stmts(self, info, fi, stmts, held, facts) -> None:
        for st in stmts:
            self._scan_node(info, fi, st, held, facts)

    def _scan_node(self, info, fi, node, held, facts) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            inner = list(held)
            for item in node.items:
                # the context expression evaluates BEFORE acquisition
                self._scan_node(info, fi, item.context_expr,
                                tuple(inner), facts)
                tok = self.token_for(info, fi, item.context_expr)
                if tok is not None:
                    facts.acquires.append(LockAcquire(
                        tok, node.lineno, tuple(inner)))
                    if tok.key not in {t.key for t in inner}:
                        inner.append(tok)
            self._scan_stmts(info, fi, node.body, tuple(inner), facts)
            return
        if isinstance(node, ast.Call):
            self._record_call(info, fi, node, held, facts)
        for child in ast.iter_child_nodes(node):
            self._scan_node(info, fi, child, held, facts)

    def _record_call(self, info, fi, call: ast.Call, held,
                     facts) -> None:
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr == "wait":
            tok = self.token_for(info, fi, f.value)
            if tok is not None:
                timeouted = bool(call.args) or any(
                    kw.arg in ("timeout", "timeout_s")
                    for kw in call.keywords)
                facts.waits.append(LockWait(
                    tok, call.lineno, tuple(held), timeouted,
                    call_desc(call)))
        hit = self.model._resolve_call_edge(info, fi, call)
        if hit is not None:
            target, kind = hit
            facts.calls.append((target, call.lineno, kind,
                                tuple(t for t in held if t.global_)))

    # ----------------------------------------------------- propagation
    def _propagate(self) -> None:
        """Fixpoint: entry(callee) ⊇ entry(caller) ∪ held-at-call for
        every confident edge.  Deterministic: functions and tokens are
        visited sorted, and the first witness for a (fn, token) entry
        is kept — chains render identically across runs and
        interpreters."""
        entry = self.entry
        for qn in self.facts:
            entry.setdefault(qn, set())
        changed = True
        while changed:
            changed = False
            for qn in sorted(self.facts):
                base = entry[qn]
                for target, line, kind, held in self.facts[qn].calls:
                    if kind not in self._PROPAGATE_KINDS:
                        continue
                    if target == qn or target not in entry:
                        continue
                    held_keys = {t.key for t in held}
                    contrib = base | held_keys
                    fresh = contrib - entry[target]
                    if not fresh:
                        continue
                    entry[target] |= fresh
                    for tkey in sorted(fresh):
                        self.entry_why.setdefault(
                            (target, tkey),
                            (qn, line, tkey in held_keys))
                    changed = True

    def chain(self, qn: str, token_key: str) -> List[str]:
        """Printable caller hops explaining how ``qn`` may run with
        ``token_key`` held: root (the function that actually acquires
        it) first.  Line-number-free so finding messages stay
        baseline-stable."""
        hops = [qn]
        seen = {qn}
        cur = qn
        while True:
            why = self.entry_why.get((cur, token_key))
            if why is None:
                break
            caller, _line, local = why
            if caller in seen:
                break
            hops.append(caller)
            seen.add(caller)
            cur = caller
            if local:
                break
        return [_short_fn(h) for h in reversed(hops)]

    # ----------------------------------------------------------- graph
    def _build_graph(self) -> None:
        for qn in sorted(self.facts):
            entry_keys = sorted(self.entry.get(qn, ()))
            fi = self.model.functions[qn]
            rel = self.model.modules[fi.module].relpath
            for acq in self.facts[qn].acquires:
                if not acq.token.global_:
                    continue
                local_keys = {t.key for t in acq.held if t.global_}
                for lkey in sorted(set(entry_keys) | local_keys):
                    if lkey == acq.token.key:
                        continue
                    wl = self.edges.setdefault(
                        (lkey, acq.token.key), [])
                    if len(wl) < self._MAX_WITNESSES:
                        wl.append((qn, rel, acq.line,
                                   lkey not in local_keys))

    def cycles(self, max_cycles: int = 64) -> List[List[str]]:
        """Simple cycles in the lock-order graph, each a token list
        ``[t0, .., tk]`` meaning t0->t1->..->tk->t0.  Deterministic:
        SCCs found over sorted adjacency, one shortest cycle per
        in-SCC edge, deduped by node set.  Self-loops (reentrant
        RLock) are not cycles."""
        adj: Dict[str, List[str]] = {}
        for (a, b) in self.edges:
            if a == b:
                continue
            adj.setdefault(a, []).append(b)
            adj.setdefault(b, [])
        for k in adj:
            adj[k] = sorted(set(adj[k]))
        out: List[List[str]] = []
        seen_sets: Set[frozenset] = set()
        for scc in _tarjan_sccs(adj):
            if len(scc) < 2:
                continue
            nodes = set(scc)
            for a in sorted(nodes):
                for b in adj[a]:
                    if b not in nodes:
                        continue
                    back = _shortest_path(adj, b, a, nodes)
                    if back is None:
                        continue
                    cyc = [a] + back[:-1]
                    key = frozenset(cyc)
                    if key in seen_sets:
                        continue
                    seen_sets.add(key)
                    out.append(cyc)
                    if len(out) >= max_cycles:
                        return out
        return out

    # ------------------------------------------------------------ dumps
    def to_json(self) -> Dict:
        """The global lock-order graph, offline-inspection shape
        (``ray_tpu lint --lock-graph json``)."""
        nodes = sorted({k for e in self.edges for k in e})
        return {
            "nodes": nodes,
            "edges": [{
                "from": a, "to": b,
                "witnesses": [{"function": fn, "path": rel,
                               "line": line, "via_entry": ve}
                              for fn, rel, line, ve in wits],
            } for (a, b), wits in sorted(self.edges.items())],
            "cycles": self.cycles(),
        }

    def to_dot(self) -> str:
        cyc_nodes = {t for cyc in self.cycles() for t in cyc}
        lines = ["digraph lock_order {",
                 '  rankdir=LR; node [shape=box, fontsize=10];']
        for tok in sorted({k for e in self.edges for k in e}):
            style = ', color=red, penwidth=2' if tok in cyc_nodes \
                else ''
            lines.append(f'  "{tok}" [label="{_short_key(tok)}"'
                         f'{style}];')
        for (a, b), wits in sorted(self.edges.items()):
            fn, rel, line, _ve = wits[0]
            lines.append(f'  "{a}" -> "{b}" '
                         f'[label="{_short_fn(fn)}:{line}", '
                         f'fontsize=8];')
        lines.append("}")
        return "\n".join(lines) + "\n"


def _short_fn(qualname: str) -> str:
    """'pkg.mod:Cls.meth' -> 'mod:Cls.meth' (message-stable)."""
    mod, _, rest = qualname.partition(":")
    return f"{mod.rsplit('.', 1)[-1]}:{rest}"


def _short_key(token_key: str) -> str:
    mod, _, rest = token_key.partition(":")
    return f"{mod.rsplit('.', 1)[-1]}.{rest}"


def _tarjan_sccs(adj: Dict[str, List[str]]) -> List[List[str]]:
    """Iterative Tarjan over sorted nodes/neighbors (deterministic,
    recursion-free — lock graphs are small but cycles in them are
    exactly when a recursive walk would go deep)."""
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]

    for root in sorted(adj):
        if root in index:
            continue
        work = [(root, iter(adj[root]))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(adj[nxt])))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(sorted(scc))
    return sccs


def _shortest_path(adj: Dict[str, List[str]], src: str, dst: str,
                   allowed: Set[str]) -> Optional[List[str]]:
    """BFS path src..dst (inclusive) within ``allowed``; sorted
    neighbor order keeps the chosen path deterministic."""
    if src == dst:
        return [src]
    prev: Dict[str, str] = {src: src}
    frontier = [src]
    while frontier:
        nxt_frontier = []
        for node in frontier:
            for nxt in adj.get(node, ()):
                if nxt not in allowed or nxt in prev:
                    continue
                prev[nxt] = node
                if nxt == dst:
                    path = [dst]
                    while path[-1] != src:
                        path.append(prev[path[-1]])
                    return list(reversed(path))
                nxt_frontier.append(nxt)
        frontier = nxt_frontier
    return None


# --------------------------------------------------------------------------
# hot-path classifier
# --------------------------------------------------------------------------

# ONE token table behind every hot-path heuristic, split into two
# profiles.  "dispatch": per-message/per-request control-plane verbs
# (log-hygiene's original set — eager work there is paid per op even
# when the result is discarded).  "device": per-step/per-chunk verbs of
# the jit/pjit hot loops (jit-in-hot-path's original set, plus the
# fwd/bwd shorthand the pipeline stages use).  The builder exemption is
# shared: make_train_step and friends exist to pay setup cost once.
_DISPATCH_TOKENS = (
    "submit", "dispatch", "enqueue", "push", "send", "put", "call",
    "request", "recv", "handle", "deliver", "ship", "ingest", "accept",
    "execute", "step", "read", "write", "flush", "poll", "emit",
    "sample", "observe", "record")
_DEVICE_TOKENS = (
    "dispatch", "handle", "submit", "execute", "request", "recv",
    "decode", "generate", "sample", "collect", "predict", "forward",
    "backward", "fwd", "bwd", "step", "loop", "round", "chunk",
    "process", "call")
_BUILDER_TOKENS = (
    "make", "build", "init", "create", "compile", "setup", "warmup")


def _token_re(tokens: Tuple[str, ...]) -> "re.Pattern":
    return re.compile(
        r"(?:^|_)(?:" + "|".join(tokens) + r")(?:_|$)|(?:^|_)on_", re.I)


class HotPathClassifier:
    """Name-based hot-path classification shared by log-hygiene,
    jit-in-hot-path, and the device-plane rules.

    ``dispatch_hot``: the message/RPC dispatch plane (no builder
    exemption — log-hygiene's historical behavior).  ``device_hot``:
    the jit/decode/train-step plane, builder-exempt.  ``sync_hot``:
    the union profile the host-device-sync rule uses — a blocking
    transfer hurts on EITHER plane, but builders/warmups are sync
    points by design."""

    def __init__(self):
        self._dispatch = _token_re(_DISPATCH_TOKENS)
        self._device = _token_re(_DEVICE_TOKENS)
        self._builder = re.compile(
            r"(?:^|_)(?:" + "|".join(_BUILDER_TOKENS) + r")(?:_|$)",
            re.I)

    def is_builder(self, name: str) -> bool:
        return bool(self._builder.search(name))

    def dispatch_hot(self, name: str) -> bool:
        return bool(self._dispatch.search(name))

    def device_hot(self, name: str) -> bool:
        return bool(self._device.search(name)) and \
            not self.is_builder(name)

    def sync_hot(self, name: str) -> bool:
        if self.is_builder(name):
            return False
        return bool(self._dispatch.search(name)
                    or self._device.search(name))


hot_paths = HotPathClassifier()


# --------------------------------------------------------------------------
# device-plane dataflow: the traced-value lattice
# --------------------------------------------------------------------------

def lvalue_key(expr: ast.AST) -> Optional[str]:
    """'self._apply' / 'cache' for Name/Attribute chains, ignoring
    the Load/Store context."""
    parts: List[str] = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if not isinstance(expr, ast.Name):
        return None
    parts.append(expr.id)
    return ".".join(reversed(parts))


def jit_build_desc(info: ModuleInfo, call: ast.Call) -> Optional[str]:
    """'jax.jit' / 'pjit' when this call builds a jit wrapper, else
    None.  Resolution is import-aware but tolerant of function-local
    ``import jax`` (the name itself then reads as the module)."""
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr in ("jit", "pjit"):
        base = f.value
        name = (base.id if isinstance(base, ast.Name)
                else getattr(base, "attr", ""))
        resolved = info.imports.get(name, name)
        if resolved == "jax" or resolved.startswith("jax."):
            return f"{name}.{f.attr}"
        return None
    if isinstance(f, ast.Name) and f.id in ("jit", "pjit"):
        resolved = info.imports.get(f.id, "")
        if resolved.startswith("jax"):
            return f.id
    return None


# Module roots whose call results live on device (the lattice's TRACED
# generators) and the host-side numpy root (results are host values;
# asarray/array of a traced input is the implicit-sync shape).
_DEVICE_MODULES = ("jax", "jax.numpy", "jax.lax", "jax.random",
                   "jax.nn", "jax.scipy", "jax.tree", "jax.tree_util",
                   "optax")
# jax.* calls whose results are host-side metadata (device handles,
# counts, backend names) — NOT arrays, never a sync to consume.
_JAX_HOST_FNS = frozenset((
    "devices", "local_devices", "device_count", "local_device_count",
    "process_index", "process_count", "default_backend",
    "live_arrays", "clear_caches", "make_mesh", "debug_print"))
# Bare-name fallbacks for function-local aliases the import table
# can't see ("jnp = self._jnp" in the serve engine).
_DEVICE_NAME_HINTS = {"jnp": "jax.numpy", "jax": "jax"}
_HOST_NAME_HINTS = {"np": "numpy", "numpy": "numpy"}


@dataclass
class JitBuild:
    """One ``jax.jit``/``pjit`` wrapper build site with the facts the
    device rules need: where it lives (``key`` — 'self._update',
    a module-level name, or None for anonymous builds that only feed
    the jitted-body index), what it donates, and whether any arg is
    static (bucketing evidence for recompile-hazard)."""
    qualname: str                # function containing the build
    module: str
    line: int
    desc: str                    # "jax.jit" / "pjit"
    key: Optional[str] = None
    donated: Tuple[int, ...] = ()
    donate_names: bool = False
    has_static: bool = False
    fn_qualnames: Tuple[str, ...] = ()

    def merged_with(self, other: "JitBuild") -> "JitBuild":
        """Conservative join when one attribute can hold either of two
        builds (a factory with a mesh and a mesh-less branch): only
        argnums BOTH donate count as donated; static-ness of either
        exempts (no false recompile findings)."""
        return JitBuild(
            qualname=self.qualname, module=self.module, line=self.line,
            desc=self.desc, key=self.key,
            donated=tuple(sorted(set(self.donated)
                                 & set(other.donated))),
            donate_names=self.donate_names or other.donate_names,
            has_static=self.has_static or other.has_static,
            fn_qualnames=tuple(sorted(set(self.fn_qualnames)
                                      | set(other.fn_qualnames))))


@dataclass
class SyncSite:
    """A host-forcing operation applied to a traced value."""
    line: int
    kind: str                    # "float()" / ".item()" / "truth-test"
    expr: str                    # printable traced expression
    annotated: bool              # inside a *.annotation(...) region


@dataclass
class WrapperArg:
    index: int
    key: Optional[str]           # lvalue key when Name/Attribute
    fresh_device_temp: bool      # inline jnp.asarray(...)-style temp
    dead_local: bool             # single-use local fed by a call
    scalar_desc: Optional[str]   # "len(xs)" when per-call-varying


@dataclass
class WrapperCall:
    """A call of a known jit wrapper, with everything missing-donation
    / recompile-hazard need about its arguments and targets."""
    line: int
    build: JitBuild
    args: List[WrapperArg]
    kw_scalars: List[Tuple[str, str]]  # (kwarg name, scalar desc)
    target_keys: Tuple[str, ...]       # lvalue keys when the call is
    #                                    the RHS of an assignment
    starred_from: Optional[int]        # index of first *args, if any
    in_loop: bool


@dataclass
class ShapeBranch:
    line: int
    desc: str


# A taint is False (host), True (may hold a jax.Array), or a tuple of
# bools — one per element of a tuple-shaped value, so unpacking
# ``toks, snapshot, t0 = pending`` taints only the device leaf, not
# the host bookkeeping riding in the same tuple.
Taint = object


def _join_taint(a, b):
    if a is True or b is True:
        return True
    if not a:
        return b
    if not b:
        return a
    if isinstance(a, tuple) and isinstance(b, tuple) and \
            len(a) == len(b):
        return tuple(x or y for x, y in zip(a, b))
    return True


def _taint_any(t) -> bool:
    return any(t) if isinstance(t, tuple) else bool(t)


@dataclass
class FuncFlow:
    """Per-function device-plane facts from one abstract-interpretation
    pass: the sites rules turn into findings, plus the summary bits
    (returns/assigns traced values) the interprocedural fixpoint
    propagates."""
    sync_sites: List[SyncSite] = field(default_factory=list)
    wrapper_calls: List[WrapperCall] = field(default_factory=list)
    returns_traced: bool = False
    # per-element taints of literal-tuple returns; None once a traced
    # NON-tuple return poisons the element view
    return_tuples: List[Tuple[bool, ...]] = field(default_factory=list)
    returns_poisoned: bool = False
    # (class qualname, attr) assigned a traced value in this function
    traced_attr_assigns: Set[Tuple[str, str]] = field(
        default_factory=set)
    # callee qualname -> {param name: taint} observed at call sites
    callee_traced_params: Dict[str, Dict[str, Taint]] = field(
        default_factory=dict)


class DeviceFlow:
    """The conservative traced-value lattice over the package.

    A value is TRACED when it may hold a ``jax.Array`` (or a pytree of
    them): the return of a jitted wrapper, a ``jnp.*``/``jax.*`` call
    result (collectives included), a traced attribute (model params,
    KV caches), or anything data-derived from one (subscripts, method
    calls, arithmetic).  ``jax.device_get`` / ``float()`` / ``np.
    asarray()`` results are HOST — the conversions themselves are the
    implicit-sync sites host-device-sync reports.

    Tracedness propagates intraprocedurally (statement-ordered, with
    strong updates so an explicit ``device_get`` kills the taint) and
    interprocedurally over the call graph's confident edges, exactly
    the kinds LockAnalysis trusts: callee returns flow to caller
    assignment targets, traced arguments flow to callee parameters,
    traced ``self.X =`` assignments flow class-wide.  All three
    summaries grow monotonically, so the worklist fixpoint terminates;
    iteration is sorted everywhere for byte-identical runs."""

    _PROPAGATE_KINDS = ("self", "local", "module", "import", "init")
    _SYNC_BUILTINS = ("float", "int", "bool")

    def __init__(self, model: ProjectModel):
        self.model = model
        # wrapper registries
        self._attr_builds: Dict[Tuple[str, str],
                                Dict[str, JitBuild]] = {}
        self._local_builds: Dict[Tuple[str, str], JitBuild] = {}
        self._module_builds: Dict[Tuple[str, str], JitBuild] = {}
        self.builds: List[JitBuild] = []
        self.jitted: Set[str] = set()          # jitted-body qualnames
        self.dispatchers: Set[str] = set()     # _run(fn, *a) shims
        self.shape_branches: Dict[str, List[ShapeBranch]] = {}
        self.mesh_axes: Set[str] = set()       # constructible axes
        # interprocedural summaries (monotone)
        self.returns_traced: Set[str] = set()
        # qualname -> per-element taints when every traced return is a
        # literal tuple (callers unpacking it get leaf-level taint)
        self.returns_tuple: Dict[str, Tuple[bool, ...]] = {}
        self.param_traced: Dict[str, Dict[str, Taint]] = {}
        self.traced_attrs: Dict[str, Set[str]] = {}
        self.flows: Dict[str, FuncFlow] = {}
        self._rev_edges: Dict[str, Set[str]] = {}
        self._class_methods: Dict[str, List[str]] = {}

        self._scan_builds()
        self._scan_dispatchers()
        self._mark_jitted_bodies()
        self._scan_mesh_axes()
        self._build_reverse_edges()
        self._fixpoint()
        self._scan_shape_branches()

    # ------------------------------------------------- wrapper registry
    def _scan_builds(self) -> None:
        for modname in sorted(self.model.modules):
            info = self.model.modules[modname]
            # module-level "step = jax.jit(...)" bindings
            for node in info.tree.body:
                if isinstance(node, ast.Assign) and \
                        len(node.targets) == 1 and \
                        isinstance(node.targets[0], ast.Name) and \
                        isinstance(node.value, ast.Call):
                    build = self._parse_build(info, None, node.value)
                    if build is not None:
                        build.key = node.targets[0].id
                        self._module_builds[
                            (modname, build.key)] = build
        for qn in sorted(self.model.functions):
            fi = self.model.functions[qn]
            info = self.model.modules[fi.module]
            for node in self.model.walk_own(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                build = self._parse_build(info, fi, node)
                if build is None:
                    continue
                self.builds.append(build)
            for node in self.model.walk_own(fi.node):
                if isinstance(node, ast.Assign) and \
                        len(node.targets) == 1 and \
                        isinstance(node.value, ast.Call):
                    build = self._parse_build(info, fi, node.value,
                                              register=False)
                    if build is None:
                        continue
                    key = lvalue_key(node.targets[0])
                    if key is None:
                        continue
                    build.key = key
                    if key.startswith("self.") and fi.cls is not None:
                        self._register_attr(fi.module, fi.cls,
                                            key[5:], build)
                    elif "." not in key:
                        self._local_builds[(qn, key)] = build
        # attrs filled from a factory: self._update = self._make_...()
        for qn in sorted(self.model.functions):
            fi = self.model.functions[qn]
            if fi.cls is None:
                continue
            info = self.model.modules[fi.module]
            for node in self.model.walk_own(fi.node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.value, ast.Call)):
                    continue
                key = lvalue_key(node.targets[0])
                if key is None or not key.startswith("self."):
                    continue
                hit = self.model._resolve_call_edge(info, fi,
                                                    node.value)
                if hit is None or hit[1] not in self._PROPAGATE_KINDS:
                    continue
                build = self._returned_build(hit[0])
                if build is not None:
                    self._register_attr(fi.module, fi.cls, key[5:],
                                        build)

    def _register_attr(self, module: str, cls: str, attr: str,
                       build: JitBuild) -> None:
        slot = self._attr_builds.setdefault((module, cls), {})
        if attr in slot:
            slot[attr] = slot[attr].merged_with(build)
        else:
            slot[attr] = build

    def _parse_build(self, info: ModuleInfo, fi: Optional[FuncInfo],
                     call: ast.Call,
                     register: bool = True) -> Optional[JitBuild]:
        desc = jit_build_desc(info, call)
        if desc is None:
            return None
        donated: Tuple[int, ...] = ()
        donate_names = False
        has_static = False
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                v = kw.value
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, int):
                    donated = (v.value,)
                elif isinstance(v, (ast.Tuple, ast.List)):
                    donated = tuple(
                        e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int))
            elif kw.arg == "donate_argnames":
                donate_names = True
            elif kw.arg in ("static_argnums", "static_argnames"):
                has_static = True
        fn_qns: List[str] = []
        if call.args:
            fn_qns = self._resolve_callable(info, fi, call.args[0])
        qn = fi.qualname if fi is not None else f"{info.name}:<module>"
        build = JitBuild(qualname=qn, module=info.name,
                         line=call.lineno, desc=desc, donated=donated,
                         donate_names=donate_names,
                         has_static=has_static,
                         fn_qualnames=tuple(fn_qns))
        return build

    def _resolve_callable(self, info: ModuleInfo,
                          fi: Optional[FuncInfo],
                          expr: ast.AST) -> List[str]:
        """Project qualnames a jit build's first argument may name."""
        if isinstance(expr, ast.Name):
            if fi is not None:
                hit = self.model._resolve_name_kind(info, fi, expr.id)
                if hit is not None:
                    return [hit[0]]
            qn = f"{info.name}:{expr.id}"
            if qn in self.model.functions:
                return [qn]
            imported = info.imports.get(expr.id)
            if imported:
                mod, _, sym = imported.rpartition(".")
                qn = f"{mod}:{sym}"
                if qn in self.model.functions:
                    return [qn]
        elif isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name):
            target = info.imports.get(expr.value.id)
            if target in self.model.modules:
                qn = f"{target}:{expr.attr}"
                if qn in self.model.functions:
                    return [qn]
        elif isinstance(expr, ast.Call):
            # functools.partial(fn, ...) and friends: chase arg 0
            if expr.args:
                return self._resolve_callable(info, fi, expr.args[0])
        return []

    def _returned_build(self, qn: str) -> Optional[JitBuild]:
        """The JitBuild a factory function returns, when its return
        statements are jit builds (directly, or a local bound to
        one).  Multiple return branches merge conservatively."""
        fi = self.model.functions.get(qn)
        if fi is None:
            return None
        info = self.model.modules[fi.module]
        found: Optional[JitBuild] = None
        for node in self.model.walk_own(fi.node):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            build: Optional[JitBuild] = None
            if isinstance(node.value, ast.Call):
                build = self._parse_build(info, fi, node.value,
                                          register=False)
            elif isinstance(node.value, ast.Name):
                build = self._local_builds.get((qn, node.value.id))
            if build is None:
                continue
            found = build if found is None else \
                found.merged_with(build)
        return found

    # --------------------------------------------------- jitted bodies
    def _scan_dispatchers(self) -> None:
        """Functions that only forward to their first parameter
        (``def _run(self, fn, *args): return fn(*args)``) — a wrapper
        passed through one still counts as called."""
        for qn in sorted(self.model.functions):
            fi = self.model.functions[qn]
            args = [a.arg for a in fi.node.args.args
                    if a.arg != "self"]
            if not args:
                continue
            p0 = args[0]
            returns = [n for n in self.model.walk_own(fi.node)
                       if isinstance(n, ast.Return)
                       and n.value is not None]
            if not returns:
                continue
            if all(isinstance(r.value, ast.Call)
                   and isinstance(r.value.func, ast.Name)
                   and r.value.func.id == p0 for r in returns):
                self.dispatchers.add(qn)

    def _mark_jitted_bodies(self) -> None:
        """Every function a jit build compiles, closed transitively
        over confident call edges: code that runs under trace cannot
        host-sync (it would fail at trace time), so the sync rule
        skips it wholesale."""
        pending = set()
        for build in self.builds:
            pending.update(build.fn_qualnames)
        for builds in (self._module_builds, self._local_builds):
            for b in builds.values():
                pending.update(b.fn_qualnames)
        for slot in self._attr_builds.values():
            for b in slot.values():
                pending.update(b.fn_qualnames)
        while pending:
            nxt: Set[str] = set()
            for qn in sorted(pending):
                if qn in self.jitted:
                    continue
                self.jitted.add(qn)
                for e in self.model.call_edges.get(qn, ()):
                    if e.kind in self._PROPAGATE_KINDS and \
                            e.target not in self.jitted:
                        nxt.add(e.target)
            pending = nxt

    # ------------------------------------------------------- mesh axes
    def _scan_mesh_axes(self) -> None:
        """Axis names a mesh constructible in this package can carry:
        ``Mesh(...)/AbstractMesh(...)`` axis tuples, ``*AXIS*``
        module constants, and the MeshSpec/ShardingRules field
        vocabulary.  sharding-contract checks literal PartitionSpec
        axes against this set."""
        def strings_in(node: ast.AST) -> List[str]:
            """DIRECT string literals only — a ``tuple(d["axis_names"])``
            expression contributes nothing (its subscript key is not an
            axis name)."""
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                return [node.value]
            if isinstance(node, (ast.Tuple, ast.List)):
                out: List[str] = []
                for e in node.elts:
                    out.extend(strings_in(e))
                return out
            return []

        for modname in sorted(self.model.modules):
            info = self.model.modules[modname]
            for node in ast.walk(info.tree):
                if isinstance(node, ast.Assign) and \
                        len(node.targets) == 1 and \
                        isinstance(node.targets[0], ast.Name) and \
                        ("AXIS" in node.targets[0].id.upper()
                         or "AXES" in node.targets[0].id.upper()) and \
                        isinstance(node.value, (ast.Tuple, ast.List)):
                    self.mesh_axes.update(strings_in(node.value))
                elif isinstance(node, ast.Call):
                    fname = (node.func.attr
                             if isinstance(node.func, ast.Attribute)
                             else getattr(node.func, "id", ""))
                    if fname in ("Mesh", "AbstractMesh", "make_mesh"):
                        for kw in node.keywords:
                            if kw.arg == "axis_names":
                                self.mesh_axes.update(
                                    strings_in(kw.value))
                        if len(node.args) >= 2:
                            self.mesh_axes.update(
                                strings_in(node.args[1]))
                    elif fname in ("ShardingRules", "MeshSpec"):
                        for kw in node.keywords:
                            if isinstance(kw.value, ast.Constant) and \
                                    isinstance(kw.value.value, str):
                                self.mesh_axes.add(kw.value.value)
                elif isinstance(node, ast.ClassDef) and \
                        node.name in ("ShardingRules", "MeshSpec"):
                    for item in node.body:
                        if isinstance(item, ast.AnnAssign) and \
                                isinstance(item.target, ast.Name):
                            self.mesh_axes.add(item.target.id)
                            if item.value is not None:
                                self.mesh_axes.update(
                                    strings_in(item.value))

    # --------------------------------------------------- the fixpoint
    def _build_reverse_edges(self) -> None:
        for qn in sorted(self.model.call_edges):
            for e in self.model.call_edges[qn]:
                if e.kind in self._PROPAGATE_KINDS:
                    self._rev_edges.setdefault(e.target,
                                               set()).add(qn)
        for cqn in sorted(self.model.classes):
            ci = self.model.classes[cqn]
            self._class_methods[cqn] = sorted(ci.methods.values())

    def _fixpoint(self) -> None:
        pending = set(self.model.functions)
        rounds = 0
        while pending and rounds < 24:
            rounds += 1
            requeue: Set[str] = set()
            for qn in sorted(pending):
                flow = _FlowInterp(self, qn).run()
                self.flows[qn] = flow
                if flow.returns_traced and \
                        qn not in self.returns_traced:
                    self.returns_traced.add(qn)
                    requeue.update(self._rev_edges.get(qn, ()))
                rt: Optional[Tuple[bool, ...]] = None
                if flow.return_tuples and not flow.returns_poisoned:
                    rt = flow.return_tuples[0]
                    for t in flow.return_tuples[1:]:
                        joined = _join_taint(rt, t)
                        rt = joined if isinstance(joined, tuple) \
                            else None
                        if rt is None:
                            break
                if rt is not None and \
                        self.returns_tuple.get(qn) != rt:
                    self.returns_tuple[qn] = rt
                    requeue.update(self._rev_edges.get(qn, ()))
                elif rt is None and qn in self.returns_tuple:
                    del self.returns_tuple[qn]
                    requeue.update(self._rev_edges.get(qn, ()))
                for cls_qn, attr in sorted(flow.traced_attr_assigns):
                    attrs = self.traced_attrs.setdefault(cls_qn,
                                                         set())
                    if attr not in attrs:
                        attrs.add(attr)
                        requeue.update(
                            self._class_methods.get(cls_qn, ()))
                for callee in sorted(flow.callee_traced_params):
                    taints = flow.callee_traced_params[callee]
                    have = self.param_traced.setdefault(callee, {})
                    for name in sorted(taints):
                        new = _join_taint(have.get(name, False),
                                          taints[name])
                        if new != have.get(name, False):
                            have[name] = new
                            requeue.add(callee)
            pending = requeue

    def attr_traced(self, module: str, cls: Optional[str],
                    attr: str) -> bool:
        if cls is None:
            return False
        seen: Set[str] = set()
        stack = [f"{module}:{cls}"]
        while stack:
            key = stack.pop()
            if key in seen:
                continue
            seen.add(key)
            if attr in self.traced_attrs.get(key, ()):
                return True
            ci = self.model.classes.get(key)
            if ci is None:
                continue
            for base in ci.bases:
                if f"{ci.module}:{base}" in self.model.classes:
                    stack.append(f"{ci.module}:{base}")
        return False

    def attr_build(self, module: str, cls: Optional[str],
                   attr: str) -> Optional[JitBuild]:
        if cls is None:
            return None
        seen: Set[str] = set()
        stack = [(module, cls)]
        while stack:
            mk = stack.pop()
            if mk in seen:
                continue
            seen.add(mk)
            hit = self._attr_builds.get(mk, {}).get(attr)
            if hit is not None:
                return hit
            ci = self.model.classes.get(f"{mk[0]}:{mk[1]}")
            if ci is None:
                continue
            for base in ci.bases:
                if f"{ci.module}:{base}" in self.model.classes:
                    stack.append((ci.module, base))
        return None

    # ------------------------------------------------- shape branches
    def _scan_shape_branches(self) -> None:
        """Python ``if``/``while`` on ``.shape``/``len()`` inside
        jitted bodies: legal (shapes are static under trace) but each
        distinct shape class re-traces — the static half of the
        recompile-storm signal."""
        for qn in sorted(self.jitted):
            fi = self.model.functions.get(qn)
            if fi is None or hot_paths.is_builder(fi.name):
                continue
            sites: List[ShapeBranch] = []
            for node in self.model.walk_own(fi.node):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                for sub in ast.walk(node.test):
                    if (isinstance(sub, ast.Attribute)
                            and sub.attr in ("shape", "ndim")) or \
                            (isinstance(sub, ast.Call)
                             and isinstance(sub.func, ast.Name)
                             and sub.func.id == "len"):
                        try:
                            desc = ast.unparse(node.test)
                        except Exception:
                            desc = "<test>"
                        sites.append(ShapeBranch(node.lineno, desc))
                        break
            if sites:
                self.shape_branches[qn] = sites


class _FlowInterp:
    """One statement-ordered abstract-interpretation pass over one
    function: ``env`` maps local names and ``self.X`` keys to
    may-be-traced, with strong updates (``stats = jax.device_get(
    stats)`` kills the taint for everything after it).  ``if`` runs
    both arms on copies and joins with union; loop bodies run twice so
    a value traced at the bottom taints the top.  Side products are
    the SyncSites and WrapperCalls the device rules read."""

    def __init__(self, df: DeviceFlow, qn: str):
        self.df = df
        self.qn = qn
        self.fi = df.model.functions[qn]
        self.info = df.model.modules[self.fi.module]
        self.flow = FuncFlow()
        self.env: Dict[str, bool] = {}
        # name -> per-element taints for locals known to hold a tuple
        # (a mixed device/host bundle unpacks leaf-by-leaf)
        self._tuples: Dict[str, Tuple[bool, ...]] = {}
        self._ann_depth = 0
        self._loop_depth = 0
        self._params = [a.arg for a in self._all_args(self.fi.node)]
        # name -> Load-occurrence count / Call-RHS-assignment count,
        # for the dead-local judgement
        self._loads: Dict[str, int] = {}
        self._call_assigns: Dict[str, int] = {}
        self._other_assigns: Dict[str, int] = {}
        for node in df.model.walk_own(self.fi.node):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    self._loads[node.id] = \
                        self._loads.get(node.id, 0) + 1
            if isinstance(node, ast.Assign):
                is_call = isinstance(node.value, ast.Call)
                for t in node.targets:
                    for sub in ast.walk(t):
                        if isinstance(sub, ast.Name):
                            slot = (self._call_assigns if is_call
                                    else self._other_assigns)
                            slot[sub.id] = slot.get(sub.id, 0) + 1
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                                   ast.For)):
                tgt = getattr(node, "target", None)
                if tgt is not None:
                    for sub in ast.walk(tgt):
                        if isinstance(sub, ast.Name):
                            self._other_assigns[sub.id] = \
                                self._other_assigns.get(sub.id, 0) + 1

    @staticmethod
    def _all_args(node: ast.AST) -> List[ast.arg]:
        a = node.args
        return (list(a.posonlyargs) + list(a.args)
                + list(a.kwonlyargs))

    def run(self) -> FuncFlow:
        seeds = self.df.param_traced.get(self.qn, {})
        for name in sorted(seeds):
            taint = seeds[name]
            self.env[name] = _taint_any(taint)
            if isinstance(taint, tuple):
                self._tuples[name] = taint
        self._block(self.fi.node.body)
        return self.flow

    # --------------------------------------------------- statements
    def _block(self, stmts: List[ast.stmt]) -> None:
        for node in stmts:
            self._stmt(node)

    def _stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return                       # executes elsewhere
        if isinstance(node, ast.Assign):
            tkeys = tuple(k for t in node.targets
                          for k in self._target_keys(t))
            traced = self._eval(node.value, targets=tkeys)
            elems = self._value_tuple(node.value, traced)
            for t in node.targets:
                if elems is not None and \
                        isinstance(t, (ast.Tuple, ast.List)) and \
                        len(t.elts) == len(elems) and \
                        not any(isinstance(e, ast.Starred)
                                for e in t.elts):
                    for e, et in zip(t.elts, elems):
                        self._bind(e, et)
                    continue
                self._bind(t, traced)
                if isinstance(t, ast.Name):
                    if elems is not None:
                        self._tuples[t.id] = elems
                    else:
                        self._tuples.pop(t.id, None)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                tkeys = tuple(self._target_keys(node.target))
                traced = self._eval(node.value, targets=tkeys)
                self._bind(node.target, traced)
        elif isinstance(node, ast.AugAssign):
            traced = self._eval(node.value)
            key = lvalue_key(node.target)
            if key is not None:
                old = self._lookup(key, node.target)
                self._set(key, old or traced, node.target)
        elif isinstance(node, ast.Expr):
            self._eval(node.value)
        elif isinstance(node, ast.Return):
            if node.value is not None:
                if isinstance(node.value, ast.Tuple):
                    elems = tuple(bool(self._eval(e))
                                  for e in node.value.elts)
                    self.flow.return_tuples.append(elems)
                    if any(elems):
                        self.flow.returns_traced = True
                elif self._eval(node.value):
                    self.flow.returns_traced = True
                    # a traced non-tuple return: callers can no
                    # longer rely on the per-element view
                    self.flow.returns_poisoned = True
        elif isinstance(node, (ast.If, ast.While)):
            self._truth_test(node.test)
            if isinstance(node, ast.While):
                self._loop_depth += 1
                for _ in range(2):
                    self._block(node.body)
                self._loop_depth -= 1
                self._block(node.orelse)
            else:
                saved = dict(self.env)
                self._block(node.body)
                then_env = self.env
                self.env = dict(saved)
                self._block(node.orelse)
                for k in sorted(set(then_env) | set(self.env)):
                    self.env[k] = then_env.get(k, False) or \
                        self.env.get(k, False)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            it_traced = self._eval(node.iter)
            self._bind(node.target, it_traced)
            self._loop_depth += 1
            for _ in range(2):
                self._block(node.body)
            self._loop_depth -= 1
            self._block(node.orelse)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            annotated = any(self._is_annotation_cm(item.context_expr)
                            for item in node.items)
            for item in node.items:
                traced = self._eval(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, traced)
            if annotated:
                self._ann_depth += 1
            self._block(node.body)
            if annotated:
                self._ann_depth -= 1
        elif isinstance(node, ast.Try):
            self._block(node.body)
            for h in node.handlers:
                self._block(h.body)
            self._block(node.orelse)
            self._block(node.finalbody)
        elif isinstance(node, ast.Assert):
            self._truth_test(node.test)
        elif isinstance(node, ast.Raise):
            if node.exc is not None:
                self._eval(node.exc)
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                key = lvalue_key(t)
                if key is not None and key in self.env:
                    del self.env[key]

    def _value_tuple(self, expr: ast.expr, traced: bool
                     ) -> Optional[Tuple[bool, ...]]:
        """Per-element taints when this (already-evaluated) RHS is
        known tuple-shaped: a local carrying one, or a call whose
        callee returns literal tuples.  No re-evaluation — the lookup
        must not duplicate sync sites."""
        if not traced:
            return None
        if isinstance(expr, ast.Name):
            return self._tuples.get(expr.id)
        if isinstance(expr, ast.Call):
            edge = self.df.model._resolve_call_edge(self.info,
                                                    self.fi, expr)
            if edge is not None and \
                    edge[1] in DeviceFlow._PROPAGATE_KINDS:
                return self.df.returns_tuple.get(edge[0])
        return None

    def _target_keys(self, target: ast.AST) -> List[str]:
        if isinstance(target, (ast.Tuple, ast.List)):
            out: List[str] = []
            for e in target.elts:
                out.extend(self._target_keys(e))
            return out
        key = lvalue_key(target)
        return [key] if key is not None else []

    def _bind(self, target: ast.AST, traced: bool) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, traced)
            return
        if isinstance(target, ast.Starred):
            self._bind(target.value, traced)
            return
        if isinstance(target, ast.Subscript):
            # container[k] = traced taints the container itself —
            # self._inputs[i] = activations makes _inputs a traced
            # store whose .pop() later yields a traced value.
            if traced:
                key = lvalue_key(target.value)
                if key is not None:
                    self._set(key, True, target.value)
            return
        key = lvalue_key(target)
        if key is not None:
            self._set(key, traced, target)

    def _set(self, key: str, traced: bool, node: ast.AST) -> None:
        self.env[key] = traced
        if traced and key.startswith("self.") and \
                "." not in key[5:] and self.fi.cls is not None:
            self.flow.traced_attr_assigns.add(
                (f"{self.fi.module}:{self.fi.cls}", key[5:]))

    def _lookup(self, key: str, node: ast.AST) -> bool:
        if key in self.env:
            return self.env[key]
        if key.startswith("self.") and "." not in key[5:]:
            return self.df.attr_traced(self.fi.module, self.fi.cls,
                                       key[5:])
        return False

    # -------------------------------------------------- expressions
    def _truth_test(self, test: ast.expr) -> None:
        """Truth-testing a traced value is a blocking device->host
        read; ``x is None`` guards are identity checks and stay
        host-side."""
        if isinstance(test, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops):
            for sub in [test.left] + list(test.comparators):
                self._eval(sub)
            return
        if self._eval(test):
            self._sync(test, "truth-test", test)
        elif isinstance(test, ast.BoolOp):
            for v in test.values:
                if self._eval(v):
                    self._sync(v, "truth-test", v)

    def _sync(self, node: ast.AST, kind: str,
              expr: ast.AST) -> None:
        try:
            desc = ast.unparse(expr)
        except Exception:
            desc = "<expr>"
        if len(desc) > 60:
            desc = desc[:57] + "..."
        self.flow.sync_sites.append(SyncSite(
            line=getattr(node, "lineno", self.fi.line), kind=kind,
            expr=desc, annotated=self._ann_depth > 0))

    def _is_annotation_cm(self, expr: ast.expr) -> bool:
        return isinstance(expr, ast.Call) and \
            isinstance(expr.func, ast.Attribute) and \
            expr.func.attr == "annotation"

    def _module_root(self, expr: ast.expr) -> Optional[str]:
        """The fully-qualified module a Name/Attribute base refers to
        ('jnp' -> 'jax.numpy'), import-table first, then the bare-name
        conventions local aliases like ``jnp = self._jnp`` follow."""
        if isinstance(expr, ast.Name):
            hit = self.info.imports.get(expr.id)
            if hit:
                return hit
            return _DEVICE_NAME_HINTS.get(expr.id) or \
                _HOST_NAME_HINTS.get(expr.id)
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self":
            return {"_jnp": "jax.numpy", "_jax": "jax",
                    "_np": "numpy"}.get(expr.attr)
        return None

    def _eval(self, expr: ast.expr,
              targets: Tuple[str, ...] = ()) -> bool:
        if isinstance(expr, ast.Call):
            return self._eval_call(expr, targets)
        if isinstance(expr, (ast.Name, ast.Attribute)):
            key = lvalue_key(expr)
            if key is not None:
                if key in self.env:
                    return self.env[key]
                if isinstance(expr, ast.Name):
                    return False
                return self._lookup(key, expr)
            # attribute OF a computed value: metadata access
            # (x.shape, x.dtype) — host-side, never a sync
            if isinstance(expr, ast.Attribute):
                self._eval(expr.value)
            return False
        if isinstance(expr, ast.Subscript):
            traced = self._eval(expr.value)
            self._eval(expr.slice)
            return traced
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            return any([self._eval(e) for e in expr.elts])
        if isinstance(expr, ast.Dict):
            vals = [self._eval(v) for v in expr.values
                    if v is not None]
            for k in expr.keys:
                if k is not None:
                    self._eval(k)
            return any(vals)
        if isinstance(expr, ast.Starred):
            return self._eval(expr.value)
        if isinstance(expr, ast.BinOp):
            left = self._eval(expr.left)
            right = self._eval(expr.right)
            return left or right
        if isinstance(expr, ast.UnaryOp):
            return self._eval(expr.operand)
        if isinstance(expr, ast.BoolOp):
            return any([self._eval(v) for v in expr.values])
        if isinstance(expr, ast.Compare):
            vals = [self._eval(expr.left)]
            vals += [self._eval(c) for c in expr.comparators]
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In,
                                   ast.NotIn)) for op in expr.ops):
                return False
            return any(vals)
        if isinstance(expr, ast.IfExp):
            self._truth_test(expr.test)
            body = self._eval(expr.body)
            orelse = self._eval(expr.orelse)
            return body or orelse
        if isinstance(expr, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp, ast.DictComp)):
            return self._eval_comp(expr)
        if isinstance(expr, ast.JoinedStr):
            for v in expr.values:
                if isinstance(v, ast.FormattedValue):
                    self._eval(v.value)
            return False
        if isinstance(expr, (ast.Await, ast.YieldFrom)):
            return self._eval(expr.value)
        if isinstance(expr, ast.Yield):
            if expr.value is not None:
                self._eval(expr.value)
            return False
        if isinstance(expr, ast.Lambda):
            return False
        if isinstance(expr, ast.NamedExpr):
            traced = self._eval(expr.value)
            self._bind(expr.target, traced)
            return traced
        return False

    def _eval_comp(self, expr: ast.expr) -> bool:
        saved = dict(self.env)
        for gen in expr.generators:
            it_traced = self._eval(gen.iter)
            self._bind(gen.target, it_traced)
            for cond in gen.ifs:
                self._truth_test(cond)
        if isinstance(expr, ast.DictComp):
            self._eval(expr.key)
            traced = self._eval(expr.value)
        else:
            traced = self._eval(expr.elt)
        self.env = saved
        return traced

    def _fstring_traced(self, expr: ast.expr) -> bool:
        if not isinstance(expr, ast.JoinedStr):
            return False
        return any(self._eval(v.value) for v in expr.values
                   if isinstance(v, ast.FormattedValue))

    # --------------------------------------------------------- calls
    def _eval_call(self, call: ast.Call,
                   targets: Tuple[str, ...] = ()) -> bool:
        f = call.func
        fname = (f.id if isinstance(f, ast.Name)
                 else f.attr if isinstance(f, ast.Attribute) else "")

        # -- explicit host/device boundary builtins ------------------
        if isinstance(f, ast.Name):
            if f.id in DeviceFlow._SYNC_BUILTINS and \
                    len(call.args) == 1 and not call.keywords:
                if self._eval(call.args[0]):
                    self._sync(call, f"{f.id}()", call.args[0])
                return False
            if f.id == "print":
                for a in call.args:
                    if self._eval(a) or self._fstring_traced(a):
                        self._sync(call, "print", a)
                        break
                for kw in call.keywords:
                    self._eval(kw.value)
                return False
            if f.id == "len":
                for a in call.args:
                    self._eval(a)
                return False           # shape metadata, not a sync

        if isinstance(f, ast.Attribute):
            root = self._module_root(f.value)
            base_traced = (self._eval(f.value)
                           if root is None else False)
            if root is not None and (root == "numpy"
                                     or root.startswith("numpy.")):
                if f.attr in ("asarray", "array", "copy") and \
                        call.args and self._eval(call.args[0]):
                    self._sync(call, f"np.{f.attr}()", call.args[0])
                for a in call.args[1:]:
                    self._eval(a)
                for kw in call.keywords:
                    self._eval(kw.value)
                return False
            if root is not None and (root in _DEVICE_MODULES
                                     or root.startswith("jax.")):
                for a in call.args:
                    self._eval(a)
                for kw in call.keywords:
                    self._eval(kw.value)
                if f.attr == "device_get":
                    return False       # explicit transfer: host out
                if f.attr in _JAX_HOST_FNS:
                    return False       # host-side metadata
                # block_until_ready and everything else: device out
                return True
            if f.attr == "item" and base_traced and not call.args:
                self._sync(call, ".item()", f.value)
                return False
            if f.attr == "block_until_ready" and base_traced:
                return True
            if base_traced:
                # method on a traced pytree/array (.items(), .get(),
                # .pop(), .astype(), dict views...) keeps tracedness
                for a in call.args:
                    self._eval(a)
                for kw in call.keywords:
                    self._eval(kw.value)
                return True

        # -- known jit wrapper? --------------------------------------
        build, shifted = self._wrapper_of(call)
        if build is not None:
            self._record_wrapper(call, build, shifted, targets)
            return True

        # -- project call edge: propagate args in, returns out -------
        edge = self.df.model._resolve_call_edge(self.info, self.fi,
                                                call)
        arg_taints: List[Taint] = []
        for a in call.args:
            t: Taint = self._eval(a)
            if t and isinstance(a, ast.Name) and \
                    a.id in self._tuples:
                t = self._tuples[a.id]
            arg_taints.append(t)
        kw_traced = [(kw.arg, self._eval(kw.value))
                     for kw in call.keywords]
        if edge is not None and \
                edge[1] in DeviceFlow._PROPAGATE_KINDS:
            callee, _kind = edge
            cfi = self.df.model.functions.get(callee)
            if cfi is not None:
                params = [a.arg for a in self._all_args(cfi.node)]
                if params and params[0] == "self":
                    params = params[1:]
                hot: Dict[str, Taint] = {
                    p: arg_taints[i]
                    for i, p in enumerate(params)
                    if i < len(arg_taints)
                    and _taint_any(arg_taints[i])}
                for kw, t in kw_traced:
                    if t and kw in params:
                        hot[kw] = True
                if hot:
                    slot = self.flow.callee_traced_params.setdefault(
                        callee, {})
                    for name in sorted(hot):
                        slot[name] = _join_taint(
                            slot.get(name, False), hot[name])
            return callee in self.df.returns_traced
        return False

    def _wrapper_of(self, call: ast.Call
                    ) -> Tuple[Optional[JitBuild], int]:
        """(build, arg shift) when this call invokes a known jit
        wrapper — directly, or through a ``_run(fn, *args)``-shaped
        dispatcher whose first argument is the wrapper."""
        build = self._build_for_expr(call.func)
        if build is not None:
            return build, 0
        edge = self.df.model._resolve_call_edge(self.info, self.fi,
                                                call)
        if edge is not None and edge[0] in self.df.dispatchers \
                and call.args:
            inner = self._build_for_expr(call.args[0])
            if inner is not None:
                return inner, 1
        return None, 0

    def _build_for_expr(self, expr: ast.expr) -> Optional[JitBuild]:
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self":
            return self.df.attr_build(self.fi.module, self.fi.cls,
                                      expr.attr)
        if isinstance(expr, ast.Name):
            hit = self.df._local_builds.get((self.qn, expr.id))
            if hit is not None:
                return hit
            return self.df._module_builds.get(
                (self.fi.module, expr.id))
        return None

    def _record_wrapper(self, call: ast.Call, build: JitBuild,
                        shift: int,
                        targets: Tuple[str, ...]) -> None:
        args: List[WrapperArg] = []
        starred_from: Optional[int] = None
        for i, a in enumerate(call.args[shift:]):
            if isinstance(a, ast.Starred):
                if starred_from is None:
                    starred_from = i
                self._eval(a.value)
                continue
            self._eval(a)
            args.append(WrapperArg(
                index=i, key=lvalue_key(a),
                fresh_device_temp=self._is_fresh_device_temp(a),
                dead_local=self._is_dead_local(a),
                scalar_desc=self._scalar_desc(a)))
        kw_scalars: List[Tuple[str, str]] = []
        for kw in call.keywords:
            self._eval(kw.value)
            if kw.arg is not None:
                desc = self._scalar_desc(kw.value)
                if desc is not None:
                    kw_scalars.append((kw.arg, desc))
        self.flow.wrapper_calls.append(WrapperCall(
            line=call.lineno, build=build, args=args,
            kw_scalars=kw_scalars, target_keys=targets,
            starred_from=starred_from,
            in_loop=self._loop_depth > 0))

    def _is_fresh_device_temp(self, expr: ast.expr) -> bool:
        """An inline jnp.*/jax.* call: a device value nothing else can
        reference — dead the moment the wrapper consumes it."""
        if not (isinstance(expr, ast.Call)
                and isinstance(expr.func, ast.Attribute)):
            return False
        root = self._module_root(expr.func.value)
        return root is not None and (root in _DEVICE_MODULES
                                     or root.startswith("jax."))

    def _is_dead_local(self, expr: ast.expr) -> bool:
        """A plain local whose ONLY load is this argument, bound
        exactly once from a call result: the buffer has no other
        referent, so donating it is free."""
        if not isinstance(expr, ast.Name) or self._loop_depth > 0:
            return False
        name = expr.id
        if name in self._params:
            return False
        return (self._loads.get(name, 0) == 1
                and self._call_assigns.get(name, 0) == 1
                and self._other_assigns.get(name, 0) == 0)

    def _scalar_desc(self, expr: ast.expr) -> Optional[str]:
        """Per-call-varying Python scalar shapes that re-trigger
        tracing when fed to a jitted callee as dynamic args:
        ``len(x)``, ``int(x)``, ``x.shape[i]``."""
        if isinstance(expr, ast.Call) and \
                isinstance(expr.func, ast.Name):
            if expr.func.id == "len" and expr.args:
                return _safe_unparse(expr)
            if expr.func.id == "int" and expr.args and \
                    not isinstance(expr.args[0], ast.Constant):
                return _safe_unparse(expr)
        if isinstance(expr, ast.Subscript) and \
                isinstance(expr.value, ast.Attribute) and \
                expr.value.attr == "shape":
            return _safe_unparse(expr)
        return None


def _safe_unparse(expr: ast.AST) -> str:
    try:
        out = ast.unparse(expr)
    except Exception:
        return "<expr>"
    return out if len(out) <= 40 else out[:37] + "..."
