"""AST call graph over the repo with jit/shard_map/pallas_call boundaries.

The static rail's foundation: REP001 ("no host materialization inside a
device program") is a property of *reachability* — ``np.asarray`` is fine in
flush orchestration code and fatal three frames below a ``jax.jit``. This
module builds, with nothing but the stdlib ``ast``:

* a table of every function/method in the analyzed tree, keyed
  ``module:qualname`` (nested defs use dotted qualnames, ``outer.inner``);
* the set of *device boundaries* — functions that become device programs:
  decorated with ``jax.jit`` (directly or through ``functools.partial``),
  wrapped by a ``jax.jit(f)`` / ``shard_map(f, ...)`` call, or passed as the
  kernel to ``pl.pallas_call`` (including through a local
  ``functools.partial`` alias);
* a conservative call graph: name calls resolve within the module, imported
  names resolve across analyzed modules (``from repro.kernels import ops``
  then ``ops.frontier_relax(...)``), and ``self.method()`` resolves to every
  analyzed method of that name (over-approximate on purpose — a lint rule
  must not lose an edge to polymorphism);
* the transitive *reachable* set from the boundaries, which is exactly
  "code that runs under a trace".

Resolution is intentionally name-based and over-approximate: a false edge
costs a spurious manual review, a missing edge costs a silent host sync on
a hot path. The latter is the bug class this whole subsystem exists for.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

# Call-expression heads that turn their first function argument into a
# device program. Matched on the attribute tail, so ``jax.jit``, ``jit``,
# ``pjit``, ``pl.pallas_call`` and ``jax.shard_map``
# all resolve the same way.
_BOUNDARY_WRAPPERS = {"jit", "pjit", "shard_map", "pallas_call"}


def dotted_name(node: ast.AST) -> str:
    """Full dotted source text of a Name/Attribute chain, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def is_jit_expr(node: ast.AST) -> bool:
    """Is this expression a jit transform reference or a partial of one?

    Matches ``jax.jit``, ``jit``, ``pjit`` and
    ``functools.partial(jax.jit, ...)`` (any partial whose first argument is
    itself a jit reference).
    """
    name = dotted_name(node)
    if name.split(".")[-1] in ("jit", "pjit"):
        return True
    if isinstance(node, ast.Call) and dotted_name(node.func).split(".")[-1] == "partial":
        return bool(node.args) and is_jit_expr(node.args[0])
    return False


@dataclass
class FunctionInfo:
    key: str                     # "relpath:qualname"
    path: str                    # file the function lives in (relative)
    module: str                  # dotted module guess ("repro.kernels.ops")
    qualname: str
    node: ast.AST                # FunctionDef | AsyncFunctionDef
    boundary: str | None = None  # "jit" | "shard_map" | "pallas_call" | None
    calls: set[str] = field(default_factory=set)         # resolved keys
    method_calls: set[str] = field(default_factory=set)  # bare self.X names


@dataclass
class ModuleInfo:
    path: str
    module: str
    tree: ast.Module
    source: str
    # import alias -> dotted module ("ops" -> "repro.kernels.ops")
    import_aliases: dict[str, str] = field(default_factory=dict)
    # imported name -> "module.attr" ("insert_affected_set" ->
    # "repro.core.updates.insert_affected_set")
    from_imports: dict[str, str] = field(default_factory=dict)
    functions: dict[str, FunctionInfo] = field(default_factory=dict)  # qualname->


def module_name_for(path: str) -> str:
    """Best-effort dotted module for a file path (anchored at ``repro``)."""
    parts = [p for p in path.replace("\\", "/")[:-3].split("/") if p not in ("", ".")]
    if "repro" in parts:
        parts = parts[parts.index("repro"):]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class _DefCollector(ast.NodeVisitor):
    """Pass 1: register every function/method (and decorator boundaries).

    Runs before the edge pass so a call to a function defined *later* in
    the file still resolves — module-level forward references are legal
    Python and common in top-down-styled code.
    """

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.stack: list[str] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        qual = ".".join(self.stack + [node.name]) if self.stack else node.name
        info = FunctionInfo(
            key=f"{self.mod.path}:{qual}",
            path=self.mod.path,
            module=self.mod.module,
            qualname=qual,
            node=node,
        )
        self.mod.functions[qual] = info
        for dec in node.decorator_list:
            if is_jit_expr(dec):
                info.boundary = "jit"
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()


class _ModuleScanner(ast.NodeVisitor):
    """Pass 2 per module: imports, boundary marks, call edges."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.stack: list[str] = []       # qualname segments
        self.fn_stack: list[FunctionInfo] = []

    # -- imports --------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self.mod.import_aliases[alias.asname or alias.name.split(".")[0]] = (
                alias.name
            )

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        base = node.module or ""
        for alias in node.names:
            local = alias.asname or alias.name
            self.mod.from_imports[local] = f"{base}.{alias.name}" if base else alias.name
            # "from repro.kernels import ops" imports a MODULE: record the
            # alias too so "ops.frontier_relax" resolves across modules
            self.mod.import_aliases.setdefault(local, f"{base}.{alias.name}")

    # -- functions (already registered by _DefCollector) ----------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        qual = ".".join(self.stack + [node.name]) if self.stack else node.name
        info = self.mod.functions[qual]
        self.stack.append(node.name)
        self.fn_stack.append(info)
        self.generic_visit(node)
        self.fn_stack.pop()
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    # -- calls ----------------------------------------------------------

    def _resolve_local(self, name: str) -> str | None:
        """A bare name, resolved against enclosing scopes then the module."""
        for depth in range(len(self.stack), -1, -1):
            qual = ".".join(self.stack[:depth] + [name]) if depth else name
            if qual in self.mod.functions:
                return qual
        return None

    def _record_callee(self, func: ast.AST) -> None:
        if not self.fn_stack:
            return
        info = self.fn_stack[-1]
        name = dotted_name(func)
        if not name:
            return
        head, _, rest = name.partition(".")
        if head in ("self", "cls") and rest and "." not in rest:
            info.method_calls.add(rest)
            return
        if "." not in name:
            local = self._resolve_local(name)
            if local is not None:
                info.calls.add(f"{self.mod.path}:{local}")
            elif name in self.mod.from_imports:
                info.calls.add(f"import:{self.mod.from_imports[name]}")
            return
        # module-attribute call through an import alias
        if head in self.mod.import_aliases and rest:
            info.calls.add(f"import:{self.mod.import_aliases[head]}.{rest}")

    def _mark_boundary_arg(self, node: ast.AST, kind: str) -> None:
        """Mark the function referenced by ``node`` as a device boundary."""
        if isinstance(node, ast.Lambda):
            return  # lambdas have no table entry; their body is tiny anyway
        if isinstance(node, ast.Call):
            # functools.partial(kernel, ...) -> the underlying function
            if dotted_name(node.func).split(".")[-1] == "partial" and node.args:
                self._mark_boundary_arg(node.args[0], kind)
            return
        name = dotted_name(node)
        if not name or "." in name:
            return
        local = self._resolve_local(name)
        if local is not None:
            fn = self.mod.functions[local]
            if fn.boundary is None:
                fn.boundary = kind
            # re-scan later marks via fixpoint in build_callgraph

    def visit_Call(self, node: ast.Call) -> None:
        self._record_callee(node.func)
        tail = dotted_name(node.func).split(".")[-1]
        if tail in _BOUNDARY_WRAPPERS and node.args:
            kind = "jit" if tail in ("jit", "pjit") else tail
            self._mark_boundary_arg(node.args[0], kind)
        if tail == "partial" and node.args and is_jit_expr(node):
            # functools.partial(jax.jit, static...)(f) handled at outer Call;
            # direct partial(jax.jit, f) marks f
            if len(node.args) >= 2:
                self._mark_boundary_arg(node.args[1], "jit")
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        # f = jax.jit(g)  /  kernel = functools.partial(_kernel, k=k)
        if isinstance(node.value, ast.Call):
            inner = node.value
            if is_jit_expr(inner.func) and inner.args:
                self._mark_boundary_arg(inner.args[0], "jit")
        self.generic_visit(node)


@dataclass
class CallGraph:
    modules: dict[str, ModuleInfo]            # path -> module
    functions: dict[str, FunctionInfo]        # key -> info
    reachable: set[str]                       # keys reachable from boundaries

    def is_reachable(self, path: str, qualname: str) -> bool:
        return f"{path}:{qualname}" in self.reachable

    def boundaries(self) -> list[FunctionInfo]:
        return [f for f in self.functions.values() if f.boundary]


def build_callgraph(modules: dict[str, ModuleInfo]) -> CallGraph:
    """Scan every module, then close the boundary set over the call graph."""
    for mod in modules.values():
        _DefCollector(mod).visit(mod.tree)
        _ModuleScanner(mod).visit(mod.tree)

    functions: dict[str, FunctionInfo] = {}
    by_module_attr: dict[str, str] = {}   # "repro.kernels.ops.topk_merge" -> key
    by_method_name: dict[str, list[str]] = {}
    for mod in modules.values():
        for fn in mod.functions.values():
            functions[fn.key] = fn
            if mod.module:
                by_module_attr[f"{mod.module}.{fn.qualname}"] = fn.key
            tail = fn.qualname.split(".")[-1]
            if "." in fn.qualname:  # a method or nested def: callable by name
                by_method_name.setdefault(tail, []).append(fn.key)

    def resolve(edge: str) -> list[str]:
        if edge.startswith("import:"):
            target = edge[len("import:"):]
            if "repro" in target:
                target = target[target.index("repro"):]
            key = by_module_attr.get(target)
            return [key] if key else []
        return [edge] if edge in functions else []

    # BFS from the boundaries
    frontier = [f.key for f in functions.values() if f.boundary]
    reachable = set(frontier)
    while frontier:
        nxt: list[str] = []
        for key in frontier:
            fn = functions[key]
            targets: list[str] = []
            for edge in fn.calls:
                targets.extend(resolve(edge))
            for m in fn.method_calls:
                targets.extend(by_method_name.get(m, []))
            # a nested def inside a device function is itself device code
            prefix = f"{fn.path}:{fn.qualname}."
            targets.extend(k for k in functions if k.startswith(prefix))
            for t in targets:
                if t not in reachable:
                    reachable.add(t)
                    nxt.append(t)
        frontier = nxt
    return CallGraph(modules=modules, functions=functions, reachable=reachable)
