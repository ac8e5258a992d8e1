"""The cross-file fact index kvmini-lint's checkers share.

One ``ast.parse`` per file, then cheap linear walks that record:

- every function/method (qualname, params, decorators, nesting),
- import aliases (``np`` -> ``numpy``, ``rt_tracing`` -> ``...tracing``),
- which functions are **jit roots** (decorated with / wrapped by
  ``jax.jit``/``pjit``/``shard_map``, including the repo's dominant
  ``@partial(jax.jit, ...)`` inner-def idiom) plus their static args,
- which bindings *hold* jitted callables (``self._steps[key] = fn`` with
  ``fn`` bound from a builder of ``runtime/steps.py``, ``self._cache =
  jax.jit(...)``) and which functions *return* them (``Engine._step``, a
  builder's ``return jax.jit(step, ...)``) — so checkers can tell "this host function dispatches
  compiled work" (the decode hot path) from ordinary host code,
- a name-resolution-lite call graph: callee candidates per callsite with
  positional/keyword argument mapping, enough for the jit-purity
  checker's cross-function taint propagation,
- which engine methods a multihost follower replays (``engine.<m>(...)``
  inside ``run_follower``-named functions), anchoring the lockstep rules.

Resolution is deliberately approximate (no full type inference): a call
resolves to same-scope defs, same-class methods via ``self.``, class
attribute aliases (``self._fwd = forward``), ``from``-imports, and
module-alias attributes. Unresolved calls simply contribute no edges —
checkers under-approximate rather than guess.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable, Optional

from kserve_vllm_mini_tpu.lint.diagnostics import Suppressions

JIT_WRAPPER_NAMES = {"jit", "pjit", "shard_map"}


def iter_scope(fn_node: ast.AST):
    """Walk a function's own scope: every descendant EXCEPT the bodies of
    nested function/class definitions (each nested def is analyzed as its
    own FunctionInfo, so descending here would double-report and leak
    the outer scope's taint into the inner one). Lambdas are NOT excluded:
    they get no FunctionInfo of their own, so their (expression-only)
    bodies are checked as part of the enclosing scope — a `.item()` inside
    an inline lambda is still a host sync at this site.

    The walk is memoized on the node: with fourteen checker families each
    re-walking every function, generator re-walks were the single largest
    cost in the full-package profile. The cached tuple preserves the
    exact historical yield order (DFS, reversed child order), so findings
    are byte-identical; the store is an idempotent single attribute
    write, safe under concurrent checker threads."""
    cached = getattr(fn_node, "_kvmini_scope", None)
    if cached is None:
        out = []
        stack = list(ast.iter_child_nodes(fn_node))
        while stack:
            n = stack.pop()
            out.append(n)
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                stack.extend(ast.iter_child_nodes(n))
        cached = tuple(out)
        fn_node._kvmini_scope = cached
    return cached


def _last_attr(node: ast.AST) -> Optional[str]:
    """`jax.jit` -> "jit", `jit` -> "jit", anything else -> None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_jit_wrapper(node: ast.AST) -> bool:
    return _last_attr(node) in JIT_WRAPPER_NAMES


def _argnum_kwargs(call: ast.Call, num_key: str,
                   name_key: str) -> tuple[set[int], set[str]]:
    nums: set[int] = set()
    names: set[str] = set()
    for kw in call.keywords:
        if kw.arg == num_key and isinstance(kw.value, (ast.Tuple, ast.List)):
            nums |= {e.value for e in kw.value.elts
                     if isinstance(e, ast.Constant) and isinstance(e.value, int)}
        if kw.arg == name_key and isinstance(kw.value, (ast.Tuple, ast.List)):
            names |= {e.value for e in kw.value.elts
                      if isinstance(e, ast.Constant) and isinstance(e.value, str)}
    return nums, names


def _static_args_from_call(call: ast.Call) -> tuple[set[int], set[str]]:
    return _argnum_kwargs(call, "static_argnums", "static_argnames")


def _donate_args_from_call(call: ast.Call) -> tuple[set[int], set[str]]:
    return _argnum_kwargs(call, "donate_argnums", "donate_argnames")


# type-annotation tokens that can carry traced array data; a param whose
# annotation mentions NONE of these is host-static config (ModelConfig,
# Mesh, int, bool, str...) and never carries a tracer
ARRAYISH_ANNOTATION_TOKENS = {
    "ndarray", "Array", "ArrayLike", "Params", "Any", "dict", "Dict",
    "Mapping", "list", "List", "tuple", "Tuple", "Sequence", "PyTree",
    "object", "Tracer",
}


def _annotation_is_static(ann: Optional[ast.AST]) -> bool:
    if ann is None:
        return False
    for n in ast.walk(ann):
        if isinstance(n, ast.Name) and n.id in ARRAYISH_ANNOTATION_TOKENS:
            return False
        if isinstance(n, ast.Attribute) and n.attr in ARRAYISH_ANNOTATION_TOKENS:
            return False
        if isinstance(n, ast.Constant) and isinstance(n.value, str) and any(
                tok in n.value for tok in ARRAYISH_ANNOTATION_TOKENS):
            return False
    return True


@dataclass
class FunctionInfo:
    path: str
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    class_name: Optional[str]
    parent: Optional["FunctionInfo"]
    params: list[str] = field(default_factory=list)
    annotated_static: set[str] = field(default_factory=set)
    jit_root: bool = False
    static_argnums: set[int] = field(default_factory=set)
    static_argnames: set[str] = field(default_factory=set)
    donated_argnums: set[int] = field(default_factory=set)
    donated_argnames: set[str] = field(default_factory=set)
    returns_jitted: bool = False
    # the specific jit-root FunctionInfos a jitted-returning getter hands
    # out (so donation-aware checkers can map a `fn = self._get_step()`
    # binding back to the root's donate_argnums)
    returned_jit_roots: list["FunctionInfo"] = field(default_factory=list)
    # local names / `self.<attr>`s this function binds to other functions
    # (one level of alias, enclosing scopes chained at lookup time)
    local_aliases: dict[str, list[ast.AST]] = field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]

    def key(self) -> tuple[str, str]:
        return (self.path, self.qualname)


@dataclass
class ModuleFacts:
    path: str  # repo-relative, forward slashes
    source: str
    tree: ast.Module
    suppressions: Suppressions
    functions: dict[str, FunctionInfo] = field(default_factory=dict)
    import_aliases: dict[str, str] = field(default_factory=dict)   # np -> numpy
    from_imports: dict[str, tuple[str, str]] = field(default_factory=dict)
    # (class, attr) -> names of functions it aliases (self._fwd = forward)
    class_attr_fn_aliases: dict[tuple[str, str], list[str]] = field(default_factory=dict)
    # bindings that hold jit-compiled callables: local/module names,
    # (class, attr) pairs, and (class, attr) dicts subscript-assigned
    jitted_names: set[str] = field(default_factory=set)
    jitted_attrs: set[tuple[str, str]] = field(default_factory=set)
    # memoized full-tree walk — several families scan every module node;
    # one materialized tuple replaces a dozen generator re-walks (same
    # ast.walk BFS order, so findings are byte-identical). Idempotent
    # single-attribute store: safe under concurrent checker threads.
    _walk_cache: Optional[tuple] = field(default=None, repr=False,
                                         compare=False)

    def walk(self) -> tuple:
        if self._walk_cache is None:
            self._walk_cache = tuple(ast.walk(self.tree))
        return self._walk_cache


class _ModuleWalker(ast.NodeVisitor):
    def __init__(self, facts: ModuleFacts):
        self.f = facts
        self.class_stack: list[str] = []
        self.fn_stack: list[FunctionInfo] = []

    # -- imports ------------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.f.import_aliases[a.asname or a.name.split(".")[0]] = a.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        for a in node.names:
            self.f.from_imports[a.asname or a.name] = (mod, a.name)
        self.generic_visit(node)

    # -- classes / functions ------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def _qualname(self, name: str) -> str:
        parts = []
        if self.fn_stack:
            parts.append(self.fn_stack[-1].qualname + ".<locals>")
        elif self.class_stack:
            parts.append(".".join(self.class_stack))
        parts.append(name)
        return ".".join(parts)

    def _handle_def(self, node) -> None:
        info = FunctionInfo(
            path=self.f.path,
            qualname=self._qualname(node.name),
            node=node,
            class_name=self.class_stack[-1] if self.class_stack else None,
            parent=self.fn_stack[-1] if self.fn_stack else None,
        )
        a = node.args
        all_args = a.posonlyargs + a.args + a.kwonlyargs
        info.params = [p.arg for p in all_args]
        info.annotated_static = {
            p.arg for p in all_args if _annotation_is_static(p.annotation)
        }
        for dec in node.decorator_list:
            if _is_jit_wrapper(dec):
                info.jit_root = True
            elif isinstance(dec, ast.Call):
                if _is_jit_wrapper(dec.func) or (
                    _last_attr(dec.func) == "partial"
                    and any(_is_jit_wrapper(x) for x in dec.args)
                ):
                    info.jit_root = True
                    nums, names = _static_args_from_call(dec)
                    info.static_argnums |= nums
                    info.static_argnames |= names
                    dnums, dnames = _donate_args_from_call(dec)
                    info.donated_argnums |= dnums
                    info.donated_argnames |= dnames
        self.f.functions[info.qualname] = info
        self.fn_stack.append(info)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_FunctionDef = _handle_def
    visit_AsyncFunctionDef = _handle_def

    # -- bindings -----------------------------------------------------------
    def _alias_candidates(self, value: ast.AST) -> list[ast.AST]:
        """Expressions a binding may refer to, through IfExp/BoolOp."""
        if isinstance(value, ast.IfExp):
            return self._alias_candidates(value.body) + self._alias_candidates(value.orelse)
        if isinstance(value, ast.BoolOp):
            out: list[ast.AST] = []
            for v in value.values:
                out += self._alias_candidates(v)
            return out
        if isinstance(value, (ast.Name, ast.Attribute, ast.Call)):
            return [value]
        return []

    def _value_is_jitted(self, value: ast.AST) -> bool:
        if isinstance(value, ast.Call) and _is_jit_wrapper(value.func):
            return True
        if isinstance(value, ast.Name):
            fn = self._lookup_fn(value.id)
            if fn is not None and fn.jit_root:
                return True
            return value.id in self.f.jitted_names
        return False

    def _lookup_fn(self, name: str) -> Optional[FunctionInfo]:
        # nested defs of the current function chain, then module scope
        for fi in reversed(self.fn_stack):
            cand = self.f.functions.get(fi.qualname + ".<locals>." + name)
            if cand is not None:
                return cand
        return self.f.functions.get(name)

    def visit_Assign(self, node: ast.Assign) -> None:
        cands = self._alias_candidates(node.value)
        jitted = self._value_is_jitted(node.value)
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                if self.fn_stack:
                    self.fn_stack[-1].local_aliases.setdefault(tgt.id, []).extend(cands)
                if jitted:
                    self.f.jitted_names.add(tgt.id)
            elif (isinstance(tgt, ast.Attribute)
                  and isinstance(tgt.value, ast.Name) and tgt.value.id == "self"
                  and self.class_stack):
                cls = self.class_stack[-1]
                for c in cands:
                    if isinstance(c, ast.Name):
                        self.f.class_attr_fn_aliases.setdefault(
                            (cls, tgt.attr), []).append(c.id)
                    elif (isinstance(c, ast.Call) and _is_jit_wrapper(c.func)
                          and c.args and isinstance(c.args[0], ast.Name)):
                        # self._step = jax.jit(step, ...): the attr aliases
                        # the wrapped function (donation facts resolvable)
                        self.f.class_attr_fn_aliases.setdefault(
                            (cls, tgt.attr), []).append(c.args[0].id)
                if jitted:
                    self.f.jitted_attrs.add((cls, tgt.attr))
            elif (isinstance(tgt, ast.Subscript)
                  and isinstance(tgt.value, ast.Attribute)
                  and isinstance(tgt.value.value, ast.Name)
                  and tgt.value.value.id == "self"
                  and self.class_stack and jitted):
                # self._fns[key] = <jit-decorated def>
                self.f.jitted_attrs.add((self.class_stack[-1], tgt.value.attr))
        # jax.jit(fn) marks fn itself a root even when the wrapper is bound
        if isinstance(node.value, ast.Call) and _is_jit_wrapper(node.value.func):
            self._mark_wrapped_root(node.value)
        self.generic_visit(node)

    def _mark_wrapped_root(self, call: ast.Call) -> None:
        for arg in call.args[:1]:
            if isinstance(arg, ast.Name):
                fn = self._lookup_fn(arg.id)
                if fn is not None:
                    fn.jit_root = True
                    nums, names = _static_args_from_call(call)
                    fn.static_argnums |= nums
                    fn.static_argnames |= names
                    dnums, dnames = _donate_args_from_call(call)
                    fn.donated_argnums |= dnums
                    fn.donated_argnames |= dnames

    def visit_Call(self, node: ast.Call) -> None:
        if _is_jit_wrapper(node.func):
            self._mark_wrapped_root(node)
        self.generic_visit(node)

    def visit_Return(self, node: ast.Return) -> None:
        # children first: `return jax.jit(step, ...)` marks `step` a root
        self.generic_visit(node)
        if (self.fn_stack and node.value is not None
                and self._value_is_jitted(node.value)):
            me = self.fn_stack[-1]
            me.returns_jitted = True
            root: Optional[ast.AST] = None
            if isinstance(node.value, ast.Name):
                root = node.value
            elif isinstance(node.value, ast.Call) and node.value.args:
                root = node.value.args[0]  # return jax.jit(fn, ...)
            if isinstance(root, ast.Name):
                fn = self._lookup_fn(root.id)
                if fn is not None and fn.jit_root and fn not in me.returned_jit_roots:
                    me.returned_jit_roots.append(fn)


@dataclass
class CallSite:
    caller: FunctionInfo
    node: ast.Call
    callees: list[FunctionInfo]  # resolved candidates (may be empty)


class FactIndex:
    """All modules + the resolution/call-graph layer."""

    def __init__(self, root: Path):
        self.root = root
        self.modules: dict[str, ModuleFacts] = {}
        self.parse_errors: list[tuple[str, int, str]] = []
        # False when the index covers only a slice of the package (a
        # single-file or --changed scan): checkers whose rules reason
        # from the ABSENCE of facts (an axis no scanned mesh binds, a
        # function no scanned shard_map reaches) must stand down — the
        # missing fact may live in an unscanned module. Same contract as
        # the KVM032 docs-drift full-scan rule. run_lint sets it.
        self.full_scan: bool = True
        # dotted module name -> repo-relative path (for import resolution)
        self._by_dotted: dict[str, str] = {}
        # call_sites is re-requested per taint-fixpoint round and again by
        # each checker; the AST walk + name resolution dominate runtime,
        # and resolution is deterministic once the index is built
        self._call_sites_cache: dict[tuple[str, str], list["CallSite"]] = {}

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, root: Path, files: Iterable[Path]) -> "FactIndex":
        idx = cls(root)
        for f in files:
            try:
                rel = f.relative_to(root).as_posix()
            except ValueError:  # outside the lint root: keep the path as-is
                rel = f.as_posix()
            try:
                source = f.read_text(encoding="utf-8")
                tree = ast.parse(source)
            except (SyntaxError, UnicodeDecodeError) as e:
                idx.parse_errors.append((rel, getattr(e, "lineno", 0) or 0, str(e)))
                continue
            facts = ModuleFacts(
                path=rel, source=source, tree=tree,
                suppressions=Suppressions.scan(source),
            )
            _ModuleWalker(facts).visit(tree)
            idx.modules[rel] = facts
            dotted = rel[:-3].replace("/", ".") if rel.endswith(".py") else rel
            if dotted.endswith(".__init__"):
                dotted = dotted[: -len(".__init__")]
            idx._by_dotted[dotted] = rel
        idx._propagate_returns_jitted()
        return idx

    def _propagate_returns_jitted(self) -> None:
        """`def _spec(self): return build_spec_step_sampled(...)` — a getter
        returning another jitted-returning factory's result is itself a
        jitted-value source, and so is one that binds the result to a local
        first (`fn = steps.build_decode(...); self._steps[k] = fn; return
        fn`, the engine's one step getter): the attribute it files the
        value under then holds jitted callables. Cross-module, so it runs
        after all modules parse; small fixpoint (getter chains are short)."""
        for _ in range(4):
            changed = False
            for mod in self.modules.values():
                for fn in mod.functions.values():
                    for node in iter_scope(fn.node):
                        if isinstance(node, ast.Return) and node.value is not None:
                            roots = self._factory_roots(mod, fn, node.value)
                            if roots is None:
                                continue
                            if not fn.returns_jitted:
                                fn.returns_jitted = True
                                changed = True
                            for root in roots:
                                if root not in fn.returned_jit_roots:
                                    fn.returned_jit_roots.append(root)
                                    changed = True
                        elif (isinstance(node, ast.Assign) and fn.class_name
                              and self._factory_roots(mod, fn, node.value) is not None):
                            for tgt in node.targets:
                                if isinstance(tgt, ast.Subscript):
                                    tgt = tgt.value  # self._steps[key] = fn
                                if (isinstance(tgt, ast.Attribute)
                                        and isinstance(tgt.value, ast.Name)
                                        and tgt.value.id == "self"):
                                    mod.jitted_attrs.add((fn.class_name, tgt.attr))
            if not changed:
                return

    def _factory_roots(self, mod: ModuleFacts, fn: FunctionInfo,
                       expr: ast.AST) -> Optional[list[FunctionInfo]]:
        """The jit roots `expr` may evaluate to when it is a call of a
        jitted-returning factory, or a local name bound to such a call
        (the union, where a getter hands out several programs); None when
        it is neither."""
        if isinstance(expr, ast.Name):
            calls = [a for a in fn.local_aliases.get(expr.id, [])
                     if isinstance(a, ast.Call)]
        else:
            calls = [expr] if isinstance(expr, ast.Call) else []
        factories = [
            callee for call in calls
            for callee in self._resolve_expr(mod, fn, call.func)
            if callee.returns_jitted
        ]
        if not factories:
            return None
        return [root for f in factories for root in f.returned_jit_roots]

    # -- lookups ------------------------------------------------------------
    def functions(self) -> Iterable[FunctionInfo]:
        for m in self.modules.values():
            yield from m.functions.values()

    def module_for_dotted(self, dotted: str) -> Optional[ModuleFacts]:
        rel = self._by_dotted.get(dotted)
        if rel is None and dotted:
            # suffix match: `from models.llama import x` inside the package
            for d, r in self._by_dotted.items():
                if d.endswith("." + dotted) or d == dotted:
                    rel = r
                    break
        return self.modules.get(rel) if rel else None

    def _resolve_name(self, mod: ModuleFacts, caller: Optional[FunctionInfo],
                      name: str, _depth: int = 0) -> list[FunctionInfo]:
        """A bare name in `caller`'s scope -> function candidates."""
        if _depth > 4:
            return []
        out: list[FunctionInfo] = []
        fi = caller
        while fi is not None:
            cand = mod.functions.get(fi.qualname + ".<locals>." + name)
            if cand is not None:
                return [cand]
            for aliased in fi.local_aliases.get(name, []):
                # resolve the aliased expression in fi's OWN scope — the
                # binding may point at one of fi's nested defs
                out += self._resolve_expr(mod, fi, aliased, _depth + 1)
            if out:
                return out
            fi = fi.parent
        if name in mod.functions:
            return [mod.functions[name]]
        if caller is not None and caller.class_name:
            cand = mod.functions.get(f"{caller.class_name}.{name}")
            if cand is not None:
                return [cand]
        if name in mod.from_imports:
            src_mod, orig = mod.from_imports[name]
            target = self.module_for_dotted(src_mod)
            if target is not None and orig in target.functions:
                return [target.functions[orig]]
        return out

    def _resolve_expr(self, mod: ModuleFacts, caller: Optional[FunctionInfo],
                      expr: ast.AST, _depth: int = 0) -> list[FunctionInfo]:
        if isinstance(expr, ast.Name):
            return self._resolve_name(mod, caller, expr.id, _depth)
        if isinstance(expr, ast.Attribute):
            if isinstance(expr.value, ast.Name):
                if expr.value.id == "self" and caller is not None and caller.class_name:
                    cand = mod.functions.get(f"{caller.class_name}.{expr.attr}")
                    if cand is not None:
                        return [cand]
                    out = []
                    for aliased in mod.class_attr_fn_aliases.get(
                            (caller.class_name, expr.attr), []):
                        out += self._resolve_name(mod, None, aliased, _depth + 1)
                    return out
                dotted = mod.import_aliases.get(expr.value.id)
                if dotted is None and expr.value.id in mod.from_imports:
                    # `from pkg import module`: module-alias attributes too
                    dotted = ".".join(mod.from_imports[expr.value.id])
                if dotted is not None:
                    target = self.module_for_dotted(dotted)
                    if target is not None and expr.attr in target.functions:
                        return [target.functions[expr.attr]]
        return []

    def resolve_call(self, mod: ModuleFacts, caller: FunctionInfo,
                     call: ast.Call) -> list[FunctionInfo]:
        return self._resolve_expr(mod, caller, call.func)

    def call_sites(self, mod: ModuleFacts, fn: FunctionInfo) -> list[CallSite]:
        key = fn.key()
        cached = self._call_sites_cache.get(key)
        if cached is not None:
            return cached
        out = []
        for node in iter_scope(fn.node):
            if isinstance(node, ast.Call):
                out.append(CallSite(fn, node, self.resolve_call(mod, fn, node)))
        self._call_sites_cache[key] = out
        return out

    # -- jit dispatch detection --------------------------------------------
    def calls_jitted_value(self, mod: ModuleFacts, fn: FunctionInfo,
                           call: ast.Call) -> bool:
        """Does this callsite invoke a jit-compiled callable (directly, via a
        jitted binding, or via a name bound from a jitted-returning getter)?"""
        f = call.func
        if isinstance(f, ast.Call) and _is_jit_wrapper(f.func):
            return True  # jax.jit(fn)(args)
        if isinstance(f, ast.Name):
            if f.id in mod.jitted_names:
                return True
            fi = fn
            while fi is not None:
                for aliased in fi.local_aliases.get(f.id, []):
                    if isinstance(aliased, ast.Call):
                        for g in self._resolve_expr(mod, fi, aliased.func):
                            if g.returns_jitted:
                                return True
                    for g in self._resolve_expr(mod, fi, aliased):
                        if g.jit_root:
                            return True
                fi = fi.parent
            for g in self._resolve_name(mod, fn, f.id):
                if g.jit_root:
                    return True
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id == "self" and fn.class_name:
                if (fn.class_name, f.attr) in mod.jitted_attrs:
                    return True
        if isinstance(f, ast.Subscript) and isinstance(f.value, ast.Attribute):
            sub = f.value
            if (isinstance(sub.value, ast.Name) and sub.value.id == "self"
                    and fn.class_name
                    and (fn.class_name, sub.attr) in mod.jitted_attrs):
                return True  # self._steps[key](...)
        return False

    # -- lockstep anchors ---------------------------------------------------
    def follower_replayed_methods(self) -> set[str]:
        """Method names a multihost follower replays: `<obj>.<m>(...)` calls
        inside any function named run_follower*/run_replica*."""
        out: set[str] = set()
        for mod in self.modules.values():
            for fn in mod.functions.values():
                if not fn.name.startswith(("run_follower", "run_replica")):
                    continue
                for node in ast.walk(fn.node):
                    if (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and isinstance(node.func.value, ast.Name)):
                        out.add(node.func.attr)
        return out
