"""Cross-module symbol/call graph for fluidlint's whole-program rules.

fluidlint v1 rules were single-module by design: a rule saw one
``ModuleContext`` and pattern-matched names. The donated-buffer
lifecycle rules (lifecycle_rules.py) need more — at a call site in
``tpu_sequencer.py`` they must know that ``serve_step.serve_megakernel``
donates its first three arguments, even though that fact lives in a
``functools.partial(jax.jit, donate_argnums=(0, 1, 2), …)``
``(_serve_megakernel)`` assignment two modules away. This module builds that map once per run:

* every function/method def in the analyzed tree, keyed by qualname
  (``module:func`` / ``module:Class.method``);
* how each is jitted — decorator (``@jax.jit``, ``@partial(jax.jit,…)``),
  call form (``jax.jit(fn, …)``), or assignment-wrapper form
  (``name = functools.partial(jax.jit, …)(fn)`` and
  ``name = jax.jit(fn, …)``, including ``fn.__wrapped__`` targets);
* module import aliases (plain, from-import, relative) so a dotted
  callee resolves across the package;
* simple module-level aliases (``g = f``) and instance-attribute jit
  handles (``self._step = jax.jit(full_step, donate_argnums=(0, 1))``).

Resolution is intentionally name-based and conservative: an
unresolvable callee yields ``None`` and the dataflow pass simply models
no effect — whole-program soundness is traded for a near-zero false
positive rate, the same bargain every fluidlint rule makes.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import (
    JitInfo,
    _dotted,
    _is_jit_ref,
    decorator_jit_info as _decorator_jit_info,
)

_MAX_ALIAS_HOPS = 8  # alias-chain bound: cycles and pathological chains stop


@dataclass
class FunctionDecl:
    """One function/method def somewhere in the analyzed tree."""
    qualname: str                  # "module:func" / "module:Class.meth"
    module: str                    # dotted module name
    name: str
    class_name: Optional[str]
    node: ast.AST                  # FunctionDef | AsyncFunctionDef
    jit: Optional[JitInfo] = None  # donation/static info when jitted

    @property
    def param_names(self) -> List[str]:
        args = self.node.args
        return [a.arg for a in args.posonlyargs + args.args]


@dataclass
class DonationSignature:
    """What a call site needs to know about a donating callee: which of
    ITS OWN argument positions/keywords hand their buffers over."""
    callee: str                       # display name for messages
    positions: Set[int] = field(default_factory=set)
    names: Set[str] = field(default_factory=set)

    def donated_args(self, call: ast.Call,
                     bound_self: bool = False) -> List[ast.AST]:
        """The argument expressions at donated positions of ``call``.
        ``bound_self`` shifts positions down by one (method called via
        ``self.m(...)``: param 0 is the bound instance). Starred args
        make positions unmappable — the call is skipped entirely, which
        is the conservative (quiet) choice."""
        if any(isinstance(a, ast.Starred) for a in call.args):
            return []
        shift = 1 if bound_self else 0
        out: List[ast.AST] = []
        for i, arg in enumerate(call.args):
            if (i + shift) in self.positions:
                out.append(arg)
        for kw in call.keywords:
            if kw.arg is not None and kw.arg in self.names:
                out.append(kw.value)
        return out


@dataclass
class _JitWrap:
    """``name = jax.jit(target, …)`` / ``partial(jax.jit, …)(target)``
    at module level: ``name`` is a jitted callable over ``target``."""
    target: Optional[str]          # local name the wrap was applied to
    donate_argnums: Set[int]
    donate_argnames: Set[str]


class ModuleSymbols:
    """Per-module symbol table: defs, aliases, imports, jit wrappers."""

    def __init__(self, module: str, tree: ast.Module, path: str = ""):
        self.module = module
        self.path = path
        # A package __init__ is its own package for relative imports
        # (`from . import x` inside server/__init__.py resolves against
        # fluidframework_tpu.server, not its parent).
        self.is_package = path.replace("\\", "/").endswith("__init__.py")
        self.tree = tree
        self.functions: Dict[str, FunctionDecl] = {}
        self.methods: Dict[str, Dict[str, FunctionDecl]] = {}
        self.aliases: Dict[str, str] = {}          # name -> local name
        self.jit_wrappers: Dict[str, _JitWrap] = {}
        self.imports: Dict[str, str] = {}          # name -> absolute dotted
        # (class, attr) -> _JitWrap for `self.attr = jax.jit(fn, …)`
        self.attr_wrappers: Dict[Tuple[str, str], _JitWrap] = {}
        self._index()

    # -- indexing ----------------------------------------------------------
    def _index(self) -> None:
        # Imports index from the WHOLE tree: this codebase routinely
        # defers imports into function bodies (`from . import
        # serve_step` inside the dispatch path) and those aliases must
        # still resolve at call sites. Collisions are rare enough that
        # a module-wide alias table is the right trade.
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                self._index_import(node)
        for stmt in self.tree.body:
            self._index_stmt(stmt, class_name=None)

    def _index_stmt(self, stmt: ast.stmt, class_name: Optional[str]) -> None:
        if isinstance(stmt, (ast.Import, ast.ImportFrom)):
            pass  # indexed tree-wide in _index
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            decl = FunctionDecl(
                qualname=(f"{self.module}:{class_name}.{stmt.name}"
                          if class_name else f"{self.module}:{stmt.name}"),
                module=self.module, name=stmt.name, class_name=class_name,
                node=stmt, jit=_decorator_jit_info(stmt))
            if class_name is None:
                self.functions[stmt.name] = decl
            else:
                self.methods.setdefault(class_name, {})[stmt.name] = decl
            if class_name is not None:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Assign):
                        self._index_attr_wrap(sub, class_name)
        elif isinstance(stmt, ast.ClassDef):
            for sub in stmt.body:
                self._index_stmt(sub, class_name=stmt.name)
        elif isinstance(stmt, ast.Assign) and class_name is None:
            self._index_module_assign(stmt)
        elif isinstance(stmt, (ast.If, ast.Try)):
            for sub in ast.iter_child_nodes(stmt):
                if isinstance(sub, ast.stmt):
                    self._index_stmt(sub, class_name)

    def _index_import(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Import):
            for alias in stmt.names:
                local = alias.asname or alias.name.split(".")[0]
                target = alias.name if alias.asname else \
                    alias.name.split(".")[0]
                self.imports[local] = target
        elif isinstance(stmt, ast.ImportFrom):
            base = self._resolve_from_base(stmt)
            if base is None:
                return
            for alias in stmt.names:
                if alias.name == "*":
                    continue
                local = alias.asname or alias.name
                self.imports[local] = (f"{base}.{alias.name}"
                                       if base else alias.name)

    def _resolve_from_base(self, stmt: ast.ImportFrom) -> Optional[str]:
        if stmt.level == 0:
            return stmt.module or ""
        # Relative: peel the module's own dotted name down to its
        # package, then climb one level per extra dot.
        parts = self.module.split(".")
        if not self.is_package:
            parts = parts[:-1]
        up = stmt.level - 1
        if up > len(parts):
            return None
        base_parts = parts[:len(parts) - up] if up else parts
        if stmt.module:
            base_parts = base_parts + stmt.module.split(".")
        return ".".join(base_parts)

    def _index_module_assign(self, stmt: ast.Assign) -> None:
        if len(stmt.targets) != 1 or not isinstance(stmt.targets[0],
                                                    ast.Name):
            return
        name = stmt.targets[0].id
        wrap = _parse_jit_wrap(stmt.value)
        if wrap is not None:
            self.jit_wrappers[name] = wrap
            return
        if isinstance(stmt.value, ast.Name):
            self.aliases[name] = stmt.value.id

    def _index_attr_wrap(self, stmt: ast.Assign, class_name: str) -> None:
        """``self.attr = jax.jit(fn, donate_argnums=…)`` inside a method:
        the instance attribute is a jitted callable other methods invoke
        as ``self.attr(…)`` (server/bridge.py's ``self._step``)."""
        if len(stmt.targets) != 1:
            return
        t = stmt.targets[0]
        if not (isinstance(t, ast.Attribute) and
                isinstance(t.value, ast.Name) and t.value.id == "self"):
            return
        wrap = _parse_jit_wrap(stmt.value)
        if wrap is not None:
            self.attr_wrappers[(class_name, t.attr)] = wrap


def _parse_jit_wrap(value: ast.AST) -> Optional[_JitWrap]:
    """Recognize the two assignment-wrapper jit forms:
    ``jax.jit(fn, donate_argnums=…)`` and
    ``functools.partial(jax.jit, donate_argnums=…)(fn)``; ``fn`` may be
    a Name or ``name.__wrapped__`` (unwrapping an already-jitted def)."""
    if not isinstance(value, ast.Call):
        return None
    donate_nums: Set[int] = set()
    donate_names: Set[str] = set()
    target_expr: Optional[ast.AST] = None
    if _is_jit_ref(value.func) and value.args:
        target_expr = value.args[0]
        _collect_donates(value.keywords, donate_nums, donate_names)
    elif (isinstance(value.func, ast.Call)
          and _dotted(value.func.func) in ("functools.partial", "partial")
          and value.func.args and _is_jit_ref(value.func.args[0])
          and value.args):
        target_expr = value.args[0]
        _collect_donates(value.func.keywords, donate_nums, donate_names)
    else:
        return None
    target = _wrap_target_name(target_expr)
    return _JitWrap(target=target, donate_argnums=donate_nums,
                    donate_argnames=donate_names)


def _wrap_target_name(expr: ast.AST) -> Optional[str]:
    if isinstance(expr, ast.Attribute) and expr.attr == "__wrapped__":
        expr = expr.value
    if isinstance(expr, ast.Name):
        return expr.id
    return None


def _collect_donates(keywords, nums: Set[int], names: Set[str]) -> None:
    from .engine import _int_elems, _str_elems
    for kw in keywords:
        if kw.arg == "donate_argnums":
            nums |= _int_elems(kw.value)
        elif kw.arg == "donate_argnames":
            names |= _str_elems(kw.value)


@dataclass
class ResolvedCallee:
    """A call site resolved to a program symbol: the def (when found),
    its donation signature (when it donates), and whether the call binds
    ``self`` (method form — donated positions shift by one)."""
    qualname: str
    decl: Optional[FunctionDecl]
    donation: Optional[DonationSignature]
    bound_self: bool = False


class ProgramIndex:
    """The whole-program symbol/call graph.

    Build it from ``(module_name, tree, path)`` triples (the engine
    hands it every parsed ``ModuleContext``); query with
    :meth:`resolve_call` from a rule/dataflow visitor positioned inside
    one module, or :meth:`call_edges` for the plain caller→callee graph
    the unit tests exercise."""

    def __init__(self, modules: Sequence[Tuple[str, ast.Module, str]]):
        self.modules: Dict[str, ModuleSymbols] = {}
        for name, tree, path in modules:
            self.modules[name] = ModuleSymbols(name, tree, path)

    # -- symbol lookup -----------------------------------------------------
    def lookup(self, module: str, name: str,
               _hops: int = 0) -> Optional[ResolvedCallee]:
        """Resolve a bare name in ``module`` to a program symbol,
        chasing aliases, jit wrappers, and from-imports."""
        syms = self.modules.get(module)
        if syms is None or _hops > _MAX_ALIAS_HOPS:
            return None
        if name in syms.functions:
            decl = syms.functions[name]
            return ResolvedCallee(decl.qualname, decl,
                                  _decl_donation(decl))
        if name in syms.jit_wrappers:
            return self._resolve_wrap(syms, name, syms.jit_wrappers[name],
                                      _hops)
        if name in syms.aliases:
            return self.lookup(module, syms.aliases[name], _hops + 1)
        if name in syms.imports:
            return self._lookup_dotted(syms.imports[name], _hops + 1)
        return None

    def _resolve_wrap(self, syms: ModuleSymbols, name: str, wrap: _JitWrap,
                      _hops: int) -> ResolvedCallee:
        decl = None
        if wrap.target:
            inner = self.lookup(syms.module, wrap.target, _hops + 1)
            if inner is not None:
                decl = inner.decl
        donation = None
        if wrap.donate_argnums or wrap.donate_argnames:
            names = set(wrap.donate_argnames)
            if decl is not None:
                params = decl.param_names
                names |= {params[i] for i in wrap.donate_argnums
                          if i < len(params)}
            donation = DonationSignature(
                callee=name, positions=set(wrap.donate_argnums),
                names=names)
        qual = decl.qualname if decl else f"{syms.module}:{name}"
        return ResolvedCallee(f"{syms.module}:{name}" if decl is None
                              else qual, decl, donation)

    def _lookup_dotted(self, dotted: str,
                       _hops: int = 0) -> Optional[ResolvedCallee]:
        """Resolve an absolute dotted symbol ("pkg.mod.func" or
        "pkg.mod" + later attribute): longest module prefix wins."""
        if _hops > _MAX_ALIAS_HOPS:
            return None
        parts = dotted.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:cut])
            if mod in self.modules:
                rest = parts[cut:]
                if len(rest) == 1:
                    return self.lookup(mod, rest[0], _hops + 1)
                if len(rest) == 2:  # Class.method
                    decl = self.modules[mod].methods.get(
                        rest[0], {}).get(rest[1])
                    if decl is not None:
                        return ResolvedCallee(decl.qualname, decl,
                                              _decl_donation(decl))
                return None
        return None

    # -- call-site resolution ---------------------------------------------
    def resolve_call(self, module: str, call: ast.Call,
                     class_name: Optional[str] = None,
                     local_defs: Optional[Dict[str, ast.AST]] = None
                     ) -> Optional[ResolvedCallee]:
        """Resolve ``call``'s callee as seen from ``module`` (and, for
        ``self.x(...)`` forms, from ``class_name``). ``local_defs``
        carries the enclosing function's nested defs, which shadow
        module symbols."""
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "__wrapped__":
            func = func.value
        dotted = _dotted(func)
        if not dotted:
            return None
        parts = dotted.split(".")
        if len(parts) == 1:
            name = parts[0]
            if local_defs and name in local_defs:
                node = local_defs[name]
                decl = FunctionDecl(
                    qualname=f"{module}:<local>.{name}", module=module,
                    name=name, class_name=class_name, node=node,
                    jit=_decorator_jit_info(node))
                return ResolvedCallee(decl.qualname, decl,
                                      _decl_donation(decl))
            return self.lookup(module, name)
        if parts[0] == "self" and class_name is not None:
            syms = self.modules.get(module)
            if syms is None or len(parts) != 2:
                return None
            decl = syms.methods.get(class_name, {}).get(parts[1])
            if decl is not None:
                res = ResolvedCallee(decl.qualname, decl,
                                     _decl_donation(decl),
                                     bound_self=True)
                return res
            wrap = syms.attr_wrappers.get((class_name, parts[1]))
            if wrap is not None:
                return self._resolve_wrap_attr(syms, class_name,
                                               parts[1], wrap)
            return None
        syms = self.modules.get(module)
        if syms is not None and parts[0] in syms.imports:
            dotted_abs = ".".join([syms.imports[parts[0]]] + parts[1:])
            return self._lookup_dotted(dotted_abs)
        return None

    def _resolve_wrap_attr(self, syms: ModuleSymbols, class_name: str,
                           attr: str, wrap: _JitWrap) -> ResolvedCallee:
        decl = None
        if wrap.target:
            inner = self.lookup(syms.module, wrap.target)
            if inner is not None:
                decl = inner.decl
        donation = None
        if wrap.donate_argnums or wrap.donate_argnames:
            names = set(wrap.donate_argnames)
            if decl is not None:
                params = decl.param_names
                names |= {params[i] for i in wrap.donate_argnums
                          if i < len(params)}
            donation = DonationSignature(
                callee=f"self.{attr}", positions=set(wrap.donate_argnums),
                names=names)
        qual = decl.qualname if decl else \
            f"{syms.module}:{class_name}.{attr}"
        return ResolvedCallee(qual, decl, donation)

    # -- enumeration -------------------------------------------------------
    def iter_functions(self):
        for syms in self.modules.values():
            yield from syms.functions.values()
            for methods in syms.methods.values():
                yield from methods.values()

    def call_edges(self, module: str) -> Set[Tuple[str, str]]:
        """(caller qualname, callee qualname) edges for one module —
        the call-graph surface the resolution unit tests pin."""
        syms = self.modules.get(module)
        if syms is None:
            return set()
        edges: Set[Tuple[str, str]] = set()
        for decl in list(syms.functions.values()) + [
                m for ms in syms.methods.values() for m in ms.values()]:
            local_defs = {n.name: n for n in ast.walk(decl.node)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))
                          and n is not decl.node}
            for sub in ast.walk(decl.node):
                if not isinstance(sub, ast.Call):
                    continue
                res = self.resolve_call(module, sub,
                                        class_name=decl.class_name,
                                        local_defs=local_defs)
                if res is not None:
                    edges.add((decl.qualname, res.qualname))
        return edges

    def signature_digest_items(self) -> List[str]:
        """Stable serialization of every donation-relevant interface
        fact; the engine hashes this into the cache key so editing a
        signature anywhere invalidates every module's cached result."""
        items: List[str] = []
        for mod in sorted(self.modules):
            syms = self.modules[mod]
            for decl in sorted(
                    list(syms.functions.values())
                    + [m for ms in syms.methods.values()
                       for m in ms.values()],
                    key=lambda d: d.qualname):
                if decl.jit is not None and (decl.jit.donate_argnums
                                             or decl.jit.donate_argnames):
                    items.append(
                        f"{decl.qualname}|"
                        f"{sorted(decl.jit.donate_argnums)}|"
                        f"{sorted(decl.jit.donate_argnames)}")
            for name in sorted(syms.jit_wrappers):
                w = syms.jit_wrappers[name]
                items.append(f"{mod}:{name}|{sorted(w.donate_argnums)}|"
                             f"{sorted(w.donate_argnames)}|w:{w.target}")
            for (cls, attr) in sorted(syms.attr_wrappers):
                w = syms.attr_wrappers[(cls, attr)]
                items.append(f"{mod}:{cls}.{attr}|"
                             f"{sorted(w.donate_argnums)}|"
                             f"{sorted(w.donate_argnames)}|w:{w.target}")
        return items


def _decl_donation(decl: FunctionDecl) -> Optional[DonationSignature]:
    jit = decl.jit
    if jit is None or not (jit.donate_argnums or jit.donate_argnames):
        return None
    params = decl.param_names
    names = set(jit.donate_argnames)
    names |= {params[i] for i in jit.donate_argnums if i < len(params)}
    return DonationSignature(callee=decl.name,
                             positions=set(jit.donate_argnums),
                             names=names)


def module_name_for_path(path: str) -> str:
    """Dotted module name for a repo-root-relative path; fixture paths
    ("<memory>", tmp files) fall back to their stem so single-module
    analysis still resolves local symbols."""
    p = path.replace("\\", "/")
    if p.endswith(".py"):
        p = p[:-3]
    if p.endswith("/__init__"):
        p = p[:-len("/__init__")]
    if p.startswith("<") or "/" not in p:
        return p.rsplit("/", 1)[-1] or p
    return p.replace("/", ".")
