"""Donated-buffer lifecycle rules (fluidlint v2, whole-program).

Three rule families over the callgraph + dataflow layer
(callgraph.py / dataflow.py), guarding the donated-dispatch discipline
that has produced this repo's costliest bug class three PRs running
(docs/serving_pipeline.md R6, docs/static_analysis.md):

* ``USE_AFTER_DONATE`` — a binding (or alias: tuple leaf, pytree-carry
  member, attribute chain) whose buffer went to a ``donate_argnums``
  position is read again before reassignment. The PR 7 burst-fallback
  shape — an except handler re-reading the donated scan carry — is the
  seeded regression fixture.
* ``DONATED_ESCAPE`` — a donated binding stored into ``self.*`` state
  that outlives the dispatch (the PR 5 stale-lane-plane shape), either
  stored-then-donated or donated-then-stored.
* ``PAGE_ID_DTYPE`` (v2) — the int16/int32/int64/uint32 dtype lattice
  propagated through ``astype``/``asarray``/arithmetic/subscripts, so a
  page id widened or narrowed through an intermediate binding is caught
  where the old regex (which only saw page-NAMED assignments) was
  blind. Scope, triggers, and message shape are unchanged from v1.

Sanctioned patterns are modeled as guards, not blanket suppressions:
``serve_megakernel_keep``-style non-donating variants simply resolve to
a smaller donation signature; the megakernel fallback's
liveness-probe-then-reraise (``tree_leaves``/``.is_deleted()``, also
through ``map(_gone, states)``) is recognized as a metadata read; and
the canonical ``state, ys = step(state, xs)`` rebind kills the donation
in the same statement.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, List, Optional, Set, Tuple

from .engine import ModuleContext, Violation
from .registry import rule
from .jax_rules import _scan_scope

# Page-table indices must ride the canonical int32 page-id dtype
# (mergetree.constants.PAGE_ID_DTYPE); see the v1 rationale. The name
# trigger and kernel surface are unchanged from v1 — only the engine
# underneath moved from regex matching to the dataflow lattice.
_PAGE_NAME_RE = re.compile(
    r"(^|_)(page_?(ids?|tables?)|pids)($|_)", re.IGNORECASE)

_PAGED_KERNEL_NAMES = {
    "gather_pages", "scatter_pages", "rollback_pages", "apply_ops_paged",
    "compact_pages", "compact_extract_paged", "serve_paged_burst",
}


def _program_for(ctx: ModuleContext):
    """The whole-program context. analyze_paths attaches one spanning
    every analyzed module; analyze_source (fixtures) gets a
    single-module program built on demand."""
    program = getattr(ctx, "program", None)
    if program is None:
        from .engine import ProgramContext
        program = ProgramContext([ctx])
        ctx.program = program
    return program


def _enclosing_class(ctx: ModuleContext, fn: ast.AST) -> Optional[str]:
    cur = ctx.parents.get(fn)
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            return cur.name
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a def nested in a method still sees that method's class
            cur = ctx.parents.get(cur)
            continue
        cur = ctx.parents.get(cur)
    return None


def _jit_wrapped_defs(ctx: ModuleContext, program) -> Set[str]:
    """Names of module functions consumed by an assignment jit wrapper
    (``serve_megakernel = partial(jax.jit, …)(_serve_megakernel)``): their
    bodies are traced code exactly like decorator-jitted ones."""
    from .callgraph import module_name_for_path
    mod = program.index.modules.get(module_name_for_path(ctx.path))
    if mod is None:
        return set()
    return {w.target for w in mod.jit_wrappers.values() if w.target}


def _inside_jit(ctx: ModuleContext, fn: ast.AST,
                wrapped_names: Set[str]) -> bool:
    cur: Optional[ast.AST] = fn
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if cur in ctx.jit_functions or cur.name in wrapped_names:
                return True
        cur = ctx.parents.get(cur)
    return False


def _module_findings(ctx: ModuleContext):
    """Run the dataflow pass once per module; the three rules below
    each filter their kind. Cached on the context because the registry
    invokes every rule's check() independently."""
    cached = getattr(ctx, "_lifecycle_findings", None)
    if cached is not None:
        return cached
    from .callgraph import module_name_for_path
    from .dataflow import FunctionDataflow
    program = _program_for(ctx)
    module_name = module_name_for_path(ctx.path)
    wrapped = _jit_wrapped_defs(ctx, program)
    findings: List[Tuple[str, ast.AST, str]] = []
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        df = FunctionDataflow(
            fn, module_name, _enclosing_class(ctx, fn),
            program.index, program.summaries,
            page_name_re=_PAGE_NAME_RE,
            paged_kernel_names=_PAGED_KERNEL_NAMES,
            # Donation is a CALL-BOUNDARY effect: inside a traced body
            # jax ignores nested donation, so only host functions get
            # lifecycle tracking (dtype checks still run everywhere).
            track_donation=not _inside_jit(ctx, fn, wrapped))
        for f in df.run():
            findings.append((f.kind, f.node, f.message))
    # Module-level statements (page-table staging helpers built at
    # import time): dtype lattice only, no donation semantics.
    mod_fn = ast.FunctionDef(
        name="<module>", body=[s for s in ctx.tree.body
                               if not isinstance(s, (ast.FunctionDef,
                                                     ast.AsyncFunctionDef,
                                                     ast.ClassDef))],
        decorator_list=[],
        args=ast.arguments(posonlyargs=[], args=[], vararg=None,
                           kwonlyargs=[], kw_defaults=[], kwarg=None,
                           defaults=[]))
    df = FunctionDataflow(mod_fn, module_name, None, program.index,
                          program.summaries,
                          page_name_re=_PAGE_NAME_RE,
                          paged_kernel_names=_PAGED_KERNEL_NAMES,
                          track_donation=False)
    for f in df.run():
        findings.append((f.kind, f.node, f.message))
    ctx._lifecycle_findings = findings
    return findings


def _emit(ctx: ModuleContext, kind: str) -> Iterator[Violation]:
    if not _scan_scope(ctx):
        return
    seen: Set[Tuple[int, int, str]] = set()
    for k, node, message in _module_findings(ctx):
        if k != kind:
            continue
        key = (getattr(node, "lineno", 0),
               getattr(node, "col_offset", 0), message)
        if key in seen:
            continue
        seen.add(key)
        yield ctx.violation(kind, node, message)


@rule("USE_AFTER_DONATE",
      "Read of a donated binding (or alias) after the donating "
      "dispatch, before reassignment",
      family="jax",
      rationale="donate_argnums hands the buffer to XLA: the dispatch "
                "may reuse or free it immediately, so a later read "
                "returns garbage or raises on a deleted array — the PR 7 "
                "burst-fallback bug class. Rebind from the call result, "
                "or probe liveness (tree_leaves/.is_deleted()) and "
                "re-raise instead of falling back onto the carry.")
def use_after_donate(ctx: ModuleContext) -> Iterator[Violation]:
    yield from _emit(ctx, "USE_AFTER_DONATE")


@rule("DONATED_ESCAPE",
      "Donated binding stored into self.*/module state that outlives "
      "the dispatch",
      family="jax",
      rationale="Instance state holding a donated plane is a time bomb: "
                "the next reader (often a whole flush later) sees freed "
                "or recycled device memory — the PR 5 stale-lane-plane "
                "shape. Store the call's RESULT, or rebind the attribute "
                "before returning.")
def donated_escape(ctx: ModuleContext) -> Iterator[Violation]:
    yield from _emit(ctx, "DONATED_ESCAPE")


@rule("PAGE_ID_DTYPE",
      "Page-table index built, cast, or propagated with a non-int32 "
      "integer dtype",
      family="jax",
      rationale="Page ids are the canonical int32 device index "
                "(mergetree.constants.PAGE_ID_DTYPE): int64 doubles "
                "every page-table transfer, int16 wraps past 32k pages "
                "into another document's page, and unsigned 32-bit "
                "destroys the -1 padding sentinel. v2 propagates the "
                "dtype through astype/asarray/arithmetic, so the drift "
                "is caught even through intermediate bindings.")
def page_id_dtype(ctx: ModuleContext) -> Iterator[Violation]:
    yield from _emit(ctx, "PAGE_ID_DTYPE")
