"""JX* rules: JAX/TPU kernel hygiene inside jit-decorated functions.

Every rule here scopes itself to functions the engine indexed as jitted
(decorator or ``jax.jit(fn)`` call form), so host-side code never
trips them. They are heuristics over the AST — no dataflow — tuned to
stay quiet on the idioms this codebase deliberately uses (``is None``
staging guards, ``static_argnums`` flags, shape/dtype attribute reads).
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Optional, Set, Tuple

from .engine import ModuleContext, Violation, _dotted
from .registry import rule

DTYPE_NAME_RE = re.compile(
    r"^(u?int(8|16|32|64)|float(16|32|64)|bfloat16|bool_|complex(64|128))$")

_NUMPY_MODULES = ("jnp", "np", "numpy", "jax.numpy")


def _canonical_dtypes() -> Set[str]:
    try:
        from ..mergetree.constants import CANONICAL_DEVICE_DTYPES
        return set(CANONICAL_DEVICE_DTYPES)
    except ImportError:  # analyzer used standalone against another tree
        return {"int32", "bool_"}


def _within(ctx: ModuleContext, node: ast.AST, stop: ast.AST):
    """Ancestors of ``node`` up to and including ``stop``."""
    cur = ctx.parents.get(node)
    while cur is not None:
        yield cur
        if cur is stop:
            return
        cur = ctx.parents.get(cur)


def _is_static_read(ctx: ModuleContext, name: ast.Name,
                    test: ast.AST) -> bool:
    """True when this Name occurrence cannot force a concrete value out
    of a tracer: identity tests, isinstance/len(), or attribute reads
    (shape/ndim/dtype and namedtuple statics like ``.capacity``)."""
    for anc in _within(ctx, name, test):
        if isinstance(anc, ast.Attribute):
            return True
        if isinstance(anc, ast.Subscript) and anc.value is not name:
            continue
        if isinstance(anc, ast.Call):
            fn = _dotted(anc.func)
            if fn in ("isinstance", "len", "getattr", "hasattr", "type"):
                return True
        if isinstance(anc, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in anc.ops):
            return True
    if isinstance(ctx.parents.get(name), ast.Compare):
        comp = ctx.parents[name]
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in comp.ops):
            return True
    return False


def _hazard_names(ctx: ModuleContext, test: ast.AST,
                  traced: Set[str]) -> Set[str]:
    out: Set[str] = set()
    nodes = [test] + [n for n in ast.walk(test)]
    for node in nodes:
        if (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)
                and node.id in traced
                and not _is_static_read(ctx, node, test)):
            out.add(node.id)
    return out


@rule("TRACED_BRANCH",
      "Python if/while branches on a traced value inside a jitted function",
      family="jax",
      rationale="A concrete branch on a tracer either raises at trace time "
                "or silently bakes one path into the compiled program; use "
                "jnp.where / lax.cond, or mark the argument static.")
def traced_branch(ctx: ModuleContext) -> Iterator[Violation]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        info = ctx.enclosing_jit(node)
        if info is None:
            continue
        names = _hazard_names(ctx, node.test, info.traced_params())
        if names:
            kind = "while" if isinstance(node, ast.While) else "if"
            yield ctx.violation(
                "TRACED_BRANCH", node,
                f"`{kind}` branches on traced argument(s) "
                f"{', '.join(sorted(names))} inside jitted "
                f"`{info.node.name}`; use jnp.where/lax.cond or add the "
                f"argument to static_argnums")


@rule("HOST_SYNC",
      "Host synchronization (.item()/.tolist()/bool()/int()/float() on a "
      "traced value) inside a jitted function",
      family="jax",
      rationale="Forcing a concrete Python value out of a tracer raises a "
                "ConcretizationTypeError at trace time — or, on the host "
                "staging path, blocks on a device round-trip per call.")
def host_sync(ctx: ModuleContext) -> Iterator[Violation]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        info = ctx.enclosing_jit(node)
        if info is None:
            continue
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in ("item", "tolist")
                and not node.args):
            yield ctx.violation(
                "HOST_SYNC", node,
                f"`.{node.func.attr}()` inside jitted "
                f"`{info.node.name}` forces a host sync")
            continue
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("bool", "int", "float")
                and len(node.args) == 1):
            names = _hazard_names(ctx, node.args[0], info.traced_params())
            if names:
                yield ctx.violation(
                    "HOST_SYNC", node,
                    f"`{node.func.id}()` concretizes traced argument(s) "
                    f"{', '.join(sorted(names))} inside jitted "
                    f"`{info.node.name}`")


def _jnp_calls(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            fn = _dotted(sub.func)
            if fn.startswith(("jnp.", "jax.numpy.", "jax.lax.", "lax.")):
                yield sub


@rule("RETRACE_HAZARD",
      "jnp/lax calls inside a Python for/while loop in a jitted function",
      family="jax",
      rationale="A Python loop unrolls at trace time: program size (and "
                "compile time) scales with the trip count, and a "
                "data-dependent count retraces per shape. Use lax.scan/"
                "fori_loop, or suppress when the unroll is deliberately "
                "bounded (e.g. the per-group serving-window unroll).")
def retrace_hazard(ctx: ModuleContext) -> Iterator[Violation]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        info = ctx.enclosing_jit(node)
        if info is None:
            continue
        calls = list(_jnp_calls(node))
        if calls:
            yield ctx.violation(
                "RETRACE_HAZARD", node,
                f"Python loop inside jitted `{info.node.name}` unrolls "
                f"{len(calls)} jnp/lax call(s) at trace time; prefer "
                f"lax.scan/fori_loop")


def _module_mutable_globals(ctx: ModuleContext) -> Set[str]:
    out: Set[str] = set()
    for stmt in ctx.tree.body:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        mutable = isinstance(value, (ast.List, ast.Dict, ast.Set,
                                     ast.ListComp, ast.DictComp,
                                     ast.SetComp))
        if isinstance(value, ast.Call) and _dotted(value.func) in (
                "list", "dict", "set", "collections.defaultdict",
                "defaultdict", "collections.deque", "deque", "bytearray"):
            mutable = True
        if mutable:
            for t in targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


@rule("MUTABLE_CAPTURE",
      "Jitted function reads a module-level mutable (list/dict/set) global",
      family="jax",
      rationale="jit captures closed-over values at trace time; later "
                "mutations are invisible to the compiled program (or force "
                "a retrace via a changed hash). Pass the data as an "
                "argument or freeze it into a tuple/constant.")
def mutable_capture(ctx: ModuleContext) -> Iterator[Violation]:
    mutables = _module_mutable_globals(ctx)
    if not mutables:
        return
    for fn, info in ctx.jit_functions.items():
        local: Set[str] = {a.arg for a in fn.args.args}
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
                local.add(sub.id)
        seen: Set[str] = set()
        for sub in ast.walk(fn):
            if (isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
                    and sub.id in mutables and sub.id not in local
                    and sub.id not in seen):
                seen.add(sub.id)
                yield ctx.violation(
                    "MUTABLE_CAPTURE", sub,
                    f"jitted `{fn.name}` reads module-level mutable "
                    f"global `{sub.id}`; trace-time capture freezes it")


@rule("DTYPE_DRIFT",
      "Device dtype literal outside the canonical set from "
      "mergetree/constants.py",
      family="jax",
      rationale="The device schema is int32 columns + bool_ masks "
                "(CANONICAL_DEVICE_DTYPES); a stray int64/float literal "
                "silently doubles a column's bytes or forces an x64 "
                "fallback. Deliberate narrow packing (e.g. the int16 wire "
                "result) carries an inline suppression.")
def dtype_drift(ctx: ModuleContext) -> Iterator[Violation]:
    canonical = _canonical_dtypes()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Attribute):
            continue
        if not DTYPE_NAME_RE.match(node.attr):
            continue
        if _dotted(node.value) not in _NUMPY_MODULES:
            continue
        if node.attr in canonical:
            continue
        info = ctx.enclosing_jit(node)
        if info is None:
            continue
        yield ctx.violation(
            "DTYPE_DRIFT", node,
            f"dtype `{_dotted(node.value)}.{node.attr}` in jitted "
            f"`{info.node.name}` drifts from the canonical device dtypes "
            f"({', '.join(sorted(canonical))})")


# Host-callback hazards inside scan/while_loop bodies: the serving
# megakernel (serve_step.serve_megakernel) runs ticket→apply→extract for
# K windows inside ONE lax.scan precisely to remove per-window host
# round-trips — an io_callback/debug.callback re-entering the host per
# scan step (or a block_until_ready forcing a device sync at
# trace/staging time) would silently reintroduce the serialized RPC the
# fusion exists to delete, K times per ring. A pl.pallas_call kernel
# body raises the stakes: it IS the persistent device program — a host
# callback there cannot lower at all on TPU (the fallback would eat the
# whole kernel, silently), and a sync at trace time stalls the one-in-
# flight megakernel ring.
_SCAN_DRIVER_BODY_ARGS = {
    "scan": (0,),          # lax.scan(body, init, xs)
    "while_loop": (0, 1),  # lax.while_loop(cond_fun, body_fun, init)
    "fori_loop": (2,),     # lax.fori_loop(lo, hi, body_fun, init)
    "pallas_call": (0,),   # pl.pallas_call(kernel, out_shape=..., ...)
}

# pallas_call is not a lax symbol; it arrives as pl.pallas_call /
# pallas.pallas_call / fully qualified. Bare "scan" is too generic to
# match unqualified; "pallas_call" is not.
_PALLAS_HEADS = ("pl", "pallas", "jax.experimental.pallas", "")

_HOST_CALLBACK_NAMES = {
    "io_callback", "jax.experimental.io_callback",
    "debug.callback", "jax.debug.callback",
    "host_callback.call", "jax.experimental.host_callback.call",
    "hcb.call", "pure_callback", "jax.pure_callback",
}

# Same operational scope as SPAN_LEAK: the op pipeline's device code.
_SCAN_SCOPE_PREFIXES = (
    "fluidframework_tpu/mergetree", "fluidframework_tpu/server",
    "<memory>")


def _scan_scope(ctx: ModuleContext) -> bool:
    path = ctx.path.replace("\\", "/")
    return any(path.startswith(p) or f"/{p}" in path
               for p in _SCAN_SCOPE_PREFIXES)


def _scan_driver(call: ast.Call) -> Optional[Tuple[str, tuple]]:
    """(driver name, body-arg positions) when `call` is a lax.scan /
    while_loop / fori_loop invocation (plain or jax.lax-qualified)."""
    fn = _dotted(call.func)
    if not fn:
        return None
    head, _, tail = fn.rpartition(".")
    if tail == "pallas_call" and head in _PALLAS_HEADS:
        return tail, _SCAN_DRIVER_BODY_ARGS[tail]
    if tail in _SCAN_DRIVER_BODY_ARGS and head in ("lax", "jax.lax", ""):
        # Bare names ("scan") only count when qualified — too generic
        # otherwise.
        if head or tail in ("while_loop", "fori_loop"):
            return tail, _SCAN_DRIVER_BODY_ARGS[tail]
    return None


def _body_functions(ctx: ModuleContext, call: ast.Call,
                    positions: tuple):
    """Resolve a scan driver call's body argument(s) to AST function
    nodes: inline lambdas directly, Name references to module-level (or
    nested) defs by name, functools.partial(f, ...) through its first
    argument. Unresolvable bodies (imports, attributes) are skipped —
    the rule is single-module by design, like every fluidlint check."""
    by_name: dict = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.FunctionDef):
            by_name.setdefault(node.name, []).append(node)
    exprs = []
    for pos in positions:
        if pos < len(call.args):
            exprs.append(call.args[pos])
    for kw in call.keywords:
        if kw.arg in ("f", "body_fun", "cond_fun", "kernel") \
                and kw.value not in exprs:
            exprs.append(kw.value)
    for expr in exprs:
        if isinstance(expr, ast.Call) and \
                _dotted(expr.func) in ("functools.partial", "partial") \
                and expr.args:
            expr = expr.args[0]
        if isinstance(expr, ast.Lambda):
            yield "<lambda>", expr
        elif isinstance(expr, ast.Name):
            for fn in by_name.get(expr.id, []):
                yield expr.id, fn


def _host_callback_hazards(body: ast.AST):
    for node in ast.walk(body):
        if not isinstance(node, ast.Call):
            continue
        fn = _dotted(node.func)
        if fn in _HOST_CALLBACK_NAMES or \
                fn.rpartition(".")[2] == "io_callback":
            yield node, fn
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "block_until_ready"
              and not node.args):
            yield node, ".block_until_ready()"


@rule("SCAN_HOST_CALLBACK",
      "Host callback / device sync inside a scanned or pallas kernel body",
      family="jax",
      rationale="A scanned body re-entering the host (io_callback, "
                "debug.callback, pure_callback) or forcing a sync "
                "(.block_until_ready()) serializes every scan step on a "
                "host round-trip — exactly the per-window RPC the fused "
                "serving burst exists to remove. Inside a pl.pallas_call "
                "kernel the same constructs cannot lower at all: the "
                "megakernel would silently fall back to the scan path "
                "every ring. Move the host work to the carry/ys "
                "boundary, or keep the value device-side.")
def scan_host_callback(ctx: ModuleContext) -> Iterator[Violation]:
    if not _scan_scope(ctx):
        return
    seen: Set[int] = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        driver = _scan_driver(node)
        if driver is None:
            continue
        name, positions = driver
        for body_name, body in _body_functions(ctx, node, positions):
            for hazard, what in _host_callback_hazards(body):
                key = id(hazard)
                if key in seen:
                    continue
                seen.add(key)
                if name == "pallas_call":
                    yield ctx.violation(
                        "SCAN_HOST_CALLBACK", hazard,
                        f"`{what}` inside `{body_name}`, a "
                        f"`pl.pallas_call` kernel body: the kernel is a "
                        f"persistent device program — host re-entry "
                        f"cannot lower, and a sync stalls the "
                        f"megakernel ring")
                else:
                    yield ctx.violation(
                        "SCAN_HOST_CALLBACK", hazard,
                        f"`{what}` inside `{body_name}`, the body of a "
                        f"`lax.{name}`: every step pays a host "
                        f"round-trip, serializing the scanned program")


# serve/window joined step/apply when the serving program gained
# lane-state donation: the serving megakernel threads the ticket state,
# the page pool and every LWW lane plane per flush, so a dropped
# donation there doubles peak HBM on the hottest path in the system.
# Word-ish anchoring so names that merely CONTAIN a keyword (observe,
# reserved, stepper-adjacent helpers like `misapply`) don't fire.
_STEP_NAME_RE = re.compile(r"(^|_)(step|apply|serve|window)",
                           re.IGNORECASE)


def _threads_state(fn: ast.FunctionDef) -> bool:
    if not fn.args.args:
        return False
    first = fn.args.args[0].arg
    return first == "state" or first.endswith("state")


@rule("MISSING_DONATE",
      "State-threading step/apply function jitted without donate_argnums",
      family="jax",
      rationale="A step function that returns the next state without "
                "donating the previous one doubles peak device memory for "
                "every column it threads. Non-donating variants kept for "
                "retry paths carry an inline suppression explaining why.")
def missing_donate(ctx: ModuleContext) -> Iterator[Violation]:
    for fn, info in ctx.jit_functions.items():
        if info.donate_argnums or info.donate_argnames:
            continue
        if not (_STEP_NAME_RE.search(fn.name) and _threads_state(fn)):
            continue
        yield ctx.violation(
            "MISSING_DONATE", fn,
            f"jitted `{fn.name}` threads `{fn.args.args[0].arg}` but "
            f"declares no donate_argnums; the previous state stays live "
            f"across the step")
    # Call-form jit over a function we could NOT resolve in this module
    # (e.g. jax.jit(full_step) over an import): flag by name pattern.
    resolved = {info.node.name for info in ctx.jit_functions.values()}
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and _dotted(node.func)
                in ("jax.jit", "jit") and node.args):
            continue
        target = node.args[0]
        if not isinstance(target, ast.Name):
            continue
        if target.id in resolved:
            continue
        if any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in node.keywords):
            continue
        if _STEP_NAME_RE.search(target.id):
            yield ctx.violation(
                "MISSING_DONATE", node,
                f"`jax.jit({target.id})` without donate_argnums; if "
                f"`{target.id}` threads state, the previous buffers stay "
                f"live across every step")


# PAGE_ID_DTYPE moved to lifecycle_rules.py in v2: the regex that only
# saw page-NAMED assignments became a dtype lattice propagated through
# astype/asarray/arithmetic by the dataflow pass (analysis/dataflow.py),
# so drift through intermediate bindings is caught too. Rule id,
# scope, and message shape are unchanged.
