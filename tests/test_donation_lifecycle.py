"""fluidlint v2: whole-program donated-buffer lifecycle analysis.

Covers the three layers ISSUE 9 added:

* the cross-module symbol/call graph (analysis/callgraph.py) — jit
  forms (decorator, ``jax.jit(fn)`` assignment, ``functools.partial``
  wrapper), aliases, methods, instance-attribute jit handles, and
  cross-module resolution;
* the dataflow rules — USE_AFTER_DONATE (including the seeded PR 7
  burst-fallback carry-read regression fixture), DONATED_ESCAPE (the
  PR 5 stale-lane-plane shape), and the PAGE_ID_DTYPE v2 lattice;
* the engine's fingerprint cache + --changed-only scoping, with the
  warm-run-faster gate the Makefile's lint-analysis target relies on.

Every rule keeps the house convention: one true-positive fixture per
shape the rule exists for, one false-positive guard per sanctioned
idiom it must stay quiet on.
"""

import ast
import json
import subprocess
import sys
import textwrap
from pathlib import Path

from fluidframework_tpu.analysis import analyze_paths, analyze_source
from fluidframework_tpu.analysis.callgraph import (
    ProgramIndex,
    module_name_for_path,
)
from fluidframework_tpu.analysis.cache import ResultCache

PACKAGE_DIR = Path(__file__).resolve().parents[1] / "fluidframework_tpu"


def lint(src, rule):
    return [v.rule_id for v in
            analyze_source(textwrap.dedent(src), only=[rule])]


def build_index(mods):
    """ProgramIndex over {dotted_module_name: source} fixtures."""
    triples = []
    for name, src in mods.items():
        path = name.replace(".", "/") + ".py"
        triples.append((name, ast.parse(textwrap.dedent(src)), path))
    return ProgramIndex(triples)


def resolve(index, module, call_src, class_name=None):
    call = ast.parse(textwrap.dedent(call_src), mode="eval").body
    assert isinstance(call, ast.Call)
    return index.resolve_call(module, call, class_name=class_name)


# ---------------------------------------------------------------------------
# call graph resolution
# ---------------------------------------------------------------------------

DONATING_MOD = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
    def step(state, ops, fused=False):
        return state

    def raw_apply(state, ops):
        return state

    fast_apply = jax.jit(raw_apply, donate_argnums=(0, 1))
    step_keep = functools.partial(jax.jit, static_argnums=(2,))(
        step.__wrapped__)
    step_alias = step
"""


class TestCallGraphResolution:
    def test_decorated_form(self):
        idx = build_index({"m": DONATING_MOD})
        res = resolve(idx, "m", "step(s, o)")
        assert res is not None and res.qualname == "m:step"
        assert res.donation.positions == {0}
        assert "state" in res.donation.names

    def test_jit_call_assignment_form(self):
        idx = build_index({"m": DONATING_MOD})
        res = resolve(idx, "m", "fast_apply(s, o)")
        assert res is not None and res.decl.name == "raw_apply"
        assert res.donation.positions == {0, 1}

    def test_partial_wrapper_over_wrapped(self):
        """The serve_window_keep shape: a partial(jax.jit, …) wrapper
        over an already-jitted def's __wrapped__, donating LESS than
        the original — the keep variant's whole point."""
        idx = build_index({"m": DONATING_MOD})
        res = resolve(idx, "m", "step_keep(s, o)")
        assert res is not None and res.decl.name == "step"
        assert res.donation is None  # keep variant: no donation

    def test_alias_form(self):
        idx = build_index({"m": DONATING_MOD})
        res = resolve(idx, "m", "step_alias(s, o)")
        assert res is not None and res.qualname == "m:step"
        assert res.donation.positions == {0}

    def test_method_form_binds_self(self):
        idx = build_index({"m": """
            import functools
            import jax

            class Seq:
                @functools.partial(jax.jit, donate_argnums=(1,))
                def advance(self, state, ops):
                    return state
        """})
        res = resolve(idx, "m", "self.advance(s, o)", class_name="Seq")
        assert res is not None and res.qualname == "m:Seq.advance"
        assert res.bound_self
        # donated param 1 is `state` — the FIRST call argument once
        # self is bound, which donated_args must honor.
        call = ast.parse("self.advance(s, o)", mode="eval").body
        args = res.donation.donated_args(call, bound_self=True)
        assert [a.id for a in args] == ["s"]

    def test_instance_attr_jit_handle(self):
        """server/bridge.py's `self._step = jax.jit(full_step,
        donate_argnums=(0, 1))` in __init__, invoked as self._step(…)."""
        idx = build_index({"m": """
            import jax

            def full_step(a, b):
                return a, b

            class Bridge:
                def __init__(self):
                    self._step = jax.jit(full_step, donate_argnums=(0, 1))

                def run(self, a, b):
                    return self._step(a, b)
        """})
        res = resolve(idx, "m", "self._step(a, b)", class_name="Bridge")
        assert res is not None
        assert res.donation.positions == {0, 1}

    def test_cross_module_from_import(self):
        idx = build_index({
            "pkg.kernel": DONATING_MOD,
            "pkg.host": """
                from pkg.kernel import step

                def run(s, o):
                    return step(s, o)
            """,
        })
        res = resolve(idx, "pkg.host", "step(s, o)")
        assert res is not None and res.qualname == "pkg.kernel:step"
        assert res.donation.positions == {0}

    def test_cross_module_relative_module_import(self):
        """`from . import serve_step` + `serve_step.serve_window(…)` —
        the tpu_sequencer call shape, including the import living
        INSIDE a function body."""
        idx = build_index({
            "pkg.serve_step": DONATING_MOD,
            "pkg.sequencer": """
                def dispatch(s, o):
                    from . import serve_step
                    return serve_step.step(s, o)
            """,
        })
        res = resolve(idx, "pkg.sequencer", "serve_step.step(s, o)")
        assert res is not None and res.qualname == "pkg.serve_step:step"
        assert res.donation.positions == {0}

    def test_call_edges(self):
        idx = build_index({
            "pkg.kernel": DONATING_MOD,
            "pkg.host": """
                from pkg.kernel import step

                def run(s, o):
                    return step(s, o)
            """,
        })
        edges = idx.call_edges("pkg.host")
        assert ("pkg.host:run", "pkg.kernel:step") in edges

    def test_real_tree_resolves_serve_burst_donation(self):
        """The live contract: from tpu_sequencer, `serve_step.
        serve_megakernel(…)` must resolve to the partial-jit wrapper
        over _serve_megakernel with donate_argnums=(0, 1, 2) — this is
        the signature every lifecycle finding in the serving path hangs
        off, so its resolution is pinned against the real tree."""
        triples = []
        for rel in ("server/serve_step.py", "server/tpu_sequencer.py"):
            p = PACKAGE_DIR / rel
            name = module_name_for_path("fluidframework_tpu/" + rel)
            triples.append((name, ast.parse(p.read_text()), str(p)))
        idx = ProgramIndex(triples)
        call = "(a, b, c, d, e, f, g, h, i, j, k, l, m)"
        res = resolve(idx, "fluidframework_tpu.server.tpu_sequencer",
                      "serve_step.serve_megakernel" + call)
        assert res is not None
        assert res.decl.name == "_serve_megakernel"
        assert res.donation.positions == {0, 1, 2}
        keep = resolve(idx, "fluidframework_tpu.server.tpu_sequencer",
                       "serve_step.serve_megakernel_keep" + call)
        assert keep is not None and keep.decl.name == "_serve_megakernel"
        # Mesh pools donate nothing.
        assert keep.donation is None or not keep.donation.positions


# ---------------------------------------------------------------------------
# USE_AFTER_DONATE
# ---------------------------------------------------------------------------

class TestUseAfterDonate:
    def test_true_positive_direct_read_after_donate(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops):
                out = step(state, ops)
                return state.sum() + out
        """
        assert lint(src, "USE_AFTER_DONATE") == ["USE_AFTER_DONATE"]

    def test_true_positive_alias_read(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops):
                backup = state
                out = step(state, ops)
                return backup
        """
        assert lint(src, "USE_AFTER_DONATE") == ["USE_AFTER_DONATE"]

    def test_true_positive_carry_leaf_read(self):
        """Pytree-carry leaves die with the carry: unpacked members of
        a donated composite are aliases of it."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def burst(carry, xs):
                return carry, xs

            def flush(carry, xs):
                tstate, lanes = carry
                out, ys = burst(carry, xs)
                return lanes
        """
        assert lint(src, "USE_AFTER_DONATE") == ["USE_AFTER_DONATE"]

    def test_regression_pr7_burst_fallback_carry_read(self):
        """The seeded PR 7 shape: a fused-burst dispatch fails AFTER
        lowering, the except handler falls back by re-dispatching from
        the donated scan carry — reading buffers the failed scan may
        already have consumed. The fix (shipped in PR 7's review) was
        to probe liveness and re-raise; the rule now proves the bug
        class can't come back."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def serve_burst(tstate, merge_states, lww_states, xs):
                return tstate, merge_states, lww_states, xs

            class Seq:
                def dispatch_burst(self, tstate, merge_states,
                                   lww_states, xs):
                    try:
                        (tstate, new_m, new_l, ys) = serve_burst(
                            tstate, tuple(merge_states),
                            tuple(lww_states), xs)
                    except Exception:
                        # BUG: the carry was donated; falling back onto
                        # it reads freed device memory.
                        return self._per_window(tstate, merge_states,
                                                lww_states, xs)
                    return ys
        """
        hits = lint(src, "USE_AFTER_DONATE")
        assert hits == ["USE_AFTER_DONATE"] * 3  # all three carry legs

    def test_true_positive_carry_packed_inside_try(self):
        """The carry may be PACKED inside the try whose handler falls
        back onto it — the binding never existed at try entry and was
        rebound after the donation, yet the handler still reads the
        donated buffer at its arbitrary raise point."""
        src = """
            import functools, jax
            import numpy as np

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(carry, xs):
                return carry

            def flush(xs):
                try:
                    carry = pack(xs)
                    carry = step(carry, xs)
                except Exception:
                    return np.asarray(carry)
                return carry
        """
        assert lint(src, "USE_AFTER_DONATE") == ["USE_AFTER_DONATE"]

    def test_true_positive_branch_kill_does_not_hide_read(self):
        """A rebind on ONE branch must not hide the donated read on the
        path where that branch was not taken."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops, cond):
                out = step(state, ops)
                if cond:
                    state = make()
                return state.sum()
        """
        assert lint(src, "USE_AFTER_DONATE") == ["USE_AFTER_DONATE"]

    def test_guard_conditional_dispatch_and_rebind(self):
        """`if c: state = step(state, x)` donates AND rebinds on the
        same branch — the other path never donated, so the later read
        is clean."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops, cond):
                if cond:
                    state = step(state, ops)
                return state.sum()
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_guard_same_statement_rebind(self):
        """The canonical `state, ys = step(state, xs)` threading: the
        donation and the rebind are one statement — clean."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            class Seq:
                def flush(self, ops):
                    self.tstate = step(self.tstate, ops)
                    return self.tstate
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_guard_keep_variant_retains_rollback_states(self):
        """serve_window_keep's contract: the partial wrapper donates
        only the ticket state, so the rollback path's reads of the
        retained lane states are sanctioned BY SIGNATURE, not by
        suppression."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0, 2))
            def serve(tstate, cols, states):
                return tstate, states

            serve_keep = functools.partial(jax.jit, donate_argnums=(0,))(
                serve.__wrapped__)

            def recover(tstate, cols, states):
                tstate2, out = serve_keep(tstate, cols, states)
                return states  # retained by the keep variant: fine
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_guard_liveness_probe_then_reraise(self):
        """The sanctioned burst-fallback idiom: metadata-only probes
        (tree_leaves / .is_deleted()) of the donated carry, including
        through map(probe, xs), then re-raise."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def burst(carry, states, xs):
                return carry, states, xs

            def _gone(tree):
                leaf = jax.tree_util.tree_leaves(tree)
                return bool(leaf) and bool(leaf[0].is_deleted())

            def dispatch(carry, states, xs):
                try:
                    carry, states, ys = burst(carry, tuple(states), xs)
                except Exception:
                    if _gone(carry) or any(map(_gone, states)):
                        raise
                    return None
                return ys
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_guard_metadata_reads(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops):
                out = step(state, ops)
                if state is None:
                    return out
                return (out, len(ops), state.shape)
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_guard_branch_rebind_then_read(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops):
                state = step(state, ops)
                return state
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_guard_jitted_body_exempt(self):
        """Inside a traced body a nested donating call is a no-op for
        jax — donation is a call-boundary effect only."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def inner(state, ops):
                return state

            @jax.jit
            def outer(state, ops):
                out = inner(state, ops)
                return out + state
        """
        assert lint(src, "USE_AFTER_DONATE") == []

    def test_out_of_scope_module_is_quiet(self):
        src = textwrap.dedent("""
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            def flush(state, ops):
                out = step(state, ops)
                return state
        """)
        hits = analyze_source(src, path="examples/clicker.py",
                              only=["USE_AFTER_DONATE"])
        assert hits == []


# ---------------------------------------------------------------------------
# DONATED_ESCAPE
# ---------------------------------------------------------------------------

class TestDonatedEscape:
    def test_true_positive_stored_then_donated(self):
        """The PR 5 stale-lane-plane shape: an instance attribute keeps
        pointing at a plane the dispatch later donates."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            class Seq:
                def flush(self, state, ops):
                    self.lane_plane = state
                    out = step(state, ops)
                    return out
        """
        assert lint(src, "DONATED_ESCAPE") == ["DONATED_ESCAPE"]

    def test_true_positive_donated_then_stored(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            class Seq:
                def flush(self, state, ops):
                    out = step(state, ops)
                    self.lane_plane = state
                    return out
        """
        assert lint(src, "DONATED_ESCAPE") == ["DONATED_ESCAPE"]

    def test_guard_store_overwritten_before_exit(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            class Seq:
                def flush(self, state, ops):
                    self.lane_plane = state
                    out = step(state, ops)
                    self.lane_plane = out
                    return out
        """
        assert lint(src, "DONATED_ESCAPE") == []

    def test_guard_attr_donate_and_rebind(self):
        """Passing self.X straight into the donating call and rebinding
        it from the result is THE canonical serving pattern."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            class Seq:
                def flush(self, ops):
                    self.tstate = step(self.tstate, ops)
        """
        assert lint(src, "DONATED_ESCAPE") == []

    def test_guard_stores_fresh_result(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(state, ops):
                return state

            class Seq:
                def flush(self, state, ops):
                    self.lane_plane = step(state, ops)
        """
        assert lint(src, "DONATED_ESCAPE") == []


# ---------------------------------------------------------------------------
# PAGE_ID_DTYPE v2 — the lattice beyond the old regex
# ---------------------------------------------------------------------------

class TestPageIdDtypeLattice:
    def test_propagates_through_intermediate_binding(self):
        """The v1 regex only saw page-NAMED assignment targets; v2
        follows the dtype through a neutrally-named intermediate."""
        src = """
            import numpy as np

            def stage(table):
                wide = np.asarray(table, np.int64)
                page_ids = wide
                return page_ids
        """
        assert lint(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_propagates_through_arithmetic(self):
        src = """
            import numpy as np

            def stage(table, base):
                offs = np.asarray(table, np.int64)
                page_ids = offs + base
                return page_ids
        """
        assert lint(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_kernel_operand_via_lattice(self):
        """A neutrally-named binding with a bad inferred dtype handed
        to the gather/scatter surface: invisible to v1, caught by v2."""
        src = """
            import numpy as np
            from fluidframework_tpu.mergetree import kernel

            def stage(pool, table, counts, mins, seqs):
                ids = np.asarray(table, np.int64)
                return kernel.gather_pages(pool, ids, counts, mins, seqs)
        """
        assert lint(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_guard_int32_propagation_stays_quiet(self):
        src = """
            import numpy as np
            import jax.numpy as jnp
            from fluidframework_tpu.mergetree import kernel

            def stage(pool, table, counts, mins, seqs):
                ids = np.asarray(table, np.int32)
                pids = jnp.asarray(ids)
                view = kernel.gather_pages(pool, pids, counts, mins,
                                           seqs)
                return view
        """
        assert lint(src, "PAGE_ID_DTYPE") == []

    def test_guard_unrelated_wide_dtype_quiet(self):
        src = """
            import numpy as np

            def hints(lanes):
                seq_hint = np.zeros(lanes, np.int64)
                total = seq_hint + 1
                return total
        """
        assert lint(src, "PAGE_ID_DTYPE") == []


# ---------------------------------------------------------------------------
# engine: cache + restrict
# ---------------------------------------------------------------------------

DONOR_SRC = """
import functools, jax

@functools.partial(jax.jit, donate_argnums=(0,))
def step(state, ops):
    return state
"""

CALLER_SRC = """
from .donor import step

def flush(state, ops):
    out = step(state, ops)
    return state
"""


class TestResultCache:
    def _write_pkg(self, tmp_path):
        pkg = tmp_path / "fluidframework_tpu" / "server"
        pkg.mkdir(parents=True)
        (pkg / "donor.py").write_text(DONOR_SRC)
        (pkg / "caller.py").write_text(CALLER_SRC)
        return pkg

    def test_warm_run_hits_and_matches_cold(self, tmp_path):
        pkg = self._write_pkg(tmp_path)
        cache = ResultCache(tmp_path / "cache.json")
        cold = analyze_paths([str(pkg)], cache=cache)
        assert cold.cache_hits == 0 and cold.cache_misses == 2
        warm_cache = ResultCache(tmp_path / "cache.json")
        warm = analyze_paths([str(pkg)], cache=warm_cache)
        assert warm.cache_hits == 2 and warm.cache_misses == 0
        assert [v.fingerprint for v in warm.violations] == \
            [v.fingerprint for v in cold.violations]

    def test_source_edit_invalidates_only_that_module(self, tmp_path):
        pkg = self._write_pkg(tmp_path)
        cache = ResultCache(tmp_path / "cache.json")
        analyze_paths([str(pkg)], cache=cache)
        (pkg / "caller.py").write_text(CALLER_SRC + "\nX = 1\n")
        warm = analyze_paths([str(pkg)],
                             cache=ResultCache(tmp_path / "cache.json"))
        assert warm.cache_hits == 1 and warm.cache_misses == 1

    def test_signature_edit_invalidates_every_module(self, tmp_path):
        """Editing donate_argnums in donor.py must re-analyze caller.py
        too — its cached result hangs off donor's interface. This is
        the whole-program twist a plain per-file mtime cache gets
        wrong, and the caller's finding set really does change."""
        pkg = self._write_pkg(tmp_path)
        cold = analyze_paths([str(pkg)],
                             cache=ResultCache(tmp_path / "cache.json"))
        assert [v.rule_id for v in cold.violations] == \
            ["USE_AFTER_DONATE"]
        (pkg / "donor.py").write_text(
            DONOR_SRC.replace("donate_argnums=(0,)",
                              "donate_argnums=(1,)"))
        warm = analyze_paths([str(pkg)],
                             cache=ResultCache(tmp_path / "cache.json"))
        assert warm.cache_misses == 2  # interface change: nothing hits
        assert warm.violations == []   # state no longer donated

    def test_restrict_scopes_reporting_not_the_program(self, tmp_path):
        """--changed-only's engine half: only restricted files report,
        but the donation signature still resolves from the unrestricted
        module set."""
        pkg = self._write_pkg(tmp_path)
        rel_caller = str((pkg / "caller.py").resolve())
        from fluidframework_tpu.analysis.engine import _rel_path
        restrict = {_rel_path(Path(rel_caller))}
        result = analyze_paths([str(pkg)], restrict=restrict)
        assert result.files == 1
        assert [v.rule_id for v in result.violations] == \
            ["USE_AFTER_DONATE"]

    def test_cached_full_package_run_is_faster(self, tmp_path):
        """The make lint-analysis acceptance gate: a second (cached)
        run over the real package completes measurably faster than the
        cold run, and the stamped stats prove the cache did it."""
        cache_path = tmp_path / "cache.json"
        cold = analyze_paths([str(PACKAGE_DIR)],
                             cache=ResultCache(cache_path))
        warm = analyze_paths([str(PACKAGE_DIR)],
                             cache=ResultCache(cache_path))
        assert warm.cache_hits == warm.files and warm.cache_misses == 0
        assert warm.violations == cold.violations
        assert warm.wall_ms < cold.wall_ms, (
            f"cached run not faster: {warm.wall_ms:.0f}ms vs cold "
            f"{cold.wall_ms:.0f}ms")


class TestChangedOnlyCli:
    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "fluidframework_tpu.analysis", *args],
            capture_output=True, text=True,
            cwd=str(PACKAGE_DIR.parent))

    def test_changed_only_runs_clean(self, tmp_path):
        """On any tree state, --changed-only must terminate with a
        parseable summary and a gate-shaped exit code (0 here: the
        working tree carries no unbaselined violations)."""
        proc = self.run_cli("--changed-only", "--cache-file",
                            str(tmp_path / "c.json"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert set(last) == {"violations", "baselined"}
        assert last["violations"] == 0

    def test_bench_json_record(self, tmp_path):
        out = tmp_path / "lint_bench.json"
        proc = self.run_cli(str(PACKAGE_DIR / "analysis"),
                            "--cache-file", str(tmp_path / "c.json"),
                            "--bench-json", str(out))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        rec = json.loads(out.read_text())
        assert rec["unit"] == "ms" and rec["wall_ms"] > 0
        assert rec["files"] > 0
        assert {"cache_hits", "cache_misses", "violations",
                "baselined"} <= set(rec)
