"""fluidlint v3: whole-program lockset race detection.

Covers the layers ISSUE 11 added:

* the concurrency model (analysis/concurrency_model.py) — thread-root
  discovery in every spawn form (Thread with lambda/partial/bound
  method targets, executor submit / run_in_executor, HTTP handler
  entry points, pump subscribe callbacks), lock discovery and held-set
  tracking (with blocks, acquire/release incl. try/finally and the
  non-blocking-acquire idiom), transitive held-lockset inheritance,
  and guarded-by annotations;
* the four rule families (analysis/race_rules.py) —
  SHARED_STATE_NO_LOCK, ATOMICITY_CHECK_THEN_ACT,
  LOCK_ORDER_INVERSION (both-orders requirement), SIGNAL_WITHOUT_LOCK;
* the runtime verifier (testing/lockcheck.py) — including catching at
  runtime a violation the static pass was suppressed on;
* the seeded ring-entry regression fixture
  (tests/fixtures/race_ring_entry.py), pinned must-fire;
* engine integration — the whole-tree gate (0 unbaselined findings),
  --changed-only reach expansion, and the race_rules_wall_ms stamp.

House convention: one true-positive fixture per shape the rule exists
for, one false-positive guard per sanctioned idiom it must stay quiet
on.
"""

import textwrap
import threading
from pathlib import Path

import pytest

from fluidframework_tpu.analysis import analyze_paths, analyze_source

PACKAGE_DIR = Path(__file__).resolve().parents[1] / "fluidframework_tpu"
FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "race_ring_entry.py"

RACE_RULES = ["SHARED_STATE_NO_LOCK", "ATOMICITY_CHECK_THEN_ACT",
              "LOCK_ORDER_INVERSION", "SIGNAL_WITHOUT_LOCK"]


def lint(src, rule):
    return [v.rule_id for v in
            analyze_source(textwrap.dedent(src), only=[rule])]


def findings(src, rule):
    return [v for v in analyze_source(textwrap.dedent(src), only=[rule])]


# ---------------------------------------------------------------------------
# SHARED_STATE_NO_LOCK
# ---------------------------------------------------------------------------

class TestSharedStateNoLock:
    def test_true_positive_unguarded_cross_thread_attr(self):
        vs = findings("""
            import threading

            class Seq:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    self.items.append(1)

                def read(self):
                    return list(self.items)
        """, "SHARED_STATE_NO_LOCK")
        assert {v.rule_id for v in vs} == {"SHARED_STATE_NO_LOCK"}
        # one site per accessing function: the thread write + main read
        assert {v.symbol for v in vs} == {"Seq._drain", "Seq.read"}

    def test_guard_both_sides_locked(self):
        assert lint("""
            import threading

            class Seq:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    with self._lock:
                        self.items.append(1)

                def read(self):
                    with self._lock:
                        return list(self.items)
        """, "SHARED_STATE_NO_LOCK") == []

    def test_guard_no_thread_no_sharing(self):
        """Single-threaded classes never fire, however unguarded."""
        assert lint("""
            class Seq:
                def __init__(self):
                    self.items = []

                def push(self):
                    self.items.append(1)

                def read(self):
                    return list(self.items)
        """, "SHARED_STATE_NO_LOCK") == []

    def test_guard_init_writes_are_setup_not_races(self):
        """__init__ construction happens-before publication; writes
        there must not poison the intersection."""
        assert lint("""
            import threading

            class Seq:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []
                    self.items.append(0)   # setup, unguarded, fine

                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    with self._lock:
                        self.items.append(1)

                def read(self):
                    with self._lock:
                        return list(self.items)
        """, "SHARED_STATE_NO_LOCK") == []

    def test_wrong_lock_still_fires(self):
        """Every access locked, but not by a COMMON lock — the
        intersection is empty and the hint names the majority lock."""
        vs = findings("""
            import threading

            class Seq:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.items = []

                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    with self._a:
                        self.items.append(1)

                def also_drain(self):
                    with self._a:
                        self.items.append(2)

                def read(self):
                    with self._b:
                        return list(self.items)
        """, "SHARED_STATE_NO_LOCK")
        assert vs and all("Seq._a" in v.message for v in vs)
        assert {v.symbol for v in vs} == {"Seq.read"}

    def test_guarded_by_annotation_trusted(self):
        """# fluidlint: guarded-by=<attr> adds the named lock to the
        access's lockset — the runtime verifier's job to keep honest."""
        assert lint("""
            import threading

            class Seq:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    with self._lock:
                        self.items.append(1)

                def read_locked_by_caller(self):
                    return list(self.items)  # fluidlint: guarded-by=_lock
        """, "SHARED_STATE_NO_LOCK") == []

    def test_suppressed_access_leaves_the_pair(self):
        """A disable= on the cross-thread access declares it safe: the
        attr stops being shared, so OTHER accessors stay quiet instead
        of inheriting an empty intersection (the sanctioned
        racy-by-design probe pattern — e.g. monotonic stat reads)."""
        assert lint("""
            import threading

            class Seq:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stat = 0

                def start(self):
                    threading.Thread(target=self._drain).start()

                def _drain(self):
                    # fluidlint: disable=SHARED_STATE_NO_LOCK — monotonic
                    # stat bump; readers tolerate any interleaving
                    self.stat += 1

                def read(self):
                    return self.stat
        """, "SHARED_STATE_NO_LOCK") == []

    def test_module_level_lock_and_global(self):
        assert lint("""
            import threading

            _lock = threading.Lock()
            _counters = {}

            def start():
                threading.Thread(target=_bump).start()

            def _bump():
                with _lock:
                    _counters["n"] = _counters.get("n", 0) + 1

            def snapshot():
                with _lock:
                    return dict(_counters)
        """, "SHARED_STATE_NO_LOCK") == []
        vs = findings("""
            import threading

            _lock = threading.Lock()
            _counters = {}

            def start():
                threading.Thread(target=_bump).start()

            def _bump():
                _counters["n"] = 1

            def snapshot():
                with _lock:
                    return dict(_counters)
        """, "SHARED_STATE_NO_LOCK")
        assert vs and "_counters" in vs[0].message


# ---------------------------------------------------------------------------
# thread-root discovery forms
# ---------------------------------------------------------------------------

_ROOT_TEMPLATE = """
    import threading
    from functools import partial

    class S:
        def __init__(self, executor=None, loop=None, log=None):
            self.n = 0
            self.executor = executor
            self.loop = loop
            self.log = log

        def start(self):
            {spawn}

        def _bump(self{extra}):
            self.n += 1

        def read(self):
            return self.n
"""


def _root_fixture(spawn, extra=""):
    return _ROOT_TEMPLATE.format(spawn=spawn, extra=extra)


class TestThreadRootDiscovery:
    @pytest.mark.parametrize("spawn,extra", [
        ("threading.Thread(target=self._bump).start()", ""),
        ("threading.Thread(target=lambda: self._bump()).start()", ""),
        ("threading.Thread(target=partial(self._bump, 1)).start()",
         ", k"),
        ("self.executor.submit(self._bump)", ""),
        ("self.loop.run_in_executor(None, self._bump)", ""),
        ("self.log.subscribe('raw', 0, self._bump)", ""),
    ], ids=["bound-method", "lambda", "partial", "executor-submit",
            "run-in-executor", "subscribe"])
    def test_spawn_form_discovered(self, spawn, extra):
        src = _root_fixture(spawn, extra)
        assert "SHARED_STATE_NO_LOCK" in lint(src,
                                              "SHARED_STATE_NO_LOCK")

    def test_local_def_target(self):
        """The tpu_sequencer fetch-closure form: a nested def handed to
        Thread(target=...) is its own root."""
        vs = findings("""
            import threading

            class S:
                def __init__(self):
                    self.results = {}

                def dispatch(self, wid, dev):
                    def fetch():
                        self.results[wid] = dev

                    threading.Thread(target=fetch, daemon=True).start()

                def drain(self):
                    return dict(self.results)
        """, "SHARED_STATE_NO_LOCK")
        assert vs and "S.results" in vs[0].message

    def test_http_handler_entry_point(self):
        vs = findings("""
            import threading
            from http.server import BaseHTTPRequestHandler

            class Svc:
                def __init__(self):
                    self.probes = {}
                    service = self

                    class Handler(BaseHTTPRequestHandler):
                        def do_GET(self):
                            service._route(self)

                def add_probe(self, name, fn):
                    self.probes[name] = fn

                def _route(self, handler):
                    for name in self.probes:
                        pass
        """, "SHARED_STATE_NO_LOCK")
        assert vs and any("http:" in v.message for v in vs)

    def test_unresolvable_target_models_no_effect(self):
        """serve_forever on an attribute with no type binding: quiet —
        the conservative bargain every fluidlint layer makes."""
        assert lint("""
            import threading

            class S:
                def __init__(self, httpd):
                    self._httpd = httpd
                    self.n = 0

                def start(self):
                    threading.Thread(
                        target=self._httpd.serve_forever).start()

                def bump(self):
                    self.n += 1
        """, "SHARED_STATE_NO_LOCK") == []


# ---------------------------------------------------------------------------
# held-lockset mechanics
# ---------------------------------------------------------------------------

class TestHeldLocksets:
    def test_transitive_callee_inherits_callers_lock(self):
        assert lint("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def start(self):
                    threading.Thread(target=self.worker).start()

                def worker(self):
                    with self._lock:
                        self._bump()

                def _bump(self):
                    self.n += 1

                def read(self):
                    with self._lock:
                        return self.n
        """, "SHARED_STATE_NO_LOCK") == []

    def test_helper_called_locked_and_unlocked_fires(self):
        """Inheritance is a MEET over call contexts: one unlocked
        caller breaks the helper's inherited lockset."""
        vs = findings("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def start(self):
                    threading.Thread(target=self.worker).start()

                def worker(self):
                    with self._lock:
                        self._bump()

                def sloppy(self):
                    self._bump()

                def _bump(self):
                    self.n += 1

                def read(self):
                    with self._lock:
                        return self.n
        """, "SHARED_STATE_NO_LOCK")
        assert vs and vs[0].symbol == "S._bump"

    def test_try_finally_acquire_release(self):
        assert lint("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def start(self):
                    threading.Thread(target=self.worker).start()

                def worker(self):
                    self._lock.acquire()
                    try:
                        self.n += 1
                    finally:
                        self._lock.release()

                def read(self):
                    if not self._lock.acquire(blocking=False):
                        return 0
                    try:
                        return self.n
                    finally:
                        self._lock.release()
        """, "SHARED_STATE_NO_LOCK") == []

    def test_release_before_access_fires(self):
        vs = findings("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def start(self):
                    threading.Thread(target=self.worker).start()

                def worker(self):
                    self._lock.acquire()
                    self._lock.release()
                    self.n += 1

                def read(self):
                    with self._lock:
                        return self.n
        """, "SHARED_STATE_NO_LOCK")
        assert vs and vs[0].symbol == "S.worker"

    def test_lock_through_typed_attr_chain(self):
        """self.store._lock resolves through the instance-attr type
        binding (the `self.merge = MergeLaneStore(...)` shape)."""
        assert lint("""
            import threading

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.rows = []

                def add(self):
                    with self._lock:
                        self.rows.append(1)

            class Seq:
                def __init__(self):
                    self.store = Store()

                def start(self):
                    threading.Thread(target=self.worker).start()

                def worker(self):
                    with self.store._lock:
                        self.store.rows.append(2)
        """, "SHARED_STATE_NO_LOCK") == []


# ---------------------------------------------------------------------------
# ATOMICITY_CHECK_THEN_ACT
# ---------------------------------------------------------------------------

_ATOM_PREAMBLE = """
    import threading

    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self.pending = []

        def start(self):
            threading.Thread(target=self.worker).start()

        def worker(self):
            with self._lock:
                self.pending.append(1)
"""


class TestAtomicityCheckThenAct:
    def test_true_positive_unlocked_test_locked_act(self):
        vs = findings(_ATOM_PREAMBLE + """
        def take(self):
            if self.pending:
                with self._lock:
                    return self.pending.pop()
        """, "ATOMICITY_CHECK_THEN_ACT")
        assert [v.rule_id for v in vs] == ["ATOMICITY_CHECK_THEN_ACT"]
        assert "not the test" in vs[0].message

    def test_true_positive_two_acquisitions(self):
        vs = findings(_ATOM_PREAMBLE + """
        def take(self):
            self._lock.acquire()
            if self.pending:
                self._lock.release()
                self._lock.acquire()
                self.pending.pop()
            self._lock.release()
        """, "ATOMICITY_CHECK_THEN_ACT")
        assert [v.rule_id for v in vs] == ["ATOMICITY_CHECK_THEN_ACT"]
        assert "two separate acquisitions" in vs[0].message

    def test_guard_one_critical_section(self):
        assert lint(_ATOM_PREAMBLE + """
        def take(self):
            with self._lock:
                if self.pending:
                    return self.pending.pop()
        """, "ATOMICITY_CHECK_THEN_ACT") == []

    def test_guard_lock_inherited_from_caller(self):
        assert lint(_ATOM_PREAMBLE + """
        def take(self):
            with self._lock:
                self._take_locked()

        def _take_locked(self):
            if self.pending:
                self.pending.pop()
        """, "ATOMICITY_CHECK_THEN_ACT") == []

    def test_guard_unshared_attr_quiet(self):
        """No cross-thread sharing: the pattern is single-threaded
        and must not fire."""
        assert lint("""
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.pending = []

                def take(self):
                    if self.pending:
                        with self._lock:
                            return self.pending.pop()
        """, "ATOMICITY_CHECK_THEN_ACT") == []


# ---------------------------------------------------------------------------
# LOCK_ORDER_INVERSION
# ---------------------------------------------------------------------------

class TestLockOrderInversion:
    def test_true_positive_both_orders(self):
        vs = findings("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, "LOCK_ORDER_INVERSION")
        assert len(vs) == 2  # one finding per direction
        assert {v.symbol for v in vs} == {"S.one", "S.two"}

    def test_guard_single_order_never_fires(self):
        """The both-orders requirement: nesting A->B everywhere is a
        discipline, not a deadlock."""
        assert lint("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """, "LOCK_ORDER_INVERSION") == []

    def test_inversion_through_transitive_held_set(self):
        """Caller holds A, callee acquires B; elsewhere B then A — the
        cross-function deadlock shape."""
        vs = findings("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        self._inner()

                def _inner(self):
                    with self._b:
                        pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, "LOCK_ORDER_INVERSION")
        assert len(vs) == 2

    def test_inversion_two_levels_below_mixed_context_caller(self):
        """The may-held set propagates TRANSITIVELY: an unlocked second
        caller of the helper empties its must-inheritance, but the
        A-held path still reaches the B acquisition two call levels
        down — the pair must still form."""
        vs = findings("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        self._helper()

                def unlocked(self):
                    self._helper()   # empties helper's MUST set

                def _helper(self):
                    self._mid()

                def _mid(self):
                    with self._b:
                        pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, "LOCK_ORDER_INVERSION")
        assert len(vs) == 2


# ---------------------------------------------------------------------------
# SIGNAL_WITHOUT_LOCK
# ---------------------------------------------------------------------------

class TestSignalWithoutLock:
    def test_true_positive_notify_outside_lock(self):
        vs = findings("""
            import threading

            class W:
                def __init__(self):
                    self._cv = threading.Condition()

                def kick(self):
                    self._cv.notify()
        """, "SIGNAL_WITHOUT_LOCK")
        assert [v.rule_id for v in vs] == ["SIGNAL_WITHOUT_LOCK"]

    def test_guard_with_condition_held(self):
        assert lint("""
            import threading

            class W:
                def __init__(self):
                    self._cv = threading.Condition()

                def kick(self):
                    with self._cv:
                        self._cv.notify_all()

                def park(self):
                    with self._cv:
                        self._cv.wait()
        """, "SIGNAL_WITHOUT_LOCK") == []

    def test_guard_owning_lock_held(self):
        """Condition(self._lock): holding the owning lock sanctions the
        signal even without entering the condition itself."""
        assert lint("""
            import threading

            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)

                def kick(self):
                    with self._lock:
                        self._cv.notify()
        """, "SIGNAL_WITHOUT_LOCK") == []

    def test_wait_outside_lock_fires(self):
        vs = findings("""
            import threading

            class W:
                def __init__(self):
                    self._cv = threading.Condition()

                def park(self):
                    self._cv.wait()
        """, "SIGNAL_WITHOUT_LOCK")
        assert vs and "wait" in vs[0].message


# ---------------------------------------------------------------------------
# the seeded regression fixture
# ---------------------------------------------------------------------------

class TestSeededRingFixture:
    def test_ring_entry_fixture_must_fire(self):
        """The PR 5 quarantine-fixup shape with _guard_lock removed,
        committed under tests/fixtures — the rule can never regress to
        vacuous while this pin holds."""
        src = FIXTURE.read_text()
        vs = [v for v in analyze_source(src,
                                        only=["SHARED_STATE_NO_LOCK"])]
        assert vs, "seeded ring-entry fixture no longer fires"
        attrs = {v.message.split("`")[1] for v in vs}
        # the fetch thread's direct ring mutations are all caught
        assert "RingSequencer.ring_entries" in attrs
        assert "RingSequencer._pending_windows" in attrs
        assert "RingSequencer.fetch_errors" in attrs
        # and the root is the daemon fetch closure, not main
        assert any("dispatch_window.fetch" in v.message for v in vs)


# ---------------------------------------------------------------------------
# runtime lockcheck
# ---------------------------------------------------------------------------

class TestRuntimeLockcheck:
    def _store_cls(self):
        class Store:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def good(self):
                with self._lock:
                    self.items.append(1)

            def bad(self):
                self.items.append(2)

        return Store

    def test_records_unguarded_access_with_thread(self):
        from fluidframework_tpu.testing.lockcheck import (
            LockDisciplineError, instrument)
        s = self._store_cls()()
        check = instrument(s, {"items": "_lock"})
        try:
            s.good()
            assert check.violations == []
            t = threading.Thread(target=s.bad, name="drain")
            t.start()
            t.join()
            assert len(check.violations) == 1
            v = check.violations[0]
            assert (v.attr, v.lock, v.thread) == ("items", "_lock",
                                                  "drain")
            with pytest.raises(LockDisciplineError):
                check.assert_clean()
        finally:
            check.uninstrument()
        # uninstrumented: no further recording
        s.bad()
        assert len(check.violations) == 1

    def test_strict_mode_raises_at_the_access(self):
        from fluidframework_tpu.testing.lockcheck import (
            LockDisciplineError, instrument)
        s = self._store_cls()()
        check = instrument(s, {"items": "_lock"}, strict=True)
        try:
            with pytest.raises(LockDisciplineError):
                s.bad()
        finally:
            check.uninstrument()

    def test_catches_violation_the_static_pass_was_suppressed_on(self):
        """The model-and-code-can't-drift pairing: a disable= makes the
        static pass quiet, but the runtime wrap still catches the
        unguarded access when the code actually runs."""
        src = """
            import threading

            class Stats:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.counts = {}

                def start(self):
                    threading.Thread(target=self._bump).start()

                def _bump(self):
                    # fluidlint: disable=SHARED_STATE_NO_LOCK — claimed
                    # monotonic; lockcheck keeps this claim honest
                    self.counts["n"] = 1

                def read(self):
                    with self._lock:
                        return dict(self.counts)
        """
        assert lint(src, "SHARED_STATE_NO_LOCK") == []  # static: quiet

        from fluidframework_tpu.testing.lockcheck import instrument

        class Stats:
            def __init__(self):
                self._lock = threading.Lock()
                self.counts = {}

            def _bump(self):
                self.counts["n"] = 1

            def read(self):
                with self._lock:
                    return dict(self.counts)

        s = Stats()
        check = instrument(s, {"counts": "_lock"})
        try:
            t = threading.Thread(target=s._bump)
            t.start()
            t.join()
            assert len(check.violations) == 1  # runtime: caught
        finally:
            check.uninstrument()

    def test_static_guards_infers_real_store_discipline(self):
        """static_guards derives the guard map fluidlint inferred for
        the real MergeLaneStore — the summarize-epoch state is
        _guard_lock-disciplined."""
        from fluidframework_tpu.server.tpu_sequencer import MergeLaneStore
        from fluidframework_tpu.testing.lockcheck import static_guards
        guards = static_guards(MergeLaneStore)
        assert guards.get("_snap_cache") == "_guard_lock"
        assert guards.get("_extract_guards") == "_guard_lock"
        assert guards.get("last_summarized_gen") == "_guard_lock"


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

DONOR = """
import threading

from .util import bump


class Worker:
    def __init__(self):
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self.drain).start()

    def drain(self):
        bump()
"""

UTIL = """
import threading

_lock = threading.Lock()
_stats = {}


def bump():
    _stats["n"] = 1


def snapshot():
    with _lock:
        return dict(_stats)
"""


class TestEngineIntegration:
    def _write_pkg(self, tmp_path):
        pkg = tmp_path / "fluidframework_tpu" / "server"
        pkg.mkdir(parents=True)
        (pkg / "donor.py").write_text(DONOR)
        (pkg / "util.py").write_text(UTIL)
        return pkg

    def test_cross_module_root_reach_finding(self, tmp_path):
        """The thread root in donor.py reaches util.bump across the
        module boundary; the unguarded module-global write fires
        THERE."""
        pkg = self._write_pkg(tmp_path)
        result = analyze_paths([str(pkg)], only=RACE_RULES)
        assert [(v.rule_id, v.path.rsplit("/", 1)[-1])
                for v in result.violations] == \
            [("SHARED_STATE_NO_LOCK", "util.py")]

    def test_changed_only_reach_expansion(self, tmp_path):
        """Locksets are whole-program: restricting reporting to a file
        in a thread root's reach still re-reports that root's findings
        in OTHER files of the same reach (the --changed-only
        satellite)."""
        from fluidframework_tpu.analysis.engine import _rel_path
        pkg = self._write_pkg(tmp_path)
        restrict = {_rel_path(pkg / "donor.py")}
        result = analyze_paths([str(pkg)], restrict=restrict,
                               only=RACE_RULES)
        paths = {v.path for v in result.violations}
        assert any(p.endswith("util.py") for p in paths), \
            "race finding in util.py must re-report when donor.py " \
            "(in the same root's reach) changed"

    def test_changed_only_outside_reach_stays_scoped(self, tmp_path):
        """A changed file OUTSIDE every thread root's reach must not
        drag unrelated race findings into the report."""
        from fluidframework_tpu.analysis.engine import _rel_path
        pkg = self._write_pkg(tmp_path)
        (pkg / "island.py").write_text("X = 1\n")
        restrict = {_rel_path(pkg / "island.py")}
        result = analyze_paths([str(pkg)], restrict=restrict,
                               only=RACE_RULES)
        assert result.violations == []

    def test_non_race_rules_unaffected_by_expansion(self, tmp_path):
        """Expansion re-runs ONLY the race family on extra files: a
        lifecycle/CC finding in util.py must not appear when only
        donor.py is in the restrict set."""
        from fluidframework_tpu.analysis.engine import _rel_path
        pkg = self._write_pkg(tmp_path)
        restrict = {_rel_path(pkg / "donor.py")}
        result = analyze_paths([str(pkg)], restrict=restrict)
        non_race = [v for v in result.violations
                    if v.rule_id not in RACE_RULES]
        assert all(not v.path.endswith("util.py") for v in non_race)

    def test_race_wall_ms_stamped(self, tmp_path):
        pkg = self._write_pkg(tmp_path)
        result = analyze_paths([str(pkg)], only=RACE_RULES)
        assert result.race_rules_wall_ms > 0
        assert "race_rules_wall_ms" in result.stats

    def test_non_race_filtered_run_skips_the_model(self, tmp_path):
        """A rule filter excluding the race family must not pay the
        lockset-model build — neither for the rules nor for the cache
        digest (their cached results contain no race findings, and the
        rule filter is part of the cache key)."""
        from fluidframework_tpu.analysis.cache import ResultCache
        pkg = self._write_pkg(tmp_path)
        result = analyze_paths([str(pkg)], only=["MUTABLE_DEFAULT"],
                               cache=ResultCache(tmp_path / "c.json"))
        assert result.race_rules_wall_ms == 0

    def test_changed_only_shared_attr_coupling(self, tmp_path):
        """A main-side file can flip ANOTHER file's lockset verdict
        without sharing any spawned root's call graph: the writer in
        a.py is guarded (typed attr chain), the thread-side reader in
        b.py is not — restricting to a.py must still re-report the
        finding in b.py through the shared-ATTR coupling group."""
        from fluidframework_tpu.analysis.engine import _rel_path
        pkg = tmp_path / "fluidframework_tpu" / "server"
        pkg.mkdir(parents=True)
        (pkg / "b.py").write_text(textwrap.dedent("""
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.state = {}

                def start(self):
                    threading.Thread(target=self._poll).start()

                def _poll(self):
                    return len(self.state)
        """))
        (pkg / "a.py").write_text(textwrap.dedent("""
            from .b import Svc

            class Owner:
                def __init__(self):
                    self.svc = Svc()

                def put(self, k, v):
                    with self.svc._lock:
                        self.svc.state[k] = v
        """))
        restrict = {_rel_path(pkg / "a.py")}
        result = analyze_paths([str(pkg)], restrict=restrict,
                               only=RACE_RULES)
        assert any(v.path.endswith("b.py") for v in result.violations), \
            [v.render() for v in result.violations]

    def test_concurrency_edit_invalidates_cached_modules(self, tmp_path):
        """Dropping the thread spawn in donor.py changes the program's
        concurrency digest, so util.py re-analyzes even though its
        bytes never changed — the v3 twist on the v2 signature test."""
        from fluidframework_tpu.analysis.cache import ResultCache
        pkg = self._write_pkg(tmp_path)
        cold = analyze_paths([str(pkg)],
                             cache=ResultCache(tmp_path / "c.json"))
        assert any(v.rule_id == "SHARED_STATE_NO_LOCK"
                   for v in cold.violations)
        (pkg / "donor.py").write_text(DONOR.replace(
            "threading.Thread(target=self.drain).start()", "pass"))
        warm = analyze_paths([str(pkg)],
                             cache=ResultCache(tmp_path / "c.json"))
        assert warm.cache_misses == 2  # concurrency change: nothing hits
        assert not any(v.rule_id == "SHARED_STATE_NO_LOCK"
                       for v in warm.violations)

    def test_pure_line_drift_keeps_cache_warm(self, tmp_path):
        """The digest is line-number-free: prepending a comment to
        donor.py re-analyzes donor.py alone; util.py stays cached."""
        from fluidframework_tpu.analysis.cache import ResultCache
        pkg = self._write_pkg(tmp_path)
        analyze_paths([str(pkg)],
                      cache=ResultCache(tmp_path / "c.json"))
        (pkg / "donor.py").write_text("# moved down one line\n" + DONOR)
        warm = analyze_paths([str(pkg)],
                             cache=ResultCache(tmp_path / "c.json"))
        assert warm.cache_hits == 1 and warm.cache_misses == 1


class TestWholeTreeGate:
    def test_no_unbaselined_race_findings(self):
        """The make lint-races acceptance: server/ + telemetry/ carry
        zero unbaselined race findings after the true-positive fixes
        and reasoned annotations of this PR."""
        from fluidframework_tpu.analysis.baseline import Baseline
        result = analyze_paths(
            [str(PACKAGE_DIR / "server"), str(PACKAGE_DIR / "telemetry")],
            baseline=Baseline.load(), only=RACE_RULES)
        assert result.violations == [], "\n".join(
            v.render() for v in result.violations)

    def test_real_tree_discovers_the_known_roots(self):
        """The model sees the tier's actual thread architecture: the
        sequencer's daemon fetch threads, the async-summary worker, and
        the monitor's HTTP handler entry point."""
        import ast
        from fluidframework_tpu.analysis.engine import (
            ModuleContext, ProgramContext, _rel_path, iter_python_files)
        contexts = []
        for f in iter_python_files([str(PACKAGE_DIR / "server"),
                                    str(PACKAGE_DIR / "telemetry")]):
            src = f.read_text()
            contexts.append(ModuleContext(_rel_path(f), src,
                                          ast.parse(src)))
        model = ProgramContext(contexts).concurrency()
        roots = {r.root_id for r in model.roots}
        assert any("summarize_documents_async.work" in r for r in roots)
        assert any("_dispatch_megakernel_chunk.fetch" in r for r in roots)
        assert any(r.startswith("http:") and "ServiceMonitor" in r
                   for r in roots)
