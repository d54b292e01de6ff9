"""fluidlint: rule fixtures, suppressions, baseline, and the repo gate.

Every rule gets one true-positive fixture (the rule must fire) and one
false-positive guard (an adjacent legitimate idiom the rule must stay
quiet on). The final class is the CI gate itself: the analyzer over the
whole package must report zero non-baselined violations, so any future
kernel or lambda change that introduces a hazard fails tier-1 here.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from fluidframework_tpu.analysis import (
    Baseline,
    analyze_paths,
    analyze_source,
    all_rules,
)
from fluidframework_tpu.telemetry import counters

PACKAGE_DIR = Path(__file__).resolve().parents[1] / "fluidframework_tpu"


def lint(src, rule=None):
    only = [rule] if rule else ()
    return analyze_source(textwrap.dedent(src), only=only)


def rule_ids(src, rule=None):
    return [v.rule_id for v in lint(src, rule)]


# ---------------------------------------------------------------------------
# JX family
# ---------------------------------------------------------------------------

class TestTracedBranch:
    def test_true_positive_if_on_traced_arg(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """
        assert rule_ids(src, "TRACED_BRANCH") == ["TRACED_BRANCH"]

    def test_true_positive_while(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                while x > 0:
                    x = x - 1
                return x
        """
        assert rule_ids(src, "TRACED_BRANCH") == ["TRACED_BRANCH"]

    def test_guard_static_argnums(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, fused):
                if fused:
                    return x * 2
                return x
        """
        assert rule_ids(src, "TRACED_BRANCH") == []

    def test_guard_is_none_and_isinstance_and_shape(self):
        src = """
            import jax

            @jax.jit
            def f(x, runs=None):
                if runs is None:
                    return x
                if isinstance(runs, tuple):
                    return x
                if x.ndim > 1:
                    return x.sum()
                return x
        """
        assert rule_ids(src, "TRACED_BRANCH") == []

    def test_guard_not_jitted(self):
        src = """
            def f(x):
                if x > 0:
                    return x
                return -x
        """
        assert rule_ids(src, "TRACED_BRANCH") == []


class TestHostSync:
    def test_true_positive_item(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                return x.sum().item()
        """
        assert rule_ids(src, "HOST_SYNC") == ["HOST_SYNC"]

    def test_true_positive_int_on_traced(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                return int(x)
        """
        assert rule_ids(src, "HOST_SYNC") == ["HOST_SYNC"]

    def test_guard_int_on_shape(self):
        src = """
            import jax

            @jax.jit
            def f(x):
                n = int(x.shape[0])
                return x * n
        """
        assert rule_ids(src, "HOST_SYNC") == []

    def test_guard_item_outside_jit(self):
        src = """
            def host_read(arr):
                return arr.sum().item()
        """
        assert rule_ids(src, "HOST_SYNC") == []


class TestRetraceHazard:
    def test_true_positive_jnp_in_loop(self):
        src = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x, cols):
                for c in cols:
                    x = x + jnp.sum(c)
                return x
        """
        assert rule_ids(src, "RETRACE_HAZARD") == ["RETRACE_HAZARD"]

    def test_guard_loop_without_jnp(self):
        src = """
            import jax

            @jax.jit
            def f(x, names):
                total = 0
                for n in names:
                    total += len(n)
                return x * total
        """
        assert rule_ids(src, "RETRACE_HAZARD") == []

    def test_guard_lax_scan(self):
        src = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                out, _ = jax.lax.scan(lambda c, t: (c + t, None), x,
                                      jnp.arange(4))
                return out
        """
        assert rule_ids(src, "RETRACE_HAZARD") == []


class TestMutableCapture:
    def test_true_positive_module_dict(self):
        src = """
            import jax

            CACHE = {}

            @jax.jit
            def f(x):
                return x * len(CACHE)
        """
        assert rule_ids(src, "MUTABLE_CAPTURE") == ["MUTABLE_CAPTURE"]

    def test_guard_tuple_constant(self):
        src = """
            import jax

            SHAPES = (64, 256, 1024)

            @jax.jit
            def f(x):
                return x * SHAPES[0]
        """
        assert rule_ids(src, "MUTABLE_CAPTURE") == []

    def test_guard_shadowed_by_param(self):
        src = """
            import jax

            table = {}

            @jax.jit
            def f(x, table):
                return x * len(table)
        """
        assert rule_ids(src, "MUTABLE_CAPTURE") == []


class TestDtypeDrift:
    def test_true_positive_int64_in_jit(self):
        src = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x.astype(jnp.int64)
        """
        assert rule_ids(src, "DTYPE_DRIFT") == ["DTYPE_DRIFT"]

    def test_guard_canonical_int32(self):
        src = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x.astype(jnp.int32) & jnp.bool_(True)
        """
        assert rule_ids(src, "DTYPE_DRIFT") == []

    def test_guard_host_side_float64(self):
        src = """
            import numpy as np

            def host_stats(xs):
                return np.asarray(xs, np.float64).mean()
        """
        assert rule_ids(src, "DTYPE_DRIFT") == []


class TestMissingDonate:
    def test_true_positive_step_without_donate(self):
        src = """
            import jax

            @jax.jit
            def serve_step(state, ops):
                return state._replace(seq=state.seq + 1)
        """
        assert rule_ids(src, "MISSING_DONATE") == ["MISSING_DONATE"]

    def test_true_positive_call_form_unresolved(self):
        src = """
            import jax
            from .pipeline import full_step

            stepper = jax.jit(full_step)
        """
        assert rule_ids(src, "MISSING_DONATE") == ["MISSING_DONATE"]

    def test_guard_with_donate(self):
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def apply_ops(state, ops):
                return state._replace(seq=state.seq + 1)
        """
        assert rule_ids(src, "MISSING_DONATE") == []

    def test_guard_non_state_function(self):
        src = """
            import jax

            @jax.jit
            def decode(buf, table):
                return buf + table
        """
        assert rule_ids(src, "MISSING_DONATE") == []

    def test_true_positive_serve_window_signature_without_donate(self):
        """The donated serve_window shape (tstate + merge/LWW lane-state
        lists threaded through one fused window): dropping its
        donate_argnums must keep firing — a regression here doubles peak
        HBM for every lane plane on every serving window."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, static_argnums=(6,))
            def serve_window(tstate, ticket_cols, merge_states,
                             merge_cols, lww_states, lww_cols,
                             fused=False, merge_runs=None):
                return tstate, merge_states, lww_states
        """
        assert rule_ids(src, "MISSING_DONATE") == ["MISSING_DONATE"]

    def test_guard_serve_window_with_lane_state_donation(self):
        """The shipped signature: donate_argnums=(0, 2, 4) covers the
        ticket state AND both lane-state lists."""
        src = """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0, 2, 4),
                               static_argnums=(6,))
            def serve_window(tstate, ticket_cols, merge_states,
                             merge_cols, lww_states, lww_cols,
                             fused=False, merge_runs=None):
                return tstate, merge_states, lww_states
        """
        assert rule_ids(src, "MISSING_DONATE") == []


class TestScanHostCallback:
    def test_true_positive_io_callback_in_scan_body(self):
        src = """
            import jax
            from jax import lax
            from jax.experimental import io_callback

            def serve_burst(carry, xs):
                def body(c, x):
                    io_callback(print, None, x)
                    return c, x
                return lax.scan(body, carry, xs)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == \
            ["SCAN_HOST_CALLBACK"]

    def test_true_positive_block_until_ready_in_while_body(self):
        src = """
            import jax

            def drain(state):
                def cond(s):
                    return s.pending > 0
                def step(s):
                    s.planes.block_until_ready()
                    return s.advance()
                return jax.lax.while_loop(cond, step, state)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == \
            ["SCAN_HOST_CALLBACK"]

    def test_true_positive_debug_callback_in_lambda_body(self):
        src = """
            from jax import lax, debug

            def trace_scan(init, xs):
                return lax.scan(
                    lambda c, x: (debug.callback(print, c), x)[1:],
                    init, xs)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == \
            ["SCAN_HOST_CALLBACK"]

    def test_guard_callback_outside_scan_body(self):
        """Host callbacks in straight-line staging code are fine — the
        hazard is per-STEP re-entry, not host work around the program."""
        src = """
            import jax
            from jax import lax
            from jax.experimental import io_callback

            def serve(carry, xs):
                def body(c, x):
                    return c, x + 1
                out = lax.scan(body, carry, xs)
                io_callback(print, None, out)
                return out
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == []

    def test_guard_pure_device_scan_body(self):
        src = """
            import jax.numpy as jnp
            from jax import lax

            def apply_ops(state, ops):
                def body(s, t):
                    return s + jnp.sum(ops[t]), None
                return lax.scan(body, state, jnp.arange(4))
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == []

    def test_guard_block_until_ready_on_host_path(self):
        src = """
            import numpy as np

            def fetch(result):
                result.block_until_ready()
                return np.asarray(result)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == []

    def test_out_of_scope_module_is_quiet(self):
        src = textwrap.dedent("""
            from jax import lax
            from jax.experimental import io_callback

            def f(c, xs):
                def body(c, x):
                    io_callback(print, None, x)
                    return c, x
                return lax.scan(body, c, xs)
        """)
        hits = analyze_source(src, path="examples/clicker.py",
                              only=["SCAN_HOST_CALLBACK"])
        assert hits == []

    def test_true_positive_callback_in_pallas_kernel_body(self):
        """R10: the megakernel body is a persistent device program — a
        host callback there cannot lower and would silently eat the
        whole pallas path (fallback every ring)."""
        src = """
            import jax
            from jax.experimental import pallas as pl
            from jax import debug

            def apply_megakernel(ops, pool):
                def kernel(ops_ref, pool_ref, out_ref):
                    debug.callback(print, ops_ref[0])
                    out_ref[...] = pool_ref[...]
                return pl.pallas_call(
                    kernel,
                    out_shape=jax.ShapeDtypeStruct(pool.shape,
                                                   pool.dtype))(ops, pool)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == \
            ["SCAN_HOST_CALLBACK"]

    def test_true_positive_block_until_ready_in_pallas_kernel(self):
        src = """
            from jax.experimental import pallas as pl

            def gather(pool, pids, out_shape):
                def kernel(pool_ref, pids_ref, out_ref):
                    pool_ref[...].block_until_ready()
                    out_ref[...] = pool_ref[...]
                return pl.pallas_call(kernel, out_shape=out_shape)(
                    pool, pids)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == \
            ["SCAN_HOST_CALLBACK"]

    def test_guard_pure_pallas_kernel_body(self):
        """The shipped megakernel shape: ref loads/stores and lax ops
        only — must stay quiet."""
        src = """
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def apply_megakernel(ops, pool, out_shape):
                def kernel(ops_ref, pool_ref, out_ref):
                    rows = pool_ref[...]
                    out_ref[...] = rows + jnp.int32(1)
                return pl.pallas_call(kernel, out_shape=out_shape)(
                    ops, pool)
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == []

    def test_guard_callback_in_staging_around_pallas_call(self):
        """Host work AROUND the pallas dispatch (staging, fetch) is the
        normal drain pattern — only the kernel body is in scope."""
        src = """
            from jax.experimental import pallas as pl
            from jax.experimental import io_callback

            def drain(pool, out_shape):
                def kernel(pool_ref, out_ref):
                    out_ref[...] = pool_ref[...]
                out = pl.pallas_call(kernel, out_shape=out_shape)(pool)
                io_callback(print, None, out)
                return out
        """
        assert rule_ids(src, "SCAN_HOST_CALLBACK") == []


class TestPageIdDtype:
    def test_true_positive_int64_page_table(self):
        src = """
            import numpy as np

            def stage(table):
                page_ids = np.asarray(table, np.int64)
                return page_ids
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_true_positive_astype_and_kernel_operand(self):
        src = """
            import numpy as np
            import jax.numpy as jnp
            from fluidframework_tpu.mergetree import kernel

            def dispatch(pool, pids, counts, mins, seqs, ops):
                wide = pids.astype(np.int64)
                return kernel.apply_ops_paged(
                    pool, jnp.asarray(wide, jnp.int16), counts, mins,
                    seqs, ops)
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == \
            ["PAGE_ID_DTYPE", "PAGE_ID_DTYPE"]

    def test_true_positive_string_dtype_keyword(self):
        src = """
            import numpy as np

            def build(n):
                page_table = np.zeros(n, dtype="int16")
                return page_table
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_true_positive_tuple_unpack_target(self):
        src = """
            import numpy as np

            def stage(table):
                pids, n = np.asarray(table, np.int64), len(table)
                return pids, n
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_true_positive_uint32_kills_padding_sentinel(self):
        """uint32 is 32 bits wide but turns the -1 padding sentinel into
        4294967295 — the scatter drop-guard (page_ids >= 0) goes
        vacuously true and padding rows overwrite a real page."""
        src = """
            import numpy as np

            def stage(table):
                pids = np.asarray(table, np.uint32)
                return pids
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == ["PAGE_ID_DTYPE"]

    def test_guard_int32_page_ids_quiet(self):
        src = """
            import numpy as np
            import jax.numpy as jnp

            def stage(table, flagged):
                page_ids = np.full((4, 8), -1, np.int32)
                pids = jnp.asarray(page_ids)
                sel = np.asarray(flagged, np.int64)  # not page-named
                return pids, sel
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == []

    def test_guard_unrelated_int64_names_quiet(self):
        src = """
            import numpy as np

            def hints(lanes):
                count_hint = np.zeros(lanes, np.int64)
                page_fill = float(count_hint.sum())
                return page_fill
        """
        assert rule_ids(src, "PAGE_ID_DTYPE") == []

    def test_out_of_scope_module_is_quiet(self):
        src = textwrap.dedent("""
            import numpy as np

            def stage(table):
                page_ids = np.asarray(table, np.int64)
                return page_ids
        """)
        hits = analyze_source(src, path="examples/clicker.py",
                              only=["PAGE_ID_DTYPE"])
        assert hits == []


# ---------------------------------------------------------------------------
# CC family
# ---------------------------------------------------------------------------

class TestAwaitInLock:
    def test_true_positive(self):
        src = """
            async def handler(self, op):
                async with self._lock:
                    await self.store.write(op)
        """
        assert rule_ids(src, "AWAIT_IN_LOCK") == ["AWAIT_IN_LOCK"]

    def test_guard_await_outside_lock(self):
        src = """
            async def handler(self, op):
                async with self._lock:
                    self.pending.append(op)
                await self.store.flush()
        """
        assert rule_ids(src, "AWAIT_IN_LOCK") == []

    def test_guard_non_lock_context(self):
        src = """
            async def handler(self, op):
                async with self.session() as s:
                    await s.write(op)
        """
        assert rule_ids(src, "AWAIT_IN_LOCK") == []


class TestBlockingInAsync:
    def test_true_positive_time_sleep(self):
        src = """
            import time

            async def poll(self):
                time.sleep(1)
        """
        assert rule_ids(src, "BLOCKING_IN_ASYNC") == ["BLOCKING_IN_ASYNC"]

    def test_true_positive_open(self):
        src = """
            async def load(self, path):
                with open(path) as f:
                    return f.read()
        """
        assert rule_ids(src, "BLOCKING_IN_ASYNC") == ["BLOCKING_IN_ASYNC"]

    def test_guard_asyncio_sleep_and_sync_def(self):
        src = """
            import asyncio, time

            async def poll(self):
                await asyncio.sleep(1)

            def sync_poll(self):
                time.sleep(1)
        """
        assert rule_ids(src, "BLOCKING_IN_ASYNC") == []


class TestSwallowedException:
    def test_true_positive_pass(self):
        src = """
            def f(sock):
                try:
                    sock.send(b"x")
                except Exception:
                    pass
        """
        assert rule_ids(src, "SWALLOWED_EXCEPTION") == [
            "SWALLOWED_EXCEPTION"]

    def test_true_positive_bare_except_return(self):
        src = """
            def f(raw):
                try:
                    return decode(raw)
                except:
                    return None
        """
        assert rule_ids(src, "SWALLOWED_EXCEPTION") == [
            "SWALLOWED_EXCEPTION"]

    def test_guard_typed_except(self):
        src = """
            def f(sock):
                try:
                    sock.send(b"x")
                except OSError:
                    pass
        """
        assert rule_ids(src, "SWALLOWED_EXCEPTION") == []

    def test_guard_counter_call(self):
        src = """
            from fluidframework_tpu.telemetry.counters import record_swallow

            def f(sock):
                try:
                    sock.send(b"x")
                except Exception:
                    record_swallow("test.site")
        """
        assert rule_ids(src, "SWALLOWED_EXCEPTION") == []

    def test_guard_reraise(self):
        src = """
            def f(guard, work):
                try:
                    work()
                except BaseException:
                    guard.release()
                    raise
        """
        assert rule_ids(src, "SWALLOWED_EXCEPTION") == []

    def test_guard_error_stored(self):
        src = """
            def f(ctx, work):
                try:
                    work()
                except Exception as err:
                    ctx["error"] = err
        """
        assert rule_ids(src, "SWALLOWED_EXCEPTION") == []


class TestListenerLeak:
    def test_true_positive_on_without_off(self):
        src = """
            class Emitter:
                def __init__(self):
                    self.listeners = []

                def on(self, event, fn):
                    self.listeners.append(fn)
        """
        assert rule_ids(src, "LISTENER_LEAK") == ["LISTENER_LEAK"]

    def test_guard_on_with_off(self):
        src = """
            class Emitter:
                def __init__(self):
                    self.listeners = []

                def on(self, event, fn):
                    self.listeners.append(fn)

                def off(self, event, fn):
                    self.listeners.remove(fn)
        """
        assert rule_ids(src, "LISTENER_LEAK") == []

    def test_guard_subscribe_with_unsubscribe(self):
        src = """
            class Broker:
                def subscribe(self, topic, fn):
                    self.topics[topic].append(fn)

                def unsubscribe(self, topic, fn):
                    self.topics[topic].remove(fn)
        """
        assert rule_ids(src, "LISTENER_LEAK") == []


class TestMutableDefault:
    def test_true_positive(self):
        src = """
            def enqueue(op, queue=[]):
                queue.append(op)
                return queue
        """
        assert rule_ids(src, "MUTABLE_DEFAULT") == ["MUTABLE_DEFAULT"]

    def test_true_positive_kwonly_dict(self):
        src = """
            def connect(url, *, headers={}):
                return (url, headers)
        """
        assert rule_ids(src, "MUTABLE_DEFAULT") == ["MUTABLE_DEFAULT"]

    def test_guard_none_default(self):
        src = """
            def enqueue(op, queue=None):
                queue = queue or []
                queue.append(op)
                return queue
        """
        assert rule_ids(src, "MUTABLE_DEFAULT") == []

    def test_guard_tuple_default(self):
        src = """
            def make(capacities=(64, 256, 1024)):
                return list(capacities)
        """
        assert rule_ids(src, "MUTABLE_DEFAULT") == []


class TestSpanLeak:
    def test_true_positive_started_never_ended(self):
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                sp = tracing.span("serving.flush")
                for item in backlog:
                    process(item)
        """
        assert rule_ids(src, "SPAN_LEAK") == ["SPAN_LEAK"]

    def test_true_positive_end_in_straight_line_code(self):
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                sp = tracing.span("serving.flush")
                dispatch(backlog)   # raises -> sp leaks
                sp.end()
        """
        assert rule_ids(src, "SPAN_LEAK") == ["SPAN_LEAK"]

    def test_true_positive_unrelated_finally_does_not_cover_start(self):
        # The finally holds an end(), but its try starts AFTER dispatch:
        # dispatch() raising leaks the span — exactly the hole-in-the-
        # trace failure the rule exists for.
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                sp = tracing.span("serving.flush")
                dispatch(backlog)   # raises -> sp leaks; try below moot
                try:
                    other()
                finally:
                    sp.end()
        """
        assert rule_ids(src, "SPAN_LEAK") == ["SPAN_LEAK"]

    def test_guard_start_inside_try_body(self):
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                try:
                    sp = tracing.span("serving.flush")
                    dispatch(backlog)
                finally:
                    sp.end()
        """
        assert rule_ids(src, "SPAN_LEAK") == []

    def test_guard_with_statement(self):
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                with tracing.span("serving.flush"):
                    dispatch(backlog)
        """
        assert rule_ids(src, "SPAN_LEAK") == []

    def test_guard_end_in_finally(self):
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                sp = tracing.span("serving.flush")
                try:
                    dispatch(backlog)
                finally:
                    sp.end()
        """
        assert rule_ids(src, "SPAN_LEAK") == []

    def test_guard_cancel_in_finally(self):
        src = """
            from fluidframework_tpu.telemetry import tracing

            def flush(backlog):
                sp = tracing.span("serving.flush")
                try:
                    dispatch(backlog)
                    sp.end()
                finally:
                    sp.cancel()
        """
        assert rule_ids(src, "SPAN_LEAK") == []

    def test_guard_non_span_call(self):
        src = """
            def flush(backlog):
                spacing = compute_spacing("x")
                return spacing
        """
        assert rule_ids(src, "SPAN_LEAK") == []

    def test_out_of_scope_module_is_quiet(self):
        from fluidframework_tpu.analysis import analyze_source
        src = textwrap.dedent("""
            from fluidframework_tpu.telemetry import tracing

            def f():
                sp = tracing.span("x")
        """)
        hits = analyze_source(src, path="examples/clicker.py",
                              only=["SPAN_LEAK"])
        assert hits == []


# ---------------------------------------------------------------------------
# suppressions + baseline + CLI
# ---------------------------------------------------------------------------

SWALLOW_SRC = """
    def f(sock):
        try:
            sock.send(b"x")
        except Exception:
            pass
"""


class TestSuppressions:
    def test_inline_same_line(self):
        src = """
            def f(sock):
                try:
                    sock.send(b"x")
                except Exception:  # fluidlint: disable=SWALLOWED_EXCEPTION
                    pass
        """
        assert rule_ids(src) == []

    def test_standalone_comment_above(self):
        src = """
            def f(sock):
                try:
                    sock.send(b"x")
                # fluidlint: disable=SWALLOWED_EXCEPTION — reply socket is
                # already dead; nothing to tell anyone.
                except Exception:
                    pass
        """
        assert rule_ids(src) == []

    def test_suppression_is_rule_specific(self):
        src = """
            def f(sock):
                try:
                    sock.send(b"x")
                except Exception:  # fluidlint: disable=MUTABLE_DEFAULT
                    pass
        """
        assert rule_ids(src) == ["SWALLOWED_EXCEPTION"]

    def test_disable_all(self):
        src = """
            def f(sock):
                try:
                    sock.send(b"x")
                except Exception:  # fluidlint: disable
                    pass
        """
        assert rule_ids(src) == []

    def test_unsuppressed_fires(self):
        assert rule_ids(SWALLOW_SRC) == ["SWALLOWED_EXCEPTION"]


class TestBaselineRoundTrip:
    def test_round_trip(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(SWALLOW_SRC))
        # First pass: the violation is new.
        result = analyze_paths([str(bad)], baseline=Baseline())
        assert [v.rule_id for v in result.violations] == [
            "SWALLOWED_EXCEPTION"]
        assert result.baselined == []
        # Accept it, save, reload: now it is baselined, not new.
        bl_path = tmp_path / "baseline.json"
        Baseline().updated_with(result.violations).save(bl_path)
        reloaded = Baseline.load(bl_path)
        result2 = analyze_paths([str(bad)], baseline=reloaded)
        assert result2.violations == []
        assert [v.rule_id for v in result2.baselined] == [
            "SWALLOWED_EXCEPTION"]
        assert result2.summary == {"violations": 0, "baselined": 1}

    def test_fingerprint_survives_line_shift(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(SWALLOW_SRC))
        result = analyze_paths([str(bad)], baseline=Baseline())
        bl = Baseline().updated_with(result.violations)
        # Shift the violation down: same symbol + line text => same
        # fingerprint, so the baseline still matches.
        bad.write_text("GREETING = 'hello'\n\n"
                       + textwrap.dedent(SWALLOW_SRC))
        result2 = analyze_paths([str(bad)], baseline=bl)
        assert result2.violations == []
        assert len(result2.baselined) == 1

    def test_edited_line_escapes_baseline(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(SWALLOW_SRC))
        bl = Baseline().updated_with(
            analyze_paths([str(bad)], baseline=Baseline()).violations)
        # A semantic edit to the flagged line changes the fingerprint:
        # the finding counts as NEW again (accepted debt cannot mutate).
        bad.write_text(textwrap.dedent(SWALLOW_SRC).replace(
            "except Exception:", "except BaseException:"))
        result = analyze_paths([str(bad)], baseline=bl)
        assert [v.rule_id for v in result.violations] == [
            "SWALLOWED_EXCEPTION"]

    def test_reason_preserved_on_regenerate(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(SWALLOW_SRC))
        vs = analyze_paths([str(bad)], baseline=Baseline()).violations
        bl = Baseline().updated_with(vs)
        bl.entries[0]["reason"] = "socket already dead"
        bl2 = Baseline(bl.entries).updated_with(vs)
        assert bl2.entries[0]["reason"] == "socket already dead"


class TestCli:
    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "fluidframework_tpu.analysis", *args],
            capture_output=True, text=True,
            cwd=str(PACKAGE_DIR.parent))

    def test_clean_file_exits_zero_with_summary(self, tmp_path):
        ok = tmp_path / "ok.py"
        ok.write_text("def f():\n    return 1\n")
        proc = self.run_cli(str(ok))
        assert proc.returncode == 0
        assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
            "violations": 0, "baselined": 0}

    def test_violation_exits_nonzero_with_summary(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(SWALLOW_SRC))
        proc = self.run_cli(str(bad))
        assert proc.returncode == 1
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert last == {"violations": 1, "baselined": 0}
        assert "SWALLOWED_EXCEPTION" in proc.stdout

    def test_list_rules(self):
        proc = self.run_cli("--list-rules")
        assert proc.returncode == 0
        for r in all_rules():
            assert r.id in proc.stdout

    def test_unknown_rule_id_is_a_clean_error(self):
        proc = self.run_cli("--rule", "BOGUS")
        assert proc.returncode == 2
        assert "unknown rule id" in proc.stderr

    def test_nonexistent_path_is_an_error_not_a_vacuous_pass(self):
        proc = self.run_cli("no_such_dir/")
        assert proc.returncode != 0
        assert "do not exist" in proc.stderr

    def test_empty_match_is_an_error_not_a_vacuous_pass(self, tmp_path):
        proc = self.run_cli(str(tmp_path))  # exists, holds no .py files
        assert proc.returncode == 2
        assert "no Python files" in proc.stderr

    def test_scoped_write_baseline_preserves_out_of_scope_entries(
            self, tmp_path):
        """--write-baseline over a subset of paths must merge, never
        discard curated acceptances for files outside the scope."""
        a, b = tmp_path / "a.py", tmp_path / "b.py"
        a.write_text(textwrap.dedent(SWALLOW_SRC))
        b.write_text(textwrap.dedent(SWALLOW_SRC))
        bl_path = tmp_path / "bl.json"
        proc = self.run_cli(str(a), str(b), "--baseline", str(bl_path),
                            "--write-baseline")
        assert proc.returncode == 0
        entries = json.loads(bl_path.read_text())["entries"]
        assert len(entries) == 2
        # Scoped re-write over only a.py: b.py's entry must survive.
        proc = self.run_cli(str(a), "--baseline", str(bl_path),
                            "--write-baseline")
        assert proc.returncode == 0
        entries = json.loads(bl_path.read_text())["entries"]
        assert len(entries) == 2
        # Full-scope re-write after fixing a.py retires its stale entry.
        a.write_text("def f():\n    return 1\n")
        proc = self.run_cli(str(a), str(b), "--baseline", str(bl_path),
                            "--write-baseline")
        assert proc.returncode == 0
        entries = json.loads(bl_path.read_text())["entries"]
        assert len(entries) == 1
        assert entries[0]["path"].endswith("b.py")


# ---------------------------------------------------------------------------
# runtime cross-checks: swallow counters + the retrace probe
# ---------------------------------------------------------------------------

class TestRuntimeCounters:
    def setup_method(self):
        counters.reset()

    def test_record_swallow_counts(self):
        counters.record_swallow("test.site")
        counters.record_swallow("test.site")
        assert counters.get("swallowed.test.site") == 2

    def test_monitor_healthz_exports_counters(self):
        from fluidframework_tpu.server.monitor import ServiceMonitor
        import urllib.request
        counters.record_swallow("test.healthz")
        mon = ServiceMonitor().start()
        try:
            body = json.loads(urllib.request.urlopen(
                mon.url + "/healthz", timeout=5).read())
            assert body["counters"]["swallowed.test.healthz"] == 1.0
            report = mon.report()
            assert report["counters"]["swallowed.test.healthz"] == 1.0
        finally:
            mon.stop()

    def test_retrace_probe_counts_cache_growth(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp
        probed = counters.JitRetraceProbe(jax.jit(lambda x: x + 1),
                                          name="test.kernel")
        probed(jnp.zeros((4,), jnp.int32))
        # First signature: a compile, not a retrace.
        assert counters.get("test.kernel.compiles") == 1
        assert counters.get("test.kernel.retraces") == 0
        probed(jnp.zeros((4,), jnp.int32))  # cache hit: no growth
        assert counters.get("test.kernel.compiles") == 1
        # New shape after warmup: that is the retrace signal.
        probed(jnp.zeros((8,), jnp.int32))
        assert counters.get("test.kernel.retraces") == 1
        assert counters.get("kernel.retrace_count") == 1

    def test_probe_over_warm_cache_counts_compile_not_retrace(self):
        """A probe attached to an already-warm jitted fn must treat the
        first growth IT observes as a compile, never a phantom retrace;
        pre-probe compiles by other callers are not charged to it."""
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp
        fn = jax.jit(lambda x: x * 2)
        fn(jnp.zeros((4,), jnp.int32))  # warmed by another caller
        probed = counters.JitRetraceProbe(fn, name="test.warm")
        probed(jnp.zeros((4,), jnp.int32))  # cache hit: nothing to count
        assert counters.get("test.warm.compiles") == 0
        probed(jnp.zeros((8,), jnp.int32))  # first growth WE observe
        assert counters.get("test.warm.compiles") == 1
        assert counters.get("test.warm.retraces") == 0
        probed(jnp.zeros((16,), jnp.int32))  # growth after growth: retrace
        assert counters.get("test.warm.retraces") == 1

    def test_sequencer_batched_apply_is_probed(self):
        from fluidframework_tpu.server import tpu_sequencer
        for probe, name in (
                (tpu_sequencer._apply_paged_probe, "kernel.paged_apply"),
                (tpu_sequencer._apply_paged_keep_probe,
                 "kernel.paged_apply_keep")):
            assert isinstance(probe, counters.JitRetraceProbe)
            assert probe.name == name


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

class TestRepoGate:
    def test_package_is_clean_against_baseline(self):
        """The hard gate: the analyzer over the whole package must come
        back clean (every finding fixed, suppressed with a reason, or
        baselined with a reason). A new kernel or lambda hazard fails
        tier-1 right here."""
        result = analyze_paths([str(PACKAGE_DIR)], baseline=Baseline.load())
        rendered = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], (
            f"new fluidlint violations:\n{rendered}\n"
            f"Fix them, suppress inline with a reason, or baseline via "
            f"python -m fluidframework_tpu.analysis --write-baseline")
        assert result.files > 100  # the walk actually covered the package

    def test_baseline_entries_all_still_match(self):
        """Stale baseline entries (fixed code, lingering acceptance) rot
        the gate; regenerating keeps violations+baselined == reality."""
        result = analyze_paths([str(PACKAGE_DIR)], baseline=Baseline.load())
        assert len(result.baselined) == len(Baseline.load()), (
            "baseline has entries no longer observed; regenerate with "
            "--write-baseline to drop them")

    def test_baseline_reasons_filled_in(self):
        for entry in Baseline.load().entries:
            assert entry["reason"] and "TODO" not in entry["reason"], (
                f"baseline entry {entry['fingerprint']} "
                f"({entry['path']}) has no justification")


class TestUnboundedQueue:
    def test_true_positive_list_append(self):
        src = """
            class Ingest:
                def __init__(self):
                    self._queue = []

                def on_message(self, msg):
                    self._queue.append(msg)
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == ["UNBOUNDED_QUEUE"]

    def test_true_positive_deque_without_maxlen(self):
        src = """
            import collections

            class Pump:
                def __init__(self):
                    self.backlog = collections.deque()

                def feed(self, batch):
                    self.backlog.extend(batch)
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == ["UNBOUNDED_QUEUE"]

    def test_guard_deque_maxlen(self):
        src = """
            import collections

            class Pump:
                def __init__(self):
                    self.backlog = collections.deque(maxlen=1024)

                def feed(self, batch):
                    self.backlog.extend(batch)
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []

    def test_guard_len_limit_check(self):
        src = """
            class Ingest:
                def __init__(self, limit):
                    self._queue = []
                    self.limit = limit

                def on_message(self, msg):
                    if len(self._queue) >= self.limit:
                        return False
                    self._queue.append(msg)
                    return True
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []

    def test_guard_slicing_trim(self):
        src = """
            class Recorder:
                def __init__(self):
                    self.pending = []

                def push(self, item):
                    self.pending.append(item)
                    self.pending = self.pending[-512:]
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []

    def test_guard_del_trim(self):
        src = """
            class Recorder:
                def __init__(self):
                    self.pending = []

                def push(self, item):
                    self.pending.append(item)
                    if True:
                        del self.pending[:256]
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []

    def test_guard_swap_and_drain_clear(self):
        src = """
            class Batcher:
                def __init__(self):
                    self.inbox = []

                def push(self, item):
                    self.inbox.append(item)

                def drain(self):
                    out = list(self.inbox)
                    self.inbox.clear()
                    return out
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []

    def test_non_queueish_names_are_ignored(self):
        src = """
            class Registry:
                def __init__(self):
                    self.rules = []

                def add(self, r):
                    self.rules.append(r)
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []

    def test_pop_alone_is_not_a_bound(self):
        # Consumption is not a bound: producers can outpace the pump.
        src = """
            class Pump:
                def __init__(self):
                    self._queue = []

                def feed(self, msg):
                    self._queue.append(msg)

                def pump_one(self):
                    if self._queue:
                        return self._queue.pop(0)
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == ["UNBOUNDED_QUEUE"]

    def test_out_of_scope_module_ignored(self):
        from fluidframework_tpu.analysis import analyze_source
        src = textwrap.dedent("""
            class ClientPending:
                def __init__(self):
                    self.pending = []

                def queue_op(self, op):
                    self.pending.append(op)
        """)
        assert [v.rule_id for v in analyze_source(
            src, path="fluidframework_tpu/loader/pending.py",
            only=["UNBOUNDED_QUEUE"])] == []
        assert [v.rule_id for v in analyze_source(
            src, path="fluidframework_tpu/server/newpump.py",
            only=["UNBOUNDED_QUEUE"])] == ["UNBOUNDED_QUEUE"]

    def test_suppression_with_reason(self):
        src = """
            class Ingest:
                def __init__(self):
                    self._queue = []

                def on_message(self, msg):
                    # fluidlint: disable=UNBOUNDED_QUEUE — bounded by
                    # the admission front door (docs/overload.md)
                    self._queue.append(msg)
        """
        assert rule_ids(src, "UNBOUNDED_QUEUE") == []
