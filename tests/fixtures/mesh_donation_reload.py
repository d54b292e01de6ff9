"""Seeded known-bug fixture for fluidlint v4's MESH_DONATION_GATE.

A stripped-down serving step with the R6 bug shape: a module-level
donating jit (``donate_argnums=(0,)``) dispatched on dp-mesh-sharded
state. This is what the real warm-reload corruption looked like — on
jax 0.4.37 a donated dp-sharded lane-state plane reloaded from the
persistent XLA compilation cache returns corrupt lane planes (repro:
tests/test_mesh_serving.py warm vs cold after clearing the compile
cache; docs/serving_pipeline.md R6). The real
``tpu_sequencer`` selects the non-donating ``_keep`` dispatch whenever
a mesh is present (``PagedMergeStore.donate = mesh is None``); this fixture
is what the code would look like if someone "optimized" that back into
an unconditional donating dispatch.

Committed as a must-fire true positive (pinned by
``tests/test_placement_lint.py::TestSeededMeshDonationFixture``): if
the rule ever stops firing here, it has gone vacuous and the gate
fails. This file is NEVER imported by production code and sits outside
the analyzer's default package scope — only the pin test feeds it
through ``analyze_source``.
"""

import functools

import jax

from fluidframework_tpu.parallel.mesh import make_mesh, shard_docs


@functools.partial(jax.jit, donate_argnums=(0,))
def serve(state, ops):
    """The donating dispatch — fine on a single chip, where donation
    is the whole point of the serving fast path."""
    return state


def warm_reload_step(state, ops):
    """BUG: `state` is definitely dp-sharded when it reaches the
    donating `serve` — exactly the placement R6 forbids donating,
    because a warm reload through the persistent compile cache
    corrupts the donated sharded planes."""
    mesh = make_mesh(dp=8)
    state = shard_docs(mesh, state)
    return serve(state, ops)
