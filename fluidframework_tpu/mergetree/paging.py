"""Paged lane memory: fixed-size segment pages + per-doc page tables.

The merge lanes' storage (tpu_sequencer.MergeLaneStore): segment rows
live in fixed-size PAGES (Ragged Paged Attention's recipe, PAPERS.md)
rather than in one contiguous row per document padded to a fixed
capacity, so a storm document pads nothing but its own last page and
growth needs no promotion or fold — a device-resident pool of
`[n_pages, PAGE_ROWS]` flat16-column pages, a host-side per-doc page
table of int32 page ids, and a refcounted free-list allocator. Document
growth is "append a page + one page-table row write" — no row ever moves
on growth, because the apply-time view is GATHERED from the doc's own
pages (kernel.gather_pages) rather than stored contiguously.

Invariants (asserted, docs/paged_memory.md):
- page 0 is the reserved BLANK page: never allocated, always zeroed;
  page-table padding (-1) gathers it, so padded view rows are canonical
  blank padding, bit-identical to make_state's.
- a page is owned by exactly one document (refcount 1) or free;
  releasing a free page raises (double-free), releasing to zero returns
  the page to the free list ZEROED, so reallocation hands out blank rows.
- `counts[key] <= len(tables[key]) * page_rows` always: callers pre-grow
  with `ensure_rows` (each applied op adds at most 2 rows), so an apply
  can never spill rows into gather padding, where a scatter would drop
  them.

Zamboni becomes page-granular: trailing pages wholly past the live row
count release immediately after every apply (`release_trailing`), and
only fragmented documents pay a gather-compact-scatter pass, budgeted
per tick (MergeLaneStore._compact_tick_paged, compact_budget_per_tick).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .constants import MAX_OVERLAP_CLIENTS, PAGE_ROWS
from .state import DocState, make_state, DEFAULT_ANNO_SLOTS

BLANK_PAGE = 0  # reserved, never allocated, always zeroed


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_pool_pages(pool: DocState, idx: jnp.ndarray,
                     blank: DocState) -> DocState:
    """Blank the pages at ``idx`` IN PLACE (pool donated): an eager
    undonated .at[].set here would copy the entire pool per column on
    the per-flush release path. ``idx`` is pow2-padded by the caller
    with repeats (duplicate scatters of the same blank are idempotent),
    bounding the compiled variants at log2."""
    k = idx.shape[0]
    return jax.tree_util.tree_map(
        lambda col, b: col.at[idx].set(
            jnp.broadcast_to(b, (k,) + b.shape)) if col.ndim else col,
        pool, blank)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_pool_pages(pool: DocState, idx: jnp.ndarray,
                    row: DocState) -> DocState:
    """Write one doc's page-reshaped columns ([k, R, ...]) into pages
    ``idx`` with the pool donated; padding ids >= n_pages drop."""
    def s(col, v):
        if col.ndim <= 1:
            return col
        return col.at[idx].set(v, mode="drop")

    return jax.tree_util.tree_map(s, pool, row)


# Non-donating variants for MESH-placed pools: donating a dp-sharded
# plane through the persistent XLA compile cache corrupts it on warm
# reload (jax 0.4.37 — docs/serving_pipeline.md R6, now lint-enforced
# by MESH_DONATION_GATE). A mesh store dispatches through THESE; the
# single-chip store keeps the donated fast path above.
_zero_pool_pages_keep = jax.jit(_zero_pool_pages.__wrapped__)
_put_pool_pages_keep = jax.jit(_put_pool_pages.__wrapped__)


class PageAllocator:
    """Host-side refcounted free-list allocator over the page pool.

    O(1) alloc/release; double-free (releasing a page whose refcount is
    already zero) and foreign-free (blank/out-of-range ids) raise
    instead of corrupting the free list — the PayloadTable.free
    discipline, applied to device pages."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("page pool needs the blank page + 1")
        self.capacity = n_pages
        self.refcount = np.zeros(n_pages, np.int32)
        self.refcount[BLANK_PAGE] = 1  # pinned forever
        self._free: List[int] = list(range(n_pages - 1, BLANK_PAGE, -1))

    @property
    def pages_in_use(self) -> int:
        return self.capacity - len(self._free) - 1  # minus the blank page

    @property
    def pages_free(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        """One free page, refcount 0 -> 1. Raises IndexError when the
        pool is exhausted — callers grow the pool first (grow())."""
        pid = self._free.pop()
        assert self.refcount[pid] == 0, \
            f"free-list page {pid} has refcount {self.refcount[pid]}"
        self.refcount[pid] = 1
        return pid

    def alloc_many(self, n: int) -> List[int]:
        return [self.alloc() for _ in range(n)]

    def retain(self, pid: int) -> None:
        """Share a page (refcount++). Blank page and free pages refuse."""
        self._check(pid)
        if self.refcount[pid] <= 0:
            raise ValueError(f"retain of free page {pid}")
        self.refcount[pid] += 1

    def release(self, pid: int) -> bool:
        """Drop one reference; True when the page actually freed (the
        caller must zero it before the free list hands it out again).
        Releasing an already-free page is a DOUBLE FREE and raises."""
        self._check(pid)
        if self.refcount[pid] <= 0:
            raise ValueError(f"double free of page {pid}")
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            self._free.append(pid)
            return True
        return False

    def grow(self, new_capacity: int) -> None:
        if new_capacity <= self.capacity:
            return
        grown = np.zeros(new_capacity, np.int32)
        grown[:self.capacity] = self.refcount
        self.refcount = grown
        self._free.extend(range(new_capacity - 1, self.capacity - 1, -1))
        self.capacity = new_capacity

    def _check(self, pid: int) -> None:
        if not (0 < pid < self.capacity):
            raise ValueError(f"page id {pid} outside pool "
                             f"(1..{self.capacity - 1})")


def pages_for(rows: int, page_rows: int = PAGE_ROWS) -> int:
    """Pages needed to hold `rows` segment rows (minimum one)."""
    return max(1, -(-rows // page_rows))


def pow2_pages(n: int) -> int:
    """The page-count bucket: page-table widths pad to powers of two so
    the compiled (B, P, T) apply shapes stay bounded at log2 variants.
    Only the GATHERED VIEW pads; storage stays O(actual pages)."""
    return 1 << max(n - 1, 0).bit_length()


class PagedMergeStore:
    """The device page pool + per-doc page tables + host scalar mirrors.

    Segment columns live batched as pages (`pool`: a DocState whose
    batch axis is pages and whose capacity axis is `page_rows`; the
    per-page scalar fields are unused padding). Per-doc scalars (count,
    min_seq, seq) are authoritative HOST-side — every apply returns the
    exact post-window values in the same small D2H the overflow check
    already pays, so occupancy bookkeeping is exact, not hinted."""

    def __init__(self, page_rows: int = PAGE_ROWS, pages: int = 64,
                 anno_slots: int = DEFAULT_ANNO_SLOTS,
                 overlap_slots: int = MAX_OVERLAP_CLIENTS,
                 mesh=None):
        self.page_rows = page_rows
        self.anno_slots = anno_slots
        self.overlap_slots = overlap_slots
        # Mesh placement rides the partition-rule table
        # (partition_rules.POOL_PARTITION_RULES: page axis over 'dp',
        # rows/slots replicated) — pool capacity scales with the mesh.
        # The page count rounds up to a dp multiple so the sharded axis
        # divides; doubling growth preserves divisibility afterwards.
        self.mesh = mesh
        # R6: donation is gated OFF on meshes (warm-compile-cache
        # reload corrupts donated sharded planes; MESH_DONATION_GATE
        # enforces this statically). Dispatch selection happens once
        # here, not per call site.
        self.donate = mesh is None
        self._zero_dispatch = _zero_pool_pages if self.donate \
            else _zero_pool_pages_keep
        self._put_dispatch = _put_pool_pages if self.donate \
            else _put_pool_pages_keep
        if mesh is not None:
            dp = int(mesh.shape.get("dp", 1))
            pages = ((pages + dp - 1) // dp) * dp
        self.pool: DocState = make_state(page_rows, anno_slots,
                                         overlap_slots, batch=pages)
        if mesh is not None:
            from .partition_rules import (POOL_PARTITION_RULES,
                                          place_with_rules)
            self.pool = place_with_rules(mesh, self.pool,
                                         POOL_PARTITION_RULES)
        self.pool_replacements = 0  # leaves re-placed after spec drift
        self.allocator = PageAllocator(pages)
        self.tables: Dict[tuple, List[int]] = {}
        self.counts: Dict[tuple, int] = {}
        self.min_seqs: Dict[tuple, int] = {}
        self.seqs: Dict[tuple, int] = {}
        # Rows applied since the doc's last defrag pass — the
        # fragmentation pressure heuristic the budgeted compact tick
        # ranks by (tombstones cannot be counted host-side without a
        # D2H; applied-op volume is the upper bound on new garbage).
        self.ops_since_compact: Dict[tuple, int] = {}
        self._blank_row: Optional[DocState] = None
        self.pool_grows = 0

    # -- pool growth / zeroing --------------------------------------------
    def _blank(self) -> DocState:
        if self._blank_row is None:
            self._blank_row = make_state(
                self.page_rows, self.anno_slots, self.overlap_slots)
        return self._blank_row

    def grow_pool(self, need_pages: int = 1) -> None:
        new_cap = self.allocator.capacity
        while new_cap - 1 - self.allocator.pages_in_use < need_pages:
            new_cap *= 2
        if new_cap == self.allocator.capacity:
            return
        grown = make_state(self.page_rows, self.anno_slots,
                           self.overlap_slots, batch=new_cap)
        old = self.allocator.capacity
        self.adopt_pool(jax.tree_util.tree_map(
            lambda g, s: g.at[:old].set(s) if g.ndim else s,
            grown, self.pool))
        self.allocator.grow(new_cap)
        self.pool_grows += 1

    def adopt_pool(self, new_pool: DocState) -> None:
        """Adopt a dispatch-returned pool. On a mesh, verify every
        leaf still matches its rule-table spec and re-place drifted
        leaves (counted in ``pool_replacements``) — GSPMD usually
        preserves input shardings through the scatter-shaped paged
        dispatches, but 'usually' is not a placement contract."""
        if self.mesh is not None:
            from .partition_rules import (POOL_PARTITION_RULES,
                                          ensure_placement)
            new_pool, replaced = ensure_placement(
                self.mesh, new_pool, POOL_PARTITION_RULES)
            self.pool_replacements += replaced
        self.pool = new_pool

    def zero_pages(self, pids: List[int]) -> None:
        """Blank freed pages in ONE batched, pool-DONATED scatter, so
        reallocation (and gather padding through the blank page) always
        reads canonical make_state rows. The id vector pow2-pads with
        repeats (idempotent) to bound the compiled variants."""
        if not pids:
            return
        k_pad = pow2_pages(len(pids))
        padded = list(pids) + [pids[0]] * (k_pad - len(pids))
        idx = jnp.asarray(np.asarray(padded, np.int32))
        self.adopt_pool(self._zero_dispatch(self.pool, idx,
                                            self._blank()))

    # -- per-doc tables ----------------------------------------------------
    def ensure(self, key: tuple) -> None:
        if key in self.tables:
            return
        if self.allocator.pages_free < 1:
            self.grow_pool()
        self.tables[key] = [self.allocator.alloc()]
        self.counts[key] = 0
        self.min_seqs[key] = 0
        self.seqs[key] = 0

    def rows_allocated(self, key: tuple) -> int:
        return len(self.tables[key]) * self.page_rows

    def ensure_rows(self, key: tuple, need: int) -> None:
        """Append pages until the doc can hold `need` rows: THE paged
        growth path — one allocator pop + one page-table append per
        page, no data movement, no promotion, no refold."""
        self.ensure(key)
        table = self.tables[key]
        want = pages_for(need, self.page_rows)
        if want > len(table):
            missing = want - len(table)
            if self.allocator.pages_free < missing:
                self.grow_pool(missing)
            table.extend(self.allocator.alloc_many(missing))

    def release_trailing(self, key: tuple) -> None:
        """Free pages wholly past the live row count (the page-granular
        zamboni fast half: fully-dead pages go back to the pool with no
        device pass at all beyond the zeroing scatter)."""
        self.zero_pages(self._release_trailing_ids(key))

    def release_trailing_many(self, keys) -> None:
        """release_trailing over a whole group with ONE zeroing scatter:
        the apply/extract/compact paths pre-grow to the 2-rows-per-op
        worst case, so most multi-page docs free something every window
        — per-key scatters would cost up to one device dispatch per doc
        per flush."""
        freed: List[int] = []
        for key in keys:
            freed.extend(self._release_trailing_ids(key))
        self.zero_pages(freed)

    def _release_trailing_ids(self, key: tuple) -> List[int]:
        table = self.tables.get(key)
        if not table:
            return []
        keep = pages_for(self.counts.get(key, 0), self.page_rows)
        if keep >= len(table):
            return []
        dead, self.tables[key] = table[keep:], table[:keep]
        return [pid for pid in dead if self.allocator.release(pid)]

    def free_all(self, key: tuple) -> None:
        table = self.tables.pop(key, None)
        for d in (self.counts, self.min_seqs, self.seqs,
                  self.ops_since_compact):
            d.pop(key, None)
        if table:
            freed = [pid for pid in table if self.allocator.release(pid)]
            self.zero_pages(freed)

    # -- staging -----------------------------------------------------------
    def page_ids_array(self, keys: List[tuple], width: int) -> np.ndarray:
        """[len(keys), width] int32 page-table plane, -1-padded (gathers
        the blank page; scatters drop). `width` is the group's pow2 page
        bucket — every doc's table must already fit it."""
        out = np.full((len(keys), width), -1, np.int32)
        for j, key in enumerate(keys):
            table = self.tables[key]
            assert len(table) <= width, (key, len(table), width)
            out[j, :len(table)] = table
        return out

    def scalars_arrays(self, keys: List[tuple]
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        counts = np.asarray([self.counts[k] for k in keys], np.int32)
        mins = np.asarray([self.min_seqs[k] for k in keys], np.int32)
        seqs = np.asarray([self.seqs[k] for k in keys], np.int32)
        return counts, mins, seqs

    def adopt_scalars(self, keys: List[tuple], counts, min_seqs,
                      seqs) -> None:
        """Post-apply host mirror update + the spill assert (the
        `counts <= allocated` invariant a dropped scatter row would
        silently break)."""
        for j, key in enumerate(keys):
            c = int(counts[j])
            assert c <= self.rows_allocated(key), \
                f"paged apply spilled rows for {key}: {c} > " \
                f"{self.rows_allocated(key)} allocated"
            self.counts[key] = c
            self.min_seqs[key] = int(min_seqs[j])
            self.seqs[key] = int(seqs[j])

    # -- single-doc host access -------------------------------------------
    def row(self, key: tuple) -> DocState:
        """One document gathered to a single-doc DocState view (host-side
        read path: text/entries/summaries of one lane)."""
        table = self.tables[key]
        pids = np.asarray(table, np.int32)

        def g(col):
            x = col[jnp.asarray(pids)]
            return x.reshape((len(table) * self.page_rows,) + x.shape[2:])

        return DocState(
            length=g(self.pool.length), ins_seq=g(self.pool.ins_seq),
            ins_client=g(self.pool.ins_client),
            local_seq=g(self.pool.local_seq), rem_seq=g(self.pool.rem_seq),
            rem_local_seq=g(self.pool.rem_local_seq),
            rem_clients=g(self.pool.rem_clients),
            origin_op=g(self.pool.origin_op),
            origin_off=g(self.pool.origin_off), anno=g(self.pool.anno),
            count=jnp.asarray(self.counts[key], jnp.int32),
            min_seq=jnp.asarray(self.min_seqs[key], jnp.int32),
            seq=jnp.asarray(self.seqs[key], jnp.int32),
            overflow=jnp.asarray(False),
        )

    def put_row(self, key: tuple, row: DocState, count: int) -> None:
        """Write a single-doc DocState (capacity == a whole number of
        pages; pad with ensure_rows first) into the doc's pages in ONE
        pool-DONATED scatter per column (_put_pool_pages — the eager
        form copied the whole pool). Seeds and host rescues come
        through here. The page axis pow2-pads with out-of-bounds ids
        (dropped) to bound the compiled variants."""
        c = row.capacity
        self.ensure_rows(key, c)
        table = self.tables[key][:pages_for(c, self.page_rows)]
        assert c == len(table) * self.page_rows, (c, len(table))
        k, r = len(table), self.page_rows
        k_pad = pow2_pages(k)
        oob = self.allocator.capacity  # mode="drop" target for padding
        idx = jnp.asarray(np.asarray(
            table + [oob] * (k_pad - k), np.int32))

        def pv(v):
            vp = v.reshape((k, r) + v.shape[1:])
            if k_pad > k:
                vp = jnp.concatenate(
                    [vp, jnp.zeros((k_pad - k,) + vp.shape[1:],
                                   vp.dtype)], 0)
            return vp

        paged = row._replace(
            length=pv(row.length), ins_seq=pv(row.ins_seq),
            ins_client=pv(row.ins_client), local_seq=pv(row.local_seq),
            rem_seq=pv(row.rem_seq),
            rem_local_seq=pv(row.rem_local_seq),
            rem_clients=pv(row.rem_clients),
            origin_op=pv(row.origin_op), origin_off=pv(row.origin_off),
            anno=pv(row.anno))
        self.adopt_pool(self._put_dispatch(self.pool, idx, paged))
        self.counts[key] = count
        self.min_seqs[key] = int(np.asarray(row.min_seq))
        self.seqs[key] = int(np.asarray(row.seq))
        self.release_trailing(key)

    # -- placement ---------------------------------------------------------
    def placement_spec_table(self) -> Dict[str, str]:
        """Leaf name -> rule-resolved PartitionSpec string for the pool
        (partition_rules.resolved_spec_table) — the table
        dryrun_multichip stamps and testing/shardcheck verifies."""
        from .partition_rules import (POOL_PARTITION_RULES,
                                      resolved_spec_table)
        return resolved_spec_table(self.pool, POOL_PARTITION_RULES)

    # -- stats -------------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        return self.allocator.pages_in_use

    def page_fill_frac(self) -> float:
        """Live rows / allocated page rows across all documents — the
        anti-padding headline: a fixed-capacity layout's analog (rows /
        capacity) decays toward 0 as one storm doc drags every lane up
        to its depth; pages keep it near 1."""
        rows = sum(len(t) for t in self.tables.values()) * self.page_rows
        if not rows:
            return 1.0
        return sum(self.counts.values()) / rows
