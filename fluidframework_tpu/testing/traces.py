"""Realistic editing-trace synthesis for benchmarks and replay tests.

The reference replays real captured op logs (ProseMirror/Monaco sessions:
packages/test/snapshots/src/replayMultipleFiles.ts:1 over an LFS corpus)
and stress profiles (packages/test/service-load-test/src/nodeStressTest.ts:
24-33). Real editor traffic is nothing like uniform-random ops: it is
keystroke bursts at a slowly-moving cursor, backspace runs, word/line
deletions, cursor jumps with strong locality, occasional format
(annotate) sweeps, and rare large paste/cut blocks. This module
synthesizes that shape deterministically, keystroke by keystroke, so the
kernel is measured on the position-locality distribution serving actually
sees rather than the uniform dense streams it finds easiest.
"""

from __future__ import annotations

import random
from typing import List, Tuple

import numpy as np

# Wire op types (mergetree/client.py, reference ops.ts:29).
OP_INSERT, OP_REMOVE, OP_ANNOTATE = 0, 1, 2

WORDS = ("the quick brown fox jumps over a lazy dog while typing "
         "structured paragraphs of filler prose for replay traces").split()


def keystroke_trace(n_ops: int, seed: int = 0, n_clients: int = 1,
                    window: int = 128) -> List[Tuple[dict, int, int, int,
                                                     int]]:
    """A sequenced single-document editing trace:
    [(wire_op, seq, ref_seq, client_ordinal, msn)].

    Emission model (per op, roughly matching captured editor sessions):
      74% keystroke insert (1 char at the cursor; bursts extend words)
      10% backspace (remove 1 char before the cursor)
       6% word/selection delete (remove 2-24 chars near the cursor)
       4% paste (insert 20-200 chars at the cursor)
       4% format sweep (annotate 5-80 chars near the cursor)
       2% cursor jump (no op emitted; moves the locality anchor)

    Multi-client mode interleaves independent cursors with a shared
    sequencing order and per-client ref_seq lag, the concurrent-editor
    shape of the service-load profiles."""
    rng = random.Random(seed)
    length = 0
    cursors = [0] * n_clients
    out: List[Tuple[dict, int, int, int, int]] = []
    seq = 0
    burst_left = 0
    burst_client = 0
    while len(out) < n_ops:
        if burst_left > 0:
            c = burst_client
            burst_left -= 1
            roll = 0.0  # keystroke continues the burst
        else:
            c = rng.randrange(n_clients)
            roll = rng.random()
            if roll >= 0.98:  # cursor jump: move anchor, no op
                cursors[c] = rng.randrange(length + 1) if length else 0
                continue
            if roll < 0.74:  # start a word burst
                burst_left = rng.randrange(2, 9)
                burst_client = c
        cur = min(cursors[c], length)
        if roll < 0.74:  # keystroke
            word = rng.choice(WORDS)
            ch = word[rng.randrange(len(word))] if rng.random() < 0.85 \
                else " "
            op = {"type": OP_INSERT, "pos1": cur, "seg": {"text": ch}}
            length += 1
            cursors[c] = cur + 1
        elif roll < 0.84:  # backspace
            if cur == 0 or length == 0:
                burst_left = 0
                continue
            op = {"type": OP_REMOVE, "pos1": cur - 1, "pos2": cur}
            length -= 1
            cursors[c] = cur - 1
        elif roll < 0.90:  # word/selection delete
            if length < 4:
                continue
            span = min(rng.randrange(2, 25), length)
            start = max(0, min(cur - span // 2, length - span))
            op = {"type": OP_REMOVE, "pos1": start, "pos2": start + span}
            length -= span
            cursors[c] = start
        elif roll < 0.94:  # paste
            n = rng.randrange(20, 201)
            text = " ".join(rng.choice(WORDS)
                            for _ in range(max(1, n // 6)))[:n]
            op = {"type": OP_INSERT, "pos1": cur, "seg": {"text": text}}
            length += len(text)
            cursors[c] = cur + len(text)
        else:  # format sweep
            if length < 2:
                continue
            span = min(rng.randrange(5, 81), length)
            start = max(0, min(cur - span // 2, length - span))
            op = {"type": OP_ANNOTATE, "pos1": start, "pos2": start + span,
                  "props": {"fmt": rng.randrange(4)}}
        seq += 1
        # Concurrent editors lag each other by a small ref_seq window.
        lag = 0 if n_clients == 1 else rng.randrange(0, 4)
        out.append((op, seq, max(0, seq - 1 - lag), 1 + c,
                    max(0, seq - window)))
    return out


def matrix_storm(rows: int, cols: int, n_ops: int, seed: int = 0):
    """Spreadsheet op storm for a rows×cols SharedMatrix (BASELINE config
    #3): 6% row inserts, 4% col inserts, 2% row/col removes, 88% cell
    sets with row/col locality (edits cluster around a moving active
    cell, the way spreadsheet sessions behave).

    Yields ("insert_rows"|"insert_cols"|"remove_rows"|"remove_cols"|
    "set", args...) host commands for a driver loop; the dimensions are
    tracked so every command is valid at emission time."""
    rng = random.Random(seed)
    r, c = rows, cols
    active_r, active_c = 0, 0
    out = []
    for i in range(n_ops):
        roll = rng.random()
        if roll < 0.06:
            at = rng.randrange(r + 1)
            out.append(("insert_rows", at, 1))
            r += 1
        elif roll < 0.10:
            at = rng.randrange(c + 1)
            out.append(("insert_cols", at, 1))
            c += 1
        elif roll < 0.11 and r > 8:
            at = rng.randrange(r - 1)
            out.append(("remove_rows", at, 1))
            r -= 1
        elif roll < 0.12 and c > 8:
            at = rng.randrange(c - 1)
            out.append(("remove_cols", at, 1))
            c -= 1
        else:
            if rng.random() < 0.8:  # locality: stay near the active cell
                active_r = min(max(active_r + rng.randrange(-2, 3), 0),
                               r - 1)
                active_c = min(max(active_c + rng.randrange(-2, 3), 0),
                               c - 1)
            else:  # jump
                active_r, active_c = rng.randrange(r), rng.randrange(c)
            out.append(("set", active_r, active_c, i))
    return out


def directory_merge_script(n_ops: int, n_clients: int = 4, depth: int = 3,
                           fanout: int = 5, seed: int = 0):
    """Nested-subtree merge workload for SharedDirectory (BASELINE config
    #4): concurrent editors write keys into a tree of subdirectories
    (depth×fanout), with per-client working-directory locality, subtree
    creation, key deletes, and occasional whole-subtree clears.

    Returns [(client, path_tuple, command, *args)]."""
    rng = random.Random(seed)
    paths = [()]
    last = [()]
    for _ in range(depth):
        last = [p + (f"d{i}",) for p in last for i in range(fanout)]
        paths += last
    homes = [rng.choice(paths) for _ in range(n_clients)]
    out = []
    for i in range(n_ops):
        c = rng.randrange(n_clients)
        if rng.random() < 0.85:  # work near home
            path = homes[c]
        else:
            path = rng.choice(paths)
            homes[c] = path
        roll = rng.random()
        if roll < 0.80:
            out.append((c, path, "set", f"k{rng.randrange(32)}", i))
        elif roll < 0.90:
            out.append((c, path, "delete", f"k{rng.randrange(32)}"))
        elif roll < 0.97:
            sub = f"s{rng.randrange(8)}"
            out.append((c, path, "set_subdir_key", sub,
                        f"k{rng.randrange(8)}", i))
        else:
            out.append((c, path, "clear"))
    return out


def gen_traces(n_docs: int, n_ops: int, seed: int = 0):
    """Vectorized synthetic editing traces: per-doc sequential ops (the
    ProseMirror/Monaco replay shape): 70% insert (1-8 chars), 30% remove,
    positions uniform over the current doc length (tracked arithmetically).
    Returns numpy op columns [B, T] in mergetree.oppack layout."""
    rng = np.random.default_rng(seed)
    b, t = n_docs, n_ops
    kind = np.where(rng.random((b, t)) < 0.7, 1, 2).astype(np.int32)
    ins_len = rng.integers(1, 9, (b, t), dtype=np.int32)
    frac_pos = rng.random((b, t))
    frac_end = rng.random((b, t))

    pos1 = np.zeros((b, t), np.int32)
    pos2 = np.zeros((b, t), np.int32)
    lengths = np.zeros(b, np.int64)
    for j in range(t):
        kj = kind[:, j].copy()
        # Removes on empty docs become inserts.
        kj[(kj == 2) & (lengths < 2)] = 1
        kind[:, j] = kj
        is_ins = kj == 1
        p = (frac_pos[:, j] * (lengths + 1)).astype(np.int64)
        p = np.minimum(p, lengths)
        # Remove [p, e): p < length, e in (p, min(len, p+16)]
        pr = np.minimum(p, lengths - 1)
        pr[pr < 0] = 0
        span = np.minimum(lengths - pr, 16)
        e = pr + 1 + (frac_end[:, j] * span).astype(np.int64)
        e = np.minimum(e, lengths)
        e = np.maximum(e, pr + 1)
        pos1[:, j] = np.where(is_ins, p, pr).astype(np.int32)
        pos2[:, j] = np.where(is_ins, 0, e).astype(np.int32)
        lengths = np.where(is_ins, lengths + ins_len[:, j], lengths - (e - pr))
    seq = np.tile(np.arange(1, t + 1, dtype=np.int32), (b, 1))
    return {
        "kind": kind, "seq": seq, "ref_seq": seq - 1,
        "client": np.ones((b, t), np.int32),
        "pos1": pos1, "pos2": pos2,
        "op_id": np.tile(np.arange(t, dtype=np.int32), (b, 1)),
        "new_len": np.where(kind == 1, ins_len, 0).astype(np.int32),
        "local_seq": np.zeros((b, t), np.int32),
        "msn": seq - 1,
    }
