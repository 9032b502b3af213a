"""Tile-batch packing: the pure-NumPy half of the pipeline, and the worker
processes that run it off the dispatching interpreter.

:func:`pack_dense` turns one chunk of tiles -- its members ``V`` (B, T),
sizes ``sz`` and, for the truss family, per-tile pi_tau thresholds -- into
the kernels' packed form, given the graph's :class:`EdgeIndex`.  Pairwise
adjacency is one ``searchsorted`` over the canonical edge keys; the hybrid
ordering colors and relabels each tile; bit packing goes straight into the
uint32 word layout.  :mod:`repro.core.pipeline` calls it inline, and
:class:`PackPool` runs it in worker processes (DESIGN.md section 8).

This module imports only NumPy and the NumPy side of the package
(``graph``, ``bitops``): a worker process starts from it and never loads
JAX, whose TPU runtime belongs to the parent alone.
"""
from __future__ import annotations

import atexit
import os
import pickle
import queue
import signal
import subprocess
import sys
import threading
import time
import traceback
import weakref
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from .bitops import pack_bits
from .graph import ragged_expand

# pairwise-expansion budget per internal slice (caps peak index memory)
_PAIR_BUDGET = 4_000_000


class EdgeIndex(NamedTuple):
    """What packing needs of the graph: the sorted canonical edge keys
    ``u * n + v`` and, for the truss family, the pi_tau rank per edge id
    (``erank``; None for the color family)."""
    ekeys: np.ndarray
    erank: Optional[np.ndarray]
    n: int
    m: int


def edge_lookup(ekeys: np.ndarray, m: int, n: int, lo: np.ndarray,
                hi: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Membership probe for canonical pairs (lo < hi) against the sorted
    edge keys ``u * n + v``.

    Returns (hit mask, position in the sorted key array) -- position is
    only meaningful where ``hit``; callers needing the edge id (e.g. for a
    pi_tau rank lookup) index with it.  This is the single home of the
    searchsorted/clip/equality idiom; keep the key encoding in sync with
    :meth:`repro.core.graph.Graph.edge_keys`.
    """
    keys = lo * np.int64(n) + hi
    p = np.searchsorted(ekeys, keys)
    p = np.clip(p, 0, max(m - 1, 0))
    hit = (ekeys[p] == keys) if m else np.zeros(0, dtype=bool)
    return hit, p


def dense_pairs(V: np.ndarray, sz: np.ndarray, thresh: Optional[np.ndarray],
                index: EdgeIndex):
    """Dense bool adjacency for one chunk of tiles.

    ``V`` (B, T) holds each tile's members (zero past ``sz``); ``thresh``
    (B,) is each tile's pi_tau rank for the truss family, where a member
    pair is adjacent only through an edge ranked after it.  Returns (D
    (B,T,T) bool, nedges, pairs) with ``pairs = (tile, i, j, pair_rank)``
    for i<j adjacent pairs (pair_rank is None for the color family).
    """
    B, T = V.shape
    truss = index.erank is not None
    D = np.zeros((B, T, T), dtype=bool)
    po_l: List[np.ndarray] = []
    pi_l: List[np.ndarray] = []
    pj_l: List[np.ndarray] = []
    pr_l: List[np.ndarray] = []
    # slice the chunk so the i x j pair expansion stays within budget
    start = 0
    quad = sz.astype(np.int64) ** 2
    cum = np.cumsum(quad)
    while start < B:
        stop = int(np.searchsorted(
            cum, (cum[start - 1] if start else 0) + _PAIR_BUDGET) + 1)
        stop = max(start + 1, min(stop, B))
        so = sz[start:stop]
        powner, ppos = ragged_expand(so * so)
        s_rep = so[powner]
        i = ppos // s_rep
        j = ppos % s_rep
        keep = i < j
        powner, i, j = powner[keep], i[keep], j[keep]
        powner_g = powner + start
        gu = V[powner_g, i]
        gv = V[powner_g, j]
        hit, p = edge_lookup(index.ekeys, index.m, index.n,
                             np.minimum(gu, gv), np.maximum(gu, gv))
        if truss:
            hit &= index.erank[p] > thresh[powner_g]
        powner_g, i, j, p = powner_g[hit], i[hit], j[hit], p[hit]
        D[powner_g, i, j] = True
        D[powner_g, j, i] = True
        po_l.append(powner_g)
        pi_l.append(i)
        pj_l.append(j)
        if truss:
            pr_l.append(index.erank[p])
        start = stop
    po = np.concatenate(po_l) if po_l else np.zeros(0, np.int64)
    pi = np.concatenate(pi_l) if pi_l else np.zeros(0, np.int64)
    pj = np.concatenate(pj_l) if pj_l else np.zeros(0, np.int64)
    pr = (np.concatenate(pr_l) if pr_l else np.zeros(0, np.int64)) \
        if truss else None
    nedges = np.bincount(po, minlength=B).astype(np.int64)
    return D, nedges, (po, pi, pj, pr)


def greedy_color(D: np.ndarray, sz: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized-across-tiles greedy coloring, replicating ``_local_color``.

    Processing order per tile: degree descending, local id descending (the
    reference's ``sorted(..., reverse=True)`` tie-break); color = smallest
    positive value unused by any tile-neighbor.  Returns (colors (B,T) with
    0 on padding, perm (B,T) = relabel order: color desc, id asc, padding
    last).
    """
    B, T, _ = D.shape
    ids = np.broadcast_to(np.arange(T, dtype=np.int64), (B, T))
    deg = D.sum(-1).astype(np.int64)
    real = ids < sz[:, None]
    degk = np.where(real, deg, -1)
    order = np.lexsort((-ids, -degk), axis=1)
    colors = np.zeros((B, T), dtype=np.int64)
    for t in range(int(sz.max(initial=0))):
        # step t touches only tiles with a t-th vertex; indexing the active
        # subset keeps per-step work O(#active * T), not O(B * T) -- the
        # dominant win on mixed-size bins (bench_pipeline_stages)
        act = np.nonzero(t < sz)[0]
        v = order[act, t]
        nb = D[act, v]                                    # (A, T)
        ncol = np.where(nb, colors[act], 0)
        present = np.zeros((act.size, T + 2), dtype=bool)
        present[np.arange(act.size)[:, None], ncol] = True
        mex = np.argmin(present[:, 1:], axis=1) + 1       # first free >= 1
        colors[act, v] = mex
    perm = np.lexsort((ids, -colors), axis=1)
    return colors, perm


def relabel(D, V, colors, perm):
    """Permute each tile's slots by ``perm``: (D, V, colors) relabelled."""
    # one flat gather for the (B, T, T) permute (measurably faster than
    # both a chained take_along_axis and the triple-broadcast fancy index)
    B, T = V.shape
    idx = (perm[:, :, None] * T + perm[:, None, :]).reshape(B, T * T)
    D2 = np.take_along_axis(D.reshape(B, T * T), idx, axis=1) \
        .reshape(B, T, T)
    V2 = np.take_along_axis(V, perm, axis=1)
    C2 = np.take_along_axis(colors, perm, axis=1)
    return D2, V2, C2


def pack_dense(V: np.ndarray, sz: np.ndarray, thresh: Optional[np.ndarray],
               index: EdgeIndex, hybrid: bool):
    """One chunk in the kernels' form: (A (B,T,W) uint32 adjacency bitsets,
    cand (B,W) uint32 candidate masks, V (B,T) members after any hybrid
    relabel, nedges (B,))."""
    D, nedges, _ = dense_pairs(V, sz, thresh, index)
    if hybrid:
        colors, perm = greedy_color(D, sz)
        D, V, _ = relabel(D, V, colors, perm)
    T = V.shape[1]
    cand = pack_bits(np.arange(T)[None, :] < sz[:, None])
    return pack_bits(D), cand, V, nedges


# ---------------------------------------------------------------------------
# worker processes
# ---------------------------------------------------------------------------

class PackWorkerError(RuntimeError):
    """A pack worker process failed: it died, or its packing raised."""


# the directory holding the ``repro`` package, put first on the worker's path
_SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# a fresh interpreter that imports this module and nothing of the parent's
# __main__ (multiprocessing's spawn and forkserver re-import the parent's
# main module, which may import JAX)
BOOT = ("import sys; sys.path.insert(0, sys.argv[1]); "
        "from repro.core.packing import serve; serve()")


def serve() -> None:
    """Worker loop: read pickled requests on stdin, reply on stdout.

    ``("index", EdgeIndex)`` replaces the index the worker holds (no
    reply); ``("pack", V, sz, thresh, hybrid)`` replies ``("ok", A, cand,
    V, nedges, seconds)`` or ``("err", traceback)``.  EOF ends the loop, so
    a worker exits with its parent's pipe.
    """
    signal.signal(signal.SIGINT, signal.SIG_IGN)  # the parent handles ^C
    rd, wr = sys.stdin.buffer, sys.stdout.buffer
    sys.stdout = sys.stderr  # nothing else may write on the reply pipe
    index = None
    while True:
        try:
            msg = pickle.load(rd)
        except EOFError:
            return
        if msg[0] == "index":
            index = msg[1]
            continue
        _, V, sz, thresh, hybrid = msg
        t0 = time.perf_counter()
        try:
            reply = ("ok",) + pack_dense(V, sz, thresh, index, hybrid) \
                + (time.perf_counter() - t0,)
        except Exception:  # reported to the parent, which raises it
            reply = ("err", traceback.format_exc())
        pickle.dump(reply, wr, protocol=pickle.HIGHEST_PROTOCOL)
        wr.flush()


class _Worker:
    """One worker process and the index it holds (weakly, by identity)."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, "-c", BOOT, _SRC],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.held: Optional[Tuple] = None
        self.index_sends = 0
        self.dead = False

    def _holds(self, index: EdgeIndex) -> bool:
        if self.held is None:
            return False
        ekeys, erank, n, m = self.held
        return (ekeys() is index.ekeys and n == index.n and m == index.m
                and (erank() if erank is not None else None) is index.erank)

    def call(self, index: EdgeIndex, item: Tuple):
        if self.dead:
            raise PackWorkerError(
                f"pack worker {self.proc.pid} exited with code "
                f"{self.proc.poll()}")
        try:
            if not self._holds(index):
                pickle.dump(("index", index), self.proc.stdin,
                            protocol=pickle.HIGHEST_PROTOCOL)
                self.index_sends += 1
                self.held = (weakref.ref(index.ekeys),
                             None if index.erank is None
                             else weakref.ref(index.erank),
                             index.n, index.m)
            pickle.dump(("pack",) + item, self.proc.stdin,
                        protocol=pickle.HIGHEST_PROTOCOL)
            self.proc.stdin.flush()
            reply = pickle.load(self.proc.stdout)
        except (OSError, ValueError, EOFError, pickle.UnpicklingError) as exc:
            # a dead process: broken pipe, EOF or a truncated reply (a pipe
            # closed by close() reads as ValueError)
            self.dead = True
            self.proc.kill()
            raise PackWorkerError(
                f"pack worker {self.proc.pid} exited with code "
                f"{self.proc.wait()}") from exc
        if reply[0] != "ok":
            raise PackWorkerError(f"pack worker {self.proc.pid} failed:\n"
                                  f"{reply[1]}")
        return reply[1:]

    def close(self) -> None:
        self.proc.kill()
        self.proc.wait()
        self.proc.stdin.close()
        self.proc.stdout.close()


class PackPool:
    """Pack worker processes, lent out one call at a time.

    :meth:`pack` blocks its calling thread on a worker's pipe with the
    interpreter lock released, so the packing itself runs outside this
    interpreter; a stream drives as many calls at once as it has threads.
    A worker that dies fails every call routed to it (none waits on it),
    and the pool reads :attr:`broken` from then on.
    """

    def __init__(self, workers: int):
        self._all: List[_Worker] = []
        self._idle: "queue.SimpleQueue[_Worker]" = queue.SimpleQueue()
        self.grow(workers)

    def grow(self, workers: int) -> None:
        """Start processes until the pool holds ``workers``."""
        while len(self._all) < workers:
            w = _Worker()
            self._all.append(w)
            self._idle.put(w)

    @property
    def broken(self) -> bool:
        """True once a worker process has died."""
        return any(w.dead for w in self._all)

    @property
    def index_sends(self) -> int:
        """Edge indexes shipped to the workers so far (one per worker and
        table it packs for)."""
        return sum(w.index_sends for w in self._all)

    def pids(self) -> List[int]:
        """The worker processes' ids."""
        return [w.proc.pid for w in self._all]

    def pack(self, index: EdgeIndex, V: np.ndarray, sz: np.ndarray,
             thresh: Optional[np.ndarray], hybrid: bool) -> Tuple:
        """Pack one chunk (see :func:`pack_dense`) in an idle worker
        process: ``(A, cand, V, nedges, seconds)``, the last the worker's
        own packing seconds."""
        w = self._idle.get()
        try:
            return w.call(index, (V, sz, thresh, hybrid))
        finally:
            self._idle.put(w)

    def close(self) -> None:
        """End the worker processes."""
        for w in self._all:
            w.close()


_POOL: Optional[PackPool] = None
_POOL_LOCK = threading.Lock()


def pool(workers: int) -> PackPool:
    """This interpreter's pool, with at least ``workers`` processes.

    The pool outlives the streams that use it, so only the first stream
    pays for starting processes; a worker receives a graph's edge index
    once, on the first chunk of a table other than the one it holds.  A
    broken pool is replaced.  :func:`shutdown` (also run at interpreter
    exit) ends it.
    """
    global _POOL
    with _POOL_LOCK:
        if _POOL is not None and _POOL.broken:
            _POOL.close()
            _POOL = None
        if _POOL is None:
            _POOL = PackPool(workers)
        else:
            _POOL.grow(workers)
        return _POOL


def shutdown() -> None:
    """End every pack worker process of this interpreter."""
    global _POOL
    with _POOL_LOCK:
        p, _POOL = _POOL, None
    if p is not None:
        p.close()


atexit.register(shutdown)


def default_proc_workers() -> int:
    """Auto worker-process count: the cores this process may run on, less
    one for the dispatching thread and one for the runtime's own, at most
    16 (11 on a 13-core host)."""
    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    return max(1, min(16, cores - 2))
