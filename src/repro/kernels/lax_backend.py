"""Compiled ``jax.lax`` kernel backend: the bitset DFS without Pallas.

On this CPU container every Pallas invocation runs in interpret mode (the
kernel body executes in Python), so the paper's exponential hot loop is
dominated by interpreter overhead.  This module expresses the exact same
word-wise bitset DFS -- counting and listing -- in pure ``jax.lax``
(explicit-stack ``while_loop`` ``vmap``ped over the batch axis with masked
lanes), jit-compiled to native XLA:CPU/GPU code.  Same inputs, same
fixed-capacity ``(B, capacity, l)`` buffer contract, byte-identical
outputs; no Pallas, no interpreter.

Two structural changes make the compiled path fast:

* **Lifted base case** (shared with the Pallas kernels via
  :mod:`repro.kernels.common`): a branch closes as soon as *three* levels
  remain, with the closed-form triangle count / vectorized triangle emit
  over the candidate-induced subgraph -- one (T, T, W) word-AND + popcount
  (plus a (T, T, T) lex-order scatter when listing) instead of the deepest
  and widest scalar DFS level.  l <= 3 therefore never enters the loop at
  all: the whole tile is one fused vectorized op.
* **Frontier-vectorized stepping**: the DFS stack stores *todo* frontier
  bitsets rather than cursors; each iteration extracts the lowest set bit
  (word-parallel), so the loop runs one iteration per actual branch, not
  per vertex slot.  Every iteration is branch-free (``where``-selected
  push/close/pop), which is exactly what ``vmap`` wants: lanes that
  finished early ride along masked instead of forcing per-lane ``cond``
  branches into ``select``-both-sides.

Batch hygiene: callers stream many distinct batch sizes (ragged tails,
hypothesis graphs), and XLA compiles one executable per shape -- so the
public entry points pad the batch axis up to a power of two (zero-``cand``
lanes are exactly count-neutral and emit nothing) and chunk very large
(B, T) combinations to bound the transient (T, T, T) emit memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (
    WORD,
    edges_within,
    emit_edges,
    emit_frontier,
    emit_triangles,
    gt_masks_np,
    num_words,
    popcount,
    triangles_within,
)

#: soft cap (bytes) on the per-chunk transient emit mask; the (T, T, T)
#: int32 lex-order scatter is the largest intermediate of the listing path
_EMIT_BYTES_BUDGET = 256 << 20


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pad_lanes(A, cand, B_to: int):
    """Zero-pad the batch axis to ``B_to`` lanes (cand == 0 is neutral)."""
    pad = B_to - A.shape[0]
    if pad == 0:
        return A, cand
    A = jnp.pad(A, ((0, pad), (0, 0), (0, 0)))
    cand = jnp.pad(cand, ((0, pad), (0, 0)))
    return A, cand


def _list_chunk_lanes(T: int, l: int) -> int:
    """Lanes per jitted listing call so the packed (T, T, W) pair
    intersections and per-slot gather transients of the emit stay within
    the budget; always a power of two >= 1."""
    per_lane = (T * T) * (T // 32 * 8 + 16) + 64
    lanes = max(1, _EMIT_BYTES_BUDGET // per_lane)
    p = 1
    while p * 2 <= lanes:
        p *= 2
    return min(p, 1024)


def _lowest_set(todo):
    """Extract the lowest set bit of a packed (W,) bitset.

    Returns (any_bit bool, v int32 vertex id, after (W,) todo minus v).
    With an empty todo: any_bit False, v out of range, after all-zero --
    callers mask on any_bit.
    """
    nz = todo != jnp.uint32(0)
    any_bit = nz.any()
    w_idx = jnp.argmax(nz).astype(jnp.int32)
    word = todo[w_idx]
    lsb = word & (jnp.uint32(0) - word)
    tz = popcount(lsb - jnp.uint32(1)).astype(jnp.int32)
    v = w_idx * WORD + tz
    after = todo.at[w_idx].set(word & (word - jnp.uint32(1)))
    return any_bit, v, after


# ---------------------------------------------------------------------------
# counting
# ---------------------------------------------------------------------------


def _count_tile_dfs(A, cand, gt, l: int):
    """Per-tile l-clique count, l >= 4: explicit-todo-stack DFS closed two
    levels early by the triangle form.  Uniform branch-free iteration:
    consume the lowest frontier bit, close its sub-branch when three
    levels would remain, push otherwise, pop on an empty frontier."""
    W = cand.shape[0]
    S = l - 3  # stack depths 0..l-4; a sub-branch at 3 remaining closes
    stack0 = jnp.zeros((S, W), dtype=jnp.uint32).at[0].set(cand)
    state0 = (jnp.int32(0), stack0, jnp.uint32(0))

    def cond(state):
        return state[0] >= 0

    def body(state):
        depth, stack, count = state
        todo = jax.lax.dynamic_index_in_dim(stack, depth, 0, keepdims=False)
        any_bit, v, after = _lowest_set(todo)
        row_v = jax.lax.dynamic_index_in_dim(A, v, 0, keepdims=False)
        sub = after & row_v            # cand & N(v) & gt(v)
        closing = depth == (l - 4)     # sub would have 3 levels remaining
        tri = triangles_within(A, sub, gt)
        count = count + jnp.where(any_bit & closing, tri, jnp.uint32(0))
        nsub = popcount(sub).sum().astype(jnp.int32)
        push = any_bit & (~closing) & (nsub >= l - depth - 1)
        stack = jax.lax.dynamic_update_index_in_dim(stack, after, depth, 0)
        nxt = jnp.where(push, depth + 1, depth)
        stack = jax.lax.dynamic_update_index_in_dim(
            stack, jnp.where(push, sub, after), nxt, 0)
        depth = jnp.where(any_bit, jnp.where(push, depth + 1, depth),
                          depth - 1)
        return depth, stack, count

    _, _, count = jax.lax.while_loop(cond, body, state0)
    return count


@functools.partial(jax.jit, static_argnames=("l",))
def _count_batch(A, cand, l: int):
    B, T, W = A.shape
    gt = jnp.asarray(gt_masks_np(T))
    if l == 1:
        return popcount(cand).sum(-1).astype(jnp.uint32)
    if l == 2:
        return jax.vmap(lambda a, c: edges_within(a, c, gt))(A, cand)
    if l == 3:
        return jax.vmap(lambda a, c: triangles_within(a, c, gt))(A, cand)
    return jax.vmap(lambda a, c: _count_tile_dfs(a, c, gt, l))(A, cand)


def count_tiles(A: jax.Array, cand: jax.Array, l: int) -> jax.Array:
    """Count l-cliques per tile. (B,T,W) uint32 x (B,W) uint32 -> (B,) u32.

    Same contract as the Pallas kernels, compiled to native code.
    """
    if l < 1:
        raise ValueError("lax counting backend requires l >= 1")
    B, T, W = A.shape
    assert W == num_words(T) and cand.shape == (B, W)
    Bp = _pow2_ceil(max(B, 1))
    A, cand = _pad_lanes(jnp.asarray(A), jnp.asarray(cand), Bp)
    return _count_batch(A, cand, l)[:B]


# ---------------------------------------------------------------------------
# listing
# ---------------------------------------------------------------------------


def _list_tile_dfs(A, cand, gt, l: int, capacity: int):
    """Per-tile listing, l >= 4: same DFS walk as counting but the close
    scatters the whole *edge* frontier (u', w') of the sub-branch into the
    fixed-capacity buffer, prefixed by the stacked branch vertices.

    The close fires at two-remaining rather than the counting path's
    three-remaining: the emit runs on *every* loop iteration (vmap turns a
    ``cond`` into compute-both-sides), so its per-step footprint must stay
    (T, T)-shaped -- the dense (T, T, T) triangle scatter is reserved for
    the l == 3 top level where it runs exactly once per tile.  Relative to
    the pre-lift kernel this still deletes the deepest scalar level: the
    old DFS stepped vertex-by-vertex through two-remaining and only
    vectorized the final one-remaining frontier."""
    W = cand.shape[0]
    S = l - 2  # stack depths 0..l-3; a sub-branch at 2 remaining closes
    stack0 = jnp.zeros((S, W), dtype=jnp.uint32).at[0].set(cand)
    prefix0 = jnp.zeros((S,), dtype=jnp.int32)
    buf0 = jnp.zeros((capacity, l), dtype=jnp.int32)
    state0 = (jnp.int32(0), stack0, prefix0, buf0, jnp.uint32(0))

    def cond(state):
        return state[0] >= 0

    def body(state):
        depth, stack, prefix, buf, count = state
        todo = jax.lax.dynamic_index_in_dim(stack, depth, 0, keepdims=False)
        any_bit, v, after = _lowest_set(todo)
        row_v = jax.lax.dynamic_index_in_dim(A, v, 0, keepdims=False)
        sub = after & row_v
        closing = depth == (l - 3)
        prefix = jax.lax.dynamic_update_index_in_dim(prefix, v, depth, 0)
        # emission is unconditional but masked: a zeroed frontier scatters
        # nothing and leaves count unchanged (vmap-friendly, no cond)
        emit_cand = jnp.where(any_bit & closing, sub, jnp.uint32(0))
        buf, count = emit_edges(
            buf, count, A, emit_cand, gt, prefix,
            l=l, T=A.shape[0], capacity=capacity)
        nsub = popcount(sub).sum().astype(jnp.int32)
        push = any_bit & (~closing) & (nsub >= l - depth - 1)
        stack = jax.lax.dynamic_update_index_in_dim(stack, after, depth, 0)
        nxt = jnp.where(push, depth + 1, depth)
        stack = jax.lax.dynamic_update_index_in_dim(
            stack, jnp.where(push, sub, after), nxt, 0)
        depth = jnp.where(any_bit, jnp.where(push, depth + 1, depth),
                          depth - 1)
        return depth, stack, prefix, buf, count

    _, _, _, buf, count = jax.lax.while_loop(cond, body, state0)
    return buf, count


def _list_batch(A, cand, l: int, capacity: int):
    B, T, W = A.shape
    gt = jnp.asarray(gt_masks_np(T))
    zbuf = jnp.zeros((capacity, l), dtype=jnp.int32)
    zpfx = jnp.zeros((max(l, 1),), dtype=jnp.int32)
    zcnt = jnp.uint32(0)
    if l == 1:
        def one(a, c):
            return emit_frontier(zbuf, zcnt, c, zpfx, l=l, T=T,
                                 capacity=capacity)
    elif l == 2:
        def one(a, c):
            return emit_edges(zbuf, zcnt, a, c, gt, zpfx, l=l, T=T,
                              capacity=capacity)
    elif l == 3:
        def one(a, c):
            return emit_triangles(zbuf, zcnt, a, c, gt, zpfx, l=l, T=T,
                                  capacity=capacity)
    else:
        def one(a, c):
            return _list_tile_dfs(a, c, gt, l, capacity)
    buf, count = jax.vmap(one)(A, cand)
    overflow = (count > jnp.uint32(capacity)).astype(jnp.uint32)
    return buf, count, overflow


@functools.lru_cache(maxsize=None)
def _list_step(l: int):
    """The jitted :func:`_list_batch` for ``l``, compiled as the module
    ``jit_list_l<l>`` so a profiler trace names it."""

    def step(A, cand, capacity: int):
        return _list_batch(A, cand, l, capacity)

    step.__name__ = step.__qualname__ = f"list_l{l}"
    return jax.jit(step, static_argnames=("capacity",))


def list_tiles(A: jax.Array, cand: jax.Array, l: int, capacity: int):
    """List l-cliques per tile into fixed-capacity local-id buffers.

    Same contract (and byte-identical buffers) as
    :func:`repro.kernels.clique_list.clique_list_tiles`: returns
    (out (B, capacity, l) int32, count (B,) uint32 TRUE totals,
    overflow (B,) uint32).  Large (B, T) combinations are processed in
    equal power-of-two lane chunks so the transient (T, T, T) emit mask
    stays within a fixed memory budget; chunking is invisible in the
    output.
    """
    if l < 1:
        raise ValueError("listing kernel requires l >= 1")
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    B, T, W = A.shape
    assert W == num_words(T) and cand.shape == (B, W)
    A = jnp.asarray(A)
    cand = jnp.asarray(cand)
    chunk = min(_pow2_ceil(max(B, 1)), _list_chunk_lanes(T, l))
    Bp = -(-B // chunk) * chunk
    A, cand = _pad_lanes(A, cand, Bp)
    step = _list_step(l)
    outs = [
        step(A[i:i + chunk], cand[i:i + chunk], capacity=capacity)
        for i in range(0, Bp, chunk)
    ]
    if len(outs) == 1:
        buf, cnt, ovf = outs[0]
    else:
        buf = jnp.concatenate([o[0] for o in outs])
        cnt = jnp.concatenate([o[1] for o in outs])
        ovf = jnp.concatenate([o[2] for o in outs])
    return buf[:B], cnt[:B], ovf[:B]


__all__ = ["count_tiles", "list_tiles"]
