"""XLA device kernels over packed u32 words — the compute hot path.

This layer replaces the reference's native popcount kernels
(roaring/assembly_amd64.s: popcntAndSliceAsm and siblings, dispatched from
roaring.go:1266-1268,1431-1443): each fused op is one jitted XLA computation
``reduce(population_count(a ⊕ b))`` that XLA compiles to a single
VPU-resident loop over HBM — bitwise op, popcount, and row reduction fused,
nothing materialized.

Conventions:
- operands are u32 arrays, either ``[n_words]`` (one row) or
  ``[n_rows, n_words]`` (a row block); ops are elementwise in the last axis.
- counts are int32 per row (a slice row holds ≤ 2^20 bits, and even a full
  1 B-column row count fits int32); callers sum across rows/slices host-side
  in Python ints, or via psum on the mesh (pilosa_tpu.parallel).
- all entry points are jit-compiled with the op name static, so each
  (op, shape) pair compiles once and is cached.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_BITWISE = {
    "and": jnp.bitwise_and,
    "or": jnp.bitwise_or,
    "xor": jnp.bitwise_xor,
    "andnot": lambda a, b: jnp.bitwise_and(a, jnp.bitwise_not(b)),
}

OPS = tuple(_BITWISE)


@functools.partial(jax.jit, static_argnums=0)
def op_count_rows(op: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """Fused ``popcount(a ⊕ b)`` summed over the word axis → int32 per row."""
    words = _BITWISE[op](a, b)
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnums=0)
def _op_count_total_parts(op: str, a: jax.Array, b: jax.Array):
    words = _BITWISE[op](a, b)
    pc = jax.lax.population_count(words).astype(jnp.int32)
    row = jnp.sum(pc, axis=-1).ravel()
    # Split per-row counts into 16-bit halves before the cross-row reduce:
    # int64 is unavailable without x64, and a plain int32 sum overflows past
    # 2^31 total bits. Exact for ≤ 2^15 rows (lo ≤ 65535·2^15 < 2^31).
    # Stacked into ONE output: separate outputs each pay their own
    # host-fetch round trip.
    return jnp.stack([jnp.sum(row >> 16), jnp.sum(row & 0xFFFF)])


def op_count_total(op: str, a: jax.Array, b: jax.Array) -> int:
    """Fused ``popcount(a ⊕ b)`` reduced over every axis → exact Python int.

    The Count() building block: shape-agnostic, so callers can hand XLA the
    layout that tiles best. Per-row counts stay in int32 (each row ≤ 2^31
    bits); the cross-row total is recombined host-side so it cannot
    overflow. Supports up to 2^15 rows per call.
    """
    if a.ndim > 1 and a.shape[0] > (1 << 15):
        raise ValueError("op_count_total: more than 2^15 rows per call")
    hilo = np.asarray(_op_count_total_parts(op, a, b))
    return (int(hilo[0]) << 16) + int(hilo[1])


@jax.jit
def popcount_rows(a: jax.Array) -> jax.Array:
    """Per-row popcount → int32."""
    return jnp.sum(jax.lax.population_count(a).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnums=0)
def row_block_op_count(op: str, rows: jax.Array, other: jax.Array
                       ) -> jax.Array:
    """Count ``popcount(rows[i] ⊕ other)`` for every row of a block.

    The TopN building block: ``rows`` is ``[n_rows, n_words]`` (the candidate
    row block resident in HBM), ``other`` a single ``[n_words]`` filter row
    broadcast against it. Replaces the reference's sequential
    per-row IntersectionCount loop (fragment.go:560-614) with one
    vectorized pass — different algorithm, same semantics.
    """
    words = _BITWISE[op](rows, other[None, :])
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32), axis=-1)


# -- BSI bit-plane comparison circuit (storage.bsi row layout) ----------------

# Supported comparison operators; "><" (between) composes two circuits
# at the caller (>= low AND <= high).
BSI_OPS = ("==", "!=", "<", "<=", ">", ">=")


def _bsi_eq_lt_gt(pbits, planes):
    """One MSB→LSB pass of the bit-sliced comparison over stacked
    planes ``[depth+1, ..., W]`` (planes[0] = existence, planes[1+i] =
    offset-value bit i): (eq, lt, gt) matched-word triples. ``pbits``
    is the predicate's bits LSB-first (``[depth]`` u32 of 0/1) and is
    TRACED — one compiled program serves every predicate at a given
    depth. Plain jnp body: usable inside jit/shard_map contexts."""
    depth = planes.shape[0] - 1
    eq = planes[0]
    lt = jnp.zeros_like(eq)
    gt = jnp.zeros_like(eq)
    for i in reversed(range(depth)):
        plane = planes[1 + i]
        bit = pbits[i] != 0
        not_plane = jnp.bitwise_not(plane)
        lt = jnp.where(bit, lt | (eq & not_plane), lt)
        gt = jnp.where(bit, gt, gt | (eq & plane))
        eq = jnp.where(bit, eq & plane, eq & not_plane)
    return eq, lt, gt


def bsi_compare_select(op: str, pbits, planes):
    """Matched words of ``value OP predicate`` from the circuit triple
    (``op`` static; see _bsi_eq_lt_gt for the layout)."""
    eq, lt, gt = _bsi_eq_lt_gt(pbits, planes)
    if op == "==":
        return eq
    if op == "!=":
        return planes[0] & jnp.bitwise_not(eq)
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return gt
    if op == ">=":
        return gt | eq
    raise ValueError(f"invalid BSI op: {op!r}")


@functools.partial(jax.jit, static_argnums=0)
def bsi_compare_words(op: str, pbits: jax.Array,
                      planes: jax.Array) -> jax.Array:
    """The whole comparison circuit as ONE XLA program: stacked
    bit-plane words in, matched words out — the single-device form of
    parallel.mesh.bsi_range_sharded. Compiles once per (op, depth,
    shape); the predicate rides in as data."""
    return bsi_compare_select(op, pbits, planes)


def bsi_predicate_bits(upred: int, depth: int) -> np.ndarray:
    """LSB-first u32 bit vector of an offset-space predicate."""
    return np.array([(upred >> i) & 1 for i in range(depth)],
                    dtype=np.uint32)


def bsi_compare_words_host(op: str, upred: int,
                           planes: np.ndarray) -> np.ndarray:
    """Pure-numpy twin of bsi_compare_words (the no-device fallback;
    also the differential oracle for the XLA program)."""
    depth = planes.shape[0] - 1
    eq = planes[0].copy()
    lt = np.zeros_like(eq)
    gt = np.zeros_like(eq)
    for i in reversed(range(depth)):
        plane = planes[1 + i]
        if (upred >> i) & 1:
            lt |= eq & ~plane
            eq &= plane
        else:
            gt |= eq & plane
            eq &= ~plane
    if op == "==":
        return eq
    if op == "!=":
        return planes[0] & ~eq
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return gt
    if op == ">=":
        return gt | eq
    raise ValueError(f"invalid BSI op: {op!r}")

