"""Bitonic co-sort Pallas TPU kernel — the frontier-merge hot spot.

The paper's Challenge III is the cost of keeping the candidate queue in
strict order.  Our queue ops (core/queue.py) spend their time in two
``lax.sort`` passes of length L+C per step per walker.  This kernel performs
the (key, payload, payload) co-sort entirely inside VMEM with a bitonic
network, so a frontier merge is a single fused kernel invocation rather than
an XLA variadic-sort (which lowers to a serial sort per row on TPU).

Bitonic networks map beautifully onto the TPU vector unit because the
partner exchange ``i ↔ i^j`` for a power-of-two ``j`` is a static reshape +
flip — no gathers:

    (n,) -> (n / 2j, 2, j) -> flip middle axis -> (n,)

All log²(n)/2 passes run on (8, n/8)-shaped VMEM-resident registers; keys
are f32 distances, payloads int32 ids / meta bits.  Ties break on payload0
(id) for determinism, matching ``jax.lax.sort(num_keys=2)`` semantics.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call


def _xor_perm(x: jax.Array, j: int) -> jax.Array:
    """x[i ^ j] for power-of-two j, as reshape + flip (no gather)."""
    n = x.shape[-1]
    y = x.reshape(x.shape[:-1] + (n // (2 * j), 2, j))
    y = jnp.flip(y, axis=-2)
    return y.reshape(x.shape)


def _bitonic_pass(keys, p0, p1, k: int, j: int, n: int):
    idx = jax.lax.iota(jnp.int32, n)
    pk = _xor_perm(keys, j)
    pp0 = _xor_perm(p0, j)
    pp1 = _xor_perm(p1, j)
    asc = (idx & k) == 0           # ascending block?
    lower = (idx & j) == 0         # lane is the lower partner?
    take_min = asc == lower
    # partner is smaller when (key, payload0, payload1) orders it first;
    # p1 participates as the final tiebreak so the comparison is TOTAL —
    # otherwise a full (key, p0) tie with distinct p1 would duplicate one
    # lane's payload instead of exchanging (the classic bitonic tie bug)
    partner_first = (pk < keys) | (
        (pk == keys) & ((pp0 < p0) | ((pp0 == p0) & (pp1 < p1))))
    take_partner = jnp.where(take_min, partner_first, ~partner_first)
    keys = jnp.where(take_partner, pk, keys)
    p0 = jnp.where(take_partner, pp0, p0)
    p1 = jnp.where(take_partner, pp1, p1)
    return keys, p0, p1


def _sort_kernel(k_ref, p0_ref, p1_ref, ko_ref, p0o_ref, p1o_ref, *, n: int):
    keys = k_ref[0, :]
    p0 = p0_ref[0, :]
    p1 = p1_ref[0, :]
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            keys, p0, p1 = _bitonic_pass(keys, p0, p1, k, j, n)
            j //= 2
        k *= 2
    ko_ref[0, :] = keys
    p0o_ref[0, :] = p0
    p1o_ref[0, :] = p1


def sort_pairs(
    keys: jax.Array, p0: jax.Array, p1: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Row-wise ascending co-sort by (key, p0).  Shapes (B, n), n = 2**k.

    keys f32; p0/p1 int32 payloads.  Returns sorted (keys, p0, p1).
    """
    bsz, n = keys.shape
    assert n & (n - 1) == 0, f"bitonic length {n} must be a power of two"
    kernel = functools.partial(_sort_kernel, n=n)
    specs = [pl.BlockSpec((1, n), lambda b: (b, 0)) for _ in range(3)]
    return pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=specs,
        out_specs=tuple(specs),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, n), jnp.int32),
            jax.ShapeDtypeStruct((bsz, n), jnp.int32),
        ),
    )(keys.astype(jnp.float32), p0.astype(jnp.int32), p1.astype(jnp.int32))
