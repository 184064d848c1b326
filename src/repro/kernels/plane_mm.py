"""Pallas TPU kernel: fused plane-pair matrix multiplication.

This is the TPU incarnation of bitSMM's MAC-with-accumulator: the plane
loop (the paper's temporal bit stream) runs *inside* the VMEM-resident
output tile, so partial products are accumulated on-chip and never touch
HBM — exactly the role of the per-MAC accumulator register in the
hardware. One kernel serves both execution levels:

* bit-plane level: planes in {0,1} (SBMwC) / {-1,0,+1} (Booth), weights
  ±2^(i+j);
* digit level (Booth recode): int8 digit planes, weights ±256^(i+j).

Tiling: grid (M/bm, N/bn, K/bk); each step loads an (P_a, bm, bk) slab of
activation planes and a (P_w, bk, bn) slab of weight planes into VMEM and
runs P_a*P_w MXU passes of (bm,bk)@(bk,bn) int8 matmuls, accumulating in
an int32 VMEM tile. MXU alignment: bm, bn multiples of 128; bk a multiple
of 128 (int8 lane width permitting).

VMEM budget at defaults (bm=bn=128, bk=512, 8x8 planes):
  A slab 8*128*512 B = 512 KiB + W slab 512 KiB + out 64 KiB  « 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _plane_mm_kernel(pw_ref, a_ref, w_ref, o_ref, *, n_a: int, n_w: int, unroll: bool):
    """One (bm, bn) output tile for one K-chunk; grid dim 2 iterates K."""
    k_step = pl.program_id(2)

    def pair(p, acc):
        i, j = p // n_w, p % n_w
        prod = jnp.dot(a_ref[i], w_ref[j], preferred_element_type=jnp.int32)
        return acc + pw_ref[p] * prod

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    if unroll:
        for p in range(n_a * n_w):
            acc = pair(p, acc)
    else:
        acc = lax.fori_loop(0, n_a * n_w, pair, acc)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(k_step > 0)
    def _accum():
        o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "unroll", "interpret"),
)
def plane_matmul(
    a_planes: jax.Array,
    w_planes: jax.Array,
    pair_weights: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    unroll: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """sum_{i,j} pair_weights[i*P_w+j] * (a_planes[i] @ w_planes[j]).

    a_planes: (P_a, M, K) int8;  w_planes: (P_w, K, N) int8;
    pair_weights: (P_a*P_w,) int32. Returns (M, N) int32 exactly.
    M, N, K must be multiples of bm, bn, bk (the ops.py wrapper pads).
    """
    n_a, m, k = a_planes.shape
    n_w, k2, n = w_planes.shape
    if k != k2:
        raise ValueError(f"K mismatch {a_planes.shape} vs {w_planes.shape}")
    if pair_weights.shape != (n_a * n_w,):
        raise ValueError("pair_weights must have shape (P_a * P_w,)")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shapes ({m},{n},{k}) must tile by ({bm},{bn},{bk})")

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_plane_mm_kernel, n_a=n_a, n_w=n_w, unroll=unroll)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # pair weights are read at a loop index: scalars from SMEM
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n_a, bm, bk), lambda mi, ni, ki: (0, mi, ki)),
            pl.BlockSpec((n_w, bk, bn), lambda mi, ni, ki: (0, ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
    )(pair_weights, a_planes, w_planes)
