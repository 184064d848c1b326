"""Pallas TPU kernel: plane-pair matmul over *packed* bit-planes.

Same contraction as :mod:`repro.kernels.plane_mm` — sum_{i,j} pw[i,j] *
(A_i @ W_j) with an int32 VMEM accumulator — but the operands arrive as
bit-packed int32 words (32 plane values per word, planar layout; see
DESIGN.md §"Packed plane format") and are unpacked *on-chip* with
shift/mask VPU ops right before the MXU passes. At 8×8-bit SBMwC this
moves 8× fewer HBM bytes per operand than the unpacked int8 plane path
(Booth ternary: 4×, one extra sign word per 32 values); the paper's
bandwidth argument for bit-serial operand streams (and BISMO's packed
buffer layout) in Pallas form.

The planar word layout makes unpacking gather-free: word j bit t holds
the plane value at (padded, permuted) contraction index k = t*W + j, so
a (rows, bkw) word block expands to (rows, bk) by concatenating the 32
shift/mask chunks along the contraction axis. Both operands are packed
against the same global word count, so they agree on the K permutation
and the matmul needs no unpermute.

VMEM at defaults (bm=bn=128, bk=512, 8 binary planes/side): packed A
slab 8*128*16 int32 = 64 KiB + unpacked scratch planes 512 KiB per side
+ out 64 KiB — comfortably under budget; the HBM→VMEM traffic is what
shrinks by the packing factor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitplanes import WORD_BITS, PackedPlanes, occupancy_per_tile

#: (M, N, K) grid: output tiles are independent, K accumulates in scratch
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _expand_words(words: jax.Array, axis: int) -> jax.Array:
    """(.., W, ..) int32 words -> (.., 32*W, ..) {0,1} int32 along ``axis``."""
    chunks = [(words >> t) & 1 for t in range(WORD_BITS)]
    return jnp.concatenate(chunks, axis=axis)


def _packed_mm_kernel(
    *refs, n_a: int, n_w: int, a_signed: bool, w_signed: bool, gated: bool
):
    """One (bm, bn) output tile for one packed-K chunk; grid dim 2 is K.

    ``gated``: occupancy-gated sparse plane execution (DESIGN.md §8) — an
    SMEM-prefetched per-(weight plane, K step) occupancy bitmap rides in
    as the first ref, each weight plane's MXU pass is predicated on it
    AND'd with the *dynamic* activation-plane occupancy (a word-level
    non-zero test on the packed A slab already in VMEM), and the
    accumulator moves into the output ref so skipped pairs cost exactly
    one predicate check. Zero planes contribute zero to the sum, so the
    gated result is bit-identical to the dense one.
    """
    it = iter(refs)
    occ_ref = next(it) if gated else None  # SMEM (n_w, nk) weight occupancy
    pw_ref = next(it)
    am_ref = next(it)
    as_ref = next(it) if a_signed else None
    wm_ref = next(it)
    ws_ref = next(it) if w_signed else None
    o_ref = next(it)
    k_step = pl.program_id(2)

    # Unpack every plane once (shift/mask on the VPU), not once per pair.
    def unpack_a(i):
        v = _expand_words(am_ref[i], axis=1)  # (bm, bkw) -> (bm, bk)
        if a_signed:
            v = v - 2 * _expand_words(as_ref[i], axis=1)
        return v.astype(jnp.int8)

    def unpack_w(j):
        v = _expand_words(wm_ref[j], axis=0)  # (bkw, bn) -> (bk, bn)
        if w_signed:
            v = v - 2 * _expand_words(ws_ref[j], axis=0)
        return v.astype(jnp.int8)

    a_planes = [unpack_a(i) for i in range(n_a)]
    w_planes = [unpack_w(j) for j in range(n_w)]

    if gated:
        @pl.when(k_step == 0)
        def _zero():
            o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

        for i in range(n_a):
            # dynamic activation occupancy: one word-level test per plane
            # (mag words cover Booth too — a set sign bit implies mag)
            occ_a = jnp.any(am_ref[i] != 0)
            for j in range(n_w):
                pred = jnp.logical_and(occ_a, occ_ref[j, k_step] != 0)

                @pl.when(pred)
                def _pass(i=i, j=j):
                    prod = jnp.dot(
                        a_planes[i], w_planes[j], preferred_element_type=jnp.int32
                    )
                    o_ref[...] += pw_ref[i * n_w + j] * prod

        return

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for i in range(n_a):
        for j in range(n_w):
            prod = jnp.dot(a_planes[i], w_planes[j], preferred_element_type=jnp.int32)
            acc = acc + pw_ref[i * n_w + j] * prod

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(k_step > 0)
    def _accum():
        o_ref[...] += acc


def validate_packed_operands(
    packed_a: PackedPlanes, packed_w: PackedPlanes, pair_weights: jax.Array
) -> None:
    """Shared operand checks (also used by the jnp dispatch path, so the
    contract errors are backend-independent)."""
    if packed_a.axis != 2 or packed_w.axis != 1:
        raise ValueError(
            f"expected A packed on axis 2 and W on axis 1, got "
            f"{packed_a.axis} / {packed_w.axis}"
        )
    if packed_a.k != packed_w.k or packed_a.n_words != packed_w.n_words:
        raise ValueError(
            f"operands packed against different K: "
            f"{packed_a.k}/{packed_a.n_words} vs {packed_w.k}/{packed_w.n_words}"
        )
    if packed_a.block != packed_w.block:
        # Any *shared* word layout contracts matching K subsets per word
        # slice; mixing global-planar with blocked operands would not.
        raise ValueError(
            f"operands packed with different layouts: block="
            f"{packed_a.block} vs {packed_w.block}"
        )
    n_a = packed_a.mag.shape[0]
    n_w = packed_w.mag.shape[0]
    if pair_weights.shape != (n_a * n_w,):
        raise ValueError("pair_weights must have shape (P_a * P_w,)")


def _pad_dim(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    rem = (-x.shape[axis]) % mult
    if not rem:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "gate", "interpret")
)
def plane_matmul_packed(
    packed_a: PackedPlanes,
    packed_w: PackedPlanes,
    pair_weights: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    gate: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """sum_{i,j} pair_weights[i*P_w+j] * (A_i @ W_j) from packed planes.

    ``packed_a``: words (P_a, M, KW), axis=2 (K packed along the last axis);
    ``packed_w``: words (P_w, KW, N), axis=1 (K packed along the rows);
    both sides packed against the same K (same KW). Returns (M, N) int32,
    bit-exact vs ``ref.plane_matmul_ref`` on the unpacked planes. Inputs
    are padded here (zero words are zero planes — inert), the output is
    sliced back; ``bk`` must be a multiple of 32.

    ``gate=True`` enables occupancy-gated sparse plane execution: the
    weight operand's pack-time occupancy bitmap is reduced onto the K
    grid, prefetched to SMEM, and every plane-pair MXU pass is predicated
    on it AND'd with dynamic activation-word occupancy — all-zero pairs
    cost a predicate check instead of an MXU pass, and the result stays
    bit-identical (zero planes contribute zero).
    """
    if bk % WORD_BITS:
        raise ValueError(f"bk must be a multiple of {WORD_BITS}, got {bk}")
    validate_packed_operands(packed_a, packed_w, pair_weights)
    if gate and packed_w.occupancy is None:
        raise ValueError(
            "gate=True needs weight occupancy metadata; re-pack the weight "
            "operand (pack_planes computes it) or pass gate=False"
        )
    n_a, m, _ = packed_a.mag.shape
    n_w, _, n = packed_w.mag.shape
    bkw = bk // WORD_BITS
    a_signed = packed_a.sign is not None
    w_signed = packed_w.sign is not None

    def prep_a(x):
        return _pad_dim(_pad_dim(x, 1, bm), 2, bkw)

    def prep_w(x):
        return _pad_dim(_pad_dim(x, 1, bkw), 2, bn)

    am = prep_a(packed_a.mag)
    wm = prep_w(packed_w.mag)
    mp, kw = am.shape[1], am.shape[2]
    np_ = wm.shape[2]
    grid = (mp // bm, np_ // bn, kw // bkw)

    operands = [pair_weights, am]
    in_specs = [
        pl.BlockSpec((n_a * n_w,), lambda mi, ni, ki: (0,)),
        pl.BlockSpec((n_a, bm, bkw), lambda mi, ni, ki: (0, mi, ki)),
    ]
    if gate:
        # (P_w, nk) weight occupancy, whole array in SMEM for every step
        operands.insert(0, occupancy_per_tile(packed_w.occupancy, bkw))
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    if a_signed:
        operands.append(prep_a(packed_a.sign))
        in_specs.append(pl.BlockSpec((n_a, bm, bkw), lambda mi, ni, ki: (0, mi, ki)))
    operands.append(wm)
    in_specs.append(pl.BlockSpec((n_w, bkw, bn), lambda mi, ni, ki: (0, ki, ni)))
    if w_signed:
        operands.append(prep_w(packed_w.sign))
        in_specs.append(pl.BlockSpec((n_w, bkw, bn), lambda mi, ni, ki: (0, ki, ni)))

    kernel = functools.partial(
        _packed_mm_kernel,
        n_a=n_a,
        n_w=n_w,
        a_signed=a_signed,
        w_signed=w_signed,
        gated=gate,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)
    return out[:m, :n]
