"""Pallas TPU kernel: fully-fused bit-serial linear layer.

The staged serving path violates the paper's operand-stream model twice
per projection: activations are decomposed into ``a_bits`` int8 plane
tensors in HBM (an 8x blow-up at 8 bits) before the matmul, and the
int32 accumulator is written to HBM and re-read by a separate XLA op for
the ``acc * a_scale * w_scale`` dequant. This kernel runs the whole
linear in one launch:

1. the raw quantized **int8 activation** tile (natural K order) is
   bit-sliced **on-chip** with shift/mask VPU ops — the same trick
   ``plane_mm_packed`` uses to unpack words, applied to live values;
2. the block-packed weight plane words (PR-1 format, ``block`` layout so
   whole-block word slices unpack to natural K order) are unpacked
   on-chip;
3. the ``P_a x P_w`` plane-pair MXU passes accumulate into an int32
   **VMEM scratch** tile across the K grid dimension;
4. at the last K step a fused epilogue applies ``a_scale[m] *
   w_scale[n]``, optional bias and activation (gelu/silu), and writes
   the output dtype (bf16) directly.

Plane tensors, packed activation words and int32 accumulators never
touch HBM: per projection the kernel reads int8 activations + packed
weight words + scales and writes bf16 — the bit-serial operand-stream
byte model of the paper (BISMO keeps the bit-slicing in the fetch stage
and TMA folds the rescale into the PE datapath for the same reason).

VMEM at defaults (bm=bn=128, bk=512, 8x8 bits, booth): x tile 64 KiB +
packed W words 2*8*16*128*4 = 64 KiB + unpacked W scratch planes 512 KiB
+ int32 acc 64 KiB + epilogue vectors < 1 KiB — comfortably in budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitplanes import WORD_BITS, PackedPlanes, occupancy_per_tile
from repro.kernels.plane_mm_packed import _COMPILER_PARAMS, _expand_words, _pad_dim

ACTIVATIONS = {
    "none": lambda x: x,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def slice_activation_planes(x: jax.Array, a_bits: int, variant: str) -> list[jax.Array]:
    """Bit-slice live integer activation values into their bit-planes.

    The in-kernel mirror of :func:`repro.core.bitplanes.to_bitplanes`
    (same shift/mask arithmetic, so the plane values — and hence the
    accumulator — are bit-identical to the staged path), producing a list
    of int8 planes instead of a stacked HBM tensor.
    """
    u = x.astype(jnp.int32) & ((1 << a_bits) - 1)  # two's-complement low bits
    cur = [(u >> i) & 1 for i in range(a_bits)]
    if variant == "booth":
        planes = [(cur[i - 1] if i else 0) - cur[i] for i in range(a_bits)]
    else:  # sbmwc / unsigned share raw bit planes; only the weights differ
        planes = cur
    return [p.astype(jnp.int8) for p in planes]


def _fused_kernel(
    *refs,
    a_bits: int,
    n_w: int,
    variant: str,
    w_signed: bool,
    has_epilogue: bool,
    has_bias: bool,
    activation: str,
    nk: int,
    gated: bool,
):
    """One (bm, bn) output tile; grid dim 2 walks the K pack blocks.

    ``gated``: occupancy-gated sparse plane execution — the static
    per-(weight plane, K block) occupancy bitmap arrives in SMEM as the
    first ref; activation-plane occupancy is computed *in-kernel* from the
    just-sliced planes (the operand is live int8 — there is no pack-time
    metadata to prefetch). Each plane-pair MXU pass is predicated on the
    AND of the two, accumulating into the VMEM scratch, so an all-zero
    pair costs one predicate check. Bit-identical to dense execution.
    """
    it = iter(refs)
    occ_ref = next(it) if gated else None  # SMEM (n_w, nk) weight occupancy
    pw_ref = next(it)
    x_ref = next(it)
    wm_ref = next(it)
    ws_ref = next(it) if w_signed else None
    if has_epilogue:
        as_ref = next(it)  # (bm, 1) per-token activation scales
        wsc_ref = next(it)  # (1, bn) per-channel weight scales
        b_ref = next(it) if has_bias else None
    o_ref = next(it)
    acc_ref = next(it)  # (bm, bn) int32 VMEM scratch
    k_step = pl.program_id(2)

    a_planes = slice_activation_planes(x_ref[...], a_bits, variant)

    def unpack_w(j):
        v = _expand_words(wm_ref[j], axis=0)  # (bkw, bn) -> (bk, bn)
        if w_signed:
            v = v - 2 * _expand_words(ws_ref[j], axis=0)
        return v.astype(jnp.int8)

    w_planes = [unpack_w(j) for j in range(n_w)]

    if gated:
        @pl.when(k_step == 0)
        def _zero():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)

        for i in range(a_bits):
            occ_a = jnp.any(a_planes[i] != 0)  # dynamic, from live values
            for j in range(n_w):
                pred = jnp.logical_and(occ_a, occ_ref[j, k_step] != 0)

                @pl.when(pred)
                def _pass(i=i, j=j):
                    prod = jnp.dot(
                        a_planes[i], w_planes[j], preferred_element_type=jnp.int32
                    )
                    acc_ref[...] += pw_ref[i * n_w + j] * prod
    else:
        acc = jnp.zeros(acc_ref.shape, jnp.int32)
        for i in range(a_bits):
            for j in range(n_w):
                prod = jnp.dot(
                    a_planes[i], w_planes[j], preferred_element_type=jnp.int32
                )
                acc = acc + pw_ref[i * n_w + j] * prod

        @pl.when(k_step == 0)
        def _init():
            acc_ref[...] = acc

        @pl.when(k_step > 0)
        def _accum():
            acc_ref[...] += acc

    @pl.when(k_step == nk - 1)
    def _epilogue():
        final = acc_ref[...]
        if has_epilogue:
            out = final.astype(jnp.float32) * as_ref[...] * wsc_ref[...]
            if has_bias:
                out = out + b_ref[...]
            out = ACTIVATIONS[activation](out)
            o_ref[...] = out.astype(o_ref.dtype)
        else:
            o_ref[...] = final  # pre-epilogue int32 (parity-test mode)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_bits", "variant", "activation", "out_dtype", "bm", "bn", "gate",
        "interpret",
    ),
)
def fused_plane_linear(
    x_q: jax.Array,
    packed_w: PackedPlanes,
    pair_weights: jax.Array,
    *,
    a_bits: int,
    variant: str,
    a_scale: jax.Array | None = None,
    w_scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    activation: str = "none",
    out_dtype=jnp.bfloat16,
    bm: int = 128,
    bn: int = 128,
    gate: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused bit-serial linear: quantized matmul + dequant epilogue.

    ``x_q``: (M, K) int8 quantized activations (natural K order);
    ``packed_w``: blocked-layout :class:`PackedPlanes`, words (P_w, KW, N)
    with K packed along the rows (``axis=1``) — the K tile size ``bk`` is
    the pack block, so each grid step consumes exactly one block and the
    in-kernel unpack yields natural K order; ``pair_weights``:
    (a_bits * P_w,) int32.

    With ``a_scale`` (M,)/(M,1) and ``w_scale`` (N,)/(1,N) the epilogue
    ``acc * a_scale * w_scale [+ bias]; activation`` runs in-kernel and the
    result is ``out_dtype``. With ``a_scale=None`` the raw int32
    accumulator is returned (pre-epilogue parity-test mode).

    ``gate=True``: occupancy-gated sparse plane execution — weight-plane
    occupancy from pack time is prefetched to SMEM and AND'd with
    in-kernel activation-plane occupancy to skip all-zero plane-pair MXU
    passes (bit-identical; see ``_fused_kernel``).
    """
    if gate and packed_w.occupancy is None:
        raise ValueError(
            "gate=True needs weight occupancy metadata; re-pack the weight "
            "operand (pack_planes computes it) or pass gate=False"
        )
    if packed_w.axis != 1:
        raise ValueError(f"expected W packed on axis 1, got {packed_w.axis}")
    if packed_w.block is None:
        raise ValueError(
            "fused_plane_linear needs blocked-layout packed weights "
            "(pack_planes(..., block=bk)); the global planar layout permutes "
            "K and cannot contract against raw activations"
        )
    m, k = x_q.shape
    if k != packed_w.k:
        raise ValueError(f"K mismatch: x {x_q.shape} vs packed weight k={packed_w.k}")
    n_w, kw, n = packed_w.mag.shape
    if pair_weights.shape != (a_bits * n_w,):
        raise ValueError("pair_weights must have shape (a_bits * P_w,)")
    bk = packed_w.block
    bkw = bk // WORD_BITS
    nk = kw // bkw
    w_signed = packed_w.sign is not None
    has_epilogue = a_scale is not None
    has_bias = bias is not None
    if has_bias and not has_epilogue:
        raise ValueError("bias requires the epilogue (a_scale/w_scale)")

    xp = _pad_dim(_pad_dim(x_q.astype(jnp.int8), 0, bm), 1, nk * bk)
    mp = xp.shape[0]
    wm = _pad_dim(packed_w.mag, 2, bn)
    np_ = wm.shape[2]
    grid = (mp // bm, np_ // bn, nk)

    operands = [pair_weights, xp]
    in_specs = [
        pl.BlockSpec((a_bits * n_w,), lambda mi, ni, ki: (0,)),
        pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
        pl.BlockSpec((n_w, bkw, bn), lambda mi, ni, ki: (0, ki, ni)),
    ]
    operands.insert(2, wm)
    if gate:
        # (P_w, nk) weight occupancy, whole array in SMEM for every step
        operands.insert(0, occupancy_per_tile(packed_w.occupancy, bkw))
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    if w_signed:
        operands.append(_pad_dim(packed_w.sign, 2, bn))
        in_specs.append(pl.BlockSpec((n_w, bkw, bn), lambda mi, ni, ki: (0, ki, ni)))
    if has_epilogue:
        # broadcast_to validates length and expands per-tensor (scalar /
        # (1,1)) scales to the full extent — padding with 1.0 afterwards
        # would otherwise silently dequantize padded rows/cols with scale 1
        asc = jnp.broadcast_to(a_scale.reshape(-1, 1).astype(jnp.float32), (m, 1))
        wsc = jnp.broadcast_to(w_scale.reshape(1, -1).astype(jnp.float32), (1, n))
        asc = _pad_dim(asc, 0, bm, value=1.0)
        wsc = _pad_dim(wsc, 1, bn, value=1.0)
        operands += [asc, wsc]
        in_specs += [
            pl.BlockSpec((bm, 1), lambda mi, ni, ki: (mi, 0)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ]
        if has_bias:
            bia = jnp.broadcast_to(bias.reshape(1, -1).astype(jnp.float32), (1, n))
            operands.append(_pad_dim(bia, 1, bn))
            in_specs.append(pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)))

    kernel = functools.partial(
        _fused_kernel,
        a_bits=a_bits,
        n_w=n_w,
        variant=variant,
        w_signed=w_signed,
        has_epilogue=has_epilogue,
        has_bias=has_bias,
        activation=activation,
        nk=nk,
        gated=gate,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), jnp.dtype(out_dtype) if has_epilogue else jnp.int32
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)
    return out[:m, :n]
