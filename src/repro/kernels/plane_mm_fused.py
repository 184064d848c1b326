"""Pallas TPU kernel: fully-fused bit-serial linear layer.

The staged serving path violates the paper's operand-stream model twice
per projection: activations are decomposed into ``a_bits`` int8 plane
tensors in HBM (an 8x blow-up at 8 bits) before the matmul, and the
int32 accumulator is written to HBM and re-read by a separate XLA op for
the ``acc * a_scale * w_scale`` dequant. This kernel runs the whole
linear in one launch, per (bm, bn) output tile and K block:

1. the raw quantized **int8 activation** tile (natural K order) is the
   activation operand as it stands: the plane sum ``sum_i aw_i * A_i``
   of its low ``a_bits`` bits is their sign extension, taken on-chip;
2. the tile's **integer weights** ``w = sum_j ww_j * W_j`` are rebuilt
   as one int8 ``(bk, bn)`` block in VMEM, straight from the packed
   magnitude and sign words of the tier's plane prefix (block layout, so
   the block comes out in natural K order): word-level Booth digit
   subtraction into two's-complement bit slots, a bit-matrix transpose
   of the 8 slot words into bytes, one shift pair per byte;
3. **one int8 MXU pass** ``a @ w`` accumulates into an int32 **VMEM
   scratch** tile across the K grid dimension. The pair weights are the
   outer product ``aw_i * ww_j``, so this is the same integer as the
   ``P_a x P_w`` plane-pair sum, bit for bit;
4. at the last K step a fused epilogue applies ``a_scale[m] *
   w_scale[n]``, optional bias and activation (gelu/silu), and writes
   the output dtype (bf16) directly.

The packed words stay the only copy of the weights: a truncated prefix
reads only its kept planes' words, and no int8 weight reaches HBM. Plane
tensors, int8 weight blocks and int32 accumulators never touch HBM: per
projection the kernel reads int8 activations + packed weight words +
scales and writes bf16 — the bit-serial operand-stream byte model of the
paper (BISMO keeps the bit-slicing in the fetch stage and TMA folds the
rescale into the PE datapath for the same reason).

``gate=True`` keeps the plane-pair loop: ``a_bits x P_w`` MXU passes,
each predicated on weight and activation plane occupancy (DESIGN.md §8).
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
    of int8 planes instead of a stacked HBM tensor. The gated plane-pair
    loop's activation operand.
    """
    u = x.astype(jnp.int32) & ((1 << a_bits) - 1)  # two's-complement low bits
    cur = [(u >> i) & 1 for i in range(a_bits)]
    if variant == "booth":
        planes = [(cur[i - 1] if i else 0) - cur[i] for i in range(a_bits)]
    else:  # sbmwc / unsigned share raw bit planes; only the weights differ
        planes = cur
    return [p.astype(jnp.int8) for p in planes]


def int8_activations(x: jax.Array, a_bits: int, variant: str) -> jax.Array:
    """``sum_i aw_i * A_i`` over :func:`slice_activation_planes`, as int8.

    Booth and SBMwC planes of the low ``a_bits`` bits sum to their sign
    extension, unsigned planes to the bits themselves — so a value
    outside the ``a_bits`` range wraps exactly as the planes do. Needs
    ``a_bits < 8`` for ``unsigned`` (see :func:`single_pass_fits`).
    """
    if a_bits >= 8 and variant != "unsigned":
        return x.astype(jnp.int8)
    u = x.astype(jnp.int32)
    if variant == "unsigned":
        u = u & ((1 << a_bits) - 1)
    else:
        u = (u << (32 - a_bits)) >> (32 - a_bits)
    return u.astype(jnp.int8)


#: two's-complement bits of an int8 weight, and the bit-matrix transpose's
#: masks: stage i swaps bit i of the slot index with bit i of the position
INT8_SLOTS = 8
_SWAP_MASKS = (0x55555555, 0x33333333, 0x0F0F0F0F)


def weight_slots(weights: tuple[int, ...], ternary: bool) -> tuple[int, ...] | None:
    """The plane that feeds each two's-complement bit of an int8 weight.

    Entry ``e`` is the index of the plane weighted ``2^e`` (-1: no such
    plane, the bit comes from the borrow or is 0). An SBMwC top weight
    ``-2^e`` also fills slots ``e+1..7``: its sign extension. ``None``
    when the planes' integers have no int8 form: a weight that is not
    ``±2^e`` with ``e < 8``, a repeated one, a negative ternary weight, a
    negative weight below a positive one, or a binary ``+2^7`` (unsigned
    8-bit). Ternary planes with a ``2^7`` plane reach ``+2^7`` (Booth's
    closed range after prefix truncation), which the kernel corrects.
    """
    slots = [-1] * INT8_SLOTS
    for p, w in enumerate(weights):
        e = abs(w).bit_length() - 1
        if w == 0 or abs(w) != 1 << e or e >= INT8_SLOTS or slots[e] != -1:
            return None
        slots[e] = p
    neg = [p for p, w in enumerate(weights) if w < 0]
    if neg:
        e = abs(weights[neg[0]]).bit_length() - 1
        if ternary or len(neg) > 1 or any(x != -1 for x in slots[e + 1:]):
            return None
        slots[e + 1:] = [neg[0]] * (INT8_SLOTS - 1 - e)
    elif not ternary and slots[-1] != -1:
        return None
    return tuple(slots)


def single_pass_fits(weights: tuple[int, ...], ternary: bool, a_bits: int, variant: str) -> bool:
    """Whether both operands of a plane-pair sum have an int8 form, so one
    int8 MXU pass computes it (every tier the plans route to the fused
    kernel: SBMwC or Booth, at most 8 bits a side)."""
    return weight_slots(weights, ternary) is not None and not (
        variant == "unsigned" and a_bits >= 8
    )


def _slot_words(mag: list, sign: list | None, slots: tuple[int, ...]):
    """Words of each two's-complement bit of the rebuilt weights (bit t of
    slot word e: bit e of the weight at word bit t) and, for ternary
    planes, the final borrow. Booth digits are ``mag - 2*sign``: their
    positive and negative parts are subtracted bit-serially, a word at a
    time (the sign bits are a subset of the magnitude bits)."""
    zero = jnp.zeros_like(mag[0])
    if sign is None:
        return [mag[p] if p >= 0 else zero for p in slots], None
    bits, borrow = [], zero
    for p in slots:
        m, s = (mag[p], sign[p]) if p >= 0 else (zero, zero)
        bits.append(m ^ borrow)
        borrow = s | (borrow & ~m)
    return bits, borrow


def _bytes_of_slots(bits: list) -> list:
    """Bit-matrix transpose of the 8 slot words: byte b of word q becomes
    the int8 weight at word bit ``t = 8*b + q`` (bit e from slot word e).
    Three delta-swap stages over word pairs, 6 ops a pair."""
    y = list(bits)
    for i, mask in enumerate(_SWAP_MASKS):
        s = 1 << i
        for lo in range(INT8_SLOTS):
            if lo & s:
                continue
            hi = lo | s
            t = ((y[lo] >> s) ^ y[hi]) & mask
            y[hi] = y[hi] ^ t
            y[lo] = y[lo] ^ (t << s)
    return y


def rebuild_int8_weights(mag: list, sign: list | None, slots: tuple[int, ...]):
    """``sum_j ww_j * W_j`` of one blocked word tile as an int8 (bk, bn) block.

    ``mag``/``sign``: per plane, the (bk/32, bn) words of one pack block
    (``sign`` None for binary planes); ``slots``: :func:`weight_slots`.
    Returns the block in natural K order (row ``t * bk/32 + r`` from bit
    t of word row r, the blocked layout's order) and, where a ternary
    weight can leave int8, the pair of words flagging the weights that
    read 256 too low and 256 too high in it (else None): the true weight
    is the 9-bit ``bits - 256 * borrow``.
    """
    bits, borrow = _slot_words(mag, sign, slots)
    y = _bytes_of_slots(bits)
    rows = []
    for t in range(WORD_BITS):
        q, b = t % INT8_SLOTS, t // INT8_SLOTS
        v = y[q] if b == 3 else y[q] << (24 - 8 * b)
        rows.append(v >> 24)  # arithmetic: the byte, sign-extended
    w = jnp.concatenate(rows, axis=0).astype(jnp.int8)
    wide = None
    if borrow is not None and slots[-1] != -1:
        over = bits[-1] ^ borrow  # sign bit and 9-bit sign disagree
        wide = (bits[-1] & over, borrow & over)
    return w, wide


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
    slots: tuple[int, ...] | None,
    k_blocks: int,
):
    """One (bm, bn) output tile; grid dim 2 walks the K pack blocks.

    Not gated: one int8 MXU pass a K step, ``int8_activations(x) @
    rebuild_int8_weights(words)`` over its ``k_blocks`` pack blocks. A
    ternary weight that leaves int8 (Booth's ``+2^7`` at the 8-bit tier)
    reads 256 off in the rebuilt block; the words flag it, and a second
    pass adds ``256 * a @ fix`` only in a K step that holds one.

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

    if gated:
        a_planes = slice_activation_planes(x_ref[...], a_bits, variant)

        def unpack_w(j):
            v = _expand_words(wm_ref[j], axis=0)  # (bkw, bn) -> (bk, bn)
            if w_signed:
                v = v - 2 * _expand_words(ws_ref[j], axis=0)
            return v.astype(jnp.int8)

        w_planes = [unpack_w(j) for j in range(n_w)]

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
        a = int8_activations(x_ref[...], a_bits, variant)
        bkw = wm_ref.shape[1] // k_blocks
        blocks = []
        for c in range(k_blocks):  # pack blocks, each in natural K order
            rows = slice(c * bkw, (c + 1) * bkw)
            mag = [wm_ref[j, rows, :] for j in range(n_w)]
            sign = [ws_ref[j, rows, :] for j in range(n_w)] if w_signed else None
            blocks.append(rebuild_int8_weights(mag, sign, slots))
        w = jnp.concatenate([b[0] for b in blocks], axis=0)
        prod = jnp.dot(a, w, preferred_element_type=jnp.int32)

        @pl.when(k_step == 0)
        def _init():
            acc_ref[...] = prod

        @pl.when(k_step > 0)
        def _accum():
            acc_ref[...] += prod

        wide = [b[1] for b in blocks if b[1] is not None]  # every block or none
        if wide:
            over = functools.reduce(jnp.bitwise_or, [lo | hi for lo, hi in wide])

            @pl.when(jnp.max((over != 0).astype(jnp.int32)) > 0)
            def _wide():
                fix = jnp.concatenate(
                    [_expand_words(lo, axis=0) - _expand_words(hi, axis=0) for lo, hi in wide],
                    axis=0,
                )
                acc_ref[...] += 256 * jnp.dot(
                    a, fix.astype(jnp.int8), preferred_element_type=jnp.int32
                )

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
        "a_bits", "variant", "activation", "out_dtype", "bm", "bn", "k_blocks",
        "gate", "interpret",
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
    k_blocks: int = 1,
    gate: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused bit-serial linear: quantized matmul + dequant epilogue.

    ``x_q``: (M, K) int8 quantized activations (natural K order);
    ``packed_w``: blocked-layout :class:`PackedPlanes`, words (P_w, KW, N)
    with K packed along the rows (``axis=1``) — the K tile size ``bk`` is
    the pack block, so each grid step consumes exactly one block and the
    in-kernel rebuild yields natural K order; ``pair_weights``:
    (a_bits * P_w,) int32, the outer product of the activation plane
    weights and ``packed_w.weights`` (as ``ops.fused_linear`` builds it).
    The single pass reads the weights statically from
    ``packed_w.weights`` and needs both operands' integers in int8
    (:func:`single_pass_fits`); the gated loop reads ``pair_weights``.

    With ``a_scale`` (M,)/(M,1) and ``w_scale`` (N,)/(1,N) the epilogue
    ``acc * a_scale * w_scale [+ bias]; activation`` runs in-kernel and the
    result is ``out_dtype``. With ``a_scale=None`` the raw int32
    accumulator is returned (pre-epilogue parity-test mode).

    ``k_blocks``: pack blocks per K grid step of the single pass (each
    rebuilt in its natural K order, one MXU pass over them all); the
    gated loop takes one.

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
    w_signed = packed_w.sign is not None
    slots = weight_slots(packed_w.weights, w_signed)
    if not gate and (
        len(packed_w.weights) != n_w
        or not single_pass_fits(packed_w.weights, w_signed, a_bits, variant)
    ):
        raise ValueError(
            f"the single-pass kernel needs int8 operands; plane weights "
            f"{packed_w.weights} ({'ternary' if w_signed else 'binary'}, {n_w} "
            f"planes) with {a_bits}-bit {variant} activations have none "
            "(gate=True runs the plane-pair loop)"
        )
    if gate and k_blocks != 1:
        raise ValueError("the gated loop takes one pack block a K step")
    bk = packed_w.block * k_blocks
    bkw = bk // WORD_BITS
    nk = kw // bkw
    if nk * bkw != kw:
        raise ValueError(f"k_blocks={k_blocks} does not divide the {kw // (bkw // k_blocks)} pack blocks")
    has_epilogue = a_scale is not None
    has_bias = bias is not None
    if has_bias and not has_epilogue:
        raise ValueError("bias requires the epilogue (a_scale/w_scale)")

    xp = _pad_dim(_pad_dim(x_q.astype(jnp.int8), 0, bm), 1, nk * bk)
    mp = xp.shape[0]

    def hbm_words(words):
        # The kernel's pipeline reads the packed words from HBM itself. Left
        # free, XLA stages a layer's words in VMEM in the op that slices them
        # out of the layer stack, and the kernel's time leaves its reads out.
        words = _pad_dim(words, 2, bn)
        return words if interpret else pltpu.with_memory_space_constraint(words, pltpu.HBM)

    wm = hbm_words(packed_w.mag)
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
        operands.append(hbm_words(packed_w.sign))
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
        slots=slots,
        k_blocks=k_blocks,
    )
    out = pl.pallas_call(
        kernel,
        name="fused_plane_linear",
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
