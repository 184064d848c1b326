"""Bit-plane and digit-plane decompositions of integer tensors.

This is the arithmetic heart of the bitSMM reproduction. The paper streams
operands one bit per cycle; here the temporal stream becomes a leading
``planes`` axis:

* **unsigned** bit-planes: ``x = sum_i 2^i * p_i``, ``p_i in {0,1}``.
* **SBMwC** (standard binary multiplication with correction) planes: two's
  complement, i.e. unsigned planes except the MSB plane carries weight
  ``-2^(b-1)`` (the paper's "subtract at the multiplier sign bit").
* **Booth** signed-digit planes: radix-2 recoding ``d_i = x_{i-1} - x_i``
  (``x_{-1} = 0``), digits in ``{-1, 0, +1}``, weights ``2^i`` — the
  paper's Booth MAC, Table I.

Digit-plane (radix ``2^k``) variants generalize the same three schemes to
the width the TPU MXU natively consumes (k = 8 → int8 digits); see
DESIGN.md §2. All decompositions are exact: ``reconstruct(decompose(x)) == x``.

Bit-planes additionally support a *packed* storage format
(:func:`pack_planes` / :func:`unpack_planes`): binary {0,1} planes pack
32 plane values per int32 word, ternary Booth {-1,0,+1} planes pack as a
sign/magnitude word pair — 32× / 16× less HBM traffic than int8 plane
tensors. See DESIGN.md §"Packed plane format" for the word layout.

Packed words are why tensor-parallel sharding (DESIGN.md §11) slices
*values*, never plane words: a K-shard boundary falls mid-word, so
``sharding.tp.shard_quantized`` slices the quantized ``w_q`` per shard
and re-runs the decomposition here per shard. ABFT checksums and
occupancy masks are computed over the per-shard planes by the same code
path as the single-device build — per-shard integrity needs no special
casing in this module.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax
import jax.numpy as jnp

Variant = Literal["unsigned", "sbmwc", "booth"]

WORD_BITS = 32  # plane values per packed int32 word
DEFAULT_BLOCK = 512  # K values per pack block in the blocked (fused-kernel) layout


@dataclasses.dataclass(frozen=True)
class PlaneDecomposition:
    """Planes stacked on a leading axis plus their integer weights.

    ``planes``:   int8/int32 array, shape ``(n_planes,) + x.shape``.
    ``weights``:  int64-safe Python ints, length ``n_planes``; the
                  reconstruction is ``sum_i weights[i] * planes[i]``.
    """

    planes: jax.Array
    weights: tuple[int, ...]

    @property
    def n_planes(self) -> int:
        return len(self.weights)

    def reconstruct(self, dtype=jnp.int32) -> jax.Array:
        w = jnp.asarray(self.weights, dtype=dtype)
        w = w.reshape((self.n_planes,) + (1,) * (self.planes.ndim - 1))
        return jnp.sum(self.planes.astype(dtype) * w, axis=0)


def _check_bits(bits: int, max_bits: int = 32) -> None:
    if not 1 <= bits <= max_bits:
        raise ValueError(f"bits must be in [1, {max_bits}], got {bits}")


def signed_range(bits: int) -> tuple[int, int]:
    """Two's-complement representable range for ``bits``-bit values."""
    if bits == 1:
        # 1-bit two's complement: values {-1, 0}. For NN quantization we
        # instead use the binary {0,1} / ternary conventions upstream.
        return -1, 0
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1


def plane_weights(bits: int, variant: Variant) -> tuple[int, ...]:
    """Plane weights of :func:`to_bitplanes` without computing the planes.

    Lets the fused kernel path build pair weights from ``(a_bits, variant)``
    alone — the activation planes themselves are sliced on-chip.
    """
    _check_bits(bits)
    if variant in ("unsigned", "booth"):
        return tuple(1 << i for i in range(bits))
    if variant == "sbmwc":
        return tuple(1 << i for i in range(bits - 1)) + (-(1 << (bits - 1)),)
    raise ValueError(f"unknown variant {variant!r}")


def to_bitplanes(x: jax.Array, bits: int, variant: Variant = "sbmwc") -> PlaneDecomposition:
    """Decompose integer tensor ``x`` into ``bits`` binary/ternary planes.

    ``x`` (any integer dtype — int8 quantized activations pass straight
    through) must be representable in ``bits``-bit two's complement (for
    ``sbmwc``/``booth``) or unsigned ``bits``-bit (for ``unsigned``).
    """
    weights = plane_weights(bits, variant)
    x = x.astype(jnp.int32)

    if variant == "unsigned":
        shifts = jnp.arange(bits, dtype=jnp.int32).reshape((bits,) + (1,) * x.ndim)
        planes = ((x[None] >> shifts) & 1).astype(jnp.int8)
        return PlaneDecomposition(planes, weights)

    if variant == "sbmwc":
        # Two's complement bit extraction: reinterpret the signed value's low
        # `bits` bits; MSB plane weight is negative (the correction).
        u = x & ((1 << bits) - 1) if bits < 32 else x.view(jnp.uint32).astype(jnp.int32)
        shifts = jnp.arange(bits, dtype=jnp.int32).reshape((bits,) + (1,) * x.ndim)
        planes = ((u[None] >> shifts) & 1).astype(jnp.int8)
        return PlaneDecomposition(planes, weights)

    if variant == "booth":
        # d_i = x_{i-1} - x_i over the two's-complement bits, x_{-1} = 0.
        u = x & ((1 << bits) - 1) if bits < 32 else x.view(jnp.uint32).astype(jnp.int32)
        shifts = jnp.arange(bits, dtype=jnp.int32).reshape((bits,) + (1,) * x.ndim)
        cur = ((u[None] >> shifts) & 1).astype(jnp.int8)
        prev = jnp.concatenate([jnp.zeros_like(cur[:1]), cur[:-1]], axis=0)
        planes = (prev - cur).astype(jnp.int8)  # {-1, 0, +1}
        if bits < 32:
            # Closed-range extension: ternary digits represent the CLOSED
            # interval [-2^(b-1), +2^(b-1)] — +2^(b-1) is (0,..,0,+1) —
            # but the two's-complement recode above wraps it to -2^(b-1).
            # Booth prefix truncation rounds half up, so its requantized
            # values live on the closed interval (see shift_requantize);
            # fix the single wrapped value so the truncation oracle is
            # exact. In-range inputs are untouched.
            top = jnp.int8(2) * (x[None] == (1 << (bits - 1))).astype(jnp.int8)
            planes = planes.at[bits - 1].add(top[0])
        return PlaneDecomposition(planes, weights)

    raise ValueError(f"unknown variant {variant!r}")


def to_digits(
    x: jax.Array,
    bits: int,
    variant: Variant = "booth",
    radix_bits: int = 8,
) -> PlaneDecomposition:
    """Radix-``2^radix_bits`` digit decomposition (the TPU-native adaptation).

    * ``unsigned``: digits in ``[0, 2^k - 1]``.
    * ``sbmwc``: low digits unsigned in ``[0, 2^k - 1]``; the top digit is
      signed — the digit-level analogue of the paper's MSB correction. Low
      digits do NOT fit int8 (they reach 255), so this variant dots in
      int32 — mirroring the paper's finding that SBMwC needs the wider
      datapath (two adders).
    * ``booth``: carry-propagating signed-digit recode; **every** digit fits
      ``[-2^(k-1), 2^(k-1)-1]`` (int8 for k=8) at the cost of at most one
      extra digit — the radix-2^k analogue of Booth recoding, and the
      variant that hits the MXU's native int8 path.
    """
    _check_bits(bits)
    if radix_bits < 1 or radix_bits > 16:
        raise ValueError(f"radix_bits must be in [1,16], got {radix_bits}")
    x = x.astype(jnp.int32)
    k = radix_bits
    base = 1 << k
    n_digits = -(-bits // k)  # ceil

    if variant == "unsigned":
        digits, weights, rem = [], [], x
        for i in range(n_digits):
            digits.append(rem & (base - 1))
            weights.append(base**i)
            rem = rem >> k
        planes = jnp.stack(digits).astype(jnp.int32)
        return PlaneDecomposition(planes, tuple(weights))

    if variant == "sbmwc":
        digits, weights = [], []
        rem = x
        for i in range(n_digits):
            if i < n_digits - 1:
                digits.append(rem & (base - 1))
                rem = rem >> k  # arithmetic shift keeps the sign in the top digit
            else:
                digits.append(rem)  # signed top digit (the correction)
            weights.append(base**i)
        planes = jnp.stack(digits).astype(jnp.int32)
        return PlaneDecomposition(planes, tuple(weights))

    if variant == "booth":
        half = base // 2
        digits, weights = [], []
        rem = x
        # Worst case needs one extra digit (e.g. 32767 -> [-1, -128, 1] at k=8).
        for i in range(n_digits + 1):
            d = ((rem & (base - 1)) ^ half) - half  # sign-extend low k bits
            digits.append(d)
            weights.append(base**i)
            rem = (rem - d) >> k
        planes = jnp.stack(digits).astype(jnp.int8 if k <= 8 else jnp.int16)
        return PlaneDecomposition(planes, tuple(weights))

    raise ValueError(f"unknown variant {variant!r}")


# ---------------------------------------------------------------------------
# Packed plane storage (DESIGN.md §"Packed plane format")
# ---------------------------------------------------------------------------
#
# Word layout ("planar"): with W = ceil(K / 32) int32 words covering a
# padded extent of 32*W along the packed axis, bit t of word j holds the
# plane value at padded position k = t*W + j. Unpacking is therefore a
# concatenation of 32 shift-and-mask chunks — no gathers and no lane
# interleaves, which is what lets the Pallas kernel unpack on-chip with
# plain VPU ops. The layout is a fixed permutation of K, so a matmul over
# operands packed with the *same* W contracts identical K elements and
# needs no unpermute.


@dataclasses.dataclass(frozen=True)
class PackedPlanes:
    """Bit-packed plane tensor plus the metadata needed to unpack it.

    ``mag``:     int32 words; bit t of word j = |plane value| at k = t*W + j.
    ``sign``:    int32 words with the same layout, bit set where the plane
                 value is -1 (ternary Booth planes); ``None`` for binary
                 {0,1} planes. A set sign bit implies a set mag bit, so the
                 value is always ``mag - 2*sign``.
    ``k``:       unpadded extent of the packed axis.
    ``axis``:    which axis of the *unpacked* plane array was packed
                 (normalized non-negative; never 0, the planes axis).
    ``weights``: plane weights carried through from the decomposition.
    ``block``:   ``None`` for the global planar layout (word j bit t holds
                 k = t*W + j over the whole padded extent); an int for the
                 *blocked* layout, where K is split into chunks of ``block``
                 values and each chunk is planar-packed independently. A
                 word slice covering whole blocks then unpacks to K values
                 in natural order — the layout the fused linear kernel
                 needs, since its activation operand is raw (unpermuted)
                 int8. Must be a multiple of ``WORD_BITS``.
    ``occupancy``: per-(plane, word) {0,1} int32 bitmap, shape
                 ``(n_planes, n_words)``: 1 where any plane value packed
                 into that word (across all non-packed axes) is non-zero.
                 A zero entry proves a whole word slice of a plane is
                 inert, so a kernel K step can skip that plane's MXU pass
                 (DESIGN.md §8). ``mag`` alone determines it (a set Booth
                 sign bit implies a set mag bit). Word granularity reduces
                 exactly onto any word-aligned K tile via
                 :func:`occupancy_per_tile` — the same reduction for the
                 global-planar and blocked layouts, since both tile the
                 word axis.
    ``checksum``: optional per-plane *column checksums* for ABFT-checked
                 execution (DESIGN.md §9): entry ``(p, k)`` is the signed
                 sum of plane ``p``'s values at unpacked position ``k``
                 over every non-packed axis — for a ``(P, K, N)`` weight
                 plane tensor, ``checksum[p, k] = sum_n plane[p, k, n]``.
                 Folding with the plane weights (:func:`checksum_vector`)
                 yields the exact row-sum vector of the reconstructed
                 integer matrix, the reference side of the matmul-time
                 row-sum identity. Sliced by the same plane-index masks
                 as the words under truncation and compaction, so every
                 precision tier of a checksummed cache stays checkable.
    """

    mag: jax.Array
    sign: Optional[jax.Array]
    k: int
    axis: int
    weights: tuple[int, ...]
    block: Optional[int] = None
    occupancy: Optional[jax.Array] = None
    checksum: Optional[jax.Array] = None

    @property
    def n_planes(self) -> int:
        return self.mag.shape[0]

    @property
    def n_words(self) -> int:
        return self.mag.shape[self.axis]

    @property
    def nbytes(self) -> int:
        n = self.mag.size * self.mag.dtype.itemsize
        if self.sign is not None:
            n += self.sign.size * self.sign.dtype.itemsize
        return n

    def unpack(self, dtype=jnp.int8) -> jax.Array:
        return unpack_planes(self, dtype=dtype)

    def fingerprint(self) -> jax.Array:
        """Whole-cache fingerprint: uint32 fold of the bit patterns of
        every stored array (words, occupancy, column checksums). Any
        single bit flip moves it — including flips in padding bit
        positions that the value-level checksums cannot see."""
        from repro.core import integrity

        return integrity.tree_checksum(
            (self.mag, self.sign, self.occupancy, self.checksum)
        )


def _packed_flatten(p: PackedPlanes):
    return (p.mag, p.sign, p.occupancy, p.checksum), (
        p.k, p.axis, p.weights, p.block,
    )


def _packed_unflatten(aux, children):
    mag, sign, occupancy, checksum = children
    k, axis, weights, block = aux
    return PackedPlanes(
        mag=mag, sign=sign, k=k, axis=axis, weights=weights, block=block,
        occupancy=occupancy, checksum=checksum,
    )


jax.tree_util.register_pytree_node(PackedPlanes, _packed_flatten, _packed_unflatten)


def _to_words(bits01: jax.Array, axis: int, n_words: int) -> jax.Array:
    """Pack a {0,1} int array along ``axis`` into int32 words (planar layout).

    Works axis-in-place (no transposes): the extent splits into an adjacent
    (32, W) pair, the bit axis is shifted into place and summed away —
    disjoint bit positions make the int32 sum exactly the bitwise OR.
    """
    pad = n_words * WORD_BITS - bits01.shape[axis]
    x = bits01
    if pad:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, pad)
        x = jnp.pad(x, pads)
    sh = x.shape
    x = x.reshape(sh[:axis] + (WORD_BITS, n_words) + sh[axis + 1 :]).astype(jnp.int32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32).reshape(
        (WORD_BITS,) + (1,) * (x.ndim - 1 - axis)
    )
    return jnp.sum(x << shifts, axis=axis)


def _from_words(words: jax.Array, axis: int, k: int) -> jax.Array:
    """Inverse of :func:`_to_words`: int32 words -> {0,1} int32 values.

    Bit t of word j is value t*W + j, so expanding a bit axis right before
    the word axis and merging the two (C order) restores the padded
    sequence — again transpose-free.
    """
    sh = words.shape
    w = jnp.expand_dims(words, axis)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.int32).reshape(
        (WORD_BITS,) + (1,) * (w.ndim - 1 - axis)
    )
    bits = (w >> shifts) & 1
    bits = bits.reshape(sh[:axis] + (WORD_BITS * sh[axis],) + sh[axis + 1 :])
    return jax.lax.slice_in_dim(bits, 0, k, axis=axis)


def _to_words_blocked(bits01: jax.Array, axis: int, block: int) -> jax.Array:
    """Blocked planar pack: split the extent into ``block``-value chunks and
    planar-pack each chunk independently (word layout local to the chunk)."""
    bkw = block // WORD_BITS
    k = bits01.shape[axis]
    nkb = -(-k // block)
    pad = nkb * block - k
    x = bits01
    if pad:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, pad)
        x = jnp.pad(x, pads)
    sh = x.shape
    x = x.reshape(sh[:axis] + (nkb, block) + sh[axis + 1 :])
    w = _to_words(x, axis + 1, bkw)  # (..., nkb, bkw, ...)
    return w.reshape(sh[:axis] + (nkb * bkw,) + sh[axis + 1 :])


def _from_words_blocked(words: jax.Array, axis: int, k: int, block: int) -> jax.Array:
    """Inverse of :func:`_to_words_blocked`."""
    bkw = block // WORD_BITS
    sh = words.shape
    nkb = sh[axis] // bkw
    w = words.reshape(sh[:axis] + (nkb, bkw) + sh[axis + 1 :])
    vals = _from_words(w, axis + 1, block)  # (..., nkb, block, ...)
    vals = vals.reshape(sh[:axis] + (nkb * block,) + sh[axis + 1 :])
    return jax.lax.slice_in_dim(vals, 0, k, axis=axis)


def pack_planes(
    planes: jax.Array,
    *,
    axis: int = -1,
    ternary: bool = False,
    weights: tuple[int, ...] = (),
    block: Optional[int] = None,
    checksum: bool = False,
) -> PackedPlanes:
    """Bit-pack plane values along ``axis`` into int32 words.

    ``planes`` must hold values in {0,1} (``ternary=False``; the unsigned /
    SBMwC bit-plane alphabets) or {-1,0,+1} (``ternary=True``; Booth).
    ``ternary`` is a static flag — the packed alphabet cannot be inferred
    from traced values. Digit planes (radix > 2) are not packable.
    ``axis`` may not be 0 (the planes axis). Ragged extents pad with zero
    plane values, which are exactly inert in the plane matmul.

    ``block=None`` gives the global planar layout; an int gives the blocked
    layout (see :class:`PackedPlanes`), clamped so a small K never pads up
    to a full oversized block.

    ``checksum=True`` additionally stores per-plane column checksums
    (signed sums over the non-packed axes) for ABFT-checked execution.
    """
    axis = axis % planes.ndim
    if axis == 0:
        raise ValueError("cannot pack along the planes axis (axis 0)")
    k = planes.shape[axis]
    v = planes.astype(jnp.int32)
    if block is not None:
        if block % WORD_BITS:
            raise ValueError(f"block must be a multiple of {WORD_BITS}, got {block}")
        # The clamp for small K rounds to the TPU lane width (128): the
        # fused kernel uses the pack block as its K tile, and a last-dim
        # tile that is not a lane multiple would not lower on Mosaic. A
        # clamped block spans all of K, so its (block / 32)-row word tile
        # is the full extent, which Mosaic accepts at any row count; a
        # block that splits K must be a multiple of 256 (8 word rows, the
        # sublane tile) — ops.tiles_legal is the same rule. (An explicitly
        # sub-lane caller-chosen block is left alone: the interpreter
        # tests use them.)
        lane = 4 * WORD_BITS
        if block > lane:
            block = min(block, -(-k // lane) * lane)
            if block < k and (block // WORD_BITS) % 8:
                raise ValueError(
                    f"block {block} splits K={k} into {block // WORD_BITS}-row "
                    "word tiles; a K-splitting block must be a multiple of "
                    f"{8 * WORD_BITS} (8 word rows) to lower on the TPU"
                )

        def towords(x):
            return _to_words_blocked(x, axis, block)

    else:
        n_words = -(-k // WORD_BITS)

        def towords(x):
            return _to_words(x, axis, n_words)

    if ternary:
        mag = towords(jnp.abs(v))
        sign = towords((v < 0).astype(jnp.int32))
    else:
        mag = towords(v)
        sign = None
    # Per-(plane, word) occupancy: reduce the non-zero mask over every axis
    # except the planes axis and the packed-word axis. Sign bits are a
    # subset of mag bits, so mag alone decides occupancy.
    reduce_axes = tuple(a for a in range(mag.ndim) if a not in (0, axis))
    occupancy = jnp.any(mag != 0, axis=reduce_axes).astype(jnp.int32)
    chk = None
    if checksum:
        # Signed column sums of the *unpacked* values: exact, and bounded
        # by the non-packed extent so int32 never saturates.
        chk = jnp.sum(v, axis=reduce_axes)
    return PackedPlanes(
        mag=mag, sign=sign, k=k, axis=axis, weights=tuple(weights), block=block,
        occupancy=occupancy, checksum=chk,
    )


def unpack_planes(packed: PackedPlanes, dtype=jnp.int8) -> jax.Array:
    """Exact inverse of :func:`pack_planes` (round-trip guarantee)."""
    if packed.block is not None:
        def fromwords(w):
            return _from_words_blocked(w, packed.axis, packed.k, packed.block)
    else:
        def fromwords(w):
            return _from_words(w, packed.axis, packed.k)

    vals = fromwords(packed.mag)
    if packed.sign is not None:
        vals = vals - 2 * fromwords(packed.sign)
    return vals.astype(dtype)


def pack_decomposition(
    dec: PlaneDecomposition,
    *,
    axis: int = -1,
    variant: Variant = "sbmwc",
    block: Optional[int] = None,
    checksum: bool = False,
) -> PackedPlanes:
    """Pack a bit-plane :class:`PlaneDecomposition` (carries its weights)."""
    return pack_planes(
        dec.planes, axis=axis, ternary=variant == "booth", weights=dec.weights,
        block=block, checksum=checksum,
    )


# ---------------------------------------------------------------------------
# Occupancy & plane compaction (DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# Booth recoding's value is that most digits are zero; occupancy metadata
# turns that into skippable work. Two granularities:
#
#   * per-(plane, word) bitmaps (``PackedPlanes.occupancy``, computed at
#     pack time) let a kernel K step predicate individual plane-pair MXU
#     passes (:func:`occupancy_per_tile` reduces words onto K tiles);
#   * *compaction* (:func:`compact_packed`) drops planes whose bitmap is
#     zero everywhere — the grid of plane pairs itself shrinks, on every
#     backend. Kept planes keep their original shift: the ``weights``
#     tuple is filtered, not renumbered by position, so the plane axis is
#     no longer dense in bit index and downstream code must consult
#     ``weights`` (all executors already do).


def occupancy_per_tile(occ: jax.Array, words_per_tile: int) -> jax.Array:
    """Reduce a per-(plane, word) occupancy bitmap onto word-aligned K
    tiles: entry ``(p, t)`` is 1 iff any word of tile ``t`` in plane ``p``
    is occupied. The word axis zero-pads up to a whole tile (padding words
    are inert), matching the kernels' operand padding."""
    p, w = occ.shape
    nt = -(-w // words_per_tile)
    pad = nt * words_per_tile - w
    if pad:
        occ = jnp.pad(occ, ((0, 0), (0, pad)))
    return jnp.any(occ.reshape(p, nt, words_per_tile) != 0, axis=-1).astype(jnp.int32)


def _take_planes(arr: Optional[jax.Array], idx: list[int], axis: int):
    if arr is None:
        return None
    return jnp.take(arr, jnp.asarray(idx, jnp.int32), axis=axis)


def compact_packed(packed: PackedPlanes) -> PackedPlanes:
    """Drop planes whose occupancy bitmap is all-zero (host-side, load
    time: the kept-plane set is static shape information, so operands must
    be concrete — never call this under ``jit``).

    The surviving planes keep their original shift weights, so the result
    reconstructs the identical integers and any plane-pair matmul over it
    is bit-identical to the dense execution — there are just fewer pairs.
    An all-zero operand keeps one inert plane so downstream kernels never
    see a zero-extent planes axis."""
    import numpy as np

    if packed.occupancy is None:
        raise ValueError("compact_packed needs occupancy metadata (re-pack first)")
    if len(packed.weights) != packed.occupancy.shape[-2]:
        raise ValueError(
            "compaction needs per-plane weights to renumber shifts; pack via "
            "pack_decomposition / make_weight_planes (got "
            f"{len(packed.weights)} weights for {packed.occupancy.shape[-2]} planes)"
        )
    occ = np.asarray(packed.occupancy)
    # stacked/scanned caches carry leading batch dims: a plane survives if
    # it is occupied anywhere in the stack (the kept set must be shared)
    plane_axis = occ.ndim - 2
    reduce_axes = tuple(a for a in range(occ.ndim) if a != plane_axis)
    alive = occ.any(axis=reduce_axes)
    idx = [i for i, a in enumerate(alive) if a] or [0]
    if len(idx) == len(packed.weights):
        return packed
    mag_pa = packed.mag.ndim - 3  # (*batch, P, words-or-rows, cols)
    return PackedPlanes(
        mag=_take_planes(packed.mag, idx, mag_pa),
        sign=_take_planes(packed.sign, idx, mag_pa),
        k=packed.k,
        axis=packed.axis,
        weights=tuple(packed.weights[i] for i in idx),
        block=packed.block,
        occupancy=_take_planes(packed.occupancy, idx, plane_axis),
        checksum=None if packed.checksum is None else _take_planes(
            packed.checksum, idx, packed.checksum.ndim - 2  # (*batch, P, K)
        ),
    )


def compact_weight_planes(wp: "WeightPlanes") -> "WeightPlanes":
    """Compact a bit-plane weight cache: drop statically-zero planes from
    the packed words AND the optional raw planes (same kept set). The
    stored width ``w_bits`` is unchanged — compaction removes *work*, not
    precision — and prefix truncation of the result stays exact (the
    truncation mask filters by plane weight, see :func:`truncate_packed`)."""
    if wp.level != "bitplane" or wp.packed is None:
        raise ValueError("compaction needs a packed bitplane cache")
    packed = compact_packed(wp.packed)
    if packed is wp.packed:
        return wp
    keep = {w for w in packed.weights}
    idx = [i for i, w in enumerate(wp.weights) if w in keep]
    planes = (
        None if wp.planes is None
        else _take_planes(wp.planes, idx, wp.planes.ndim - 3)
    )
    return WeightPlanes(
        packed=packed, planes=planes, weights=packed.weights,
        level=wp.level, variant=wp.variant, w_bits=wp.w_bits,
    )


@dataclasses.dataclass(frozen=True)
class WeightPlanes:
    """Pre-decomposed weight operand for the serving weight-plane cache.

    Built once per checkpoint load (DESIGN.md §"Weight-cache lifecycle") so
    the forward pass never re-decomposes static weights.

    ``packed``: :class:`PackedPlanes` with K packed along the rows
                (bit-plane level — binary/ternary planes);
    ``planes``: raw planes ``(P, K, N)`` — always set at digit level
                (radix-256 digits don't bit-pack); optionally *also* set at
                bit-plane level so backends without an in-kernel unpacker
                (the CPU/jnp scan) skip per-call weight-side work entirely.
    """

    packed: Optional[PackedPlanes]
    planes: Optional[jax.Array]
    weights: tuple[int, ...]
    level: str
    variant: str
    w_bits: int

    @property
    def n_out(self) -> int:
        arr = self.packed.mag if self.packed is not None else self.planes
        return arr.shape[-1]


def _wp_flatten(wp: WeightPlanes):
    return (wp.packed, wp.planes), (wp.weights, wp.level, wp.variant, wp.w_bits)


def _wp_unflatten(aux, children):
    packed, planes = children
    weights, level, variant, w_bits = aux
    return WeightPlanes(
        packed=packed, planes=planes, weights=weights,
        level=level, variant=variant, w_bits=w_bits,
    )


jax.tree_util.register_pytree_node(WeightPlanes, _wp_flatten, _wp_unflatten)


def make_weight_planes(
    w_q: jax.Array,
    *,
    w_bits: int,
    variant: Variant = "booth",
    level: str = "digit",
    radix_bits: int = 8,
    store: str = "auto",
    block: Optional[int] = DEFAULT_BLOCK,
    checksum: bool = False,
) -> WeightPlanes:
    """Decompose (and, at bit-plane level, pack) a quantized weight matrix.

    ``w_q``: integer ``(K, N)`` weight. Stacked/scanned weights (leading
    layer or expert dims) are handled by the caller (``lax.map`` in
    ``models.quant``) so the stacked leaves keep their leading axes
    scannable.

    ``store`` (bit-plane level): ``"packed"`` keeps only the packed words
    (the HBM-lean serving format); ``"both"`` additionally keeps the raw
    int8 planes so the jnp scan path pays zero per-call weight-side work;
    ``"auto"`` = packed-only on TPU, both elsewhere.

    ``block``: pack block size for the bit-plane cache. The default stores
    the *blocked* layout the fused linear kernel consumes directly (raw
    int8 activations, no K permutation); ``None`` stores the global planar
    layout of the staged packed kernel. Both are valid operands for
    ``plane_matmul_packed`` — the activation side is packed to match.
    """
    if w_q.ndim != 2:
        raise ValueError(f"make_weight_planes expects (K, N), got {w_q.shape}")
    if store not in ("auto", "packed", "both"):
        raise ValueError(f"unknown store mode {store!r}")
    if store == "auto":
        store = "packed" if jax.default_backend() == "tpu" else "both"
    if level == "bitplane":
        dec = to_bitplanes(w_q, w_bits, variant)
        packed = pack_decomposition(
            dec, axis=-2, variant=variant, block=block, checksum=checksum,
        )
        return WeightPlanes(
            packed=packed,
            planes=dec.planes if store == "both" else None,
            weights=dec.weights,
            level=level, variant=variant, w_bits=w_bits,
        )
    if level == "digit":
        dec = to_digits(w_q, w_bits, variant, radix_bits)
        return WeightPlanes(
            packed=None, planes=dec.planes, weights=dec.weights,
            level=level, variant=variant, w_bits=w_bits,
        )
    raise ValueError(f"no weight-plane cache for level {level!r}")


# ---------------------------------------------------------------------------
# Prefix truncation (runtime precision reconfiguration; DESIGN.md §7)
# ---------------------------------------------------------------------------
#
# Bit-plane decompositions are MSB-first prefix-truncatable: the top
# ``to_bits`` planes of a ``from_bits``-bit decomposition are, after
# dividing the plane weights by 2^(from-to), themselves a complete
# ``to_bits``-bit decomposition of a requantized value. The planes axis is
# leading and planes are stored LSB-first, so truncation is one slice of
# the plane (or packed-word) tensor — no re-quantization and no new
# decomposition work. The requantized value the kept planes represent is
# variant-specific (the truncation invariant, asserted by tests):
#
#   * unsigned / sbmwc:  x >> s            (floor; plane-identical to a
#                                           fresh decomposition of x >> s)
#   * booth:             (x >> s) + bit_(s-1)(x)   (round half up: the
#         dropped digit d_{s-1} = x_{s-2} - x_{s-1} leaves a +2^s * x_{s-1}
#         carry in the kept prefix; value-identical — the kept digit
#         string differs from a fresh recode but reconstructs the same
#         integer, so matmul results are bit-identical)
#
# Booth's round-half-up can land on +2^(to-1) (one past the two's-
# complement max) — representable in ternary signed digits and by the
# closed-range extension of :func:`to_bitplanes`.


def shift_requantize(
    x: jax.Array, from_bits: int, to_bits: int, variant: Variant = "sbmwc"
) -> jax.Array:
    """Requantize ``from_bits``-bit integers to ``to_bits`` by the exact
    value the truncated plane prefix represents (see above). The effective
    scale of the result is ``2^(from_bits - to_bits)`` times the original.
    """
    if to_bits > from_bits:
        raise ValueError(f"cannot requantize {from_bits} bits up to {to_bits}")
    s = from_bits - to_bits
    if s == 0:
        return x
    x = x.astype(jnp.int32)
    if variant == "booth":
        return (x >> s) + ((x >> (s - 1)) & 1)
    return x >> s  # arithmetic shift: floor division by 2^s


def truncate_packed(
    packed: PackedPlanes,
    to_bits: int,
    variant: Variant,
    from_bits: Optional[int] = None,
) -> PackedPlanes:
    """Top-``to_bits`` plane prefix of a packed decomposition.

    A pure slice of the planes axis of the packed words — the dropped
    planes are never read, so a kernel consuming the result moves
    ``to_bits/from_bits`` of the weight bytes. The slice keeps the planes
    whose weight magnitude is at least ``2^s`` (``s = from_bits -
    to_bits``) and shifts each kept weight down by ``s`` — on a dense
    decomposition that is exactly the old ``planes[s:]`` prefix with the
    fresh ``plane_weights(to_bits)``, and on a *compacted* one it keeps
    whatever high planes survived compaction (the occupancy bitmap rows
    slice with the same mask: the truncation-consistency invariant,
    DESIGN.md §8). ``from_bits`` defaults to the plane count and must be
    given for compacted inputs (whose plane count no longer encodes the
    stored width)."""
    from_bits = packed.n_planes if from_bits is None else from_bits
    if not 1 <= to_bits <= from_bits:
        raise ValueError(f"to_bits must be in [1, {from_bits}], got {to_bits}")
    s = from_bits - to_bits
    if s == 0:
        return packed
    floor = 1 << s
    idx = [i for i, w in enumerate(packed.weights) if abs(w) >= floor]
    pa = packed.mag.ndim - 3
    if not idx:
        # every kept plane fell below the cut (a compacted cache whose
        # surviving planes were all low): the requantized value is exactly
        # 0 for every element (Booth's round-half-up carry included — an
        # all-zero suffix above the cut forces the carry to cancel), so
        # one inert zero plane stands in to keep the planes axis non-empty
        mag = jnp.zeros_like(jax.lax.slice_in_dim(packed.mag, 0, 1, axis=pa))
        return PackedPlanes(
            mag=mag,
            sign=None if packed.sign is None else jnp.zeros_like(mag),
            k=packed.k, axis=packed.axis, weights=(1,), block=packed.block,
            occupancy=None if packed.occupancy is None else jnp.zeros_like(
                jax.lax.slice_in_dim(
                    packed.occupancy, 0, 1, axis=packed.occupancy.ndim - 2
                )
            ),
            checksum=None if packed.checksum is None else jnp.zeros_like(
                jax.lax.slice_in_dim(
                    packed.checksum, 0, 1, axis=packed.checksum.ndim - 2
                )
            ),
        )
    return PackedPlanes(
        mag=_take_planes(packed.mag, idx, pa),
        sign=None if packed.sign is None else _take_planes(packed.sign, idx, pa),
        k=packed.k,
        axis=packed.axis,
        weights=tuple(packed.weights[i] >> s for i in idx),
        block=packed.block,
        occupancy=None if packed.occupancy is None
        else _take_planes(packed.occupancy, idx, packed.occupancy.ndim - 2),
        checksum=None if packed.checksum is None
        else _take_planes(packed.checksum, idx, packed.checksum.ndim - 2),
    )


def truncate_weight_planes(wp: WeightPlanes, to_bits: int) -> WeightPlanes:
    """Truncate a bit-plane weight cache to its top ``to_bits`` planes.

    The result is a valid ``to_bits`` :class:`WeightPlanes` consuming the
    *same* stored arrays (sliced views — zero decomposition work), so one
    8-bit decomposition serves every precision below it. Digit-level
    caches (radix 256) are not prefix-truncatable.
    """
    if wp.level != "bitplane":
        raise ValueError(
            f"only bitplane caches are prefix-truncatable, got level={wp.level!r}"
        )
    if not 1 <= to_bits <= wp.w_bits:
        raise ValueError(f"to_bits must be in [1, {wp.w_bits}], got {to_bits}")
    if to_bits == wp.w_bits:
        return wp
    s = wp.w_bits - to_bits
    floor = 1 << s
    # same weight-magnitude mask as truncate_packed, so the packed words,
    # the raw planes and the occupancy bitmap all slice consistently —
    # also correct for compacted caches, whose planes axis is sparse in
    # bit index (the mask degenerates to the old [s:] prefix when dense)
    idx = [i for i, w in enumerate(wp.weights) if abs(w) >= floor]
    packed = (
        None if wp.packed is None
        else truncate_packed(wp.packed, to_bits, wp.variant, from_bits=wp.w_bits)  # type: ignore[arg-type]
    )
    if wp.planes is None:
        planes = None
    elif idx:
        planes = _take_planes(wp.planes, idx, wp.planes.ndim - 3)
    else:
        planes = jnp.zeros_like(
            jax.lax.slice_in_dim(wp.planes, 0, 1, axis=wp.planes.ndim - 3)
        )
    weights = tuple(wp.weights[i] >> s for i in idx) or (1,)
    return WeightPlanes(
        packed=packed,
        planes=planes,
        weights=weights,
        level=wp.level,
        variant=wp.variant,
        w_bits=to_bits,
    )


# ---------------------------------------------------------------------------
# ABFT column checksums (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# A checksummed pack stores, per plane, the signed sum of plane values
# over the non-packed axes. Two detectors build on it:
#
#   * checksum_vector folds the per-plane checksums with the plane
#     weights into the exact row-sum vector of the reconstructed integer
#     weight matrix. The plan executors use it for the matmul-time
#     row-sum identity (exact in int32 wraparound arithmetic):
#         sum_n out[m, n] == sum_k x[m, k] * checksum_vector[k]
#     Truncation and compaction slice the checksum rows with the same
#     plane-index mask as the words, so the identity holds at every
#     precision tier of one stored cache.
#   * verify_packed recomputes checksums and occupancy from the stored
#     words and compares — an at-rest scrubbing probe for the cache
#     itself. Flips in padding bit positions (beyond ``k`` in the last
#     word) are semantically inert and invisible here; the bit-pattern
#     ``fingerprint()`` catches those.


def checksum_vector(packed: PackedPlanes, dtype=jnp.int32) -> jax.Array:
    """Fold per-plane column checksums with the plane weights:
    ``sum_p weights[p] * checksum[p]`` — the exact row-sum vector
    (length K, plus any leading batch dims) of the reconstructed
    integer matrix."""
    if packed.checksum is None:
        raise ValueError(
            "checksum_vector needs a checksummed pack "
            "(pack_planes(..., checksum=True))"
        )
    ww = jnp.asarray(packed.weights, dtype).reshape((-1, 1))
    return jnp.sum(packed.checksum.astype(dtype) * ww, axis=-2)


def verify_packed(packed: PackedPlanes) -> jax.Array:
    """Recompute column checksums (and occupancy) from the stored words
    and compare against the stored copies. Returns a scalar bool array:
    True = consistent. Detects any single-bit flip in the consumed extent
    of ``mag``/``sign``/``occupancy``/``checksum``; combine with
    :meth:`PackedPlanes.fingerprint` to also cover padding bits.

    Operates on unbatched packs (``mag.ndim == 3`` for weights); verify
    stacked caches under ``jax.vmap`` or via the fingerprint."""
    if packed.checksum is None:
        raise ValueError("verify_packed needs a checksummed pack")
    vals = unpack_planes(packed, dtype=jnp.int32)
    reduce_axes = tuple(a for a in range(vals.ndim) if a not in (0, packed.axis))
    ok = jnp.all(jnp.sum(vals, axis=reduce_axes) == packed.checksum)
    if packed.occupancy is not None:
        occ_axes = tuple(
            a for a in range(packed.mag.ndim) if a not in (0, packed.axis)
        )
        occ = jnp.any(packed.mag != 0, axis=occ_axes).astype(jnp.int32)
        ok = ok & jnp.all(occ == packed.occupancy)
    if packed.sign is not None:
        # structural invariant: a set sign bit implies a set mag bit
        ok = ok & jnp.all((packed.sign & ~packed.mag) == 0)
    return ok


def booth_nonzero_digit_count(x: jax.Array, bits: int) -> jax.Array:
    """Number of non-zero Booth digits per element (the paper's motivation:
    runs of ones collapse to two non-zero digits; useful for plane-skip
    scheduling analytics)."""
    dec = to_bitplanes(x, bits, "booth")
    return jnp.sum(jnp.abs(dec.planes.astype(jnp.int32)), axis=0)
