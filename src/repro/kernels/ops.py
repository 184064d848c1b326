"""Jitted dispatch wrappers over the Pallas kernels.

Backend selection:
  * ``"pallas"``    — compile the TPU kernel (requires a TPU backend);
  * ``"interpret"`` — run the same kernel body through the Pallas
                      interpreter on CPU (used by tests);
  * ``"jnp"``       — the pure-jnp path from repro.core / ref.py;
  * ``"auto"``      — pallas on TPU, jnp elsewhere.

Operands are accepted at their quantized storage width (int8/int16): the
decompose helpers widen internally, so callers never round-trip int32
operand tensors through HBM just to satisfy the kernel signature.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bitplanes as bp
from repro.core import bitserial as bs
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.plane_mm import plane_matmul as _plane_mm_pallas
from repro.kernels.plane_mm_fused import ACTIVATIONS
from repro.kernels.plane_mm_fused import fused_plane_linear as _fused_pallas
from repro.kernels.plane_mm_packed import plane_matmul_packed as _plane_mm_packed
from repro.kernels.plane_mm_packed import validate_packed_operands


def resolve_backend(backend: str) -> str:
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n > 1 else 1


def auto_tiles(
    m: int,
    k: int,
    bm: Optional[int],
    bk: Optional[int],
    n: Optional[int] = None,
    bn: Optional[int] = None,
) -> tuple[int, ...]:
    """Decode-shape block heuristic.

    The fixed ``bm=128`` tile wastes 16x+ of every MXU pass on an M=1..8
    decode step (127/128 rows are padding). ``bm=None`` auto-selects the
    smallest legal sublane multiple covering M (power of two, >= 8, capped
    at 128); ``bk=None`` takes the 512 default capped to K rounded up to
    the 128 lane width (also a whole number of packed words).

    With ``n`` given the output-feature tile joins the heuristic and a
    3-tuple ``(bm, bn, bk)`` comes back: ``bn=None`` picks N rounded up to
    the 128 lane width, capped at 256 — on the M<=8 decode shapes the
    wider tile halves the N grid (and its per-step weight-plane unpack
    setup) while the (bm, bn) operands stay far under the VMEM budget; the
    128 floor is the Mosaic lane width. Without ``n`` the historical
    2-tuple contract is unchanged.
    """
    if bm is None:
        bm = min(128, max(8, _pow2_ceil(m)))
    if bk is None:
        bk = min(512, max(128, -(-k // 128) * 128))
    if n is None:
        return bm, bk
    if bn is None:
        bn = min(256, max(128, -(-n // 128) * 128))
    return bm, bn, bk


def _int8_bm(bm: int) -> int:
    """Mosaic's minimum int8 tile is (32, 128): an auto-selected bm below
    32 is legal for the int32/f32 operands auto_tiles was written for but
    not for int8 blocks — the kernel-facing int8 dispatchers floor it here
    (explicit bm passes through to fail loudly in the kernel instead)."""
    return max(bm, 32)


#: Mosaic geometry shared by the legality predicate and the autotuner's
#: candidate generator (core/autotune): the 128-wide vector lane, the
#: 8-row sublane, the (32, 128) minimum int8 tile, bits per packed plane
#: word, and the per-core VMEM working-set budget (~16 MB on current
#: TPUs; we cap the per-grid-step estimate at half to leave room for
#: double buffering and semaphores).
MOSAIC_LANE = 128
MOSAIC_SUBLANE = 8
MOSAIC_INT8_MIN_BM = 32
PACKED_WORD_BITS = 32
VMEM_BUDGET_BYTES = 8 * 2**20


#: the fused kernel's grid step (one int8 MXU pass a tile, so each step
#: costs moving and rebuilding its weight words plus a fixed overhead).
#: Measured on one v5e at LLaMA-7B's linears, M 32 and 64: bn 256 with one
#: pack block a step reached 36-45% of the HBM roofline, the widest steps
#: tried (bn up to 1024, bn x blocks up to 2048 columns) 57-82%.
FUSED_MAX_BN = 1024
FUSED_MAX_K_BLOCKS = 4
FUSED_MAX_STEP_COLS = 2048


def fused_bn(n: int) -> int:
    """The fused kernel's output tile for N: the widest lane multiple, up to
    ``FUSED_MAX_BN``, that divides N rounded up to the lane width — so no
    weight word is padded past that per call."""
    lanes = -(-n // MOSAIC_LANE)
    return MOSAIC_LANE * max(
        c for c in range(1, FUSED_MAX_BN // MOSAIC_LANE + 1) if lanes % c == 0
    )


def fused_k_blocks(bn: int, n_blocks: int) -> int:
    """Pack blocks per K step of the single-pass fused kernel: the most, up
    to ``FUSED_MAX_K_BLOCKS`` and ``FUSED_MAX_STEP_COLS // bn``, that
    divide the weight's ``n_blocks`` pack blocks."""
    return max(
        c for c in range(1, FUSED_MAX_K_BLOCKS + 1)
        if n_blocks % c == 0 and bn * c <= FUSED_MAX_STEP_COLS
    )


def tiles_legal(
    bm: int,
    bn: int,
    bk: int,
    *,
    int8: bool = True,
    vmem_bytes: int = 0,
    k: int,
) -> bool:
    """Would Mosaic accept this (bm, bn, bk) tile triple?

    The single source of truth the autotuner's candidate generation and
    the property tests share: bm a positive sublane multiple of 8 (>= 32
    when the route keeps int8 operand tiles), bn and bk positive
    multiples of the 128-wide lane, and — when the caller supplies its
    working-set estimate — the grid step within the VMEM budget.

    The packed weight block is ``(P_w, bk / 32, bn)`` words, so its
    ``bk / 32`` rows must be a whole sublane multiple: bk a multiple of
    256, unless the block spans the whole packed extent (``bk >= k`` for
    the contraction length ``k``).
    """
    if bm <= 0 or bn <= 0 or bk <= 0:
        return False
    if bm % MOSAIC_SUBLANE or (int8 and bm < MOSAIC_INT8_MIN_BM):
        return False
    if bn % MOSAIC_LANE or bk % MOSAIC_LANE:
        return False
    if (bk // PACKED_WORD_BITS) % MOSAIC_SUBLANE and bk < k:
        return False
    return vmem_bytes <= VMEM_BUDGET_BYTES


def _pad_to(x: jax.Array, multiples: tuple[int, ...]) -> jax.Array:
    pads = []
    for dim, mult in zip(x.shape, multiples):
        rem = (-dim) % mult if mult else 0
        pads.append((0, rem))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


# ---------------------------------------------------------------------------
# Fused epilogue
# ---------------------------------------------------------------------------


class Epilogue(NamedTuple):
    """Dequant/bias/activation epilogue of a quantized linear layer.

    ``a_scale``: per-token activation scales, broadcastable against the
    accumulator's leading dims (``lead + (1,)``); ``w_scale``: per-channel
    weight scales, broadcastable against the output features. On the fused
    kernel path this runs in-kernel and the int32 accumulator never
    reaches HBM; every other path applies the identical math in XLA via
    :func:`apply_epilogue`.
    """

    a_scale: jax.Array
    w_scale: jax.Array
    bias: Optional[jax.Array] = None
    activation: str = "none"
    out_dtype: Any = jnp.bfloat16


def apply_epilogue(acc: jax.Array, ep: Epilogue) -> jax.Array:
    """XLA reference of the in-kernel epilogue (same op order and dtypes)."""
    out = acc.astype(jnp.float32) * ep.a_scale * ep.w_scale
    if ep.bias is not None:
        out = out + ep.bias.astype(jnp.float32)
    out = ACTIVATIONS[ep.activation](out)
    return out.astype(ep.out_dtype)


# ---------------------------------------------------------------------------
# Kernel wrappers (padding + backend dispatch)
# ---------------------------------------------------------------------------


def plane_matmul(
    a_planes: jax.Array,
    w_planes: jax.Array,
    pair_weights: jax.Array,
    *,
    backend: str = "auto",
    bm: Optional[int] = None,
    bn: int = 128,
    bk: Optional[int] = None,
) -> jax.Array:
    """Padding + dispatch wrapper for the plane-pair matmul kernel."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return ref.plane_matmul_ref(a_planes, w_planes, pair_weights)
    _, m, k = a_planes.shape
    _, _, n = w_planes.shape
    auto_bm = bm is None
    bm, bk = auto_tiles(m, k, bm, bk)
    if auto_bm:
        bm = _int8_bm(bm)  # the plane operands are int8 tiles
    ap = _pad_to(a_planes, (0, bm, bk))
    wp = _pad_to(w_planes, (0, bk, bn))
    out = _plane_mm_pallas(
        ap, wp, pair_weights, bm=bm, bn=bn, bk=bk, interpret=backend == "interpret"
    )
    return out[:m, :n]


def plane_matmul_packed(
    packed_a: bp.PackedPlanes,
    packed_w: bp.PackedPlanes,
    pair_weights: jax.Array,
    *,
    backend: str = "auto",
    bm: Optional[int] = None,
    bn: int = 128,
    bk: Optional[int] = None,
    gate: bool = False,
) -> jax.Array:
    """Dispatch wrapper for the packed plane matmul kernel.

    The jnp path unpacks and runs the reference — the parity oracle the
    packed kernel is tested against (and an exact pack/unpack round trip).
    ``gate``: occupancy-gated sparse plane execution (skip all-zero
    plane-pair MXU passes; bit-identical). The jnp oracle has no passes to
    skip and ignores it.
    """
    backend = resolve_backend(backend)
    if backend == "jnp":
        validate_packed_operands(packed_a, packed_w, pair_weights)
        return ref.plane_matmul_ref(
            bp.unpack_planes(packed_a), bp.unpack_planes(packed_w), pair_weights
        )
    m = packed_a.mag.shape[1]
    # auto bk is a 128 multiple (word-aligned); an explicit bk passes
    # through untouched and the kernel rejects non-word multiples.
    bm, bk = auto_tiles(m, packed_a.k, bm, bk)
    return _plane_mm_packed(
        packed_a, packed_w, pair_weights,
        bm=bm, bn=bn, bk=bk, gate=gate, interpret=backend == "interpret",
    )


def fused_linear(
    x_q: jax.Array,
    packed_w: bp.PackedPlanes,
    epilogue: Optional[Epilogue],
    *,
    a_bits: int,
    variant: str,
    backend: str = "auto",
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    gate: bool = False,
) -> jax.Array:
    """Fully-fused bit-serial linear over 2-D quantized activations.

    ``x_q``: (M, K) int8; ``packed_w``: blocked-layout packed weight
    planes (the pack block IS the kernel's K tile — there is no separate
    ``bk`` knob here); ``epilogue``: the dequant epilogue (``None``
    returns the raw int32 accumulator — the pre-epilogue parity mode).
    The jnp backend is the staged parity oracle: decompose +
    :func:`ref.plane_matmul_ref` + :func:`apply_epilogue`, bit-identical
    pre-epilogue. The kernel makes one int8 MXU pass per K step over the
    tier's integer weights, rebuilt in VMEM from the packed words; its
    grid step comes from the shape (:func:`fused_bn` when ``bn=None``,
    :func:`fused_k_blocks`). ``gate``: occupancy-gated sparse plane
    execution, the plane-pair loop, one pack block a step and the
    :func:`auto_tiles` ``bn`` (ignored by the jnp oracle).
    """
    backend = resolve_backend(backend)
    pair_w = bs._wrap_weights(
        [x * y for x in bp.plane_weights(a_bits, variant) for y in packed_w.weights],
        jnp.int32,
    )
    if backend == "jnp":
        dec_a = bp.to_bitplanes(x_q, a_bits, variant)
        acc = ref.plane_matmul_ref(dec_a.planes, bp.unpack_planes(packed_w), pair_w)
        return acc if epilogue is None else apply_epilogue(acc, epilogue)
    m = x_q.shape[0]
    auto_bm = bm is None
    n = packed_w.mag.shape[-1]
    bm, gate_bn, _ = auto_tiles(m, x_q.shape[1], bm, None, n=n, bn=bn)
    if auto_bm:
        bm = _int8_bm(bm)  # x_q is an int8 tile
    if gate:  # the plane-pair loop: one pack block a step
        bn, k_blocks = gate_bn, 1
    else:
        bn = fused_bn(n) if bn is None else bn
        k_blocks = fused_k_blocks(bn, packed_w.n_words // (packed_w.block // bp.WORD_BITS))
    kw = dict(a_bits=a_bits, variant=variant, bm=bm, bn=bn, k_blocks=k_blocks,
              gate=gate, interpret=backend == "interpret")
    if epilogue is None:
        return _fused_pallas(x_q, packed_w, pair_w, **kw)
    return _fused_pallas(
        x_q, packed_w, pair_w,
        a_scale=epilogue.a_scale, w_scale=epilogue.w_scale, bias=epilogue.bias,
        activation=epilogue.activation, out_dtype=jnp.dtype(epilogue.out_dtype),
        **kw,
    )


def _pair_weights(wa: tuple, ww: tuple) -> jax.Array:
    return bs._wrap_weights([x * y for x in wa for y in ww], jnp.int32)


@functools.partial(jax.jit, static_argnames=("a_bits", "variant", "level"))
def _matmul_cached_jnp(
    a2: jax.Array,
    w_planes: bp.WeightPlanes,
    *,
    a_bits: int,
    variant: str,
    level: str,
) -> jax.Array:
    """jnp path over cached weight planes: the same plane-pair scan as
    :func:`repro.core.bitserial.bitserial_matmul`, minus the weight-side
    decomposition."""
    if level == "bitplane":
        dec_a = bp.to_bitplanes(a2, a_bits, variant)
        wpl = (
            w_planes.planes
            if w_planes.planes is not None
            else bp.unpack_planes(w_planes.packed, dtype=jnp.int8)
        )
    else:
        dec_a = bp.to_digits(a2, a_bits, variant)
        wpl = w_planes.planes
    dec_w = bp.PlaneDecomposition(wpl, w_planes.weights)
    return bs._plane_pair_scan(dec_a, dec_w, jnp.int32)


def bitserial_matmul(
    a: jax.Array,
    w: jax.Array,
    *,
    a_bits: int,
    w_bits: int,
    variant: str = "booth",
    level: str = "digit",
    mode: str = "fully_serial",
    backend: str = "auto",
    accum_dtype=jnp.int32,
    packed: bool | None = None,
    w_planes: bp.WeightPlanes | None = None,
    fused: bool | None = None,
    epilogue: Optional[Epilogue] = None,
    **tile_kw,
) -> jax.Array:
    """Kernel-dispatching bit-serial matmul — **legacy compatibility shim**.

    This entry point predates the plan API and re-resolved every flag
    (``packed=``, ``fused=``, ``epilogue=``, tiles, cache layout) on every
    call. It now builds (or fetches, interned by shape/precision/backend)
    a :class:`repro.core.plan.MatmulPlan` and executes it, preserving the
    historical dispatch semantics exactly:

    * ``packed=True`` still raises for unpackable configs (digit planes,
      non-serial modes, non-int32 accumulation) instead of silently
      falling back; ``None`` = auto.
    * ``fused=True`` still raises for configs the fused kernel cannot
      serve; ``None`` = auto (fused on the TPU bitplane path whenever an
      epilogue is given and the cache layout allows it); ``False`` keeps
      the staged kernels + XLA epilogue.
    * ``w_planes`` still supplies the decompose-once serving cache.

    The ``packed=``/``fused=``/``epilogue=`` keywords are **deprecated**
    (one :class:`DeprecationWarning` each per process, kept for one
    release): new code should resolve a plan once via
    :func:`repro.core.plan.make_plan` / ``plan_for_operands`` and call it
    — which is also what unlocks runtime precision reconfiguration
    (:meth:`~repro.core.plan.MatmulPlan.with_precision`).
    """
    from repro.core import plan as plan_mod

    unknown = set(tile_kw) - {"bm", "bn", "bk"}
    if unknown:
        # the old signature forwarded **tile_kw into the kernel wrappers,
        # where a typo raised TypeError; keep that fail-loud contract
        raise TypeError(
            f"bitserial_matmul got unexpected keyword argument(s) {sorted(unknown)}; "
            "tile keywords are bm/bn/bk"
        )
    for kw_name, val in (("packed", packed), ("fused", fused), ("epilogue", epilogue)):
        if val is not None:
            plan_mod._warn_deprecated(kw_name)
    plan = plan_mod.plan_for_operands(
        (a.shape, w.shape),
        a_bits=a_bits,
        w_bits=w_bits,
        variant=variant,
        level=level,
        mode=mode,
        backend=backend,
        accum_dtype=accum_dtype,
        has_epilogue=epilogue is not None,
        w_planes=w_planes,
        fused=fused,
        packed=packed,
        bm=tile_kw.get("bm"),
        bn=tile_kw.get("bn"),
        bk=tile_kw.get("bk"),
    )
    return plan(a, w, w_planes=w_planes, epilogue=epilogue)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    backend: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
    kv_lens: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Padding + dispatch wrapper. ``kv_lens`` (B,) masks per-sequence
    valid KV lengths (the slot-array serving path); ``k_scale``/``v_scale``
    (B, Hkv, Sk) consume an int8-quantized KV cache as stored, folding the
    dequant into the kernel (see kernels.flash_attention). The jnp path
    dequantizes explicitly and runs the reference — the parity oracle."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        if k_scale is not None:
            k = k.astype(jnp.float32) * k_scale[..., None]
        if v_scale is not None:
            v = v.astype(jnp.float32) * v_scale[..., None]
        return ref.attention_ref(
            q, k.astype(q.dtype), v.astype(q.dtype),
            causal=causal, sm_scale=sm_scale, kv_lens=kv_lens,
        )
    sq, sk = q.shape[2], k.shape[2]
    qp = _pad_to(q, (0, 0, block_q, 0))
    kp = _pad_to(k, (0, 0, block_k, 0))
    vp = _pad_to(v, (0, 0, block_k, 0))
    quant_kw = {}
    if k_scale is not None:
        quant_kw["k_scale"] = _pad_to(k_scale, (0, 0, block_k))
    if v_scale is not None:
        quant_kw["v_scale"] = _pad_to(v_scale, (0, 0, block_k))
    out = _flash_pallas(
        qp,
        kp,
        vp,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        # padded KV columns are masked out of the softmax, either by the
        # per-sequence lengths or by the static unpadded length
        kv_len=None if kv_lens is not None else sk,
        kv_lens=kv_lens,
        interpret=backend == "interpret",
        **quant_kw,
    )
    return out[:, :, :sq, :]
