"""Compile-once execution plans for the bit-serial matmul.

The kernel-facing API grew one boolean flag per PR (``packed=``,
``fused=``, ``w_planes=``, ``epilogue=``, ``backend=``, ``bm=``/``bk=``),
all re-resolved on every call. This module replaces that with the
plan/execute split of BISMO's instruction-generation layer: a
:class:`MatmulPlan` resolves *once* — kernel variant (fused / packed /
staged / jnp oracle), tile sizes, pack layout, epilogue fusion — and
``plan(x, w)`` executes with zero per-call dispatch logic. Plans are
interned in a :class:`PlanRegistry` keyed on shape / precision / backend /
cache layout, so repeated traces of the same layer fetch the identical
plan object.

On top of the split, plans make precision a *runtime* knob — the paper's
headline feature (a bitSMM MAC synthesized for 16 bits runs at any
effective width 1–16). Packed bit-plane decompositions are MSB-first
prefix-truncatable (:func:`repro.core.bitplanes.truncate_weight_planes`),
so :meth:`MatmulPlan.with_precision` re-plans to consume only the top
planes of the existing decomposition: no re-quantization, no new weight
bytes — an 8-bit weight cache serves 1..8-bit execution, and the serving
engines swap plans mid-flight (``set_precision``).

Plan lifecycle (DESIGN.md §7):

    policy + layer name + shapes ──make_plan──► PlanKey ──registry──► MatmulPlan
                                                     │ miss
                                                _build_plan (dispatch
                                                resolution, runs once)
    plan(x_q, w_q, w_planes=…, epilogue=…)  ──► resolved kernel call
    plan.with_precision(a', w')             ──► sibling plan, same stored
                                                operands, truncated planes
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Tuple

import jax.numpy as jnp

from repro.core import bitplanes as bp
from repro.core import bitserial as bs
from repro.core.precision import LayerPrecision, PrecisionPolicy

__all__ = [
    "MatmulPlan",
    "PlanKey",
    "PlanRegistry",
    "DEFAULT_REGISTRY",
    "make_plan",
    "plan_for_operands",
    "plan_cacheable",
    "truncation_audit",
]


def _ops():
    # repro.kernels.ops imports this module lazily (its bitserial_matmul is
    # a compatibility shim over plans); importing it lazily here breaks the
    # cycle without an import-time dependency in either direction.
    from repro.kernels import ops

    return ops


# ---------------------------------------------------------------------------
# Keys and registry
# ---------------------------------------------------------------------------


def _cache_spec(wp: Optional[bp.WeightPlanes]) -> Optional[tuple]:
    """Static descriptor of a weight-plane cache (route resolution only
    needs the layout, never the array contents). The plane count is part
    of the layout: a *compacted* cache (zero planes dropped at pack time)
    has fewer planes than its stored width and therefore different
    operand shapes and pair-weight grids."""
    if wp is None:
        return None
    packed = wp.packed
    return (
        wp.level,
        wp.variant,
        int(wp.w_bits),
        packed is not None,
        None if packed is None else packed.block,
        wp.planes is not None,
        len(wp.weights),
        packed is not None and packed.checksum is not None,
    )


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Everything plan resolution depends on — all static Python values,
    hashable, and independent of array contents."""

    m: int
    k: int
    n: int
    a_bits: int  # executed activation width
    w_bits: int  # executed weight width
    a_in_bits: int  # width activations are *provided* at (>= a_bits)
    w_in_bits: int  # width weights are *provided*/stored at (>= w_bits)
    variant: str
    level: str
    mode: str
    backend: str  # resolved (never "auto")
    accum: str  # accumulator dtype name
    has_epilogue: bool
    cache: Optional[tuple]  # _cache_spec of the weight-plane cache
    fused: Optional[bool]  # requested flag (None = auto)
    packed: Optional[bool]  # requested flag (None = auto)
    bm: Optional[int]  # requested tiles (None = auto)
    bn: Optional[int]
    bk: Optional[int]
    sparsity: str = "off"  # occupancy-gated sparse plane execution
    integrity: str = "off"  # ABFT row-sum checking: off / detect / scrub
    #: tensor-parallel placement: None (unsharded) or a static
    #: ``(axis_name, axis_size, role)`` triple, role "col" | "row". The
    #: m/k/n fields of a sharded key are the *local* (per-shard) shape, so
    #: tile resolution sees what the device executes — the field exists so
    #: a shard plan never aliases an unsharded plan of the same local
    #: shape (their collective/epilogue contracts differ: a "row" plan is
    #: built without an epilogue and its caller psums the raw accumulator).
    shard: Optional[tuple] = None


class PlanRegistry:
    """Interning cache: ``PlanKey -> MatmulPlan``.

    ``get`` returns the *identical* plan object for a repeated key (the
    cache-hit contract the tests assert), so dispatch resolution runs once
    per distinct (shape, precision, backend, layout) combination per
    process. ``hits``/``misses`` are observability counters.

    An attached tuner (``attach_tuner``; see ``core/autotune``) is
    consulted during plan builds *before* the ``auto_tiles`` fallback —
    with a persistent store behind it, compile-once becomes
    tune-once-per-fleet. ``clear`` drops plans but keeps the tuner: a
    re-resolved plan should still find its stored tiles.
    """

    def __init__(self) -> None:
        self._plans: dict[PlanKey, "MatmulPlan"] = {}
        self.hits = 0
        self.misses = 0
        self.tuner = None

    def get(self, key: PlanKey) -> "MatmulPlan":
        plan = self._plans.get(key)
        if plan is None:
            self.misses += 1
            plan = _build_plan(key, self)
            self._plans[key] = plan
        else:
            self.hits += 1
        return plan

    def attach_tuner(self, tuner) -> None:
        """Attach (or with None, detach) a ``PlanAutotuner``-shaped object:
        ``tiles_for(key, kernel) -> (bm, bn, bk) | None`` plus ``stats()``.
        Injected by the serving layer — core never imports runtime."""
        self.tuner = tuner

    def store_stats(self) -> dict:
        """Tuner/store counters for engine ``stats()`` blocks; zeros with
        no tuner attached so callers need not branch."""
        if self.tuner is None:
            return {"store_hits": 0, "store_misses": 0, "tunes": 0}
        return self.tuner.stats()

    def clear(self) -> None:
        self._plans.clear()
        self.hits = 0
        self.misses = 0

    def plans(self) -> tuple["MatmulPlan", ...]:
        """Snapshot of every resolved plan (public enumeration — the bench
        truncation audit and the examples introspect routes through this)."""
        return tuple(self._plans.values())

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._plans


#: Process-wide default registry (``make_plan`` / ``with_precision`` use it
#: unless given another one; tests may instantiate private registries).
DEFAULT_REGISTRY = PlanRegistry()


def truncation_audit(registry: Optional[PlanRegistry] = None) -> dict:
    """Audit the no-requantization invariant over every *dialed* plan in
    ``registry`` (default: the process registry): a plan resolved with
    ``w_shift > 0`` — executing below its stored width — must consume the
    stored decomposition by MSB-prefix truncation (``trunc_cache``),
    never re-decompose the weight (``requant_w``). The precision-sweep
    bench and the autopilot bench both gate on this; the engine's dial
    check calls it after binding a new tier.

    Returns ``{"dialed_plans", "routes", "truncated_ok"}`` where
    ``truncated_ok`` is False when no dialed plan exists (vacuous audits
    must not pass) or any dialed plan requantizes.
    """
    reg = DEFAULT_REGISTRY if registry is None else registry
    dialed = [p for p in reg.plans() if p.w_shift > 0]
    return {
        "dialed_plans": len(dialed),
        "routes": sorted({p.kernel for p in dialed}),
        "truncated_ok": bool(dialed)
        and all(p.trunc_cache and not p.requant_w for p in dialed),
    }


# ---------------------------------------------------------------------------
# Plan resolution (the one-time dispatch logic)
# ---------------------------------------------------------------------------


def _resolve_packed(packed: Optional[bool], level: str) -> bool:
    """Whether the packed plane kernel serves: only when asked for. Its
    activation word block ``(bm, bk / 32)`` puts a sub-lane last dimension
    before Mosaic whenever the K tile splits K, which the TPU compiler
    refuses — so auto routing keeps to the fused and unpacked-plane
    kernels, on the chip exactly as in the interpreter."""
    return level == "bitplane" and bool(packed)


def _build_plan(key: PlanKey, registry: "PlanRegistry") -> "MatmulPlan":
    """Port of the per-call flag-resolution tree that used to live in
    ``ops.bitserial_matmul`` — now run exactly once per PlanKey."""
    ops = _ops()
    serial = key.mode == "fully_serial"
    int32_acc = key.accum == "int32"
    kernel_ok = (
        key.level == "bitplane" or (key.level == "digit" and key.variant == "booth")
    ) and int32_acc
    use_packed = serial and int32_acc and _resolve_packed(key.packed, key.level)
    if key.packed and not use_packed:
        raise ValueError(
            "packed=True requires level='bitplane', mode='fully_serial' and "
            f"int32 accumulation; got level={key.level!r}, mode={key.mode!r}, "
            f"accum_dtype={key.accum}"
        )

    fused_ok = (
        key.has_epilogue
        and serial
        and int32_acc
        and key.level == "bitplane"
        and key.variant in ("sbmwc", "booth")
        and key.a_bits <= 8
        and key.w_bits <= 8
    )
    if key.fused and not fused_ok:
        raise ValueError(
            "fused=True requires an epilogue, level='bitplane', "
            "mode='fully_serial', int32 accumulation and <=8-bit operands; "
            f"got epilogue={'set' if key.has_epilogue else None}, "
            f"level={key.level!r}, mode={key.mode!r}, "
            f"a_bits={key.a_bits}, w_bits={key.w_bits}"
        )
    use_fused = fused_ok and key.backend != "jnp" and key.fused is not False

    # ABFT-checked execution (DESIGN.md §9): the row-sum identity needs
    # the raw int32 accumulator of the exact fully-serial bitplane path.
    # The fused kernel hides it (its epilogue scales in-kernel), so
    # integrity falls back to the staged/cached routes — that is the
    # "integrity overhead" the bench's integrity section measures.
    check = key.integrity != "off"
    if check:
        if not (serial and int32_acc and key.level == "bitplane"):
            raise ValueError(
                "integrity-checked execution requires level='bitplane', "
                "mode='fully_serial' and int32 accumulation; got "
                f"level={key.level!r}, mode={key.mode!r}, accum={key.accum}"
            )
        if key.fused:
            raise ValueError(
                "fused=True cannot be integrity-checked: the fused epilogue "
                "writes the scaled output and hides the int32 accumulator "
                "the row-sum identity compares; leave fused unset (or False) "
                "when integrity != 'off'"
            )
        use_fused = False

    # Cache usability: the cache must hold the operand as *stored*
    # (w_in_bits); executing below that width truncates its plane prefix
    # (bitplane level only — radix-256 digits are not truncatable).
    cache = key.cache
    w_shift = key.w_in_bits - key.w_bits
    cache_ok = (
        cache is not None
        and serial
        and int32_acc
        and cache[0] == key.level
        and cache[1] == key.variant
        and cache[2] == key.w_in_bits
        and (w_shift == 0 or key.level == "bitplane")
    )
    fused_cache_ok = cache_ok and cache[3] and cache[4] is not None
    if check and cache_ok and not cache[7]:
        raise ValueError(
            "integrity-checked cached execution needs a checksummed plane "
            "cache: rebuild it with make_weight_planes(..., checksum=True) "
            "(quantize_params does this when policy.integrity != 'off')"
        )
    if use_fused and cache_ok and not fused_cache_ok and key.fused is None:
        # A cache in the global planar layout can't feed the fused kernel;
        # auto mode keeps the decompose-once staged path instead of
        # silently re-packing the static weight every call.
        use_fused = False

    # Route selection (static).
    if use_fused:
        kernel = "fused_cached" if fused_cache_ok else "fused_repack"
    elif cache_ok:
        if key.backend == "jnp" or (key.level == "digit" and key.variant != "booth"):
            kernel = "cached_scan"
        elif key.level == "bitplane" and use_packed and cache[3]:
            kernel = "cached_packed"
        else:
            kernel = "cached_planes"
    elif (key.backend == "jnp" and not use_packed) or not kernel_ok or not serial:
        kernel = "oracle"
    elif use_packed:
        kernel = "staged_packed"
    else:
        kernel = "staged"

    # Tile resolution (once; executors pass explicit tiles to the kernel
    # wrappers, which never override explicit values). Explicit tiles win
    # unconditionally; otherwise an attached tuner is consulted (store
    # hit or fresh micro-benchmark — core/autotune) and only then the
    # auto_tiles heuristic. bn joins the heuristic: fused decode steps
    # take the N-derived wide tile.
    tuned = False
    bm = bn = bk = None
    if (
        registry.tuner is not None
        and key.bm is None
        and key.bn is None
        and key.bk is None
    ):
        tiles = registry.tuner.tiles_for(key, kernel)
        if tiles is not None:
            bm, bn, bk = tiles
            tuned = True
    if not tuned:
        bm, bn, bk = ops.auto_tiles(key.m, key.k, key.bm, key.bk, n=key.n, bn=key.bn)
        if key.bm is None and kernel in ("fused_cached", "fused_repack", "staged", "cached_planes"):
            bm = ops._int8_bm(bm)  # these kernels consume int8 operand tiles
    pack_block = bk  # fused_repack packs the weight with the K tile as block

    # Occupancy gating is a property of the plane-pair kernels: the jnp
    # routes compute the full sum (and the oracle has no occupancy), so
    # only the Pallas plane-pair kernels receive the gate flag. "compact"
    # implies gating too — kept planes still have zero K blocks to skip.
    gate = key.sparsity in ("gate", "compact") and kernel in (
        "fused_cached", "fused_repack", "cached_packed", "staged_packed"
    ) and key.backend != "jnp"
    if not tuned and key.bn is None and kernel.startswith("fused") and not gate:
        bn = ops.fused_bn(key.n)  # one MXU pass a tile: a wide step pays

    a_shift = key.a_in_bits - key.a_bits
    requant_w = w_shift > 0 and kernel in (
        "fused_repack", "staged", "staged_packed", "oracle"
    )
    trunc_cache = w_shift > 0 and kernel.startswith(("cached", "fused_cached"))
    return MatmulPlan(
        key=key,
        registry=registry,
        kernel=kernel,
        bm=bm,
        bn=bn,
        bk=bk,
        pack_block=pack_block,
        a_shift=a_shift,
        w_shift=w_shift,
        scale_mult=float(1 << (a_shift + w_shift)),
        requant_w=requant_w,
        trunc_cache=trunc_cache,
        gate=gate,
        check=check,
        tuned=tuned,
    )


# ---------------------------------------------------------------------------
# Executors — one per resolved kernel route, zero flag logic inside
# ---------------------------------------------------------------------------


def _shift_activations(x, from_bits: int, to_bits: int, variant: str):
    """Runtime activation-width reduction for operands provided at a wider
    quantization (``with_precision`` on an existing plan). Booth's round
    half up saturates at the two's-complement max so the in-kernel
    bit-slicer and the jnp oracle see identical values."""
    q = bp.shift_requantize(x, from_bits, to_bits, variant)
    if variant == "booth":
        q = jnp.minimum(q, (1 << (to_bits - 1)) - 1)
    return q.astype(jnp.int8 if to_bits <= 8 else jnp.int32)


def _finish(plan: "MatmulPlan", out2, lead, ep):
    ops = _ops()
    out = out2.reshape(lead + (out2.shape[-1],))
    return out if ep is None else ops.apply_epilogue(out, ep)


def _trunc(plan: "MatmulPlan", wp: bp.WeightPlanes) -> bp.WeightPlanes:
    return bp.truncate_weight_planes(wp, plan.key.w_bits) if plan.trunc_cache else wp


def _abft_check(plan: "MatmulPlan", x2, out2, check_vec) -> None:
    """ABFT row-sum identity on the pre-epilogue int32 accumulator:
    ``sum_n out2[m, n] == x2 @ check_vec`` exactly (int32 wraparound on
    both sides), where ``check_vec`` is the row-sum vector of the integer
    weight matrix the kernel consumed. Any single-bit corruption of the
    consumed weight state (or of the accumulator) breaks the identity;
    the (traced) mismatch flag is reported to the ambient integrity
    collector under this plan's key."""
    from repro.core import integrity

    expected = jnp.matmul(x2.astype(jnp.int32), check_vec.astype(jnp.int32))
    got = jnp.sum(out2.astype(jnp.int32), axis=-1)
    integrity.report(plan.key, jnp.any(expected != got))


def _exec_fused_cached(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    packed_w = _trunc(plan, wp).packed
    ep2 = ep._replace(a_scale=ep.a_scale.reshape(-1, 1))
    out2 = ops.fused_linear(
        x2, packed_w, ep2, a_bits=key.a_bits, variant=key.variant,
        backend=key.backend, bm=plan.bm, bn=plan.bn, gate=plan.gate,
    )
    return out2.reshape(lead + (packed_w.mag.shape[-1],))


def _exec_fused_repack(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    dec_w = bp.to_bitplanes(w, key.w_bits, key.variant)
    packed_w = bp.pack_decomposition(
        dec_w, axis=-2, variant=key.variant, block=plan.pack_block
    )
    ep2 = ep._replace(a_scale=ep.a_scale.reshape(-1, 1))
    out2 = ops.fused_linear(
        x2, packed_w, ep2, a_bits=key.a_bits, variant=key.variant,
        backend=key.backend, bm=plan.bm, bn=plan.bn, gate=plan.gate,
    )
    return out2.reshape(lead + (packed_w.mag.shape[-1],))


def _exec_cached_packed(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    wp_eff = _trunc(plan, wp)
    dec_a = bp.to_bitplanes(x2, key.a_bits, key.variant)
    pw = ops._pair_weights(dec_a.weights, wp_eff.weights)
    pa = bp.pack_planes(
        dec_a.planes, axis=-1, ternary=key.variant == "booth",
        block=wp_eff.packed.block,
    )
    out2 = ops.plane_matmul_packed(
        pa, wp_eff.packed, pw, backend=key.backend,
        bm=plan.bm, bn=plan.bn, bk=plan.bk, gate=plan.gate,
    )
    if plan.check:
        _abft_check(plan, x2, out2, bp.checksum_vector(wp_eff.packed))
    return _finish(plan, out2, lead, ep)


def _exec_cached_planes(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    wp_eff = _trunc(plan, wp)
    if key.level == "bitplane":
        dec_a = bp.to_bitplanes(x2, key.a_bits, key.variant)
        wpl = (
            wp_eff.planes
            if wp_eff.planes is not None
            else bp.unpack_planes(wp_eff.packed)
        )
    else:
        dec_a = bp.to_digits(x2, key.a_bits, key.variant)
        wpl = wp_eff.planes
    pw = ops._pair_weights(dec_a.weights, wp_eff.weights)
    out2 = ops.plane_matmul(
        dec_a.planes.astype(jnp.int8), wpl.astype(jnp.int8), pw,
        backend=key.backend, bm=plan.bm, bn=plan.bn, bk=plan.bk,
    )
    if plan.check:
        _abft_check(plan, x2, out2, bp.checksum_vector(wp_eff.packed))
    return _finish(plan, out2, lead, ep)


def _exec_cached_scan(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    wp_eff = _trunc(plan, wp)
    out2 = ops._matmul_cached_jnp(
        x2, wp_eff, a_bits=key.a_bits, variant=key.variant, level=key.level
    )
    if plan.check:
        _abft_check(plan, x2, out2, bp.checksum_vector(wp_eff.packed))
    return _finish(plan, out2, lead, ep)


def _exec_staged(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if key.level == "bitplane":
        dec_a = bp.to_bitplanes(x2, key.a_bits, key.variant)
        dec_w = bp.to_bitplanes(w, key.w_bits, key.variant)
    else:
        dec_a = bp.to_digits(x2, key.a_bits, key.variant)
        dec_w = bp.to_digits(w, key.w_bits, key.variant)
    pw = ops._pair_weights(dec_a.weights, dec_w.weights)
    if plan.kernel == "staged_packed":
        ternary = key.variant == "booth"
        pa = bp.pack_planes(dec_a.planes, axis=-1, ternary=ternary)
        pwk = bp.pack_planes(dec_w.planes, axis=-2, ternary=ternary)
        out2 = ops.plane_matmul_packed(
            pa, pwk, pw, backend=key.backend, bm=plan.bm, bn=plan.bn, bk=plan.bk,
            gate=plan.gate,
        )
    else:
        out2 = ops.plane_matmul(
            dec_a.planes.astype(jnp.int8), dec_w.planes.astype(jnp.int8), pw,
            backend=key.backend, bm=plan.bm, bn=plan.bn, bk=plan.bk,
        )
    if plan.check:
        # uncached route: the reference row-sum comes from the in-hand
        # integer weight (already requantized to the executed width)
        _abft_check(plan, x2, out2, w.astype(jnp.int32).sum(axis=-1))
    return _finish(plan, out2, lead, ep)


def _exec_oracle(plan, x, w, wp, ep):
    ops = _ops()
    key = plan.key
    acc = bs.bitserial_matmul(
        x, w, a_bits=key.a_bits, w_bits=key.w_bits, variant=key.variant,
        level=key.level, mode=key.mode, accum_dtype=jnp.dtype(key.accum),
    )
    if plan.check:
        _abft_check(
            plan,
            x.reshape((-1, x.shape[-1])),
            acc.reshape((-1, acc.shape[-1])),
            w.astype(jnp.int32).sum(axis=-1),
        )
    return acc if ep is None else ops.apply_epilogue(acc, ep)


_EXECUTORS: dict[str, Callable] = {
    "fused_cached": _exec_fused_cached,
    "fused_repack": _exec_fused_repack,
    "cached_packed": _exec_cached_packed,
    "cached_planes": _exec_cached_planes,
    "cached_scan": _exec_cached_scan,
    "staged_packed": _exec_staged,
    "staged": _exec_staged,
    "oracle": _exec_oracle,
}


# ---------------------------------------------------------------------------
# MatmulPlan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    """A fully-resolved bit-serial matmul: call it with operands.

    ``plan(x_q, w_q, w_planes=…, epilogue=…)`` runs the route this plan
    resolved to, with the tiles it resolved, period — no per-call flag
    logic. Unused operands may be omitted (a cached-route plan never reads
    ``w_q``; an uncached one never reads ``w_planes``).

    ``with_precision(a_bits, w_bits)`` returns the sibling plan executing
    at a lower width against the *same* stored operands: weight planes by
    MSB-prefix truncation of the existing decomposition (or shift
    requantization on cache-less routes), activations by shift. The
    dequant correction ``2^(a_shift + w_shift)`` folds into the epilogue's
    ``w_scale`` — exact in f32. Calls without an epilogue return the raw
    truncated-precision accumulator (scales are then the caller's).
    """

    key: PlanKey
    #: owning registry — with_precision interns sibling plans here, so a
    #: private registry never leaks dialed plans into the global one
    registry: "PlanRegistry" = dataclasses.field(compare=False, repr=False)
    kernel: str
    bm: int
    bn: int
    bk: int
    pack_block: int
    a_shift: int
    w_shift: int
    scale_mult: float
    requant_w: bool
    trunc_cache: bool
    #: occupancy-gated sparse plane execution resolved for this route
    #: (sparsity != "off" on a Pallas plane-pair kernel)
    gate: bool = False
    #: ABFT row-sum verification resolved for this route (integrity !=
    #: "off"; executors compare the accumulator row-sums against the
    #: cache's column checksums and report to the integrity collector)
    check: bool = False
    #: provenance: tiles came from an attached autotuner (store hit or
    #: fresh micro-benchmark) rather than the ``auto_tiles`` heuristic.
    #: Tuned plans are bit-identical to heuristic plans — tiles change
    #: the MXU pass schedule, never the integer arithmetic.
    tuned: bool = False

    def __call__(self, x, w=None, *, w_planes=None, epilogue=None):
        key = self.key
        if key.has_epilogue != (epilogue is not None):
            raise ValueError(
                f"plan was resolved {'with' if key.has_epilogue else 'without'} "
                f"an epilogue but called {'without' if epilogue is None else 'with'} "
                "one; build a matching plan (has_epilogue=)"
            )
        if x.shape[-1] != key.k:
            raise ValueError(f"plan expects K={key.k}, got x K={x.shape[-1]}")
        if self.a_shift:
            x = _shift_activations(x, key.a_in_bits, key.a_bits, key.variant)
        if epilogue is not None and self.scale_mult != 1.0:
            epilogue = epilogue._replace(w_scale=epilogue.w_scale * self.scale_mult)
        if self.requant_w:
            w = bp.shift_requantize(w, key.w_in_bits, key.w_bits, key.variant)
        return _EXECUTORS[self.kernel](self, x, w, w_planes, epilogue)

    def with_precision(
        self, a_bits: Optional[int] = None, w_bits: Optional[int] = None
    ) -> "MatmulPlan":
        """Sibling plan at a lower runtime precision (same stored operands).

        ``None`` keeps an operand at this plan's width. The ceiling is the
        width the operands are *provided* at (``a_in_bits``/``w_in_bits``)
        — the software analogue of the accelerator's synthesis-time
        maximum. Repeated calls intern in the registry, so switching back
        and forth costs nothing after the first resolution.
        """
        a = self.key.a_bits if a_bits is None else a_bits
        w = self.key.w_bits if w_bits is None else w_bits
        if not 1 <= a <= self.key.a_in_bits:
            raise ValueError(
                f"a_bits must be in [1, {self.key.a_in_bits}] "
                f"(the provided operand width), got {a}"
            )
        if not 1 <= w <= self.key.w_in_bits:
            raise ValueError(
                f"w_bits must be in [1, {self.key.w_in_bits}] "
                f"(the stored decomposition width), got {w}"
            )
        if (a, w) == (self.key.a_bits, self.key.w_bits):
            return self
        return self.registry.get(
            dataclasses.replace(self.key, a_bits=a, w_bits=w)
        )

    def sparsity_stats(self, w_planes: Optional[bp.WeightPlanes] = None) -> dict:
        """Plane-pair MXU passes skipped vs executed under this plan.

        Static, weight-side accounting from the cache's occupancy bitmap
        (host-side — materializes the bitmap with numpy; do not call under
        ``jit``): ``pair_passes_dense`` is what sparsity="off" issues at
        the executed width, ``pair_passes_after_compaction`` what survives
        the cache's plane compaction, ``pair_passes_executed`` what the
        weight-occupancy gate leaves. Dynamic activation-side gating skips
        strictly more at run time and is not counted here.
        ``mxu_passes_per_tile`` says which path the plan engages: 1 on the
        fused routes' single int8 pass, ``a_planes x planes`` on every
        plane-pair loop (the gated fused kernel among them). Without a
        packed bit-plane cache only the mode/route fields are reported,
        with the dense plane count.
        """
        import numpy as np

        key = self.key
        single_pass = self.kernel in ("fused_cached", "fused_repack") and not self.gate
        out = {
            "mode": key.sparsity,
            "kernel": self.kernel,
            "gated": self.gate,
            "planes_dense": key.w_bits,
            "a_planes": key.a_bits,
            "mxu_passes_per_tile": 1 if single_pass else key.a_bits * key.w_bits,
        }
        if (
            w_planes is None
            or w_planes.packed is None
            or w_planes.packed.occupancy is None
        ):
            return out
        wp = _trunc(self, w_planes)
        packed = wp.packed
        wpt = (packed.block or self.bk) // bp.WORD_BITS
        occ = np.asarray(packed.occupancy)
        occ = occ.any(axis=tuple(range(occ.ndim - 2)))  # stacked caches: OR
        n_kept = occ.shape[0]
        # same tile reduction the gated kernels consume — one source of truth
        tiles = np.asarray(bp.occupancy_per_tile(jnp.asarray(occ, jnp.int32), wpt))
        nk = tiles.shape[1]
        occupied = int(tiles.sum())
        dense = key.a_bits * key.w_bits * nk
        executed = key.a_bits * occupied
        out.update(
            mxu_passes_per_tile=1 if single_pass else key.a_bits * n_kept,
            planes_kept=n_kept,
            k_tiles=nk,
            pair_passes_dense=dense,
            pair_passes_after_compaction=key.a_bits * n_kept * nk,
            pair_passes_executed=executed,
            pair_passes_skipped=dense - executed,
            skipped_fraction=round(1.0 - executed / max(dense, 1), 4),
        )
        return out

    def integrity_stats(self) -> dict:
        """Pass/fail accounting of this plan's ABFT row-sum checks.

        Reads the process-wide tally keyed by :class:`PlanKey` (shared by
        every engine and collector — plan interning makes the key the
        natural aggregation unit): ``checks`` harvested check executions,
        ``alarms`` of them mismatching.
        """
        from repro.core import integrity

        out = {
            "mode": self.key.integrity,
            "checked": self.check,
            "kernel": self.kernel,
        }
        out.update(integrity.stats_for(self.key))
        return out

    def describe(self) -> str:
        k = self.key
        s = (
            f"MatmulPlan[{k.m}x{k.k}x{k.n}] w{k.w_bits}a{k.a_bits} "
            f"{k.level}/{k.variant} -> {self.kernel} backend={k.backend} "
            f"tiles=(bm={self.bm}, bn={self.bn}, bk={self.bk})"
        )
        if self.tuned:
            s += " tuned"
        if self.a_shift or self.w_shift:
            s += f" trunc(w {k.w_in_bits}->{k.w_bits}, a {k.a_in_bits}->{k.a_bits})"
        if k.sparsity != "off":
            s += f" sparsity={k.sparsity}{' (gated)' if self.gate else ''}"
        if k.integrity != "off":
            s += f" integrity={k.integrity}{' (checked)' if self.check else ''}"
        return s


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _norm_shapes(shapes) -> Tuple[int, int, int]:
    """(m, k, n) ints, or ((…, k), (k, n)) shape pair."""
    if len(shapes) == 3 and all(isinstance(s, int) for s in shapes):
        return tuple(shapes)  # type: ignore[return-value]
    if len(shapes) == 2:
        x_shape, w_shape = shapes
        m = 1
        for d in x_shape[:-1]:
            m *= int(d)
        return m, int(x_shape[-1]), int(w_shape[-1])
    raise ValueError(f"shapes must be (m, k, n) or (x_shape, w_shape), got {shapes!r}")


def plan_for_operands(
    shapes,
    *,
    a_bits: int,
    w_bits: int,
    variant: str = "booth",
    level: str = "digit",
    mode: str = "fully_serial",
    backend: str = "auto",
    accum_dtype: Any = jnp.int32,
    has_epilogue: bool = False,
    w_planes: Optional[bp.WeightPlanes] = None,
    a_in_bits: Optional[int] = None,
    w_in_bits: Optional[int] = None,
    fused: Optional[bool] = None,
    packed: Optional[bool] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    sparsity: str = "off",
    integrity: str = "off",
    shard: Optional[tuple] = None,
    registry: Optional[PlanRegistry] = None,
) -> MatmulPlan:
    """Policy-free plan construction from explicit operand metadata (the
    compatibility shim and kernel-level callers use this; model code goes
    through :func:`make_plan`).

    ``shard``: static tensor-parallel placement triple
    ``(axis_name, axis_size, role)`` — see :class:`PlanKey`. ``shapes``
    must then be the *local* per-shard shapes."""
    if sparsity not in ("off", "gate", "compact"):
        raise ValueError(
            f"sparsity must be 'off', 'gate' or 'compact', got {sparsity!r}"
        )
    if integrity not in ("off", "detect", "scrub"):
        raise ValueError(
            f"integrity must be 'off', 'detect' or 'scrub', got {integrity!r}"
        )
    m, k, n = _norm_shapes(shapes)
    key = PlanKey(
        m=m, k=k, n=n,
        a_bits=a_bits, w_bits=w_bits,
        a_in_bits=a_bits if a_in_bits is None else a_in_bits,
        w_in_bits=w_bits if w_in_bits is None else w_in_bits,
        variant=variant, level=level, mode=mode,
        backend=_ops().resolve_backend(backend),
        accum=jnp.dtype(accum_dtype).name,
        has_epilogue=has_epilogue,
        cache=_cache_spec(w_planes),
        fused=fused, packed=packed,
        bm=bm, bn=bn, bk=bk,
        sparsity=sparsity,
        integrity=integrity,
        shard=shard,
    )
    return (DEFAULT_REGISTRY if registry is None else registry).get(key)


def make_plan(
    policy: PrecisionPolicy,
    layer_name: str,
    shapes,
    backend: str = "auto",
    *,
    w_planes: Optional[bp.WeightPlanes] = None,
    w_stored_bits: Optional[int] = None,
    has_epilogue: bool = True,
    accum_dtype: Any = None,
    registry: Optional[PlanRegistry] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    shard: Optional[tuple] = None,
) -> MatmulPlan:
    """Resolve the execution plan for one layer of a policy.

    ``shapes``: ``(m, k, n)`` or ``(x_shape, w_shape)``. ``w_stored_bits``
    is the width the weights are stored/decomposed at (the configured
    policy width on the serving path); when the policy's runtime dial
    (:meth:`PrecisionPolicy.with_runtime_bits`) lowers the executed width
    below it, the plan consumes the stored decomposition's plane prefix.
    Activations are assumed quantized at the *effective* width by the
    caller (they are re-quantized per token anyway). ``shard`` is the
    static tensor-parallel placement triple (with local ``shapes``) — see
    :class:`PlanKey`. ``backend="auto"`` defers to ``policy.backend``.
    """
    configured = policy.lookup(layer_name)
    if not configured.active:
        raise ValueError(f"layer {layer_name!r}: policy is inactive — no plan to build")
    eff = policy.effective(configured)
    if accum_dtype is None:
        accum_dtype = jnp.int32 if max(eff.w_bits, eff.a_bits) <= 8 else jnp.float32
    return plan_for_operands(
        shapes,
        a_bits=eff.a_bits,
        w_bits=eff.w_bits,
        a_in_bits=eff.a_bits,
        w_in_bits=configured.w_bits if w_stored_bits is None else w_stored_bits,
        variant=policy.variant,
        level=policy.level,
        mode=policy.mode,
        backend=policy.backend if backend == "auto" else backend,
        accum_dtype=accum_dtype,
        has_epilogue=has_epilogue,
        w_planes=w_planes,
        fused=policy.fuse_epilogue,
        bm=bm, bn=bn, bk=bk,
        sparsity=policy.sparsity,
        integrity=policy.integrity,
        shard=shard,
        registry=registry,
    )


def plan_cacheable(policy: PrecisionPolicy, prec: LayerPrecision) -> bool:
    """Whether a layer at ``prec`` can use the decompose-once weight-plane
    cache (and therefore plan-time truncation): the int32-exact
    fully-serial kernel configs — wider configs accumulate in f32 and
    resolve to the jnp oracle anyway."""
    return (
        policy.mode == "fully_serial"
        and policy.level in ("bitplane", "digit")
        and prec.active
        and max(prec.w_bits, prec.a_bits) <= 8
    )


# ---------------------------------------------------------------------------
# Deprecation plumbing for the legacy flag API
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set[str] = set()

_DEPRECATION_TEXT = {
    "packed": "bitserial_matmul(packed=…) is deprecated; packing is resolved "
    "once at plan time — use repro.core.plan.make_plan / plan_for_operands",
    "fused": "bitserial_matmul(fused=…) is deprecated; epilogue fusion is "
    "resolved once at plan time — use repro.core.plan.make_plan / "
    "plan_for_operands",
    "epilogue": "bitserial_matmul(epilogue=…) is deprecated; build a plan "
    "with has_epilogue=True and pass the epilogue to the plan call",
}


def _warn_deprecated(kw: str) -> None:
    """One DeprecationWarning per legacy kwarg per process (the shim keeps
    working for one release; see ISSUE 4 satellite 1)."""
    if kw in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(kw)
    warnings.warn(_DEPRECATION_TEXT[kw], DeprecationWarning, stacklevel=3)


def _reset_deprecation_warnings() -> None:
    _DEPRECATION_WARNED.clear()
