"""Parameter-pytree quantization for the serving path.

Converts dense linear weights to stored-quantized form ({'w_q','w_scale'})
according to the PrecisionPolicy — the software analogue of loading
pre-quantized weights into accelerator memory at their configured widths
(the paper's weights-in-memory-at-b-bits deployment model). Halves (int8)
the serving HBM footprint vs bf16, visible in the dry-run memory terms.

With ``plane_cache=True`` each quantized weight is additionally decomposed
into its bit/digit planes exactly once at load time (packed to int32 words
at bit-plane level) and the result rides in the param tree as
``'w_planes'`` — so the per-forward cost of the bit-serial path is only
the activation-side decomposition. See DESIGN.md §"Weight-cache
lifecycle".

The cache is what makes precision a *runtime* knob (DESIGN.md §7): at
bit-plane level the stored decomposition is MSB-prefix truncatable, so
one quantization at the policy width serves every lower width — an
execution plan fetched with a runtime-dialed policy
(:meth:`PrecisionPolicy.with_runtime_bits`) consumes only the top planes,
with zero re-quantization. Which layers are cacheable is the plan
module's contract (:func:`repro.core.plan.plan_cacheable`), so quantize
time and plan resolution can never disagree about cache usability.

Tensor-parallel serving (DESIGN.md §11) composes with this module by
ordering, not modification: ``sharding.tp.shard_quantized`` calls
:func:`quantize_params` over the **full** weights first (global scales),
slices ``w_q`` per shard, and re-runs the plane decomposition per shard
— so sharded plane caches, checksums, and occupancy masks are exact
slices/recomputations of what this module would build on one device.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import bitplanes as bp
from repro.core.plan import plan_cacheable
from repro.core.precision import PrecisionPolicy
from repro.core.quantize import quantize


def _is_linear(node) -> bool:
    return isinstance(node, dict) and "w" in node and getattr(node["w"], "ndim", 0) >= 2


def decompose_linear_weight(
    w_q: jax.Array,
    *,
    w_bits: int,
    variant: str,
    level: str,
    block: int | None = bp.DEFAULT_BLOCK,
    checksum: bool = False,
) -> bp.WeightPlanes:
    """Decompose one stored-quantized weight into cached planes.

    At bit-plane level the cache stores the *blocked* packed layout
    (``block`` K values planar-packed per chunk) — the format the fused
    linear kernel consumes directly against raw int8 activations; the
    staged packed kernel accepts it too (the activation side is packed to
    match). Only the packed words and the per-channel scales ride in the
    serving tree.

    Stacked/scanned weights (leading layer/expert dims) are decomposed
    one matrix at a time (``lax.map`` inside one jitted program), so the
    cache leaves keep their leading axes scannable by ``lax.scan`` while
    the int32 plane temporaries stay one matrix deep: eagerly over a whole
    stack they would outgrow a chip's memory at published widths. A
    module-level function so load-time decomposition counts can be
    observed (tests monkeypatch this).
    """
    return _decompose(
        w_q, w_bits=w_bits, variant=variant, level=level, block=block,
        checksum=checksum,
    )


#: Output columns decomposed per step. Packing materializes ~45 bytes of
#: temporaries per weight on a TPU: 9 GiB for a whole 4096 x 49408
#: lm_head, under 1 GiB per 4096-column slice.
COLUMN_CHUNK = 4096


def _join_columns(parts: bp.WeightPlanes, n: int) -> bp.WeightPlanes:
    """Reassemble per-column-chunk caches (leading chunk axis) into the
    cache of the whole ``n``-column matrix: packing runs along K, so
    columns are independent — words and planes concatenate, occupancy
    ORs and checksums sum over the chunks (zero padding columns are
    inert in both)."""

    def cat(x):  # (chunks, ..., C) -> (..., chunks * C)[..., :n]
        if x is None:
            return None
        x = jnp.moveaxis(x, 0, -2)
        return x.reshape(x.shape[:-2] + (-1,))[..., :n]

    packed = parts.packed
    if packed is not None:
        packed = dataclasses.replace(
            packed,
            mag=cat(packed.mag),
            sign=cat(packed.sign),
            occupancy=jnp.any(packed.occupancy != 0, axis=0).astype(jnp.int32),
            checksum=None if packed.checksum is None else packed.checksum.sum(0),
        )
    return dataclasses.replace(parts, packed=packed, planes=cat(parts.planes))


@functools.partial(
    jax.jit, static_argnames=("w_bits", "variant", "level", "block", "checksum")
)
def _decompose(w_q, *, w_bits, variant, level, block, checksum):
    def whole(w):
        return bp.make_weight_planes(
            w, w_bits=w_bits, variant=variant, level=level, block=block,
            checksum=checksum,
        )

    def one(w):  # (K, N), N split into COLUMN_CHUNK slices
        k, n = w.shape
        n_chunks = -(-n // COLUMN_CHUNK)
        if n_chunks == 1:
            return whole(w)
        w = jnp.pad(w, ((0, 0), (0, n_chunks * COLUMN_CHUNK - n)))
        chunks = jnp.moveaxis(w.reshape(k, n_chunks, COLUMN_CHUNK), 1, 0)
        return _join_columns(jax.lax.map(whole, chunks), n)

    fn = one
    for _ in range(w_q.ndim - 2):
        fn = functools.partial(jax.lax.map, fn)
    return fn(w_q)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_weight(w, bits: int):
    """Per-output-channel quantization (reduce over the input dim, axis
    -2, so stacked/scanned leading dims are handled) as one program: the
    f32 copy of the weight and its temporaries never exist whole."""
    q = quantize(w.astype(jnp.float32), bits, axis=-2)
    return q.values, q.scale


def quantize_params(
    params,
    policy: PrecisionPolicy,
    *,
    plane_cache: bool = False,
    value_bits: int | None = None,
):
    """Walk the parameter pytree, converting policy-active linears.

    ``plane_cache=True`` also attaches the pre-decomposed weight planes
    (the decompose-once serving cache). Weights are quantized and
    decomposed at the policy's *configured* width — the storage width the
    runtime precision dial truncates from — never at the dialed width, so
    the same tree serves every precision at or below it.

    ``value_bits``: quantize the weight *values* at a narrower width than
    the storage/decomposition width (``value_bits < w_bits``) — the
    narrow-checkpoint deployment: a layer quantized at, say, 4 bits served
    from the engine's uniform 8-bit plane cache. The narrow integers
    sign-extend in the wide container, so their high Booth planes are
    identically zero and ``policy.sparsity="compact"`` recovers the
    narrow-width execution cost automatically from the occupancy bitmaps
    (DESIGN.md §8). ``None`` quantizes at the configured width.

    With ``policy.sparsity == "compact"`` each cached decomposition is
    compacted at load time (entirely-zero planes dropped, shifts
    renumbered) — a host-side transform, so call this eagerly (engine
    construction), never under ``jit``."""

    def rec(node, path):
        if _is_linear(node):
            prec = policy.lookup(path)
            if prec.active:
                if value_bits is not None and not 1 <= value_bits <= prec.w_bits:
                    raise ValueError(
                        f"layer {path}: value_bits must be in [1, {prec.w_bits}] "
                        f"(the configured storage width), got {value_bits}"
                    )
                w_q, w_scale = _quantize_weight(node["w"], value_bits or prec.w_bits)
                out = {"w_q": w_q, "w_scale": w_scale}
                if plane_cache and plan_cacheable(policy, prec):
                    out["w_planes"] = decompose_linear_weight(
                        w_q,
                        w_bits=prec.w_bits,
                        variant=policy.variant,
                        level=policy.level,
                        # ABFT column checksums ride in the cache so every
                        # plan built from it is row-sum checkable
                        checksum=policy.integrity != "off",
                    )
                    if policy.sparsity == "compact" and policy.level == "bitplane":
                        out["w_planes"] = bp.compact_weight_planes(out["w_planes"])
                return out
            return node
        if isinstance(node, dict):
            return {k: rec(v, f"{path}/{k}") for k, v in node.items()}
        if isinstance(node, list):
            return [rec(v, f"{path}/{i}") for i, v in enumerate(node)]
        if isinstance(node, tuple):
            return tuple(rec(v, f"{path}/{i}") for i, v in enumerate(node))
        return node

    return rec(params, "")
