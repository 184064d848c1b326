"""Occupancy-gated sparse plane execution (ISSUE 5).

The load-bearing invariant: gated and compacted execution are
bit-identical to dense execution for both MAC variants (sbmwc + Booth) on
the jnp and interpret backends, including across ``with_precision``
prefix truncation — occupancy bitmaps and plane sets must truncate
consistently with the MSB-prefix plane slice (DESIGN.md §8). Zero planes
contribute zero to the plane-pair sum, so skipping them can never change
a result; these tests pin that end to end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplanes as bp
from repro.core import plan as plan_mod
from repro.core.precision import PrecisionPolicy
from repro.kernels import ops, ref
from repro.layers.linear import linear_apply, linear_init
from repro.models.quant import quantize_params


def _narrow_weights(rng, k, n, bits=4):
    """Integer weights using only ``bits`` of an 8-bit container — the
    narrow-checkpoint case whose high Booth planes are identically zero."""
    lo, hi = bp.signed_range(bits)
    return jnp.asarray(rng.integers(lo, hi + 1, (k, n)), jnp.int32)


# -- occupancy metadata -------------------------------------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("block", [None, 64])
def test_pack_occupancy_matches_reference(variant, block, rng):
    """pack_planes' per-(plane, word) bitmap == the word-level non-zero
    reduction of the packed mag words; for the blocked layout the
    per-K-tile reduction also matches the plane values block by block
    (blocked word chunks cover natural-order K blocks)."""
    w = jnp.asarray(rng.integers(-128, 128, (70, 9)), jnp.int32)
    dec = bp.to_bitplanes(w, 8, variant)
    packed = bp.pack_decomposition(dec, axis=-2, variant=variant, block=block)
    occ = np.asarray(packed.occupancy)
    want = (np.asarray(packed.mag) != 0).any(axis=-1).astype(np.int32)
    np.testing.assert_array_equal(occ, want)
    if block is not None:
        bkw = block // bp.WORD_BITS
        per_tile = np.asarray(bp.occupancy_per_tile(packed.occupancy, bkw))
        planes = np.asarray(dec.planes)  # (P, K, N), natural K order
        nk = per_tile.shape[1]
        for p in range(planes.shape[0]):
            for t in range(nk):
                blk = planes[p, t * block:(t + 1) * block]
                assert bool(per_tile[p, t]) == bool((blk != 0).any())


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
def test_truncate_preserves_occupancy(variant, rng):
    """Pack → truncate round trip: the truncated decomposition's bitmap is
    the MSB-prefix slice of the stored bitmap AND equals the bitmap a
    fresh reduction of the truncated words would compute — occupancy can
    never claim a skipped plane the sliced words still populate."""
    w = jnp.asarray(rng.integers(-128, 128, (70, 9)), jnp.int32)
    wp8 = bp.make_weight_planes(w, w_bits=8, variant=variant, level="bitplane",
                                store="both", block=64)
    wp4 = bp.truncate_weight_planes(wp8, 4)
    occ4 = np.asarray(wp4.packed.occupancy)
    np.testing.assert_array_equal(occ4, np.asarray(wp8.packed.occupancy)[4:])
    fresh = (np.asarray(wp4.packed.mag) != 0).any(axis=-1).astype(np.int32)
    np.testing.assert_array_equal(occ4, fresh)


def test_booth_zero_fraction_exceeds_sbmwc(rng):
    """The paper's motivation, measured: Booth recoding of gaussian int8
    weights zeroes measurably more plane values than sbmwc (runs of ones
    — sign extensions of small negatives — collapse to two non-zero
    digits), and on narrow-checkpoint values whole high planes go zero
    for Booth while sbmwc keeps them occupied."""
    from repro.core.quantize import quantize

    w = quantize(jnp.asarray(rng.standard_normal((128, 64)), jnp.float32),
                 8, axis=0).values.astype(jnp.int32)
    fracs = {}
    for variant in ("sbmwc", "booth"):
        planes = bp.to_bitplanes(w, 8, variant).planes
        fracs[variant] = float(jnp.mean((planes == 0).astype(jnp.float32)))
    # measured ~0.55 vs ~0.49 on absmax-quantized gaussians (the per-
    # channel scale keeps values large; narrower data widens the gap)
    assert fracs["booth"] > fracs["sbmwc"] + 0.03, fracs
    # plane-level: narrow (4-bit) values sign-extend, so Booth's top 4
    # planes are identically zero and compaction drops them; sbmwc's top
    # planes carry the sign-extension ones and all survive
    v = _narrow_weights(rng, 70, 9)
    booth = bp.compact_weight_planes(
        bp.make_weight_planes(v, w_bits=8, variant="booth", level="bitplane",
                              store="both", block=64))
    sbmwc = bp.compact_weight_planes(
        bp.make_weight_planes(v, w_bits=8, variant="sbmwc", level="bitplane",
                              store="both", block=64))
    assert len(booth.weights) == 4 and booth.weights == (1, 2, 4, 8)
    assert len(sbmwc.weights) == 8


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
def test_compaction_reconstructs_exactly(variant, rng):
    """Compaction drops only zero planes: the kept (plane, weight) pairs
    reconstruct the identical integers, raw planes and packed words agree
    on the kept set, and truncating a compacted cache still lands on
    shift_requantize (the truncation-consistency invariant)."""
    v = _narrow_weights(rng, 70, 9)
    wp = bp.make_weight_planes(v, w_bits=8, variant=variant, level="bitplane",
                               store="both", block=64)
    c = bp.compact_weight_planes(wp)

    def recon(planes, weights):
        wts = jnp.asarray(weights, jnp.int32).reshape(-1, 1, 1)
        return jnp.sum(planes.astype(jnp.int32) * wts, axis=0)

    np.testing.assert_array_equal(recon(c.planes, c.weights), v)
    np.testing.assert_array_equal(c.planes, bp.unpack_planes(c.packed))
    assert c.w_bits == 8  # compaction removes work, not precision
    t = bp.truncate_weight_planes(c, 5)
    np.testing.assert_array_equal(
        recon(bp.unpack_planes(t.packed), t.weights),
        bp.shift_requantize(v, 8, 5, variant),
    )


def test_compaction_requires_weights_and_occupancy(rng):
    planes = bp.to_bitplanes(jnp.zeros((8, 8), jnp.int32), 4, "sbmwc").planes
    naked = bp.pack_planes(planes, axis=-2)  # no weights carried
    with pytest.raises(ValueError, match="per-plane weights"):
        bp.compact_packed(naked)
    import dataclasses
    no_occ = dataclasses.replace(
        bp.pack_decomposition(bp.to_bitplanes(jnp.zeros((8, 8), jnp.int32), 4,
                                              "sbmwc"), axis=-2),
        occupancy=None,
    )
    with pytest.raises(ValueError, match="occupancy"):
        bp.compact_packed(no_occ)


# -- gated kernels: bit-exact parity -----------------------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("a_bits,w_bits", [(8, 8), (8, 4)])
def test_gated_packed_kernel_parity(variant, a_bits, w_bits, rng):
    """plane_matmul_packed(gate=True) == the dense reference, exactly —
    ragged M/K/N, weight occupancy from pack time AND'd with dynamic
    activation occupancy in-kernel."""
    alo, ahi = bp.signed_range(a_bits)
    a = jnp.asarray(rng.integers(alo, ahi + 1, (5, 70)), jnp.int32)
    w = _narrow_weights(rng, 70, 9, bits=w_bits)
    da = bp.to_bitplanes(a, a_bits, variant)
    dw = bp.to_bitplanes(w, w_bits, variant)
    pw = jnp.asarray([x * y for x in da.weights for y in dw.weights], jnp.int32)
    pa = bp.pack_decomposition(da, axis=-1, variant=variant)
    pk = bp.pack_decomposition(dw, axis=-2, variant=variant)
    want = ref.plane_matmul_ref(da.planes, dw.planes, pw)
    got = ops.plane_matmul_packed(pa, pk, pw, backend="interpret",
                                  bm=8, bn=16, bk=64, gate=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("compact", [False, True])
def test_gated_fused_kernel_parity(variant, compact, rng):
    """fused_plane_linear(gate=True) — in-kernel activation occupancy over
    the live int8 slices — matches the dense accumulator exactly, with and
    without pack-time weight compaction."""
    a = jnp.asarray(rng.integers(-128, 128, (5, 70)), jnp.int8)
    w = _narrow_weights(rng, 70, 9)
    da = bp.to_bitplanes(a, 8, variant)
    dw = bp.to_bitplanes(w, 8, variant)
    want = ref.plane_matmul_ref(
        da.planes, dw.planes,
        jnp.asarray([x * y for x in da.weights for y in dw.weights], jnp.int32),
    )
    packed = bp.pack_decomposition(dw, axis=-2, variant=variant, block=64)
    if compact:
        packed = bp.compact_packed(packed)
        if variant == "booth":
            assert packed.n_planes == 4  # the grid itself shrank
    got = ops.fused_linear(a, packed, None, a_bits=8, variant=variant,
                           backend="interpret", bm=8, bn=16, gate=True)
    np.testing.assert_array_equal(got, want)


def test_gate_requires_occupancy(rng):
    dec = bp.to_bitplanes(_narrow_weights(rng, 64, 8), 4, "sbmwc")
    import dataclasses
    packed = dataclasses.replace(
        bp.pack_decomposition(dec, axis=-2, variant="sbmwc", block=64),
        occupancy=None,
    )
    with pytest.raises(ValueError, match="occupancy"):
        ops.fused_linear(jnp.zeros((4, 64), jnp.int8), packed, None,
                         a_bits=4, variant="sbmwc", backend="interpret",
                         bm=8, bn=8, gate=True)


# -- plan dimension -----------------------------------------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
@pytest.mark.parametrize("sparsity", ["gate", "compact"])
def test_plan_sparsity_parity_with_truncation(variant, backend, sparsity, rng):
    """The acceptance criterion: sparse plans (gate + compact caches) are
    bit-identical to dense plans on both backends for both MAC variants,
    INCLUDING after with_precision truncation — the occupancy bitmap and
    kept-plane set truncate consistently with the plane-prefix slice."""
    a8 = jnp.asarray(rng.integers(-128, 128, (5, 70)), jnp.int8)
    w = _narrow_weights(rng, 70, 9, bits=5)
    wp = bp.make_weight_planes(w, w_bits=8, variant=variant, level="bitplane",
                               store="both", block=64)
    wp_s = bp.compact_weight_planes(wp) if sparsity == "compact" else wp
    # packed=True: interpret resolves the gateable cached_packed route
    # (jnp keeps its scan oracle, where gating is a no-op by design)
    kw = dict(a_bits=8, w_bits=8, variant=variant, level="bitplane",
              backend=backend, packed=True, bm=8, bn=8, bk=64)
    dense = plan_mod.plan_for_operands((5, 70, 9), w_planes=wp, **kw)
    sparse = plan_mod.plan_for_operands((5, 70, 9), w_planes=wp_s,
                                        sparsity=sparsity, **kw)
    assert sparse.key.sparsity == sparsity
    assert sparse.gate == (backend != "jnp")
    assert sparse.kernel == ("cached_scan" if backend == "jnp" else "cached_packed")
    np.testing.assert_array_equal(
        sparse(a8, w, w_planes=wp_s), dense(a8, w, w_planes=wp)
    )
    # truncated siblings agree too (6 keeps a mix of planes under compact)
    for bits in (6, 4):
        got = sparse.with_precision(bits, bits)(a8, w, w_planes=wp_s)
        want = dense.with_precision(bits, bits)(a8, w, w_planes=wp)
        np.testing.assert_array_equal(got, want)


def test_sparsity_keys_and_validation(rng):
    with pytest.raises(ValueError, match="sparsity"):
        plan_mod.plan_for_operands((4, 64, 8), a_bits=8, w_bits=8,
                                   backend="jnp", sparsity="bogus")
    with pytest.raises(ValueError, match="sparsity"):
        PrecisionPolicy.uniform(8, 8, sparsity="dense")
    reg = plan_mod.PlanRegistry()
    kw = dict(a_bits=8, w_bits=8, variant="booth", level="bitplane",
              backend="jnp", registry=reg)
    p_off = plan_mod.plan_for_operands((4, 64, 8), **kw)
    p_gate = plan_mod.plan_for_operands((4, 64, 8), sparsity="gate", **kw)
    assert p_off is not p_gate  # sparsity is part of the plan key
    assert "sparsity=gate" in p_gate.describe()


def test_sparsity_stats_totals_match_reference(rng):
    """sparsity_stats() accounting equals a direct count over the raw
    planes: dense passes = P_a * P_w * K-tiles, executed = P_a * occupied
    (plane, K-tile) cells, skipped = the difference; compaction shows up
    in planes_kept and the after-compaction total."""
    v = _narrow_weights(rng, 130, 9)  # 3 K-tiles at block=64
    wp = bp.compact_weight_planes(
        bp.make_weight_planes(v, w_bits=8, variant="booth", level="bitplane",
                              store="both", block=64))
    plan = plan_mod.plan_for_operands(
        (5, 130, 9), a_bits=8, w_bits=8, variant="booth", level="bitplane",
        backend="interpret", w_planes=wp, sparsity="compact", packed=True,
        bm=8, bn=8, bk=64,
    )
    stats = plan.sparsity_stats(wp)
    planes = np.asarray(wp.planes)  # (P_kept, K, N)
    block = wp.packed.block
    nk = -(-planes.shape[1] // block)
    occupied = sum(
        bool((planes[p, t * block:(t + 1) * block] != 0).any())
        for p in range(planes.shape[0]) for t in range(nk)
    )
    assert stats["mode"] == "compact" and stats["gated"]
    assert stats["planes_kept"] == len(wp.weights) == 4
    assert stats["k_tiles"] == nk == 3
    assert stats["pair_passes_dense"] == 8 * 8 * nk
    assert stats["pair_passes_after_compaction"] == 8 * len(wp.weights) * nk
    assert stats["pair_passes_executed"] == 8 * occupied
    assert stats["pair_passes_skipped"] == 8 * 8 * nk - 8 * occupied
    assert 0.0 <= stats["skipped_fraction"] <= 1.0
    # plans without a cache still report their mode/route
    bare = plan_mod.plan_for_operands((4, 64, 8), a_bits=8, w_bits=8,
                                      backend="jnp", sparsity="gate")
    assert bare.sparsity_stats() == {
        "mode": "gate", "kernel": bare.kernel, "gated": False,
        "planes_dense": 8, "a_planes": 8, "mxu_passes_per_tile": 64,
    }


@pytest.mark.parametrize("sparsity", ["off", "gate", "compact"])
@pytest.mark.parametrize("tier", [8, 4])
def test_mxu_passes_per_tile_names_the_path(sparsity, tier, rng):
    """The fused route makes one int8 MXU pass a tile; gated, it runs the
    plane-pair loop, a_bits x (kept, truncated) planes passes a tile."""
    v = _narrow_weights(rng, 130, 9)
    wp = bp.make_weight_planes(v, w_bits=8, variant="booth", level="bitplane",
                               block=64)
    if sparsity == "compact":
        wp = bp.compact_weight_planes(wp)
    plan = plan_mod.plan_for_operands(
        (5, 130, 9), a_bits=8, w_bits=8, variant="booth", level="bitplane",
        backend="interpret", w_planes=wp, sparsity=sparsity, has_epilogue=True,
        registry=plan_mod.PlanRegistry(),
    ).with_precision(w_bits=tier)
    assert plan.kernel == "fused_cached"
    assert plan.gate == (sparsity != "off")
    stats = plan.sparsity_stats(wp)
    kept = bp.truncate_weight_planes(wp, tier).packed.n_planes
    assert stats["mxu_passes_per_tile"] == (1 if sparsity == "off" else 8 * kept)
    # without the cache the count is the dense one
    assert plan.sparsity_stats()["mxu_passes_per_tile"] == (
        1 if sparsity == "off" else 8 * tier)


# -- layer / serving integration ---------------------------------------------


def test_linear_apply_compact_matches_dense(rng):
    """quantize_params(policy.sparsity='compact', value_bits=4) through
    linear_apply equals the dense-cache result bit for bit — the whole
    narrow-checkpoint serving story in one projection."""
    params = linear_init(jax.random.PRNGKey(0), 64, 16, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    outs = {}
    for sparsity in ("off", "compact"):
        pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane",
                                      sparsity=sparsity)
        q = quantize_params({"l": params}, pol, plane_cache=True, value_bits=4)["l"]
        if sparsity == "compact":
            assert len(q["w_planes"].weights) == 4
        outs[sparsity] = linear_apply(q, x, name="l", policy=pol, backend="jnp")
    np.testing.assert_array_equal(outs["off"], outs["compact"])


def test_quantize_params_value_bits_validation():
    params = {"l": linear_init(jax.random.PRNGKey(0), 16, 8, jnp.float32)}
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    with pytest.raises(ValueError, match="value_bits"):
        quantize_params(params, pol, plane_cache=True, value_bits=12)


def test_auto_tiles_bn():
    """The N-derived output tile: lane-width floor, 256 cap, historical
    2-tuple contract untouched without n."""
    assert ops.auto_tiles(4, 700, None, None) == (8, 512)
    assert ops.auto_tiles(4, 700, None, None, n=96) == (8, 128, 512)
    assert ops.auto_tiles(4, 700, None, None, n=200) == (8, 256, 512)
    assert ops.auto_tiles(4, 700, None, None, n=4096) == (8, 256, 512)
    assert ops.auto_tiles(4, 700, None, None, n=4096, bn=512) == (8, 512, 512)


def test_fused_step_from_shape():
    """The single-pass kernel's grid step: the widest lane-multiple bn up to
    1024 that divides N (in lanes), then up to 4 pack blocks a K step, at
    most 2048 weight columns a step, dividing K's blocks."""
    assert [ops.fused_bn(n) for n in (96, 4096, 11008, 32000, 49155, 12800)] == [
        128, 1024, 256, 640, 896, 640]
    assert ops.fused_k_blocks(1024, 8) == 2  # LLaMA q/o: K 4096
    assert ops.fused_k_blocks(256, 8) == 4  # gate/up: N 11008
    assert ops.fused_k_blocks(1024, 22) == 2  # down: K 11008
    assert ops.fused_k_blocks(640, 8) == 2  # lm_head: N 32000
    assert ops.fused_k_blocks(640, 25) == 1
    # a fused plan takes the wide step; gated, the plane-pair loop keeps 256
    kw = dict(a_bits=8, w_bits=8, variant="booth", level="bitplane",
              backend="interpret", has_epilogue=True, registry=plan_mod.PlanRegistry())
    assert plan_mod.plan_for_operands((32, 4096, 4096), **kw).bn == 1024
    assert plan_mod.plan_for_operands((32, 4096, 4096), sparsity="gate", **kw).bn == 256


def test_fused_decode_auto_bn(rng):
    """ops.fused_linear with bn=None derives the tile from N and stays
    bit-exact on the decode shape."""
    a = jnp.asarray(rng.integers(-8, 8, (1, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (64, 200)), jnp.int32)
    dw = bp.to_bitplanes(w, 4, "booth")
    packed = bp.pack_decomposition(dw, axis=-2, variant="booth", block=64)
    got = ops.fused_linear(a, packed, None, a_bits=4, variant="booth",
                           backend="interpret", bm=8)
    np.testing.assert_array_equal(got, a.astype(jnp.int32) @ w)
