"""Plan-based execution API (ISSUE 4): registry interning, prefix-
truncation parity, runtime precision serving, and the deprecation shim.

The load-bearing invariant: ``MatmulPlan.with_precision`` at (4,4) from an
8-bit packed decomposition is bit-identical to a fresh (4,4) decomposition
of the shift-requantized integers, for BOTH MAC variants and on both the
jnp and interpret backends — no re-quantization, only a plane-prefix
slice of the stored words.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplanes as bp
from repro.core import plan as plan_mod
from repro.core.precision import LayerPrecision, PrecisionPolicy
from repro.kernels import ops
from repro.layers.linear import linear_apply, linear_init
from repro.models.quant import quantize_params


# -- registry ----------------------------------------------------------------


def test_plan_registry_cache_hit():
    """Same shapes/policy/backend -> the IDENTICAL plan object (interned),
    different key -> different plan; hit/miss counters observe it."""
    reg = plan_mod.PlanRegistry()
    kw = dict(a_bits=8, w_bits=8, variant="booth", level="bitplane",
              backend="jnp", registry=reg)
    p1 = plan_mod.plan_for_operands((4, 64, 16), **kw)
    p2 = plan_mod.plan_for_operands((4, 64, 16), **kw)
    assert p1 is p2
    assert (reg.hits, reg.misses) == (1, 1)
    p3 = plan_mod.plan_for_operands((8, 64, 16), **kw)  # different M
    assert p3 is not p1
    assert len(reg) == 2


def test_make_plan_policy_lookup_cache_hit():
    """The policy-facing entry interns too: two traces of the same layer
    fetch one plan (a frozen policy hashes into the key)."""
    reg = plan_mod.PlanRegistry()
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    p1 = plan_mod.make_plan(pol, "layers/attn/q_proj", (4, 64, 16), "jnp",
                            registry=reg)
    p2 = plan_mod.make_plan(pol, "layers/attn/q_proj", (4, 64, 16), "jnp",
                            registry=reg)
    assert p1 is p2
    # the runtime dial is part of the key: dialing produces a sibling plan
    p4 = plan_mod.make_plan(pol.with_runtime_bits(4, 4), "layers/attn/q_proj",
                            (4, 64, 16), "jnp", registry=reg)
    assert p4 is not p1
    assert (p4.key.a_bits, p4.key.w_bits, p4.key.w_in_bits) == (4, 4, 8)


def test_plan_resolution_routes(rng):
    """Resolution picks the documented route per (backend, cache, flags)."""
    w = jnp.asarray(rng.integers(-128, 128, (64, 16)), jnp.int32)
    wp = bp.make_weight_planes(w, w_bits=8, variant="booth", level="bitplane",
                               store="packed", block=64)
    common = dict(a_bits=8, w_bits=8, variant="booth", level="bitplane")
    assert plan_mod.plan_for_operands((4, 64, 16), backend="jnp",
                                      **common).kernel == "oracle"
    assert plan_mod.plan_for_operands((4, 64, 16), backend="interpret",
                                      **common).kernel == "staged"
    assert plan_mod.plan_for_operands((4, 64, 16), backend="jnp", w_planes=wp,
                                      **common).kernel == "cached_scan"
    assert plan_mod.plan_for_operands(
        (4, 64, 16), backend="interpret", w_planes=wp, has_epilogue=True,
        **common
    ).kernel == "fused_cached"
    assert plan_mod.plan_for_operands(
        (4, 64, 16), backend="interpret", w_planes=wp, packed=True, **common
    ).kernel == "cached_packed"


# -- prefix truncation parity (acceptance criterion) -------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
def test_truncate_weight_planes_values(variant, rng):
    """The top-4 plane prefix of an 8-bit decomposition reconstructs
    exactly shift_requantize(w, 8, 4) — floor for sbmwc, round-half-up
    for Booth (the dropped-digit carry) — including the int8 boundary."""
    w = jnp.asarray(rng.integers(-128, 128, (33, 9)), jnp.int32)
    w = w.at[0, 0].set(127).at[1, 0].set(-128)
    wp8 = bp.make_weight_planes(w, w_bits=8, variant=variant, level="bitplane",
                                store="both", block=64)
    wp4 = bp.truncate_weight_planes(wp8, 4)
    assert wp4.w_bits == 4 and wp4.weights == bp.plane_weights(4, variant)
    got = jnp.sum(
        jnp.asarray(wp4.weights, jnp.int32)[:, None, None]
        * bp.unpack_planes(wp4.packed).astype(jnp.int32),
        axis=0,
    )
    want = bp.shift_requantize(w, 8, 4, variant)
    np.testing.assert_array_equal(got, want)
    # the sliced raw planes agree with the sliced packed words
    np.testing.assert_array_equal(wp4.planes, bp.unpack_planes(wp4.packed))
    if variant == "sbmwc":
        # sbmwc truncation is PLANE-identical to a fresh decomposition
        fresh = bp.to_bitplanes(want, 4, "sbmwc")
        np.testing.assert_array_equal(bp.unpack_planes(wp4.packed), fresh.planes)
    else:
        # booth rounds half up onto the closed range [-8, 8]; the fresh
        # recode of the requantized value reconstructs it exactly
        assert int(jnp.max(want)) <= 8 and int(jnp.min(want)) >= -8
        np.testing.assert_array_equal(
            bp.to_bitplanes(want, 4, "booth").reconstruct(), want
        )


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_with_precision_matches_fresh_decomposition(variant, backend, rng):
    """plan8.with_precision(4,4) over the 8-bit packed cache is
    bit-identical to a fresh 4-bit decomposition of the shift-requantized
    operands — per the ISSUE 4 acceptance criterion, both MAC variants."""
    a8 = jnp.asarray(rng.integers(-128, 128, (5, 70)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (70, 9)), jnp.int32)
    w = w.at[0, 0].set(127).at[1, 0].set(-128)  # exercise the boundary
    wp8 = bp.make_weight_planes(w, w_bits=8, variant=variant, level="bitplane",
                                store="both", block=64)
    p8 = plan_mod.plan_for_operands(
        (5, 70, 9), a_bits=8, w_bits=8, variant=variant, level="bitplane",
        backend=backend, w_planes=wp8, bm=8, bn=8, bk=64,
    )
    p4 = p8.with_precision(4, 4)
    assert p4.w_shift == 4 and p4.trunc_cache and not p4.requant_w
    got = p4(a8, w, w_planes=wp8)

    # fresh 4-bit reference: decompose the requantized integers from scratch
    a4 = bp.shift_requantize(a8, 8, 4, variant)
    if variant == "booth":
        a4 = jnp.minimum(a4, 7)  # activation shift saturates (int8-native)
    w4 = bp.shift_requantize(w, 8, 4, variant)
    wp4_fresh = bp.WeightPlanes(
        packed=bp.pack_decomposition(
            bp.to_bitplanes(w4, 4, variant), axis=-2, variant=variant, block=64
        ),
        planes=bp.to_bitplanes(w4, 4, variant).planes,
        weights=bp.plane_weights(4, variant),
        level="bitplane", variant=variant, w_bits=4,
    )
    p4_fresh = plan_mod.plan_for_operands(
        (5, 70, 9), a_bits=4, w_bits=4, variant=variant, level="bitplane",
        backend=backend, w_planes=wp4_fresh, bm=8, bn=8, bk=64,
    )
    want = p4_fresh(a4.astype(jnp.int8), w4, w_planes=wp4_fresh)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        want, a4.astype(jnp.int32) @ w4.astype(jnp.int32)
    )


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
def test_with_precision_fused_epilogue_scale(variant, rng):
    """The fused-cached route truncates too, and the 2^(a_shift+w_shift)
    dequant correction folds into the epilogue exactly."""
    w = jnp.asarray(rng.integers(-128, 128, (70, 16)), jnp.int32)
    a8 = jnp.asarray(rng.integers(-128, 128, (5, 70)), jnp.int8)
    a_scale = jnp.asarray(rng.uniform(0.01, 0.1, (5, 1)), jnp.float32)
    w_scale = jnp.asarray(rng.uniform(0.01, 0.1, (1, 16)), jnp.float32)
    ep = ops.Epilogue(a_scale, w_scale, None, "none", jnp.float32)
    wp8 = bp.make_weight_planes(w, w_bits=8, variant=variant, level="bitplane",
                                store="packed", block=64)
    p8 = plan_mod.plan_for_operands(
        (5, 70, 16), a_bits=8, w_bits=8, variant=variant, level="bitplane",
        backend="interpret", w_planes=wp8, has_epilogue=True, bm=8, bn=8, bk=64,
    )
    p4 = p8.with_precision(4, 4)
    assert p8.kernel == p4.kernel == "fused_cached"
    got = p4(a8, w, w_planes=wp8, epilogue=ep)
    a4 = bp.shift_requantize(a8, 8, 4, variant)
    if variant == "booth":
        a4 = jnp.minimum(a4, 7)
    w4 = bp.shift_requantize(w, 8, 4, variant)
    acc = a4.astype(jnp.int32) @ w4
    want = ops.apply_epilogue(acc, ep._replace(w_scale=w_scale * 256.0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_with_precision_validates_ceiling():
    p = plan_mod.plan_for_operands((4, 64, 8), a_bits=8, w_bits=8,
                                   variant="booth", level="bitplane",
                                   backend="jnp")
    with pytest.raises(ValueError, match="stored decomposition width"):
        p.with_precision(8, 12)
    with pytest.raises(ValueError, match="provided operand width"):
        p.with_precision(12, 8)
    assert p.with_precision(8, 8) is p
    assert p.with_precision(4, 4).with_precision(8, 8).key == p.key


# -- runtime dial through the layer stack ------------------------------------


def test_linear_apply_runtime_dial_matches_requantized(rng):
    """policy.with_runtime_bits(4,4) over an 8-bit quantized layer equals
    computing with the shift-requantized weights and the 2^4-adjusted
    scale explicitly — on the cached and cache-less paths."""
    params = linear_init(jax.random.PRNGKey(0), 64, 16, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    q = quantize_params({"l": params}, pol, plane_cache=True)["l"]
    y = linear_apply(q, x, name="l", policy=pol.with_runtime_bits(4, 4),
                     backend="jnp")
    # explicit reference: requantized ints at the adjusted scale
    from repro.core.quantize import quantize
    xq = quantize(x, 4, axis=-1)
    w4 = bp.shift_requantize(q["w_q"], 8, 4, "booth")
    acc = xq.values.astype(jnp.int32) @ w4
    want = (acc.astype(jnp.float32) * xq.scale * (q["w_scale"] * 16.0)).astype(x.dtype)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # cache-less path (no w_planes) agrees bit-for-bit with the cached one
    q2 = {k: v for k, v in q.items() if k != "w_planes"}
    y2 = linear_apply(q2, x, name="l", policy=pol.with_runtime_bits(4, 4),
                      backend="jnp")
    np.testing.assert_allclose(y2, want, rtol=1e-5, atol=1e-6)


def test_effective_bits():
    pol = PrecisionPolicy.uniform(8, 6)
    prec = pol.lookup("x")
    assert (prec.w_bits, prec.a_bits) == (8, 6)
    eff = pol.with_runtime_bits(4, 4).effective(prec)
    assert (eff.w_bits, eff.a_bits) == (4, 4)
    # the dial never raises precision
    eff = pol.with_runtime_bits(16, 16).effective(prec)
    assert (eff.w_bits, eff.a_bits) == (8, 6)
    # inactive layers stay dense
    assert not pol.with_runtime_bits(4, 4).effective(LayerPrecision()).active


# -- mid-serving precision switch --------------------------------------------


@pytest.mark.parametrize("arch", ["granite-3-8b"])
def test_set_precision_continuity(arch):
    """In-flight slots finish correctly across a mid-serving precision
    switch: same completion set/lengths, greedy tokens before the switch
    identical to the unswitched run."""
    from repro.configs import get_reduced
    from repro.launch.serve import ContinuousBatchingEngine
    from repro.models.transformer import init_params
    from repro.runtime.scheduler import Request

    cfg = get_reduced(arch)
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def requests():
        r = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=r.integers(0, cfg.vocab_size, (s,)),
                    max_new_tokens=8, arrival_step=i * 2)
            for i, s in enumerate([4, 8, 12])
        ]

    eng = ContinuousBatchingEngine(cfg, params, pol, n_slots=2, max_len=24)
    res_ref, _ = eng.run(requests())
    res_sw, stats = eng.run(requests(), precision_schedule={4: 4})
    assert stats["precision_switches"] == [(4, (4, 4))]
    assert set(res_sw) == set(res_ref)
    for rid in res_ref:
        assert res_sw[rid].shape == res_ref[rid].shape
    # request 0 decodes from step 0: its first 4 greedy tokens predate the
    # switch and must be identical
    np.testing.assert_array_equal(
        np.asarray(res_ref[0])[:4], np.asarray(res_sw[0])[:4]
    )
    # engine restored to a fresh run must reproduce the reference exactly
    eng.set_precision(None)
    res_back, _ = eng.run(requests())
    for rid in res_ref:
        np.testing.assert_array_equal(res_back[rid], res_ref[rid])


def test_set_precision_validation():
    from repro.configs import get_reduced
    from repro.launch.serve import Engine
    from repro.models.transformer import init_params

    cfg = get_reduced("granite-3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    pol_digit = PrecisionPolicy.uniform(8, 8, variant="booth", level="digit")
    eng = Engine(cfg, params, pol_digit, max_len=16)
    with pytest.raises(ValueError, match="bitplane"):
        eng.set_precision(4)
    pol_bp = PrecisionPolicy.uniform(4, 4, variant="booth", level="bitplane")
    eng = Engine(cfg, params, pol_bp, max_len=16)
    with pytest.raises(ValueError, match="stored width"):
        eng.set_precision(8)  # dial cannot exceed the decomposition width
    with pytest.raises(ValueError, match=">= 1 bit"):
        eng.set_precision(0)  # and never below one plane


# -- deprecation shim ---------------------------------------------------------


def test_bitserial_matmul_legacy_kwargs_warn_once(rng):
    """packed=/fused=/epilogue= each emit exactly one DeprecationWarning
    per process and still route through the plan path correctly."""
    a = jnp.asarray(rng.integers(-8, 8, (4, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (64, 8)), jnp.int32)
    ep = ops.Epilogue(jnp.ones((4, 1), jnp.float32), jnp.ones((1, 8), jnp.float32),
                      out_dtype=jnp.float32)
    kw = dict(a_bits=4, w_bits=4, variant="booth", level="bitplane", backend="jnp")
    plan_mod._reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        y1 = ops.bitserial_matmul(a, w, packed=False, fused=False, epilogue=ep, **kw)
        y2 = ops.bitserial_matmul(a, w, packed=False, fused=False, epilogue=ep, **kw)
    deps = [r for r in rec if issubclass(r.category, DeprecationWarning)]
    assert len(deps) == 3  # one per kwarg, not per call
    msgs = " | ".join(str(d.message) for d in deps)
    for kw_name in ("packed", "fused", "epilogue"):
        assert msgs.count(f"bitserial_matmul({kw_name}") == 1
    np.testing.assert_allclose(y1, y2)
    want = ops.apply_epilogue(a.astype(jnp.int32) @ w, ep)
    np.testing.assert_allclose(y1, want, rtol=1e-6, atol=1e-6)
    # unflagged calls stay silent
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ops.bitserial_matmul(a, w, a_bits=4, w_bits=4, backend="jnp")
    assert not [r for r in rec if issubclass(r.category, DeprecationWarning)]


def test_bitserial_matmul_rejects_unknown_tile_kwargs(rng):
    """Typo'd tile keywords must fail loudly (the old **tile_kw forwarding
    raised TypeError in the kernel wrappers; the shim keeps that)."""
    a = jnp.asarray(rng.integers(-8, 8, (4, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (64, 8)), jnp.int32)
    with pytest.raises(TypeError, match="bkk"):
        ops.bitserial_matmul(a, w, a_bits=4, w_bits=4, backend="jnp", bkk=256)


def test_with_precision_stays_in_owning_registry():
    """Dialed siblings intern in the registry the plan was built in — a
    private registry never leaks plans into DEFAULT_REGISTRY."""
    reg = plan_mod.PlanRegistry()
    # a shape no other test uses, so the DEFAULT_REGISTRY check is
    # order-independent
    p = plan_mod.plan_for_operands((3, 96, 7), a_bits=8, w_bits=8,
                                   variant="booth", level="bitplane",
                                   backend="jnp", registry=reg)
    p4 = p.with_precision(4, 4)
    assert len(reg) == 2
    assert p4.key not in plan_mod.DEFAULT_REGISTRY
    assert reg.get(p4.key) is p4


def test_set_precision_asymmetric_dial():
    """Only the weight dial is capped by the stored decomposition;
    an over-wide activation dial is clamped by policy.effective()."""
    from repro.configs import get_reduced
    from repro.launch.serve import Engine
    from repro.models.transformer import init_params

    cfg = get_reduced("granite-3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    eng = Engine(cfg, params, pol, max_len=16)
    eng.set_precision((8, 4))  # weights truncated, activations at width
    assert eng.precision == (8, 4)
    with pytest.raises(ValueError, match="weight precision"):
        eng.set_precision((4, 12))  # weight dial above storage: rejected


def test_plan_epilogue_contract():
    p = plan_mod.plan_for_operands((4, 64, 8), a_bits=4, w_bits=4,
                                   variant="booth", level="bitplane",
                                   backend="jnp", has_epilogue=True)
    with pytest.raises(ValueError, match="epilogue"):
        p(jnp.zeros((4, 64), jnp.int8), jnp.zeros((64, 8), jnp.int8))


# -- booth closed-range extension ---------------------------------------------


def test_booth_closed_range_decomposition():
    """to_bitplanes('booth') handles the closed interval including
    +2^(b-1) (the round-half-up truncation boundary) exactly."""
    for bits in (2, 4, 8):
        top = 1 << (bits - 1)
        x = jnp.asarray([-top, -1, 0, 1, top - 1, top], jnp.int32)
        dec = bp.to_bitplanes(x, bits, "booth")
        np.testing.assert_array_equal(dec.reconstruct(), x)
        assert int(jnp.max(jnp.abs(dec.planes))) <= 1


def test_policy_backend_routes_its_plans():
    """``PrecisionPolicy.backend`` picks where a policy's matmuls run —
    how a chip run serves its jnp reference next to the Pallas route —
    and plans key on it; an explicit ``make_plan`` backend still wins."""
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    shapes = ((8, 128), (128, 128))
    auto = plan_mod.make_plan(pol, "layers/mlp/up", shapes)
    assert auto.key.backend == ops.resolve_backend("auto")
    ref = dataclasses.replace(pol, backend="jnp")
    plan = plan_mod.make_plan(ref, "layers/mlp/up", shapes)
    assert (plan.key.backend, plan.kernel) == ("jnp", "oracle")
    explicit = plan_mod.make_plan(ref, "layers/mlp/up", shapes, "interpret")
    assert explicit.key.backend == "interpret"
    with pytest.raises(ValueError, match="backend must be"):
        dataclasses.replace(pol, backend="tpu")
