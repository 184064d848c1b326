"""Fully-fused bit-serial linear kernel vs the staged reference.

Per the PR-2 acceptance criteria: the fused path must be bit-exact
(pre-epilogue) against the staged ``plane_matmul`` reference in interpret
mode for all supported (variant, a_bits, w_bits) configs, and the fused
epilogue must match the XLA epilogue over the staged accumulator.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplanes as bp
from repro.core.precision import PrecisionPolicy
from repro.kernels import ops, ref
from repro.layers.linear import linear_apply, linear_init
from repro.models.quant import quantize_params


def _resident_bytes() -> int:
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError):
        return 0


@pytest.fixture(autouse=True)
def _drop_compiled():
    """Interpret-mode kernels compile to large CPU executables, and JAX's
    caches keep every one: a process running this file grew past 4 GB and
    crashed in XLA's CPU compiler. Past 1.5 GB resident the caches are
    dropped (every test would double the file's time)."""
    yield
    if _resident_bytes() > 3 << 29:
        jax.clear_caches()


def _operands(rng, m, k, n, a_bits, w_bits):
    alo, ahi = bp.signed_range(a_bits)
    wlo, whi = bp.signed_range(w_bits)
    a = jnp.asarray(rng.integers(alo, ahi + 1, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(wlo, whi + 1, (k, n)), jnp.int32)
    return a, w


def _staged_acc(a, w, a_bits, w_bits, variant):
    da = bp.to_bitplanes(a, a_bits, variant)
    dw = bp.to_bitplanes(w, w_bits, variant)
    pw = jnp.asarray([x * y for x in da.weights for y in dw.weights], jnp.int32)
    return ref.plane_matmul_ref(da.planes, dw.planes, pw)


# -- pre-epilogue bit-exactness ----------------------------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("a_bits,w_bits", [(4, 4), (8, 8), (8, 4)])
@pytest.mark.parametrize("m,k,n", [(8, 32, 8), (5, 70, 9), (1, 33, 16)])
def test_fused_preepilogue_bitexact(variant, a_bits, w_bits, m, k, n, rng):
    """Fused kernel (in-kernel activation bit-slicing + packed-weight
    unpacking, interpret mode) == staged plane_matmul reference, exactly —
    including ragged M/K/N and the M=1 decode shape."""
    a, w = _operands(rng, m, k, n, a_bits, w_bits)
    dw = bp.to_bitplanes(w, w_bits, variant)
    packed_w = bp.pack_decomposition(dw, axis=-2, variant=variant, block=32)
    got = ops.fused_linear(
        a, packed_w, None, a_bits=a_bits, variant=variant,
        backend="interpret", bm=8, bn=8,
    )
    want = _staged_acc(a, w, a_bits, w_bits, variant)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(want, a.astype(jnp.int32) @ w)
    # jnp parity oracle of the fused dispatch agrees too
    got_jnp = ops.fused_linear(a, packed_w, None, a_bits=a_bits, variant=variant,
                               backend="jnp")
    np.testing.assert_array_equal(got_jnp, want)


def test_fused_multi_k_blocks(rng):
    """K spanning several pack blocks exercises the VMEM-scratch grid
    accumulation and the blocked word layout's natural-K-order guarantee."""
    a, w = _operands(rng, 8, 200, 8, 4, 4)
    dw = bp.to_bitplanes(w, 4, "booth")
    packed_w = bp.pack_decomposition(dw, axis=-2, variant="booth", block=64)
    got = ops.fused_linear(a, packed_w, None, a_bits=4, variant="booth",
                           backend="interpret", bm=8, bn=8)
    np.testing.assert_array_equal(got, a.astype(jnp.int32) @ w)


# -- fused epilogue -----------------------------------------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("activation", ["none", "gelu", "silu"])
@pytest.mark.parametrize("with_bias", [False, True])
def test_fused_epilogue_matches_staged(variant, activation, with_bias, rng):
    """In-kernel dequant/bias/activation == staged accumulator + the XLA
    epilogue (same op order and dtypes)."""
    m, k, n = 5, 70, 9
    a, w = _operands(rng, m, k, n, 4, 4)
    a_scale = jnp.asarray(rng.uniform(0.01, 0.1, (m, 1)), jnp.float32)
    w_scale = jnp.asarray(rng.uniform(0.01, 0.1, (1, n)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(n), jnp.float32) if with_bias else None
    ep = ops.Epilogue(a_scale, w_scale, bias, activation, jnp.float32)
    kw = dict(a_bits=4, w_bits=4, variant=variant, level="bitplane",
              epilogue=ep, bm=8, bn=8, bk=32)
    got = ops.bitserial_matmul(a, w, backend="interpret", fused=True, **kw)
    want = ops.apply_epilogue(_staged_acc(a, w, 4, 4, variant), ep)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # staged dispatch (fused=False) with the same epilogue agrees
    staged = ops.bitserial_matmul(a, w, backend="interpret", fused=False, **kw)
    np.testing.assert_allclose(staged, want, rtol=1e-6, atol=1e-6)


def test_fused_bf16_output(rng):
    a, w = _operands(rng, 4, 32, 8, 4, 4)
    ep = ops.Epilogue(
        jnp.full((4, 1), 0.05, jnp.float32), jnp.full((1, 8), 0.02, jnp.float32)
    )
    got = ops.bitserial_matmul(
        a, w, a_bits=4, w_bits=4, variant="booth", level="bitplane",
        backend="interpret", fused=True, epilogue=ep, bm=8, bn=8, bk=32,
    )
    assert got.dtype == jnp.bfloat16


def test_fused_true_rejected_for_unsupported_configs(rng):
    """Explicit fused=True must not silently fall back."""
    a = jnp.zeros((4, 32), jnp.int8)
    w = jnp.zeros((32, 4), jnp.int8)
    ep = ops.Epilogue(jnp.ones((4, 1)), jnp.ones((1, 4)))
    with pytest.raises(ValueError, match="fused=True"):  # no epilogue
        ops.bitserial_matmul(a, w, a_bits=4, w_bits=4, variant="booth",
                             level="bitplane", backend="jnp", fused=True)
    with pytest.raises(ValueError, match="fused=True"):  # digit level
        ops.bitserial_matmul(a, w, a_bits=8, w_bits=8, variant="booth",
                             level="digit", backend="jnp", fused=True, epilogue=ep)
    with pytest.raises(ValueError, match="fused=True"):  # >8-bit operands
        ops.bitserial_matmul(a, w, a_bits=12, w_bits=12, variant="booth",
                             level="bitplane", backend="jnp", fused=True,
                             epilogue=ep, accum_dtype=jnp.float32)


# -- layer-level dispatch -----------------------------------------------------


@pytest.fixture
def lin_setup(rng):
    params = linear_init(jax.random.PRNGKey(0), 64, 16, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    return params, x


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
def test_linear_apply_fused_serving_cache(lin_setup, variant):
    """Serving path: the blocked plane cache feeds the fused kernel; jnp,
    staged-interpret and fused-interpret agree."""
    params, x = lin_setup
    pol = PrecisionPolicy.uniform(8, 8, variant=variant, level="bitplane")
    q = quantize_params({"l": params}, pol, plane_cache=True)["l"]
    assert q["w_planes"].packed.block is not None  # fused cache layout
    y_jnp = linear_apply(q, x, name="l", policy=pol, backend="jnp")
    y_fused = linear_apply(q, x, name="l", policy=pol, backend="interpret")
    pol_staged = PrecisionPolicy.uniform(
        8, 8, variant=variant, level="bitplane", fuse_epilogue=False
    )
    y_staged = linear_apply(q, x, name="l", policy=pol_staged, backend="interpret")
    np.testing.assert_allclose(y_fused, y_jnp, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y_fused, y_staged, rtol=1e-5, atol=1e-6)


def test_linear_apply_fused_bias_activation(lin_setup, rng):
    """bias/activation ride the epilogue on every path and agree across
    backends."""
    params, x = lin_setup
    bias = jnp.asarray(rng.standard_normal(16), jnp.float32)
    pol = PrecisionPolicy.uniform(8, 8, level="bitplane")
    q = quantize_params({"l": params}, pol, plane_cache=True)["l"]
    kw = dict(name="l", policy=pol, bias=bias, activation="silu")
    y_jnp = linear_apply(q, x, backend="jnp", **kw)
    y_fused = linear_apply(q, x, backend="interpret", **kw)
    np.testing.assert_allclose(y_fused, y_jnp, rtol=1e-5, atol=1e-6)
    # dense reference: same epilogue applied to the float matmul
    dense = jax.nn.silu(x @ params["w"] + bias)
    rel = float(jnp.linalg.norm(y_jnp - dense) / (jnp.linalg.norm(dense) + 1e-9))
    assert rel < 0.1


def test_linear_apply_onthefly_fused(lin_setup):
    """On-the-fly quantized inference (dense weights, no cache) packs the
    weight planes per call and still fuses."""
    params, x = lin_setup
    pol = PrecisionPolicy.uniform(4, 4, variant="booth", level="bitplane")
    y_i = linear_apply(params, x, name="l", policy=pol, backend="interpret")
    y_j = linear_apply(params, x, name="l", policy=pol, backend="jnp")
    np.testing.assert_allclose(y_i, y_j, rtol=1e-5, atol=1e-6)


def test_int8_operands_end_to_end(rng):
    """Satellite: int8/int16 operands give bit-identical accumulators to
    int32 operands — the int32 operand round trip is gone."""
    a8 = jnp.asarray(rng.integers(-8, 8, (5, 40)), jnp.int8)
    w8 = jnp.asarray(rng.integers(-8, 8, (40, 7)), jnp.int8)
    for level in ("bitplane", "digit"):
        got8 = ops.bitserial_matmul(a8, w8, a_bits=4, w_bits=4, variant="booth",
                                    level=level, backend="jnp")
        got32 = ops.bitserial_matmul(a8.astype(jnp.int32), w8.astype(jnp.int32),
                                     a_bits=4, w_bits=4, variant="booth",
                                     level=level, backend="jnp")
        np.testing.assert_array_equal(got8, got32)
    got16 = ops.bitserial_matmul(a8.astype(jnp.int16), w8.astype(jnp.int16),
                                 a_bits=4, w_bits=4, variant="booth",
                                 level="bitplane", backend="jnp")
    np.testing.assert_array_equal(got16, a8.astype(jnp.int32) @ w8.astype(jnp.int32))


# -- blocked pack layout ------------------------------------------------------


@pytest.mark.parametrize("variant", ["sbmwc", "booth"])
@pytest.mark.parametrize("k", [1, 31, 64, 95, 200])
def test_blocked_pack_roundtrip(variant, k, rng):
    lo, hi = bp.signed_range(4)
    x = jnp.asarray(rng.integers(lo, hi + 1, (3, k)), jnp.int32)
    dec = bp.to_bitplanes(x, 4, variant)
    packed = bp.pack_decomposition(dec, axis=-1, variant=variant, block=64)
    np.testing.assert_array_equal(bp.unpack_planes(packed), dec.planes)
    w = jnp.asarray(rng.integers(lo, hi + 1, (k, 5)), jnp.int32)
    dw = bp.to_bitplanes(w, 4, variant)
    pw = bp.pack_decomposition(dw, axis=-2, variant=variant, block=64)
    np.testing.assert_array_equal(bp.unpack_planes(pw), dw.planes)


def test_blocked_pack_small_k_clamps_block():
    """A K far below the block must not pad up to a full oversized block —
    but the clamp keeps the block a 128-lane multiple (the fused kernel
    uses it as its K tile)."""
    dec = bp.to_bitplanes(jnp.zeros((4, 40), jnp.int32), 4, "sbmwc")
    packed = bp.pack_decomposition(dec, axis=-1, variant="sbmwc", block=512)
    assert packed.block == 128  # 40 rounded up to one lane-width block
    assert packed.mag.shape[-1] == 4
    # an explicitly sub-lane block (tests, tiny tiles) is left alone
    small = bp.pack_decomposition(dec, axis=-1, variant="sbmwc", block=32)
    assert small.block == 32


def test_fused_epilogue_per_tensor_scales(rng):
    """Broadcast (per-tensor) scales must dequantize every row/column —
    not just the first (regression: padding with 1.0 after a reshape)."""
    a, w = _operands(rng, 5, 40, 9, 4, 4)
    ep = ops.Epilogue(
        a_scale=jnp.full((1, 1), 0.03, jnp.float32),
        w_scale=jnp.full((1, 1), 0.07, jnp.float32),
        out_dtype=jnp.float32,
    )
    kw = dict(a_bits=4, w_bits=4, variant="booth", level="bitplane",
              epilogue=ep, bm=8, bn=8, bk=32)
    got = ops.bitserial_matmul(a, w, backend="interpret", fused=True, **kw)
    want = ops.apply_epilogue(_staged_acc(a, w, 4, 4, "booth"), ep)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_global_layout_cache_keeps_staged_path(rng, monkeypatch):
    """Auto fused dispatch must not silently discard a global-planar-layout
    cache and re-pack the static weight per call — it keeps the staged
    decompose-once path (explicit fused=True accepts the repack)."""
    a = jnp.asarray(rng.integers(-8, 8, (4, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (64, 8)), jnp.int32)
    wp = bp.make_weight_planes(w, w_bits=4, variant="booth", level="bitplane",
                               block=None, store="packed")  # global layout
    assert wp.packed.block is None
    packs = {"n": 0}
    real = bp.pack_decomposition

    def counting(*args, **kw):
        packs["n"] += 1
        return real(*args, **kw)

    monkeypatch.setattr(bp, "pack_decomposition", counting)
    ep = ops.Epilogue(jnp.full((4, 1), 0.05, jnp.float32),
                      jnp.full((1, 8), 0.02, jnp.float32), out_dtype=jnp.float32)
    kw = dict(a_bits=4, w_bits=4, variant="booth", level="bitplane",
              backend="interpret", w_planes=wp, epilogue=ep, bm=8, bn=8, bk=32)
    got_auto = ops.bitserial_matmul(a, w, fused=None, **kw)
    assert packs["n"] == 0  # staged cached path: no per-call weight repack
    got_forced = ops.bitserial_matmul(a, w, fused=True, **kw)
    assert packs["n"] == 1  # explicit fused=True accepts the repack
    want = ops.apply_epilogue(_staged_acc(a, w, 4, 4, "booth"), ep)
    np.testing.assert_allclose(got_auto, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_forced, want, rtol=1e-6, atol=1e-6)


def test_mixed_pack_layouts_rejected():
    a = bp.pack_planes(jnp.zeros((2, 8, 64), jnp.int8), axis=-1, block=32)
    w = bp.pack_planes(jnp.zeros((2, 64, 8), jnp.int8), axis=-2)
    with pytest.raises(ValueError, match="layout"):
        ops.plane_matmul_packed(a, w, jnp.zeros((4,), jnp.int32), backend="jnp")


def test_staged_packed_kernel_accepts_blocked_layout(rng):
    """The staged packed kernel contracts blocked-layout operands exactly
    (any shared word layout contracts matching K subsets per word slice)."""
    a = jnp.asarray(rng.integers(-8, 8, (8, 200)), jnp.int32)
    w = jnp.asarray(rng.integers(-8, 8, (200, 8)), jnp.int32)
    da = bp.to_bitplanes(a, 4, "booth")
    dw = bp.to_bitplanes(w, 4, "booth")
    pw = jnp.asarray([x * y for x in da.weights for y in dw.weights], jnp.int32)
    pa = bp.pack_decomposition(da, axis=-1, variant="booth", block=64)
    pk = bp.pack_decomposition(dw, axis=-2, variant="booth", block=64)
    got = ops.plane_matmul_packed(pa, pk, pw, backend="interpret", bm=8, bn=8, bk=64)
    np.testing.assert_array_equal(got, a @ w)


# -- decode-shape tile heuristic ----------------------------------------------


def test_auto_tiles_decode_shapes():
    assert ops.auto_tiles(1, 512, None, None) == (8, 512)
    assert ops.auto_tiles(8, 4096, None, None) == (8, 512)
    assert ops.auto_tiles(9, 64, None, None) == (16, 128)
    assert ops.auto_tiles(2048, 100, None, None) == (128, 128)
    # explicit tiles are never overridden
    assert ops.auto_tiles(4, 64, 128, 512) == (128, 512)


def test_default_tiles_handle_decode_shape(rng):
    """M=2 decode step through the wrappers with *default* (auto) tiles —
    previously padded to bm=128."""
    a = jnp.asarray(rng.integers(-8, 8, (2, 96)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (96, 8)), jnp.int32)
    got = ops.bitserial_matmul(a, w, a_bits=4, w_bits=4, variant="booth",
                               level="bitplane", backend="interpret")
    np.testing.assert_array_equal(got, a.astype(jnp.int32) @ w)


# -- the single int8 pass against the oracle and the plane-pair loop ----------


def _three_ways(a, packed, a_bits, variant, bm=8, bn=8, epilogue=None):
    """The single-pass kernel, the gated plane-pair loop (interpret) and
    the jnp oracle over the same packed operand."""
    kw = dict(a_bits=a_bits, variant=variant)
    single = ops.fused_linear(a, packed, epilogue, backend="interpret",
                              bm=bm, bn=bn, **kw)
    gated = ops.fused_linear(a, packed, epilogue, backend="interpret",
                             bm=bm, bn=bn, gate=True, **kw)
    oracle = ops.fused_linear(a, packed, epilogue, backend="jnp", **kw)
    return single, gated, oracle


def _pack(w, bits, variant, block):
    return bp.pack_decomposition(bp.to_bitplanes(w, bits, variant), axis=-2,
                                 variant=variant, block=block)


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
@pytest.mark.parametrize("a_bits,w_bits", [(8, 8), (6, 6), (4, 4), (8, 4), (8, 6)])
@pytest.mark.parametrize("m,k,n,block", [(1, 200, 9, 64), (33, 70, 16, 32)])
def test_single_pass_matches_oracle_and_pair_loop(variant, a_bits, w_bits, m, k, n,
                                                  block, rng):
    """One int8 MXU pass a K block gives the plane-pair sum bit for bit:
    ragged M/K/N, several K blocks, every tier pair the plans route here."""
    a, w = _operands(rng, m, k, n, a_bits, w_bits)
    single, gated, oracle = _three_ways(a, _pack(w, w_bits, variant, block),
                                        a_bits, variant)
    np.testing.assert_array_equal(single, oracle)
    np.testing.assert_array_equal(single, gated)
    np.testing.assert_array_equal(single, a.astype(jnp.int32) @ w)


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
@pytest.mark.parametrize("to_bits", [6, 4])
def test_single_pass_truncated_prefix(variant, to_bits, rng):
    """8->6 and 8->4 prefixes of an 8-bit store (truncate_packed): the
    rebuilt weights are the requantized integers, Booth's round half up to
    +2^(b-1) included (planted: 127 rounds up to it at both tiers)."""
    a, w = _operands(rng, 6, 150, 12, 8, 8)
    w = w.at[0, :].set(127).at[7, 3].set(-128)
    packed = bp.truncate_packed(_pack(w, 8, variant, 64), to_bits, variant)
    wq = bp.shift_requantize(w, 8, to_bits, variant)
    if variant == "booth":
        assert int(wq.max()) == 1 << (to_bits - 1)  # one past the signed max
    single, gated, oracle = _three_ways(a, packed, 8, variant)
    np.testing.assert_array_equal(single, oracle)
    np.testing.assert_array_equal(single, gated)
    np.testing.assert_array_equal(single, a.astype(jnp.int32) @ wq)


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
@pytest.mark.parametrize("to_bits", [8, 6, 4])
def test_single_pass_compacted_cache(variant, to_bits, rng):
    """A compacted cache (all-zero planes dropped, kept planes keep their
    weights) and its truncated prefixes: the slots follow the weights."""
    a, _ = _operands(rng, 5, 130, 9, 8, 8)
    w = jnp.asarray(rng.integers(-8, 8, (130, 9)), jnp.int32) * 4  # planes 0, 1 empty
    packed = bp.compact_packed(_pack(w, 8, variant, 64))
    assert packed.n_planes < 8
    packed = bp.truncate_packed(packed, to_bits, variant, from_bits=8)
    single, gated, oracle = _three_ways(a, packed, 8, variant)
    np.testing.assert_array_equal(single, oracle)
    np.testing.assert_array_equal(single, gated)
    np.testing.assert_array_equal(
        single, a.astype(jnp.int32) @ bp.shift_requantize(w, 8, to_bits, variant))


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
def test_single_pass_extreme_weights(variant, rng):
    """Weights at -128 (SBMwC: the MSB plane alone, its negative weight
    sign-extended over the top slots) and at 127, next to small ones."""
    a, w = _operands(rng, 8, 96, 16, 8, 8)
    w = w.at[::3, :].set(-128).at[1::7, ::2].set(127).at[2, :].set(-1)
    single, gated, oracle = _three_ways(a, _pack(w, 8, variant, 32), 8, variant)
    np.testing.assert_array_equal(single, oracle)
    np.testing.assert_array_equal(single, gated)
    np.testing.assert_array_equal(single, a.astype(jnp.int32) @ w)


@pytest.mark.parametrize("m,k,n", [(5, 96, 9), (8, 256, 16)])
def test_single_pass_booth_plus_128(m, k, n, rng):
    """Booth's closed range reaches +2^7 at 8 bits (a 16-bit store's 8-bit
    prefix rounds up to it): the int8 rebuild reads -128 there, and the
    correcting pass adds 256 * a only in the K blocks that hold one."""
    a, w = _operands(rng, m, k, n, 8, 8)
    w = w.at[3, 2].set(128).at[k - 1, n - 1].set(128).at[40, :].set(128)
    single, gated, oracle = _three_ways(a, _pack(w, 8, "booth", 32), 8, "booth")
    np.testing.assert_array_equal(single, oracle)
    np.testing.assert_array_equal(single, gated)
    np.testing.assert_array_equal(single, a.astype(jnp.int32) @ w)


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
@pytest.mark.parametrize("a_bits", [4, 6])
def test_single_pass_activations_wrap(variant, a_bits, rng):
    """Activations outside the a_bits range wrap as the sliced planes do:
    the sign extension of their low a_bits bits, as in the plane-pair
    loop. (The jnp oracle's Booth decomposition reads exactly +2^(a-1) as
    itself, by its closed range; the in-range cases compare it.)"""
    a = jnp.asarray(rng.integers(-128, 128, (7, 80)), jnp.int8)
    _, w = _operands(rng, 7, 80, 10, 8, 8)
    packed = _pack(w, 8, variant, 32)
    single = ops.fused_linear(a, packed, None, a_bits=a_bits, variant=variant,
                              backend="interpret", bm=8, bn=8)
    gated = ops.fused_linear(a, packed, None, a_bits=a_bits, variant=variant,
                             backend="interpret", bm=8, bn=8, gate=True)
    s = 32 - a_bits
    wrapped = (a.astype(jnp.int32) << s) >> s
    np.testing.assert_array_equal(single, gated)
    np.testing.assert_array_equal(single, wrapped @ w)


@pytest.mark.parametrize("variant", ["booth", "sbmwc"])
@pytest.mark.parametrize("raw", [True, False], ids=["raw_int32", "epilogue"])
def test_single_pass_output_modes(variant, raw, rng):
    """The raw int32 mode (the TP row role's, has_epilogue=False) and the
    in-kernel epilogue both sit on the one pass."""
    a, w = _operands(rng, 5, 100, 12, 8, 8)
    ep = None if raw else ops.Epilogue(
        jnp.asarray(rng.uniform(0.01, 0.1, (5, 1)), jnp.float32),
        jnp.asarray(rng.uniform(0.01, 0.1, (1, 12)), jnp.float32),
        out_dtype=jnp.float32,
    )
    single, gated, oracle = _three_ways(a, _pack(w, 8, variant, 32), 8, variant,
                                        epilogue=ep)
    if raw:
        assert single.dtype == jnp.int32
        np.testing.assert_array_equal(single, oracle)
        np.testing.assert_array_equal(single, gated)
    else:
        want = ops.apply_epilogue(a.astype(jnp.int32) @ w, ep)
        np.testing.assert_allclose(single, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(single, gated, rtol=1e-6, atol=1e-6)


def test_single_pass_refuses_operands_without_int8_form(rng):
    """Operands with no int8 form (unsigned 8-bit activations, a plane
    weighted 2^8) have no single pass; the gated loop still takes them."""
    from repro.kernels.plane_mm_fused import single_pass_fits, weight_slots

    assert weight_slots(bp.plane_weights(8, "booth"), True) == tuple(range(8))
    assert weight_slots(bp.plane_weights(4, "sbmwc"), False) == (0, 1, 2, 3, 3, 3, 3, 3)
    assert weight_slots(bp.plane_weights(8, "unsigned"), False) is None
    assert weight_slots(bp.plane_weights(9, "booth"), True) is None
    assert not single_pass_fits(bp.plane_weights(8, "sbmwc"), False, 8, "unsigned")
    a = jnp.asarray(rng.integers(0, 200, (4, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (64, 8)), jnp.int32)
    packed = _pack(w, 4, "sbmwc", 32)
    with pytest.raises(ValueError, match="single-pass"):
        ops.fused_linear(a, packed, None, a_bits=8, variant="unsigned",
                         backend="interpret", bm=8, bn=8)
    got = ops.fused_linear(a, packed, None, a_bits=8, variant="unsigned",
                           backend="interpret", bm=8, bn=8, gate=True)
    np.testing.assert_array_equal(got, (a.astype(jnp.int32) & 255) @ w)


def test_single_pass_booth_plus_128_through_plan(rng):
    """A plan reaches Booth's +2^7: a 16-bit store served at the 8-bit tier
    (prefix truncation rounds 32767 up to 128) takes the fused route, and
    its output equals the jnp route's."""
    from repro.core import plan as plan_mod

    a = jnp.asarray(rng.integers(-128, 128, (4, 96)), jnp.int8)
    w = jnp.asarray(rng.integers(-32768, 32768, (96, 8)), jnp.int32).at[5, :].set(32767)
    wp = bp.make_weight_planes(w, w_bits=16, variant="booth", level="bitplane", block=32)
    assert int(bp.shift_requantize(w, 16, 8, "booth").max()) == 128
    ep = ops.Epilogue(jnp.full((4, 1), 0.5, jnp.float32), jnp.full((1, 8), 0.25, jnp.float32),
                      out_dtype=jnp.float32)
    kw = dict(a_bits=8, w_bits=8, w_in_bits=16, variant="booth", level="bitplane",
              has_epilogue=True, w_planes=wp, registry=plan_mod.PlanRegistry())
    fused = plan_mod.plan_for_operands((4, 96, 8), backend="interpret", bm=8, bn=8, **kw)
    assert fused.kernel == "fused_cached"
    oracle = plan_mod.plan_for_operands((4, 96, 8), backend="jnp", **kw)
    np.testing.assert_array_equal(fused(a, w_planes=wp, epilogue=ep),
                                  oracle(a, w_planes=wp, epilogue=ep))
