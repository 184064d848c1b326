"""Quantizer + precision-policy unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image without hypothesis: seeded fallback
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.precision import LayerPrecision, PrecisionPolicy
from repro.core.quantize import dequantize, fake_quant, quantization_error, quantize


def test_quantize_roundtrip_error_shrinks_with_bits(rng):
    x = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    errs = [float(quantization_error(x, b)) for b in (2, 4, 8, 12, 16)]
    assert all(e1 > e2 for e1, e2 in zip(errs, errs[1:]))
    assert errs[-1] < 1e-4


def test_quantize_per_channel_beats_per_tensor(rng):
    x = jnp.asarray(rng.standard_normal((128, 8)) * jnp.logspace(-2, 2, 8), jnp.float32)
    per_tensor = float(jnp.sqrt(jnp.mean((dequantize(quantize(x, 8)) - x) ** 2)))
    per_chan = float(jnp.sqrt(jnp.mean((dequantize(quantize(x, 8, axis=0)) - x) ** 2)))
    assert per_chan < per_tensor


def test_quantize_respects_range(rng):
    x = jnp.asarray(rng.standard_normal((32, 32)) * 10, jnp.float32)
    for bits in (2, 4, 8):
        q = quantize(x, bits)
        hi = (1 << (bits - 1)) - 1
        assert int(jnp.max(q.values)) <= hi
        assert int(jnp.min(q.values)) >= -hi - 1
        assert q.values.dtype == jnp.int8


def test_quantize_int_storage_dtype():
    x = jnp.ones((4, 4))
    assert quantize(x, 8).values.dtype == jnp.int8
    assert quantize(x, 16).values.dtype == jnp.int32


def test_fake_quant_ste_gradient(rng):
    x = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    g = jax.grad(lambda v: jnp.sum(fake_quant(v, 8)))(x)
    # straight-through: gradient ~1 for in-range values
    np.testing.assert_allclose(g, jnp.ones_like(g), atol=1e-5)


def test_fake_quant_noop_for_none():
    x = jnp.ones((3,))
    np.testing.assert_array_equal(fake_quant(x, None), x)


@given(bits=st.integers(2, 16))
@settings(max_examples=15, deadline=None)
def test_fake_quant_idempotent(bits):
    x = jnp.linspace(-2, 2, 33)
    q1 = fake_quant(x, bits)
    q2 = fake_quant(q1, bits)
    np.testing.assert_allclose(q1, q2, rtol=1e-5, atol=1e-6)


# -- PrecisionPolicy ---------------------------------------------------------


def test_policy_lookup_and_overrides():
    pol = PrecisionPolicy.from_dict(
        {"": (8, 8), "lm_head": (None, None), r"layers/0/": (4, 4)}
    )
    assert pol.lookup("layers/5/attn/q_proj").w_bits == 8
    assert not pol.lookup("lm_head").active
    assert pol.lookup("layers/0/mlp/up_proj").w_bits == 4


def test_policy_uniform_keep_dense():
    pol = PrecisionPolicy.uniform(8, keep_dense=("router",))
    assert pol.lookup("layers/moe/router").active is False
    assert pol.lookup("layers/moe/expert").w_bits == 8


def test_policy_validation():
    with pytest.raises(ValueError):
        LayerPrecision(0, 0)
    with pytest.raises(ValueError):
        LayerPrecision(17, 17)
    with pytest.raises(ValueError):
        LayerPrecision(8, None)


def test_policy_off_and_describe():
    pol = PrecisionPolicy.off()
    assert not pol.lookup("anything").active
    assert "PrecisionPolicy" in pol.describe()


@pytest.mark.parametrize(
    "shape,variant,checksum",
    [((2, 300, 700), "booth", True), ((130, 513), "sbmwc", False)],
)
def test_column_chunked_decomposition_matches_whole(monkeypatch, shape, variant, checksum):
    """Load-time decomposition splits N into COLUMN_CHUNK slices (so the
    plane temporaries of a published-width lm_head fit a chip): the
    reassembled cache — words, occupancy, checksums — equals the cache of
    the whole matrix."""
    from repro.models import quant

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
    kw = dict(w_bits=8, variant=variant, level="bitplane", checksum=checksum)
    whole = quant.decompose_linear_weight(w, **kw)
    monkeypatch.setattr(quant, "COLUMN_CHUNK", 128)
    quant._decompose.clear_cache()
    try:
        chunked = quant.decompose_linear_weight(w, **kw)
    finally:
        quant._decompose.clear_cache()
    got, got_def = jax.tree_util.tree_flatten(chunked)
    want, want_def = jax.tree_util.tree_flatten(whole)
    assert got_def == want_def
    for g, x in zip(got, want):
        assert (g.shape, g.dtype) == (x.shape, x.dtype)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
