"""Tensor-parallel serving of the packed bit-plane path (DESIGN.md §11).

The single-device :class:`ContinuousBatchingEngine` is the parity oracle:
every TP configuration must produce token-bit-identical output on the
same mixed-length, staggered-arrival greedy workload. These tests run on
8 *virtual* CPU devices — the CI leg sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the setdefault
below makes a bare ``pytest tests/test_sharding_serving.py`` work too,
provided jax was not already initialized by an earlier import).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_reduced
from repro.core import plan as plan_mod
from repro.core.precision import PrecisionPolicy
from repro.layers.linear import linear_apply
from repro.models.cache import init_cache, insert_slot, select_slots
from repro.models.quant import quantize_params
from repro.models.transformer import init_params
from repro.runtime.scheduler import Request
from repro.sharding.tp import (
    TPContext, plane_cache_device_bytes, shard_quantized,
)

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI: XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

ARCH = "granite-3-8b"
# n_kv_heads=4 so the head-parallel KV cache divides at model=4 (the stock
# reduced config has 2 KV heads); the SAME modified config is used at every
# model_parallel including the model=1 oracle, so the comparison is apples
# to apples.
LENS = [5, 9, 13, 7, 11]
GEN = 6
N_SLOTS = 2  # < len(LENS): forces evict + readmit through the slot cache


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_reduced(ARCH), n_kv_heads=4)
    policy = PrecisionPolicy.uniform(8, 8, level="bitplane", variant="booth")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, policy, params


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [
        Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (s,)),
                max_new_tokens=GEN, temperature=0.0, arrival_step=i * 2)
        for i, s in enumerate(LENS)
    ]


def _run(cfg, params, policy, model_parallel, **kw):
    from repro.launch.serve import ContinuousBatchingEngine

    engine = ContinuousBatchingEngine(
        cfg, params, policy, n_slots=N_SLOTS, max_len=max(LENS) + GEN,
        model_parallel=model_parallel, **kw,
    )
    results, stats = engine.run(_requests(cfg))
    toks = {rid: np.asarray(t).tolist() for rid, t in results.items()}
    return toks, engine, stats


@needs_devices
def test_tp_token_parity(setup):
    """Sharded continuous-batching decode is token-bit-identical to the
    single-device engine at model=2 and model=4, on a mixed-length
    staggered workload that overflows the slot count (evict/readmit)."""
    cfg, policy, params = setup
    base, _, _ = _run(cfg, params, policy, 1)
    assert sorted(base) == list(range(len(LENS)))  # nothing failed
    for mp in (2, 4):
        toks, _, _ = _run(cfg, params, policy, mp)
        assert toks == base, f"model_parallel={mp} diverged from the oracle"


@needs_devices
def test_tp_parity_under_integrity_detect(setup):
    """integrity="detect" survives sharding: per-shard checksummed plane
    caches, alarms OR-reduced across shards, tokens still bit-identical."""
    cfg, _, params = setup
    policy = PrecisionPolicy.uniform(
        8, 8, level="bitplane", variant="booth", integrity="detect"
    )
    base, _, stats1 = _run(cfg, params, policy, 1)
    toks, _, stats2 = _run(cfg, params, policy, 2)
    assert toks == base
    assert stats2["integrity"]["abft_alarms"] == 0
    assert stats2["integrity"]["abft_checks"] == stats1["integrity"]["abft_checks"]


@needs_devices
def test_per_shard_plan_interning(setup):
    """TP plans intern under PlanKey.shard = (axis, size, role) with the
    LOCAL shapes, never aliasing single-device plans; row-parallel plans
    carry has_epilogue=False (the epilogue defers past the psum)."""
    cfg, policy, params = setup
    _run(cfg, params, policy, 2)
    keys = [p.key for p in plan_mod.DEFAULT_REGISTRY.plans()]
    sharded = [k for k in keys if k.shard is not None]
    assert sharded, "no sharded plans interned"
    # the module-shared registry may also hold model=4 keys from the
    # parity test — every sharded key must still be well-formed
    assert all(k.shard[0] == "model" and k.shard[1] in (2, 4) for k in sharded)
    roles = {k.shard[2] for k in sharded}
    assert {"col", "row", "vocab"} <= roles
    for k in sharded:
        if k.shard[2] == "row":
            # local K, deferred epilogue
            assert not k.has_epilogue
            assert k.k in (cfg.d_model // k.shard[1], cfg.d_ff // k.shard[1])
        elif k.shard[2] == "vocab":
            assert k.n < cfg.vocab_size  # local vocab slice
    # a sharded key never equals any unsharded key (registry-level aliasing
    # would silently reuse global tile resolution for local shapes)
    unsharded = {k for k in keys if k.shard is None}
    assert not unsharded & set(sharded)


@needs_devices
def test_row_parallel_epilogue(setup):
    """Row-parallel linear under shard_map — raw int32 partial sums,
    exact psum, ONE post-psum epilogue (bias added once, activation after
    dequant) — matches the single-device epilogue bitwise."""
    _, policy, _ = setup
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    d_in, d_out = 64, 48
    params = {"mlp": {"down_proj": {
        "w": (jax.random.normal(k1, (d_in, d_out), jnp.float32) * 0.1
              ).astype(jnp.bfloat16)
    }}}
    x = jax.random.normal(k2, (2, 3, d_in), jnp.bfloat16)
    bias = jax.random.normal(k3, (d_out,), jnp.float32) * 0.05

    qp = quantize_params(params, policy, plane_cache=True)
    ref = linear_apply(
        qp["mlp"]["down_proj"], x, name="mlp/down_proj", policy=policy,
        bias=bias, activation="silu",
    )

    tp = TPContext.create(2)
    tree, specs = shard_quantized(params, policy, tp, plane_cache=True)

    def body(pp, xx, bb):
        local = tp.localize(pp, specs)
        # each shard consumes its K-slice of the (replicated) activation
        i = jax.lax.axis_index(tp.axis)
        xs = jax.lax.dynamic_slice_in_dim(
            xx, i * (d_in // tp.size), d_in // tp.size, axis=-1
        )
        with tp.scope():
            return linear_apply(
                local["mlp"]["down_proj"], xs, name="mlp/down_proj",
                policy=policy, bias=bb, activation="silu",
            )

    out = jax.shard_map(
        body, mesh=tp.mesh, in_specs=(specs, P(), P()), out_specs=P(),
        check_vma=False,
    )(tree, x, bias)
    np.testing.assert_array_equal(
        np.asarray(ref, np.float32), np.asarray(out, np.float32)
    )


@needs_devices
def test_sharded_kv_round_trip(setup):
    """insert_slot / select_slots on the head-sharded slot cache are
    bitwise identical to the single-device cache ops (append on admit,
    evict + readmit on slot reuse are exactly these two)."""
    cfg, _, _ = setup
    tp = TPContext.create(2)

    def fill(tree, seed):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        rng = np.random.default_rng(seed)
        out = []
        for leaf in leaves:
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                out.append(jnp.asarray(
                    rng.standard_normal(leaf.shape), jnp.float32
                ).astype(leaf.dtype))
            else:
                info = jnp.iinfo(leaf.dtype)
                out.append(jnp.asarray(rng.integers(
                    info.min, info.max, leaf.shape), leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    slot_cache = fill(init_cache(cfg, N_SLOTS, 16, cfg.dtype, kv_quant=True), 1)
    seq_cache = fill(init_cache(cfg, 1, 16, cfg.dtype, kv_quant=True), 2)
    specs = tp.cache_specs(slot_cache)
    put = lambda tree, sp: jax.device_put(
        tree, jax.tree_util.tree_map(
            lambda s: NamedSharding(tp.mesh, s), sp)
    )
    slot_s = put(slot_cache, specs)
    seq_s = put(seq_cache, tp.cache_specs(seq_cache))

    ref_ins = jax.jit(insert_slot)(slot_cache, seq_cache, jnp.int32(1))
    got_ins = jax.jit(insert_slot)(slot_s, seq_s, jnp.int32(1))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        ),
        ref_ins, got_ins,
    )

    take = jnp.asarray([True, False])
    ref_sel = jax.jit(select_slots)(slot_cache, ref_ins, take)
    got_sel = jax.jit(select_slots)(slot_s, got_ins, take)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        ),
        ref_sel, got_sel,
    )


@needs_devices
def test_plane_cache_bytes_shrink(setup):
    """Per-device plane-cache bytes shrink ~1/model_parallel (pack-word
    padding and replicated non-TP leaves give the slack)."""
    cfg, policy, params = setup
    base = plane_cache_device_bytes(quantize_params(
        params, policy, plane_cache=True))
    for mp in (2, 4):
        tp = TPContext.create(mp)
        tree, specs = shard_quantized(params, policy, tp, plane_cache=True)
        per_dev = plane_cache_device_bytes(tree, specs, n_shards=mp)
        assert per_dev <= base / mp * 1.25, (mp, per_dev, base)
        assert per_dev >= base / mp * 0.75, (mp, per_dev, base)


@needs_devices
def test_tp_validation(setup):
    from repro.launch.serve import ContinuousBatchingEngine

    cfg, policy, params = setup
    with pytest.raises(ValueError, match="active quantization"):
        ContinuousBatchingEngine(
            cfg, params, PrecisionPolicy.off(), model_parallel=2
        )
    # the STOCK reduced config has n_kv_heads=2: indivisible at model=4
    stock = get_reduced(ARCH)
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatchingEngine(stock, params, policy, model_parallel=4)
