"""Compile-only tests for a TPU v5e, with no chip attached.

The TPU compiler is installed with jax, and it compiles for a chip that is
described, not attached (``jax.experimental.topologies``). These tests
lower the fused bit-plane linear kernel — the serving path's matmul — at
granite-3-8b's published widths for one v5e chip and check that Mosaic
accepts it (a ``tpu_custom_call`` in the compiled HLO), and check that
the serving steps' rounding option reaches the TPU compiler. Interpret
mode cannot see what they catch: tiles Mosaic refuses, unknown compiler
params, bf16 rounding the TPU compiler moves.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers each import every test file.
"""

from __future__ import annotations

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import bitplanes as bp
from repro.kernels import ops
from repro.launch.steps import jit_step

#: granite-3-8b: d_model 4096, 8 KV heads of 128, d_ff 12800, vocab 49155
GRANITE_LINEARS = {
    "q_o_proj": (4096, 4096),
    "k_v_proj": (4096, 1024),
    "gate_up_proj": (4096, 12800),
    "down_proj": (12800, 4096),
    "lm_head": (4096, 49155),
}
#: the continuous-batching engine's decode batch and prefill chunk
M_DECODE, M_PREFILL = 8, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _packed_weight(k: int, n: int):
    """Shapes of the serving cache's packed 8-bit Booth planes of a (k, n)
    weight, in the blocked layout the fused kernel reads."""
    wp = jax.eval_shape(
        lambda w: bp.make_weight_planes(w, w_bits=8, variant="booth", level="bitplane"),
        jax.ShapeDtypeStruct((k, n), jnp.int8),
    )
    return wp.packed


@pytest.mark.parametrize("m", [M_DECODE, M_PREFILL], ids=["decode", "prefill"])
@pytest.mark.parametrize("name", sorted(GRANITE_LINEARS))
def test_fused_linear_compiles_for_v5e(one_chip, name, m):
    k, n = GRANITE_LINEARS[name]
    packed = _packed_weight(k, n)

    def linear(x_q, packed_w, a_scale, w_scale):
        ep = ops.Epilogue(a_scale=a_scale, w_scale=w_scale)
        return ops.fused_linear(
            x_q, packed_w, ep, a_bits=8, variant="booth", backend="pallas"
        )

    args = _on(one_chip, (
        jax.ShapeDtypeStruct((m, k), jnp.int8),
        packed,
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
    ))
    text = jax.jit(linear).lower(*args).compile().as_text()
    # the kernel's instruction is named for the trace: a device trace names
    # each op by its instruction, and the benchmark finds the kernel by it
    kernel = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernel) == 1
    assert re.match(r"\s*(ROOT )?%fused_plane_linear(\.\d+)? = ", kernel[0]), kernel[0][:200]
    out = jax.eval_shape(linear, *args)
    assert (out.shape, out.dtype) == ((m, n), jnp.bfloat16)


def test_serving_steps_round_bf16_where_the_source_says(one_chip):
    """The v5e computes bf16 arithmetic in f32. Under XLA's default excess
    precision ``x * y`` stays f32 into the add; a serving step compiled
    by ``jit_step`` rounds it to bf16 first, as the source says — so the
    Pallas and jnp routes cannot round differently by fusing differently."""
    arg = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)

    def chain(x, y):
        return x * y + y

    default = jax.jit(chain).lower(arg, arg).compile().as_text()
    exact = jit_step(chain).lower(arg, arg).compile().as_text()
    assert "reduce-precision" not in default
    assert "reduce-precision" in exact


def test_tiles_legal_refuses_what_v5e_refuses(one_chip):
    """bk=128 splitting K=4096 packs to a (P_w, 4, bn) word block — not a
    whole 8-row sublane tile. The compiler refuses it, and so does
    ``tiles_legal``; bk=256 is accepted by both."""
    k, n, m = 4096, 1024, M_DECODE

    def compile_at(bk):
        packed = jax.eval_shape(
            lambda w: bp.make_weight_planes(
                w, w_bits=8, variant="booth", level="bitplane", block=bk
            ),
            jax.ShapeDtypeStruct((k, n), jnp.int8),
        ).packed
        x = jax.ShapeDtypeStruct((m, k), jnp.int8)

        def linear(x_q, packed_w):
            return ops.fused_linear(
                x_q, packed_w, None, a_bits=8, variant="booth", backend="pallas",
                bm=32, bn=256,
            )

        return jax.jit(linear).lower(*_on(one_chip, (x, packed))).compile()

    assert not ops.tiles_legal(32, 256, 128, k=k)
    with pytest.raises(Exception):
        compile_at(128)
    assert ops.tiles_legal(32, 256, 256, k=k)
    assert "tpu_custom_call" in compile_at(256).as_text()
