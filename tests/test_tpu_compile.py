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
from jax._src.lib import xla_client
from jax.sharding import SingleDeviceSharding

from repro.core import bitplanes as bp
from repro.kernels import ops
from repro.kernels.plane_mm_fused import fused_plane_linear
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
#: the benchmark's LLaMA-7B (d_model 4096, SwiGLU 11008, vocab 32000) at
#: its decode batch of 32 slots and its 64-token chunk
LLAMA_LINEARS = {
    "llama_q_o_proj": (4096, 4096),
    "llama_gate_up_proj": (4096, 11008),
    "llama_down_proj": (11008, 4096),
    "llama_lm_head": (4096, 32000),
}
FUSED_CASES = [
    (name, m, f"{name}-{phase}")
    for name in sorted(GRANITE_LINEARS)
    for m, phase in ((M_DECODE, "decode"), (M_PREFILL, "prefill"))
] + [(name, m, f"{name}-m{m}") for name in sorted(LLAMA_LINEARS) for m in (32, 64)]


def _roofline_signature():
    """The benchmark's reader of the kernel's trace name and operands
    (``bench/metrics/plane_mm_roofline.py``): a 1-D int vector, then
    ``s8[M,K]`` activations, then ``s32[P,K/32,N]`` magnitude words."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parents[1] / "bench" / "metrics" / "plane_mm_roofline.py"
    spec = importlib.util.spec_from_file_location("plane_mm_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


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


@pytest.mark.parametrize(
    "name,m", [c[:2] for c in FUSED_CASES], ids=[c[2] for c in FUSED_CASES]
)
def test_fused_linear_compiles_for_v5e(one_chip, name, m):
    k, n = {**GRANITE_LINEARS, **LLAMA_LINEARS}[name]
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
    compiled = jax.jit(linear).lower(*args).compile()
    text = compiled.as_text()
    # the kernel's instruction is named for the trace: a device trace names
    # each op by its instruction, and the benchmark finds the kernel by it
    kernel = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernel) == 1
    assert re.match(r"\s*(ROOT )?%fused_plane_linear(\.\d+)? = ", kernel[0]), kernel[0][:200]
    # ... with the operands the roofline reader parses, in its order, in
    # the instruction as a trace prints it (operand shapes inline)
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_percent = opts.print_operand_shape = True
    traced = [
        ln.strip().removeprefix("ROOT ")
        for ln in compiled.runtime_executable().hlo_modules()[0].to_string(opts).splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    ]
    sig = _roofline_signature().match(traced[0])
    assert sig is not None, traced[0][:300]
    rows, cols, x_rows, x_k, planes = map(int, sig.groups())
    assert (x_rows, x_k) == (rows, -(-k // 512) * 512) and rows >= m
    assert cols >= n and planes == packed.n_planes == 8
    out = jax.eval_shape(linear, *args)
    assert (out.shape, out.dtype) == ((m, n), jnp.bfloat16)


def test_fused_kernel_reads_its_words_from_hbm(one_chip):
    """In a scan over stacked layers, the kernel's packed words stay in HBM
    (no ``S(1)``, VMEM): XLA would otherwise stage each layer's words in
    VMEM in the op that slices them out of the stack, and the kernel's
    time would leave its weight reads out."""
    layers, k, n, m = 2, 4096, 11008, 32
    one = _packed_weight(k, n)
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((layers,) + s.shape, s.dtype), one
    )

    def step(x, packed, a_scale, w_scale):
        def layer(h, p):
            y = ops.fused_linear(h, p, ops.Epilogue(a_scale, w_scale), a_bits=8,
                                 variant="booth", backend="pallas")
            return y[:, :k].astype(jnp.int8), None

        return jax.lax.scan(layer, x, packed)[0]

    args = _on(one_chip, (
        jax.ShapeDtypeStruct((m, k), jnp.int8), stacked,
        jax.ShapeDtypeStruct((m, 1), jnp.float32), jax.ShapeDtypeStruct((n,), jnp.float32),
    ))
    compiled = jax.jit(step).lower(*args).compile()
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_percent = opts.print_operand_shape = True
    kernel = [
        ln for ln in compiled.runtime_executable().hlo_modules()[0].to_string(opts).splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    ]
    assert len(kernel) == 1
    words = re.findall(r"s32\[8,128,11008\]\{([^}]*)\}", kernel[0].split("custom_call_target")[0])
    assert len(words) == 2 and not any("S(1)" in w for w in words), kernel[0][:400]


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
    whole 8-row sublane tile. The compiler refuses a K step of one such
    block, and so does ``tiles_legal``; bk=256 is accepted by both. (The
    dispatch steps over several pack blocks at once, which makes 128 legal:
    ``tiles_legal`` of the step it takes.)"""
    k, n, m = 4096, 1024, M_DECODE

    def compile_at(bk, k_blocks=1):
        packed = jax.eval_shape(
            lambda w: bp.make_weight_planes(
                w, w_bits=8, variant="booth", level="bitplane", block=bk
            ),
            jax.ShapeDtypeStruct((k, n), jnp.int8),
        ).packed
        x = jax.ShapeDtypeStruct((m, k), jnp.int8)
        pw = ops._pair_weights(bp.plane_weights(8, "booth"), packed.weights)

        def linear(x_q, packed_w):
            return fused_plane_linear(
                x_q, packed_w, pw, a_bits=8, variant="booth", bm=32, bn=256,
                k_blocks=k_blocks,
            )

        return jax.jit(linear).lower(*_on(one_chip, (x, packed))).compile()

    assert not ops.tiles_legal(32, 256, 128, k=k)
    with pytest.raises(Exception):
        compile_at(128)
    assert ops.tiles_legal(32, 256, 256, k=k)
    assert "tpu_custom_call" in compile_at(256).as_text()
    steps = ops.fused_k_blocks(256, k // 128)
    assert ops.tiles_legal(32, 256, 128 * steps, k=k)
    assert "tpu_custom_call" in compile_at(128, steps).as_text()
