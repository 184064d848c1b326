"""Pallas TPU kernel: blockwise (flash) attention with online softmax.

Needed by the long-sequence shape cells: materializing a 32k x 32k score
matrix is impossible, so attention is computed KV-block by KV-block with
a running (max, sum, acc) in VMEM scratch — the standard flash schedule,
re-tiled for the TPU (128-aligned blocks, MXU matmuls, VMEM scratch).

Supports causal masking (with whole-block skipping above the diagonal)
and GQA via a query-head -> kv-head index map (no KV broadcast in HBM).

Serving extensions (the continuous-batching cache path):

* ``kv_lens`` — per-sequence valid KV lengths, an SMEM-resident (B, 1)
  int32 operand. Each batch lane masks its own length inside the same
  grid, so one launch covers a slot array with mixed sequence lengths
  (the jit static ``kv_len`` remains for fixed wrapper padding).
* int8 K/V with ``k_scale``/``v_scale`` — the int8-quantized KV cache is
  consumed *as stored*: K/V blocks stream from HBM at 1 byte/value and
  the per-(position, head) scales are folded into the scores (K) and the
  softmax probabilities (V) in VMEM, so a dequantized cache tile never
  exists anywhere. This is the kernel half of the cache's
  quantize-on-append contract (models.cache.quantize_kv).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
)

NEG_INF = -1e30


def _flash_kernel(
    *refs,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_steps: int,
    kv_len: int,
    has_lens: bool,
    has_k_scale: bool,
    has_v_scale: bool,
):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    lens_ref = next(it) if has_lens else None
    k_scale_ref = next(it) if has_k_scale else None
    v_scale_ref = next(it) if has_v_scale else None
    o_ref, m_scratch, l_scratch, acc_scratch = next(it), next(it), next(it), next(it)

    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _reset():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # Causal: skip KV blocks strictly above the diagonal.
    should_run = True
    if causal:
        should_run = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(should_run)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if has_k_scale:
            # int8 K: fold the per-(position, head) scale into the scores —
            # (q . k_q) * scale == q . dequant(k), no dequant tile needed.
            s = s * k_scale_ref[0, 0][None, :]

        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if has_lens:
            # Per-sequence valid length from SMEM: the mask-aware serving
            # path (mixed slot lengths share one launch).
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < lens_ref[0, 0], s, NEG_INF)
        elif kv_len < kv_steps * block_k:
            # Padded KV columns must not receive attention mass. Applied
            # under causal masking too: query rows at q_pos >= kv_len would
            # otherwise attend padded columns on the diagonal's far side.
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev = m_scratch[...]  # (block_q, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if has_v_scale:
            # int8 V: weight the probabilities instead of dequantizing V —
            # (p * scale) . v_q == p . dequant(v).
            p = p * v_scale_ref[0, 0][None, :]
        acc_scratch[...] = acc_scratch[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l = l_scratch[...]
        o_ref[0, 0] = (acc_scratch[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "block_q", "block_k", "kv_len", "out_dtype",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,
    kv_lens: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); Hq % Hkv == 0 (GQA).

    Sq % block_q == 0 and Sk % block_k == 0 (wrapper pads otherwise).
    ``kv_len``: number of *real* KV positions (<= Sk); columns past it are
    padding and are masked out of the softmax. NOTE: ``kv_len`` is a jit
    *static* argument — each distinct value compiles a new kernel. It is
    meant for fixed wrapper padding (ops.flash_attention passes the
    constant unpadded length), not as a per-step decode cursor.

    ``kv_lens``: (B,) int32 *array* of per-sequence valid lengths — the
    dynamic counterpart for the slot-array decode/prefill path (one
    compiled kernel serves every mix of lengths; mutually exclusive with
    ``kv_len``). Lanes with length 0 produce finite garbage (free decode
    slots), never NaN — NEG_INF is a finite sentinel.

    ``k_scale``/``v_scale``: (B, Hkv, Sk) f32 per-(position, head) scales
    of an int8-quantized K/V (see models.cache.quantize_kv); K/V then
    stream at int8 and are dequantized implicitly in VMEM. ``out_dtype``
    overrides the output dtype (defaults to q.dtype — pass e.g. bfloat16
    when q itself is int8).

    Returns (B, Hq, Sq, D).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}")
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d**-0.5
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) must tile by ({block_q},{block_k})")
    if kv_lens is not None and kv_len is not None:
        raise ValueError("kv_len (static) and kv_lens (per-sequence) are exclusive")
    if kv_lens is not None and kv_lens.shape != (b,):
        raise ValueError(f"kv_lens must be ({b},), got {kv_lens.shape}")
    for name, scale in (("k_scale", k_scale), ("v_scale", v_scale)):
        if scale is not None and scale.shape != (b, hkv, sk):
            raise ValueError(
                f"{name} must be ({b},{hkv},{sk}), got {scale.shape}"
            )

    kv_steps = sk // block_k
    static_kv_len = sk if kv_len is None else kv_len
    if not 0 < static_kv_len <= sk:
        raise ValueError(f"kv_len {static_kv_len} out of range (0, {sk}]")
    grid = (b, hq, sq // block_q, kv_steps)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_steps=kv_steps,
        kv_len=static_kv_len,
        has_lens=kv_lens is not None,
        has_k_scale=k_scale is not None,
        has_v_scale=v_scale is not None,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec(
            (1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)
        ),
    ]
    operands = [q, k, v]
    if kv_lens is not None:
        in_specs.append(
            pl.BlockSpec(
                (1, 1), lambda bi, hi, qi, ki: (bi, 0), memory_space=pltpu.SMEM
            )
        )
        operands.append(kv_lens.reshape(b, 1).astype(jnp.int32))
    scale_spec = pl.BlockSpec(
        (1, 1, block_k), lambda bi, hi, qi, ki: (bi, hi // group, ki)
    )
    for scale in (k_scale, v_scale):
        if scale is not None:
            in_specs.append(scale_spec)
            operands.append(scale.astype(jnp.float32))
    out_dtype = q.dtype if out_dtype is None else out_dtype
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype),
        scratch_shapes=[
            _scratch(block_q, 1),
            _scratch(block_q, 1),
            _scratch(block_q, d),
        ],
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)


def _scratch(rows: int, cols: int):
    return pltpu.VMEM((rows, cols), jnp.float32)


# ---------------------------------------------------------------------------
# Paged decode attention (DESIGN.md §12)
# ---------------------------------------------------------------------------


def _paged_kernel(
    tables_ref,  # scalar-prefetch: (B, P) int32 block tables
    lens_ref,  # scalar-prefetch: (B,) int32 valid lengths
    q_ref,
    k_ref,
    v_ref,
    k_scale_ref,
    v_scale_ref,
    o_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    sm_scale: float,
    page_size: int,
    pages: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _reset():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # Pages at or past the valid length hold no live positions (their
    # table entries point at the null page): skip the whole block. Lanes
    # with length 0 never run — their (0-initialized) accumulator yields
    # finite garbage, like the dense kernel's free-slot lanes.
    @pl.when(ki * page_size < lens_ref[bi])
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (page_size, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        # int8 pools: fold the paged per-(position, head) scales exactly
        # like the dense int8 path — no dequantized page tile exists.
        s = s * k_scale_ref[0, :, 0][None, :]
        k_pos = ki * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < lens_ref[bi], s, NEG_INF)

        m_prev = m_scratch[...]
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        p = p * v_scale_ref[0, :, 0][None, :]
        acc_scratch[...] = acc_scratch[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new

    @pl.when(ki == pages - 1)
    def _finish():
        l = l_scratch[...]
        o_ref[0, 0] = (acc_scratch[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "block_q", "out_dtype", "interpret"),
)
def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_scale_pool: jax.Array,
    v_scale_pool: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    *,
    sm_scale: float | None = None,
    block_q: int = 1,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention straight off the paged int8 KV pools.

    q: (B, Hq, Sq, D); pools: (n_pages, page_size, Hkv, D) int8 with
    (n_pages, page_size, Hkv) f32 scales — the layout
    ``models.paging.paged_init_cache`` stores (one layer's slice);
    block_tables: (B, P) int32; kv_lens: (B,) int32.

    The block tables and lengths ride in as **scalar-prefetch** operands
    (``pltpu.PrefetchScalarGridSpec``): they land in SMEM before the
    body runs, so each grid step's K/V block index map dereferences
    ``tables[b, ki]`` and the DMA fetches exactly the physical page —
    the gather is the schedule, no per-slot contiguous KV copy is ever
    materialized. ``block_k`` is pinned to ``page_size``: one KV block
    == one page. Pages at or past a lane's valid length are skipped
    entirely (they point at the null page 0).

    Causality is implicit: decode queries sit at position ``len - 1``
    and the length mask admits exactly positions ``< len``.

    Returns (B, Hq, Sq, D) in ``out_dtype`` (default bfloat16).
    """
    b, hq, sq, d = q.shape
    n_pages, page_size, hkv, _ = k_pool.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}")
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d**-0.5
    if sq % block_q:
        raise ValueError(f"Sq={sq} must tile by block_q={block_q}")
    if block_tables.shape[0] != b or block_tables.ndim != 2:
        raise ValueError(
            f"block_tables must be ({b}, P), got {block_tables.shape}"
        )
    if kv_lens.shape != (b,):
        raise ValueError(f"kv_lens must be ({b},), got {kv_lens.shape}")
    for name, pool, shape in (
        ("v_pool", v_pool, k_pool.shape),
        ("k_scale_pool", k_scale_pool, k_pool.shape[:-1]),
        ("v_scale_pool", v_scale_pool, k_pool.shape[:-1]),
    ):
        if pool.shape != shape:
            raise ValueError(f"{name} must be {shape}, got {pool.shape}")
    pages = block_tables.shape[1]

    kernel = functools.partial(
        _paged_kernel,
        sm_scale=sm_scale,
        page_size=page_size,
        pages=pages,
    )
    # index maps receive the scalar-prefetch refs after the grid indices
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, sq // block_q, pages),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d),
                lambda bi, hi, qi, ki, tables, lens: (bi, hi, qi, 0),
            ),
            pl.BlockSpec(
                (1, page_size, 1, d),
                lambda bi, hi, qi, ki, tables, lens: (
                    tables[bi, ki], 0, hi // group, 0
                ),
            ),
            pl.BlockSpec(
                (1, page_size, 1, d),
                lambda bi, hi, qi, ki, tables, lens: (
                    tables[bi, ki], 0, hi // group, 0
                ),
            ),
            pl.BlockSpec(
                (1, page_size, 1),
                lambda bi, hi, qi, ki, tables, lens: (
                    tables[bi, ki], 0, hi // group
                ),
            ),
            pl.BlockSpec(
                (1, page_size, 1),
                lambda bi, hi, qi, ki, tables, lens: (
                    tables[bi, ki], 0, hi // group
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d),
            lambda bi, hi, qi, ki, tables, lens: (bi, hi, qi, 0),
        ),
        scratch_shapes=[
            _scratch(block_q, 1),
            _scratch(block_q, 1),
            _scratch(block_q, d),
        ],
    )
    out_dtype = jnp.bfloat16 if out_dtype is None else out_dtype
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype),
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        q,
        k_pool,
        v_pool,
        k_scale_pool.astype(jnp.float32),
        v_scale_pool.astype(jnp.float32),
    )
