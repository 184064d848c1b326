"""Jittable step functions: train (with gradient accumulation), prefill,
decode. These are what the launcher jits/lowers — the dry-run AOT-compiles
exactly these under the production mesh.

The inference factories take an optional ``precision=(a_bits, w_bits)``
runtime dial: the policy is re-stamped via
:meth:`PrecisionPolicy.with_runtime_bits`, so every projection inside the
step resolves its execution plan at the dialed width — weight planes by
MSB-prefix truncation of the decompose-once cache, activations by
quantizing at the lower width. Bit-widths are trace-time constants
(exactly as the accelerator's effective width is a register programmed
between matmuls), so each dialed precision is its own jit specialization;
the serving engines keep one compiled step per precision and swap between
them mid-flight (``set_precision``).

A device trace names each compiled step after its function: ``jax.jit``
calls the XLA module ``jit_<function name>``, so the continuous-batching
decode step shows as ``jit_cb_step`` and a chunk of staged prefill as
``jit_chunk_step`` (every chunk length and tier). The benchmark's
per-step device times match those names: keep ``cb_step`` and
``chunk_step`` as the inner functions' names. Inside the steps,
``jax.named_scope`` marks the model's parts in each op's metadata
(``attention``, ``mlp``, ``lm_head``, ``sampling``; ``paged_commit`` in
:func:`repro.models.paging.paged_commit`)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import PrecisionPolicy
from repro.models.cache import init_cache
from repro.models.config import ModelConfig
from repro.models.transformer import forward, loss_fn
from repro.optim import OptimConfig, apply_updates, clip_by_global_norm
from repro.optim import compress as gcomp

#: Compiler options of the serving steps. By default XLA may keep a bf16
#: intermediate at f32 inside a fusion ("excess precision"), so where a
#: value rounds depends on how the program happens to be fused: a Pallas
#: kernel writes its bf16 output to memory, while the same epilogue on the
#: jnp route can stay f32 into its consumer. On a TPU v5e that alone moved
#: 13% of the greedy argmaxes of a two-layer granite-3-8b forward. With it
#: off every route rounds where the source says, so the Pallas and jnp
#: routes — and a tensor-parallel engine and one chip — agree bit for bit.
EXACT_ROUNDING = {"xla_allow_excess_precision": False}


def jit_step(fn, **kw):
    """``jax.jit`` under :data:`EXACT_ROUNDING`: how the serving engines
    compile their prefill and decode steps."""
    return jax.jit(fn, compiler_options=EXACT_ROUNDING, **kw)


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptimConfig,
    policy: Optional[PrecisionPolicy] = None,
    microbatches: int = 1,
    compress_grads: bool = False,
    grad_accum_dtype=jnp.float32,
):
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics).

    ``microbatches`` > 1 accumulates gradients over batch slices with a
    lax.scan (bounds live activation memory); ``compress_grads`` routes
    gradients through int8 error-feedback compression (numerics of a
    compressed cross-pod all-reduce — the error buffer rides in
    opt_state['_gc_error'])."""
    optimizer = opt_cfg.build()

    def loss_one(p, mb):
        return loss_fn(cfg, p, mb, policy=policy, training=True)

    grad_fn = jax.value_and_grad(loss_one, has_aux=True)

    def train_step(params, opt_state, batch, step):
        if microbatches > 1:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]),
                batch,
            )

            def body(carry, mb):
                gsum, lsum = carry
                (l, _metrics), g = grad_fn(params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(grad_accum_dtype), gsum, g
                )
                return (gsum, lsum + l), None

            gzero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, grad_accum_dtype), params
            )
            (gsum, lsum), _ = lax.scan(body, (gzero, jnp.float32(0.0)), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
        else:
            (loss, _metrics), grads = grad_fn(params, batch)

        if compress_grads:
            err = opt_state["_gc_error"]
            qs, scales, err = gcomp.compress_tree(grads, err)
            grads = gcomp.decompress_tree(qs, scales)
            opt_state = dict(opt_state, _gc_error=err)

        grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
        inner = {k: v for k, v in opt_state.items() if not k.startswith("_")}
        updates, inner = optimizer.update(grads, inner, params, step)
        params = apply_updates(params, updates)
        new_state = dict(inner)
        for k, v in opt_state.items():
            if k.startswith("_") and k != "_gc_error":
                new_state[k] = v
        if compress_grads:
            new_state["_gc_error"] = opt_state["_gc_error"]
            new_state["_gc_error"] = err
        metrics = {"loss": loss, "grad_norm": gnorm}
        return params, new_state, metrics

    return train_step


def init_opt_state(cfg, opt_cfg: OptimConfig, params, compress_grads: bool = False):
    state = dict(opt_cfg.build().init(params))
    if compress_grads:
        state["_gc_error"] = gcomp.init_error(params)
    return state


def _dial(policy, precision: Optional[Tuple[int, int]]):
    """Apply a runtime precision override to the step's policy."""
    if precision is None or policy is None:
        return policy
    return policy.with_runtime_bits(*precision)


def _collected(collector, body):
    """Run ``body()`` under ``collector`` and return (result, alarms).

    The collector context AND the stacking must both happen inside the
    traced step body: the ABFT alarm flags the executors report are
    tracers of *this* trace, so stacking them outside it would leak
    tracers (UnexpectedTracerError). Returning the stacked vector as a
    step output is what carries the alarms across the jit boundary — the
    engine harvests the concrete values via ``collector.harvest``.
    """
    if collector is None:
        return body(), jnp.zeros((0,), jnp.bool_)
    with collector.collect():
        result = body()
        alarms = collector.stacked()
    return result, alarms


def make_prefill_step(
    cfg: ModelConfig,
    policy=None,
    max_len: Optional[int] = None,
    kv_quant: bool = False,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
):
    """prefill_step(params, batch) -> (last_logits, cache). Cache zeros are
    created inside the step so the dry-run captures their allocation.
    ``kv_quant`` stores attention KV int8 + per-(position, head) scales
    (quantize-on-append; see models.cache). ``precision`` dials the
    runtime bit-width of every projection (see module docstring).

    ``collector`` (an :class:`repro.core.integrity.Collector`): run the
    forward under ABFT alarm collection — the step returns a third output,
    the (n_checks,) bool alarm vector (see :func:`_collected`).

    With ``kv_quant`` the forward runs against a **raw** bf16 cache and
    quantizes once at the end (``models.paging.quantize_scratch``) rather
    than quantizing on store: the compiled prefill program is then the
    *same* program chunked prefill runs per chunk, which is what makes a
    chunk schedule and a monolithic launch emit bit-identical logits and
    committed KV bytes (DESIGN.md §12)."""
    from repro.models.paging import quantize_scratch

    policy = _dial(policy, precision)

    def prefill_step(params, batch):
        if cfg.frontend == "audio":
            bsz, s = batch["features"].shape[:2]
        else:
            bsz, s = batch["tokens"].shape
            if cfg.frontend == "vision" and "patches" in batch:
                s += batch["patches"].shape[1]
        cache = (
            init_cache(cfg, bsz, max_len or s, cfg.dtype, kv_quant=False)
            if cfg.is_decoder
            else None
        )

        def body():
            return forward(
                cfg, params, batch, policy=policy, cache=cache,
                last_only=cfg.is_decoder,
            )

        (logits, _aux, cache), alarms = _collected(collector, body)
        if kv_quant and cache is not None:
            cache = quantize_scratch(cache)
        if collector is None:
            return logits[:, -1, :], cache
        return logits[:, -1, :], cache, alarms

    return prefill_step


def make_chunk_prefill_step(
    cfg: ModelConfig,
    policy=None,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
):
    """One chunked-prefill stage: chunk_step(params, scratch, tokens) ->
    (last_logits, scratch[, alarms]).

    Appends ``tokens`` (1, C) to a **raw** bf16 scratch cache
    (``init_cache(cfg, 1, max_len, kv_quant=False)``) at its running
    length and attends the whole written extent — the same compiled
    program as :func:`make_prefill_step`'s forward, so any chunk schedule
    reproduces the monolithic prefill bit for bit. jit re-specializes per
    distinct chunk length, exactly like per-prompt-length prefill.
    Quantization happens once at commit (``models.paging``), never here.

    The scratch must NOT be jit-donated: shared-prefix registry entries
    hold snapshots of earlier chunk states (DESIGN.md §12).
    """
    policy = _dial(policy, precision)

    def chunk_step(params, scratch, tokens):
        def body():
            return forward(
                cfg, params, {"tokens": tokens}, policy=policy,
                cache=scratch, last_only=True,
            )

        (logits, _aux, scratch_out), alarms = _collected(collector, body)
        if collector is None:
            return logits[:, -1, :], scratch_out
        return logits[:, -1, :], scratch_out, alarms

    return chunk_step


def make_decode_step(cfg: ModelConfig, policy=None, precision: Optional[Tuple[int, int]] = None):
    """decode_step(params, cache, batch) -> (logits, new_cache)."""
    policy = _dial(policy, precision)

    def decode_step(params, cache, batch):
        logits, _aux, cache = forward(cfg, params, batch, policy=policy, cache=cache)
        return logits[:, -1, :], cache

    return decode_step


def make_serve_step(
    cfg: ModelConfig,
    policy=None,
    sample_fn=None,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
):
    """One engine iteration: decode + sample next token (the shape-cell
    ``serve_step``: one new token against a seq_len-deep cache).

    ``sample_fn(logits, key) -> (B,) int32`` over vocab-masked logits;
    defaults to greedy argmax (:func:`repro.launch.sampling.greedy`).
    ``collector``: collect ABFT alarms; the step gains a third output,
    the alarm vector (see :func:`_collected`)."""
    from repro.launch import sampling

    decode = make_decode_step(cfg, policy, precision=precision)
    sample_fn = sample_fn or sampling.greedy

    def serve_step(params, cache, tokens, key=None):
        (logits, cache), alarms = _collected(
            collector, lambda: decode(params, cache, {"tokens": tokens})
        )
        with jax.named_scope("sampling"):
            logits = sampling.mask_vocab(logits, cfg.vocab_size)
            next_tok = sample_fn(logits, key)[:, None]
        if collector is None:
            return next_tok, cache
        return next_tok, cache, alarms

    return serve_step


def _tp_shard_map(fn, tp, in_specs, out_specs):
    """``jax.shard_map`` with the repo's compile settings.

    ``check_vma=False``: the bodies return replicated values by
    construction (identical deterministic math per shard after psum/pmax),
    but the varying-manual-axes checker cannot prove that through the
    integer plane kernels."""
    return jax.shard_map(
        fn, mesh=tp.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_tp_prefill_step(
    cfg: ModelConfig,
    tp,
    param_specs,
    policy=None,
    max_len: Optional[int] = None,
    kv_quant: bool = False,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
):
    """Tensor-parallel :func:`make_prefill_step`: same signature and
    outputs, executed under ``shard_map`` over ``tp.mesh``.

    ``tp`` is a :class:`repro.sharding.tp.TPContext`; ``param_specs`` the
    spec tree returned by ``shard_quantized`` alongside the stacked
    parameter tree this step consumes. The inner step is built with the
    *local* model config, so ``init_cache`` inside the body allocates the
    per-shard (head-sharded) KV extent and every plan resolves per-shard
    tiles from the local shapes. Logits and tokens come back replicated;
    the KV cache comes back as a global head-sharded array tree; ABFT
    alarms are OR-reduced across shards before leaving the body.
    """
    from jax.sharding import PartitionSpec as P

    local_cfg = tp.local_config(cfg)
    inner = make_prefill_step(
        local_cfg, policy=policy, max_len=max_len, kv_quant=kv_quant,
        precision=precision, collector=collector,
    )
    # Rank/structure template for the output cache specs (extents are
    # irrelevant — specs only need leaf names and ranks).
    cache_specs = tp.cache_specs(
        jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len or 8, cfg.dtype, kv_quant=kv_quant)
        )
    )

    def body(params, batch):
        local = tp.localize(params, param_specs)
        with tp.scope():
            out = inner(local, batch)
        if collector is None:
            return out
        logits, cache, alarms = out
        return logits, cache, tp.reduce_alarms(alarms)

    out_specs = (P(), cache_specs) + ((P(),) if collector is not None else ())
    return _tp_shard_map(body, tp, (param_specs, P()), out_specs)


def make_tp_chunk_prefill_step(
    cfg: ModelConfig,
    tp,
    param_specs,
    policy=None,
    max_len: Optional[int] = None,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
):
    """Tensor-parallel :func:`make_chunk_prefill_step`: the raw batch-1
    scratch rides through head-sharded like any KV tree (its k/v leaves
    map through ``tp.cache_specs`` by name); logits replicate."""
    from jax.sharding import PartitionSpec as P

    local_cfg = tp.local_config(cfg)
    inner = make_chunk_prefill_step(
        local_cfg, policy=policy, precision=precision, collector=collector
    )
    scratch_specs = tp.cache_specs(
        jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len or 8, cfg.dtype, kv_quant=False)
        )
    )

    def body(params, scratch, tokens):
        local = tp.localize(params, param_specs)
        with tp.scope():
            out = inner(local, scratch, tokens)
        if collector is None:
            return out
        logits, scratch_out, alarms = out
        return logits, scratch_out, tp.reduce_alarms(alarms)

    out_specs = (P(), scratch_specs) + ((P(),) if collector is not None else ())
    return _tp_shard_map(body, tp, (param_specs, scratch_specs, P()), out_specs)


def make_tp_cb_decode_step(
    cfg: ModelConfig,
    tp,
    param_specs,
    policy=None,
    max_len: Optional[int] = None,
    n_slots: int = 1,
    kv_quant: bool = False,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
    with_logits: bool = False,
    cache_template=None,
):
    """Tensor-parallel :func:`make_cb_decode_step`: cb_step(params, cache,
    tokens, temps, key) under ``shard_map`` over ``tp.mesh``.

    The slot cache rides through sharded head-parallel (its specs are
    derived from a ``(n_slots, max_len)`` eval-shape template — only leaf
    names/ranks matter); tokens/temps/key replicate. Sampling runs
    redundantly and bit-identically on every shard from the replicated
    post-psum logits, so the returned tokens are replicated without a
    collective. See DESIGN.md §11.

    ``cache_template``: zero-arg callable building the cache tree the
    step will carry (its eval-shape feeds ``tp.cache_specs``); overrides
    the dense ``init_cache`` template — the paged engine passes
    ``models.paging.paged_init_cache`` here, whose pool/scale leaves
    shard head-parallel by the same leaf-name rules and whose block
    tables replicate (DESIGN.md §12).
    """
    from jax.sharding import PartitionSpec as P

    local_cfg = tp.local_config(cfg)
    inner = make_cb_decode_step(
        local_cfg, policy=policy, precision=precision, collector=collector,
        with_logits=with_logits,
    )
    template = cache_template or (
        lambda: init_cache(cfg, n_slots, max_len or 8, cfg.dtype, kv_quant=kv_quant)
    )
    cache_specs = tp.cache_specs(jax.eval_shape(template))

    def body(params, cache, tokens, temps, key):
        local = tp.localize(params, param_specs)
        with tp.scope():
            out = inner(local, cache, tokens, temps, key)
        if collector is None:
            return out
        lst = list(out)
        lst[2] = tp.reduce_alarms(lst[2])
        return tuple(lst)

    extras = ((P(),) if collector is not None else ()) + (
        (P(),) if with_logits else ()
    )
    return _tp_shard_map(
        body,
        tp,
        (param_specs, cache_specs, P(), P(), P()),
        (P(), cache_specs) + extras,
    )


def make_cb_decode_step(
    cfg: ModelConfig,
    policy=None,
    precision: Optional[Tuple[int, int]] = None,
    collector=None,
    with_logits: bool = False,
):
    """One continuous-batching engine iteration over the whole slot array.

    cb_step(params, cache, tokens, temps, key) -> (next_tokens, cache):
    every slot decodes one token against its own per-slot cache length and
    position; ``temps`` (B,) carries per-request sampling temperatures
    (0 = greedy, exactly). Free/finished slots still compute — their
    lanes are garbage the scheduler never reads, which is what keeps the
    step a single jit specialization regardless of occupancy.

    ``precision=(a_bits, w_bits)`` dials the step's runtime precision
    against the same weight tree (plane-prefix truncation); the engine
    compiles one such step per precision tier and swaps mid-serving.
    ``collector``: collect ABFT alarms; the step gains a third output,
    the alarm vector (see :func:`_collected`).
    ``with_logits``: additionally return the step's raw (pre-mask)
    per-slot logits as the last output — the autopilot's shadow quality
    probe scores per-tier logit KL from them (slice ``[:vocab_size]``
    before any softmax: positions past it are padding, and the masked
    logits' ``-inf`` would poison a KL)."""
    from repro.launch import sampling

    decode = make_decode_step(cfg, policy, precision=precision)

    def cb_step(params, cache, tokens, temps, key):
        (logits, cache), alarms = _collected(
            collector, lambda: decode(params, cache, {"tokens": tokens})
        )
        raw_logits = logits
        with jax.named_scope("sampling"):
            logits = sampling.mask_vocab(logits, cfg.vocab_size)
            next_tok = sampling.sample_tokens(logits, temps, key)[:, None]
        out = (next_tok, cache)
        if collector is not None:
            out = out + (alarms,)
        if with_logits:
            out = out + (raw_logits,)
        return out

    return cb_step
