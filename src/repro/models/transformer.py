"""Composable model assembly covering all ten assigned architectures.

A model is a frontend/embedding, a stack of blocks, and a head. Blocks
come in five kinds — ``dense`` (attention+MLP), ``moe`` (attention+MoE),
``ssm`` (Mamba2 SSD), ``rec`` (RG-LRU+MLP), ``attn`` (hybrid
local-attention+MLP) — grouped into *periods* (the hybrid layer pattern)
and scanned with ``lax.scan`` + ``jax.checkpoint`` so compile time and
activation memory are independent of depth. Setting
``cfg.scan_layers=False`` unrolls the stack and gives every layer an
index-qualified name, enabling the paper's per-layer precision dial at
full granularity (see examples/precision_sweep.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import integrity
from repro.core.precision import PrecisionPolicy
from repro.layers.attention import attention_apply, attention_init
from repro.layers.embedding import (
    embedding_apply,
    embedding_init,
    frontend_apply,
    frontend_init,
    lm_head_apply,
    lm_head_init,
)
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.moe import moe_apply, moe_init
from repro.layers.norms import rmsnorm_apply, rmsnorm_init
from repro.layers.rglru import rglru_apply, rglru_init
from repro.layers.ssm import ssm_apply, ssm_init
from repro.models.config import ModelConfig
from repro.sharding.rules import constrain


# --------------------------------------------------------------------------
# Block init / apply
# --------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind in ("dense", "attn", "moe"):
        params = {
            "attn_norm": rmsnorm_init(d),
            "attn": attention_init(
                ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype, cfg.qk_norm
            ),
            "mlp_norm": rmsnorm_init(d),
        }
        if kind == "moe":
            params["moe"] = moe_init(ks[1], d, cfg.moe_d_ff, cfg.n_experts, cfg.dtype)
        else:
            params["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.act, cfg.dtype)
        return params
    if kind == "ssm":
        return {
            "norm": rmsnorm_init(d),
            "ssm": ssm_init(
                ks[0],
                d,
                d_inner=cfg.ssm_d_inner,
                n_heads=cfg.ssm_heads,
                head_dim=cfg.ssm_head_dim,
                d_state=cfg.ssm_state,
                conv_width=cfg.conv_width,
                dtype=cfg.dtype,
            ),
        }
    if kind == "rec":
        return {
            "norm": rmsnorm_init(d),
            "rglru": rglru_init(ks[0], d, cfg.lru_width, cfg.conv_width, cfg.dtype),
            "mlp_norm": rmsnorm_init(d),
            "mlp": mlp_init(ks[1], d, cfg.d_ff, cfg.act, cfg.dtype),
        }
    raise ValueError(kind)


def _apply_block(
    cfg: ModelConfig,
    kind: str,
    params,
    x,
    positions,
    *,
    policy: PrecisionPolicy,
    training: bool,
    name: str,
    cache=None,
):
    """Pre-norm residual block. Returns (x, new_cache, aux)."""
    aux = jnp.float32(0.0)
    if kind in ("dense", "attn", "moe"):
        h = rmsnorm_apply(params["attn_norm"], x)
        # named scopes set only op metadata: a device trace can split a
        # step's time by part of the model
        with jax.named_scope("attention"):
            attn_out, new_attn_cache = attention_apply(
                params["attn"],
                h,
                positions,
                n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim,
                causal=cfg.causal,
                window=cfg.local_window if kind == "attn" else 0,
                rope_theta=cfg.rope_theta,
                qk_norm=cfg.qk_norm,
                chunk=cfg.attn_chunk,
                policy=policy,
                training=training,
                name=f"{name}/attn",
                cache=cache,
            )
        # Reduce-scatter the block output before the residual add (Megatron
        # sequence parallelism); the mirrored constraint also pins the
        # backward cotangent to seq-sharded, keeping weight grads shard-local.
        attn_out = constrain(attn_out, ("batch", "seq", None))
        x = x + attn_out
        x = constrain(x, ("batch", "seq", None))
        h = rmsnorm_apply(params["mlp_norm"], x)
        with jax.named_scope("mlp"):
            if kind == "moe":
                mlp_out, aux = moe_apply(
                    params["moe"],
                    h,
                    n_experts=cfg.n_experts,
                    top_k=cfg.top_k,
                    policy=policy,
                    training=training,
                    name=f"{name}/moe",
                    impl=cfg.moe_impl,
                    capacity_factor=cfg.moe_capacity_factor,
                )
            else:
                mlp_out = mlp_apply(
                    params["mlp"], h, act=cfg.act, policy=policy, training=training,
                    name=f"{name}/mlp",
                )
        mlp_out = constrain(mlp_out, ("batch", "seq", None))
        x = x + mlp_out
        x = constrain(x, ("batch", "seq", None))
        return x, new_attn_cache, aux

    if kind == "ssm":
        h = rmsnorm_apply(params["norm"], x)
        out, new_cache = ssm_apply(
            params["ssm"],
            h,
            d_inner=cfg.ssm_d_inner,
            n_heads=cfg.ssm_heads,
            head_dim=cfg.ssm_head_dim,
            d_state=cfg.ssm_state,
            conv_width=cfg.conv_width,
            chunk=cfg.ssd_chunk,
            policy=policy,
            training=training,
            name=f"{name}/ssm",
            cache=cache,
        )
        x = constrain(x + out, ("batch", "seq", None))
        return x, new_cache, aux

    if kind == "rec":
        h = rmsnorm_apply(params["norm"], x)
        out, new_cache = rglru_apply(
            params["rglru"],
            h,
            lru_width=cfg.lru_width,
            conv_width=cfg.conv_width,
            policy=policy,
            training=training,
            name=f"{name}/rglru",
            cache=cache,
        )
        x = x + out
        h = rmsnorm_apply(params["mlp_norm"], x)
        x = x + mlp_apply(
            params["mlp"], h, act=cfg.act, policy=policy, training=training,
            name=f"{name}/mlp",
        )
        x = constrain(x, ("batch", "seq", None))
        return x, new_cache, aux

    raise ValueError(kind)


# --------------------------------------------------------------------------
# Model init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    kinds = cfg.layer_kinds()
    keys = jax.random.split(key, cfg.n_layers + 4)
    params: dict = {"embed": embedding_init(keys[-1], cfg.vocab_padded, cfg.d_model, cfg.dtype)}
    if cfg.frontend != "none":
        params["frontend"] = frontend_init(keys[-2], cfg.frontend_dim, cfg.d_model, cfg.dtype)
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = lm_head_init(keys[-3], cfg.d_model, cfg.vocab_padded, cfg.dtype)

    blocks = [_init_block(keys[i], cfg, kind) for i, kind in enumerate(kinds)]
    if not cfg.scan_layers:
        params["layers"] = blocks
        return params

    period = cfg.period if cfg.period else (kinds[0],)
    plen = len(period)
    n_full = cfg.n_layers // plen
    period_dicts = [
        {f"b{j}_{period[j]}": blocks[i * plen + j] for j in range(plen)}
        for i in range(n_full)
    ]
    params["periods"] = (
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *period_dicts)
        if n_full > 0
        else {}
    )
    params["tail"] = blocks[n_full * plen :]
    return params


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _embed_inputs(cfg: ModelConfig, params, batch, *, policy, training, cache):
    """Returns (x, positions)."""
    if cfg.frontend == "audio":
        x = frontend_apply(
            params["frontend"], batch["features"], policy=policy, training=training
        )
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        return x, positions

    tokens = batch["tokens"]
    x = embedding_apply(params["embed"], tokens)
    b, s = x.shape[:2]
    if cfg.frontend == "vision" and "patches" in batch:
        patch = frontend_apply(
            params["frontend"], batch["patches"], policy=policy, training=training
        )
        x = jnp.concatenate([patch.astype(x.dtype), x], axis=1)
        s = x.shape[1]
    if cache is not None and s == 1:  # decode: per-slot positions
        positions = cache["step"][:, None].astype(jnp.int32)
    elif cache is not None:
        # prefill-with-cache: continue from the running per-slot offset
        # (zero for a fresh cache, so monolithic prefill is the special
        # case; chunked prefill appends successive chunks)
        positions = cache["step"][:, None].astype(jnp.int32) + jnp.arange(
            s, dtype=jnp.int32
        )[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def forward(
    cfg: ModelConfig,
    params,
    batch,
    *,
    policy: Optional[PrecisionPolicy] = None,
    training: bool = False,
    cache=None,
    last_only: bool = False,
):
    """Returns (logits, aux_loss, new_cache). ``last_only`` computes the
    LM head for the final position only (prefill: avoids the (B,S,V)
    logits tensor entirely)."""
    policy = policy or PrecisionPolicy.off()
    x, positions = _embed_inputs(
        cfg, params, batch, policy=policy, training=training, cache=cache
    )
    x = constrain(x, ("batch", "seq", None))
    aux = jnp.float32(0.0)
    kinds = cfg.layer_kinds()

    if not cfg.scan_layers:
        new_layer_caches = []
        for i, kind in enumerate(kinds):
            blk_cache = cache["layers"][i] if cache is not None else None
            x, nc, a = _apply_block(
                cfg,
                kind,
                params["layers"][i],
                x,
                positions,
                policy=policy,
                training=training,
                name=f"layers/{i}/{kind}",
                cache=blk_cache,
            )
            aux += a
            new_layer_caches.append(nc)
        new_cache = None
        if cache is not None:
            # decode advances by 1, prefill by the chunk length — always
            # from the running offset (zero for a fresh cache)
            step = cache["step"] + x.shape[1]
            new_cache = {"step": step, "layers": new_layer_caches}
    else:
        period = cfg.period if cfg.period else (kinds[0],)
        plen = len(period)
        n_full = cfg.n_layers // plen

        def apply_period(x, aux, p_params, p_cache):
            new_caches = {}
            for j, kind in enumerate(period):
                key = f"b{j}_{kind}"
                blk_cache = p_cache[key] if p_cache is not None else None
                x, nc, a = _apply_block(
                    cfg,
                    kind,
                    p_params[key],
                    x,
                    positions,
                    policy=policy,
                    training=training,
                    name=f"layers/{kind}",
                    cache=blk_cache,
                )
                aux = aux + a
                new_caches[key] = nc
            return x, aux, new_caches

        def period_body(carry, xs):
            x, aux = carry
            p_params, p_cache = xs
            x, aux, new_caches = apply_period(x, aux, p_params, p_cache)
            return (x, aux), (new_caches if p_cache is not None else 0)

        body = jax.checkpoint(period_body) if training else period_body
        if n_full > 0:
            grp = cfg.remat_group if cache is None else 1
            if grp > 1 and n_full % grp == 0:
                # Two-level remat: the outer checkpoint saves one residual
                # per GROUP of `grp` periods (residual stack shrinks by grp);
                # the inner checkpoint keeps the within-group recompute at
                # per-period granularity, so group backward does NOT
                # materialize grp periods of intermediates at once (the
                # failure mode recorded in EXPERIMENTS.md §Perf iter for the
                # single-level version).
                grouped = jax.tree_util.tree_map(
                    lambda a: a.reshape((n_full // grp, grp) + a.shape[1:]),
                    params["periods"],
                )

                def one_period(x, aux, pj):
                    x, aux, _ = apply_period(x, aux, pj, None)
                    return x, aux

                inner = jax.checkpoint(one_period) if training else one_period

                def group_body(carry, pg):
                    x, aux, alarm = carry
                    # ABFT flags raised inside the scan body fold into the
                    # carry (integrity.scan_scope) — they are body-trace
                    # tracers the outer collector could not stack.
                    with integrity.scan_scope() as scope:
                        for j in range(grp):
                            pj = jax.tree_util.tree_map(lambda a: a[j], pg)
                            x, aux = inner(x, aux, pj)
                    return (x, aux, alarm | scope.any_alarm()), 0

                gbody = jax.checkpoint(group_body) if training else group_body
                (x, aux, alarm), _ = lax.scan(
                    gbody, (x, aux, jnp.bool_(False)), grouped
                )
                integrity.report_carried(alarm)
                new_periods = {}
            elif cache is None:
                # scan cannot carry a None xs leaf: close over it.
                def body_noc(carry, p_params):
                    x, aux, alarm = carry
                    with integrity.scan_scope() as scope:
                        (x, aux), _ = body((x, aux), (p_params, None))
                    return (x, aux, alarm | scope.any_alarm()), None

                (x, aux, alarm), _ = lax.scan(
                    body_noc, (x, aux, jnp.bool_(False)), params["periods"]
                )
                integrity.report_carried(alarm)
                new_periods = {}
            else:
                # The stacked cache rides in the CARRY and is updated in
                # place per layer (dynamic_update_index_in_dim): XLA keeps
                # ONE buffer for a while-carried array. Emitting the new
                # cache as scan ys instead allocates a second full stacked
                # cache (+7.9 GiB/dev on the 405B decode cell —
                # EXPERIMENTS.md §Perf).
                def body_inplace(carry, p_params):
                    x, aux, ctree, i, alarm = carry
                    p_cache = jax.tree_util.tree_map(
                        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                        ctree,
                    )
                    with integrity.scan_scope() as scope:
                        (x, aux), new_caches = body((x, aux), (p_params, p_cache))
                    ctree = jax.tree_util.tree_map(
                        lambda a, u: lax.dynamic_update_index_in_dim(
                            a, u.astype(a.dtype), i, 0
                        ),
                        ctree,
                        new_caches,
                    )
                    return (x, aux, ctree, i + 1, alarm | scope.any_alarm()), None

                (x, aux, new_periods, _, alarm), _ = lax.scan(
                    body_inplace,
                    (x, aux, cache["periods"], jnp.int32(0), jnp.bool_(False)),
                    params["periods"],
                )
                integrity.report_carried(alarm)

        new_tail = []
        tail_kinds = kinds[n_full * plen :]
        for i, kind in enumerate(tail_kinds):
            blk_cache = cache["tail"][i] if cache is not None else None
            x, nc, a = _apply_block(
                cfg,
                kind,
                params["tail"][i],
                x,
                positions,
                policy=policy,
                training=training,
                name=f"layers/tail/{kind}",
                cache=blk_cache,
            )
            aux += a
            new_tail.append(nc)

        new_cache = None
        if cache is not None:
            # decode advances by 1, prefill by the chunk length — always
            # from the running offset (zero for a fresh cache)
            step = cache["step"] + x.shape[1]
            new_cache = {"step": step, "periods": new_periods, "tail": new_tail}

    if last_only:
        x = x[:, -1:]
    x = rmsnorm_apply(params["final_norm"], x)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = x.astype(jnp.float32) @ params["embed"]["embedding"].astype(jnp.float32).T
        else:
            logits = lm_head_apply(
                params["lm_head"], x, policy=policy, training=training
            )
    logits = constrain(logits, ("batch", None, "vocab"))
    return logits, aux, new_cache


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------


def cross_entropy(logits, targets, mask=None, n_valid=None):
    """Stable CE in fp32; works with vocab-sharded logits under GSPMD (the
    max/logsumexp reductions become small collectives). ``n_valid`` masks
    padded-vocab columns out of the partition function."""
    logits = logits.astype(jnp.float32)
    if n_valid is not None and n_valid != logits.shape[-1]:
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(col < n_valid, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(cfg: ModelConfig, params, batch, *, policy=None, training=True, aux_weight=0.01):
    logits, aux, _ = forward(cfg, params, batch, policy=policy, training=training)
    targets = batch["targets"]
    if cfg.frontend == "vision" and "patches" in batch:
        logits = logits[:, batch["patches"].shape[1] :, :]
    loss = cross_entropy(logits, targets, batch.get("loss_mask"), n_valid=cfg.vocab_size)
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux_loss": aux}
