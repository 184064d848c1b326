"""Mixture-of-Experts layer: top-k routing with expert parallelism.

Two dispatch implementations:

* ``gshard_ep`` (default) — GShard-style capacity-bounded dispatch under
  ``shard_map``: tokens are all-gathered across the expert-parallel
  ("model") mesh axis to the shards owning their experts, computed with
  dense per-expert batched GEMMs, and combined back with a
  ``psum_scatter``. All collectives are static-shaped (all-gather /
  psum_scatter over ICI), dispatch buffers are bounded by
  ``capacity_factor``, and nothing in the layer materializes a
  global-token-count tensor — this is what lets the 128-expert/94-layer
  qwen3 cell fit 16 GB/chip (EXPERIMENTS.md §Perf).
  ``capacity_factor=0`` means dropless (capacity = every copy could land
  on one expert) — the default for tests/small runs, numerically
  identical across any mesh.

* ``global_sort`` — the original dropless sorted-dispatch
  (argsort + ``lax.ragged_dot`` over all token copies). Exact and simple,
  but the data-dependent global gather/scatter cannot be sharded by
  GSPMD (it replicates the (T*k, d) dispatch tensors on every device:
  477 GiB/device for qwen3 train_4k — the refuted baseline in
  EXPERIMENTS.md §Perf). Kept for single-host runs and as the oracle the
  EP path is tested against.

Expert weights are sharded (model=experts, data=FSDP on d_model); the
router is replicated. Per-expert precision follows the PrecisionPolicy
(``<name>/expert`` pattern), quantizing the expert GEMMs with the same
symmetric quantizer the bit-serial path uses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.quantize import fake_quant
from repro.layers.linear import linear_init
from repro.sharding.rules import current_rules


def moe_init(key, d_model: int, d_ff: int, n_experts: int, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    scale = (1.0 / d_model) ** 0.5
    w = lambda k, shape, s: (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)
    return {
        "router": linear_init(ks[0], d_model, n_experts, jnp.float32),
        "gate": w(ks[1], (n_experts, d_model, d_ff), scale),
        "up": w(ks[2], (n_experts, d_model, d_ff), scale),
        "down": w(ks[3], (n_experts, d_ff, d_model), (1.0 / d_ff) ** 0.5),
    }


def _maybe_quant(w, x, prec, training):
    """Apply the bit-serial quantizer to an expert GEMM's operands."""
    if not prec.active:
        return w, x
    wq = fake_quant(w.astype(jnp.float32), prec.w_bits, axis=1).astype(w.dtype)
    xq = fake_quant(x.astype(jnp.float32), prec.a_bits, axis=-1).astype(x.dtype)
    return wq, xq


# ---------------------------------------------------------------------------
# GShard-style EP dispatch (shard_map)
# ---------------------------------------------------------------------------


def _route(xf, router_w, n_experts: int, top_k: int):
    """Top-k routing. xf: (T, d) -> (probs (T,E), top_p (T,k), top_ids)."""
    logits = (xf.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return probs, top_p, top_ids


def _aux_loss(probs, top_ids, n_experts: int):
    """Switch-style load-balancing loss from local router statistics."""
    importance = jnp.mean(probs, axis=0)  # (E,)
    load = jnp.mean(
        jax.nn.one_hot(top_ids, n_experts, dtype=jnp.float32).sum(axis=1), axis=0
    )
    return n_experts * jnp.sum(importance * load)


def _ep_block(
    x,               # (b_loc, s_loc, d) local tokens
    router_w,        # (d_loc, E)       FSDP-sharded on d
    gate, up, down,  # (E_loc, d_loc, f), (E_loc, d_loc, f), (E_loc, f, d_loc)
    *,
    n_experts: int,
    top_k: int,
    capacity: int,
    prec,
    training: bool,
    model_axis: Optional[str],
    fsdp_axis: Optional[str],
    batch_axes,
    seq_sharded: bool,
):
    """The per-device body. Collectives: all-gather tokens + weights (fwd),
    psum_scatter combine; their AD transposes handle backward."""
    b_loc, s_loc, d_loc_x = x.shape

    # 1. Assemble the token row this expert shard will serve.
    if model_axis is not None and seq_sharded:
        xg = lax.all_gather(x, model_axis, axis=1, tiled=True)  # (b_loc, s, d)
    else:
        xg = x
    t_row = xg.shape[0] * xg.shape[1]
    xf = xg.reshape(t_row, xg.shape[2])

    # 2. FSDP: gather the d_model-sharded weights for this layer.
    if fsdp_axis is not None:
        router_w = lax.all_gather(router_w, fsdp_axis, axis=0, tiled=True)
        gate = lax.all_gather(gate, fsdp_axis, axis=1, tiled=True)
        up = lax.all_gather(up, fsdp_axis, axis=1, tiled=True)
        down = lax.all_gather(down, fsdp_axis, axis=2, tiled=True)
    e_loc = gate.shape[0]

    # 3. Route (replicated within a model-axis row: every shard computes the
    #    same routing for its token row — cheap, and avoids broadcasting ids).
    probs, top_p, top_ids = _route(xf, router_w, n_experts, top_k)

    # local expert id range [e0, e0 + e_loc)
    if model_axis is not None:
        shard = lax.axis_index(model_axis)
    else:
        shard = 0
    e0 = shard * e_loc

    # 4. Capacity-bounded dispatch for LOCAL experts only.
    flat_ids = top_ids.reshape(-1)                      # (N,) N = T_row*k
    flat_w = top_p.reshape(-1)
    n = flat_ids.shape[0]
    token_of = jnp.arange(n, dtype=jnp.int32) // top_k

    local = (flat_ids >= e0) & (flat_ids < e0 + e_loc)
    lid = jnp.where(local, flat_ids - e0, e_loc)        # e_loc = overflow row
    onehot = jax.nn.one_hot(lid, e_loc, dtype=jnp.int32)  # (N, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot            # position in expert
    pos = jnp.sum(pos * onehot, axis=1)                  # (N,)
    keep = local & (pos < capacity)
    dst = jnp.where(keep, lid * capacity + pos, e_loc * capacity)

    buf = jnp.zeros((e_loc * capacity + 1, xf.shape[1]), xf.dtype)
    buf = buf.at[dst].set(xf[token_of], mode="drop")
    buf3 = buf[:-1].reshape(e_loc, capacity, xf.shape[1])

    # 5. Dense per-expert GEMMs (MXU batched matmuls).
    wg, xb = _maybe_quant(gate, buf3, prec, training)
    wu, _ = _maybe_quant(up, buf3, prec, training)
    g = jnp.einsum("ecd,edf->ecf", xb, wg.astype(xb.dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xb, wu.astype(xb.dtype),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    wd, hq = _maybe_quant(down, h, prec, training)
    o = jnp.einsum("ecf,efd->ecd", hq, wd.astype(hq.dtype),
                   preferred_element_type=jnp.float32)  # (E_loc, cap, d)

    # 6. Combine: weighted scatter-add back to the token row, then
    #    reduce-scatter over the expert shards (each takes its seq chunk).
    of = o.reshape(e_loc * capacity, o.shape[2])
    contrib = jnp.zeros((t_row, of.shape[1]), jnp.float32)
    gathered = jnp.where(keep[:, None], of[jnp.minimum(dst, e_loc * capacity - 1)], 0.0)
    contrib = contrib.at[token_of].add(flat_w[:, None] * gathered)
    contrib = contrib.reshape(xg.shape[0], xg.shape[1], of.shape[1])

    if model_axis is not None and seq_sharded:
        out = lax.psum_scatter(contrib, model_axis, scatter_dimension=1, tiled=True)
    elif model_axis is not None:
        out = lax.psum(contrib, model_axis)
    else:
        out = contrib

    # 7. Load-balance aux (global mean over all token shards).
    aux = _aux_loss(probs, top_ids, n_experts)
    axes = tuple(a for a in (batch_axes or ()))
    if axes:
        aux = lax.pmean(aux, axes)

    return out.astype(x.dtype), aux


def _capacity_for(t_row: int, top_k: int, n_experts: int, e_loc: int,
                  capacity_factor: float) -> int:
    n = t_row * top_k
    if capacity_factor <= 0:  # dropless: any expert could get every copy
        return n
    cap = int(capacity_factor * n / n_experts)
    return max(min(cap, n), 1)


def moe_apply_gshard(
    params,
    x: jax.Array,
    *,
    n_experts: int,
    top_k: int,
    policy,
    training: bool = False,
    name: str = "moe",
    capacity_factor: float = 0.0,
):
    """EP dispatch. x: (B, S, d). Returns (out, aux_loss)."""
    b, s, d = x.shape
    prec = policy.lookup(f"{name}/expert")
    rules = current_rules()

    if rules is None or rules.model_axis is None:
        # single-device / no-mesh path: same math, no collectives
        cap = _capacity_for(b * s, top_k, n_experts, n_experts, capacity_factor)
        return _ep_block(
            x, params["router"]["w"], params["gate"], params["up"], params["down"],
            n_experts=n_experts, top_k=top_k, capacity=cap, prec=prec,
            training=training, model_axis=None, fsdp_axis=None,
            batch_axes=(), seq_sharded=False,
        )

    mesh = rules.mesh
    m_axis, f_axis = rules.model_axis, rules.fsdp_axis
    msize = mesh.shape[m_axis]
    fsize = mesh.shape[f_axis] if f_axis else 1
    bsz = 1
    for a in rules.batch_axes:
        bsz *= mesh.shape[a]

    if n_experts % msize != 0:
        raise ValueError(
            f"n_experts={n_experts} must divide over model axis ({msize})"
        )

    batch_spec = rules.batch_axes if (b % bsz == 0 and b >= bsz) else None
    seq_ok = rules.seq_shard and s % msize == 0 and s >= msize
    seq_spec = m_axis if seq_ok else None
    d_spec = f_axis if (f_axis and d % fsize == 0) else None
    f_down_spec = d_spec

    x_spec = P(batch_spec, seq_spec, None)
    specs = dict(
        x=x_spec,
        router=P(d_spec, None),
        gate=P(m_axis, d_spec, None),
        up=P(m_axis, d_spec, None),
        down=P(m_axis, None, f_down_spec),
    )

    b_loc = b // bsz if batch_spec else b
    s_row = s  # after the in-block all-gather over model
    cap = _capacity_for(
        b_loc * s_row, top_k, n_experts, n_experts // msize, capacity_factor
    )

    body = functools.partial(
        _ep_block,
        n_experts=n_experts,
        top_k=top_k,
        capacity=cap,
        prec=prec,
        training=training,
        model_axis=m_axis,
        fsdp_axis=d_spec,  # None when d doesn't divide (weights replicated)
        batch_axes=tuple(rules.batch_axes),
        seq_sharded=seq_ok,
    )

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(specs["x"], specs["router"], specs["gate"], specs["up"],
                  specs["down"]),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"]["w"], params["gate"], params["up"], params["down"])
    return out, aux


def moe_apply_global_sort(
    params,
    x: jax.Array,
    *,
    n_experts: int,
    top_k: int,
    policy,
    training: bool = False,
    name: str = "moe",
):
    """Dropless sorted dispatch (single-host oracle). x: (B, S, d)."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    prec = policy.lookup(f"{name}/expert")

    probs, top_p, top_ids = _route(xf, params["router"]["w"], n_experts, top_k)

    # Dropless dispatch: sort the T*k token copies by expert id.
    flat_ids = top_ids.reshape(-1)  # (T*k,)
    flat_w = top_p.reshape(-1)
    order = jnp.argsort(flat_ids)
    token_idx = order // top_k  # source token of each sorted copy
    xs = xf[token_idx]  # (T*k, d)
    group_sizes = jnp.bincount(flat_ids, length=n_experts).astype(jnp.int32)

    wg, xs_q = _maybe_quant(params["gate"], xs, prec, training)
    wu, _ = _maybe_quant(params["up"], xs, prec, training)
    g = lax.ragged_dot(xs_q, wg.astype(xs.dtype), group_sizes)
    u = lax.ragged_dot(xs_q, wu.astype(xs.dtype), group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    wd, h_q = _maybe_quant(params["down"], h, prec, training)
    out_sorted = lax.ragged_dot(h_q, wd.astype(x.dtype), group_sizes)  # (T*k, d)

    out_sorted = out_sorted.astype(jnp.float32) * flat_w[order][:, None]
    out = jnp.zeros((t, d), jnp.float32).at[token_idx].add(out_sorted)

    aux = _aux_loss(probs, top_ids, n_experts)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_apply(
    params,
    x: jax.Array,
    *,
    n_experts: int,
    top_k: int,
    policy,
    training: bool = False,
    name: str = "moe",
    impl: str = "gshard_ep",
    capacity_factor: float = 0.0,
):
    """x: (B, S, d). Returns (out, aux_loss)."""
    if impl == "global_sort":
        return moe_apply_global_sort(
            params, x, n_experts=n_experts, top_k=top_k, policy=policy,
            training=training, name=name,
        )
    return moe_apply_gshard(
        params, x, n_experts=n_experts, top_k=top_k, policy=policy,
        training=training, name=name, capacity_factor=capacity_factor,
    )
