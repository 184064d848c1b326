"""Explicit tensor-parallel sharding of the packed bit-plane serving stack.

This is the serving-side counterpart of :mod:`repro.sharding.rules`:
instead of GSPMD constraint propagation, the quantized parameter tree is
*relaid out* per shard on the host and the serving steps run under
``shard_map`` over a one-axis ``("model",)`` mesh
(:func:`repro.launch.mesh.make_tp_mesh`). The layout is the Megatron
split specialized to the bit-plane cache (DESIGN.md §11):

* **column-parallel** (``q/k/v/gate/up``): the output dim N is sharded —
  each shard holds ``w_q[:, n0:n1]``, its own plane decomposition of that
  slice, and the matching ``w_scale`` columns. No collective; outputs
  stay sharded (heads for attention, ffn columns for the MLP).
* **row-parallel** (``o/down``): the input dim K is sharded — each shard
  holds ``w_q[k0:k1, :]`` and its decomposition; ``w_scale`` replicates.
  The plan runs *without* an epilogue, the raw int32 accumulators are
  ``lax.psum``-ed (exact: int32 addition is associative mod 2^32) and
  the dequant/bias/activation epilogue is applied once, after the psum.
* **vocab-parallel** (``lm_head/head``): column-sharded like the
  col-parallel set; the sharded logits are re-assembled with one tiled
  ``all_gather`` so the replicated sampler sees the full vocab. This is
  what makes the per-device plane-cache footprint actually ~1/P — the
  lm_head cache is the largest single entry on small-vocab configs.
* **KV head-parallel**: the slot-indexed int8 KV cache and its scale
  vectors shard on the ``n_kv_heads`` axis; attention is head-local.

The cardinal ordering rule: weights are quantized **globally first**
(per-output-channel scales over the full K), then sliced, then
decomposed per shard. Slicing K before quantizing would change the
per-column amax and break bit-identity with the single-device engine —
the parity oracle every TP configuration is tested against. Per-shard
decomposition also makes the ABFT column checksums and occupancy bitmaps
local by construction, so ``integrity="detect"/"scrub"`` and
``sparsity`` gating survive sharding unchanged.

Sharded leaves are *stacked* with a leading ``(n_shards,)`` axis and fed
to ``shard_map`` with ``PartitionSpec("model")``; inside the body
:meth:`TPContext.localize` squeezes the leading unit axis away. Stacking
(rather than device_put of a global array) is what lets the per-shard
plane packs have independent word padding and checksums.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bitplanes as bp

#: parameter-path suffixes whose output dim is model-sharded
COL_PARALLEL = frozenset({"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"})
#: parameter-path suffixes whose input dim is model-sharded (deferred epilogue)
ROW_PARALLEL = frozenset({"o_proj", "down_proj"})
#: vocab-parallel heads: column-sharded like COL_PARALLEL, but the output is
#: the logits the (replicated) sampler consumes, so ``linear_apply`` tiles an
#: exact ``all_gather`` onto the sharded output (axis-index-ordered
#: concatenation — bit-identical to computing the full vocab locally).
#: Both spellings appear: "head" is the parameter-tree path component
#: (``lm_head/head/w_q``), "lm_head" the layer name ``lm_head_apply``
#: passes to ``linear_apply``.
VOCAB_PARALLEL = frozenset({"head", "lm_head"})

#: KV-cache leaf names sharded on their head axis
_KV_VALUE_LEAVES = frozenset({"k", "v", "k_q", "v_q"})
_KV_SCALE_LEAVES = frozenset({"k_scale", "v_scale"})


def tp_role(name: str) -> Optional[str]:
    """Classify a layer/parameter path: "col", "row", "vocab" or None
    (replicated).

    Matches on the last path component, so both parameter-tree paths
    (``.../attn/o_proj``) and layer names given to ``linear_apply``
    (``layers/dense/attn/o_proj``) resolve identically.
    """
    leaf = name.rsplit("/", 1)[-1]
    if leaf in COL_PARALLEL:
        return "col"
    if leaf in ROW_PARALLEL:
        return "row"
    if leaf in VOCAB_PARALLEL:
        return "vocab"
    return None


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Static description of one tensor-parallel serving configuration.

    Installed ambiently (:meth:`scope`) inside the ``shard_map`` step
    bodies so :func:`repro.layers.linear.linear_apply` can detect TP
    execution and apply the row-parallel deferred-epilogue protocol
    without threading arguments through the model."""

    mesh: Mesh
    size: int
    axis: str = "model"

    @classmethod
    def create(cls, model_parallel: int, axis: str = "model") -> "TPContext":
        """Build the context plus its mesh over the first ``model_parallel``
        devices (raises if the host has fewer — CI forces 8 virtual CPU
        devices via XLA_FLAGS)."""
        from repro.launch.mesh import make_tp_mesh

        return cls(mesh=make_tp_mesh(model_parallel), size=model_parallel, axis=axis)

    # -- ambient scope -----------------------------------------------------

    @contextlib.contextmanager
    def scope(self):
        """Install this context for the duration of a step-body trace."""
        token = _TP.set(self)
        try:
            yield self
        finally:
            _TP.reset(token)

    # -- model-side helpers ------------------------------------------------

    def local_config(self, cfg):
        """Per-shard model config: heads divide over the model axis.

        Only the head counts change — the residual stream (d_model) stays
        replicated and every other dimension is derived from the (already
        sliced) parameter shapes at apply time."""
        if cfg.n_heads % self.size or cfg.n_kv_heads % self.size:
            raise ValueError(
                f"model_parallel={self.size} must divide n_heads="
                f"{cfg.n_heads} and n_kv_heads={cfg.n_kv_heads} "
                "(head-parallel attention + head-parallel KV cache)"
            )
        return dataclasses.replace(
            cfg,
            n_heads=cfg.n_heads // self.size,
            n_kv_heads=cfg.n_kv_heads // self.size,
        )

    def reduce_alarms(self, alarms: jax.Array) -> jax.Array:
        """OR-reduce a per-shard ABFT alarm vector across the model axis
        (inside a ``shard_map`` body) so the engine sees an alarm no
        matter which shard's plane words were hit."""
        if alarms.size == 0:
            return alarms
        return lax.pmax(alarms.astype(jnp.int32), self.axis).astype(jnp.bool_)

    def global_amax(self, x: jax.Array) -> jax.Array:
        """Cross-shard per-row |x| maximum of a K-sharded activation
        (keepdims) — the row-parallel path feeds this to
        :func:`repro.core.quantize.quantize` so every shard uses the
        *global* per-token scale (f32 max is exact, so the scale is
        bit-identical to the unsharded quantization)."""
        local = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        return lax.pmax(local, self.axis)

    # -- spec construction / localization ----------------------------------

    def shard_spec(self) -> P:
        """Spec of a stacked per-shard leaf (leading ``(n_shards,)`` axis)."""
        return P(self.axis)

    def localize(self, tree, specs):
        """Inside a ``shard_map`` body: squeeze the leading unit axis off
        every leaf whose spec shards the stacking axis, recovering the
        per-shard tree the (local-config) model consumes."""

        def one(leaf, spec):
            if len(spec) and spec[0] == self.axis:
                return leaf[0]
            return leaf

        return jax.tree_util.tree_map(one, tree, specs)

    def cache_specs(self, cache_tree):
        """PartitionSpec pytree sharding a decode cache head-parallel.

        KV value leaves ``(..., S, Hkv, D)`` shard on the ``Hkv`` axis,
        scale leaves ``(..., S, Hkv)`` on their trailing axis; everything
        else (lengths, step counters, SSM/LRU state) replicates. Accepts
        concrete caches or ``jax.eval_shape`` templates."""

        def spec(path, leaf):
            last = path[-1]
            name = getattr(last, "key", getattr(last, "name", None))
            ndim = len(leaf.shape)
            if name in _KV_VALUE_LEAVES:
                axis = ndim - 2
            elif name in _KV_SCALE_LEAVES:
                axis = ndim - 1
            else:
                return P()
            if leaf.shape[axis] % self.size:
                raise ValueError(
                    f"KV leaf {name!r} head axis {leaf.shape[axis]} does not "
                    f"divide model_parallel={self.size}"
                )
            return P(*([None] * axis + [self.axis]))

        return jax.tree_util.tree_map_with_path(spec, cache_tree)


_TP: contextvars.ContextVar[Optional[TPContext]] = contextvars.ContextVar(
    "tp_context", default=None
)


def current_tp() -> Optional[TPContext]:
    """The ambient :class:`TPContext` (inside a TP step-body trace), or
    None — single-device execution, where the TP branches in
    ``linear_apply`` are dead."""
    return _TP.get()


# ---------------------------------------------------------------------------
# Quantized-tree relayout
# ---------------------------------------------------------------------------


def _slice_stack(arr: jax.Array, axis: int, n: int) -> jax.Array:
    """Split ``arr`` into ``n`` equal slices along ``axis`` (negative ok)
    and stack them as a new leading axis."""
    axis = axis % arr.ndim
    if arr.shape[axis] % n:
        raise ValueError(
            f"axis {axis} extent {arr.shape[axis]} not divisible by {n} shards"
        )
    step = arr.shape[axis] // n
    slices = []
    for i in range(n):
        idx = tuple(
            slice(i * step, (i + 1) * step) if a == axis else slice(None)
            for a in range(arr.ndim)
        )
        slices.append(arr[idx])
    return jnp.stack(slices)


def shard_quantized(
    params, policy, tp: TPContext, *, plane_cache: bool = True, value_bits=None
):
    """Quantize a dense parameter tree and relay it out for ``tp``.

    Runs :func:`repro.models.quant.quantize_params` first — global
    quantization, global plane cache, global compaction decisions — then
    rewrites every tensor-parallel linear:

    * ``w_q`` is sliced per shard (columns for "col", rows for "row") and
      stacked with a leading ``(n_shards,)`` axis;
    * the plane cache is **re-decomposed per shard** from the sliced
      integers (vmapped over the shard and any scanned-layer axes), so
      checksums/occupancy are shard-local; compaction is re-applied on
      the stacked pack, whose kept-plane set is shared across shards (a
      plane is globally zero iff it is zero in every shard slice — the
      same set the single-device cache keeps);
    * ``w_scale`` slices for "col" ( per-output-channel), replicates for
      "row".

    Returns ``(tree, specs)`` where ``specs`` is the leaf-parallel
    ``PartitionSpec`` tree (``P("model")`` on stacked leaves, ``P()``
    elsewhere) consumed by ``shard_map`` and :meth:`TPContext.localize`.
    Must be called eagerly (host-side), never under ``jit``.
    """
    from repro.core.plan import plan_cacheable
    from repro.models.quant import decompose_linear_weight, quantize_params

    # The base tree only supplies global scales and integers: every plane
    # cache is rebuilt per shard below, so the base never builds one.
    base = quantize_params(params, policy, value_bits=value_bits)
    n = tp.size
    stacked_spec = tp.shard_spec()

    def replicate_specs(node):
        return jax.tree_util.tree_map(lambda _: P(), node)

    def rec(node, path):
        if isinstance(node, dict) and "w_q" in node:
            role = tp_role(path)
            if role is None:
                return dict(node), replicate_specs(dict(node))
            prec = policy.lookup(path)
            w_q = _slice_stack(node["w_q"], -2 if role == "row" else -1, n)
            out = {"w_q": w_q}
            spec = {"w_q": stacked_spec}
            if role == "row":
                out["w_scale"] = node["w_scale"]
                spec["w_scale"] = P()
            else:  # col / vocab: per-output-channel scales slice with N
                out["w_scale"] = _slice_stack(node["w_scale"], -1, n)
                spec["w_scale"] = stacked_spec
            if plane_cache and plan_cacheable(policy, prec):
                wp = decompose_linear_weight(
                    w_q,
                    w_bits=prec.w_bits,
                    variant=policy.variant,
                    level=policy.level,
                    checksum=policy.integrity != "off",
                )
                if policy.sparsity == "compact" and policy.level == "bitplane":
                    wp = bp.compact_weight_planes(wp)
                out["w_planes"] = wp
                spec["w_planes"] = jax.tree_util.tree_map(
                    lambda _: stacked_spec, wp
                )
            return out, spec
        if isinstance(node, dict):
            pairs = {k: rec(v, f"{path}/{k}") for k, v in node.items()}
            return (
                {k: t for k, (t, _) in pairs.items()},
                {k: s for k, (_, s) in pairs.items()},
            )
        if isinstance(node, (list, tuple)):
            pairs = [rec(v, f"{path}/{i}") for i, v in enumerate(node)]
            ctor = type(node)
            return ctor(t for t, _ in pairs), ctor(s for _, s in pairs)
        return node, jax.tree_util.tree_map(lambda _: P(), node)

    return rec(base, "")


def plane_cache_device_bytes(tree, specs=None, *, n_shards: int = 1) -> int:
    """Per-device bytes of the weight-plane cache held by ``tree``.

    Stacked tensor-parallel leaves (leading ``(n_shards,)`` axis, spec
    sharding axis 0) contribute ``nbytes / n_shards`` — each device holds
    one slice; replicated plane leaves contribute fully. This is the
    ``tp_serving`` bench's footprint metric: it must shrink ~1/P as the
    model axis grows (pack-word padding gives the "~").
    """
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    spec_leaves = (
        [s for _, s in jax.tree_util.tree_flatten_with_path(specs)[0]]
        if specs is not None
        else [P()] * len(leaves)
    )
    total = 0
    for (path, leaf), spec in zip(leaves, spec_leaves):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        if "w_planes" not in keys or not hasattr(leaf, "dtype"):
            continue
        nbytes = leaf.size * leaf.dtype.itemsize
        if len(spec) and spec[0] is not None:
            nbytes //= n_shards
        total += nbytes
    return total
