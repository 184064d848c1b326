"""Public-API snapshot: the exported names and signatures of
``repro.core.plan``, ``repro.kernels.ops``, ``repro.sharding`` and
``repro.launch.mesh`` are a contract — the serving engines, benches, and
external callers build plans and meshes against them. A signature drift
must be a conscious decision: update the snapshot below in the same
commit that changes the API, and say why in the message. Runs in the CI
lint job (fast: imports + inspect only).
"""

import dataclasses
import inspect

import repro.core.plan as plan_mod
import repro.kernels.ops as ops_mod
import repro.launch.mesh as mesh_mod
import repro.sharding.rules as rules_mod
import repro.sharding.tp as tp_mod


def _sig(obj) -> str:
    return str(inspect.signature(obj))


def _describe(obj) -> str:
    if dataclasses.is_dataclass(obj):
        fields = tuple(f.name for f in dataclasses.fields(obj))
        methods = tuple(
            n for n, m in vars(obj).items()
            if callable(m) and not n.startswith("_")
        )
        return f"dataclass{fields} methods{methods}"
    if isinstance(obj, type) and hasattr(obj, "_fields"):
        return f"NamedTuple{tuple(obj._fields)}"
    if inspect.isclass(obj):
        methods = tuple(
            n for n, m in vars(obj).items()
            if callable(m) and not n.startswith("_")
        )
        return f"class methods{methods}"
    if callable(obj):
        return _sig(obj)
    return type(obj).__name__


PLAN_SURFACE = {
    # PR 10: 'tuned' is provenance — tiles came from the autotuner/store
    # rather than the auto_tiles heuristic
    "MatmulPlan": "dataclass('key', 'registry', 'kernel', 'bm', 'bn', 'bk', "
    "'pack_block', 'a_shift', 'w_shift', 'scale_mult', 'requant_w', "
    "'trunc_cache', 'gate', 'check', 'tuned') methods('with_precision', "
    "'sparsity_stats', 'integrity_stats', 'describe')",
    # PR 8: 'shard' carries the tensor-parallel placement triple
    # (axis_name, axis_size, role) so per-shard plans (local m/k/n) never
    # alias their global counterparts in the registry
    "PlanKey": "dataclass('m', 'k', 'n', 'a_bits', 'w_bits', 'a_in_bits', "
    "'w_in_bits', 'variant', 'level', 'mode', 'backend', 'accum', "
    "'has_epilogue', 'cache', 'fused', 'packed', 'bm', 'bn', 'bk', "
    "'sparsity', 'integrity', 'shard') methods()",
    # PR 10: attach_tuner/store_stats hook the roofline autotuner in
    "PlanRegistry": "class methods('get', 'attach_tuner', 'store_stats', "
    "'clear', 'plans')",
    "DEFAULT_REGISTRY": "PlanRegistry",
    "make_plan": "(policy: 'PrecisionPolicy', layer_name: 'str', shapes, "
    "backend: 'str' = 'auto', *, w_planes: 'Optional[bp.WeightPlanes]' = None, "
    "w_stored_bits: 'Optional[int]' = None, has_epilogue: 'bool' = True, "
    "accum_dtype: 'Any' = None, registry: 'Optional[PlanRegistry]' = None, "
    "bm: 'Optional[int]' = None, bn: 'Optional[int]' = None, "
    "bk: 'Optional[int]' = None, shard: 'Optional[tuple]' = None) "
    "-> 'MatmulPlan'",
    "plan_for_operands": "(shapes, *, a_bits: 'int', w_bits: 'int', "
    "variant: 'str' = 'booth', level: 'str' = 'digit', "
    "mode: 'str' = 'fully_serial', backend: 'str' = 'auto', "
    "accum_dtype: 'Any' = <class 'jax.numpy.int32'>, "
    "has_epilogue: 'bool' = False, w_planes: 'Optional[bp.WeightPlanes]' = None, "
    "a_in_bits: 'Optional[int]' = None, w_in_bits: 'Optional[int]' = None, "
    "fused: 'Optional[bool]' = None, packed: 'Optional[bool]' = None, "
    "bm: 'Optional[int]' = None, bn: 'Optional[int]' = None, "
    "bk: 'Optional[int]' = None, sparsity: 'str' = 'off', "
    "integrity: 'str' = 'off', shard: 'Optional[tuple]' = None, "
    "registry: 'Optional[PlanRegistry]' = None) -> 'MatmulPlan'",
    "plan_cacheable": "(policy: 'PrecisionPolicy', prec: 'LayerPrecision') "
    "-> 'bool'",
    # PR 7: the no-requantization audit moved from an inline bench check
    # into the plan module so the engine's dial check and the autopilot
    # bench section gate on the same invariant
    "truncation_audit": "(registry: 'Optional[PlanRegistry]' = None) "
    "-> 'dict'",
}

OPS_SURFACE = {
    "resolve_backend": "(backend: 'str') -> 'str'",
    "auto_tiles": "(m: 'int', k: 'int', bm: 'Optional[int]', "
    "bk: 'Optional[int]', n: 'Optional[int]' = None, "
    "bn: 'Optional[int]' = None) -> 'tuple[int, ...]'",
    # PR 10: the shared Mosaic-legality predicate the autotuner's
    # candidate generator and the stored-record validator both gate on;
    # ``k`` lets a packed word block that spans all of K pass
    "tiles_legal": "(bm: 'int', bn: 'int', bk: 'int', *, "
    "int8: 'bool' = True, vmem_bytes: 'int' = 0, k: 'int') -> 'bool'",
    "Epilogue": "NamedTuple('a_scale', 'w_scale', 'bias', 'activation', "
    "'out_dtype')",
    "apply_epilogue": "(acc: 'jax.Array', ep: 'Epilogue') -> 'jax.Array'",
    "plane_matmul": "(a_planes: 'jax.Array', w_planes: 'jax.Array', "
    "pair_weights: 'jax.Array', *, backend: 'str' = 'auto', "
    "bm: 'Optional[int]' = None, bn: 'int' = 128, bk: 'Optional[int]' = None) "
    "-> 'jax.Array'",
    "plane_matmul_packed": "(packed_a: 'bp.PackedPlanes', "
    "packed_w: 'bp.PackedPlanes', pair_weights: 'jax.Array', *, "
    "backend: 'str' = 'auto', bm: 'Optional[int]' = None, bn: 'int' = 128, "
    "bk: 'Optional[int]' = None, gate: 'bool' = False) -> 'jax.Array'",
    "fused_linear": "(x_q: 'jax.Array', packed_w: 'bp.PackedPlanes', "
    "epilogue: 'Optional[Epilogue]', *, a_bits: 'int', variant: 'str', "
    "backend: 'str' = 'auto', bm: 'Optional[int]' = None, "
    "bn: 'Optional[int]' = None, gate: 'bool' = False) -> 'jax.Array'",
    "bitserial_matmul": "(a: 'jax.Array', w: 'jax.Array', *, a_bits: 'int', "
    "w_bits: 'int', variant: 'str' = 'booth', level: 'str' = 'digit', "
    "mode: 'str' = 'fully_serial', backend: 'str' = 'auto', "
    "accum_dtype=<class 'jax.numpy.int32'>, packed: 'bool | None' = None, "
    "w_planes: 'bp.WeightPlanes | None' = None, fused: 'bool | None' = None, "
    "epilogue: 'Optional[Epilogue]' = None, **tile_kw) -> 'jax.Array'",
    "flash_attention": "(q: 'jax.Array', k: 'jax.Array', v: 'jax.Array', *, "
    "causal: 'bool' = True, sm_scale: 'float | None' = None, "
    "backend: 'str' = 'auto', block_q: 'int' = 128, block_k: 'int' = 128, "
    "kv_lens: 'Optional[jax.Array]' = None, "
    "k_scale: 'Optional[jax.Array]' = None, "
    "v_scale: 'Optional[jax.Array]' = None) -> 'jax.Array'",
}


# PR 8 (tensor-parallel serving): the GSPMD rules surface and the explicit
# TP serving surface are both contracts — DESIGN.md §11 documents which one
# applies where.
RULES_SURFACE = {
    "MeshRules": "dataclass('mesh', 'batch_axes', 'fsdp_axis', "
    "'model_axis', 'seq_shard') methods()",
    "rules_for_mesh": "(mesh: 'Mesh', *, seq_shard: 'bool' = True) "
    "-> 'MeshRules'",
    "use_rules": "class methods()",
    "current_rules": "() -> 'Optional[MeshRules]'",
    "constrain": "(x: 'jax.Array', logical: 'Tuple') -> 'jax.Array'",
    "param_spec": "(path: 'str', arr) -> 'P'",
    "tree_param_specs": "(params) -> 'dict'",
    "tree_param_shardings": "(params)",
    "batch_specs": "(batch_tree) -> 'dict'",
    "tree_cache_specs": "(cache_tree)",
}

TP_SURFACE = {
    "tp_role": "(name: 'str') -> 'Optional[str]'",
    "current_tp": "() -> 'Optional[TPContext]'",
    "shard_quantized": "(params, policy, tp: 'TPContext', *, "
    "plane_cache: 'bool' = True, value_bits=None)",
    "plane_cache_device_bytes": "(tree, specs=None, *, "
    "n_shards: 'int' = 1) -> 'int'",
}

MESH_SURFACE = {
    "make_production_mesh": "(*, multi_pod: 'bool' = False) -> 'Mesh'",
    "make_mesh": "(shape, axes) -> 'Mesh'",
    "make_host_mesh": "(model: 'int' = 1) -> 'Mesh'",
    "make_tp_mesh": "(model: 'int') -> 'Mesh'",
}


def test_plan_module_exports():
    assert sorted(plan_mod.__all__) == sorted(PLAN_SURFACE)


def test_plan_api_surface():
    got = {name: _describe(getattr(plan_mod, name)) for name in PLAN_SURFACE}
    assert got == PLAN_SURFACE


def test_ops_api_surface():
    got = {name: _describe(getattr(ops_mod, name)) for name in OPS_SURFACE}
    assert got == OPS_SURFACE


def test_sharding_api_surface():
    for mod, surface in (
        (rules_mod, RULES_SURFACE),
        (tp_mod, TP_SURFACE),
        (mesh_mod, MESH_SURFACE),
    ):
        got = {name: _describe(getattr(mod, name)) for name in surface}
        assert got == surface


def test_tp_context_surface():
    """TPContext is snapshotted by attribute presence (not a vars() render:
    classmethod callability differs across the CI python matrix) plus the
    dataclass field set."""
    assert tuple(
        f.name for f in dataclasses.fields(tp_mod.TPContext)
    ) == ("mesh", "size", "axis")
    for m in ("create", "scope", "local_config", "reduce_alarms",
              "global_amax", "shard_spec", "localize", "cache_specs"):
        assert callable(getattr(tp_mod.TPContext, m)), m


def test_plan_callable_contract():
    """The execute signature itself is API: (x, w=None, *, w_planes, epilogue)."""
    assert _sig(plan_mod.MatmulPlan.__call__) == \
        "(self, x, w=None, *, w_planes=None, epilogue=None)"
    assert _sig(plan_mod.MatmulPlan.with_precision) == (
        "(self, a_bits: 'Optional[int]' = None, "
        "w_bits: 'Optional[int]' = None) -> \"'MatmulPlan'\""
    )
