"""Roofline-pruned tile autotuner + persistent plan store (DESIGN.md §13).

Two load-bearing properties:

1. **Candidate legality** — every (bm, bn, bk) triple the pruner emits,
   for randomized shapes and every tunable kernel route, must pass the
   shared Mosaic legality predicate (``ops.tiles_legal``): int8 routes
   floored at bm >= 32, bn/bk multiples of the 128-wide lane, within the
   VMEM budget, and the ``auto_tiles`` heuristic always among at most
   ``MAX_CANDIDATES`` survivors (tuning can't lose by construction).

2. **Graceful degradation** — a missing, torn, version-mismatched, or
   illegally-edited store must never crash plan resolution: the registry
   falls back to the exact ``auto_tiles`` answer, and the tuner records a
   miss. A warm (valid) store must serve plans with zero tuning runs.
"""

import json

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image without hypothesis: seeded fallback
    from _hypothesis_compat import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import autotune
from repro.core import bitplanes as bp
from repro.core import plan as plan_mod
from repro.core.autotune import (
    HARDWARE_TABLE,
    MAX_CANDIDATES,
    TPU_V5E,
    PlanAutotuner,
    calibrate_from_bench,
    hardware_model,
    host_fingerprint,
    plan_key_id,
    tile_candidates,
)
from repro.core.plan import PlanRegistry, plan_for_operands
from repro.kernels import ops
from repro.runtime.plan_store import STORE_VERSION, PlanStore

TUNABLE_KERNELS = sorted(
    set(autotune.INT8_TILE_KERNELS)
    | set(autotune.BK_TUNABLE_KERNELS)
    | {"fused_cached", "fused_repack"}
)


def _key(m=64, k=256, n=256, a_bits=4, w_bits=4, backend="interpret", **kw):
    defaults = dict(
        m=m,
        k=k,
        n=n,
        a_bits=a_bits,
        w_bits=w_bits,
        a_in_bits=a_bits,
        w_in_bits=w_bits,
        variant="booth",
        level="digit",
        mode="fully_serial",
        backend=backend,
        accum="int32",
        has_epilogue=False,
        cache=None,
        fused=None,
        packed=None,
        bm=None,
        bn=None,
        bk=None,
    )
    defaults.update(kw)
    return plan_mod.PlanKey(**defaults)


def _stub_measure(walls):
    """Deterministic measure fn: wall time looked up by tile triple."""

    def measure(key, kernel, tiles, repeats=2):
        return walls.get(tuple(tiles), 100.0)

    return measure


# -- candidate generation ----------------------------------------------------


@settings(max_examples=40)
@given(
    m=st.integers(1, 700),
    k=st.integers(32, 2048),
    n=st.integers(64, 2048),
    a_bits=st.integers(1, 8),
    w_bits=st.integers(1, 8),
    kernel=st.sampled_from(TUNABLE_KERNELS),
)
def test_candidates_always_legal(m, k, n, a_bits, w_bits, kernel):
    key = _key(m=m, k=k, n=n, a_bits=a_bits, w_bits=w_bits)
    cands = tile_candidates(key, kernel)
    int8 = kernel in autotune.INT8_TILE_KERNELS
    assert 1 <= len(cands) <= MAX_CANDIDATES
    heur = autotune._heuristic_tiles(key, kernel)
    assert heur in cands, "auto_tiles answer must always be a candidate"
    for bm, bn, bk in cands:
        assert ops.tiles_legal(bm, bn, bk, int8=int8, k=k), (kernel, bm, bn, bk)
        assert bm % 8 == 0 and bn % ops.MOSAIC_LANE == 0
        assert bk % ops.MOSAIC_LANE == 0 and bk % ops.PACKED_WORD_BITS == 0
        # the (bk / 32)-row packed word tile is a sublane multiple, or
        # spans the whole packed extent
        assert (bk // ops.PACKED_WORD_BITS) % 8 == 0 or bk >= k
        if int8:
            assert bm >= ops.MOSAIC_INT8_MIN_BM
        vmem = autotune._vmem_bytes(kernel, bm, bn, bk, a_bits, w_bits)
        assert vmem <= ops.VMEM_BUDGET_BYTES


def test_fused_routes_pin_bk_to_pack_block():
    """For fused kernels the pack block IS the K tile: bk never varies."""
    key = _key(m=256, k=1024, n=1024)
    heur = autotune._heuristic_tiles(key, "fused_cached")
    for tiles in tile_candidates(key, "fused_cached"):
        assert tiles[2] == heur[2]


def test_jnp_routes_collapse_to_heuristic():
    """Tiles are inert under XLA fusion: one candidate, nothing to bench."""
    for kernel, backend in [("cached_scan", "interpret"), ("staged", "jnp")]:
        key = _key(backend=backend)
        cands = tile_candidates(key, kernel)
        assert cands == [autotune._heuristic_tiles(key, kernel)]


def test_candidates_ranked_by_calibrated_model():
    """A bandwidth-starved model must not change legality, only order."""
    key = _key(m=512, k=1024, n=1024)
    slow = HARDWARE_TABLE["cpu"]
    fast = HARDWARE_TABLE[TPU_V5E]
    for hw in (slow, fast):
        cands = tile_candidates(key, "cached_packed", hw)
        assert len(cands) <= MAX_CANDIDATES
        for tiles in cands:
            assert ops.tiles_legal(*tiles, int8=False, k=key.k)


def test_bk_splitting_k_must_fill_word_sublanes():
    """bk=128 packs to a 4-row word tile: refused once it splits K (the
    v5e compiler refuses it — see test_tpu_compile), accepted when it is
    the whole extent."""
    assert not ops.tiles_legal(32, 256, 128, k=4096)
    assert ops.tiles_legal(32, 256, 256, k=4096)
    assert ops.tiles_legal(32, 256, 128, k=128)
    assert ops.tiles_legal(32, 256, 384, k=300)
    # the pack block is the fused kernel's K tile: the same rule holds
    w = jnp.zeros((1000, 128), jnp.int8)
    with pytest.raises(ValueError, match="8 word rows"):
        bp.make_weight_planes(w, w_bits=8, variant="booth", level="bitplane", block=384)
    clamped = bp.make_weight_planes(
        w[:300], w_bits=8, variant="booth", level="bitplane", block=512
    )
    assert clamped.packed.block == 384  # spans K=300: legal at 12 word rows


def test_hardware_table_keyed_by_device_kind():
    v5e = hardware_model(TPU_V5E)
    assert (v5e.peak_flops_bf16, v5e.peak_flops_int8, v5e.hbm_bw) == (
        197e12, 393e12, 819e9,
    )
    assert "TPU v5e" in v5e.source
    assert hardware_model() == HARDWARE_TABLE["cpu"]  # this process: CPU
    with pytest.raises(ValueError, match="not in HARDWARE_TABLE"):
        hardware_model("TPU v9 imaginary")


# -- calibration + identity --------------------------------------------------


def test_calibration_falls_back_on_garbage(tmp_path):
    base = hardware_model("cpu")
    assert calibrate_from_bench(str(tmp_path / "missing.json"), "cpu") == base
    torn = tmp_path / "torn.json"
    torn.write_text('{"benches": {"packed_plane_mat')
    assert calibrate_from_bench(str(torn), "cpu") == base
    empty = {"jax_backend": "cpu", "benches": {}}
    assert calibrate_from_bench(empty, "cpu") == base
    assert calibrate_from_bench(None, "cpu") == base


def test_calibration_refuses_report_from_another_backend():
    """Interpret-mode CPU walls must never overwrite a TPU row (and a TPU
    report says nothing about this CPU): the backend must match."""
    for backend in ("tpu", None):
        with pytest.raises(ValueError, match="measured on backend"):
            calibrate_from_bench({"jax_backend": backend, "benches": {}}, "cpu")


def test_calibration_fits_envelope():
    bench = {
        "host": "unit",
        "jax_backend": "cpu",
        "benches": {
            "packed_plane_matmul": {
                "configs": [
                    {
                        "kernel_shape": [128, 256, 128],
                        "mxu_passes": 4,
                        "wall_us": {"interpret_packed": 100.0},
                        "bytes": {"packed_operand_bytes": 40_000},
                    }
                ]
            }
        },
    }
    hw = calibrate_from_bench(bench, "cpu")
    flops = 2 * 128 * 256 * 128 * 4
    assert hw.peak_flops_int8 == pytest.approx(flops / 100e-6)
    assert hw.hbm_bw == pytest.approx(40_000 / 100e-6)
    assert hw.source == "calibrated:unit"
    # Untouched terms keep the builtin values.
    assert hw.link_bw == hardware_model("cpu").link_bw


def test_host_fingerprint_stable_and_hostname_free():
    import socket

    fp = host_fingerprint()
    assert fp == host_fingerprint()
    assert socket.gethostname() not in fp


def test_plan_key_id_drops_requested_tiles():
    a = plan_key_id(_key(bm=None, bn=None, bk=None))
    b = plan_key_id(_key(bm=64, bn=128, bk=256))
    assert a == b
    assert json.loads(a)["m"] == 64  # round-trips as JSON


# -- persistent store --------------------------------------------------------


def test_store_roundtrip_and_atomic_layout(tmp_path):
    path = tmp_path / "plans" / "store.json"
    store = PlanStore(str(path))
    assert store.get("fp", "k1") is None  # missing file: empty, no error
    assert store.load_error is None
    store.put("fp", "k1", {"bm": 64, "bn": 128, "bk": 128})
    assert PlanStore(str(path)).get("fp", "k1")["bm"] == 64
    doc = json.loads(path.read_text())
    assert doc["version"] == STORE_VERSION
    assert not list(path.parent.glob(".*tmp*")), "no temp files left behind"


def test_store_torn_json_degrades(tmp_path):
    path = tmp_path / "store.json"
    path.write_text('{"version": 1, "hosts": {"fp": {"k1"')
    store = PlanStore(str(path))
    assert store.get("fp", "k1") is None
    assert store.load_error is not None
    store.put("fp", "k2", {"bm": 32, "bn": 128, "bk": 128})  # still writable
    assert PlanStore(str(path)).get("fp", "k2") is not None


def test_store_version_mismatch_discards(tmp_path):
    path = tmp_path / "store.json"
    path.write_text(json.dumps({
        "version": 999,
        "hosts": {"fp": {"k1": {"bm": 64, "bn": 128, "bk": 128}}},
    }))
    store = PlanStore(str(path))
    assert store.get("fp", "k1") is None
    assert "version mismatch" in (store.load_error or "")


# -- tuner: store consultation + degradation ---------------------------------


def test_tuner_measures_pruned_candidates_and_persists(tmp_path):
    key = _key()
    cands = tile_candidates(key, "cached_packed")
    assert len(cands) > 1, "shape chosen to leave something to measure"
    winner = cands[-1]
    walls = {tuple(t): 50.0 for t in cands}
    walls[tuple(winner)] = 1.0
    store = PlanStore(str(tmp_path / "s.json"))
    tuner = PlanAutotuner(store, fingerprint="fp", measure=_stub_measure(walls))
    assert tuner.tiles_for(key, "cached_packed") == winner
    assert (tuner.store_hits, tuner.store_misses, tuner.tunes) == (0, 1, 1)
    rec = store.get("fp", plan_key_id(key))
    assert (rec["bm"], rec["bn"], rec["bk"]) == winner
    assert rec["source"] == "measured" and rec["candidates"] == len(cands)


def test_tuner_warm_store_zero_tunes(tmp_path):
    key = _key()
    path = str(tmp_path / "s.json")
    cold = PlanAutotuner(
        PlanStore(path), fingerprint="fp", measure=_stub_measure({})
    )
    tiles = cold.tiles_for(key, "cached_packed")
    warm = PlanAutotuner(
        PlanStore(path),
        fingerprint="fp",
        measure=_stub_measure({}),
        tune_on_miss=False,  # a tune in the warm process would return None
    )
    assert warm.tiles_for(key, "cached_packed") == tiles
    assert (warm.store_hits, warm.store_misses, warm.tunes) == (1, 0, 0)


def test_tuner_rejects_illegal_stored_record(tmp_path):
    """A hand-edited/stale record with illegal tiles is a miss, not a crash."""
    key = _key()
    store = PlanStore(str(tmp_path / "s.json"))
    for bad in ({"bm": 4, "bn": 128, "bk": 128},   # below int8 floor
                {"bm": 64, "bn": 100, "bk": 128},  # off-lane bn
                {"bm": 64, "bn": 128}):            # missing bk
        store.put("fp", plan_key_id(key), bad)
        tuner = PlanAutotuner(store, fingerprint="fp", tune_on_miss=False)
        assert tuner.tiles_for(key, "fused_cached") is None
        assert (tuner.store_hits, tuner.store_misses) == (0, 1)
    # A hand-edited file can hold a non-dict record: the store's typed
    # getter filters it out before the tuner ever sees it.
    doc = json.loads((tmp_path / "s.json").read_text())
    doc["hosts"]["fp"][plan_key_id(key)] = "not-a-dict"
    (tmp_path / "s.json").write_text(json.dumps(doc))
    assert PlanStore(str(tmp_path / "s.json")).get("fp", plan_key_id(key)) is None


def test_tuner_memoizes_within_process(tmp_path):
    key = _key()
    tuner = PlanAutotuner(
        PlanStore(str(tmp_path / "s.json")),
        fingerprint="fp",
        measure=_stub_measure({}),
    )
    first = tuner.tiles_for(key, "cached_packed")
    assert tuner.tiles_for(key, "cached_packed") == first
    assert tuner.tunes == 1, "second lookup is memoized, not re-tuned"


# -- registry integration ----------------------------------------------------


def _resolve(registry, **kw):
    return plan_for_operands(
        ((8, 64), (64, 128)),
        a_bits=4,
        w_bits=4,
        backend="jnp",
        registry=registry,
        **kw,
    )


def test_registry_uses_tuner_and_marks_provenance(tmp_path):
    registry = PlanRegistry()
    tuner = PlanAutotuner(
        PlanStore(str(tmp_path / "s.json")), fingerprint="fp"
    )
    registry.attach_tuner(tuner)
    plan = _resolve(registry)
    assert plan.tuned
    assert "tuned" in plan.describe()
    assert registry.store_stats()["tunes"] == 1
    registry.clear()  # keeps the tuner attached (warm memo)
    assert registry.tuner is tuner
    assert _resolve(registry).tuned


def test_registry_degrades_to_auto_tiles_without_tuner_answer(tmp_path):
    registry = PlanRegistry()
    registry.attach_tuner(
        PlanAutotuner(
            PlanStore(str(tmp_path / "s.json")),
            fingerprint="fp",
            tune_on_miss=False,
        )
    )
    plan = _resolve(registry)
    assert not plan.tuned
    bm, bn, bk = ops.auto_tiles(plan.key.m, plan.key.k, None, None,
                                n=plan.key.n, bn=None)
    assert (plan.bm, plan.bn) == (bm, bn)
    assert registry.store_stats()["store_misses"] == 1


def test_registry_explicit_tiles_bypass_tuner(tmp_path):
    """User-requested tiles always win; the tuner is never consulted."""
    registry = PlanRegistry()

    class Exploding:
        def tiles_for(self, key, kernel):  # pragma: no cover - must not run
            raise AssertionError("tuner consulted despite explicit tiles")

        def stats(self):
            return {"store_hits": 0, "store_misses": 0, "tunes": 0}

    registry.attach_tuner(Exploding())
    plan = _resolve(registry, bm=8, bn=128)
    assert not plan.tuned and plan.bm == 8


def test_registry_without_tuner_reports_zero_counters():
    registry = PlanRegistry()
    assert registry.store_stats() == {
        "store_hits": 0, "store_misses": 0, "tunes": 0,
    }
    assert not _resolve(registry).tuned
