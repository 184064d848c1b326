"""Roofline-calibrated tile autotuner with a pluggable persistent store.

BISMO (Umuroglu et al.) gets runtime-reconfigurable bit-serial arrays to
peak by letting an *analytic performance model* prune the configuration
space so only a handful of candidates are ever instantiated. This module
is the software analogue for our plan layer:

1. **Hardware table + calibration.** Roofline constants live in one
   :data:`HARDWARE_TABLE` keyed by the ``device_kind`` jax reports, each
   row with its source; a device missing from the table is an error, not
   a default. When a measured ``BENCH_kernel.json`` from the running
   backend is available, :func:`calibrate_from_bench` fits the
   peak-compute and memory-bandwidth terms to the *observed* envelope of
   the packed/fused kernel sections (best plane-pass FLOP/s and HBM
   byte/s across all measured configs), so the pruning model ranks
   candidates by this host's actual roofline, not a datasheet's.

2. **Legality-first candidate generation.** :func:`tile_candidates`
   enumerates (bm, bn, bk) triples that Mosaic will actually accept —
   int8 tiles floored at bm >= 32, bn/bk multiples of the 128-wide lane,
   bk a whole sublane tile of packed words, working set within the VMEM budget
   (``ops.tiles_legal`` is the shared predicate) — scores them with the
   calibrated roofline (padding waste + per-grid-step overhead are what
   separate candidates on a fixed problem), and returns at most
   :data:`MAX_CANDIDATES` survivors. The ``auto_tiles`` heuristic answer
   is always among them, so tuning can never do worse than the default
   by construction.

3. **Measure only the survivors.** :class:`PlanAutotuner` micro-benchmarks
   the pruned candidates (pure-``jnp`` routes ignore tiles entirely, so
   there the model collapses the space to the single heuristic candidate
   and no measurement runs) and records the winner in a persistent store
   keyed ``(host_fingerprint, plan key)`` — see ``runtime/plan_store``.
   ``PlanRegistry`` consults an attached tuner before falling back to
   ``auto_tiles``: compile-once becomes tune-once-per-fleet.

Layering: this is ``core`` — it must not import ``runtime`` or
``launch``. The store is duck-typed (``get``/``put``), injected by the
serving layer; ``launch/roofline.py`` imports the hardware table from
here (downward is allowed, upward is not).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import platform
import sys
import time
from typing import Callable, Optional, Tuple

__all__ = [
    "HARDWARE_TABLE",
    "HardwareModel",
    "MAX_CANDIDATES",
    "PlanAutotuner",
    "TPU_V5E",
    "calibrate_from_bench",
    "hardware_model",
    "host_fingerprint",
    "plan_key_id",
    "tile_candidates",
]

MAX_CANDIDATES = 4

# Kernel routes whose operand tiles are int8 in VMEM (Mosaic min int8
# tile is (32, 128)); mirrors the floor core/plan.py applies on the
# heuristic path.
INT8_TILE_KERNELS = ("fused_cached", "fused_repack", "staged", "cached_planes")

# Routes where the K tile is a real kernel knob. For the fused routes the
# pack block *is* the K tile (changing it means repacking the weight
# cache), so bk stays at the heuristic there; for the pure-jnp routes XLA
# fuses the whole contraction and tiles are inert metadata.
BK_TUNABLE_KERNELS = ("cached_packed", "cached_planes", "staged", "staged_packed")
JNP_KERNELS = ("cached_scan", "oracle")

# Seconds of fixed overhead per grid step in the analytic model — grid
# dispatch, DMA issue, revisiting the accumulator. This is what makes the
# model prefer fewer/larger tiles when the roofline terms tie; the
# calibrated magnitude only has to rank candidates, not predict walls.
GRID_STEP_OVERHEAD_S = 2e-6


# ---------------------------------------------------------------------------
# Hardware table + calibration


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """One row of the device-keyed roofline table (all rates per chip)."""

    name: str
    peak_flops_bf16: float  # dense MXU/FMA rate, FLOP/s
    peak_flops_int8: float  # int8 plane-pass rate, FLOP/s
    hbm_bw: float           # main-memory bandwidth, bytes/s
    link_bw: float          # per-link interconnect bandwidth, bytes/s
    hbm_bytes: int          # main-memory capacity
    source: str = ""  # where the figures come from ("calibrated:<where>" once fitted)

    def compute_rate(self, int8: bool = True) -> float:
        return self.peak_flops_int8 if int8 else self.peak_flops_bf16


#: ``device_kind`` of a TPU v5e chip, as jax reports it.
TPU_V5E = "TPU v5 lite"

HARDWARE_TABLE = {
    TPU_V5E: HardwareModel(
        name="tpu-v5e",
        peak_flops_bf16=197e12,
        peak_flops_int8=393e12,
        hbm_bw=819e9,
        link_bw=50e9,  # 1,600 Gbit/s of interconnect over 4 links
        hbm_bytes=16 * 1024**3,
        source='Google Cloud documentation, "TPU v5e"',
    ),
    # The host CPU runs only the jnp / interpret routes, where tiles are
    # inert or emulated: round numbers that rank candidates, nothing more.
    "cpu": HardwareModel(
        name="cpu-host",
        peak_flops_bf16=2e11,
        peak_flops_int8=4e11,
        hbm_bw=2e10,
        link_bw=1e10,
        hbm_bytes=8 * 1024**3,
        source="round ranking constants for the CPU interpreter; not measured",
    ),
}


def hardware_model(kind: Optional[str] = None) -> HardwareModel:
    """The hardware-table row of ``kind`` (default: the running device).

    A device missing from :data:`HARDWARE_TABLE` raises: a roofline built
    from another chip's peaks would rank tiles, and report shares, against
    the wrong machine.
    """
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    try:
        return HARDWARE_TABLE[kind]
    except KeyError:
        raise ValueError(
            f"device_kind {kind!r} is not in HARDWARE_TABLE (known: "
            f"{sorted(HARDWARE_TABLE)}); add its published peaks with a source"
        ) from None


def _measured_rates(configs, shape_key, flops_of, bytes_of, wall_keys):
    """Best observed (FLOP/s, bytes/s) over a bench section's configs."""
    best_flops = 0.0
    best_bytes = 0.0
    for cfg in configs or ():
        shape = cfg.get(shape_key)
        walls = cfg.get("wall_us") or {}
        if not shape:
            continue
        wall_us = min(
            (walls[k] for k in wall_keys if isinstance(walls.get(k), (int, float))),
            default=None,
        )
        if not wall_us or wall_us <= 0:
            continue
        wall_s = wall_us * 1e-6
        best_flops = max(best_flops, flops_of(cfg, shape) / wall_s)
        best_bytes = max(best_bytes, bytes_of(cfg, shape) / wall_s)
    return best_flops, best_bytes


def calibrate_from_bench(bench, kind: Optional[str] = None) -> HardwareModel:
    """Fit the roofline terms to a measured ``BENCH_kernel.json``.

    ``bench`` is the parsed report dict or a path to it; ``kind`` the
    ``device_kind`` whose table row is the base (default: the running
    device). The fit is the *envelope*: the fastest plane-pass FLOP/s and
    HBM byte/s observed across the ``packed_plane_matmul`` and
    ``fused_linear*`` sections become the peak-compute and bandwidth terms
    (a roofline is an upper bound, so the best measurement is the tightest
    honest estimate). Returns the table row when the report is missing,
    malformed, or has no usable kernel sections.

    A report measured on another backend than the running one (its
    ``jax_backend``) raises: interpret-mode CPU walls are not TPU peaks.
    """
    import jax

    base = hardware_model(kind)
    if isinstance(bench, str):
        try:
            with open(bench) as fh:
                bench = json.load(fh)
        except (OSError, ValueError):
            return base
    if not isinstance(bench, dict):
        return base
    measured_on = bench.get("jax_backend")
    if measured_on != jax.default_backend():
        raise ValueError(
            f"bench report was measured on backend {measured_on!r}, this "
            f"process runs {jax.default_backend()!r}: its walls cannot "
            "calibrate this device's roofline"
        )
    benches = bench.get("benches", {})
    where = bench.get("host", "bench")

    # Plane-pass FLOPs: each of the `mxu_passes` plane pairs is a full
    # (m, k, n) int multiply-accumulate over the kernel tile.
    def _plane_flops(cfg, shape):
        m, k, n = shape
        return 2.0 * m * k * n * max(1, cfg.get("mxu_passes", 1))

    flops_a, bytes_a = _measured_rates(
        benches.get("packed_plane_matmul", {}).get("configs"),
        "kernel_shape",
        _plane_flops,
        lambda cfg, s: (cfg.get("bytes") or {}).get("packed_operand_bytes", 0),
        ("interpret_packed", "interpret_unpacked"),
    )
    flops_b = bytes_b = 0.0
    for section in ("fused_linear", "fused_linear_smoke"):
        f, b = _measured_rates(
            benches.get(section, {}).get("configs"),
            "shape",
            _plane_flops,
            lambda cfg, s: (cfg.get("bytes") or {}).get("fused_hbm_bytes", 0),
            ("interpret_fused", "interpret_staged"),
        )
        flops_b, bytes_b = max(flops_b, f), max(bytes_b, b)

    peak_int8 = max(flops_a, flops_b)
    hbm_bw = max(bytes_a, bytes_b)
    if peak_int8 <= 0 or hbm_bw <= 0:
        return base
    return dataclasses.replace(
        base,
        peak_flops_int8=peak_int8,
        peak_flops_bf16=peak_int8 / 2.0,
        hbm_bw=hbm_bw,
        source=f"calibrated:{where}",
    )


# ---------------------------------------------------------------------------
# Host fingerprint + plan-key identity


def host_fingerprint() -> str:
    """Stable identity of (hardware, toolchain) tuning results bind to.

    Deliberately excludes the hostname: CI runners get a fresh name every
    run, and two fleet hosts with identical silicon + toolchain should
    share one store entry (tune-once-per-fleet). A toolchain upgrade or a
    device swap changes the fingerprint, which silently invalidates every
    stored plan for the old host — staleness is handled by keying, not by
    TTLs.
    """
    try:  # pragma: no cover - device kind varies by host
        import jax

        device = jax.devices()[0].device_kind.replace(" ", "_")
        backend = jax.default_backend()
        count = jax.device_count()
        jax_ver = jax.__version__
    except Exception:  # pragma: no cover
        device, backend, count, jax_ver = "unknown", "none", 0, "none"
    raw = "|".join(
        (
            platform.system(),
            platform.machine(),
            f"py{sys.version_info.major}.{sys.version_info.minor}",
            f"jax{jax_ver}",
            backend,
            device,
            str(count),
        )
    )
    digest = hashlib.sha256(raw.encode()).hexdigest()[:12]
    return f"{platform.system().lower()}-{platform.machine()}-{backend}-{digest}"


def plan_key_id(key) -> str:
    """Serialize a PlanKey into the store's lookup string.

    The requested-tile fields are dropped: the tuner is only consulted
    when all of them are None (explicit tiles always win), so they carry
    no information, and dropping them keeps ids stable if a caller ever
    passes an equivalent key.
    """
    d = dataclasses.asdict(key)
    for tile in ("bm", "bn", "bk"):
        d.pop(tile, None)
    if d.get("shard") is not None:
        d["shard"] = list(d["shard"])
    return json.dumps(d, sort_keys=True, separators=(",", ":"))


# ---------------------------------------------------------------------------
# Candidate generation + roofline scoring


def _candidate_axis(lo: int, hi: int, step: int, need: int) -> list:
    """Multiples of ``step`` in [lo, hi] bracketing ``need`` (the problem
    extent): one tile covering it, plus smaller splits."""
    vals = []
    v = lo
    while v <= hi:
        vals.append(v)
        if v >= need:
            break
        v *= 2
    return vals or [lo]


def _vmem_bytes(kernel: str, bm: int, bn: int, bk: int, a_bits: int, w_bits: int) -> int:
    """Conservative working-set estimate for one grid step, bytes.

    Plane-pair routes hold every activation and weight plane tile plus an
    f32 accumulator; packed routes shrink K by the 32-bit word but keep
    magnitude+sign words. Signed variants double the plane word count.
    """
    acc = 2 * bm * bn * 4  # accumulator + output tile
    bkw = max(1, math.ceil(bk / 32))
    if kernel in ("cached_packed", "staged_packed"):
        return acc + 2 * 4 * (a_bits * bm * bkw + w_bits * bkw * bn)
    if kernel in ("fused_cached", "fused_repack"):
        # x tile is int8; weight planes arrive packed (mag+sign words).
        return acc + bm * bk + 2 * 4 * w_bits * bkw * bn + a_bits * bm * bk
    # Unpacked int8 planes (cached_planes / staged).
    return acc + a_bits * bm * bk + w_bits * bk * bn


def _predict_us(
    hw: HardwareModel,
    m: int,
    k: int,
    n: int,
    passes: int,
    bm: int,
    bn: int,
    bk: int,
) -> float:
    """Calibrated-roofline cost of one matmul at these tiles, microseconds.

    The padded extents charge for the waste a tile choice creates; the
    memory term charges weight re-streaming once per M-tile row of the
    grid; the per-grid-step overhead breaks ties toward larger tiles.
    """
    gm, gn, gk = math.ceil(m / bm), math.ceil(n / bn), math.ceil(k / bk)
    pm, pn, pk = gm * bm, gn * bn, gk * bk
    flops = 2.0 * pm * pk * pn * passes
    # Activations stream once per N-tile column, weights once per M-tile
    # row, output written once. Plane operands are ~1 byte/elem/plane
    # before packing; packing is a constant factor the ranking ignores.
    bytes_moved = pm * pk * gn + pk * pn * gm + pm * pn * 4
    compute_s = flops / hw.compute_rate(int8=True)
    memory_s = bytes_moved / hw.hbm_bw
    return (max(compute_s, memory_s) + gm * gn * gk * GRID_STEP_OVERHEAD_S) * 1e6


def _heuristic_tiles(key, kernel) -> Tuple[int, int, int]:
    """The exact tiles core/plan.py's fallback path would pick."""
    from repro.kernels import ops

    bm, bn, bk = ops.auto_tiles(key.m, key.k, None, None, n=key.n, bn=None)
    if kernel in INT8_TILE_KERNELS:
        bm = ops._int8_bm(bm)
    return bm, bn, bk


def tile_candidates(key, kernel: str, hw: Optional[HardwareModel] = None) -> list:
    """Legality-filtered, roofline-ranked (bm, bn, bk) candidates.

    Returns at most :data:`MAX_CANDIDATES` triples, best predicted first.
    The ``auto_tiles`` heuristic answer is always included, so a tuner
    that measures this list can never regress below the default. For
    pure-``jnp`` routes the model knows tiles are inert and returns just
    the heuristic.
    """
    from repro.kernels import ops

    heur = _heuristic_tiles(key, kernel)
    if kernel in JNP_KERNELS or key.backend == "jnp":
        return [heur]
    hw = hw or hardware_model()
    int8 = kernel in INT8_TILE_KERNELS
    m, k, n = key.m, key.k, key.n
    passes = max(1, key.a_bits) * max(1, key.w_bits)

    bm_lo = 32 if int8 else 8
    bms = _candidate_axis(bm_lo, 512, 8, max(bm_lo, m))
    bns = _candidate_axis(128, 1024, 128, n)
    if kernel in BK_TUNABLE_KERNELS:
        bks = _candidate_axis(128, 1024, 128, k)
    else:
        bks = [heur[2]]  # fused: the pack block IS the K tile

    scored = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                if not ops.tiles_legal(
                    bm,
                    bn,
                    bk,
                    int8=int8,
                    vmem_bytes=_vmem_bytes(kernel, bm, bn, bk, key.a_bits, key.w_bits),
                    k=k,
                ):
                    continue
                scored.append(
                    (_predict_us(hw, m, k, n, passes, bm, bn, bk), (bm, bn, bk))
                )
    scored.sort(key=lambda t: (t[0], t[1]))
    out = [heur]
    for _, tiles in scored:
        if tiles not in out:
            out.append(tiles)
        if len(out) >= MAX_CANDIDATES:
            break
    return out


# ---------------------------------------------------------------------------
# The tuner


class PlanAutotuner:
    """Tile tuner `PlanRegistry` consults before falling back to auto_tiles.

    ``store`` is duck-typed (``get(fingerprint, key_id)`` /
    ``put(fingerprint, key_id, record)``) so core never imports runtime;
    pass a ``repro.runtime.plan_store.PlanStore`` from the serving layer.
    Counters: ``store_hits`` (plan served from the store), ``store_misses``
    (no usable record), ``tunes`` (micro-benchmark runs performed).
    """

    def __init__(
        self,
        store=None,
        *,
        fingerprint: Optional[str] = None,
        hw: Optional[HardwareModel] = None,
        tune_on_miss: bool = True,
        measure: Optional[Callable] = None,
        repeats: int = 2,
    ) -> None:
        self.store = store
        self.fingerprint = fingerprint or host_fingerprint()
        self.hw = hw or hardware_model()
        self.tune_on_miss = tune_on_miss
        self._measure = measure or _measure_tiles
        self.repeats = repeats
        self.store_hits = 0
        self.store_misses = 0
        self.tunes = 0
        self._memo: dict = {}

    # -- observability ----------------------------------------------------
    def stats(self) -> dict:
        return {
            "store_hits": self.store_hits,
            "store_misses": self.store_misses,
            "tunes": self.tunes,
            "fingerprint": self.fingerprint,
            "hardware": self.hw.name,
            "hardware_source": self.hw.source,
        }

    # -- the PlanRegistry hook --------------------------------------------
    def tiles_for(self, key, kernel: str) -> Optional[Tuple[int, int, int]]:
        """Tiles for ``(key, kernel)`` or None to fall back to auto_tiles.

        Store hit -> validate legality (a stale or hand-edited record must
        degrade, never crash) and return. Miss -> tune the pruned
        candidate list if ``tune_on_miss``, persist the winner, return it.
        """
        from repro.kernels import ops

        key_id = plan_key_id(key)
        if key_id in self._memo:
            return self._memo[key_id]
        record = self.store.get(self.fingerprint, key_id) if self.store else None
        if record is not None:
            tiles = _record_tiles(record)
            if tiles is not None and ops.tiles_legal(
                *tiles, int8=kernel in INT8_TILE_KERNELS, k=key.k
            ):
                self.store_hits += 1
                self._memo[key_id] = tiles
                return tiles
            record = None  # illegal/corrupt record: treat as a miss
        self.store_misses += 1
        if not self.tune_on_miss:
            return None
        tiles, detail = self.tune(key, kernel)
        self.tunes += 1
        if self.store is not None:
            self.store.put(
                self.fingerprint,
                key_id,
                {"bm": tiles[0], "bn": tiles[1], "bk": tiles[2],
                 "kernel": kernel, **detail},
            )
        self._memo[key_id] = tiles
        return tiles

    def tune(self, key, kernel: str) -> Tuple[Tuple[int, int, int], dict]:
        """Micro-benchmark the pruned candidates; return (winner, detail)."""
        cands = tile_candidates(key, kernel, self.hw)
        if len(cands) == 1:
            # Single survivor (jnp route or fully-pruned space): nothing
            # to measure — the heuristic is the winner by construction.
            return cands[0], {"candidates": 1, "source": "heuristic"}
        best, best_us = cands[0], math.inf
        walls = {}
        for tiles in cands:
            wall = self._measure(key, kernel, tiles, repeats=self.repeats)
            walls["x".join(map(str, tiles))] = round(wall, 2)
            if wall < best_us:
                best, best_us = tiles, wall
        return best, {
            "candidates": len(cands),
            "source": "measured",
            "wall_us": walls,
        }


def _record_tiles(record) -> Optional[Tuple[int, int, int]]:
    if not isinstance(record, dict):
        return None
    try:
        tiles = (int(record["bm"]), int(record["bn"]), int(record["bk"]))
    except (KeyError, TypeError, ValueError):
        return None
    return tiles if all(t > 0 for t in tiles) else None


def _measure_tiles(key, kernel: str, tiles, repeats: int = 2) -> float:
    """Default micro-benchmark: one real plane matmul at these tiles, us.

    Synthetic int8 operands at the key's shape, decomposed with the key's
    variant, run through the packed plane kernel (the tile-sensitive
    route every cached plan shares). Best-of-``repeats`` wall time.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bitplanes as bp
    from repro.kernels import ops

    bm, bn, bk = tiles
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-8, 8, size=(key.m, key.k), dtype=np.int8))
    w = jnp.asarray(rng.integers(-8, 8, size=(key.k, key.n), dtype=np.int8))
    decomp = bp.to_bitplanes if key.level == "bitplane" else bp.to_digits
    dec_a = decomp(a, key.a_bits, key.variant)
    dec_w = decomp(w, key.w_bits, key.variant)
    pair_w = ops._pair_weights(dec_a.weights, dec_w.weights)
    ternary = key.variant == "booth"
    pa = bp.pack_planes(dec_a.planes, axis=-1, ternary=ternary)
    pwk = bp.pack_planes(dec_w.planes, axis=-2, ternary=ternary)
    backend = ops.resolve_backend(key.backend)

    def run():
        out = ops.plane_matmul_packed(
            pa, pwk, pair_w, backend=backend, bm=bm, bn=bn, bk=bk
        )
        return out.block_until_ready() if hasattr(out, "block_until_ready") else out

    run()  # compile / warm
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6
