"""Serving bench: continuous batching + int8 KV vs the lockstep baseline.

Drives the mixed-length / staggered-arrival scenario the lockstep engine
cannot express natively: prompts of several lengths arrive a few decode
steps apart, the continuous-batching engine admits them into free slots
mid-flight, and the lockstep baseline serves the same requests as
per-request batch-1 runs (its only exact option for mixed lengths).

Reports, into the ``serving`` section of BENCH_kernel.json:

* decode throughput (tok/s) for continuous batching (int8 and bf16 KV)
  vs the lockstep baseline on this host;
* measured KV-cache bytes at bf16 vs int8 (+ the full-config per-token
  accounting — the TPU HBM-traffic win, 1.94x at head_dim 128);
* a ``parity`` verdict: continuous batching with ``--no-kv-quant``
  semantics must reproduce every lockstep request bit for bit — the
  invariant the CI regression gate fails the build on;
* a ``precision_sweep`` column: decode tok/s at 8/6/4-bit from ONE 8-bit
  weight decomposition (``set_precision`` plane-prefix truncation — the
  paper's runtime reconfiguration as a serving feature), with a gated
  verdict that zero weight re-quantization/decomposition ran during the
  sweep and every dialed plan resolved to a cache-consuming route;
* a ``sparsity_sweep`` section (ISSUE 5): decode tok/s with occupancy
  sparsity off / gate / compact, Booth bitplane, at full-width (8-bit)
  and narrow-checkpoint (4-bit values in the 8-bit cache) weights —
  compaction drops the identically-zero high Booth planes the narrow
  values sign-extend into, shrinking the plane-pair grid on every
  backend; gating needs the Pallas kernels' predicated MXU passes, so on
  this jnp host it is a parity column, not a wall-clock one. Tokens must
  match dense bit for bit (hard CI gate) and compact-vs-dense at the
  narrow width is floor-checked (``check_bench_regression
  --sparsity-floor``).

* an ``integrity`` section (ISSUE 6): decode tok/s with ABFT + audits on
  (``detect``) vs off, token parity between the two, and a seeded
  fault-injection run against a ``scrub`` engine that must detect every
  flipped bit and recover bit-identical tokens. Overhead is gated by
  ``check_bench_regression --integrity-ceiling``; the verdicts ride the
  hard parity gate.

* an ``autopilot`` section (ISSUE 7): a scripted overload ramp served by
  a static 8-bit engine vs the SLA-autopilot engine. The autopilot must
  hold the configured p99 queue-step SLA that the static baseline
  demonstrably exceeds, by descending precision tiers and shedding only
  past the lowest tier; every finished request must match a single-tier
  run of its admission tier bit for bit (never-degraded traffic ==
  static 8-bit run exactly). ``check_bench_regression`` hard-fails on
  the SLA and parity verdicts.

* a ``tp_serving`` section (PR 8): continuous-batching decode through the
  tensor-parallel packed-plane path at model_parallel = 1/2/4 on virtual
  CPU devices — decode tok/s (smoke signal only on one physical CPU),
  per-device plane-cache bytes (must shrink ~1/model_parallel, gated by
  ``check_bench_regression --tp-shrink-slack``), and token parity
  against the single-device oracle (hard CI gate).

* a ``paged_serving`` section (PR 9): the paged-KV engine — block-table
  indirection, chunked prefill, copy-on-write shared-prefix reuse — on a
  slot-churn ramp where 80% of the prompts open on one shared prefix.
  Token parity vs the dense engine (chunked AND monolithic prefill) is a
  hard CI gate; peak resident KV bytes must sit below the dense
  residency by ``check_bench_regression --kv-shrink-floor``; decode
  inter-token p99 (per-iteration wall incl. prefill work) contrasts
  chunked against monolithic prefill stalls.

CLI: ``python benchmarks/serving_bench.py [--smoke] [--json PATH]
[--precision-sweep] [--sparsity-sweep] [--integrity-sweep]
[--autopilot-sweep] [--tp-sweep] [--paged-sweep]`` (each sweep alone).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

# The tp_serving sweep needs 8 virtual CPU devices: the CPU entry points
# (this script's main, kernel_bench.py, run.py) set XLA_FLAGS before jax
# initializes; importing the module leaves it alone.
VIRTUAL_CPU_DEVICES = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import bitplanes as bp
from repro.core import plan as plan_mod
from repro.core.precision import PrecisionPolicy
from repro.launch.serve import ContinuousBatchingEngine, Engine
from repro.models import quant
from repro.models.transformer import init_params
from repro.runtime.faults import FaultInjector
from repro.runtime.scheduler import Request

ARCH = "granite-3-8b"


def _lockstep_baseline(cfg, params, policy, requests, gen):
    """Serve the mixed-length workload the only way the lockstep engine
    can do it exactly: one batch-1 run per request, back to back. Engines
    are built and warmed outside the timed region (a new Engine closure
    re-jits; the CB side is likewise measured warm)."""
    engines = {
        req.rid: Engine(cfg, params, policy, max_len=req.tokens.size + gen)
        for req in requests
    }
    for req in requests:  # warm: compile prefill + decode per length
        engines[req.rid].generate(jnp.asarray(req.tokens)[None, :], gen)
    outputs = {}
    t0 = time.time()
    for req in requests:
        toks, _ = engines[req.rid].generate(jnp.asarray(req.tokens)[None, :], gen)
        outputs[req.rid] = np.asarray(toks[0])
    wall = max(time.time() - t0, 1e-9)
    total = gen * len(requests)
    return outputs, total / wall


def precision_sweep(cfg, params, smoke: bool = False) -> dict:
    """Decode tok/s at 8/6/4 bits from one 8-bit bitplane decomposition.

    The engine is built (weights quantized + decomposed) once; each tier
    is just ``set_precision`` — a plan swap. A wrapped
    ``decompose_linear_weight`` proves no weight re-decomposition runs
    during the sweep, and the plan registry is audited to show every
    dialed matmul resolved to a truncated-cache route (the "no
    re-quantization step in the trace" acceptance criterion).
    """
    policy = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    if smoke:
        lens, gen, n_slots = [4, 8], 6, 2
    else:
        lens, gen, n_slots = [8, 8, 16, 16], 16, 4
    engine = ContinuousBatchingEngine(
        cfg, params, policy, n_slots=n_slots, max_len=max(lens) + gen
    )

    def requests():
        rng = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (s,)),
                    max_new_tokens=gen, arrival_step=0)
            for i, s in enumerate(lens)
        ]

    decompose_calls = {"n": 0}
    real_decompose = quant.decompose_linear_weight

    def counting(*a, **kw):
        decompose_calls["n"] += 1
        return real_decompose(*a, **kw)

    tok_per_s = {}
    quant.decompose_linear_weight = counting
    try:
        for bits in (8, 6, 4):
            engine.set_precision(None if bits == 8 else bits)
            engine.run(requests())  # warm: compile this tier's steps
            _, stats = engine.run(requests())
            tok_per_s[f"w{bits}a{bits}"] = round(stats["tok_per_s"], 2)
    finally:
        quant.decompose_linear_weight = real_decompose

    # Registry audit: every plan resolved at a dialed width must consume
    # the stored decomposition (truncation), never requantize the weight.
    audit = plan_mod.truncation_audit()
    truncated_ok = decompose_calls["n"] == 0 and audit["truncated_ok"]
    return {
        "workload": {"prompt_lens": lens, "gen": gen, "n_slots": n_slots},
        "stored_bits": 8,
        "tok_per_s": tok_per_s,
        "speedup_4_vs_8": round(tok_per_s["w4a4"] / tok_per_s["w8a8"], 2),
        "speedup_6_vs_8": round(tok_per_s["w6a6"] / tok_per_s["w8a8"], 2),
        "requantize_calls_during_sweep": decompose_calls["n"],
        "truncated_plan_routes": audit["routes"],
        "verdict": "ok" if truncated_ok else "requantized",
    }


def sparsity_sweep(cfg, params, smoke: bool = False) -> dict:
    """Decode tok/s with sparsity off/gate/compact at two effective widths.

    ``w8``: weights quantized at the full 8-bit storage width — every
    plane is occupied somewhere, compaction finds nothing to drop, and the
    tier doubles as a no-regression check. ``w4eff``: the narrow-checkpoint
    deployment (``value_bits=4`` — 4-bit values served from the uniform
    8-bit plane cache): Booth digits of sign-extended narrow integers are
    identically zero above bit 4, so compaction halves the weight-plane
    set and the plane-pair grid with it. Tokens must be bit-identical to
    dense in every cell (the ``parity`` dict CI hard-fails on); the
    compact-vs-dense ratio at w4eff is the ``--sparsity-floor`` gate.
    """
    if smoke:
        lens, gen, n_slots = [4, 8], 6, 2
    else:
        lens, gen, n_slots = [8, 8, 16, 16], 16, 4

    def requests():
        rng = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (s,)),
                    max_new_tokens=gen, arrival_step=0)
            for i, s in enumerate(lens)
        ]

    tok_per_s, planes_kept, tokens = {}, {}, {}
    for tier, value_bits in (("w8", None), ("w4eff", 4)):
        for sparsity in ("off", "gate", "compact"):
            policy = PrecisionPolicy.uniform(
                8, 8, variant="booth", level="bitplane", sparsity=sparsity
            )
            engine = ContinuousBatchingEngine(
                cfg, params, policy, n_slots=n_slots, max_len=max(lens) + gen,
                value_bits=value_bits,
            )
            engine.run(requests())  # warm: compile this tier's steps
            # best-of-2: identical warm runs swing ~1.5x on shared hosts;
            # the max is the least-interfered sample of the same work
            best = 0.0
            for _ in range(2):
                res, stats = engine.run(requests())
                best = max(best, stats["tok_per_s"])
            tok_per_s[f"{tier}_{sparsity}"] = round(best, 2)
            tokens[(tier, sparsity)] = res
            counts = {
                len(leaf.weights)
                for leaf in jax.tree_util.tree_leaves(
                    engine.q_params,
                    is_leaf=lambda x: isinstance(x, bp.WeightPlanes),
                )
                if isinstance(leaf, bp.WeightPlanes)
            }
            planes_kept[f"{tier}_{sparsity}"] = sorted(counts)

    parity = {}
    for tier in ("w8", "w4eff"):
        ok = "ok"
        for sparsity in ("gate", "compact"):
            for rid, want in tokens[(tier, "off")].items():
                if not np.array_equal(tokens[(tier, sparsity)][rid], want):
                    ok = "mismatch"
        parity[f"sparsity_tokens_{tier}"] = ok

    return {
        "workload": {"prompt_lens": lens, "gen": gen, "n_slots": n_slots},
        "variant": "booth",
        "stored_bits": 8,
        "tok_per_s": tok_per_s,
        "planes_kept": planes_kept,
        "speedup_compact_vs_dense_4bit": round(
            tok_per_s["w4eff_compact"] / tok_per_s["w4eff_off"], 2
        ),
        "speedup_compact_vs_dense_8bit": round(
            tok_per_s["w8_compact"] / tok_per_s["w8_off"], 2
        ),
        "parity": parity,
        "note": (
            "w4eff = 4-bit weight values served from the 8-bit plane cache "
            "(narrow checkpoint); compact drops the identically-zero high "
            "Booth planes. gate only skips MXU passes inside the Pallas "
            "kernels, so on a jnp host its wall-clock matches 'off' and "
            "only the parity column is meaningful"
        ),
    }


def integrity_sweep(cfg, params, smoke: bool = False) -> dict:
    """ABFT/checksum serving cost + injected-SEU detection and recovery.

    Three verdicts, all hard-gated in CI (``parity`` dict +
    ``check_bench_regression --integrity-ceiling``):

    * ``detect`` overhead: decode tok/s with per-matmul ABFT row-sum
      checks, per-iteration params audits and KV slot checksums, vs the
      same engine with integrity off. Acceptance: within the CI ceiling
      (default 1.15x).
    * token parity: the detect engine must emit bit-identical tokens to
      the unchecked engine (checks are read-only).
    * fault run: a seeded :class:`FaultInjector` flips one weight-plane
      bit and one KV bit mid-serving against a ``scrub`` engine; every
      flip must be detected AND the output tokens must still match the
      fault-free run bit for bit (scrub-and-retry recovery).
    """
    if smoke:
        lens, gen, n_slots = [4, 8], 6, 2
    else:
        lens, gen, n_slots = [8, 8, 16, 16], 16, 4

    def requests():
        rng = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (s,)),
                    max_new_tokens=gen, arrival_step=0)
            for i, s in enumerate(lens)
        ]

    # Audits (full-params fingerprint + KV slot checksums) amortize over
    # iterations in production; ABFT stays per-matmul. The fault-run
    # engine below keeps audit_interval=1 for tightest detection latency.
    audit_interval = 4
    tok_per_s, tokens = {}, {}
    detect_stats: dict = {}
    for mode in ("off", "detect"):
        policy = PrecisionPolicy.uniform(
            8, 8, variant="booth", level="bitplane", integrity=mode
        )
        engine = ContinuousBatchingEngine(
            cfg, params, policy, n_slots=n_slots, max_len=max(lens) + gen,
            audit_interval=audit_interval,
        )
        engine.run(requests())  # warm: compile this mode's steps
        best, res = 0.0, {}
        for _ in range(2):
            res, stats = engine.run(requests())
            best = max(best, stats["tok_per_s"])
            if mode == "detect":
                detect_stats = stats.get("integrity", {})
        tok_per_s[mode] = round(best, 2)
        tokens[mode] = res

    # Injected-fault run: scrub engine, one plane flip + one KV flip at
    # seed-fixed iterations. Same greedy workload, so recovery == the
    # fault-free tokens, bit for bit.
    spec = "planes@2,kv@3;seed=7"
    policy = PrecisionPolicy.uniform(
        8, 8, variant="booth", level="bitplane", integrity="scrub"
    )
    engine = ContinuousBatchingEngine(
        cfg, params, policy, n_slots=n_slots, max_len=max(lens) + gen
    )
    engine.run(requests())  # warm
    injector = FaultInjector(spec)
    res_f, stats_f = engine.run(requests(), injector=injector)
    recovered = all(
        np.array_equal(res_f.get(rid), want) for rid, want in tokens["off"].items()
    )
    detect_parity = all(
        np.array_equal(tokens["detect"].get(rid), want)
        for rid, want in tokens["off"].items()
    )

    parity = {
        "integrity_tokens_detect_vs_off": "ok" if detect_parity else "mismatch",
        "fault_detection": (
            "ok" if injector.events and not injector.undetected else "missed"
        ),
        "fault_recovery_tokens": "ok" if recovered else "mismatch",
    }
    return {
        "workload": {"prompt_lens": lens, "gen": gen, "n_slots": n_slots},
        "variant": "booth",
        "audit_interval": audit_interval,
        "tok_per_s": tok_per_s,
        "overhead_detect_vs_off_x": round(
            tok_per_s["off"] / max(tok_per_s["detect"], 1e-9), 3
        ),
        "detect_stats": {
            k: detect_stats[k]
            for k in ("abft_checks", "abft_alarms", "audits", "audit_alarms",
                      "kv_checks", "kv_alarms")
            if k in detect_stats
        },
        "fault_run": {
            "spec": spec,
            "injected": len(injector.events),
            "detected": len(injector.events) - len(injector.undetected),
            "scrubs": stats_f.get("integrity", {}).get("scrubs", 0),
            "step_retries": stats_f.get("integrity", {}).get("step_retries", 0),
        },
        "parity": parity,
        "note": (
            "detect = per-matmul ABFT row-sum checks + per-iteration params "
            "fingerprint audit + per-slot KV checksums, all inside the "
            "serving loop; fault run injects one weight-plane bit flip and "
            "one KV bit flip (seeded) against a scrub engine and requires "
            "100% detection plus bit-identical recovered tokens"
        ),
    }


def autopilot_sweep(cfg, params, smoke: bool = False) -> dict:
    """Scripted overload ramp: static 8-bit vs the SLA autopilot engine.

    The workload oversubscribes the slot array (``n_req >> n_slots``
    arriving within a few steps), so a static 8-bit engine queues the
    tail far past the SLA. The autopilot engine under the same ramp must
    hold p99 queue-wait within ``sla_queue_steps`` by descending
    precision tiers and, only past the lowest tier, shedding the queue
    tail (DESIGN.md §10). Three hard verdicts ride the CI parity gate:

    * ``autopilot_sla`` / ``static_overload``: the autopilot holds the
      SLA the static baseline demonstrably exceeds (if the ramp stops
      overloading the static engine the check is vacuous — that fails
      too);
    * ``undegraded_tokens_vs_static``: requests admitted at the widest
      tier must emit tokens bit-identical to the static 8-bit run —
      mixed-tier decode is invisible to never-degraded traffic;
    * ``degraded_tokens_vs_single_tier``: requests admitted at a lower
      tier must match a single-tier run of that tier bit for bit — the
      per-slot tier contract, not an approximation;
    * ``shed_only_at_lowest``: every shed reason names the lowest tier
      (the ladder is exhausted before any request is dropped).
    """
    from repro.runtime.autopilot import AutopilotPolicy

    policy = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    if smoke:
        plen, gen, n_slots, n_req, sla = 4, 5, 2, 8, 6
    else:
        plen, gen, n_slots, n_req, sla = 8, 8, 2, 12, 8
    ap_policy = AutopilotPolicy(
        sla_queue_steps=sla,
        degrade_patience=2,
        upgrade_patience=4,
        cooldown_steps=2,
        shadow_frac=0.5,
    )

    def requests():
        rng = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (plen,)),
                    max_new_tokens=gen, arrival_step=i // n_slots)
            for i in range(n_req)
        ]

    kw = dict(n_slots=n_slots, max_len=plen + gen)
    ap_engine = ContinuousBatchingEngine(
        cfg, params, policy, autopilot=ap_policy, **kw
    )
    ap_engine.run(requests())  # warm: compiles every tier it descends through
    ap_res, ap_stats = ap_engine.run(requests())
    apst = ap_stats["autopilot"]

    static = ContinuousBatchingEngine(cfg, params, policy, **kw)
    static.run(requests())  # warm
    st_res, st_stats = static.run(requests())

    # Per-tier contract parity: each finished request must match a
    # single-tier run of its admission tier, bit for bit. Tier w8a8
    # reuses the measured static run (same engine, same compiled steps).
    tier_runs = {"w8a8": st_res}
    lowest_w = min(w for _, w in ap_engine._tiers)
    parity = {"undegraded_tokens_vs_static": "ok",
              "degraded_tokens_vs_single_tier": "ok"}
    for rid_s, tier_name in sorted(apst["request_tiers"].items()):
        rid = int(rid_s)
        if tier_name not in tier_runs:
            w = int(tier_name.split("a")[0][1:])
            static.set_precision(None if w == 8 else w)
            tier_runs[tier_name], _ = static.run(requests())
        want = tier_runs[tier_name].get(rid)
        got = ap_res.get(rid)
        if got is None or want is None or not np.array_equal(got, want):
            key = ("undegraded_tokens_vs_static" if tier_name == "w8a8"
                   else "degraded_tokens_vs_single_tier")
            parity[key] = "mismatch"

    shed_reasons = [
        r for r in ap_stats["failed"].values() if r.startswith("overload:")
    ]
    parity["shed_only_at_lowest"] = (
        "ok" if all(f"tier w{lowest_w}" in r for r in shed_reasons)
        else "mismatch"
    )
    ap_p99 = apst["p99_queue_steps"]
    st_p99 = st_stats["p99_queue_steps"]
    parity["autopilot_sla"] = "ok" if ap_p99 <= sla else "violated"
    parity["static_overload"] = "ok" if st_p99 > sla else "vacuous"

    total_toks = max(sum(apst["tier_tokens"].values()), 1)
    return {
        "workload": {
            "prompt_len": plen, "gen": gen, "n_slots": n_slots,
            "n_requests": n_req, "arrival": "i // n_slots",
        },
        "sla_queue_steps": sla,
        "tok_per_s": {
            "static_w8": round(st_stats["tok_per_s"], 2),
            "autopilot": round(ap_stats["tok_per_s"], 2),
        },
        "p99_queue_steps": {
            "static_w8": round(st_p99, 2),
            "autopilot": round(ap_p99, 2),
        },
        "shed": apst["shed"],
        "switches": [[s, list(t), r] for s, t, r in apst["switches"]],
        "tier_token_frac": {
            name: round(n / total_toks, 3)
            for name, n in sorted(apst["tier_tokens"].items())
        },
        "shadow": {
            "probes": apst["shadow_probes"],
            "kl_ewma": (
                None if apst["shadow_kl_ewma"] is None
                else round(apst["shadow_kl_ewma"], 5)
            ),
        },
        "parity": parity,
        "note": (
            "same burst workload through a static 8-bit engine and the "
            "autopilot engine; the autopilot descends the tier ladder "
            "under queue pressure and sheds the deadline-hopeless tail "
            "only past the lowest tier. Parity compares each finished "
            "request against a single-tier run of its admission tier "
            "(the per-request tier contract)"
        ),
    }


def tp_serving_sweep(cfg, params, smoke: bool = False) -> dict:
    """Tensor-parallel packed-plane serving (DESIGN.md §11): decode tok/s
    and per-device plane-cache bytes at model_parallel = 1/2/4, with the
    model=1 run as the token-parity oracle.

    Runs on virtual CPU devices in CI (``XLA_FLAGS=--xla_force_host_
    platform_device_count=8``), so the wall-clock columns are smoke
    signals only — sharding one physical CPU across 8 virtual devices
    speeds nothing up. The content the gate consumes is (a) the parity
    dict (sharded tokens must equal the single-device oracle bit for
    bit, hard CI fail) and (b) the per-device plane-cache footprint,
    which must shrink ~1/model_parallel (``check_bench_regression
    --tp-shrink-slack``).

    The sweep builds its own config/params: the stock reduced config has
    2 KV heads (indivisible at model=4), so n_kv_heads is bumped to 4 and
    the SAME modified config serves every model_parallel *including* the
    oracle — apples to apples.
    """
    n_dev = len(jax.devices())
    meshes = [p for p in (1, 2, 4) if p <= n_dev]
    if len(meshes) < 3:
        return {
            "skipped": (
                f"needs 4 devices for model_parallel=4, found {n_dev} — "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "(the CPU bench entry points set it by default when unset)"
            ),
        }
    tcfg = dataclasses.replace(cfg, n_kv_heads=4)
    tparams = init_params(tcfg, jax.random.PRNGKey(0))
    policy = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    if smoke:
        lens, gen, n_slots, stagger = [4, 8, 6], 4, 2, 1
    else:
        lens, gen, n_slots, stagger = [8, 32, 16, 64], 12, 2, 2

    def requests():
        rng = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=rng.integers(0, tcfg.vocab_size, (s,)),
                    max_new_tokens=gen, arrival_step=i * stagger)
            for i, s in enumerate(lens)
        ]

    tok_per_s, plane_bytes, results = {}, {}, {}
    for mp in meshes:
        engine = ContinuousBatchingEngine(
            tcfg, tparams, policy, n_slots=n_slots, max_len=max(lens) + gen,
            model_parallel=mp,
        )
        engine.run(requests())  # warm: compile the sharded prefill + decode
        res, stats = engine.run(requests())
        tok_per_s[f"model{mp}"] = round(stats["tok_per_s"], 2)
        plane_bytes[f"model{mp}"] = engine.plane_cache_bytes_per_device()
        results[mp] = {rid: np.asarray(t) for rid, t in res.items()}

    parity = {}
    for mp in meshes[1:]:
        ok = sorted(results[mp]) == sorted(results[1]) and all(
            np.array_equal(results[mp][rid], results[1][rid])
            for rid in results[1]
        )
        parity[f"tp{mp}_tokens_vs_single_device"] = "ok" if ok else "mismatch"

    base_bytes = plane_bytes["model1"]
    return {
        "workload": {
            "prompt_lens": lens,
            "gen": gen,
            "n_slots": n_slots,
            "arrival_stagger_steps": stagger,
            "n_kv_heads": tcfg.n_kv_heads,
        },
        "model_parallel": meshes,
        "tok_per_s": tok_per_s,
        "plane_cache_bytes_per_device": plane_bytes,
        "shrink_x": {
            f"model{mp}": round(base_bytes / plane_bytes[f"model{mp}"], 3)
            for mp in meshes[1:]
        },
        "parity": parity,
        "note": (
            "virtual CPU devices: tok/s columns are smoke signals, not "
            "speedups; the gated content is token parity vs the model=1 "
            "oracle and the ~1/P per-device plane-cache footprint "
            "(col-parallel q/k/v/gate/up, row-parallel o/down, "
            "vocab-parallel lm_head)"
        ),
    }


def paged_serving_sweep(cfg, params, smoke: bool = False) -> dict:
    """Paged KV serving (DESIGN.md §12): residency, decode p99, parity.

    High-slot-churn workload where 80% of the prompts open on one shared
    system prefix, served three ways from the same request stream:

    * the **dense** engine — the token-parity oracle, whose cache
      residency is ``n_slots * max_len`` positions no matter what the
      prompts look like;
    * the **paged** engine with chunked prefill + CoW prefix sharing —
      the shipping configuration. Its ``kv_bytes_resident_peak`` (pages
      ever live at once x per-page bytes) must sit below the dense
      residency by ``check_bench_regression --kv-shrink-floor``;
    * the paged engine with **monolithic** prefill — the decode-p99
      contrast: a full prefill stalls the whole engine iteration, while
      chunked prefill bounds the stall to one chunk, so the chunked
      engine's inter-token p99 under the prefill-heavy ramp stays below
      the monolithic engine's (reported as ``decode_iter_p99_ms``; the
      wall-clock ratio is host-noisy, so the hard CI gates are the two
      token-parity verdicts and the residency floor).
    """
    policy = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    ps = 8
    if smoke:
        n_slots, gen, max_len, n_req = 3, 4, 48, 10
        prefix_len, body_max = 16, 12
    else:
        n_slots, gen, max_len, n_req = 4, 8, 96, 20
        prefix_len, body_max = 32, 24
    shared_n = int(n_req * 0.8)

    def requests():
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, cfg.vocab_size, (prefix_len,))
        body = np.random.default_rng(1)
        reqs = []
        for i in range(n_req):
            shared = i % n_req < shared_n  # first 80% share the prefix
            blen = int(body.integers(4, body_max))
            toks = (
                np.concatenate([prefix, body.integers(0, cfg.vocab_size, (blen,))])
                if shared
                else body.integers(0, cfg.vocab_size, (prefix_len + blen,))
            )
            reqs.append(Request(
                rid=i, tokens=toks, max_new_tokens=gen,
                arrival_step=i,  # tight ramp: prefills land mid-decode
                shared_prefix_len=prefix_len if shared else 0,
            ))
        return reqs

    kw = dict(n_slots=n_slots, max_len=max_len)
    dense = ContinuousBatchingEngine(cfg, params, policy, **kw)
    dense.run(requests())  # warm: compile per-length prefills + decode
    res_dense, st_dense = dense.run(requests())

    chunked = ContinuousBatchingEngine(
        cfg, params, policy, page_size=ps, prefill_chunk=ps,
        share_prefixes=True, **kw,
    )
    chunked.run(requests())  # warm
    res_ch, st_ch = chunked.run(requests())

    mono = ContinuousBatchingEngine(
        cfg, params, policy, page_size=ps, share_prefixes=True, **kw,
    )
    mono.run(requests())  # warm
    res_mono, st_mono = mono.run(requests())

    def same(res):
        return sorted(res) == sorted(res_dense) and all(
            np.array_equal(res[rid], res_dense[rid]) for rid in res_dense
        )

    pg = st_ch["paging"]
    dense_bytes = st_dense["kv_cache_bytes"]
    resident = max(pg["kv_bytes_resident_peak"], 1)
    return {
        "workload": {
            "n_requests": n_req, "gen": gen, "n_slots": n_slots,
            "max_len": max_len, "prefix_len": prefix_len,
            "shared_frac": round(shared_n / n_req, 2),
            "arrival": "i (1-step ramp)",
        },
        "page_size": ps,
        "prefill_chunk": ps,
        "tok_per_s": {
            "dense": round(st_dense["tok_per_s"], 2),
            "paged_chunked": round(st_ch["tok_per_s"], 2),
            "paged_monolithic": round(st_mono["tok_per_s"], 2),
        },
        "decode_iter_p99_ms": {
            "dense_monolithic": round(st_dense["decode_iter_p99_ms"], 2),
            "paged_chunked": round(st_ch["decode_iter_p99_ms"], 2),
            "paged_monolithic": round(st_mono["decode_iter_p99_ms"], 2),
        },
        "kv_bytes": {
            "dense_resident": dense_bytes,
            "paged_resident_peak": pg["kv_bytes_resident_peak"],
            "page_nbytes": pg["page_nbytes"],
            "peak_used_pages": pg["peak_used_pages"],
        },
        "kv_shrink_x": round(dense_bytes / resident, 3),
        "sharing": {
            "shared_prefix_hits": pg["shared_prefix_hits"],
            "prefix_entries": pg["prefix_entries"],
            "prefix_evictions": pg["prefix_evictions"],
        },
        "prefill_chunks": st_ch["prefill_chunks"],
        "parity": {
            "paged_chunked_tokens_vs_dense": "ok" if same(res_ch) else "mismatch",
            "paged_monolithic_tokens_vs_dense": (
                "ok" if same(res_mono) else "mismatch"
            ),
        },
        "note": (
            "kv_shrink_x = dense cache residency / peak paged page bytes "
            "at 80% shared prefixes under slot churn — the "
            "--kv-shrink-floor gate; decode_iter_p99_ms is per-iteration "
            "wall incl. prefill work (inter-token latency), where chunked "
            "prefill bounds the stall a monolithic prefill imposes"
        ),
    }


def tuned_tiles_sweep(cfg, params, smoke: bool = False) -> dict:
    """Tuned-vs-heuristic decode/prefill throughput (ISSUE 10).

    Three phases over the same decode-heavy and prefill-heavy workloads:

    1. **heuristic** — plain engines, tiles from ``auto_tiles``;
    2. **tuned (cold)** — the plan registry is cleared and engines are
       built with ``autotune=True`` against a persistent plan store
       (``REPRO_PLAN_STORE`` or a temp dir), so every plan build consults
       the roofline-pruned tuner and persists its winner;
    3. **tuned (warm)** — the registry is cleared again and a *fresh*
       tuner (zero counters) is attached to the same store, simulating a
       second process start: every consulted plan must be a store hit
       with **zero** tuning runs (the ``warm_start_zero_tune`` parity
       verdict CI hard-fails on).

    Tokens must be bit-identical across all three phases — tiles change
    the MXU pass schedule, never the integer arithmetic — and the
    tuned-vs-heuristic throughput ratios are floor-gated by
    ``check_bench_regression --tuned-floor``. On this jnp host tiles are
    inert (XLA fuses the contraction), so the tuner collapses each plan's
    candidate space to the single heuristic survivor and the ratios
    measure store-plumbing overhead (~1.0x); on a Pallas backend the same
    sweep measures real tile wins.
    """
    import tempfile

    from repro.runtime.plan_store import PlanStore

    policy = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    if smoke:
        n_slots = 2
        workloads = {"decode": ([4, 8], 6), "prefill": ([24, 32], 2)}
    else:
        n_slots = 4
        workloads = {"decode": ([8, 8, 16, 16], 16), "prefill": ([64, 96, 128, 128], 4)}

    store_dir = os.environ.get("REPRO_PLAN_STORE") or tempfile.mkdtemp(
        prefix="plan_store_"
    )
    store_path = os.path.join(store_dir, "plan_store.json")

    def requests(lens, gen):
        rng = np.random.default_rng(0)
        return [
            Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (s,)),
                    max_new_tokens=gen, arrival_step=0)
            for i, s in enumerate(lens)
        ]

    def run_phase(autotune: bool):
        """Build one engine per workload; returns (tokens, tok/s) maps."""
        tokens, tps = {}, {}
        for name, (lens, gen) in workloads.items():
            engine = ContinuousBatchingEngine(
                cfg, params, policy, n_slots=n_slots, max_len=max(lens) + gen,
                autotune=autotune,
                plan_store_path=store_path if autotune else None,
            )
            engine.run(requests(lens, gen))  # warm: compile
            # best-of-3: identical warm runs swing >1.5x on shared hosts
            # and the gated ratio here is expected ~1.0, not a real win
            best = 0.0
            for _ in range(3):
                res, stats = engine.run(requests(lens, gen))
                metric = (
                    stats["tok_per_s"]
                    if name == "decode"
                    else stats["prefill_tokens"] / max(stats["wall_s"], 1e-9)
                )
                best = max(best, metric)
            tokens[name], tps[name] = res, round(best, 2)
        return tokens, tps

    registry = plan_mod.DEFAULT_REGISTRY
    try:
        registry.attach_tuner(None)
        base_tokens, base_tps = run_phase(autotune=False)

        registry.clear()  # every plan must re-resolve through the tuner
        cold_tokens, cold_tps = run_phase(autotune=True)
        cold = dict(registry.store_stats())

        # Second-process simulation: fresh tuner (zero counters), warm store.
        registry.attach_tuner(None)
        registry.clear()
        warm_tokens, warm_tps = run_phase(autotune=True)
        warm = dict(registry.store_stats())
    finally:
        registry.attach_tuner(None)

    token_parity = "ok"
    for name in workloads:
        for phase_tokens in (cold_tokens, warm_tokens):
            for rid, toks in base_tokens[name].items():
                if not np.array_equal(phase_tokens[name][rid], toks):
                    token_parity = "mismatch"

    # Zero tuning runs at warm start, and the store served every lookup
    # the cold phase resolved (hit counter == consulted-plan count).
    consulted = cold["store_hits"] + cold["store_misses"]
    warm_ok = (
        warm["tunes"] == 0
        and warm["store_misses"] == 0
        and warm["store_hits"] == consulted
        and warm["store_hits"] > 0
    )
    tuned_tps = {k: max(cold_tps[k], warm_tps[k]) for k in workloads}
    return {
        "workload": {
            name: {"prompt_lens": lens, "gen": gen, "n_slots": n_slots}
            for name, (lens, gen) in workloads.items()
        },
        "store": {
            "path": store_path,
            "fingerprint": cold.get("fingerprint"),
            "entries": PlanStore(store_path).entries(),
        },
        "hardware": {
            "name": cold.get("hardware"),
            "source": cold.get("hardware_source"),
        },
        "tok_per_s": {
            name: {
                "heuristic": base_tps[name],
                "tuned_cold": cold_tps[name],
                "tuned_warm": warm_tps[name],
            }
            for name in workloads
        },
        "tuned_vs_heuristic": {
            name: round(tuned_tps[name] / max(base_tps[name], 1e-9), 3)
            for name in workloads
        },
        "plan_counters": {"cold": cold, "warm": warm},
        "parity": {
            "tuned_tokens_vs_heuristic": token_parity,
            "warm_start_zero_tune": (
                "ok"
                if warm_ok
                else f"hits_{warm['store_hits']}_misses_{warm['store_misses']}"
                f"_tunes_{warm['tunes']}_expected_hits_{consulted}"
            ),
        },
        "note": (
            "prefill tok/s = prefill_tokens/wall on the prefill-heavy "
            "workload; decode tok/s = engine tok_per_s. best-of-3 per "
            "phase. On the jnp backend tiles are inert, so the ratios "
            "gate store plumbing at ~1.0x; Pallas backends measure real "
            "tile wins here"
        ),
    }


def serving_bench(json_path: str | None = None, smoke: bool = False):
    """Returns report rows; writes the ``serving`` JSON section."""
    from kernel_bench import JSON_PATH, _write_bench_section

    path = json_path or JSON_PATH
    cfg = get_reduced(ARCH)
    policy = PrecisionPolicy.uniform(8, 8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if smoke:
        lens, gen, n_slots, stagger = [4, 8], 4, 2, 1
    else:
        lens, gen, n_slots, stagger = [8, 32, 128], 16, 2, 2
    max_len = max(lens) + gen
    rng = np.random.default_rng(0)
    requests = [
        Request(
            rid=i,
            tokens=rng.integers(0, cfg.vocab_size, (s,)),
            max_new_tokens=gen,
            arrival_step=i * stagger,
        )
        for i, s in enumerate(lens)
    ]

    kw = dict(n_slots=n_slots, max_len=max_len)
    cb_q = ContinuousBatchingEngine(cfg, params, policy, kv_quant=True, **kw)
    cb_x = ContinuousBatchingEngine(cfg, params, policy, kv_quant=False, **kw)
    # warm the jits (per-prompt-length prefill + the decode step), then measure
    res_q, stats_q = cb_q.run(requests)
    res_q, stats_q = cb_q.run(requests)
    res_x, stats_x = cb_x.run(requests)
    res_x, stats_x = cb_x.run(requests)
    base, base_tps = _lockstep_baseline(cfg, params, policy, requests, gen)

    parity = "ok"
    for req in requests:
        if not np.array_equal(res_x[req.rid], base[req.rid]):
            parity = "mismatch"
    first_tok_parity = "ok"
    for req in requests:
        if res_q[req.rid][0] != base[req.rid][0]:
            first_tok_parity = "mismatch"

    sweep = precision_sweep(cfg, params, smoke=smoke)
    sparsity = sparsity_sweep(cfg, params, smoke=smoke)
    integrity = integrity_sweep(cfg, params, smoke=smoke)
    autopilot = autopilot_sweep(cfg, params, smoke=smoke)
    tp_serving = tp_serving_sweep(cfg, params, smoke=smoke)
    paged = paged_serving_sweep(cfg, params, smoke=smoke)
    # last: it clears and re-resolves the process plan registry (tuner
    # attach/detach), which the other sweeps must not see mid-flight
    tuned = tuned_tiles_sweep(cfg, params, smoke=smoke)

    kv_reduction = stats_x["kv_cache_bytes"] / stats_q["kv_cache_bytes"]
    # full-config accounting: the reduced head_dim understates the win
    d, full_d = cfg.head_dim, 128
    analytic = {
        "bf16_bytes_per_pos_head": 2 * 2 * d,
        "int8_bytes_per_pos_head": 2 * (d + 4),
        "reduction_x": round(2 * d / (d + 4), 3),
        "reduction_x_at_head_dim_128": round(2 * full_d / (full_d + 4), 3),
    }

    payload = {
        "bench": "serving",
        "arch": cfg.name,
        "workload": {
            "prompt_lens": lens,
            "gen": gen,
            "n_slots": n_slots,
            "arrival_stagger_steps": stagger,
        },
        "smoke": smoke,
        "tok_per_s": {
            "cb_int8_kv": round(stats_q["tok_per_s"], 2),
            "cb_bf16_kv": round(stats_x["tok_per_s"], 2),
            "lockstep_per_request": round(base_tps, 2),
            "cb_vs_lockstep_x": round(stats_q["tok_per_s"] / base_tps, 2),
        },
        "slot_utilization": round(stats_q["slot_utilization"], 3),
        "kv_bytes": {
            "bf16": stats_x["kv_cache_bytes"],
            "int8": stats_q["kv_cache_bytes"],
            "reduction_x": round(kv_reduction, 3),
            "analytic": analytic,
        },
        "precision_sweep": sweep,
        "parity": {
            "cb_bf16_vs_lockstep_tokens": parity,
            "cb_int8_first_token": first_tok_parity,
            "sweep_uses_truncated_cache": sweep["verdict"],
        },
        "note": (
            "lockstep serves mixed lengths as sequential batch-1 runs (its "
            "only exact option); cb_bf16 must match it bit-for-bit (gated "
            "in CI). kv bytes are measured cache residency at the reduced "
            "config; 'analytic' scales the accounting to production head_dim"
        ),
    }
    _write_bench_section(path, "serving", payload)
    _write_bench_section(
        path, "sparsity_sweep",
        {"bench": "sparsity_sweep", "arch": cfg.name, "smoke": smoke, **sparsity},
    )
    _write_bench_section(
        path, "integrity",
        {"bench": "integrity", "arch": cfg.name, "smoke": smoke, **integrity},
    )
    _write_bench_section(
        path, "autopilot",
        {"bench": "autopilot", "arch": cfg.name, "smoke": smoke, **autopilot},
    )
    _write_bench_section(
        path, "tp_serving",
        {"bench": "tp_serving", "arch": cfg.name, "smoke": smoke, **tp_serving},
    )
    _write_bench_section(
        path, "paged_serving",
        {"bench": "paged_serving", "arch": cfg.name, "smoke": smoke, **paged},
    )
    _write_bench_section(
        path, "tuned_tiles",
        {"bench": "tuned_tiles", "arch": cfg.name, "smoke": smoke, **tuned},
    )
    rows = [
        ("serving/cb_int8_tok_s", payload["tok_per_s"]["cb_int8_kv"],
         f"lockstep_{payload['tok_per_s']['lockstep_per_request']}"),
        ("serving/kv_bytes_reduction_x", payload["kv_bytes"]["reduction_x"],
         f"parity_{parity}"),
        ("serving/precision_sweep_4v8_x", sweep["speedup_4_vs_8"],
         f"truncation_{sweep['verdict']}"),
        ("serving/sparsity_compact_4bit_x", sparsity["speedup_compact_vs_dense_4bit"],
         f"parity_{sparsity['parity']['sparsity_tokens_w4eff']}"),
        ("serving/integrity_detect_overhead_x", integrity["overhead_detect_vs_off_x"],
         f"faults_{integrity['parity']['fault_detection']}"
         f"_recovery_{integrity['parity']['fault_recovery_tokens']}"),
        ("serving/autopilot_p99_queue_steps", autopilot["p99_queue_steps"]["autopilot"],
         f"static_{autopilot['p99_queue_steps']['static_w8']}"
         f"_sla_{autopilot['parity']['autopilot_sla']}"
         f"_shed_{autopilot['shed']}"),
    ]
    if "skipped" in tp_serving:
        rows.append(("serving/tp4_plane_bytes_shrink_x", 0.0, "skipped"))
    else:
        rows.append((
            "serving/tp4_plane_bytes_shrink_x", tp_serving["shrink_x"]["model4"],
            f"parity_{tp_serving['parity']['tp4_tokens_vs_single_device']}",
        ))
    rows.append((
        "serving/paged_kv_shrink_x", paged["kv_shrink_x"],
        f"parity_{paged['parity']['paged_chunked_tokens_vs_dense']}"
        f"_p99_chunked_{paged['decode_iter_p99_ms']['paged_chunked']}"
        f"_mono_{paged['decode_iter_p99_ms']['paged_monolithic']}",
    ))
    rows.append((
        "serving/tuned_vs_heuristic_decode_x",
        tuned["tuned_vs_heuristic"]["decode"],
        f"prefill_{tuned['tuned_vs_heuristic']['prefill']}"
        f"_warmstart_{tuned['parity']['warm_start_zero_tune']}"
        f"_parity_{tuned['parity']['tuned_tokens_vs_heuristic']}",
    ))
    return rows


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", VIRTUAL_CPU_DEVICES)
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--precision-sweep", action="store_true",
                    help="run only the runtime-precision sweep and print it")
    ap.add_argument("--sparsity-sweep", action="store_true",
                    help="run only the occupancy-sparsity sweep and print it")
    ap.add_argument("--integrity-sweep", action="store_true",
                    help="run only the ABFT/fault-injection sweep and print it")
    ap.add_argument("--autopilot-sweep", action="store_true",
                    help="run only the SLA-autopilot overload ramp and print it")
    ap.add_argument("--tp-sweep", action="store_true",
                    help="run only the tensor-parallel serving sweep and "
                    "print it (needs 4+ devices; see XLA_FLAGS note)")
    ap.add_argument("--paged-sweep", action="store_true",
                    help="run only the paged-KV serving sweep (residency, "
                    "decode p99, parity) and print it")
    ap.add_argument("--tuned-sweep", action="store_true",
                    help="run only the autotuner sweep (tuned-vs-heuristic "
                    "throughput, warm-start zero-tune check) and print it")
    args = ap.parse_args()
    if (args.precision_sweep or args.sparsity_sweep or args.integrity_sweep
            or args.autopilot_sweep or args.tp_sweep or args.paged_sweep
            or args.tuned_sweep):
        import json as _json

        cfg = get_reduced(ARCH)
        params = init_params(cfg, jax.random.PRNGKey(0))
        fn = (precision_sweep if args.precision_sweep
              else sparsity_sweep if args.sparsity_sweep
              else integrity_sweep if args.integrity_sweep
              else autopilot_sweep if args.autopilot_sweep
              else paged_serving_sweep if args.paged_sweep
              else tuned_tiles_sweep if args.tuned_sweep
              else tp_serving_sweep)
        print(_json.dumps(fn(cfg, params, smoke=args.smoke), indent=2))
    else:
        for name, val, derived in serving_bench(args.json, smoke=args.smoke):
            print(f"{name},{val},{derived}")
