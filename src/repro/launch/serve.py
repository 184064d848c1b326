"""Serving driver: quantized weights + continuous-batching decode engine.

This is where the paper's technique earns its keep: weights live in
memory at their configured bit-width (quantize_params), activations are
quantized per token at runtime, every projection runs through the
bit-serial matmul at the policy's level/variant — and the KV cache
extends the precision dial to decode state (int8, quantize-on-append).

Two engines share the jitted steps:

* :class:`Engine` — the lockstep baseline: one fixed batch, every row
  prefills and decodes in unison. Kept as the bit-exact parity oracle
  (``--mode lockstep``) and for homogeneous batch benchmarking.
* :class:`ContinuousBatchingEngine` — slot-based serving: requests with
  different prompt lengths and arrival times are admitted into free
  decode slots mid-flight (prefill inserts into a slot while the other
  slots keep decoding) and evicted the step they finish. One jitted
  decode step covers the whole slot array at per-slot lengths; with
  ``kv_quant`` the cache holds int8 KV (2x fewer KV bytes at bf16→int8).

Both engines expose the paper's runtime precision reconfiguration as a
serving feature: :meth:`set_precision` swaps the compiled steps for ones
executing at a lower bit-width *against the same weight tree* — the
stored 8-bit plane decomposition is MSB-prefix truncated by the execution
plans (repro.core.plan), so the switch moves no weight bytes and runs no
re-quantization. In-flight slots keep decoding across the switch (their
KV cache is unchanged); use it to shed precision under queue pressure or
to serve per-tier traffic, e.g.::

    engine.set_precision(4)                 # drop every projection to 4-bit
    engine.run(requests, precision_schedule={12: 4})   # switch at step 12

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --bits 8 --level bitplane --prompt-lens 8,32,128 --gen 16 \
        --precision 8 --precision-switch 8:4
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
import warnings
from collections import deque
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.core import integrity
from repro.core.precision import PrecisionPolicy
from repro.launch import sampling
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (
    jit_step, make_cb_decode_step, make_chunk_prefill_step, make_prefill_step,
    make_serve_step, make_tp_cb_decode_step, make_tp_chunk_prefill_step,
    make_tp_prefill_step,
)
from repro.models import paging
from repro.models.cache import (
    cache_kv_bytes, cache_slot_checksums, init_cache, insert_slot, select_slots,
)
from repro.models.quant import quantize_params
from repro.sharding.tp import TPContext, plane_cache_device_bytes, shard_quantized
from repro.models.transformer import init_params
from repro.runtime.autopilot import Autopilot, AutopilotPolicy
from repro.runtime.faults import FaultInjector, FaultSpec
from repro.runtime.scheduler import HISTORY_LIMIT, Request, SlotScheduler


def _norm_precision(precision) -> Tuple[int, int]:
    """``4`` or ``(a_bits, w_bits)`` -> (a_bits, w_bits)."""
    if isinstance(precision, int):
        return (precision, precision)
    a, w = precision
    return (int(a), int(w))


class _PrecisionDial:
    """Shared set_precision plumbing: one compiled (prefill, step) pair per
    precision tier — subclasses provide ``_make_steps(precision)`` — with
    the dial validated against the policy's storage width."""

    def _init_dial(self) -> None:
        self._precision: Optional[Tuple[int, int]] = None
        self._compiled: dict = {}
        self._bind_steps(self._steps_for(None))

    def _bind_steps(self, steps) -> None:
        # (prefill, step) or, under integrity, (prefill, step, pcol, scol)
        self._prefill, self._step = steps[:2]
        self._prefill_col, self._step_col = (
            steps[2:] if len(steps) > 2 else (None, None)
        )

    def _steps_for(self, precision):
        if precision not in self._compiled:
            self._compiled[precision] = self._make_steps(precision)
        return self._compiled[precision]

    def set_precision(self, precision) -> None:
        """Dial subsequent prefills/decodes to ``precision`` (an int or an
        ``(a_bits, w_bits)`` pair; ``None`` restores the storage width).
        The weight tree is untouched — plans truncate the stored plane
        decomposition — so no weight bytes move, no re-quantization runs,
        and (continuous batching) in-flight slots keep their KV state and
        finish at the new precision from the next step."""
        if precision is None:
            self._precision = None
        else:
            p = _norm_precision(precision)
            self._dial_check(p)
            self._precision = p
        self._bind_steps(self._steps_for(self._precision))

    def _dial_check(self, precision: Tuple[int, int]) -> None:
        pol = self.policy
        stored = pol.storage_width()
        if stored is None:
            raise ValueError("set_precision needs an active quantization policy")
        a, w = precision
        if min(a, w) < 1:
            raise ValueError(f"runtime precision must be >= 1 bit, got {precision}")
        # Only the WEIGHT dial has a hard ceiling (the stored decomposition
        # has no planes above it); activations quantize fresh per token, so
        # an over-wide activation dial is merely clamped by
        # policy.effective() and needs no rejection here.
        if w > stored:
            raise ValueError(
                f"runtime weight precision {w} exceeds the stored width "
                f"{stored} — weights were quantized/decomposed at "
                f"{stored} bits; the dial can only truncate, never extend"
            )
        if pol.level != "bitplane":
            raise ValueError(
                "runtime precision reconfiguration needs level='bitplane' "
                f"(got {pol.level!r}): radix-256 digit caches are not "
                "prefix-truncatable — rebuild the engine with a bitplane "
                "policy"
            )

    @property
    def precision(self) -> Optional[Tuple[int, int]]:
        """Current runtime (a_bits, w_bits) dial, or None (storage width)."""
        return self._precision


class _IntegrityRuntime:
    """Shared fault-detection/recovery plumbing (DESIGN.md §9).

    With ``policy.integrity != "off"`` the engine layers three detectors:
    per-matmul ABFT row-sum checks (alarms harvested from the jitted
    steps via :class:`~repro.core.integrity.Collector`), a whole-tree
    parameter fingerprint audited every ``audit_interval`` iterations
    against the load-time reference, and (continuous batching) per-slot
    KV checksums. In ``scrub`` mode a params alarm triggers recovery:
    the quantized tree is rebuilt from the retained source parameters
    (``quantize_params`` is deterministic, so the rebuild fingerprint
    must equal the load-time reference — if it doesn't, the *source* is
    corrupt and :class:`~repro.core.integrity.IntegrityError` is the
    only honest answer) and the alarmed step re-executes from its
    pre-step inputs, yielding bit-identical tokens.
    """

    def _init_integrity(
        self, params, value_bits, audit_interval: int, max_retries: int
    ) -> None:
        self.integrity = integrity.check_integrity_mode(
            getattr(self.policy, "integrity", "off")
        )
        self.audit_interval = audit_interval
        self.max_retries = max_retries
        self._value_bits = value_bits
        self._scrubs = 0
        self._src_params = None
        self._params_ref = None
        if self.integrity == "off":
            return
        self._fp_fn = jax.jit(integrity.tree_checksum)
        self._params_ref = int(self._fp_fn(self.q_params))
        if self.integrity == "scrub":
            # scrub rebuilds from source: retain the dense tree (the price
            # of recoverability; detect mode skips it)
            self._src_params = params

    def _requantize(self, params):
        """Deterministic source -> serving-tree rebuild. Load time and
        scrub recovery share this one code path, so a scrub rebuild
        reproduces the load-time fingerprint regardless of layout — the
        flat single-device tree or the TP-stacked sharded one
        (DESIGN.md §11)."""
        tp = getattr(self, "tp", None)
        if tp is None:
            return quantize_params(
                params, self.policy,
                plane_cache=self.plane_cache, value_bits=self._value_bits,
            )
        tree, self._tp_specs = shard_quantized(
            params, self.policy, tp,
            plane_cache=self.plane_cache, value_bits=self._value_bits,
        )
        # Commit the stacked tree to the mesh once: every leaf lands
        # shard-resident, so the jitted shard_map steps never re-transfer
        # the plane cache per call.
        from jax.sharding import NamedSharding

        return jax.device_put(
            tree,
            jax.tree_util.tree_map(
                lambda s: NamedSharding(tp.mesh, s), self._tp_specs
            ),
        )

    def _scrub(self) -> None:
        if self._src_params is None:
            raise integrity.IntegrityError(
                "scrub requested but source parameters were not retained "
                "(integrity mode is not 'scrub')"
            )
        self.q_params = self._requantize(self._src_params)
        fp = int(self._fp_fn(self.q_params))
        if fp != self._params_ref:
            raise integrity.IntegrityError(
                "scrub rebuild fingerprint mismatch: the retained source "
                "parameters are themselves corrupt — cannot recover"
            )
        self._scrubs += 1

    def _audit_params(self) -> bool:
        """True if the at-rest parameter fingerprint drifted from the
        load-time reference (in detect mode the reference is re-baselined
        so one upset alarms once, not every audit)."""
        fp = int(self._fp_fn(self.q_params))
        if fp == self._params_ref:
            return False
        if self.integrity == "detect":
            self._params_ref = fp
        return True

    @staticmethod
    def _harvest(col, alarms) -> Tuple[bool, int]:
        """Tally a step's concrete alarm vector; returns (any_bad, n)."""
        res = col.harvest(alarms)
        return any(bad for _, bad in res), len(res)


class _PlanTuning:
    """Shared autotuner wiring for both engines (DESIGN.md §13).

    ``autotune=True`` attaches a roofline-calibrated tile autotuner
    (core/autotune) — optionally backed by a persistent per-host plan
    store (runtime/plan_store) — to the process plan registry at
    construction, i.e. *before* the first trace builds any plan: a
    serving process with a warm store starts at peak with zero tuning
    runs. ``plan_stats`` surfaces the registry hit/miss counters plus the
    tuner's store hit/miss/tune counts for ``stats()`` blocks.
    """

    def _init_autotune(self, autotune: bool, plan_store_path) -> None:
        from repro.core import plan as plan_mod

        self.autotuner = None
        if not autotune:
            if plan_store_path:
                raise ValueError(
                    "plan_store_path requires autotune=True (the store is "
                    "only read/written by the attached tuner)"
                )
            return
        from repro.core.autotune import (
            PlanAutotuner, calibrate_from_bench, hardware_model,
        )
        from repro.runtime.plan_store import PlanStore

        registry = plan_mod.DEFAULT_REGISTRY
        store = PlanStore(plan_store_path) if plan_store_path else None
        current = registry.tuner
        if (
            current is not None
            and store is not None
            and getattr(getattr(current, "store", None), "path", None) == store.path
        ):
            # Engines in one process share the tuner (and its memo):
            # tune-once applies across engine instances too.
            self.autotuner = current
            return
        # Calibrate the pruning model against a bench report only when one
        # is named ($BENCH_KERNEL_JSON; it must come from this backend);
        # the device's table row otherwise.
        bench_path = os.environ.get("BENCH_KERNEL_JSON")
        hw = calibrate_from_bench(bench_path) if bench_path else hardware_model()
        self.autotuner = PlanAutotuner(store=store, hw=hw)
        registry.attach_tuner(self.autotuner)

    def plan_stats(self) -> dict:
        from repro.core import plan as plan_mod

        reg = plan_mod.DEFAULT_REGISTRY
        out = {
            "registry_hits": reg.hits,
            "registry_misses": reg.misses,
            "resolved": len(reg),
        }
        out.update(reg.store_stats())
        return out


class Engine(_PrecisionDial, _IntegrityRuntime, _PlanTuning):
    """Minimal lockstep batched generation engine over the serve steps."""

    def __init__(
        self,
        cfg,
        params,
        policy,
        max_len: int = 256,
        plane_cache: bool = True,
        sample_fn=None,
        seed: int = 0,
        value_bits: Optional[int] = None,
        audit_interval: int = 1,
        max_retries: int = 2,
        autotune: bool = False,
        plan_store_path: Optional[str] = None,
    ):
        self._init_autotune(autotune, plan_store_path)
        self.cfg = cfg
        self.policy = policy
        self.plane_cache = plane_cache
        # Quantize AND pre-decompose/pack the weight planes exactly once at
        # load time (plane_cache) — forwards only decompose activations,
        # and every runtime precision tier truncates this one decomposition.
        # ``value_bits`` serves a narrow checkpoint from the uniform-width
        # cache (quantize_params); with policy.sparsity="compact" the
        # resulting zero planes are dropped here, at load time.
        self._value_bits = value_bits
        self.q_params = (
            self._requantize(params) if policy.default.active else params
        )
        self.sample_fn = sample_fn or sampling.greedy
        self.max_len = max_len
        self._base_key = jax.random.PRNGKey(seed)
        self._init_integrity(params, value_bits, audit_interval, max_retries)
        self._init_dial()

    def _make_steps(self, precision):
        check = self.integrity != "off"
        pcol = integrity.Collector() if check else None
        scol = integrity.Collector() if check else None
        return (
            jit_step(
                make_prefill_step(
                    self.cfg, self.policy, max_len=self.max_len,
                    precision=precision, collector=pcol,
                )
            ),
            jit_step(
                make_serve_step(
                    self.cfg, self.policy, sample_fn=self.sample_fn,
                    precision=precision, collector=scol,
                ),
                # scrub-and-retry re-executes a step from its pre-step
                # cache, so integrity mode must not donate it
                donate_argnums=() if check else (1,),
            ),
            pcol,
            scol,
        )

    def _checked_step(self, cache, tok, key):
        """One decode step with ABFT harvest + bounded scrub-and-retry."""
        for attempt in range(self.max_retries + 1):
            ntok, ncache, alarms = self._step(self.q_params, cache, tok, key)
            bad, _n = self._harvest(self._step_col, alarms)
            if not bad:
                return ntok, ncache, False
            if self.integrity != "scrub":
                return ntok, ncache, True  # detect: record, keep serving
            if attempt < self.max_retries:
                self._scrub()
        raise integrity.IntegrityError(
            f"ABFT alarm persisted through {self.max_retries} "
            "scrub-and-retry attempts — corruption is not in the "
            "scrubbable weight planes"
        )

    def generate(self, prompts: jax.Array, n_tokens: int):
        """prompts: (B, S) int32. Decodes ``n_tokens`` via the engine's
        ``sample_fn`` (greedy default); returns (tokens (B, n),
        decode_tok_per_s)."""
        check = self.integrity != "off"
        alarm_count = 0
        out_pref = self._prefill(self.q_params, {"tokens": prompts})
        if check:
            last_logits, cache, alarms = out_pref
            bad, _ = self._harvest(self._prefill_col, alarms)
            if bad:
                alarm_count += 1
                if self.integrity == "scrub":
                    self._scrub()
                    last_logits, cache, alarms = self._prefill(
                        self.q_params, {"tokens": prompts}
                    )
                    bad, _ = self._harvest(self._prefill_col, alarms)
                    if bad:
                        raise integrity.IntegrityError(
                            "prefill ABFT alarm persisted after scrub"
                        )
        else:
            last_logits, cache = out_pref
        logits = sampling.mask_vocab(last_logits, self.cfg.vocab_size)
        tok = self.sample_fn(logits, jax.random.fold_in(self._base_key, 0))[:, None]
        out = [tok]
        t0 = time.time()
        for i in range(n_tokens - 1):
            key = jax.random.fold_in(self._base_key, i + 1)
            if check and self.audit_interval and i % self.audit_interval == 0:
                if self._audit_params():
                    alarm_count += 1
                    if self.integrity == "scrub":
                        self._scrub()
            if check:
                tok, cache, bad = self._checked_step(cache, tok, key)
                alarm_count += int(bad)
            else:
                tok, cache = self._step(self.q_params, cache, tok, key)
            out.append(tok)
        jax.block_until_ready(tok)
        dt = time.time() - t0
        tokens = jnp.concatenate(out, axis=1)
        tps = prompts.shape[0] * max(n_tokens - 1, 1) / max(dt, 1e-9)
        self.last_alarms = alarm_count
        return tokens, tps


_DEGRADE_ALIAS_WARNED = False


def _degrade_alias_policy(
    degrade_after: Optional[int], degrade_to: int
) -> AutopilotPolicy:
    """PR 6's ``degrade_after``/``degrade_to`` engine kwargs, expressed
    as the autopilot policy they always were: a pure scrub-rate rule (no
    SLA, so depth/latency pressure never fires) that drops to the
    ``degrade_to`` tier once the scrub counter crosses the threshold.
    ``upgrade_patience`` is irrelevant — with no SLA there is no headroom
    signal, and the scrub cap pins the ladder anyway."""
    return AutopilotPolicy(
        scrub_degrade_after=degrade_after,
        scrub_degrade_to=degrade_to,
        shed=False,
    )


@dataclasses.dataclass
class _PrefillJob:
    """One staged prefill in flight (DESIGN.md §12): the request owns a
    reserved slot and a raw bf16 scratch cache that fills in chunks —
    one chunk per engine iteration under ``prefill_chunk``, or run to
    completion at admission (monolithic staging, ``prefill_chunk=0``).
    Commit quantizes the scratch once and installs it (paged scatter or
    dense ``insert_slot``), then the slot starts decoding."""

    slot: int
    req: Request
    steps: tuple  # compiled (chunk_fn, collector) at the admission dial
    tier_index: int
    precision: object  # the admission-time dial (registry tag + commit)
    scratch: object
    bounds: list  # [(a, b), ...] token ranges still to prefill
    next_i: int = 0
    logits: object = None  # last chunk's (1, V) last-token logits
    table: object = None  # paged: (pages_per_slot,) block-table row
    mask: object = None  # paged: owned-page write mask
    snapshot_at: int = -1  # chunk index whose result is the prefix snapshot
    snapshot: object = None
    prefix_tokens: object = None  # register these at commit (miss path)
    prefix_pages: tuple = ()
    from_hit: bool = False  # resumed from a registry snapshot


class ContinuousBatchingEngine(_PrecisionDial, _IntegrityRuntime, _PlanTuning):
    """Slot-scheduled serving over a shared, optionally int8, KV cache.

    ``n_slots`` decode lanes share one slot-indexed cache of ``max_len``
    positions per slot. :meth:`run` drives a :class:`SlotScheduler`:
    each iteration admits pending requests into free slots (per-request
    prefill + :func:`insert_slot` — jit re-specializes per distinct
    prompt length, so prompts are *not* padded and SSM/recurrent state
    stays exact), then executes one jitted decode step over the whole
    slot array. With ``kv_quant`` (default) KV is stored int8 with
    per-(position, head) scales; ``kv_quant=False`` is the bit-exact A/B
    fallback the parity tests and the CI serving gate compare against
    per-request lockstep runs.

    :meth:`set_precision` switches the decode/prefill steps to a lower
    bit-width mid-serving (plane-prefix truncation of the same weight
    tree); in-flight slots continue decoding across the switch. A
    ``precision_schedule`` on :meth:`run` automates the switch at given
    decode steps — the drop-8-to-4-under-pressure pattern.

    ``autopilot`` (an :class:`~repro.runtime.autopilot.AutopilotPolicy`)
    closes the loop instead: a per-run controller watches queue depth,
    per-token decode latency, the scrub counter and a shadow-KL quality
    probe, and moves the *admission* tier down/up the precision ladder
    with hysteresis, escalating to load shedding past the lowest tier.
    In-flight requests keep the tier they were admitted at — the engine
    groups active slots by tier and runs one plane-prefix decode pass
    per tier against the shared packed weights, merging per-slot
    (mixed-tier decode, DESIGN.md §10).
    """

    def __init__(
        self,
        cfg,
        params,
        policy,
        n_slots: int = 4,
        max_len: int = 256,
        kv_quant: bool = True,
        plane_cache: bool = True,
        seed: int = 0,
        value_bits: Optional[int] = None,
        audit_interval: int = 1,
        max_retries: int = 2,
        quarantine_after: int = 2,
        autopilot: Optional[AutopilotPolicy] = None,
        degrade_after: Optional[int] = None,
        degrade_to: int = 4,
        model_parallel: int = 1,
        page_size: int = 0,
        kv_pages: Optional[int] = None,
        prefill_chunk: int = 0,
        share_prefixes: bool = False,
        autotune: bool = False,
        plan_store_path: Optional[str] = None,
    ):
        if not cfg.is_decoder:
            raise ValueError(f"{cfg.name} is encoder-only: no decode path")
        # Attach the tuner before anything traces: warm-start at load.
        self._init_autotune(autotune, plan_store_path)
        self.cfg = cfg
        self.policy = policy
        self.n_slots = n_slots
        self.max_len = max_len
        self.kv_quant = kv_quant
        self.plane_cache = plane_cache
        # paged KV + staged (chunked) prefill (DESIGN.md §12)
        self.page_size = int(page_size)
        self.paged = self.page_size > 0
        self.prefill_chunk = int(prefill_chunk)
        self.share_prefixes = bool(share_prefixes)
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got {prefill_chunk}")
        if self.share_prefixes and not self.paged:
            raise ValueError(
                "share_prefixes needs the paged KV cache (page_size > 0): "
                "physical pages are the sharing unit"
            )
        self._pages_per_slot = 0
        self._kv_pages = 0
        if self.paged:
            if not kv_quant:
                raise ValueError(
                    "paged KV requires kv_quant=True: pages hold int8 values "
                    "plus their per-(position, head) scale vectors — there is "
                    "no raw bf16 page layout (DESIGN.md §12)"
                )
            if max_len % self.page_size:
                raise ValueError(
                    f"max_len={max_len} must be divisible by "
                    f"page_size={self.page_size} so the gathered per-slot view "
                    "keeps the dense decode grid (token-bit parity)"
                )
            self._pages_per_slot = max_len // self.page_size
            self._kv_pages = (
                int(kv_pages)
                if kv_pages is not None
                else n_slots * self._pages_per_slot + 1
            )
            if self._kv_pages < self._pages_per_slot + 1:
                raise ValueError(
                    f"kv_pages={self._kv_pages} cannot hold one full slot "
                    f"({self._pages_per_slot} pages) plus the null page"
                )
            paging._check_kinds(cfg)  # fail at construction, not first run
        elif kv_pages is not None:
            raise ValueError("kv_pages needs page_size > 0 (paged KV)")
        # staged prefill: raw scratch + commit — the paged engine always
        # stages (commit is the page scatter); dense engines stage when
        # chunking is requested
        self._staged = self.paged or self.prefill_chunk > 0
        self.model_parallel = int(model_parallel)
        self.tp = None
        if self.model_parallel > 1:
            # Tensor-parallel serving (DESIGN.md §11): shard the packed
            # plane caches column/row-parallel and the KV cache by head,
            # run the steps under shard_map, stay token-bit-identical to
            # the single-device engine.
            if not policy.default.active:
                raise ValueError(
                    "model_parallel > 1 requires an active quantization "
                    "policy: TP relays out the quantized serving tree "
                    "(shard_quantized), there is no dense TP path"
                )
            if max(policy.default.a_bits, policy.default.w_bits) > 8:
                raise ValueError(
                    "model_parallel > 1 requires <= 8-bit operands: the "
                    "row-parallel partial sums must accumulate exactly in "
                    "int32 for the psum to be bit-identical"
                )
            self.tp = TPContext.create(self.model_parallel)
            self.tp.local_config(cfg)  # fail fast on head divisibility
        self._value_bits = value_bits
        self.q_params = (
            self._requantize(params) if policy.default.active else params
        )
        base = jax.random.PRNGKey(seed)
        # disjoint streams: first-token sampling folds rid, decode folds step
        self._prefill_key, self._decode_key = jax.random.split(base)
        self._insert = jax.jit(insert_slot, donate_argnums=(0,))
        self.quarantine_after = quarantine_after
        if degrade_after is not None:
            # PR 6's one-shot scrub-degrade hook, folded into the autopilot
            # policy (scrub rate is just one more controller input now)
            global _DEGRADE_ALIAS_WARNED
            if not _DEGRADE_ALIAS_WARNED:
                warnings.warn(
                    "degrade_after/degrade_to are deprecated: pass "
                    "autopilot=AutopilotPolicy(scrub_degrade_after=..., "
                    "scrub_degrade_to=...) instead (the kwargs construct "
                    "exactly that policy)",
                    DeprecationWarning,
                    stacklevel=2,
                )
                _DEGRADE_ALIAS_WARNED = True
            if autopilot is not None:
                raise ValueError(
                    "pass either autopilot= or the deprecated degrade_after/"
                    "degrade_to aliases, not both — fold the scrub rule into "
                    "the policy via scrub_degrade_after/scrub_degrade_to"
                )
            autopilot = _degrade_alias_policy(degrade_after, degrade_to)
        if autopilot is not None:
            stored = policy.storage_width()
            if stored is None:
                raise ValueError(
                    "autopilot needs an active quantization policy (the "
                    "tier ladder truncates the stored decomposition)"
                )
            # keep only servable rungs; widest rung is pinned to the
            # storage width so tier 0 IS the static engine (same compiled
            # steps, bit-identical tokens for never-degraded slots)
            tiers = tuple(
                (min(a, stored), min(w, stored))
                for a, w in autopilot.tiers
                if min(a, w) <= stored
            )
            tiers = tuple(dict.fromkeys(tiers))  # dedupe, keep order
            if tiers[0] != (stored, stored):
                tiers = ((stored, stored),) + tiers
            self._tiers = tiers
            # the controller must see the clamped ladder: rung indices
            # are shared between Autopilot state and engine dispatch
            autopilot = dataclasses.replace(autopilot, tiers=tiers)
        self.autopilot_policy = autopilot
        self._init_integrity(params, value_bits, audit_interval, max_retries)
        if self.integrity != "off":
            self._slot_fp = jax.jit(cache_slot_checksums)
            if self.paged:
                self._paged_fp = jax.jit(paging.paged_checksums)
        self._select = jax.jit(select_slots)
        if self.paged:
            # scratch (argnum 1) is NOT donated: prefix-registry snapshots
            # alias earlier chunk states of the same tree
            self._commit_paged = jax.jit(paging.paged_commit, donate_argnums=(0,))
            self._clear_slot = jax.jit(paging.clear_slot, donate_argnums=(0,))
            self._select_paged = jax.jit(paging.select_paged)
        elif self._staged:
            if self.kv_quant:
                self._commit_dense = jax.jit(
                    lambda c, s, slot: insert_slot(
                        c, paging.quantize_scratch(s), slot
                    ),
                    donate_argnums=(0,),
                )
            else:
                self._commit_dense = jax.jit(insert_slot, donate_argnums=(0,))
        self._chunk_compiled: dict = {}
        self._shadow_compiled: dict = {}
        self._init_dial()

    def _make_steps(self, precision):
        check = self.integrity != "off"
        pcol = integrity.Collector() if check else None
        scol = integrity.Collector() if check else None
        if self.tp is not None:
            # shard_map steps: no donation in any mode — the scrub retry
            # and mixed-tier protocols re-read the pre-step cache, and the
            # sharded buffers are committed to the mesh (re-layout on
            # donation would cost more than the copy it saves on CPU CI).
            return (
                jit_step(
                    make_tp_prefill_step(
                        self.cfg, self.tp, self._tp_specs, self.policy,
                        max_len=self.max_len, kv_quant=self.kv_quant,
                        precision=precision, collector=pcol,
                    )
                ),
                jit_step(
                    make_tp_cb_decode_step(
                        self.cfg, self.tp, self._tp_specs, self.policy,
                        max_len=self.max_len, n_slots=self.n_slots,
                        kv_quant=self.kv_quant, precision=precision,
                        collector=scol,
                        cache_template=self._paged_template(),
                    )
                ),
                pcol,
                scol,
            )
        return (
            jit_step(
                make_prefill_step(
                    self.cfg, self.policy, max_len=self.max_len,
                    kv_quant=self.kv_quant, precision=precision,
                    collector=pcol,
                )
            ),
            jit_step(
                make_cb_decode_step(
                    self.cfg, self.policy, precision=precision, collector=scol
                ),
                # scrub-and-retry re-executes the step from the pre-step
                # cache, and a mixed-tier step feeds the SAME pre-step
                # cache to one pass per tier — neither may donate it
                donate_argnums=(
                    () if check or self.autopilot_policy is not None else (1,)
                ),
            ),
            pcol,
            scol,
        )

    # -- autopilot plumbing (DESIGN.md §10) ---------------------------------

    def _tier_precision(self, tier_index: int) -> Optional[Tuple[int, int]]:
        """Ladder rung -> the runtime dial it compiles at. Rung 0 (the
        storage width) maps to ``None`` so never-degraded traffic shares
        the static engine's compiled steps — the bit-identity the CI
        parity gate checks is structural, not coincidental."""
        if tier_index == 0:
            return None
        return self._tiers[tier_index]

    def _shadow_steps(self, precision):
        """Lazily-compiled logits-returning decode step per tier for the
        shadow quality probe (no collector, no donation: the probe reads
        the pre-step cache and discards its outputs)."""
        if precision not in self._shadow_compiled:
            if self.tp is not None:
                step = make_tp_cb_decode_step(
                    self.cfg, self.tp, self._tp_specs, self.policy,
                    max_len=self.max_len, n_slots=self.n_slots,
                    kv_quant=self.kv_quant, precision=precision,
                    with_logits=True,
                    cache_template=self._paged_template(),
                )
            else:
                step = make_cb_decode_step(
                    self.cfg, self.policy, precision=precision,
                    with_logits=True,
                )
            self._shadow_compiled[precision] = jit_step(step)
        return self._shadow_compiled[precision]

    def plane_cache_bytes_per_device(self) -> int:
        """Resident packed-plane bytes per device — the ``tp_serving``
        bench's footprint metric (shrinks ~1/model_parallel; DESIGN.md
        §11)."""
        if self.tp is None:
            return plane_cache_device_bytes(self.q_params)
        return plane_cache_device_bytes(
            self.q_params, self._tp_specs, n_shards=self.tp.size
        )

    def _shadow_kl(self, cache, tokens, temps, key, tier_index, active) -> float:
        """Mean KL(widest || tier) over the active slots' next-token
        distributions — the cheap quality proxy the controller's
        ``kl_budget`` guard consumes. Runs two extra (undonated) decode
        passes; the policy's ``shadow_frac`` bounds how often."""
        ref = self._shadow_steps(None)
        deg = self._shadow_steps(self._tier_precision(tier_index))
        *_, ref_logits = ref(self.q_params, cache, tokens, temps, key)
        *_, deg_logits = deg(self.q_params, cache, tokens, temps, key)
        # slice to the real vocab BEFORE log_softmax: the padded tail
        # would otherwise contribute, and masking it -inf would NaN the KL
        v = self.cfg.vocab_size
        lp_ref = jax.nn.log_softmax(
            ref_logits[..., :v].astype(jnp.float32), axis=-1
        )
        lp_deg = jax.nn.log_softmax(
            deg_logits[..., :v].astype(jnp.float32), axis=-1
        )
        kl = jnp.sum(jnp.exp(lp_ref) * (lp_ref - lp_deg), axis=-1)
        return float(jnp.mean(kl[jnp.asarray(active)]))

    def _first_token(self, logits, request: Request) -> jax.Array:
        logits = sampling.mask_vocab(logits, self.cfg.vocab_size)
        key = jax.random.fold_in(self._prefill_key, request.rid)
        temps = jnp.full((logits.shape[0],), request.temperature, jnp.float32)
        return sampling.sample_tokens(logits, temps, key)[0]

    def _prefill_checked(self, req: Request, integ: Optional[dict], steps=None):
        """Prefill one request, harvesting ABFT alarms (scrub-and-retry
        on alarm in scrub mode). ``steps``: a compiled
        (prefill, step, pcol, scol) tuple to use instead of the bound
        one — the autopilot admits each request at its contract tier's
        prefill regardless of what the dial was last bound to."""
        prefill, _, pcol, _ = steps if steps is not None else (
            self._prefill, None, self._prefill_col, None
        )
        batch = {"tokens": jnp.asarray(req.tokens)[None, :]}
        if self.integrity == "off":
            return prefill(self.q_params, batch)
        for attempt in range(self.max_retries + 1):
            logits, seq_cache, alarms = prefill(self.q_params, batch)
            bad, n = self._harvest(pcol, alarms)
            integ["abft_checks"] += n
            if not bad:
                return logits, seq_cache
            integ["abft_alarms"] += 1
            if self.integrity != "scrub":
                return logits, seq_cache  # detect: record and proceed
            if attempt < self.max_retries:
                self._scrub()
                integ["step_retries"] += 1
        raise integrity.IntegrityError(
            f"prefill ABFT alarm (rid {req.rid}) persisted through "
            f"{self.max_retries} scrub-and-retry attempts"
        )

    # -- staged prefill + paged KV plumbing (DESIGN.md §12) -----------------

    def _paged_template(self):
        """Zero-arg paged-cache builder for the TP step factories (their
        KV sharding specs derive from its eval-shape), or None (dense)."""
        if not self.paged:
            return None
        return lambda: paging.paged_init_cache(
            self.cfg, self.n_slots, self.max_len, self.page_size,
            self._kv_pages,
        )

    def _chunk_steps_for(self, precision):
        """Compiled (chunk_fn, collector) per precision tier. The chunk
        step is the same forward-with-raw-cache program monolithic
        prefill runs, so any chunk schedule is bit-identical to it; the
        scratch is never donated (registry snapshots alias it)."""
        if precision not in self._chunk_compiled:
            check = self.integrity != "off"
            ccol = integrity.Collector() if check else None
            if self.tp is not None:
                fn = make_tp_chunk_prefill_step(
                    self.cfg, self.tp, self._tp_specs, self.policy,
                    max_len=self.max_len, precision=precision, collector=ccol,
                )
            else:
                fn = make_chunk_prefill_step(
                    self.cfg, self.policy, precision=precision, collector=ccol,
                )
            self._chunk_compiled[precision] = (jit_step(fn), ccol)
        return self._chunk_compiled[precision]

    def _chunk_checked(self, steps, scratch, toks, rid, integ):
        """One prefill chunk with ABFT harvest + bounded scrub-and-retry
        (the scratch is undonated, so a retry re-runs the same chunk)."""
        chunk_fn, ccol = steps
        if self.integrity == "off":
            return chunk_fn(self.q_params, scratch, toks)
        for attempt in range(self.max_retries + 1):
            logits, out, alarms = chunk_fn(self.q_params, scratch, toks)
            bad, n = self._harvest(ccol, alarms)
            integ["abft_checks"] += n
            if not bad:
                return logits, out
            integ["abft_alarms"] += 1
            if self.integrity != "scrub":
                return logits, out  # detect: record and proceed
            if attempt < self.max_retries:
                self._scrub()
                integ["step_retries"] += 1
        raise integrity.IntegrityError(
            f"chunked-prefill ABFT alarm (rid {rid}) persisted through "
            f"{self.max_retries} scrub-and-retry attempts"
        )

    def _open_job(
        self, slot, req, sched, pager, registry, tier_index
    ) -> _PrefillJob:
        """Reserve ``slot`` and stage ``req``'s prefill: resolve the
        shared-prefix registry (hit: map its pages read-only and resume
        from its scratch snapshot; miss: cut the chunk schedule at the
        prefix boundary and snapshot there for registration at commit),
        assign pages, and lay out the chunk bounds."""
        sched.reserve(slot)
        precision = (
            self._tier_precision(tier_index)
            if self.autopilot_policy is not None
            else self._precision
        )
        steps = self._chunk_steps_for(precision)
        S = int(req.tokens.size)
        # always leave >= 1 suffix token: the request's first sampled
        # token needs the last prompt position's logits from its own
        # chunk, even on a full-prompt prefix hit
        Lp = (
            min(int(req.shared_prefix_len), S - 1)
            if (self.share_prefixes and req.shared_prefix_len > 0)
            else 0
        )
        entry = (
            registry.lookup(req.tokens[:Lp], tag=precision) if Lp else None
        )
        if entry is not None:
            scratch, start, shared = entry.scratch, Lp, list(entry.page_ids)
        else:
            scratch = init_cache(
                self.cfg, 1, self.max_len, self.cfg.dtype, kv_quant=False
            )
            start, shared = 0, []
        c = self.prefill_chunk if self.prefill_chunk > 0 else S
        bounds, pos = [], start
        while pos < S:
            nxt = min(pos + c, S)
            if entry is None and Lp and pos < Lp:
                nxt = min(nxt, Lp)  # miss: land a chunk edge exactly at Lp
            bounds.append((pos, nxt))
            pos = nxt
        job = _PrefillJob(
            slot=slot, req=req, steps=steps, tier_index=tier_index,
            precision=precision, scratch=scratch, bounds=bounds,
            from_hit=entry is not None,
        )
        if entry is None and Lp:
            job.snapshot_at = next(
                i for i, (_, b) in enumerate(bounds) if b == Lp
            )
            job.prefix_tokens = req.tokens[:Lp].copy()
        if self.paged:
            n_total = pager.pages_needed(S + req.max_new_tokens - 1)
            job.table, job.mask = pager.assign(slot, shared, n_total)
            if job.prefix_tokens is not None:
                n_prefix = Lp // self.page_size
                job.prefix_pages = tuple(
                    int(p) for p in job.table[:n_prefix]
                )
        return job

    def _job_step(self, job: _PrefillJob, integ) -> bool:
        """Run the job's next chunk; True when the prefill is complete."""
        a, b = job.bounds[job.next_i]
        toks = jnp.asarray(job.req.tokens[a:b])[None, :]
        job.logits, job.scratch = self._chunk_checked(
            job.steps, job.scratch, toks, job.req.rid, integ
        )
        if job.next_i == job.snapshot_at:
            job.snapshot = job.scratch
        job.next_i += 1
        return job.next_i >= len(job.bounds)

    def _job_commit(self, job, cache, tokens, sched, pager, registry):
        """Install the finished scratch (paged scatter or dense insert),
        sample the first token, start the slot. Returns
        (cache, tokens, done_now)."""
        req, slot = job.req, job.slot
        tok = self._first_token(job.logits, req)
        if self.paged:
            cache = self._commit_paged(
                cache, job.scratch, jnp.int32(slot),
                jnp.asarray(job.table), jnp.asarray(job.mask),
                jnp.int32(req.tokens.size),
            )
            if job.prefix_tokens is not None and registry is not None:
                registry.register(
                    job.prefix_tokens, job.prefix_pages, job.snapshot,
                    tag=job.precision,
                )
        else:
            cache = self._commit_dense(cache, job.scratch, jnp.int32(slot))
        tokens = tokens.at[slot, 0].set(tok)
        done_now = sched.start(slot, req, int(tok))
        if done_now and self.paged:
            # clear immediately, not deferred: a same-iteration admission
            # may reallocate the freed pages before the next flush point,
            # and this lane's garbage decode write must land on the null
            # page, not in the new tenant's data
            pager.release(slot)
            cache = self._clear_slot(cache, jnp.int32(slot))
        return cache, tokens, done_now

    def _decode_pass(
        self, steps, cache, tokens, temps, key, step_i, integ, injector
    ):
        """One full-slot-array decode pass through ``steps``'s compiled
        cb step, with the inline ABFT harvest + bounded scrub-and-retry
        loop. The mixed-tier step runs this once per active tier against
        the same (undonated) pre-step cache; the single-tier engines run
        it once per iteration. Returns (next_tokens, new_cache)."""
        check = self.integrity != "off"
        scrub_mode = self.integrity == "scrub"
        _, step_fn, _, scol = steps
        for attempt in range(self.max_retries + 1):
            res = step_fn(self.q_params, cache, tokens, temps, key)
            if not check:
                return res
            ntok, ncache, alarms = res
            bad, n = self._harvest(scol, alarms)
            integ["abft_checks"] += n
            if not bad:
                return ntok, ncache
            integ["abft_alarms"] += 1
            if injector is not None:
                injector.mark_detected("params", step_i)
            if not scrub_mode:
                return ntok, ncache  # detect: record and commit as-is
            if attempt < self.max_retries:
                # re-execute from the pre-step cache/tokens (not donated
                # under integrity) with scrubbed weights and the same
                # fold_in key: bit-identical retry
                self._scrub()
                integ["step_retries"] += 1
        raise integrity.IntegrityError(
            f"decode ABFT alarm at step {step_i} persisted through "
            f"{self.max_retries} scrub-and-retry attempts"
        )

    def _contain_kv(
        self, sched: SlotScheduler, bad_slots: list, slot_faults: dict,
        step_i: int, integ: dict,
    ) -> None:
        """Scrub-mode KV containment: the corrupt slot's request is
        requeued (re-prefills from its prompt — KV is regenerable state,
        unlike weights it cannot be scrubbed from a retained source) with
        exponential backoff; repeatedly-faulting slots are quarantined."""
        active = set(sched.active_slots)
        for slot in bad_slots:
            slot_faults[slot] = slot_faults.get(slot, 0) + 1
            if slot in active:
                backoff = 1 << min(slot_faults[slot], 4)
                rid = sched.requeue(slot, arrival_step=step_i + backoff)
                integ["requeued"] += 1
                if sched.retries(rid) > self.max_retries:
                    sched.drop_pending(
                        rid,
                        f"retry budget exhausted: {sched.retries(rid)} KV "
                        f"faults on request {rid}",
                    )
            # (a flip in a free slot's garbage extent is harmless now, but
            # the slot itself is suspect — count it toward quarantine)
            if (
                slot_faults[slot] >= self.quarantine_after
                and slot not in sched.quarantined_slots
            ):
                sched.quarantine(slot)
                integ["quarantined"] += 1

    def run(
        self,
        requests: list[Request],
        precision_schedule: Optional[dict] = None,
        injector: Optional[FaultInjector] = None,
    ):
        """Serve ``requests`` to completion. Returns (results, stats):
        ``results`` maps rid -> (max_new_tokens,) int32 generated tokens;
        ``stats`` reports decode throughput, step counts and KV bytes.
        Requests that cannot finish (deadline passed, retry budget
        exhausted, no servable slot left) land in ``stats['failed']``
        (rid -> reason) instead.

        ``precision_schedule``: optional ``{decode_step: precision}``
        mapping over the DECODE-step counter (idle fast-forwards between
        sparse arrivals do not advance it) — at each threshold the engine
        calls :meth:`set_precision` before executing that step
        (``precision`` as accepted there). Switches are recorded in
        ``stats['precision_switches']`` as (decode_step, (a, w)).

        ``injector``: a :class:`~repro.runtime.faults.FaultInjector` (or
        spec string) applied at the top of each engine iteration — the
        SEU test harness. With ``policy.integrity != "off"`` detections
        feed the injector's event log; in scrub mode every params fault
        is scrubbed-and-retried (bit-identical tokens) and KV faults are
        contained per-slot (requeue / quarantine).

        With an engine-level ``autopilot`` policy the loop runs closed:
        the controller observes (queue depth, per-token latency EWMA,
        scrub count, shadow KL) each iteration and moves the *admission*
        tier; in-flight slots keep their admission tier (mixed-tier
        decode), and under sustained pressure at the lowest tier the
        queue tail is shed (``stats['autopilot']`` reports switches,
        per-tier token counts, shed counts and the quality probe).
        Scheduled entries racing an autopilot switch on the same decode
        step resolve deterministically: the autopilot wins, the schedule
        entry is consumed and recorded in
        ``stats['autopilot']['schedule_conflicts']``."""
        if isinstance(injector, (str, FaultSpec)):
            injector = FaultInjector(injector)
        schedule = dict(precision_schedule or {})
        sched = SlotScheduler(self.n_slots, max_extent=self.max_len)
        for r in sorted(requests, key=lambda r: r.arrival_step):
            sched.submit(r)

        check = self.integrity != "off"
        scrub_mode = self.integrity == "scrub"
        allocator = pager = registry = None
        job: Optional[_PrefillJob] = None
        clears: list[int] = []  # deferred null-page clears (flushed pre-admission)
        page_faults: dict[int, int] = {}
        prefill_chunks = 0
        shared_hits = 0
        if self.paged:
            cache = paging.paged_init_cache(
                self.cfg, self.n_slots, self.max_len, self.page_size,
                self._kv_pages,
            )
            allocator = paging.PageAllocator(self._kv_pages, self.page_size)
            pager = paging.SlotPager(
                allocator, self.n_slots, self._pages_per_slot
            )
            if self.share_prefixes:
                registry = paging.PrefixRegistry(allocator)
            self._page_nbytes = paging.page_nbytes(cache)

            def _capacity(req: Request) -> bool:
                # free-PAGE admission gate: the ask is the request's full
                # extent minus whatever a registry hit would map shared;
                # under pressure, evict cold registry entries (their
                # pages free once no live slot also maps them)
                S = int(req.tokens.size)
                need = pager.pages_needed(S + req.max_new_tokens - 1)
                protect = None
                if self.share_prefixes and req.shared_prefix_len > 0:
                    Lp = min(int(req.shared_prefix_len), S - 1)
                    if Lp:
                        prec = (
                            self._tier_precision(ap.tier_index)
                            if ap is not None
                            else self._precision
                        )
                        protect = registry.key(req.tokens[:Lp], tag=prec)
                        hit = registry.peek(req.tokens[:Lp], tag=prec)
                        if hit is not None:
                            need -= len(hit.page_ids)
                while (
                    allocator.free_pages < need
                    and registry is not None
                    and registry.evict_oldest(protect)
                ):
                    pass
                return allocator.free_pages >= need
        else:
            cache = init_cache(
                self.cfg, self.n_slots, self.max_len, self.cfg.dtype,
                kv_quant=self.kv_quant,
            )
            _capacity = None
        tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        kv_bytes = cache_kv_bytes(cache)
        if not check:
            kv_ref = None
        elif self.paged:
            kv_ref = tuple(np.asarray(x) for x in self._paged_fp(cache))
        else:
            kv_ref = np.asarray(self._slot_fp(cache))
        integ = {
            "audits": 0, "audit_alarms": 0,
            "abft_checks": 0, "abft_alarms": 0,
            "kv_checks": 0, "kv_alarms": 0,
            "step_retries": 0, "requeued": 0, "quarantined": 0,
        }
        if self.paged:
            integ["page_faults"] = 0
            integ["pages_quarantined"] = 0
        slot_faults: dict[int, int] = {}
        scrubs0 = self._scrubs
        ap = (
            Autopilot(self.autopilot_policy, self.n_slots)
            if self.autopilot_policy is not None
            else None
        )
        slot_tier: dict[int, int] = {}  # in-flight tier contracts
        request_tiers: dict[int, tuple] = {}
        tier_tokens: dict[int, int] = {}
        schedule_conflicts: list = []
        shadow_probes = 0
        pending_kl: Optional[float] = None
        last_latency = float("nan")
        last_emitted = 0
        step_i = 0
        decode_steps = 0
        decoded_tokens = 0
        switches = []
        # Per-iteration wall time *including* admission/prefill work, for
        # iterations that emitted decode tokens: the inter-token latency an
        # active request experiences. A monolithic prefill stalls the whole
        # iteration; chunked prefill bounds the stall to one chunk — the
        # decode-p99 isolation the paged_serving bench gates (DESIGN.md §12).
        decode_iter_lat: deque = deque(maxlen=HISTORY_LIMIT)
        t0 = time.time()
        while not sched.done or job is not None:
            t_iter = time.time()
            # Each phase of the iteration runs inside its own profiler
            # span, in this order and never overlapping: serve.control,
            # .admit, .chunk, .commit, .decode, .pull, .record. Admit,
            # chunk and commit appear only in iterations that do that work.
            with TraceAnnotation("serve.control"):
                pre_expire = set(sched.active_slots) if self.paged else set()
                sched.expire(step_i)
                active_now = set(sched.active_slots)
                if self.paged:
                    for s_ in pre_expire - active_now:
                        pager.release(s_)
                        clears.append(s_)
                slot_tier = {s: t for s, t in slot_tier.items() if s in active_now}
                if not sched.servable:
                    for rid in sched.pending_rids:
                        sched.drop_pending(
                            rid,
                            "unservable: every decode slot is quarantined",
                        )
                    continue
                if injector is not None:
                    self.q_params, cache = injector.apply(
                        step_i, self.q_params, cache
                    )
                if check and self.audit_interval:
                    # at-rest audits vs the post-commit baselines of the last
                    # iteration: params fingerprint + per-slot KV checksums
                    if step_i % self.audit_interval == 0:
                        integ["audits"] += 1
                        if self._audit_params():
                            integ["audit_alarms"] += 1
                            if injector is not None:
                                injector.mark_detected("params", step_i)
                            if scrub_mode:
                                self._scrub()
                    if self.paged:
                        sums = tuple(np.asarray(x) for x in self._paged_fp(cache))
                        integ["kv_checks"] += 1
                        bad_pages = [
                            int(p)
                            for p in np.flatnonzero(sums[0] != kv_ref[0])
                            if p != 0  # null page: free lanes scatter there
                        ]
                        bad_meta = np.flatnonzero(sums[1] != kv_ref[1]).tolist()
                        if bad_pages or bad_meta:
                            integ["kv_alarms"] += len(bad_pages) + len(bad_meta)
                            if injector is not None:
                                injector.mark_detected("kv", step_i)
                            if scrub_mode:
                                # page -> holders: requeue live tenants, drop
                                # registry entries, quarantine repeat offenders
                                affected = set(bad_meta)
                                for pid in bad_pages:
                                    integ["page_faults"] += 1
                                    page_faults[pid] = page_faults.get(pid, 0) + 1
                                    if registry is not None:
                                        registry.drop_page(pid)
                                    affected.update(pager.slots_holding(pid))
                                    if page_faults[pid] >= self.quarantine_after:
                                        allocator.quarantine(pid)
                                        integ["pages_quarantined"] += 1
                                if job is not None and job.slot in affected:
                                    affected.discard(job.slot)
                                    # a fault on a page the job merely MAPS
                                    # (shared prefix) poisons data it will
                                    # decode against — abort and resubmit; a
                                    # fault on an OWNED page is overwritten
                                    # wholesale by the commit scatter
                                    shared_held = set(pager.pages(job.slot)) - set(
                                        pager.owned_pages(job.slot)
                                    )
                                    if shared_held & set(bad_pages):
                                        jslot = job.slot
                                        slot_faults[jslot] = (
                                            slot_faults.get(jslot, 0) + 1
                                        )
                                        backoff = 1 << min(slot_faults[jslot], 4)
                                        pager.release(jslot)
                                        clears.append(jslot)
                                        sched.unreserve(jslot)
                                        rid = sched.resubmit(
                                            job.req, step_i + backoff
                                        )
                                        integ["requeued"] += 1
                                        if sched.retries(rid) > self.max_retries:
                                            sched.drop_pending(
                                                rid,
                                                "retry budget exhausted: "
                                                f"{sched.retries(rid)} KV faults "
                                                f"on request {rid}",
                                            )
                                        job = None
                                self._contain_kv(
                                    sched, sorted(affected), slot_faults,
                                    step_i, integ,
                                )
                                for s_ in active_now - set(sched.active_slots):
                                    pager.release(s_)
                                    clears.append(s_)
                                active_now = set(sched.active_slots)
                                slot_tier = {
                                    s: t for s, t in slot_tier.items()
                                    if s in active_now
                                }
                            kv_ref = sums  # re-baseline: corrupt pages are dead
                            # (tenants requeued, registry entries dropped)
                    else:
                        sums = np.asarray(self._slot_fp(cache))
                        integ["kv_checks"] += 1
                        bad_slots = np.flatnonzero(sums != kv_ref).tolist()
                        if bad_slots:
                            integ["kv_alarms"] += len(bad_slots)
                            if injector is not None:
                                injector.mark_detected("kv", step_i)
                            if scrub_mode:
                                self._contain_kv(
                                    sched, bad_slots, slot_faults, step_i, integ
                                )
                                active_now = set(sched.active_slots)
                                slot_tier = {
                                    s: t for s, t in slot_tier.items()
                                    if s in active_now
                                }
                            kv_ref = sums  # re-baseline (corrupt extents are dead:
                            # their tenants were requeued; readmission overwrites)
                decision = None
                if ap is not None:
                    decision = ap.observe(
                        step_i,
                        sched.queue_depth(step_i),
                        scrubs=self._scrubs - scrubs0,
                        step_latency_s=last_latency,
                        tokens_emitted=last_emitted,
                        shadow_kl=pending_kl,
                    )
                    pending_kl = None
                    if decision.switched:
                        switches.append((decode_steps, ap.tier))
                due = [s for s in schedule if s <= decode_steps]
                for s in sorted(due):
                    prec = schedule.pop(s)
                    if ap is None:
                        # legacy open-loop semantics: a scheduled switch
                        # rebinds the global dial, in-flight slots included
                        self.set_precision(prec)
                        switches.append((decode_steps, self._precision))
                    elif decision is not None and decision.switched:
                        # race: both landed on this decode step — the
                        # closed-loop controller wins, the entry is consumed
                        schedule_conflicts.append((decode_steps, s, prec))
                    else:
                        forced = ap.force(step_i, _norm_precision(prec))
                        if forced.switched:
                            switches.append((decode_steps, ap.tier))
                if ap is not None and ap.shedding:
                    waiting = sched.waiting(step_i)
                    if waiting:
                        est = max(
                            1,
                            round(
                                sum(r.max_new_tokens for r in waiting)
                                / len(waiting)
                            ),
                        )
                        for rid in ap.shed_victims(
                            waiting, step_i, service_estimate=est
                        ):
                            sched.shed(
                                rid,
                                f"overload: shed from queue tail at step "
                                f"{step_i} (autopilot, tier w{ap.tier[1]})",
                            )
            if self._staged:
                expired = (
                    job is not None
                    and job.req.deadline_step is not None
                    and step_i >= job.req.deadline_step
                )
                if clears or expired or job is None:
                    with TraceAnnotation("serve.admit") as span:
                        # flush deferred clears BEFORE admission: pages released
                        # since the last flush may be reallocated and committed
                        # into right below, and the releasing slot's table must
                        # point at the null page before that happens
                        for s_ in clears:
                            cache = self._clear_slot(cache, jnp.int32(s_))
                        clears.clear()
                        if expired:
                            if self.paged:
                                pager.release(job.slot)
                                clears.append(job.slot)
                            sched.unreserve(job.slot)
                            sched.fail(
                                job.req.rid,
                                f"deadline: staged prefill expired at step {step_i}",
                            )
                            job = None
                        if job is None:
                            for slot, req in sched.admissible(
                                step_i, capacity=_capacity
                            ):
                                span.set_metadata(rid=req.rid, slot=slot)
                                # tier is a per-request contract fixed at
                                # admission, like the dense path
                                tier_index = ap.tier_index if ap is not None else 0
                                job = self._open_job(
                                    slot, req, sched, pager, registry, tier_index
                                )
                                shared_hits += int(job.from_hit)
                                if self.prefill_chunk > 0:
                                    # chunked: ONE job in flight, one chunk per
                                    # engine iteration — decode keeps its cadence
                                    # while the prefill burst drains in slices
                                    break
                                # monolithic staging: run to completion now, so
                                # admission timing matches the dense engine
                                done = False
                                while not done:
                                    done = self._job_step(job, integ)
                                    prefill_chunks += 1
                                cache, tokens, done_now = self._job_commit(
                                    job, cache, tokens, sched, pager, registry
                                )
                                if ap is not None:
                                    request_tiers[job.req.rid] = self._tiers[
                                        job.tier_index
                                    ]
                                    if not done_now:
                                        slot_tier[job.slot] = job.tier_index
                                job = None
                if job is not None:
                    ids = {"rid": job.req.rid, "slot": job.slot}
                    with TraceAnnotation("serve.chunk", **ids):
                        done = self._job_step(job, integ)
                    prefill_chunks += 1
                    if done:
                        with TraceAnnotation("serve.commit", **ids):
                            cache, tokens, done_now = self._job_commit(
                                job, cache, tokens, sched, pager, registry
                            )
                        if ap is not None:
                            request_tiers[job.req.rid] = self._tiers[
                                job.tier_index
                            ]
                            if not done_now:
                                slot_tier[job.slot] = job.tier_index
                        job = None
            else:
                with TraceAnnotation("serve.admit") as span:
                    for slot, req in sched.admissible(step_i):
                        span.set_metadata(rid=req.rid, slot=slot)
                        # tier is a per-request contract fixed at admission:
                        # the prefill AND every decode step run at this tier,
                        # across any later controller transitions
                        tier_steps = (
                            self._steps_for(self._tier_precision(ap.tier_index))
                            if ap is not None
                            else None
                        )
                        logits, seq_cache = self._prefill_checked(
                            req, integ if check else None, steps=tier_steps
                        )
                        tok = self._first_token(logits, req)
                        cache = self._insert(cache, seq_cache, jnp.int32(slot))
                        tokens = tokens.at[slot, 0].set(tok)
                        done_now = sched.start(slot, req, int(tok))
                        if ap is not None:
                            request_tiers[req.rid] = ap.tier
                            if not done_now:
                                slot_tier[slot] = ap.tier_index
            active = sched.active_slots
            if active:
                with TraceAnnotation("serve.decode"):
                    t_step = time.time()
                    key = jax.random.fold_in(self._decode_key, step_i)
                    temps = jnp.asarray(sched.temperatures())
                    if ap is None:
                        ntok, ncache = self._decode_pass(
                            self._steps_for(self._precision), cache, tokens,
                            temps, key, step_i, integ, injector,
                        )
                    else:
                        # mixed-tier decode: one plane-prefix pass per tier
                        # present among the active slots, all against the
                        # same pre-step cache; each slot keeps the pass of
                        # its contract tier (free slots ride the base pass —
                        # their lanes are garbage the scheduler never reads)
                        present = sorted({slot_tier.get(s, 0) for s in active})
                        ntok, ncache = self._decode_pass(
                            self._steps_for(self._tier_precision(present[0])),
                            cache, tokens, temps, key, step_i, integ, injector,
                        )
                        for ti in present[1:]:
                            tok_t, cache_t = self._decode_pass(
                                self._steps_for(self._tier_precision(ti)),
                                cache, tokens, temps, key, step_i, integ,
                                injector,
                            )
                            mask_np = np.zeros((self.n_slots,), bool)
                            for s_ in active:
                                if slot_tier.get(s_, 0) == ti:
                                    mask_np[s_] = True
                            mask = jnp.asarray(mask_np)
                            ntok = jnp.where(mask[:, None], tok_t, ntok)
                            if self.paged:
                                # pool leaves merge per PHYSICAL page: take
                                # this tier's writes only on pages owned by
                                # its slots (decode never writes shared pages)
                                pmask_np = np.zeros((self._kv_pages,), bool)
                                for s_ in active:
                                    if slot_tier.get(s_, 0) == ti:
                                        for pid in pager.owned_pages(s_):
                                            pmask_np[pid] = True
                                ncache = self._select_paged(
                                    ncache, cache_t, mask, jnp.asarray(pmask_np)
                                )
                            else:
                                ncache = self._select(ncache, cache_t, mask)
                        frac = ap.policy.shadow_frac
                        if (
                            frac > 0.0
                            and ap.tier_index > 0
                            and int(decode_steps * frac)
                            > int((decode_steps - 1) * frac)
                        ):
                            # shadow quality probe against the pre-step state
                            pending_kl = self._shadow_kl(
                                cache, tokens, temps, key, ap.tier_index, active
                            )
                            shadow_probes += 1
                    tokens, cache = ntok, ncache
                with TraceAnnotation("serve.pull"):
                    toks_np = np.asarray(tokens[:, 0])
            with TraceAnnotation("serve.record"):
                if active:
                    for slot in active:
                        if ap is not None:
                            ti = slot_tier.get(slot, 0)
                            tier_tokens[ti] = tier_tokens.get(ti, 0) + 1
                        evicted = sched.record(slot, int(toks_np[slot]))
                        decoded_tokens += 1
                        if evicted and self.paged:
                            # free the pages now (host-side); the device-side
                            # null-page clear flushes before the next admission
                            pager.release(slot)
                            clears.append(slot)
                    last_latency = time.time() - t_step
                    last_emitted = len(active)
                    sched.observe_step(step_i, last_latency)
                    decode_iter_lat.append(time.time() - t_iter)
                    decode_steps += 1
                    step_i += 1
                else:
                    # nothing in flight: fast-forward to the next arrival
                    sched.observe_step(step_i)
                    last_latency = float("nan")
                    last_emitted = 0
                    nxt = sched.next_arrival()
                    if job is not None:
                        # a staged prefill is progressing: no idle fast-forward
                        # (it would burn the job's deadline on skipped steps)
                        step_i += 1
                    else:
                        step_i = step_i + 1 if nxt is None else max(nxt, step_i + 1)
                if check and self.audit_interval:
                    if self.paged:
                        kv_ref = tuple(np.asarray(x) for x in self._paged_fp(cache))
                    else:
                        kv_ref = np.asarray(self._slot_fp(cache))
        jax.block_until_ready(tokens)
        wall = max(time.time() - t0, 1e-9)
        s = sched.stats()
        waits = np.asarray(s.queue_waits, np.float64)
        p99_wait = float(np.percentile(waits, 99)) if waits.size else 0.0
        stats = {
            "wall_s": wall,
            "decode_steps": decode_steps,
            "decoded_tokens": decoded_tokens,
            "prefill_tokens": int(sum(r.tokens.size for r in requests)),
            "tok_per_s": (decoded_tokens + s.admitted) / wall,
            "kv_cache_bytes": kv_bytes,
            "slot_utilization": (
                decoded_tokens / max(decode_steps * self.n_slots, 1)
            ),
            "admitted": s.admitted,
            "peak_occupancy": s.peak_occupancy,
            "queue_steps": s.queue_steps,
            "p99_queue_steps": p99_wait,
            # inter-token latency seen by active requests: per-iteration
            # wall incl. any prefill work the iteration absorbed
            "decode_iter_p99_ms": (
                float(np.percentile(np.asarray(decode_iter_lat), 99)) * 1e3
                if decode_iter_lat else 0.0
            ),
            "precision_switches": switches,
            "failed": dict(sched.failed),
            "requeued": s.requeued,
            "quarantined_slots": sorted(sched.quarantined_slots),
            # plan-layer observability: registry hit/miss plus the
            # autotuner's store hit/miss/tune counters (zeros untuned)
            "plans": self.plan_stats(),
        }
        if self._staged:
            stats["prefill_chunks"] = prefill_chunks
        if self.paged:
            stats["paging"] = {
                "page_size": self.page_size,
                "kv_pages": self._kv_pages,
                "pages_per_slot": self._pages_per_slot,
                "page_nbytes": self._page_nbytes,
                "peak_used_pages": allocator.peak_used,
                # the gated residency metric: bytes of pages ever live at
                # once — what dense serving would hold is n_slots *
                # pages_per_slot regardless of prompt length or sharing
                "kv_bytes_resident_peak": (
                    allocator.peak_used * self._page_nbytes
                ),
                "shared_prefix_hits": shared_hits,
                "prefix_entries": len(registry) if registry is not None else 0,
                "prefix_evictions": (
                    registry.evictions if registry is not None else 0
                ),
                "quarantined_pages": allocator.quarantined_pages,
            }
        if check:
            integ["mode"] = self.integrity
            integ["scrubs"] = self._scrubs - scrubs0
            stats["integrity"] = integ
        if ap is not None:
            stats["autopilot"] = {
                "tiers": [list(t) for t in self._tiers],
                "final_tier": list(ap.tier),
                "switches": list(ap.switches),
                "shed": s.shed,
                "request_tiers": {
                    rid: f"w{w}a{a}" for rid, (a, w) in request_tiers.items()
                },
                "tier_tokens": {
                    f"w{self._tiers[ti][1]}a{self._tiers[ti][0]}": n
                    for ti, n in sorted(tier_tokens.items())
                },
                "shadow_probes": shadow_probes,
                "shadow_kl_ewma": ap.shadow_kl_ewma,
                "latency_ewma_ms": ap.latency_ewma_ms,
                "p99_queue_steps": p99_wait,
                "schedule_conflicts": schedule_conflicts,
                "depth_history": list(s.depth_history),
            }
        return sched.finished, stats


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="bit-serial quantized serving (continuous batching by default)"
    )
    ap.add_argument("--arch", choices=ARCH_NAMES, default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--bits", type=int, default=8,
                    help="storage precision: weights are quantized and "
                    "decomposed at this width (0 disables quantization)")
    ap.add_argument("--level", default="digit", choices=("bitplane", "digit"))
    ap.add_argument("--variant", default="booth", choices=("booth", "sbmwc"))
    ap.add_argument("--mode", default="cb", choices=("cb", "lockstep"),
                    help="serving mode: continuous batching (default) or the "
                    "lockstep fixed-batch baseline engine")
    ap.add_argument("--batch", type=int, default=4,
                    help="lockstep batch size / default slot count")
    ap.add_argument("--n-slots", type=int, default=None,
                    help="continuous-batching decode slots (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="lockstep prompt length")
    ap.add_argument("--prompt-lens", default=None,
                    help="comma-separated mixed prompt lengths for the "
                    "continuous-batching workload, e.g. 8,32,128")
    ap.add_argument("--stagger", type=int, default=2,
                    help="decode steps between request arrivals")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--precision", type=int, default=None,
                    help="runtime execution precision (<= --bits): serve at "
                    "this width by plane-prefix truncation of the stored "
                    "decomposition (requires --level bitplane)")
    ap.add_argument("--precision-switch", default=None, metavar="STEP:BITS",
                    help="mid-serving reconfiguration: at decode step STEP "
                    "drop to BITS (continuous batching only), e.g. 8:4")
    ap.add_argument("--sparsity", default="off",
                    choices=("off", "gate", "compact"),
                    help="occupancy-gated sparse plane execution: 'gate' "
                    "skips all-zero plane-pair MXU passes in the TPU kernels "
                    "(pack-time weight occupancy AND dynamic activation "
                    "occupancy); 'compact' additionally drops entirely-zero "
                    "weight planes from the serving cache at load time, "
                    "shrinking the plane-pair grid on every backend. Both "
                    "are bit-identical to 'off' (requires --level bitplane)")
    ap.add_argument("--integrity", default="off",
                    choices=("off", "detect", "scrub"),
                    help="fault-tolerant serving (DESIGN.md §9): 'detect' "
                    "runs ABFT row-sum checks on every bit-serial matmul "
                    "plus at-rest fingerprint audits and counts alarms; "
                    "'scrub' additionally recovers — rebuild the weight "
                    "planes from retained source params and retry the "
                    "step (bit-identical tokens), requeue/quarantine on "
                    "KV faults (requires --level bitplane)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="SEU injection harness: comma-separated "
                    "site@step[xN] shots with optional ';seed=N', e.g. "
                    "'planes@2,kv@5x2;seed=7'; sites: planes, sign, "
                    "occupancy, checksum, scale, kv, kv_scale "
                    "(continuous batching only)")
    ap.add_argument("--autopilot", action="store_true",
                    help="closed-loop SLA autopilot (DESIGN.md §10): watch "
                    "queue depth, per-token decode latency and the shadow "
                    "quality probe, and move the admission precision tier "
                    "down/up the 8-6-4 ladder with hysteresis; past the "
                    "lowest tier, shed the queue tail. In-flight requests "
                    "keep their admission tier (mixed-tier decode). "
                    "Continuous batching only; needs --level bitplane")
    ap.add_argument("--sla-ms", type=float, default=None,
                    help="autopilot wall-clock SLA: per-emitted-token decode "
                    "latency EWMA above this is pressure (degrade), below "
                    "half of it is headroom (upgrade)")
    ap.add_argument("--sla-queue-steps", type=int, default=None,
                    help="autopilot queue SLA: per-request queue-wait budget "
                    "in engine steps — the deterministic signal the shedding "
                    "ladder evicts against (predicted wait > budget)")
    ap.add_argument("--shadow-frac", type=float, default=0.0,
                    help="fraction of decode steps shadow-scored for quality "
                    "while degraded: an extra logits pass at the stored "
                    "width and the current tier, KL between them fed to the "
                    "controller (0 disables the probe)")
    ap.add_argument("--model-parallel", type=int, default=1, metavar="P",
                    help="tensor-parallel serving over P devices "
                    "(DESIGN.md §11): plane caches shard column-parallel "
                    "(q/k/v/gate/up) and row-parallel (o/down), the KV "
                    "cache by head; tokens are bit-identical to P=1. "
                    "Needs P devices (CI: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8), --bits in "
                    "[1,8], head counts divisible by P; --mode cb only")
    ap.add_argument("--kv-page-size", type=int, default=0, metavar="POS",
                    help="paged KV cache (DESIGN.md §12): store KV in "
                    "fixed-size pages of POS positions with per-slot block "
                    "tables instead of dense per-slot extents; admission "
                    "checks free-page capacity and residency scales with "
                    "actual tokens, not worst-case max_len (0 = dense; "
                    "--mode cb only, needs int8 KV)")
    ap.add_argument("--kv-pages", type=int, default=None, metavar="N",
                    help="physical page-pool size (default: enough for "
                    "every slot's full extent plus the null page); smaller "
                    "pools admit by free-page capacity")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="TOKENS",
                    help="chunked prefill: stage each admission's prefill "
                    "in fixed TOKENS-sized chunks interleaved with decode "
                    "steps, isolating decode p99 from prefill bursts "
                    "(0 = monolithic; --mode cb only)")
    ap.add_argument("--share-prefixes", action="store_true",
                    help="copy-on-write shared-prefix reuse: requests "
                    "declaring a byte-identical prompt prefix map the same "
                    "physical KV pages read-only and resume prefill from "
                    "the registered snapshot (needs --kv-page-size)")
    ap.add_argument("--shared-prefix-len", type=int, default=0, metavar="N",
                    help="synthetic workload: give every request the same "
                    "first N prompt tokens and declare them shared")
    ap.add_argument("--autotune", action="store_true",
                    help="roofline-calibrated tile autotuning (DESIGN.md "
                    "§13): prune the (bm, bn, bk) space per plan with the "
                    "calibrated analytic model, micro-benchmark <= 4 "
                    "survivors, and serve every plan at its winning tiles "
                    "(bit-identical tokens; tiles change the MXU pass "
                    "schedule, never the arithmetic)")
    ap.add_argument("--plan-store", default=None, metavar="PATH",
                    help="persist winning tile configurations at PATH keyed "
                    "(host fingerprint, plan key): a warm store restarts "
                    "the process at peak with zero tuning runs — "
                    "tune-once-per-fleet (needs --autotune)")
    ap.add_argument("--deadline", type=int, default=None, metavar="STEPS",
                    help="per-request deadline: fail any request not "
                    "finished within STEPS engine iterations of its "
                    "arrival (frees its slot; continuous batching only)")
    ap.add_argument("--audit-interval", type=int, default=1,
                    help="integrity: run the at-rest parameter fingerprint "
                    "audit every N engine iterations (0 disables at-rest "
                    "audits, leaving per-matmul ABFT only)")
    # legacy aliases (one release of backward compat; the consolidated
    # surface is --mode / --precision)
    ap.add_argument("--no-plane-cache", action="store_true",
                    help="skip the load-time weight-plane decomposition cache")
    ap.add_argument("--no-fused", action="store_true",
                    help="stage the linear (separate plane kernel + XLA "
                    "dequant) instead of the fully-fused kernel")
    ap.add_argument("--no-kv-quant", action="store_true",
                    help="keep the KV cache in bf16 (bit-exact fallback; int8 "
                    "quantize-on-append is the default)")
    ap.add_argument("--no-cb", action="store_true",
                    help="alias for --mode lockstep (deprecated)")
    return ap


def validate_args(args) -> None:
    """Fail fast on mutually-inconsistent flag combinations (previously
    several of these silently fell back to the jnp path or were ignored)."""

    def die(msg):
        raise SystemExit(f"[serve] invalid flags: {msg}")

    if args.no_cb:
        args.mode = "lockstep"
    if args.bits and not 1 <= args.bits <= 16:
        die("--bits must be in [1, 16] (the paper's synthesis-time maximum; "
            "0 disables quantization)")
    if args.mode == "lockstep" and args.prompt_lens:
        die("--prompt-lens (mixed prompt lengths) needs --mode cb; the "
            "lockstep engine serves one fixed shape")
    if args.mode == "lockstep" and args.precision_switch:
        die("--precision-switch is a continuous-batching feature (--mode cb)")
    if not args.bits:
        for flag, val in (("--no-fused", args.no_fused),
                          ("--no-plane-cache", args.no_plane_cache),
                          ("--precision", args.precision is not None),
                          ("--precision-switch", args.precision_switch),
                          ("--sparsity", args.sparsity != "off"),
                          ("--integrity", args.integrity != "off")):
            if val:
                die(f"{flag} needs an active quantization policy (--bits > 0)")
    if args.integrity != "off":
        if args.level != "bitplane":
            die("--integrity needs --level bitplane: the ABFT column "
                "checksums ride in the packed bit-plane cache")
        if args.no_plane_cache:
            die("--integrity needs the weight-plane cache (drop "
                "--no-plane-cache): checksums are computed at decompose "
                "time and scrub rebuilds the cached decomposition")
    if args.inject_faults:
        if args.mode == "lockstep":
            die("--inject-faults drives the continuous-batching engine "
                "(--mode cb)")
        try:
            args.inject_faults = FaultSpec.parse(args.inject_faults)
        except ValueError as e:
            die(f"--inject-faults: {e}")
    if args.autopilot:
        if args.mode == "lockstep":
            die("--autopilot drives the continuous-batching engine "
                "(--mode cb): the lockstep engine has no queue to watch")
        if not args.bits:
            die("--autopilot needs an active quantization policy "
                "(--bits > 0): the tier ladder truncates the stored "
                "decomposition")
        if args.level != "bitplane":
            die("--autopilot needs --level bitplane (the precision ladder "
                "is served by plane-prefix truncation)")
        if args.no_plane_cache:
            die("--autopilot needs the weight-plane cache (drop "
                "--no-plane-cache): tier switches truncate the stored "
                "decomposition instead of re-quantizing")
    for flag, val in (("--sla-ms", args.sla_ms is not None),
                      ("--sla-queue-steps", args.sla_queue_steps is not None),
                      ("--shadow-frac", args.shadow_frac != 0.0)):
        if val and not args.autopilot:
            die(f"{flag} is an autopilot knob: add --autopilot")
    if args.sla_ms is not None and args.sla_ms <= 0:
        die("--sla-ms must be > 0")
    if args.sla_queue_steps is not None and args.sla_queue_steps < 1:
        die("--sla-queue-steps must be >= 1")
    if not 0.0 <= args.shadow_frac <= 1.0:
        die("--shadow-frac must be in [0, 1]")
    if args.model_parallel < 1:
        die("--model-parallel must be >= 1")
    if args.model_parallel > 1:
        if args.mode == "lockstep":
            die("--model-parallel drives the continuous-batching engine "
                "(--mode cb)")
        if not args.bits:
            die("--model-parallel needs an active quantization policy "
                "(--bits > 0): TP shards the quantized serving tree")
        if args.bits > 8:
            die("--model-parallel needs --bits <= 8: the row-parallel "
                "partial sums must accumulate exactly in int32")
        if len(jax.devices()) < args.model_parallel:
            die(f"--model-parallel {args.model_parallel} needs that many "
                f"devices; this host exposes {len(jax.devices())} (CPU CI "
                "sets XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    if args.deadline is not None:
        if args.mode == "lockstep":
            die("--deadline is a continuous-batching feature (--mode cb): "
                "the lockstep engine has no scheduler to evict from")
        if args.deadline < 1:
            die("--deadline must be >= 1 engine step")
    if args.kv_page_size < 0:
        die("--kv-page-size must be >= 0 (0 = dense KV)")
    if args.prefill_chunk < 0:
        die("--prefill-chunk must be >= 0 (0 = monolithic prefill)")
    if args.kv_page_size:
        if args.mode == "lockstep":
            die("--kv-page-size drives the continuous-batching engine "
                "(--mode cb): the lockstep engine has no slots to page")
        if args.no_kv_quant:
            die("--kv-page-size needs int8 KV (drop --no-kv-quant): pages "
                "hold int8 values plus their scale vectors")
    if args.kv_pages is not None:
        if not args.kv_page_size:
            die("--kv-pages needs --kv-page-size (paged KV)")
        if args.kv_pages < 2:
            die("--kv-pages must be >= 2 (page 0 is the reserved null page)")
    if args.prefill_chunk and args.mode == "lockstep":
        die("--prefill-chunk is a continuous-batching feature (--mode cb)")
    if args.share_prefixes and not args.kv_page_size:
        die("--share-prefixes needs --kv-page-size: physical pages are "
            "the sharing unit")
    if args.shared_prefix_len < 0:
        die("--shared-prefix-len must be >= 0")
    if args.plan_store and not args.autotune:
        die("--plan-store needs --autotune: the store is only read and "
            "written by the attached tuner")
    if args.audit_interval < 0:
        die("--audit-interval must be >= 0")
    if args.sparsity != "off" and args.level != "bitplane":
        die("--sparsity needs --level bitplane: occupancy bitmaps and plane "
            "compaction exist for the packed bit-plane kernels only "
            "(radix-256 digit planes carry no pack-time occupancy)")
    if args.sparsity == "compact" and args.no_plane_cache:
        die("--sparsity compact needs the weight-plane cache (drop "
            "--no-plane-cache): compaction drops zero planes from the "
            "load-time decomposition")
    if args.level == "digit" and args.variant == "sbmwc":
        die("--level digit --variant sbmwc has no TPU kernel (SBMwC radix-256 "
            "digits exceed int8) and would silently run the jnp path; use "
            "--variant booth or --level bitplane")
    wants_precision = args.precision is not None or args.precision_switch
    if wants_precision:
        if args.level != "bitplane":
            die("--precision/--precision-switch need --level bitplane "
                "(digit-plane caches are not prefix-truncatable)")
        if args.no_plane_cache:
            die("--precision/--precision-switch need the weight-plane cache "
                "(drop --no-plane-cache): runtime reconfiguration truncates "
                "the stored decomposition instead of re-quantizing")
    if args.precision is not None and not 1 <= args.precision <= args.bits:
        die(f"--precision {args.precision} must be in [1, {args.bits}] — the "
            "dial truncates the stored decomposition, never extends it")
    if args.precision_switch:
        try:
            step_s, bits_s = args.precision_switch.split(":")
            args.precision_switch = (int(step_s), int(bits_s))
        except ValueError:
            die("--precision-switch expects STEP:BITS, e.g. 8:4")
        if not 1 <= args.precision_switch[1] <= args.bits:
            die(f"--precision-switch bits {args.precision_switch[1]} must be "
                f"in [1, {args.bits}] (the storage width)")
        if args.precision_switch[0] < 0:
            die("--precision-switch step must be >= 0")


def _print_plan_stats(engine) -> None:
    ps = engine.plan_stats()
    line = (
        f"[serve] plans: {ps['resolved']} resolved "
        f"(registry {ps['registry_hits']} hits / {ps['registry_misses']} "
        f"misses), store {ps['store_hits']} hits / {ps['store_misses']} "
        f"misses, {ps['tunes']} tuned"
    )
    if "fingerprint" in ps:
        line += f", host {ps['fingerprint']}"
    print(line)


def main():
    args = build_parser().parse_args()
    validate_args(args)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.is_decoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")
    policy = (
        PrecisionPolicy.uniform(
            args.bits, args.bits, variant=args.variant, level=args.level,
            fuse_epilogue=False if args.no_fused else None,
            sparsity=args.sparsity, integrity=args.integrity,
        )
        if args.bits
        else PrecisionPolicy.off()
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    run_bits = args.precision or args.bits
    tag = f"{cfg.name} w{run_bits}a{run_bits} {args.level}/{args.variant}"
    if args.precision:
        tag += f" (stored w{args.bits}, truncated)"
    if args.sparsity != "off":
        tag += f" sparsity={args.sparsity}"
    if args.integrity != "off":
        tag += f" integrity={args.integrity}"

    if args.mode == "lockstep":
        engine = Engine(
            cfg, params, policy,
            max_len=args.prompt_len + args.gen,
            plane_cache=not args.no_plane_cache,
            sample_fn=sampling.make_sample_fn(args.temperature),
            audit_interval=args.audit_interval,
            autotune=args.autotune,
            plan_store_path=args.plan_store,
        )
        if args.autotune:
            _print_plan_stats(engine)
        if args.precision:
            engine.set_precision(args.precision)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
        )
        tokens, tps = engine.generate(prompts, args.gen)
        print(f"[serve] {tag} lockstep: generated {tokens.shape} at {tps:.1f} tok/s")
        print("[serve] first row:", np.asarray(tokens[0]))
        return

    lens = (
        [int(x) for x in args.prompt_lens.split(",")]
        if args.prompt_lens
        else [args.prompt_len]
    )
    if args.shared_prefix_len and args.shared_prefix_len >= min(lens):
        raise SystemExit(
            f"[serve] invalid flags: --shared-prefix-len "
            f"{args.shared_prefix_len} must be shorter than every prompt "
            f"length (min {min(lens)})"
        )
    n_slots = args.n_slots or args.batch
    max_len = max(lens) + args.gen
    if args.kv_page_size:
        # round up to a whole number of pages (paged_init_cache requires it)
        max_len = -(-max_len // args.kv_page_size) * args.kv_page_size
    ap_policy = (
        AutopilotPolicy(
            sla_ms=args.sla_ms,
            sla_queue_steps=args.sla_queue_steps,
            shadow_frac=args.shadow_frac,
        )
        if args.autopilot
        else None
    )
    engine = ContinuousBatchingEngine(
        cfg, params, policy,
        n_slots=n_slots, max_len=max_len,
        kv_quant=not args.no_kv_quant,
        plane_cache=not args.no_plane_cache,
        audit_interval=args.audit_interval,
        autopilot=ap_policy,
        model_parallel=args.model_parallel,
        page_size=args.kv_page_size,
        kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        share_prefixes=args.share_prefixes,
        autotune=args.autotune,
        plan_store_path=args.plan_store,
    )
    if args.autotune:
        tag += " autotuned"
    if args.model_parallel > 1:
        tag += f" tp={args.model_parallel}"
    if args.kv_page_size:
        tag += f" paged/{args.kv_page_size}"
    if args.precision:
        engine.set_precision(args.precision)
    prefix = (
        rng.integers(0, cfg.vocab_size, (args.shared_prefix_len,))
        if args.shared_prefix_len
        else None
    )

    def _prompt(s):
        if prefix is None:
            return rng.integers(0, cfg.vocab_size, (s,))
        return np.concatenate(
            [prefix, rng.integers(0, cfg.vocab_size, (s - prefix.size,))]
        )

    requests = [
        Request(
            rid=i,
            tokens=_prompt(s),
            max_new_tokens=args.gen,
            temperature=args.temperature,
            arrival_step=i * args.stagger,
            deadline_step=(
                i * args.stagger + args.deadline if args.deadline else None
            ),
            shared_prefix_len=args.shared_prefix_len,
        )
        for i, s in enumerate(lens)
    ]
    schedule = (
        {args.precision_switch[0]: args.precision_switch[1]}
        if args.precision_switch
        else None
    )
    injector = (
        FaultInjector(args.inject_faults) if args.inject_faults else None
    )
    results, stats = engine.run(
        requests, precision_schedule=schedule, injector=injector
    )
    kv = "int8" if not args.no_kv_quant else "bf16"
    print(
        f"[serve] {tag} cb/{kv}: {len(results)} requests "
        f"({stats['decoded_tokens'] + stats['admitted']} tokens) at "
        f"{stats['tok_per_s']:.1f} tok/s, "
        f"slot util {stats['slot_utilization']:.2f}, "
        f"kv cache {stats['kv_cache_bytes'] / 1024:.1f} KiB"
    )
    if "paging" in stats:
        pg = stats["paging"]
        print(
            f"[serve] paging: {pg['kv_pages']} pages x {pg['page_size']} pos, "
            f"peak {pg['peak_used_pages']} pages resident "
            f"({pg['kv_bytes_resident_peak'] / 1024:.1f} KiB), "
            f"{pg['shared_prefix_hits']} shared-prefix hits, "
            f"{stats.get('prefill_chunks', 0)} prefill chunks"
        )
    for step_i, prec in stats["precision_switches"]:
        print(f"[serve] precision switch at decode step {step_i}: -> {prec}")
    if "autopilot" in stats:
        apst = stats["autopilot"]
        print(
            f"[serve] autopilot: final tier {tuple(apst['final_tier'])}, "
            f"{len(apst['switches'])} switches, {apst['shed']} shed, "
            f"p99 queue wait {apst['p99_queue_steps']:.1f} steps, "
            f"tier tokens {apst['tier_tokens']}"
        )
        for sw_step, sw_tier, sw_reason in apst["switches"]:
            print(f"[serve]   step {sw_step}: -> {tuple(sw_tier)} ({sw_reason})")
        if apst["shadow_probes"]:
            print(
                f"[serve]   shadow probes {apst['shadow_probes']}, "
                f"KL ewma {apst['shadow_kl_ewma']:.5f}"
            )
    if "integrity" in stats:
        ig = stats["integrity"]
        print(
            f"[serve] integrity={ig['mode']}: {ig['abft_checks']} ABFT checks "
            f"({ig['abft_alarms']} alarms), {ig['audits']} audits "
            f"({ig['audit_alarms']} alarms), {ig['kv_alarms']} KV alarms, "
            f"{ig['scrubs']} scrubs, {ig['step_retries']} step retries"
        )
    if args.autotune:
        _print_plan_stats(engine)
    if injector is not None:
        undet = injector.undetected
        print(
            f"[serve] injected {len(injector.events)} faults, "
            f"{len(injector.events) - len(undet)} detected"
        )
        for e in undet:
            print(f"[serve]   UNDETECTED: {e.site}@{e.step} at {e.leaf}")
    for rid, reason in sorted(stats["failed"].items()):
        print(f"[serve] rid {rid} FAILED: {reason}")
    for rid in sorted(results):
        print(f"[serve] rid {rid}:", results[rid])


if __name__ == "__main__":
    main()
