#!/usr/bin/env python3
"""Smoke run of the bit-plane serving path on a TPU — not a benchmark.

Serves granite-3-8b at its published width (d_model 4096, 32 q-heads / 8
KV heads of 128, d_ff 12800, vocab 49155) with the depth cut to fit one
chip's memory, random weights from ``--seed``, through the serving entry
point a user calls: ``ContinuousBatchingEngine`` with 8-bit Booth
bit-planes, the plan-cached packed weight planes, an int8 paged KV cache
and chunked prefill. Eight greedy requests: prompt 128, 32 new tokens.

It exits nonzero, and prints no result line, unless

* JAX's first device is a TPU (there is no CPU fallback);
* every matmul plan the run resolved is a Pallas route on backend
  ``pallas`` (a layer on the jnp ``oracle`` / ``cached_scan`` route is an
  error);
* the greedy tokens equal those of the same requests served through the
  jnp route on the same chip — the ``oracle`` plane-pair scan over the
  stored int8 weights, which never reads the packed plane cache: the
  arithmetic is exact integer arithmetic, so they are bit-identical.

``--tp 4`` runs only the tensor-parallel engine at ``model_parallel=4``
and the one-chip engine it is compared with: tokens must be identical.

The engine compiles its steps with XLA's excess precision off
(``steps.EXACT_ROUNDING``), as every engine a user builds does: each route
rounds its bf16 values where the source says, not where fusion happens to
put them, so the comparison is bit for bit under the process's default
flags — the same ones ``serve.main`` runs with.

The last line of stdout is ``{"ok": true, "device": {...}}``. Everything
runs in this one process; it spawns none.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips of one host
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "granite-3-8b"
#: 8 layers of ~199 M weights: bf16 embedding/head plus the int8 weights
#: and their packed 8-plane Booth cache fit one 16 GB v5e with room for
#: load-time temporaries (see PERF.md for the measured peak).
N_LAYERS = 8
N_REQUESTS = 8
PROMPT_LEN = 128
NEW_TOKENS = 32
PAGE_SIZE = 16
PREFILL_CHUNK = 64


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def build(seed: int, n_layers: int):
    """(cfg, policy, dense params, requests): the published width, cut in
    depth only; weights and prompts from ``seed``."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.precision import PrecisionPolicy
    from repro.models.transformer import init_params
    from repro.runtime.scheduler import Request

    cfg = dataclasses.replace(get_config(ARCH), n_layers=n_layers)
    policy = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    requests = [
        Request(
            rid=i,
            tokens=rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)),
            max_new_tokens=NEW_TOKENS,
        )
        for i in range(N_REQUESTS)
    ]
    return cfg, policy, params, requests


def make_engine(cfg, params, policy, model_parallel: int = 1, plane_cache=True):
    from repro.launch.serve import ContinuousBatchingEngine

    max_len = -(-(PROMPT_LEN + NEW_TOKENS) // PAGE_SIZE) * PAGE_SIZE
    return ContinuousBatchingEngine(
        cfg, params, policy,
        n_slots=N_REQUESTS,
        max_len=max_len,
        page_size=PAGE_SIZE,
        prefill_chunk=PREFILL_CHUNK,
        model_parallel=model_parallel,
        plane_cache=plane_cache,
    )


def without_plane_cache(tree):
    """The quantized tree minus its packed plane caches: the same int8
    weights and scales (shared, not copied) for the jnp reference."""
    if isinstance(tree, dict):
        return {k: without_plane_cache(v) for k, v in tree.items() if k != "w_planes"}
    if isinstance(tree, (list, tuple)):
        return type(tree)(without_plane_cache(v) for v in tree)
    return tree


def serve(engine, requests, label: str):
    """Serve ``requests`` on ``engine``; returns ({rid: tokens}, plans
    resolved by this run). Fails on any failed request."""
    import numpy as np

    from repro.core import plan as plan_mod

    before = set(plan_mod.DEFAULT_REGISTRY.plans())
    t0 = time.perf_counter()
    results, stats = engine.run(requests)
    wall = time.perf_counter() - t0
    if stats["failed"] or len(results) != len(requests):
        raise RuntimeError(f"{label}: requests failed: {stats['failed']}")
    tokens = {rid: np.asarray(t) for rid, t in results.items()}
    for rid, t in tokens.items():
        if t.shape != (NEW_TOKENS,) or t.min() < 0 or t.max() >= engine.cfg.vocab_size:
            raise RuntimeError(f"{label}: rid {rid} returned tokens {t}")
    n_tok = stats["decoded_tokens"] + stats["admitted"]
    log(
        f"{label}: {len(results)} requests, {n_tok} generated tokens in "
        f"{wall:.3f} s wall, compile included on a first run "
        f"({n_tok / wall:.1f} tok/s; a smoke figure, not a benchmark)"
    )
    new_plans = [p for p in plan_mod.DEFAULT_REGISTRY.plans() if p not in before]
    return tokens, new_plans


def check_pallas_routes(plans, label: str) -> None:
    """Every plan on a Pallas route with backend ``pallas`` — no layer
    quietly served by a jnp route."""
    from repro.core.autotune import JNP_KERNELS

    if not plans:
        raise RuntimeError(f"{label}: the run resolved no matmul plans")
    routes = collections.Counter(
        (p.kernel, p.key.backend, p.key.m, p.key.k, p.key.n) for p in plans
    )
    for (kernel, backend, m, k, n), count in sorted(routes.items()):
        log(f"{label}: plan {m}x{k}x{n} -> {kernel} on {backend} (x{count})")
    bad = [p.describe() for p in plans
           if p.key.backend != "pallas" or p.kernel in JNP_KERNELS]
    if bad:
        raise RuntimeError(f"{label}: plans off the Pallas route: {bad}")


def compare(got: dict, want: dict, label: str) -> None:
    import numpy as np

    if sorted(got) != sorted(want):
        raise RuntimeError(f"{label}: rids differ: {sorted(got)} vs {sorted(want)}")
    diff = {rid: int(np.sum(got[rid] != want[rid])) for rid in got}
    if any(diff.values()):
        first = min(rid for rid, d in diff.items() if d)
        raise RuntimeError(
            f"{label}: tokens differ in {sum(map(bool, diff.values()))} of "
            f"{len(diff)} requests (mismatched tokens per rid {diff}); rid "
            f"{first}: {got[first].tolist()} vs {want[first].tolist()}"
        )
    log(f"{label}: all {len(got)} requests token-identical")


def one_chip(args) -> None:
    cfg, policy, params, requests = build(args.seed, args.layers)
    engine = make_engine(cfg, params, policy)
    del params  # the engine holds the quantized tree; drop the dense one
    tokens, plans = serve(engine, requests, "pallas, first run")
    check_pallas_routes(plans, "pallas")
    again, _ = serve(engine, requests, "pallas, second run")
    compare(again, tokens, "pallas second run vs first")
    # The reference engine shares the device-resident int8 weights
    # (quantize_params passes an already-quantized tree through) but not
    # the plane cache, and its policy puts every matmul on the jnp route:
    # each re-decomposes its weight in the jnp oracle. (The jnp cached
    # route would unpack each packed weight whole: 12 GiB of temporaries
    # at the lm_head.)
    reference = make_engine(
        cfg, without_plane_cache(engine.q_params),
        dataclasses.replace(policy, backend="jnp"), plane_cache=False,
    )
    ref_tokens, ref_plans = serve(reference, requests, "jnp oracle")
    off = [p.describe() for p in ref_plans
           if (p.key.backend, p.kernel) != ("jnp", "oracle")]
    if off or not ref_plans:
        raise RuntimeError(f"reference run resolved plans off the jnp oracle: {off}")
    compare(tokens, ref_tokens, "pallas vs jnp oracle")


def tensor_parallel(args) -> None:
    cfg, policy, params, requests = build(args.seed, args.layers)
    single = make_engine(cfg, params, policy)
    want, plans = serve(single, requests, "one chip")
    check_pallas_routes(plans, "one chip")
    del single
    engine = make_engine(cfg, params, policy, model_parallel=args.tp)
    del params
    got, plans = serve(engine, requests, f"tp={args.tp}")
    check_pallas_routes(plans, f"tp={args.tp}")
    compare(got, want, f"tp={args.tp} vs one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1, metavar="P",
                    help="run only the tensor-parallel engine at "
                    "model_parallel=P and the one-chip run it is compared "
                    "with (needs P chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=N_LAYERS,
                    help="depth of the cut-down model")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"no repro package under {SRC}: run from a checkout")
        return 2
    sys.path.insert(0, SRC)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"JAX found no TPU (first device: {dev.platform} {dev.device_kind})")
        return 1
    if len(devices) < args.tp:
        log(f"--tp {args.tp} needs {args.tp} chips, found {len(devices)}")
        return 1
    log(f"device {dev.device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.tp > 1:
        tensor_parallel(args)
    else:
        one_chip(args)
    stats = dev.memory_stats() or {}
    log(f"total {time.perf_counter() - t0:.1f} s; device 0 peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
