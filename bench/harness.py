"""One run of one benchmark cell: set-up, measured window, metrics, check.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Everything the run needs is found by name from ``BENCHMARK.json``: the
cell's configuration (``bench/configs/<config>.json``) and, by its
``model_type``, the architecture (``bench/arch/<model_type>.py``), its
traffic (``bench/traffic/<traffic>.json``), the limits of its check
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).

Set-up (``setup_s``, from the start of the process): weights drawn on the
device from the seed, the engine built (quantize and pack), then one
``engine.run`` over warm-up requests whose prompts produce every chunk
length the cell's traffic can produce, at the cell's tier and slot count.

Window: one ``engine.run`` over a seeded request list longer than any
window can serve. The window opens once as many requests as there are
slots have their first token (the slots have filled; the ramp counts as
set-up) and closes ``--seconds`` later, when the call is stopped. Only
tokens delivered inside the window count. With ``--trace 1`` the
profiler records the last :data:`TRACE_SECONDS` of the window and the
run reports the per-layer metrics.

Check: a seeded sample of the requests served in the window, the longest
among them, is run through the plain reference (``bench/reference.py``)
at the cell's tier; the gap by which a served token's logit lies below
the reference's best, the widest or the mean over the sample as
``bench/limits/<cell>.json`` names it, is held to the cell's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import arch, seam, traffic, weights, work

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
T_PROCESS = time.perf_counter()
#: seconds at the end of the window that a traced run records
TRACE_SECONDS = 3.0

#: JAX monitoring events that mean a program was traced, compiled or
#: loaded from the persistent cache
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class Refused(RuntimeError):
    """The run cannot be made here (no TPU, too few chips, unknown chip)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list  # BENCHMARK.json per_layer entries reported here
    end_to_end: list


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=name,
        chips=w["chips"],
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        per_layer=[m for m in spec["per_layer"] if name in m.get("workloads", [name])],
        end_to_end=[m for m in spec["end_to_end"] if name in m.get("workloads", [name])],
    )


def load_reader(metric: str):
    return arch.load_path(BENCH / "metrics" / f"{metric}.py").read


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    return arch.for_config(cfg).program_config(cfg)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache``,
    keeping every program however fast it compiled."""
    import jax

    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(path)


class GcPauses:
    """Every collection of the host's garbage collector: (end time,
    generation, seconds)."""

    def __init__(self):
        self.pauses: list = []
        self._t = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            t = time.perf_counter()
            self.pauses.append((t, info["generation"], t - self._t))

    def close(self):
        gc.callbacks.remove(self._on_gc)

    def between(self, t0, t1) -> dict:
        """generation -> (collections, seconds) inside [t0, t1]."""
        out: dict = {}
        for t, g, d in self.pauses:
            if t0 <= t <= t1:
                n, s = out.get(g, (0, 0.0))
                out[g] = (n + 1, s + d)
        return out


def host_stalls(win: "Window") -> str:
    """One line on where the window's time went on the host: tokens in
    each half, the median and longest gaps between engine iterations."""
    its = [t for t, _ in win.log.iterations if win.t0 <= t <= win.t1]
    gaps = np.diff(its) if len(its) > 1 else np.zeros(1)
    mid = (win.t0 + win.t1) / 2
    times = win.log.token_times(win.t0, win.t1).values()
    first = sum(1 for ts in times for t in ts if t < mid)
    n_tok = sum(len(ts) for ts in win.log.token_times(win.t0, win.t1).values())
    top = np.sort(gaps)[-5:][::-1] * 1e3
    return (f"{len(its)} iterations, tokens in each half {first} / {n_tok - first}, "
            f"iteration gap median {np.median(gaps) * 1e3:.1f} ms, longest "
            + ", ".join(f"{g:.1f}" for g in top) + " ms, over 3x the median "
            f"{gaps[gaps > 3 * np.median(gaps)].sum():.3f} s")


class CompileCounter:
    def __init__(self):
        import jax

        self.times: list = []

        def on_event(event, *args, **kwargs):
            if event in COMPILE_EVENTS:
                self.times.append(time.perf_counter())

        self._listener = on_event
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)

    def between(self, t0, t1) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def build_engine(cell: Cell, seed: int):
    """(engine, max_len, seconds to draw the weights, seconds to build the
    engine: quantize and pack)."""
    import jax

    from repro.core.precision import PrecisionPolicy
    from repro.launch.serve import ContinuousBatchingEngine
    from repro.models.transformer import init_params

    cfg, tr, srv = cell.config, cell.traffic, cell.config["serving"]
    mcfg = program_config(cfg)
    policy = PrecisionPolicy.uniform(
        srv["storage_bits"], srv["storage_bits"], variant=srv["variant"], level="bitplane"
    )
    t = time.perf_counter()
    template = jax.eval_shape(lambda: init_params(mcfg, jax.random.PRNGKey(0)))
    params = weights.to_program_tree(jax.block_until_ready(weights.canonical(cfg, seed)),
                                     template, cfg)
    t_weights = time.perf_counter() - t
    page = srv["page_size"]
    max_len = -(-traffic.max_extent(tr) // page) * page
    t = time.perf_counter()
    engine = ContinuousBatchingEngine(
        mcfg, params, policy,
        n_slots=tr["slots"], max_len=max_len, page_size=page,
        prefill_chunk=srv["prefill_chunk"], plane_cache=True,
    )
    del params
    if tr["tier_bits"] != srv["storage_bits"]:
        engine.set_precision(tr["tier_bits"])
    jax.block_until_ready(engine.q_params)
    return engine, max_len, t_weights, time.perf_counter() - t


def to_requests(reqs):
    from repro.runtime.scheduler import Request

    return [Request(rid=r["rid"], tokens=r["tokens"], max_new_tokens=r["max_new_tokens"])
            for r in reqs]


def warm_up(engine, cell: Cell, seed: int) -> dict:
    """Serve one request for each chunk length the traffic can produce,
    then one per slot: every program the window runs is compiled or
    loaded here. Returns the seconds of each part."""
    chunk = cell.config["serving"]["prefill_chunk"]
    rng = np.random.default_rng([int(seed), 7])

    def serve(lengths):
        reqs = to_requests([{"rid": i, "max_new_tokens": 2, "tokens": rng.integers(
            0, cell.config["vocab_size"], n, dtype=np.int32)} for i, n in enumerate(lengths)])
        t = time.perf_counter()
        _, stats = engine.run(reqs)
        if stats["failed"]:
            raise RuntimeError(f"warm-up requests failed: {stats['failed']}")
        return time.perf_counter() - t

    lens = traffic.chunk_lengths(cell.traffic, chunk)
    parts = {f"chunk {n}": serve([n]) for n in lens}
    parts["every slot"] = serve([lens[0]] * cell.traffic["slots"])
    return parts


class Profiler:
    """Starts the profiler ``seconds`` before the window's end, from the
    seam."""

    def __init__(self, wlog: seam.Log, seconds: float):
        self.log, self.seconds = wlog, seconds
        self.dir = None
        self.ann = None

    def tick(self, t):
        if self.dir is None and self.log.t_end is not None and t >= self.log.t_end - self.seconds:
            import jax

            from bench.tracefile import WINDOW

            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 2
            opts.python_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation(WINDOW)
            self.ann.__enter__()

    def stop(self):
        if self.dir is None:
            return None
        import jax

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return self.dir


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    log: seam.Log
    prompts: dict  # rid -> prompt tokens

    @property
    def seconds(self):
        return self.t1 - self.t0


def end_to_end(win: Window) -> dict:
    times = win.log.token_times(win.t0, win.t1)
    n_tok = sum(len(v) for v in times.values())
    itl = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])]
    ttft = [t1 - t0 for rid, (t0, t1) in win.log.first.items()
            if t0 is not None and win.t0 <= t1 <= win.t1]
    out = {"out_tok_s": (n_tok / win.seconds, "tokens/s")}
    if itl:
        out["itl_p95_ms"] = (percentile(itl, 95) * 1e3, "ms")
    if ttft:
        out["ttft_admitted_p50_ms"] = (percentile(ttft, 50) * 1e3, "ms")
    return out


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader is given."""

    cell: Cell
    window: Window
    trace: object  # bench.tracefile.Reduced
    peaks: dict
    work: object = work


def check_sample(win: Window, seed: int, n: int) -> list:
    """Seeded sample of the requests served in the window: the one with
    the most tokens served by the window's end, and ``n - 1`` others."""
    served = {rid: sum(1 for t, _ in win.log.tokens[rid] if t <= win.t1)
              for rid in win.log.token_times(win.t0, win.t1)}
    if not served:
        return []
    longest = max(sorted(served), key=lambda r: served[r])
    rest = sorted(r for r in served if r != longest)
    rng = np.random.default_rng([int(seed), 11])
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [int(r) for r in pick]


def reference_gaps(cell: Cell, seed: int, win: Window, sample: list, length: int,
                   control_bits: int = 0) -> dict:
    """How far below the reference's best logit the served tokens lie,
    over every sampled position: the widest gap and the mean gap. With
    ``control_bits`` the same for the token the reference at that lower
    tier puts first at each position (the control)."""
    import jax.numpy as jnp

    from bench import reference

    cfg, tier = cell.config, cell.traffic["tier_bits"]
    W = weights.canonical(cfg, seed)
    gaps, ctrl_gaps, agree = [], [], 0
    for rid in sample:
        prompt = np.asarray(win.prompts[rid], np.int64)
        served = [tok for t, tok in win.log.tokens[rid] if t <= win.t1]
        if [t for t in served if not 0 <= t < cfg["vocab_size"]]:
            gaps.append(np.full(len(served), np.inf))
            continue
        seq = np.concatenate([prompt, np.asarray(served, np.int64)])
        toks = np.zeros((length,), np.int32)
        toks[: len(seq)] = seq
        tgt = np.zeros((length,), np.int32)
        tgt[: len(seq) - 1] = seq[1:]
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        alt = tgt
        if control_bits:
            _, _, c_arg = reference.score(cfg, W, jnp.asarray(toks),
                                          jnp.asarray(np.stack([tgt, tgt])), control_bits)
            alt = np.asarray(c_arg, np.int32)
        best, at, arg = (np.asarray(a) for a in reference.score(
            cfg, W, jnp.asarray(toks), jnp.asarray(np.stack([tgt, alt])), tier))
        gaps.append(best[pos] - at[0][pos])
        agree += int(np.sum(arg[pos] == tgt[pos]))
        if control_bits:
            ctrl_gaps.append(best[pos] - at[1][pos])
    del W
    g = np.concatenate(gaps) if gaps else np.asarray([np.inf])
    out = {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
           "positions": int(g.size), "argmax_agree": agree / max(g.size, 1)}
    if control_bits:
        c = np.concatenate(ctrl_gaps)
        out["control"] = {"max_logit_gap": float(c.max()), "mean_logit_gap": float(c.mean()),
                          "bits": control_bits}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *, control: bool = False,
        cell: Cell | None = None, require_tpu: bool = True,
        compile_cache: bool = True) -> dict:
    """One run; returns the result object (the last stdout line)."""
    import jax

    cell = cell or load_cell(workload)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"JAX found no TPU (first device: {dev.platform} {dev.device_kind})")
    if len(devices) < cell.chips:
        raise Refused(f"cell {cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
    peaks = peaks_for(dev.device_kind) if require_tpu else load_json(
        BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    cache_dir = use_compile_cache() if compile_cache else "off"
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; cell {cell.name}; "
        f"seed {seed}; compile cache {cache_dir}")
    counter = CompileCounter()

    import repro.launch.serve as serve_mod

    seam.require(serve_mod)
    t_start = time.perf_counter() - T_PROCESS
    engine, max_len, t_weights, t_build = build_engine(cell, seed)
    warm = warm_up(engine, cell, seed)

    reqs = traffic.requests(cell.traffic, cell.config["vocab_size"], seed)
    requests = to_requests(reqs)
    wlog = seam.Log()
    prof = Profiler(wlog, min(TRACE_SECONDS, seconds / 2)) if trace else None
    restore = seam.install(serve_mod, wlog, seconds, cell.traffic["slots"],
                           on_tick=prof.tick if prof else None)
    closed = False
    gc_log = GcPauses()
    t_run = time.perf_counter()
    try:
        engine.run(requests)
    except seam.WindowClosed:
        closed = True
    finally:
        restore()
        gc_log.close()
    t_closed = time.perf_counter()
    trace_dir = prof.stop() if prof else None
    if wlog.schedulers != 1:
        raise seam.SeamMissing("engine.run did not build its scheduler through the seam")
    if not closed:
        raise RuntimeError("the traffic ran out before the window closed: raise "
                           "bench.traffic.N_REQUESTS")
    t0, t1 = wlog.t_open, wlog.t_end
    setup_s = t0 - T_PROCESS
    compiles = counter.between(t0, t1)
    counter.close()
    win = Window(t0, t1, wlog, {r["rid"]: r["tokens"] for r in reqs})
    log(f"window on the host: {host_stalls(win)}; garbage collections "
        + ", ".join(f"gen {g}: {n} in {s:.3f} s"
                    for g, (n, s) in sorted(gc_log.between(t0, t1).items())))
    failed = len(wlog.sched.failed)
    attempted = len(wlog.first) + failed
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"set-up {setup_s:.3f} s: start {t_start:.3f} s, weights {t_weights:.3f} s, engine "
        f"build {t_build:.3f} s, warm-up " + ", ".join(f"{k} {v:.3f} s" for k, v in warm.items())
        + f", slots filling {t0 - t_run:.3f} s; window {seconds} s closed "
        f"{t_closed - t1:.3f} s late; {compiles} compile events in the window")

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    metrics: dict = {}
    breakdown = None
    if trace:
        from bench import tracefile

        reduced = None
        if trace_dir:
            reduced = tracefile.reduce(jax.profiler.ProfileData.from_file(
                tracefile.find_xplane(trace_dir)))
            shutil.rmtree(trace_dir, ignore_errors=True)
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": [[n, s] for n, s in reduced.top_ops(10)],
                         "idle_gaps": reduced.idle_gaps(10)}
        ctx = Context(cell, win, reduced, peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(win)
        e2e["setup_s"] = (setup_s, "s")
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]][0], "unit": m["unit"]}

    del engine, requests
    gc.collect()
    t = time.perf_counter()
    sample = check_sample(win, seed, cell.traffic["check_requests"])
    ref = reference_gaps(cell, seed, win, sample, max_len,
                         cell.traffic["control_bits"] if control else 0)
    log(f"reference at {cell.traffic['tier_bits']} bits over {len(sample)} requests, "
        f"{ref['positions']} positions, {time.perf_counter() - t:.3f} s; served token = "
        f"reference argmax at {ref['argmax_agree']:.4f} of them; max_logit_gap "
        f"{ref['max_logit_gap']!r}, mean_logit_gap {ref['mean_logit_gap']!r}")
    checks = {name: {"value": ref[name], "limit": limit}
              for name, limit in cell.limits.items()}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and ref["positions"] > 0
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control"] = ref["control"]
        log(f"control at {cell.traffic['control_bits']} bits: max_logit_gap "
            f"{ref['control']['max_logit_gap']!r}, mean_logit_gap "
            f"{ref['control']['mean_logit_gap']!r}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the check's number for the reference at the "
                    "cell's control precision (not part of a benchmark run)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under {ROOT / 'src'}: run from a checkout of the repository")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     control=bool(args.control))
    except Refused as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
