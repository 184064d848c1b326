"""Phase spans of the continuous-batching engine loop.

``ContinuousBatchingEngine.run`` wraps each phase of an iteration in a
``jax.profiler.TraceAnnotation`` (``serve.control``, ``.admit``,
``.chunk``, ``.commit``, ``.decode``, ``.pull``, ``.record``). Checked on
a tiny paged engine with chunked prefill: with the span constructor
replaced by a recorder, the phases come in order, never overlap, and
hold the scheduler calls they should; with the CPU profiler on, the
tokens do not change and the spans land on the engine thread's line of
the trace.
"""

import glob
import os
import re

import jax
import numpy as np
import pytest

import repro.launch.serve as serve
from repro.configs import get_reduced
from repro.core.precision import PrecisionPolicy
from repro.models import init_params
from repro.runtime.scheduler import Request

ORDER = ("serve.control", "serve.admit", "serve.chunk", "serve.commit",
         "serve.decode", "serve.pull", "serve.record")
LENS = [20, 9, 16, 12]


@pytest.fixture(scope="module")
def engine():
    cfg = get_reduced("granite-3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    policy = PrecisionPolicy.uniform(8, 8, level="bitplane")
    return serve.ContinuousBatchingEngine(
        cfg, params, policy, n_slots=2, max_len=48, page_size=8, prefill_chunk=8,
    )


def _requests(vocab):
    rng = np.random.default_rng(3)
    return [Request(rid=i, tokens=rng.integers(0, vocab, (n,)), max_new_tokens=4,
                    arrival_step=i) for i, n in enumerate(LENS)]


@pytest.fixture(scope="module")
def recorded(engine):
    """(event log, stats) of one run with every span and seam call logged."""
    log = []

    class Span:
        def __init__(self, name, **meta):
            self.name, self.meta = name, dict(meta)

        def set_metadata(self, **meta):
            self.meta.update(meta)

        def __enter__(self):
            log.append(("enter", self))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self))
            return False

    class Seam(serve.SlotScheduler):
        def start(self, slot, request, first_token):
            log.append(("seam", "start"))
            return super().start(slot, request, first_token)

        def record(self, slot, token):
            log.append(("seam", "record"))
            return super().record(slot, token)

        def observe_step(self, step, latency_s=float("nan")):
            log.append(("seam", "observe_step"))
            return super().observe_step(step, latency_s)

    mp = pytest.MonkeyPatch()
    mp.setattr(serve, "TraceAnnotation", Span)
    mp.setattr(serve, "SlotScheduler", Seam)
    try:
        _, stats = engine.run(_requests(engine.cfg.vocab_size))
    finally:
        mp.undo()
    return log, stats


def _iterations(log):
    """Spans per iteration; an iteration opens with ``serve.control``."""
    its = []
    for kind, what in log:
        if kind == "enter":
            if what.name == "serve.control":
                its.append([])
            its[-1].append(what)
    return its


def test_phases_come_in_order_without_overlap(recorded):
    log, stats = recorded
    spans = [(k, w) for k, w in log if k != "seam"]
    # every span closes before the next opens
    assert all(a[0] == "enter" and b == ("exit", a[1]) for a, b in zip(spans[::2], spans[1::2]))
    its = _iterations(log)
    assert its
    for it in its:
        order = [ORDER.index(s.name) for s in it]
        assert order == sorted(set(order)), [s.name for s in it]
    assert sum(1 for it in its if it[-1].name == "serve.record") == len(its)


def test_one_pull_per_decode_iteration_and_one_commit_per_request(recorded):
    log, stats = recorded
    names = [w.name for k, w in log if k == "enter"]
    assert names.count("serve.pull") == names.count("serve.decode") == stats["decode_steps"]
    commits = [w for k, w in log if k == "enter" and w.name == "serve.commit"]
    assert sorted(w.meta["rid"] for w in commits) == list(range(len(LENS)))
    assert names.count("serve.chunk") == stats["prefill_chunks"] > len(LENS)
    for name in ("serve.admit", "serve.chunk", "serve.commit"):
        with_ids = [w for k, w in log if k == "enter" and w.name == name and w.meta]
        assert {w.meta["rid"] for w in with_ids} == set(range(len(LENS)))
        assert all(set(w.meta) == {"rid", "slot"} for w in with_ids)


def test_scheduler_calls_fall_inside_commit_and_record(recorded):
    log, _ = recorded
    inside, where = None, {}
    for kind, what in log:
        if kind == "enter":
            inside = what.name
        elif kind == "exit":
            inside = None
        else:
            where.setdefault(what, set()).add(inside)
    assert where == {"start": {"serve.commit"}, "record": {"serve.record"},
                     "observe_step": {"serve.record"}}


def _host_lines(xplane):
    data = jax.profiler.ProfileData.from_file(xplane)
    return [[e.name for e in line.events]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines]


def test_profiler_leaves_tokens_alone_and_sees_the_spans(engine, recorded, tmp_path):
    plain, stats = engine.run(_requests(engine.cfg.vocab_size))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test_engine_thread"):
            traced, _ = engine.run(_requests(engine.cfg.vocab_size))
    assert set(plain) == set(traced) == set(range(len(LENS)))
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], traced[rid])

    xplane = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True))
    engine_line = [ln for ln in _host_lines(xplane[-1]) if "test_engine_thread" in ln]
    assert len(engine_line) == 1
    names = [n.split("#", 1)[0] for n in engine_line[0]]
    assert set(ORDER) <= set(names)
    assert names.count("serve.pull") == stats["decode_steps"]
    assert names.count("serve.commit") == len(LENS)


def test_decode_step_names_the_model_parts(engine):
    """The decode step's ops carry the model part in their metadata
    (``jax.named_scope``): what a device trace splits the step by."""
    from repro.launch.steps import jit_step, make_cb_decode_step
    from repro.models.cache import init_cache

    cfg, n = engine.cfg, engine.n_slots
    step = jit_step(make_cb_decode_step(cfg, engine.policy))
    cache = init_cache(cfg, n, engine.max_len, cfg.dtype, kv_quant=True)
    text = step.lower(
        engine.q_params, cache, jax.numpy.zeros((n, 1), jax.numpy.int32),
        jax.numpy.zeros((n,), jax.numpy.float32), jax.random.PRNGKey(0),
    ).as_text(debug_info=True)
    # op locations: ``jit(cb_step)/lm_head/dot_general``, or relative to
    # the layer loop's body, ``attention/dot_general``
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for part in ("attention", "mlp", "lm_head", "sampling"):
        assert [n for n in names if re.search(rf"(^|/){part}/", n)], part
