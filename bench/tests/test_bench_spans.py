"""The readers of the engine loop's ``serve.*`` host spans, on a small
synthetic trace with the planes, lines and names a TPU v5e trace has."""

import types

import pytest

from bench import harness, tracefile
from bench.tests.test_bench_trace import E, Line, Plane, Profile

READERS = ("pull_idle_ms", "admit_idle_ms", "loop_idle_ms", "commit_iter_ms")


def spans():
    # window 1000..2000 ns: three iterations, the second one commits the
    # prefill whose chunk the first one ran
    return [
        E("serve.record", 900, 110),  # clipped to 1000-1010
        E("serve.control", 1010, 20),
        E("serve.admit#rid=3,slot=5#", 1030, 50),
        E("serve.chunk#rid=3,slot=5#", 1080, 40),
        E("_job_step", 1082, 36),  # the Python tracer's, inside a span
        E("serve.decode", 1120, 30),
        E("serve.pull", 1150, 250),
        E("serve.record", 1400, 40),
        E("serve.control", 1440, 20),
        E("serve.chunk#rid=3,slot=5#", 1460, 40),
        E("serve.commit#rid=3,slot=5#", 1500, 60),
        E("serve.decode", 1560, 40),
        E("serve.pull", 1600, 250),
        E("serve.record", 1850, 50),
        E("serve.control", 1900, 20),
        E("serve.decode", 1920, 20),
        E("serve.pull", 1940, 50),
        E("serve.record", 1990, 110),  # runs past the window
    ]


def profile(host):
    # device 0 idle in the window: 1005-1085, 1110-1125, 1390-1470,
    # 1495-1505, 1540-1565, 1840-1930 (300 ns in all)
    ops = [E("%fusion.1 = f32[8]{0} fusion(%x)", s, d) for s, d in
           ((1000, 5), (1085, 25), (1125, 265), (1470, 25), (1505, 35), (1565, 275),
            (1930, 100))]
    return Profile([
        Plane("/host:CPU", [Line("python3", [E(tracefile.WINDOW, 1000, 1000)] + host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", []), Line("XLA Ops", ops)]),
    ])


def read(name, host):
    ctx = types.SimpleNamespace(trace=tracefile.reduce(profile(host)))
    return harness.load_reader(name)(ctx)


@pytest.mark.parametrize("name, ns", [
    # pulls 1150-1400, 1600-1850, 1940-1990: idle 1390-1400, 1840-1850
    ("pull_idle_ms", 20),
    # admit+chunk 1030-1120, chunk+commit 1460-1560: idle 1030-1085,
    # 1110-1120, 1460-1470, 1495-1505, 1540-1560
    ("admit_idle_ms", 105),
    # 1000-1030, 1120-1150, 1400-1460, 1560-1600, 1850-1940, 1990-2000:
    # idle 1005-1030, 1120-1125, 1400-1460, 1560-1565, 1850-1930
    ("loop_idle_ms", 175),
])
def test_idle_by_phase_per_pull(name, ns):
    assert read(name, spans()) == pytest.approx(ns * 1e-6 / 3)


def test_the_phases_cover_the_window_idle():
    r = tracefile.reduce(profile(spans()))
    idle = tracefile.gaps([(e.start, e.end) for e in r.ops()], r.lo, r.hi)
    total = sum(read(n, spans()) for n in READERS[:3]) * 3
    assert total == pytest.approx(sum(b - a for a, b in idle) * 1e-6)


def test_commit_iteration_length():
    # pull ends 1400, 1850, 1990: the commit at 1500 lies in the first
    # iteration (450 ns); the second (140 ns) commits nothing
    assert read("commit_iter_ms", spans()) == pytest.approx(450e-6)


def test_commit_iterations_take_the_median():
    extra = [E("serve.commit", 1900, 10)]  # now both iterations commit
    assert read("commit_iter_ms", spans() + extra) == pytest.approx((450 + 140) / 2 * 1e-6)


@pytest.mark.parametrize("name", READERS)
def test_no_spans_reads_nothing(name):
    host = [E("_job_step", 1082, 36), E("_decode_pass", 1815, 90)]
    assert read(name, host) is None
    assert harness.load_reader(name)(types.SimpleNamespace(trace=None)) is None
