"""The trace reduction on a small synthetic trace with the planes, lines
and names a TPU v5e trace has."""

import pytest

from bench import tracefile


class E:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.end_ns = start + dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def profile():
    # window 1000..2000 ns; device ops cover 1000-1300, 1250-1500 (overlap),
    # 1700-1800 inside a while op 1690-1810, and 1900-2100 (clipped at
    # 2000): busy 500 + 120 + 100 = 720
    k = "%fused_plane_linear.{} = bf16[32,1024]{{1,0:T(8,128)(2,1)}} custom-call(s32[64] %a)"
    ops = [E("%fusion.1 = f32[8]{0} fusion(%x)", 1000, 300), E(k.format(3), 1250, 250),
           E(k.format(7), 1700, 100), E("%while.5 = (s32[]) while(%t)", 1690, 120),
           E("%fusion.2 = f32[8]{0} fusion(%y)", 1900, 200), E("%early = f32[1] x()", 500, 100)]
    mods = [E("jit_cb_step(17)", 1000, 500), E("jit_chunk_step(3)", 1700, 100),
            E("jit_chunk_step(4)", 1900, 200)]
    host = [E(tracefile.WINDOW, 1000, 1000), E("run", 900, 1200),
            E("_job_step", 1550, 100), E("_decode_pass", 1815, 90), E("_value", 1820, 60)]
    return Profile([
        Plane("/host:CPU", [Line("python3", host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", mods), Line("XLA Ops", ops)]),
        Plane("/device:TPU:0 SparseCore", []),
    ])


def test_busy_and_window():
    r = tracefile.reduce(profile())
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(720e-9)


def test_programs_by_module_name():
    p = tracefile.reduce(profile()).programs()
    assert p["jit_cb_step"] == (pytest.approx(500e-9), 1)
    assert p["jit_chunk_step"] == (pytest.approx(200e-9), 2)  # second clipped to 100


def test_ops_and_top_ops():
    r = tracefile.reduce(profile())
    assert len(r.ops("^%fused_plane_linear")) == 2
    assert r.ops("plane")[0].name.startswith("%fused_plane_linear.3 = bf16[32,1024]")
    top = dict(r.top_ops())
    assert top["fused_plane_linear bf16[32,1024]"] == pytest.approx(350e-9)
    assert top["fusion f32[8]"] == pytest.approx(400e-9)
    assert not [k for k in top if k.startswith(("early", "while"))]


def test_idle_gaps_named_by_the_host():
    gaps = tracefile.reduce(profile()).idle_gaps()
    # 1500-1690 (mid 1595: _job_step; "run" began before the window) and
    # 1810-1900 (mid 1855: _value inside _decode_pass)
    assert [g[0] for g in gaps] == ["_job_step", "_decode_pass > _value"]
    assert gaps[0][1] == pytest.approx(190e-9) and gaps[1][1] == pytest.approx(90e-9)


def test_union_and_gaps_helpers():
    assert tracefile.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracefile.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_a_trace_without_the_window_or_device_is_refused():
    p = profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    with pytest.raises(RuntimeError, match="host span"):
        tracefile.reduce(p)
    with pytest.raises(RuntimeError, match="device op"):
        tracefile.reduce(Profile([profile().planes[0]]))


ROOFLINE_OPS = {
    # the words of one layer, sliced out of the stack before the call
    "layer": "%fused_plane_linear.12 = bf16[32,4096]{1,0:T(8,128)(2,1)} custom-call("
             "s32[64]{0:T(128)} %p, s8[32,4096]{1,0:T(8,128)(4,1)} %x, "
             "s32[8,128,4096]{2,1,0:T(8,128)} %mag, s32[8,128,4096]{2,1,0:T(8,128)} %sign, "
             "f32[32,1]{1,0} %as, f32[1,4096]{1,0} %ws), custom_call_target=\"tpu_custom_call\"",
    # the whole stack of 8 layers and the layer's index, read in place
    "stack": "%fused_plane_linear = bf16[32,4096]{1,0:T(8,128)(2,1)} custom-call("
             "s32[1]{0} %layer, s32[64]{0:T(128)} %p, s8[32,4096]{1,0:T(8,128)(4,1)} %x, "
             "s32[8,8,128,4096]{3,2,1,0:T(8,128)} %mag, s32[8,8,128,4096]{3,2,1,0} %sign, "
             "f32[32,1]{1,0} %as, f32[1,4096]{1,0} %ws), custom_call_target=\"tpu_custom_call\"",
}


@pytest.mark.parametrize("form", sorted(ROOFLINE_OPS))
def test_the_roofline_reads_one_layer_of_words_in_either_form(form):
    """M=32, K=4096, N=4096 at 8 planes, 100 us a call: one layer's work."""
    import types

    from bench import harness, work

    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    calls = [tracefile.Event(ROOFLINE_OPS[form], t, t + 100_000) for t in (0, 200_000)]
    trace = types.SimpleNamespace(ops=lambda pattern: calls)
    ctx = types.SimpleNamespace(trace=trace, work=work, peaks=peaks)
    got = harness.load_reader("plane_mm_roofline")(ctx)
    assert got == 100.0 * 2 * work.least_seconds(32, 4096, 4096, 8, peaks) / 200e-6


@pytest.mark.parametrize("old,new", [
    ("s8[32,4096]", "bf16[32,4096]"),                  # activations that are not int8
    ("s32[64]{0:T(128)} %p, ", ""),                    # no pair-weight vector
], ids=["bf16-activations", "no-pair-weights"])
def test_the_roofline_refuses_a_call_it_cannot_read(old, new):
    import types

    from bench import harness, work

    name = ROOFLINE_OPS["layer"].replace(old, new)
    assert name != ROOFLINE_OPS["layer"]
    trace = types.SimpleNamespace(ops=lambda pattern: [tracefile.Event(name, 0, 100)])
    ctx = types.SimpleNamespace(trace=trace, work=work, peaks={})
    with pytest.raises(RuntimeError, match="unparsed fused_plane_linear"):
        harness.load_reader("plane_mm_roofline")(ctx)
