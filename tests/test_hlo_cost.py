"""Scan-aware HLO cost walker: validated against cost_analysis() on
scan-free programs and against hand counts on scanned/sharded ones."""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch import hlo_cost

W = jnp.zeros((64, 96), jnp.float32)
X = jnp.ones((32, 64), jnp.float32)


def _cost_analysis(compiled) -> dict:
    """compiled.cost_analysis() returns a dict on jax>=0.5, [dict] before."""
    ca = compiled.cost_analysis()
    return ca[0] if isinstance(ca, list) else ca


def test_scan_free_matches_cost_analysis():
    c = jax.jit(lambda x: jnp.tanh(x @ W)).lower(X).compile()
    got = hlo_cost.analyze(c.as_text())
    ca = _cost_analysis(c)
    assert got.flops == pytest.approx(float(ca["flops"]), rel=0.05)
    assert got.flops == pytest.approx(2 * 32 * 64 * 96, rel=0.05)


def test_scan_multiplies_by_trip_count():
    def f(x):
        def body(cr, _):
            return (jnp.tanh(cr @ W @ W.T), None)

        y, _ = lax.scan(body, x, None, length=7)
        return y

    c = jax.jit(f).lower(X).compile()
    got = hlo_cost.analyze(c.as_text())
    expect = 7 * (2 * 32 * 64 * 96 * 2)
    assert got.flops == pytest.approx(expect, rel=0.02)
    assert got.unknown_trip_whiles == 0
    # cost_analysis undercounts by the trip count — the bug we fix
    assert float(_cost_analysis(c)["flops"]) < expect / 3


def test_nested_scans_multiply():
    def f(x):
        def inner(cr, _):
            return (cr @ W @ W.T, None)

        def outer(cr, _):
            y, _ = lax.scan(inner, cr, None, length=3)
            return (y, None)

        y, _ = lax.scan(outer, x, None, length=5)
        return y

    c = jax.jit(f).lower(X).compile()
    got = hlo_cost.analyze(c.as_text())
    expect = 5 * 3 * (2 * 32 * 64 * 96 * 2)
    assert got.flops == pytest.approx(expect, rel=0.02)


@pytest.mark.slow
def test_collectives_in_scan_counted_per_iteration():
    import os
    import subprocess
    import sys
    import textwrap
    import json

    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_cost

        W = jnp.zeros((64, 96), jnp.float32)
        X = jnp.ones((32, 64), jnp.float32)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
        with mesh:
            def f(x, w):
                def body(cr, _):
                    y = lax.with_sharding_constraint(
                        cr @ w, NamedSharding(mesh, P(None, "model")))
                    z = lax.with_sharding_constraint(
                        y @ w.T, NamedSharding(mesh, P(None, None)))
                    return (z, None)
                y, _ = lax.scan(body, x, None, length=5)
                return y
            j = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "model"))))
            c = j.lower(X, W).compile()
        got = hlo_cost.analyze(c.as_text())
        print("RESULT" + json.dumps({
            "flops": got.flops,
            "colls": {k: [v["count"], v["wire"]] for k, v in got.collectives.items()},
        }))
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        env=dict(os.environ, PYTHONPATH="src"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    # per-device flops x devices == global math
    assert res["flops"] * 8 == pytest.approx(5 * (2 * 32 * 64 * 96 * 2), rel=0.02)
    counts = {k: v[0] for k, v in res["colls"].items()}
    assert any(c >= 5 for c in counts.values()), counts
    assert all(v[1] > 0 for v in res["colls"].values())


def test_group_size_parsing():
    assert hlo_cost._group_size("replica_groups=[16,32]<=[512]") == 32
    assert hlo_cost._group_size("replica_groups={{0,1,2,3}}") == 4
    assert hlo_cost._group_size("no groups here") == 1


def test_wire_factors():
    # all-reduce ~ 2(g-1)/g, all-gather ~ (g-1) x shard
    assert hlo_cost._wire_bytes("all-reduce", 100, 4) == pytest.approx(150)
    assert hlo_cost._wire_bytes("all-gather", 100, 4) == pytest.approx(300)
    assert hlo_cost._wire_bytes("reduce-scatter", 100, 4) == pytest.approx(75)
    assert hlo_cost._wire_bytes("all-reduce", 100, 1) == 0.0
