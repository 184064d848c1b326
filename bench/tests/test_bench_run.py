"""Whole runs at a size a CPU test run can hold (``tiny.py``): the look
for a chip is skipped, everything else is a run as on the chip."""

import json
import math
import re

import jax.numpy as jnp
import pytest

from bench import harness, seam
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def sound():
    return tiny.run(control=True)


def test_result_line_matches_the_contract(sound):
    line = json.loads(json.dumps(sound))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    units = {m["name"]: m["unit"] for m in tiny.cell().end_to_end}
    assert set(line["metrics"]) == set(units)
    for name, m in line["metrics"].items():
        assert NAME.match(name) and m["unit"] == units[name]
        assert m["value"] > 0 and math.isfinite(m["value"])
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_no_compilation_inside_the_window(sound):
    assert sound["checks"]["compiles_in_window"]["value"] == 0


def test_the_control_fails_the_limit_the_program_meets(sound):
    """The reference at the control precision (int4 for the w8 tier) in
    the program's place reads a gap over the limit."""
    gap = sound["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert sound["control"]["max_logit_gap"] > gap["limit"]
    assert sound["control"]["max_logit_gap"] >= 3 * gap["value"]


def _altered(orig, vocab):
    def sample(logits, temps, key):
        return (orig(logits, temps, key) + 1) % vocab

    return sample


def _half_batch(orig):
    def sample(logits, temps, key):
        tok = orig(logits, temps, key)
        half = tok.shape[0] // 2
        return tok.at[tok.shape[0] - half:].set(tok[:half]) if half else tok

    return sample


def _state_unchanged(orig):
    def make(*a, **kw):
        step = orig(*a, **kw)

        def cb_step(params, cache, *rest):
            out = step(params, cache, *rest)
            return (out[0], cache) + tuple(out[2:])

        return cb_step

    return make


@pytest.mark.parametrize("fault", ["token_altered", "half_batch", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    import repro.launch.serve as serve
    from repro.launch import sampling

    if fault == "token_altered":
        monkeypatch.setattr(sampling, "sample_tokens",
                            _altered(sampling.sample_tokens, tiny.CONFIG["vocab_size"]))
    elif fault == "half_batch":
        monkeypatch.setattr(sampling, "sample_tokens", _half_batch(sampling.sample_tokens))
    else:
        monkeypatch.setattr(serve, "make_cb_decode_step",
                            _state_unchanged(serve.make_cb_decode_step))
    r = tiny.run(check_requests=64)  # every request served in the window
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > r["checks"]["max_logit_gap"]["limit"]


def test_a_missing_seam_fails_loudly(monkeypatch):
    import repro.launch.serve as serve

    monkeypatch.delattr(serve, "SlotScheduler")
    with pytest.raises(seam.SeamMissing):
        tiny.run()


def test_no_tpu_is_refused():
    c = tiny.cell()
    with pytest.raises(harness.Refused, match="no TPU"):
        harness.run(c.name, 1, 1.0, False, cell=c, compile_cache=False)


def test_the_reference_agrees_with_the_program_in_float32():
    """The program's own forward, dense and in float32, on the bench's
    weights: the reference reads the same model."""
    import jax

    from bench import reference, weights
    from repro.models.transformer import forward, init_params

    cfg = tiny.CONFIG
    mcfg = harness.program_config(cfg)
    W = weights.canonical(cfg, tiny.SEED)
    tmpl = jax.eval_shape(lambda: init_params(mcfg, jax.random.PRNGKey(0)))
    P = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               weights.to_program_tree(W, tmpl, cfg))
    toks = jnp.arange(40, dtype=jnp.int32) * 7 % cfg["vocab_size"]
    with jax.default_matmul_precision("highest"):
        got, _, _ = forward(mcfg, P, {"tokens": toks[None]})
    # the reference's float path: the tier's quantizers turned into identities
    monkey = reference._quant_act, reference._quant_weight
    reference._quant_act = reference._quant_weight = lambda x, bits: x
    try:
        want = reference.logits(cfg, W, toks, 8)
    finally:
        reference._quant_act, reference._quant_weight = monkey
    assert jnp.max(jnp.abs(got[0, :, : cfg["vocab_size"]] - want)) < 1e-4
