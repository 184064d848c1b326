"""A configuration's model code is found by its ``model_type``
(``bench/arch/<model_type>.py``), and the dense Llama module reads
exactly what the benchmark has always read: golden canonical weights,
program tree, reference logits and work counts. A change to any golden
value below changes what the benchmark measures."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, harness, reference, weights, work
from bench.tests import tiny

LLAMA_7B_8L = harness.load_json(harness.ROOT / "bench" / "configs" / "llama-7b-8L.json")

#: canonical weight of the tiny config at tiny.SEED: shape, dtype, sum
#: (float64) and the first 16 hex digits of the sha256 of its bytes
WEIGHTS = {
    "attn_norm": ((2, 64), "float32", 127.55860769748688, "976e0d7ec76e9fcc"),
    "embed": ((512, 64), "bfloat16", 2.6590747237205505, "2b13254e4c9c8370"),
    "final_norm": ((64,), "float32", 64.26907604932785, "956c3c768d13f88b"),
    "head": ((64, 512), "bfloat16", 4.844152957201004, "dea99d912f6707f5"),
    "mlp_norm": ((2, 64), "float32", 126.89558750391006, "ef63761cad3175c1"),
    "w_down": ((2, 96, 64), "bfloat16", -5.75748273730278, "c8e60ce11f0c773a"),
    "w_gate": ((2, 64, 96), "bfloat16", 5.966058850288391, "502077f10d56ac12"),
    "w_up": ((2, 64, 96), "bfloat16", 2.799429178237915, "2d5d6521e9764b1c"),
    "wk": ((2, 64, 32), "bfloat16", 1.1597067713737488, "505b3d07ef15df4e"),
    "wo": ((2, 64, 64), "bfloat16", -31.12668752670288, "2fd49b65e91712da"),
    "wq": ((2, 64, 64), "bfloat16", -7.582608938217163, "47e3e6e0d6787e94"),
    "wv": ((2, 64, 32), "bfloat16", -2.636402904987335, "9db7768ee8659423"),
}
#: program parameter path -> the canonical weight laid there
TREE = {
    "embed/embedding": "embed",
    "final_norm/scale": "final_norm",
    "lm_head/head/w": "head",
    "periods/b0_dense/attn/k_proj/w": "wk",
    "periods/b0_dense/attn/o_proj/w": "wo",
    "periods/b0_dense/attn/q_proj/w": "wq",
    "periods/b0_dense/attn/v_proj/w": "wv",
    "periods/b0_dense/attn_norm/scale": "attn_norm",
    "periods/b0_dense/mlp/down_proj/w": "w_down",
    "periods/b0_dense/mlp/gate_proj/w": "w_gate",
    "periods/b0_dense/mlp/up_proj/w": "w_up",
    "periods/b0_dense/mlp_norm/scale": "mlp_norm",
}
#: reference logits of TOKENS on the tiny config at a tier: sum (float64)
#: and digest, as logits and through score (best, at the targets, argmax)
LOGITS = {
    8: (637.7960238003434, "8c093468cbeaa8cb",
        ("54e319ef3d3c7ab9", "3e55f9d066f373d7", "750d98457735b4fd")),
    4: (942.9602088293287, "bb4e313f955cae32",
        ("143c1095be525d30", "4dbb20d16983537f", "083df838596c7bfe")),
}
TOKENS = jnp.arange(40, dtype=jnp.int32) * 7 % tiny.CONFIG["vocab_size"]


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def program_template(cfg):
    from repro.models.transformer import init_params

    mcfg = harness.program_config(cfg)
    return jax.eval_shape(lambda: init_params(mcfg, jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def canon():
    return weights.canonical(tiny.CONFIG, tiny.SEED)


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_canonical_weights_of_a_seed(canon, name):
    shape, dtype, total, sha = WEIGHTS[name]
    a = np.asarray(canon[name])
    assert (a.shape, str(a.dtype)) == (shape, dtype)
    assert float(a.astype(np.float64).sum()) == total
    assert digest(a) == sha


def test_no_weight_more_or_less(canon):
    assert set(canon) == set(WEIGHTS)
    assert weights.shapes(tiny.CONFIG) == {k: v[0] for k, v in WEIGHTS.items()}


def test_program_tree_lays_out_the_canonical_weights(canon):
    tree = weights.to_program_tree(canon, program_template(tiny.CONFIG), tiny.CONFIG)
    leaves = {path_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert set(leaves) == set(TREE)
    for path, name in TREE.items():
        assert leaves[path] is canon[name]
        assert (leaves[path].shape, str(leaves[path].dtype)) == WEIGHTS[name][:2]


@pytest.mark.parametrize("bits", sorted(LOGITS))
def test_reference_logits(canon, bits):
    total, sha, scored = LOGITS[bits]
    lg = jax.jit(lambda W, t: reference.logits(tiny.CONFIG, W, t, bits))(canon, TOKENS)
    assert lg.shape == (40, tiny.CONFIG["vocab_size"])
    assert float(np.asarray(lg, np.float64).sum()) == total
    assert digest(lg) == sha
    out = reference.score(tiny.CONFIG, canon, TOKENS, jnp.stack([TOKENS, TOKENS]), bits)
    assert tuple(digest(a) for a in out) == scored


def test_llama_7b_8L_program_config_and_shapes():
    m = harness.program_config(LLAMA_7B_8L)
    assert (m.name, m.family, m.n_layers, m.d_model, m.vocab_size, m.n_heads, m.n_kv_heads,
            m.head_dim, m.d_ff, m.rope_theta, m.act, m.tie_embeddings) == (
        "llama-7b-8L", "dense", 8, 4096, 32000, 32, 32, 128, 11008, 10000.0, "swiglu", False)
    L, d, ff = 8, 4096, 11008
    assert weights.shapes(LLAMA_7B_8L) == {
        "embed": (32000, d), "attn_norm": (L, d), "wq": (L, d, d), "wk": (L, d, d),
        "wv": (L, d, d), "wo": (L, d, d), "mlp_norm": (L, d), "w_gate": (L, d, ff),
        "w_up": (L, d, ff), "w_down": (L, ff, d), "final_norm": (d,), "head": (d, 32000),
    }


def test_llama_7b_8L_program_tree():
    cfg = LLAMA_7B_8L
    shapes = weights.shapes(cfg)
    canon = {k: jax.ShapeDtypeStruct(s, jnp.float32 if k.endswith("norm") else jnp.bfloat16)
             for k, s in shapes.items()}
    tree = weights.to_program_tree(canon, program_template(cfg), cfg)
    leaves = {path_name(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert {p: leaves[p] for p in TREE} == {p: canon[n] for p, n in TREE.items()}
    assert set(leaves) == set(TREE)


def test_llama_7b_8L_work_counts():
    d, ff = 4096, 11008
    block = [("q_proj", d, d), ("k_proj", d, d), ("v_proj", d, d), ("o_proj", d, d),
             ("gate_proj", d, ff), ("up_proj", d, ff), ("down_proj", ff, d)]
    assert work.linears(LLAMA_7B_8L) == block * 8 + [("lm_head", d, 32000)]
    assert [work.decode_ops(LLAMA_7B_8L, p) for p in (0, 127, 255)] == [
        3500277760, 3516923904, 3533701120]
    assert work.prompt_ops(LLAMA_7B_8L, 128) == 415808618496


def test_every_module_gives_the_contract():
    for name in arch.names():
        mod = arch.for_config({"model_type": name})
        for fn in ("program_config", "shapes", "leaf_name", "logits", "linears",
                   "decode_ops", "prompt_ops"):
            assert callable(getattr(mod, fn)), (name, fn)


@pytest.mark.parametrize("cfg", [{"model_type": "no_such_model"}, {"name": "no model_type"}],
                         ids=["unknown", "missing"])
def test_an_unknown_or_missing_model_type_stops_the_run(cfg):
    with pytest.raises(arch.UnknownArchitecture, match="llama"):
        arch.for_config(cfg)
    tiny_cfg = {k: v for k, v in tiny.CONFIG.items() if k != "model_type"} | cfg
    with pytest.raises(arch.UnknownArchitecture):
        harness.program_config(tiny_cfg)


@pytest.fixture
def arch_dir(tmp_path, monkeypatch):
    """A directory of architectures holding a copy of the Llama module
    under another ``model_type``, put in the loader's place."""
    (tmp_path / "dense_copy.py").write_text((arch.ARCH_DIR / "llama.py").read_text())
    monkeypatch.setattr(arch, "ARCH_DIR", tmp_path)
    return tmp_path


def test_a_new_architecture_is_a_new_file(arch_dir):
    """A module and a configuration that names it run through
    ``harness.run`` to a correct result, with no other file edited."""
    cfg = {**tiny.CONFIG, "model_type": "dense_copy"}
    assert arch.names() == ["dense_copy"]
    r = tiny.run(config=cfg)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0


def test_every_key_of_the_configuration_reaches_the_reference(arch_dir, canon):
    """The reference's jitted scorer is cached on the whole configuration,
    so a key only a new architecture reads is not lost."""
    (arch_dir / "scaled.py").write_text(
        (arch_dir / "dense_copy.py").read_text()
        + "\n\n_logits = logits\n\n\ndef logits(cfg, W, tokens, bits):\n"
        "    return _logits(cfg, W, tokens, bits) * cfg['logit_scale']\n")
    targets = jnp.stack([TOKENS, TOKENS])
    one, two = ({**tiny.CONFIG, "model_type": "scaled", "logit_scale": s} for s in (1.0, 2.0))
    best1 = np.asarray(reference.score(one, canon, TOKENS, targets, 8)[0])
    best2 = np.asarray(reference.score(two, canon, TOKENS, targets, 8)[0])
    np.testing.assert_array_equal(best2, 2 * best1)
    # keys that describe the run, not the model, share one scorer
    scorers = reference._scorer.cache_info().currsize
    same = {**one, "serving": {}, "assumed": ["x"], "published": {}, "deployment": "y"}
    np.testing.assert_array_equal(reference.score(same, canon, TOKENS, targets, 8)[0], best1)
    assert reference._scorer.cache_info().currsize == scorers
