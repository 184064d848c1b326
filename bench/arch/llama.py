"""Dense Llama: GQA attention with RoPE (split-half rotation), RMSNorm and
a SwiGLU MLP, every layer alike, an untied or tied head.

The program runs it as ``family="dense"``: one period of one dense block,
its layers stacked under ``periods/b0_dense``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench import weights, work
# _linear reads the tier's quantizers from bench.reference when it runs
from bench.reference import HIGHEST, _linear, _rms, _rope


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"],
        family="dense",
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        act="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )


def shapes(cfg: dict) -> dict:
    """Canonical weight shapes of a dense GQA SwiGLU decoder config."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ff, vp = cfg["intermediate_size"], weights.padded_vocab(cfg)
    return {
        "embed": (vp, d),
        "attn_norm": (L, d),
        "wq": (L, d, hq * hd),
        "wk": (L, d, hkv * hd),
        "wv": (L, d, hkv * hd),
        "wo": (L, hq * hd, d),
        "mlp_norm": (L, d),
        "w_gate": (L, d, ff),
        "w_up": (L, d, ff),
        "w_down": (L, ff, d),
        "final_norm": (d,),
        "head": (d, vp),
    }


# program tree path -> canonical name (stacked layers under periods/b0_dense)
_BLOCK = {
    ("attn_norm", "scale"): "attn_norm",
    ("attn", "q_proj", "w"): "wq",
    ("attn", "k_proj", "w"): "wk",
    ("attn", "v_proj", "w"): "wv",
    ("attn", "o_proj", "w"): "wo",
    ("mlp_norm", "scale"): "mlp_norm",
    ("mlp", "gate_proj", "w"): "w_gate",
    ("mlp", "up_proj", "w"): "w_up",
    ("mlp", "down_proj", "w"): "w_down",
}
_TOP = {
    ("embed", "embedding"): "embed",
    ("final_norm", "scale"): "final_norm",
    ("lm_head", "head", "w"): "head",
}


def leaf_name(keys: tuple):
    """The canonical name of the program parameter at tree path ``keys``."""
    if keys[:2] == ("periods", "b0_dense"):
        return _BLOCK.get(keys[2:])
    return _TOP.get(keys)


def logits(cfg: dict, W: dict, tokens, bits: int):
    """(S, vocab_size) float32 logits of every position of ``tokens`` at
    a tier of ``bits``."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    x = W["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    layers = {k: W[k] for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                                "w_gate", "w_up", "w_down")}

    def block(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = _rope(_linear(h, lw["wq"], bits).reshape(s, hq, hd), theta)
        k = _rope(_linear(h, lw["wk"], bits).reshape(s, hkv, hd), theta)
        v = _linear(h, lw["wv"], bits).reshape(s, hkv, hd)
        k = jnp.repeat(k, hq // hkv, axis=1)  # q-head h reads kv-head h // group
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd**-0.5
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(s, hq * hd)
        x = x + _linear(o, lw["wo"], bits)
        h = _rms(x, lw["mlp_norm"], eps)
        g = jax.nn.silu(_linear(h, lw["w_gate"], bits)) * _linear(h, lw["w_up"], bits)
        return x + _linear(g, lw["w_down"], bits), None

    x, _ = lax.scan(block, x, layers)
    x = _rms(x, W["final_norm"], eps)
    return _linear(x, W["head"], bits)[:, : cfg["vocab_size"]]


def linears(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every linear one token passes through, in order."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ff = cfg["intermediate_size"]
    block = [
        ("q_proj", d, hq * hd), ("k_proj", d, hkv * hd), ("v_proj", d, hkv * hd),
        ("o_proj", hq * hd, d), ("gate_proj", d, ff), ("up_proj", d, ff),
        ("down_proj", ff, d),
    ]
    return block * L + [("lm_head", d, cfg["vocab_size"])]


def _per_token(cfg: dict) -> tuple[int, int, int]:
    """(ops of the blocks' linears, ops of the lm_head, attention ops per
    key) for one token."""
    lin = linears(cfg)
    blocks = sum(work.linear_ops(1, k, n) for name, k, n in lin if name != "lm_head")
    head = sum(work.linear_ops(1, k, n) for name, k, n in lin if name == "lm_head")
    attn = 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"]
    return blocks, head, attn


def decode_ops(cfg: dict, position: int) -> int:
    """Model operations of decoding the token at 0-based ``position``:
    every weight touched once (2 ops each), the lm_head included, plus
    attention over the ``position + 1`` keys it sees (scores and
    weighted sum)."""
    blocks, head, attn = _per_token(cfg)
    return blocks + head + attn * (position + 1)


def prompt_ops(cfg: dict, length: int) -> int:
    """Model operations of prefilling a ``length``-token prompt: every
    position through the blocks with causal attention, the lm_head once
    (only the last position's logits are computed)."""
    blocks, head, attn = _per_token(cfg)
    return length * blocks + head + attn * length * (length + 1) // 2
