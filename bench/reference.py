"""Plain reference of the served model, independent of the program.

A dense GQA decoder with RoPE (split-half rotation), RMSNorm and a SwiGLU
MLP, as the configuration file states it, with every linear at the
precision the cell serves, computed in float32 with every matmul at
``Precision.HIGHEST``. No kernel, cache or batching: one whole sequence
at a time, the layers scanned one by one with each layer's bf16 weights
widened to f32 inside the scan, so only one layer is ever held in f32.

The precision of a tier of ``bits`` (the bit-plane storage contract):
each weight column is quantized symmetrically to the stored 8 bits
(``scale = amax / 127``); a tier of ``bits`` reads the ``bits`` most
significant of its eight Booth signed-digit planes, which is the stored
integer rounded to a multiple of ``2**(8 - bits)``, halves up. Each
token's activations are quantized symmetrically to ``bits`` (``scale =
amax / (2**(bits-1) - 1)``). K and V stay f32. The control of a cell is
this forward at the next tier down.

It imports nothing of the program. Weights come from ``bench.weights``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


STORED_BITS = 8


def _quant_act(x, bits):
    """Per-token symmetric quantization to ``bits`` (dequantized)."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale


def _quant_weight(w, bits):
    """Per-column 8-bit storage, read at a tier of ``bits`` planes."""
    qmax = 2 ** (STORED_BITS - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-8) / qmax
    q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax)
    step = 2.0 ** (STORED_BITS - bits)
    return jnp.floor(q / step + 0.5) * step * scale


def _linear(x, w, bits):
    return jnp.matmul(_quant_act(x, bits), _quant_weight(w.astype(jnp.float32), bits),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, H, D), positions 0..S-1; the first and second halves of D
    rotate as pairs (the Llama / Hugging Face layout)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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


@functools.lru_cache(maxsize=None)
def _scorer(cfg_items: tuple, bits: int):
    cfg = dict(cfg_items)

    @jax.jit
    def score(W, tokens, targets):
        lg = logits(cfg, W, tokens, bits)
        at = jnp.take_along_axis(lg[None], targets[..., None], axis=-1)[..., 0]
        return jnp.max(lg, axis=-1), at, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    return score


def score(cfg: dict, W: dict, tokens, targets, bits: int):
    """For every position p of ``tokens`` (a fixed-length, zero-padded
    sequence): the best logit, the logit of ``targets[:, p]`` (one row per
    target set) and the argmax. Positions past the real sequence are
    garbage the caller does not read; causality keeps them out of the
    rest."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "vocab_size")
    return _scorer(tuple((k, cfg[k]) for k in keys), bits)(W, tokens, targets)
