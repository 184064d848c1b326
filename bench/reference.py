"""Plain reference of the served model, independent of the program.

The model is the configuration's architecture (``bench/arch/<model_type>.py``
gives its ``logits``), as the configuration file states it, with every
linear at the precision the cell serves, computed in float32 with every
matmul at ``Precision.HIGHEST``. No kernel, cache or batching: one whole
sequence at a time, the layers scanned one by one with each layer's bf16
weights widened to f32 inside the scan, so only one layer is ever held in
f32. This module holds what every architecture shares: the tier's
quantizers, the quantized linear, RMSNorm and RoPE.

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
import json

import jax
import jax.numpy as jnp
from jax import lax

from bench import arch

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
    return arch.for_config(cfg).logits(cfg, W, tokens, bits)


#: configuration keys that describe the run, not the model
NOT_MODEL = ("serving", "assumed", "published", "deployment")


@functools.lru_cache(maxsize=None)
def _scorer(cfg_json: str, bits: int):
    cfg = json.loads(cfg_json)

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
    model = {k: v for k, v in cfg.items() if k not in NOT_MODEL}
    return _scorer(json.dumps(model, sort_keys=True), bits)(W, tokens, targets)
