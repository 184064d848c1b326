"""Work the model asks for, computed from shapes alone.

The yardstick of ``plane_mm_roofline`` and ``mfu``. The work of a linear
is that of the matrix product it computes, whatever computes it: a
bit-serial route that makes ``a_bits × w_bits`` int8 passes and a route
that makes one are charged the same operations. Bytes are what the tier
has to read and write at the least: the tier's prefix of the stored
weight planes, the int8 activations, the bf16 output and the f32 scales.
"""

from __future__ import annotations

#: stored bits per weight and plane: a Booth signed digit is kept as a
#: magnitude bit plus a sign bit (two packed words per 32 weights)
BOOTH_BITS_PER_PLANE = 2


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


def linear_ops(m: int, k: int, n: int) -> int:
    """Integer multiply-adds of an (M, K) x (K, N) product, counted as 2."""
    return 2 * m * k * n


def linear_bytes(m: int, k: int, n: int, w_bits: int) -> int:
    """Least bytes one call moves at a tier of ``w_bits`` weight planes."""
    weights = w_bits * BOOTH_BITS_PER_PLANE * k * n // 8
    return weights + m * k + 2 * m * n + 4 * (m + n)


def least_seconds(m: int, k: int, n: int, w_bits: int, peaks: dict) -> float:
    """The roofline: the larger of compute time at the int8 peak and
    memory time at the HBM peak."""
    return max(
        linear_ops(m, k, n) / peaks["int8_ops_per_s"],
        linear_bytes(m, k, n, w_bits) / peaks["hbm_bytes_per_s"],
    )


def _per_token(cfg: dict) -> tuple[int, int, int]:
    """(ops of the blocks' linears, ops of the lm_head, attention ops per
    key) for one token."""
    lin = linears(cfg)
    blocks = sum(linear_ops(1, k, n) for name, k, n in lin if name != "lm_head")
    head = sum(linear_ops(1, k, n) for name, k, n in lin if name == "lm_head")
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
