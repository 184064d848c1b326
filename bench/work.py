"""Work the model asks for, computed from shapes alone.

The yardstick of ``plane_mm_roofline`` and ``mfu``. Which linears a
token passes through, and the operations of a decoded token or a prompt,
are the configuration's architecture's (``bench/arch/<model_type>.py``);
what a linear costs is this module's. The work of a linear is that of
the matrix product it computes, whatever computes it: a bit-serial route
that makes ``a_bits × w_bits`` int8 passes and a route that makes one
are charged the same operations. Bytes are what the tier has to read and
write at the least: the tier's prefix of the stored weight planes, the
int8 activations, the bf16 output and the f32 scales.
"""

from __future__ import annotations

from bench import arch

#: stored bits per weight and plane: a Booth signed digit is kept as a
#: magnitude bit plus a sign bit (two packed words per 32 weights)
BOOTH_BITS_PER_PLANE = 2


def linears(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every linear one token passes through, in order."""
    return arch.for_config(cfg).linears(cfg)


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


def decode_ops(cfg: dict, position: int) -> int:
    """Model operations of decoding the token at 0-based ``position``."""
    return arch.for_config(cfg).decode_ops(cfg, position)


def prompt_ops(cfg: dict, length: int) -> int:
    """Model operations of prefilling a ``length``-token prompt."""
    return arch.for_config(cfg).prompt_ops(cfg, length)
