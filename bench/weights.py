"""Seeded model weights, made by the benchmark and not by the program.

One jitted call draws every weight on the device from ``--seed`` in the
bench's own layout (``canonical``: the names and shapes that the
configuration's architecture, ``bench/arch/<model_type>.py``, gives). The
program is handed the same arrays rearranged into its parameter tree
(``to_program_tree``); the reference reads the canonical layout directly,
so it takes nothing the program made.

Distributions: linear weights N(0, 1/d_in), the embedding N(0, 1/d_model),
RMSNorm scales 1 + 0.1·N(0, 1) (random, so a norm that is not applied
shows in the comparison). Weights are bf16, as the program serves them;
norm scales are f32.
"""

from __future__ import annotations

import functools

import numpy as np

from bench import arch


def seed_key(seed: int):
    """A threefry key from any whole-number seed (wider than 32 bits too)."""
    import jax
    import jax.numpy as jnp

    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32), impl="threefry2x32")


def shapes(cfg: dict) -> dict:
    """Canonical weight name -> shape of ``cfg``'s architecture."""
    return arch.for_config(cfg).shapes(cfg)


def padded_vocab(cfg: dict) -> int:
    """Vocabulary rows held: rounded up to 256 (padding is never sampled)."""
    return -(-cfg["vocab_size"] // 256) * 256


@functools.lru_cache(maxsize=None)
def _generator(shape_items: tuple):
    import jax
    import jax.numpy as jnp

    names = [n for n, _ in shape_items]

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, (name, shape) in zip(keys, shape_items):
            z = jax.random.normal(k, shape, jnp.float32)
            if name.endswith("norm"):
                out[name] = 1.0 + 0.1 * z
            elif name == "embed":
                out[name] = (z * shape[1] ** -0.5).astype(jnp.bfloat16)
            else:
                out[name] = (z * shape[-2] ** -0.5).astype(jnp.bfloat16)
        return out

    return gen


def canonical(cfg: dict, seed: int) -> dict:
    """Every weight of ``cfg`` drawn on the default device from ``seed``."""
    return _generator(tuple(sorted(shapes(cfg).items())))(seed_key(seed))


def _path_keys(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "idx", p)) for p in path)


def to_program_tree(canon: dict, template, cfg: dict):
    """Lay ``canon`` out as the program's parameter tree ``template`` (a
    tree of ShapeDtypeStructs, e.g. ``jax.eval_shape`` of its init), each
    leaf named by ``cfg``'s architecture (``leaf_name``).

    Raises if a leaf of the program's layout has no name, or a shape or
    dtype differs: the seam to the program has moved."""
    import jax

    name_of = arch.for_config(cfg).leaf_name

    def pick(path, leaf):
        keys = _path_keys(path)
        name = name_of(keys)
        if name is None:
            raise RuntimeError(f"program parameter {'/'.join(map(str, keys))} has no bench weight")
        arr = canon[name]
        if tuple(arr.shape) != tuple(leaf.shape) or arr.dtype != leaf.dtype:
            raise RuntimeError(
                f"program parameter {'/'.join(map(str, keys))} is {leaf.shape} {leaf.dtype}; "
                f"bench weight {name} is {arr.shape} {arr.dtype}"
            )
        return arr

    return jax.tree_util.tree_map_with_path(pick, template)
