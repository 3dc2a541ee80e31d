"""Random weights drawn from a run's seed, by name, in float32, at the
shapes the configuration's architecture module gives (``bench/archs/``).

The benchmark hands these to the program (``bench/model.py`` puts them in the
program's parameter tree) and the plain reference draws them again from the
same seed.  Nothing here imports the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02
NORM_STD = 0.1


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return seed >> 32, seed & 0xFFFFFFFF


def base_key(hi, lo):
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0), hi), lo)


def draw(arch, c: dict, hi, lo) -> dict:
    """Every weight of configuration ``c`` (architecture module ``arch``)
    for seed words ``(hi, lo)``.  Norm entries are offsets: the gain is
    ``1 + offset``."""
    key = base_key(hi, lo)
    out = {}
    for i, (name, (shape, std)) in enumerate(sorted(arch.shapes(c).items())):
        k = jax.random.fold_in(key, i)
        out[name] = jax.random.normal(k, shape, jnp.float32) * (
            NORM_STD if std is None else std
        )
    return out
