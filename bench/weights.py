"""Random weights drawn from a run's seed, by name, in float32.

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


def dims(c: dict) -> dict:
    """Sizes a configuration file states, under short names."""
    return {
        "L": c["num_hidden_layers"], "d": c["hidden_size"],
        "H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
        "Dh": c["head_dim"], "F": c["intermediate_size"], "V": c["vocab_size"],
        "qk_norm": bool(c.get("qk_norm", False)),
    }


def shapes(c: dict) -> dict:
    """name -> (shape, std); a std of None marks a norm gain's offset."""
    m = dims(c)
    L, d, H, KV, Dh, F, V = (m[k] for k in ("L", "d", "H", "KV", "Dh", "F", "V"))
    out_std = STD / (2 * L) ** 0.5
    s = {
        "embed": ((V, d), STD),
        "final_norm": ((d,), None),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, H * Dh), STD),
        "wk": ((L, d, KV * Dh), STD),
        "wv": ((L, d, KV * Dh), STD),
        "wo": ((L, H * Dh, d), out_std),
        "ffn_norm": ((L, d), None),
        "w_gate": ((L, d, F), STD),
        "w_up": ((L, d, F), STD),
        "w_down": ((L, F, d), out_std),
    }
    if m["qk_norm"]:
        s["q_norm"] = ((L, Dh), None)
        s["k_norm"] = ((L, Dh), None)
    return s


def draw(c: dict, hi, lo) -> dict:
    """Every weight of configuration ``c`` for seed words ``(hi, lo)``.
    Norm entries are offsets: the gain is ``1 + offset``."""
    key = base_key(hi, lo)
    out = {}
    for i, (name, (shape, std)) in enumerate(sorted(shapes(c).items())):
        k = jax.random.fold_in(key, i)
        out[name] = jax.random.normal(k, shape, jnp.float32) * (
            NORM_STD if std is None else std
        )
    return out
