"""Weights of the Mellum configuration, made by the benchmark from the
run's seed, like ``weights.py`` for the dense decoder: drawn on the device
in one jitted call, float32, in the reference's layout
(``reference/mellum.py``); ``program_params`` hands the same arrays to the
program in its own parameter layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import weights as W


def shapes(dm: dict) -> dict:
    """Leaf name -> (shape, init) in the reference layout, as in
    ``weights.shapes``."""
    d, H, HK, Dh, F, V, L, E, n = (dm[k] for k in ("d", "H", "HK", "Dh",
                                                   "F", "V", "L", "E",
                                                   "held"))
    return {"embed": ((V, d), 0.02), "lm_head": ((d, V), d ** -0.5),
            "final_norm": ((d,), ("one", 0.1)),
            "ln1": ((L, d), ("one", 0.1)), "ln2": ((L, d), ("one", 0.1)),
            "wq": ((L, d, H * Dh), d ** -0.5),
            "wk": ((L, d, HK * Dh), d ** -0.5),
            "wv": ((L, d, HK * Dh), d ** -0.5),
            "wo": ((L, H * Dh, d), (H * Dh) ** -0.5),
            "router": ((L, d, E), d ** -0.5),
            "w_gate": ((L, n, d, F), d ** -0.5),
            "w_up": ((L, n, d, F), d ** -0.5),
            "w_down": ((L, n, F, d), F ** -0.5)}


@functools.lru_cache(maxsize=None)
def _maker(dm_items: tuple):
    spec = shapes(dict(dm_items))

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(sorted(spec)):
            shape, init = spec[name]
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (1.0 + init[1] * z if isinstance(init, tuple)
                         else init * z)
        return out

    return make


def make_weights(dm: dict, seed: int) -> dict:
    """Reference-layout float32 weights drawn on the device from ``seed``."""
    return _maker(tuple(sorted(dm.items())))(W.seed_key(seed))


def program_params(dm: dict, w: dict, abstract) -> dict:
    """The program's parameter tree (one stack "main" of "blk" blocks with
    an attention and a "moe" part) over the same arrays; only the
    query-head order of wq and wo is rearranged (``weights.py``).
    ``abstract`` is the program's own shape tree, which the result must
    match exactly."""
    HK, G = dm["HK"], dm["H"] // dm["HK"]
    wq, wo, _ = W._to_program_heads(HK, G, w["wq"], w["wo"], None)
    tree = {"tok_embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]},
            "stacks": {"main": {"blk": {
                "norm1": {"scale": w["ln1"]},
                "attn": {"wq": wq, "wk": w["wk"], "wv": w["wv"], "wo": wo},
                "norm2": {"scale": w["ln2"]},
                "moe": {k: w[k] for k in ("router", "w_gate", "w_up",
                                          "w_down")}}}}}
    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
    have = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    if want != have:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree:\n want {want}\n have {have}")
    return tree
