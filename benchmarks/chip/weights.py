"""Weights made by the benchmark from the run's seed.

The benchmark, not the program, makes the weights, so that the plain
reference can use them without taking anything the program made. They
are drawn on the device in one jitted call, in float32 (the type the
configurations serve), in the reference's layout (``reference/qwen.py``);
``program_params`` hands the same arrays to the program in its own
parameter layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the program's query-head order: head p = g * HK + k reads kv head
# p % HK, where the reference's head h = k * G + g reads kv head h // G


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64 bits included."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(dm: dict) -> dict:
    """Leaf name -> (shape, init) in the reference layout. init is the
    standard deviation of a normal draw, or ("one", sd) for a scale
    drawn around 1."""
    d, H, HK, Dh, F, V, L = (dm[k] for k in
                             ("d", "H", "HK", "Dh", "F", "V", "L"))
    out = {"embed": ((V, d), 0.02), "final_norm": ((d,), ("one", 0.1)),
           "ln1": ((L, d), ("one", 0.1)), "ln2": ((L, d), ("one", 0.1)),
           "wq": ((L, d, H * Dh), d ** -0.5),
           "wk": ((L, d, HK * Dh), d ** -0.5),
           "wv": ((L, d, HK * Dh), d ** -0.5),
           "wo": ((L, H * Dh, d), (H * Dh) ** -0.5),
           "w_gate": ((L, d, F), d ** -0.5), "w_up": ((L, d, F), d ** -0.5),
           "w_down": ((L, F, d), F ** -0.5)}
    if dm["bias"]:
        out.update(bq=((L, H * Dh), 0.1), bk=((L, HK * Dh), 0.1),
                   bv=((L, HK * Dh), 0.1))
    if dm["qk_norm"]:
        out.update(q_norm=((L, Dh), ("one", 0.1)),
                   k_norm=((L, Dh), ("one", 0.1)))
    return out


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
    return _maker(tuple(sorted(dm.items())))(seed_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _to_program_heads(HK: int, G: int, wq, wo, bq):
    L, d, n = wq.shape
    Dh = n // (HK * G)
    wq = wq.reshape(L, d, HK, G, Dh).transpose(0, 1, 3, 2, 4).reshape(L, d, n)
    wo = wo.reshape(L, HK, G, Dh, d).transpose(0, 2, 1, 3, 4).reshape(L, n, d)
    if bq is not None:
        bq = bq.reshape(L, HK, G, Dh).transpose(0, 2, 1, 3).reshape(L, n)
    return wq, wo, bq


def program_params(dm: dict, w: dict, abstract) -> dict:
    """The program's parameter tree (repro's dense decoder: one stack
    "main" of "blk" blocks) over the same arrays; only the query-head
    order of wq, bq and wo is rearranged. ``abstract`` is the program's
    own shape tree, which the result must match exactly."""
    HK, G = dm["HK"], dm["H"] // dm["HK"]
    wq, wo, bq = _to_program_heads(HK, G, w["wq"], w["wo"], w.get("bq"))
    attn = {"wq": wq, "wk": w["wk"], "wv": w["wv"], "wo": wo}
    if dm["bias"]:
        attn.update(bq=bq, bk=w["bk"], bv=w["bv"])
    if dm["qk_norm"]:
        attn.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    tree = {"tok_embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "stacks": {"main": {"blk": {
                "norm1": {"scale": w["ln1"]}, "attn": attn,
                "norm2": {"scale": w["ln2"]},
                "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}}}}
    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
    have = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    if want != have:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree:\n want {want}\n have {have}")
    return tree
