"""What the model-serving drivers share: the program's configuration
checked against the configuration file, weights in both layouts, the
reduction of a traced window, and the comparison's lines."""
from __future__ import annotations

import numpy as np

import trace_reduce
import weights as W
from reference import qwen as R

# configuration-file key -> the program's ModelConfig attribute
SIZE_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
             "qkv_bias": "qkv_bias", "qk_norm": "qk_norm",
             "tie_word_embeddings": "tie_embeddings"}


def program_config(config: dict, reduced: bool = False):
    """The program's ModelConfig for a configuration file's contents,
    and the reference's sizes. Fails where the program would run other
    sizes than the file states. ``reduced`` takes the program's own
    small variant (CPU tests)."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"])
    if reduced:
        cfg = cfg.reduced()
    want = dict(config)
    if reduced:
        want = reduced_sizes(cfg, want)
    diff = {k: (want[k], getattr(cfg, a)) for k, a in SIZE_KEYS.items()
            if k in want and want[k] != getattr(cfg, a)}
    hd = want.get("head_dim") or want["hidden_size"] // want[
        "num_attention_heads"]
    if hd != cfg.resolved_head_dim:
        diff["head_dim"] = (hd, cfg.resolved_head_dim)
    for k in ("param_dtype", "compute_dtype"):
        if prog[k] != getattr(cfg, k):
            diff[k] = (prog[k], getattr(cfg, k))
    if diff:
        raise ValueError(f"{prog['arch']}: the program's sizes differ from "
                         f"the configuration file (file, program): {diff}")
    return cfg, R.dims(want)


def reduced_sizes(cfg, config: dict) -> dict:
    """A configuration dict at the program's reduced sizes."""
    out = dict(config)
    out.update(hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
               num_key_value_heads=cfg.n_kv_heads,
               head_dim=cfg.resolved_head_dim, intermediate_size=cfg.d_ff,
               vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layers)
    return out


def make_params(cfg, dm, seed: int):
    """(reference-layout weights, the program's tree over the same
    arrays), drawn on the device from the seed."""
    from repro.models import abstract_params

    w = W.make_weights(dm, seed)
    return w, W.program_params(dm, w, abstract_params(cfg))


def reduce_window(prof, span_name: str, kernels):
    """Reduce the traced part of the window. Returns (trace numbers,
    stats of the ``bench.<span_name>`` spans that lie wholly inside it,
    the spans the kernel and busy times are counted in)."""
    ops, spans = trace_reduce.load(trace_reduce.find_xplane(
        str(prof.log_dir)))
    traced = [s for s in spans if s[0] == "bench.traced"]
    if traced:
        lo, hi = traced[0][1], traced[0][2]
    else:
        lo = min(s for _, _, s, _ in ops)
        hi = max(e for _, _, _, e in ops)
    inside = [s for s in spans if s[0] == f"bench.{span_name}"
              and lo <= s[1] and s[2] <= hi]
    red = trace_reduce.reduce(ops, spans, lo, hi, kernels=kernels,
                              within=[(s[1], s[2]) for s in inside])
    return red, [s[3] for s in inside]


def check_lines(numbers: dict, limits: dict):
    """[(name, value, limit)] of every number the limits file names, and
    whether all hold. A named number the run did not read fails."""
    out, ok = [], True
    for name in sorted(limits):
        lim = limits[name]
        val = float(numbers.get(name, np.nan))
        ok &= bool(np.isfinite(val) and val <= lim)
        out.append((name, val, lim))
    return out, ok

