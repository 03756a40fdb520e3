"""Split serving of a mixture-of-experts configuration with sliding and
full attention layers (Mellum2-12B-A2.5B): the split driver's open loop
with this configuration's own set-up, weights, reference, check and
kernel-call accounting.

The window, its end-to-end numbers, the counts, the summary, the release
of the program's state, the check's sample and its numbers are
``drivers/split.py``'s own, loaded by path: every slot the untrained A2C
actor decides a (version, cut) per device, each request runs through
``SplitServingEngine.infer`` with its device's decision for the slot it
is due in, FIFO, one at a time, complete when its last position's logits
are on the host.

What differs: the program's configuration is the registered arch cut to
the file's depth and held experts, and checked against every size, layer
type and rotation the file states; the weights are drawn in
``reference/mellum.py``'s layout (``mellum_weights.py``); the version
trees are ``build_version_params``'s, called outside any jit so that
they share every leaf it does not quantize (three copies of the float
experts would not fit); the check runs ``reference/mellum.py``; and the traced requests' kernel
calls are counted per layer type, with the rows routed to each held
expert taken from the reference's routing of each request after the
window.
"""
from __future__ import annotations

import gc

import numpy as np

import harness
import mellum_cost
import mellum_weights as MW
import model_glue
import weights as W
from reference import mellum as R

SPLIT = harness.load_module(harness.ROOT / "drivers" / "split.py")
VERSIONS, REF_VERSION = SPLIT.VERSIONS, SPLIT.REF_VERSION
KERNELS = ("flash_attention", "flash_attention_window", "quant_matmul",
           "grouped_matmul")
window, end_to_end, counts = SPLIT.window, SPLIT.end_to_end, SPLIT.counts
summary, release, sampled = SPLIT.summary, SPLIT.release, SPLIT.sampled
numbers = SPLIT.numbers

# configuration-file key -> the program's ModelConfig attribute
SIZE_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "head_dim": "resolved_head_dim", "vocab_size": "vocab_size",
             "intermediate_size": "d_ff", "num_hidden_layers": "n_layers",
             "moe_intermediate_size": "moe_d_ff",
             "num_experts_per_tok": "top_k",
             "num_experts": "resolved_experts_held",
             "sliding_window": "sliding_window",
             "tie_word_embeddings": "tie_embeddings",
             "attention_bias": "qkv_bias"}
# the program's RMSNorm epsilon and expert activation
PROGRAM_EPS, PROGRAM_ACT = 1e-6, {"silu": "swiglu"}


def program_config(config: dict, reduced: bool = False):
    """The program's ModelConfig for a configuration file's contents (the
    registered arch at the file's depth and held experts), and the
    reference's sizes. Fails where the program would run other sizes,
    layer types or rotations than the file states. ``reduced`` takes
    the program's own small variant (CPU tests)."""
    from repro.configs import get_config

    prog, pub = config["program"], config["published"]
    base = get_config(prog["arch"])
    diff = {f"published {k}": (pub[k], getattr(base, a))
            for k, a in (("num_hidden_layers", "n_layers"),
                         ("num_experts", "n_experts"))
            if pub[k] != getattr(base, a)}
    cfg = base.with_overrides(n_layers=int(config["num_hidden_layers"]),
                              experts_held=int(config["num_experts"]))
    if reduced:
        cfg = cfg.reduced()
    want = reduced_sizes(cfg, config) if reduced else config
    diff.update({k: (want[k], getattr(cfg, a)) for k, a in SIZE_KEYS.items()
                 if want[k] != getattr(cfg, a)})
    L = cfg.n_layers
    if tuple(want["layer_types"][:L]) != cfg.layer_types[:L]:
        diff["layer_types"] = (want["layer_types"][:L], cfg.layer_types[:L])
    full = want["rope_parameters"]["full_attention"]
    sliding = want["rope_parameters"]["sliding_attention"]
    y = cfg.rope_yarn
    rope = {"yarn": ((full["factor"],
                      full["original_max_position_embeddings"],
                      full["beta_fast"], full["beta_slow"],
                      full["attention_factor"], full["rope_theta"]),
                     y and (y.factor, y.original_max_position, y.beta_fast,
                            y.beta_slow, y.attention_factor, cfg.rope_theta)),
            "sliding rope_theta": (sliding["rope_theta"], cfg.rope_theta),
            "rms_norm_eps": (want["rms_norm_eps"], PROGRAM_EPS),
            "hidden_act": (PROGRAM_ACT.get(want["hidden_act"]), cfg.mlp_act),
            "norm_topk_prob": (want["norm_topk_prob"], True)}
    diff.update({k: v for k, v in rope.items() if v[0] != v[1]})
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
               vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layers,
               moe_intermediate_size=cfg.moe_d_ff,
               num_experts_per_tok=cfg.top_k,
               num_experts=cfg.resolved_experts_held,
               sliding_window=cfg.sliding_window,
               published=dict(config["published"],
                              num_experts=cfg.n_experts))
    return out


def setup(cell, seed: int, spans, reduced: bool = False):
    import jax
    from repro.core import (env_step, make_tpu_env, resolve_selection,
                            transformer_profile)
    from repro.policies import build_policy

    st = SPLIT.State()
    st.cell, st.spans, st.mix = cell, spans, cell.mix
    st.cfg, st.dm = program_config(cell.config, reduced)
    st.ref_config = (reduced_sizes(st.cfg, cell.config) if reduced
                     else cell.config)
    cfg = st.cfg
    st.env_cfg, st.tables = make_tpu_env([cfg] * int(st.mix["devices"]))
    env_cfg, tables = st.env_cfg, st.tables
    profile = transformer_profile(cfg)
    st.actions = {(j, k): resolve_selection(cfg, profile, j, k)
                  for j in range(tables.n_versions)
                  for k in range(tables.n_cuts)}
    st.policy = build_policy("a2c", env_cfg, tables)
    st.step = jax.jit(lambda s, a, k: env_step(env_cfg, tables, s, a, k)[0])
    st.engine = None
    reseed(st, seed)

    # warm every shape the traffic can use: each (version, cut) the
    # controller can pick, at each prompt length
    lens = st.mix["fields"]["prompt_len"]["values"]
    for version, cut in sorted(set(st.actions.values())):
        for S in lens:
            SPLIT._serve(st.engine, np.zeros(S, np.int32), cut, version)
    state = st.env_state
    for k in st.slot_keys[:2]:
        acts = np.asarray(st.decide(state, st.decide_key))
        state = jax.block_until_ready(st.step(state, acts, k))
    return st


def reseed(st, seed: int):
    """Draw the seed's weights into the state (the old ones are dropped
    first: two sets do not fit); programs already compiled are kept. The
    controller and the env's state come from the mix's ``work_seed``."""
    import jax
    from repro.core import env_reset, init_agent
    from repro.models import abstract_params
    from repro.quant import build_version_params
    from repro.serving import SplitServingEngine

    st.seed = seed
    st.w = st.params = None
    if st.engine is not None:
        st.engine.params = None
        st.engine._vparams.clear()
    gc.collect()
    st.w = MW.make_weights(st.dm, seed)
    st.params = MW.program_params(st.dm, st.w, abstract_params(st.cfg))
    if st.engine is None:
        st.engine = SplitServingEngine(st.cfg, st.params, versions=VERSIONS)
        if not isinstance(getattr(st.engine, "_vparams", None), dict):
            raise RuntimeError("SplitServingEngine has no _vparams cache "
                               "of version trees to fill in set-up")
    st.engine.params = st.params
    st.engine._vparams.update(build_version_params(st.cfg, st.params,
                                                   VERSIONS))
    key = W.seed_key(int(st.mix["work_seed"]))
    st.policy.set_params(init_agent(st.env_cfg, st.tables, st.policy.config,
                                    jax.random.fold_in(key, 1)))
    st.decide = st.policy.jitted()
    st.env_state = env_reset(st.env_cfg, st.tables,
                             jax.random.fold_in(key, 2))
    st.slot_keys = list(jax.random.split(jax.random.fold_in(key, 3),
                                         SPLIT.MAX_SLOTS))
    st.decide_key = jax.random.fold_in(key, 4)


def reference_rows(st, reqs, version: str, dtype="float32",
                   expert_rows=False):
    """The reference's last-position logits of each request, served at
    ``version`` through the request's own cut; with ``expert_rows``,
    (logits, rows routed to each held expert (L, held)) pairs."""
    rw = R.version_weights(st.w, version)
    out = []
    for r in reqs:
        S = r["prompt_len"]
        got = R.forward(st.ref_config, rw, r["tokens"], rows=[S - 1],
                        cut=r["cut"][1], link_int8=(r["version"] == "w8"),
                        dtype=dtype, expert_rows=expert_rows)
        out.append((np.asarray(got[0])[0], got[1]) if expert_rows
                   else np.asarray(got)[0])
    del rw
    return out


def expert_rows(st, reqs) -> dict:
    """rid -> (L, held) rows routed to each held expert of each layer, by
    the reference at the request's version, cut and link."""
    out = {}
    for v in VERSIONS:
        group = [r for r in reqs if r["version"] == v]
        if group:
            for r, (_, n) in zip(group, reference_rows(
                    st, group, REF_VERSION[v], expert_rows=True)):
                out[r["index"]] = n
    return out


def kernel_calls(dm: dict, S: int, version: str, rows) -> dict:
    """The kernel calls of one request of S tokens at ``version`` whose
    held experts were routed ``rows`` (L, held): per layer one attention
    call (full or windowed), three grouped matmuls; for w8 four
    projections per layer and the head, each at all S rows."""
    d, H, HK, Dh, F = dm["d"], dm["H"], dm["HK"], dm["Dh"], dm["F"]
    att = {"B": 1, "H": H, "HK": HK, "Dh": Dh, "Sq": S, "Skv": S,
           "causal": True}
    out = {k: [] for k in KERNELS}
    for full, n in zip(dm["full"], rows):
        if full:
            out["flash_attention"].append(att)
        else:
            out["flash_attention_window"].append(dict(att, window=dm["W"]))
        e = [int(x) for x in n]
        out["grouped_matmul"] += [{"rows": e, "K": d, "N": F},
                                  {"rows": e, "K": d, "N": F},
                                  {"rows": e, "K": F, "N": d}]
    if version == "w8":
        out["quant_matmul"] = [{"M": S, "K": d, "N": H * Dh},
                               {"M": S, "K": d, "N": HK * Dh},
                               {"M": S, "K": d, "N": HK * Dh},
                               {"M": S, "K": H * Dh, "N": d}] * dm["L"] \
            + [{"M": S, "K": d, "N": dm["V"]}]
    return out


def run_record(st, prof, run: harness.Run):
    """Fill the per-layer record from the traced part of the window."""
    spans = [s for s in st.spans.items if prof.covers(s[1], s[2])]
    run.host["decide_ms"] = [1e3 * (b - a) for n, a, b, _ in spans
                             if n == "decide"]
    run.host["service_ms"] = [1e3 * (r["done"] - r["start"])
                              for r in st.reqs if "done" in r
                              and prof.covers(r["start"], r["done"])]
    run.host["req_latency_ms"] = [1e3 * r["latency"] for r in st.reqs
                                  if "latency" in r and prof.covers(
                                      st.t0 + r["due"], r["done"])]
    run.counters["compiles_in_window"] = st.compiles
    red, inside = model_glue.reduce_window(prof, "infer", KERNELS)
    run.trace = red
    counted = {int(s["rid"]) for s in inside}
    traced = [r for r in st.reqs if r["index"] in counted]
    rows = expert_rows(st, traced)
    run.kernel_calls = {k: [] for k in KERNELS}
    for r in traced:
        n = rows[r["index"]]
        run.model_flops += mellum_cost.prefill(st.dm, r["prompt_len"],
                                               int(n.sum()))
        for k, calls in kernel_calls(st.dm, r["prompt_len"], r["version"],
                                     n).items():
            run.kernel_calls[k] += calls
    run.counters["traced_requests"] = len(traced)


def check(st) -> dict:
    """The numbers of each version the cell's limits name, over the
    seed's sample, each request against the reference of its own
    version, cut and link."""
    named = {name.split(".", 1)[1] for name in st.cell.limits}
    out = {}
    for v, group in sampled(st).items():
        if v in named:
            out.update(numbers(st, group, v,
                               reference_rows(st, group, REF_VERSION[v])))
    return out
