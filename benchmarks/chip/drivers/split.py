"""Split serving: the paper's served path, driven open loop.

Every slot (``slot_s``) the controller, an A2C actor with weights drawn
from the mix's ``work_seed``, decides a (version, cut) for each of
``devices`` edge devices from the TPU env's state, which ``env_step``
then advances. Each request is one sequence (B = 1) of its device; it
runs through ``SplitServingEngine.infer`` with its device's decision for
the slot the request is due in, FIFO, one at a time, and is complete
when its last position's logits are on the host. The window drives that
entry; nothing else runs in it. The trace of arrivals, lengths and
devices, and so every request's (version, cut), is the mix's own; the
run's seed draws the weights, the prompts' tokens and the sample the
check compares.

Correctness: after the window, a sample of each version's served
requests drawn from the seed (the longest among them) is run through the
plain reference on the same version's weights, cut and link. Per
version it reads the widest gap by which a served token's reference
logit lies below the reference's best (``gap.<version>``) and the
widest relative L2 error of a served logits row against the
reference's (``rel_err.<version>``). The cell's limits file names the
numbers compared; only the versions it names run through the reference.
"""
from __future__ import annotations

import time

import numpy as np

import harness
import model_cost
import model_glue
import traffic
import weights as W
from reference import qwen as R

VERSIONS = ("bf16", "w8", "w4")
# the reference's version of each served version (bf16 serves the
# float32 tree as the configuration states)
REF_VERSION = {"bf16": "f32", "w8": "w8", "w4": "w4"}
MAX_SLOTS = 128
KERNELS = ("flash_attention", "quant_matmul")


class State:
    pass


def _serve(engine, tokens, cut, version):
    """The timed path of one request: infer, then its last position's
    logits on the host."""
    logits, _ = engine.infer({"tokens": tokens[None]}, cut, version)
    return np.asarray(logits[0, -1])


def setup(cell, seed: int, spans, reduced: bool = False):
    import jax
    from repro.core import (env_step, make_tpu_env, resolve_selection,
                            transformer_profile)
    from repro.policies import build_policy
    from repro.quant import build_version_params

    st = State()
    st.cell, st.spans, st.mix = cell, spans, cell.mix
    st.cfg, st.dm = model_glue.program_config(cell.config, reduced)
    st.ref_config = (model_glue.reduced_sizes(st.cfg, cell.config)
                     if reduced else cell.config)
    cfg, arch = st.cfg, cell.config["program"]["arch"]
    # every version's tree in one jitted call, as the program builds it
    st.quantize = jax.jit(lambda p: build_version_params(cfg, p, VERSIONS))
    st.env_cfg, st.tables = make_tpu_env([arch] * int(st.mix["devices"]),
                                         reduced=reduced)
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
            _serve(st.engine, np.zeros(S, np.int32), cut, version)
    # the decide and step of the first slot (the reset state) and of
    # the later ones (a stepped state, typed as the step returns it)
    state = st.env_state
    for k in st.slot_keys[:2]:
        acts = np.asarray(st.decide(state, st.decide_key))
        state = jax.block_until_ready(st.step(state, acts, k))
    return st


def reseed(st, seed: int):
    """Draw the seed's weights into the state; programs already compiled
    are kept, since weights are their arguments. The controller and the
    env's state come from the mix's ``work_seed``: one fixed artifact of
    the deployment, the same for every seed."""
    import jax
    from repro.core import env_reset, init_agent

    from repro.serving import SplitServingEngine

    st.seed = seed
    st.w, st.params = model_glue.make_params(st.cfg, st.dm, seed)
    if st.engine is None:
        st.engine = SplitServingEngine(st.cfg, st.params, versions=VERSIONS)
        # the engine builds the version trees leaf by leaf on first use
        # unless they are in its cache; the benchmark fills that cache
        # from one jitted call, and needs it to exist
        if not isinstance(getattr(st.engine, "_vparams", None), dict):
            raise RuntimeError("SplitServingEngine has no _vparams cache "
                               "of version trees to fill in set-up")
    st.engine.params = st.params
    st.engine._vparams.clear()
    st.engine._vparams.update(st.quantize(st.params))
    key = W.seed_key(int(st.mix["work_seed"]))
    st.policy.set_params(init_agent(st.env_cfg, st.tables, st.policy.config,
                                    jax.random.fold_in(key, 1)))
    st.decide = st.policy.jitted()
    st.env_state = env_reset(st.env_cfg, st.tables,
                             jax.random.fold_in(key, 2))
    st.slot_keys = list(jax.random.split(jax.random.fold_in(key, 3),
                                         MAX_SLOTS))
    st.decide_key = jax.random.fold_in(key, 4)


def window(st, seconds: float, prof, compiles):
    """Serve the requests due in ``seconds``; returns the window's start
    on the host clock. Requests still queued at the end are served."""
    mix, V = st.mix, st.dm["V"]
    reqs = traffic.open_loop(mix, seconds)
    for r in reqs:
        r["tokens"] = np.random.default_rng([st.seed, 11, r["index"]]) \
            .integers(0, V, r["prompt_len"], dtype=np.int32)
    slot_s = float(mix["slot_s"])
    if seconds / slot_s >= MAX_SLOTS:
        raise ValueError(f"window of {seconds} s has more than "
                         f"{MAX_SLOTS} slots of {slot_s} s")
    spans, clock = st.spans, time.perf_counter
    st.rows = {}
    decisions = None
    slot = -1

    def wait_until(t):
        while True:
            now = clock()
            if prof:
                prof.poll(now - t0)
            if now >= t:
                return
            with spans.span("wait"):
                time.sleep(min(t - now, 0.05))

    if prof:
        prof.before_window()
    compiles.window_open = True
    t0 = clock()
    for r in reqs:
        # a request runs with the decision of the slot it is due in, so
        # the (version, cut) of every request is fixed by the mix alone;
        # each slot's decision is made at its start, or once the queue
        # reaches its first request when serving runs behind
        while slot < int(r["due"] // slot_s):
            wait_until(t0 + (slot + 1) * slot_s)
            slot += 1
            with spans.span("decide", slot=slot):
                acts = np.asarray(st.decide(st.env_state, st.decide_key))
            with spans.span("env_step", slot=slot):
                st.env_state = st.step(st.env_state, acts,
                                       st.slot_keys[slot])
            decisions = [st.actions[(int(j), int(k))] for j, k in acts]
        due = t0 + r["due"]
        wait_until(due)
        version, cut = decisions[r["device"]]
        with spans.span("infer", rid=r["index"]):
            t_s = clock()
            last = _serve(st.engine, r["tokens"], cut, version)
            t_e = clock()
        r.update(start=t_s, done=t_e, version=version, cut=cut,
                 latency=t_e - due, token=int(np.argmax(last)))
        st.rows[r["index"]] = last
    compiles.window_open = False
    if prof:
        prof.close()
    st.reqs, st.t0, st.compiles = reqs, t0, compiles.in_window
    return t0


def end_to_end(st) -> dict:
    """The median and the 95th percentile, over all requests of the
    window, of a request's inference latency: from the start of its
    ``infer`` until its logits are on the host."""
    t = [1e3 * (r["done"] - r["start"]) for r in st.reqs]
    return {"infer_p50_ms": harness.quantile(t, 50),
            "infer_p95_ms": harness.quantile(t, 95)}


def counts(st):
    """(attempted, failed): requests due in the window, and those not
    served."""
    return len(st.reqs), sum("token" not in r for r in st.reqs)


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
    dm = st.dm
    fa, qm = [], []
    for r in st.reqs:
        if r["index"] not in counted:
            continue
        S = r["prompt_len"]
        run.model_flops += model_cost.prefill(dm, S)
        fa += [{"B": 1, "H": dm["H"], "HK": dm["HK"], "Dh": dm["Dh"],
                "Sq": S, "Skv": S, "causal": True}] * dm["L"]
        if r["version"] == "w8":
            d, n_q, n_kv = dm["d"], dm["H"] * dm["Dh"], dm["HK"] * dm["Dh"]
            qm += [{"M": S, "K": d, "N": n_q}, {"M": S, "K": d, "N": n_kv},
                   {"M": S, "K": d, "N": n_kv}, {"M": S, "K": n_q, "N": d},
                   {"M": S, "K": d, "N": dm["F"]},
                   {"M": S, "K": d, "N": dm["F"]},
                   {"M": S, "K": dm["F"], "N": d}] * dm["L"]
    run.kernel_calls = {"flash_attention": fa, "quant_matmul": qm}
    run.counters["traced_requests"] = len(counted)


def summary(st) -> dict:
    """What the window served, for the log: the (version, cut) mix."""
    hist = {}
    for r in st.reqs:
        key = f"{r.get('version')}@{r.get('cut', ('', ''))[1]}"
        hist[key] = hist.get(key, 0) + 1
    return {"selection": dict(sorted(hist.items())),
            "requests": len(st.reqs)}


def release(st):
    """Free the program's state; the benchmark's weights stay for the
    reference."""
    import gc
    for name in ("engine", "params", "decide", "step", "env_state",
                 "policy"):
        if hasattr(st, name):
            delattr(st, name)
    gc.collect()


def reference_rows(st, reqs, version: str, dtype="float32"):
    """The reference's last-position logits of each request, served at
    ``version`` through the request's own cut."""
    rw = R.version_weights(st.w, version)
    out = []
    for r in reqs:
        S = r["prompt_len"]
        out.append(np.asarray(R.forward(
            st.ref_config, rw, r["tokens"], rows=[S - 1], cut=r["cut"][1],
            link_int8=(r["version"] == "w8"), dtype=dtype))[0])
    del rw
    return out


def sampled(st) -> dict:
    """version -> the seed's sample of the requests served at it: up to
    ``check.per_version`` of each, the longest among them."""
    per = int(st.mix["check"]["per_version"])
    out = {}
    for v in VERSIONS:
        done = [r for r in st.reqs if r.get("version") == v
                and r["index"] in st.rows]
        if done:
            out[v] = traffic.sample(done, per, st.seed,
                                    key=lambda r: r["prompt_len"])
    return out


def numbers(st, group, version: str, ref_rows) -> dict:
    """The numbers compared for one version's sample, against the
    reference's rows of the same requests."""
    return {f"gap.{version}": max(R.widest_gap(ref[None], [r["token"]])
                                  for ref, r in zip(ref_rows, group)),
            f"rel_err.{version}": max(R.rel_err(st.rows[r["index"]], ref)
                                      for ref, r in zip(ref_rows, group))}


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
