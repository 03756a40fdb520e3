"""Benchmark harness — one function per paper table/figure (+ system
benches), declared as a ``repro.bench`` case matrix. Prints
``name,us_per_call,derived`` CSV rows and writes the same rows as
machine-readable JSON (``--json``, default BENCH_results.json) with
repeated samples, bootstrap CI bounds, per-case obs phase breakdowns,
the git sha and an environment fingerprint — the record
``scripts/benchgate.py`` gates against ``BENCH_history.jsonl``
(DESIGN.md §10).

Paper artifacts:
  table1_profiles       — Table I: candidate cut points + activation bytes
  fig2_accuracy_sweep   — Fig. 2: performance vs accuracy weight w1
  fig3_latency_sweep    — Fig. 3: performance vs latency weight w2
  fig4_energy_sweep     — Fig. 4: performance vs energy weight w3
  table2_cut_selection  — Table II: (version, cut) selection at weight extremes
  a2c_convergence       — Sec. III-B: A2C learning curve vs greedy oracle
  baseline_policies     — device-only / full-offload / random / oracle

The sweeps use the per-step greedy oracle as the converged-policy proxy
(fast, deterministic); ``a2c_convergence`` demonstrates the A2C agent
approaching it. Pass --agent to run the sweeps with freshly trained agents
instead (slower; matches the paper's methodology exactly).

System benches:
  roofline_suite        — dominant roofline terms from results/dryrun.jsonl
  serving_decode        — us/token through the serving engine (reduced model)
  split_inference       — EdgeRL split execution vs monolithic forward
  megafleet_scaling     — vectorized fleet engine devices/sec scaling
                          curve (n_uavs 256 / 4k / 32k / 100k)
  megafleet_speedup     — loop-vs-vectorized per-epoch cost ratio at 32k
                          devices (gated) + speedup and scaling exponent
  scenario_sweep        — every registered scenario preset via run_scenario
  train_throughput      — A2C episodes/s, batched (vmap) vs looped
  pricing_numpy_throughput — numpy pricing-core actions/s (fleet hot path)
  online_adaptation     — repro.online incremental-update steps/s +
                          link-brownout drift recovery time
  timeline_overhead     — flight-recorder capture cost: fleet_sim wall
                          with FleetConfig.timeline on vs off (gated)
  kernels_interpret     — Pallas flash-attention kernel (interpret mode)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.bench import Matrix, Timing, history, runner, timeit

# rows flow through the active repro.bench.runner sink (CSV echo + the
# structured record benchgate consumes); Timing/_timeit live in
# repro.bench.stats now — same semantics, 5 samples by default
row = runner.emit
_timeit = timeit


# --------------------------------------------------------------------------
# paper benches
# --------------------------------------------------------------------------

def table1_profiles():
    """Table I rows, each timing its *own* model-version profile build
    (the historical harness timed one shared paper_profiles() call, so
    every row reported the identical us_per_call)."""
    from repro.core.profiles import PAPER_VERSIONS, paper_version_profile
    for model, version in PAPER_VERSIONS:
        t0 = time.perf_counter()
        v = paper_version_profile(model, version)
        us = (time.perf_counter() - t0) * 1e6
        cuts = ";".join(str(c) for c in v.cut_points)
        mb = ";".join(f"{v.cut_bytes(c)/1e6:.2f}" for c in v.cut_points)
        row(f"table1_{v.model}{v.version}", us,
            f"cuts={cuts} act_MB={mb} GF={v.total_flops/1e9:.1f} "
            f"acc={v.accuracy:.3f}")


def _sweep(weight_name: str, fig: str, use_agent: bool, episodes: int):
    from repro.core import RewardWeights, evaluate_policy, make_paper_env
    from repro.policies import build_policy
    for wv in (0.0, 0.25, 0.5, 0.75, 1.0):
        rest = (1.0 - wv) / 2
        kw = {"w_acc": rest, "w_lat": rest, "w_energy": rest}
        kw[weight_name] = wv
        cfg, tables = make_paper_env(weights=RewardWeights(**kw))
        t0 = time.perf_counter()
        if use_agent:
            pol = build_policy("a2c", cfg, tables, episodes=episodes)
            pol.train()
        else:
            pol = build_policy("greedy_oracle", cfg, tables)
        m = evaluate_policy(cfg, tables, pol, jax.random.key(0), episodes=2)
        us = (time.perf_counter() - t0) * 1e6
        modal = ";".join(f"{k}:v{v[0]}c{v[1]}"
                         for k, v in m["modal_selection"].items())
        row(f"{fig}_{weight_name}={wv}", us,
            f"reward={m['reward']:.3f} lat_ms={m['latency']*1e3:.1f} "
            f"E_J={m['energy']:.3f} accS={m['acc_score']:.3f} "
            f"alive={m['alive_slots']:.1f} {modal}")


def fig2_accuracy_sweep(use_agent=False, episodes=200):
    _sweep("w_acc", "fig2", use_agent, episodes)


def fig3_latency_sweep(use_agent=False, episodes=200):
    _sweep("w_lat", "fig3", use_agent, episodes)


def fig4_energy_sweep(use_agent=False, episodes=200):
    _sweep("w_energy", "fig4", use_agent, episodes)


def table2_cut_selection(use_agent=False, episodes=200):
    """Weight extremes; paper Table II qualitative claims: w_lat=1 pushes
    cuts LATER than w_lat=0 (transmission postpones offload); w_energy=1
    pulls cuts EARLY again."""
    from repro.core import RewardWeights, evaluate_policy, make_paper_env
    from repro.policies import build_policy
    results = {}
    for tag, kw in (("w2_0", dict(w_acc=0.5, w_lat=0.0, w_energy=0.5)),
                    ("w2_1", dict(w_acc=0.0, w_lat=1.0, w_energy=0.0)),
                    ("w3_0", dict(w_acc=0.5, w_lat=0.5, w_energy=0.0)),
                    ("w3_1", dict(w_acc=0.0, w_lat=0.0, w_energy=1.0))):
        cfg, tables = make_paper_env(weights=RewardWeights(**kw))
        t0 = time.perf_counter()
        if use_agent:
            pol = build_policy("a2c", cfg, tables, episodes=episodes)
            pol.train()
        else:
            pol = build_policy("greedy_oracle", cfg, tables)
        m = evaluate_policy(cfg, tables, pol, jax.random.key(0), episodes=2)
        us = (time.perf_counter() - t0) * 1e6
        results[tag] = m["modal_selection"]
        modal = ";".join(f"{k}:v{v[0]}c{v[1]}"
                         for k, v in m["modal_selection"].items())
        row(f"table2_{tag}", us, modal)
    later = sum(results["w2_1"][k][1] >= results["w2_0"][k][1]
                for k in results["w2_0"])
    earlier = sum(results["w3_1"][k][1] <= results["w3_0"][k][1]
                  for k in results["w3_0"])
    row("table2_pattern_check", 0.0,
        f"w_lat1_cut_later_or_eq={later}/3 "
        f"w_energy1_cut_earlier_or_eq={earlier}/3")


def a2c_convergence(episodes=250):
    from repro.core import evaluate_policy, make_paper_env
    from repro.policies import build_policy
    cfg, tables = make_paper_env()
    t0 = time.perf_counter()
    pol = build_policy("a2c", cfg, tables, episodes=episodes)
    hist = pol.train()
    us = (time.perf_counter() - t0) * 1e6 / episodes
    first = np.mean([h["mean_reward"] for h in hist[:20]])
    last = np.mean([h["mean_reward"] for h in hist[-20:]])
    oracle = evaluate_policy(cfg, tables,
                             build_policy("greedy_oracle", cfg, tables),
                             jax.random.key(0), episodes=2)["reward"]
    agent = evaluate_policy(cfg, tables, pol,
                            jax.random.key(0), episodes=2)["reward"]
    row("a2c_convergence", us,
        f"first20={first:.3f} last20={last:.3f} agent_eval={agent:.3f} "
        f"oracle={oracle:.3f} episodes={episodes}")


def baseline_policies():
    from repro.core import evaluate_policy, make_paper_env
    from repro.policies import build_policy, get_policy_spec, policy_names
    cfg, tables = make_paper_env()
    for name in policy_names():
        if get_policy_spec(name).trainable:
            continue
        t0 = time.perf_counter()
        m = evaluate_policy(cfg, tables, build_policy(name, cfg, tables),
                            jax.random.key(0), episodes=2)
        us = (time.perf_counter() - t0) * 1e6
        row(f"baseline_{name}", us,
            f"reward={m['reward']:.3f} lat_ms={m['latency']*1e3:.1f} "
            f"E_J={m['energy']:.3f}")


# --------------------------------------------------------------------------
# system benches
# --------------------------------------------------------------------------

def roofline_suite():
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.jsonl")
    if not os.path.exists(path):
        row("roofline_suite", 0.0, "skipped=no_dryrun_results")
        return
    from repro.analysis.roofline import enrich, load
    recs = [enrich(r) for r in load(path)
            if r["mesh"] == "single" and r["status"] == "ok"
            and r.get("variant", "baseline") == "baseline"]
    for r in recs:
        row(f"roofline_{r['arch']}_{r['shape']}",
            r.get("compile_s", 0.0) * 1e6,
            f"compute_s={r['compute_s']:.4g} memory_s={r['memory_s']:.4g} "
            f"collective_s={r['collective_s']:.4g} dom={r['dominant']} "
            f"model_ratio={r['ratio']:.2f}")


def serving_decode():
    from repro.configs import get_config
    from repro.models import init
    from repro.serving import ServeConfig, ServingEngine
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    eng = ServingEngine(cfg, params, ServeConfig(max_new_tokens=32))
    toks = (jnp.arange(4 * 64, dtype=jnp.int32).reshape(4, 64) * 3) \
        % cfg.vocab_size
    batch = {"tokens": toks}
    us = _timeit(lambda: eng.generate(batch), n=3)
    row("serving_decode", us / 32, "per_token,B=4,reduced_qwen2")


def split_inference():
    from repro.configs import get_config
    from repro.core.partition import cut_points
    from repro.models import forward_logits, init
    from repro.serving import SplitServingEngine
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    toks = (jnp.arange(2 * 64, dtype=jnp.int32).reshape(2, 64) * 3) \
        % cfg.vocab_size
    batch = {"tokens": toks}
    full_jit = jax.jit(lambda p, b: forward_logits(cfg, p, b))
    us_full = _timeit(lambda: full_jit(params, batch))
    eng = SplitServingEngine(cfg, params)
    cut = cut_points(cfg)[0]
    us_split = _timeit(lambda: eng.infer(batch, cut)[0])
    _, nbytes = eng.infer(batch, cut)
    row("split_inference", us_split,
        f"monolithic_us={us_full:.1f} overhead={us_split/max(us_full,1):.2f}x "
        f"act_bytes={nbytes}")


def hillclimb_variants():
    """§Perf variant deltas straight from the dry-run records."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.jsonl")
    if not os.path.exists(path):
        row("hillclimb_variants", 0.0, "skipped=no_dryrun_results")
        return
    from repro.analysis.roofline import enrich, load
    recs = load(path)
    rmap = {(r["arch"], r["shape"], r["mesh"], r.get("variant", "baseline")):
            r for r in recs}
    pairs = [("deepseek-v2-lite-16b", "decode_32k",
              ["baseline", "mla_absorb"]),
             ("mixtral-8x22b", "train_4k",
              ["baseline", "causal_skip", "noremat", "fsdp"]),
             ("llama-3.2-vision-90b", "prefill_32k",
              ["baseline", "hugechunk", "causal_skip"])]
    for arch, shape, variants in pairs:
        for v in variants:
            r = rmap.get((arch, shape, "single", v))
            if not r or r["status"] != "ok":
                continue
            e = enrich(r)
            row(f"perf_{arch}_{shape}_{v}", r.get("compile_s", 0) * 1e6,
                f"bound_s={e['bound_s']:.4g} compute_s={e['compute_s']:.4g} "
                f"memory_s={e['memory_s']:.4g} "
                f"collective_s={e['collective_s']:.4g} dom={e['dominant']}")


def ablation_a2c(episodes=80):
    """A2C hyper-parameter ablations (entropy bonus, discount)."""
    from repro.core import A2CConfig, make_paper_env, train_agent
    cfg, tables = make_paper_env()
    for tag, kw in (("ent0", dict(entropy_coef=0.0)),
                    ("ent0.01", dict(entropy_coef=0.01)),
                    ("ent0.05", dict(entropy_coef=0.05)),
                    ("gamma0.9", dict(gamma=0.9)),
                    ("gamma0.99", dict(gamma=0.99))):
        t0 = time.perf_counter()
        _, hist = train_agent(cfg, tables,
                              A2CConfig(episodes=episodes, **kw))
        us = (time.perf_counter() - t0) * 1e6 / episodes
        first = np.mean([h["mean_reward"] for h in hist[:15]])
        last = np.mean([h["mean_reward"] for h in hist[-15:]])
        row(f"ablation_a2c_{tag}", us,
            f"first15={first:.3f} last15={last:.3f} delta={last-first:+.3f}")


def ablation_agents(episodes=120):
    """Beyond-paper: the paper's A2C vs a PPO agent on the same env —
    empirical support for the paper's algorithm choice."""
    from repro.core import A2CConfig, make_paper_env
    from repro.core import a2c as A2C
    from repro.core import ppo as PPO
    cfg, tables = make_paper_env()
    t0 = time.perf_counter()
    _, h = A2C.train(cfg, tables, A2CConfig(episodes=episodes),
                     jax.random.key(0))
    us = (time.perf_counter() - t0) * 1e6 / episodes
    row("ablation_agents_a2c", us,
        f"first15={np.mean([x['mean_reward'] for x in h[:15]]):+.3f} "
        f"last15={np.mean([x['mean_reward'] for x in h[-15:]]):+.3f}")
    t0 = time.perf_counter()
    _, h = PPO.train(cfg, tables, PPO.PPOConfig(episodes=episodes),
                     jax.random.key(0))
    us = (time.perf_counter() - t0) * 1e6 / episodes
    row("ablation_agents_ppo", us,
        f"first15={np.mean([x['mean_reward'] for x in h[:15]]):+.3f} "
        f"last15={np.mean([x['mean_reward'] for x in h[-15:]]):+.3f}")


def continuous_batching():
    from repro.configs import get_config
    from repro.models import init
    from repro.serving.scheduler import ContinuousBatchingServer, Request
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    srv = ContinuousBatchingServer(cfg, params, max_batch=4, cache_len=64)
    r = np.random.default_rng(0)
    for i in range(10):
        srv.submit(Request(rid=i, tokens=r.integers(
            0, cfg.vocab_size, int(r.integers(4, 12))).astype(np.int32),
            max_new_tokens=6))
    t0 = time.perf_counter()
    done = srv.run()
    us = (time.perf_counter() - t0) * 1e6
    toks = sum(len(q.out) for q in done)
    row("continuous_batching", us / max(toks, 1),
        f"per_token,requests={len(done)} decode_steps={srv.stats.decode_steps} "
        f"prefills={srv.stats.prefills}")


def scheduler_throughput():
    """Continuous-batching tokens/s with mixed-length requests — the
    slot-refill path (individual retirement) is on the hot loop."""
    from repro.configs import get_config
    from repro.models import init
    from repro.serving.scheduler import ContinuousBatchingServer, Request
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    # one server across warm + timed runs: its jitted prefill/decode
    # closures (and their per-batch-size compile cache) live on the
    # instance, so a fresh server per run would re-compile in the timed
    # region; reseeding the rng repeats the exact request shapes
    srv = ContinuousBatchingServer(cfg, params, max_batch=4, cache_len=64)

    def one_run():
        r = np.random.default_rng(0)
        for i in range(12):
            srv.submit(Request(rid=i, tokens=r.integers(
                0, cfg.vocab_size, int(r.integers(4, 12))).astype(np.int32),
                max_new_tokens=int(r.integers(3, 12))))
        done = srv.run()
        return sum(len(q.out) for q in done)

    one_run()                       # warm the jits
    warm_reclaims = srv.stats.slot_reclaims
    warm_prefills = srv.stats.prefills
    samples, toks, dt = [], 0, 0.0
    for rep in range(3):            # repeated runs: the gate's noise model
        t0 = time.perf_counter()
        toks = one_run()
        dt = time.perf_counter() - t0
        samples.append(dt / max(toks, 1) * 1e6)
        if rep == 0:
            reclaims = srv.stats.slot_reclaims - warm_reclaims
            prefills = srv.stats.prefills - warm_prefills
    summ = srv.stats.latency_summary()
    row("scheduler_throughput", Timing(min(samples), samples),
        f"per_token,tok_per_s={toks/dt:.0f} "
        f"reclaims={reclaims} prefills={prefills} "
        f"p95_e2e_steps={summ['p95']:.0f}")


def train_throughput(loop_episodes=16, batch_envs=16):
    """Episodes/s of the A2C update path: looped single-env episodes vs
    one vmapped batch_envs update (same nets, same env). The batched
    path amortizes the per-episode scan/dispatch overhead AND the
    per-update host work (A2C.train extracts the stats history every
    update — the loop body here replicates train() exactly) across E
    parallel worlds inside one jit. Best-of-reps per path to shed
    scheduler noise on small hosts."""
    from repro.core import A2CConfig, init_agent, make_paper_env, \
        make_train_episode
    from repro.optim import adamw_init
    cfg, tables = make_paper_env()

    def eps_per_s(E, calls, reps=3):
        ac = A2CConfig(batch_envs=E)
        params = init_agent(cfg, tables, ac, jax.random.key(0))
        opt = adamw_init(params)
        step = make_train_episode(cfg, tables, ac)
        p, o, s = step(params, opt, jax.random.key(1))   # compile
        jax.block_until_ready(s["loss"])
        best = float("inf")
        for rep in range(reps):
            t0 = time.perf_counter()
            for i in range(calls):
                p, o, s = step(p, o, jax.random.key(2 + i))
                history = {k: float(v) for k, v in s.items()}  # as train()
            best = min(best, (time.perf_counter() - t0) / calls)
        assert history
        return E / best, best

    looped, us_loop = eps_per_s(1, loop_episodes)
    batched, us_batch = eps_per_s(batch_envs, 4)
    row("train_throughput", us_batch * 1e6,
        f"batched_eps_per_s={batched:.2f} looped_eps_per_s={looped:.2f} "
        f"speedup={batched/looped:.2f}x batch_envs={batch_envs} "
        f"looped_us_per_ep={us_loop*1e6:.0f}")


def pricing_numpy_throughput(n_devices=4096, iters=200, reps=5):
    """Actions/s through the numpy pricing path (the fleet simulator's
    per-epoch hot loop: one price_actions call per decision epoch).
    Timed in ``reps`` chunks so the gate has a noise model."""
    from repro.core import make_paper_env
    from repro.sim import AnalyticalBackend
    cfg, tables = make_paper_env()
    be = AnalyticalBackend(cfg, tables)
    r = np.random.default_rng(0)
    mids = r.integers(0, tables.n_models, n_devices).astype(np.int32)
    acts = np.stack([r.integers(0, tables.n_versions, n_devices),
                     r.integers(0, tables.n_cuts, n_devices)],
                    axis=-1).astype(np.int32)
    lp, pw = cfg.latency, cfg.power
    bw = r.uniform(lp.bw_min_bps, lp.bw_max_bps, n_devices)
    ptx = r.uniform(pw.p_tx_min, pw.p_tx_max, n_devices)
    pr = be.price(mids, acts, bw, ptx)                   # warm
    assert isinstance(pr.t_total, np.ndarray)
    chunk = max(iters // reps, 1)
    us = _timeit(lambda: be.price(mids, acts, bw, ptx),
                 n=chunk, reps=reps, warmup=1)
    row("pricing_numpy_throughput", us,
        f"per_call,devices={n_devices} "
        f"actions_per_s={n_devices/us*1e6:.0f}")


def fleet_sim(n_requests=100_000, n_uavs=8, reps=3):
    """repro.sim throughput: analytical-backend requests/s + epochs/s,
    parameterized over fleet size (the devices/sec scaling curve the
    mega-fleet roadmap item tracks)."""
    from repro.core import make_paper_env
    from repro.policies import build_policy
    from repro.sim import FleetConfig, PoissonTrace, simulate
    cfg, tables = make_paper_env(n_uavs=n_uavs, slot_seconds=10.0)
    trace = PoissonTrace(rate_rps=15.0)
    pol = build_policy("greedy_oracle", cfg, tables)
    kw = dict(n_requests=n_requests, seed=0, fleet=FleetConfig(slo_s=1.0))
    simulate(cfg, tables, pol, trace, **kw)  # warm
    samples, dts = [], []
    for _ in range(reps):   # same seed: identical epochs each repetition
        t0 = time.perf_counter()
        res = simulate(cfg, tables, pol, trace, **kw)
        dts.append(time.perf_counter() - t0)
        samples.append(dts[-1] / max(res.epochs, 1) * 1e6)
    dt = min(dts)
    s = res.summary
    name = "fleet_sim" if n_uavs == 8 else f"fleet_sim[n_uavs={n_uavs}]"
    row(name, Timing(min(samples), samples),
        f"per_epoch,req_per_s={res.served/dt:.0f} epochs_per_s="
        f"{res.epochs/dt:.1f} requests={res.served} "
        f"p95_s={s['p95']:.3f} slo_att={s['slo_attainment']:.3f}",
        devices=n_uavs,
        devices_per_s=n_uavs * res.epochs / dt)


def timeline_overhead(n_requests=100_000, n_uavs=8, reps=3):
    """Flight-recorder capture cost on the fleet_sim smoke world: wall
    ratio of ``FleetConfig.timeline`` on vs off, paired per rep (same
    seed → identical epochs). The gated value is the on/off ratio —
    capture-cost regressions show up as the increase; the acceptance
    bar is < 1.05 (under 5% added wall). The recorded trace's
    ``fleet.timeline`` span is the same cost seen as a phase."""
    from repro.core import make_paper_env
    from repro.policies import build_policy
    from repro.sim import FleetConfig, PoissonTrace, simulate
    cfg, tables = make_paper_env(n_uavs=n_uavs, slot_seconds=10.0)
    trace = PoissonTrace(rate_rps=15.0)
    pol = build_policy("greedy_oracle", cfg, tables)

    def one(timeline):
        kw = dict(n_requests=n_requests, seed=0,
                  fleet=FleetConfig(slo_s=1.0, timeline=timeline))
        t0 = time.perf_counter()
        res = simulate(cfg, tables, pol, trace, **kw)
        return time.perf_counter() - t0, res

    one(False), one(True)                      # warm (policy jit)
    ratios = []
    for _ in range(reps):
        off_s, _ = one(False)
        on_s, res = one(True)
        ratios.append(on_s / off_s)
    tl = res.timeline
    row("timeline_overhead", Timing(min(ratios), ratios),
        f"on_over_off_wall,overhead_pct={(min(ratios)-1)*100:.2f} "
        f"epochs={res.epochs} rows={len(tl)} "
        f"slo_attainment={tl.slo_report.attainment:.3f} "
        f"alerts={len(tl.slo_report.alerts)}",
        overhead_pct=(min(ratios) - 1) * 100)


def _megafleet_world(n_uavs):
    """One mega-fleet bench world: paper env provisioned per device,
    1 s slots, Poisson 5 rps/device, static oracle policy."""
    from repro.core import make_paper_env
    from repro.core.latency import LatencyParams
    from repro.policies import build_policy
    from repro.sim import AnalyticalBackend, PoissonTrace
    cfg, tables = make_paper_env(
        n_uavs=n_uavs, slot_seconds=1.0, peak_rps=10.0,
        latency=LatencyParams(server_flops=0.55e12 * n_uavs,
                              bw_max_bps=1e9),
        frames_per_slot=10.0)
    mids = np.arange(n_uavs, dtype=np.int32) % tables.n_models
    pol = build_policy("greedy_oracle", cfg, tables)
    return cfg, tables, mids, pol, AnalyticalBackend(cfg, tables), \
        PoissonTrace(rate_rps=5.0)


def _megafleet_epoch_s(world, engine, epochs, reps):
    """Best-of-reps per-epoch seconds for one engine (+ samples)."""
    from repro.sim import FleetConfig, simulate
    cfg, tables, mids, pol, backend, trace = world
    fl = FleetConfig(engine=engine, max_epochs=epochs,
                     record_epochs=False)
    kw = dict(n_requests=10**12, seed=0, fleet=fl, backend=backend,
              model_ids=mids)
    simulate(cfg, tables, pol, trace, **kw)          # warm (policy jit)
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res = simulate(cfg, tables, pol, trace, **kw)
        samples.append((time.perf_counter() - t0) / res.epochs)
    return min(samples), samples, res


def megafleet_scaling(n_uavs=4096, epochs=4, reps=3):
    """Devices/sec of the vectorized fleet engine across fleet sizes —
    the mega-fleet scaling curve (n_uavs axis up to 100k devices)."""
    world = _megafleet_world(n_uavs)
    sec, samples, res = _megafleet_epoch_s(world, "vectorized",
                                           epochs, reps)
    row(f"megafleet_scaling[n_uavs={n_uavs}]",
        Timing(sec * 1e6, [s * 1e6 for s in samples]),
        f"per_epoch,devices_per_s={n_uavs/sec:,.0f} "
        f"req_per_epoch={res.served//res.epochs} engine=vectorized",
        devices=n_uavs, devices_per_s=n_uavs / sec)


def megafleet_speedup(n_uavs=32768, epochs=4, reps=3):
    """Loop-vs-vectorized cost ratio at 32k devices (the mega-fleet
    acceptance claim: vectorized >= 20x devices*epochs/sec).

    The *gated* value is the vectorized/loop per-epoch cost ratio —
    lower is better, so losing speedup shows up as the increase the
    gate flags. The speedup itself and the scaling exponent (log-log
    slope of vectorized per-epoch time over a 256..32k size sweep;
    1.0 = linear in devices) ride along as extra fields."""
    world = _megafleet_world(n_uavs)
    vec_s, vec_samples, _ = _megafleet_epoch_s(world, "vectorized",
                                               epochs, reps)
    loop_s, _, _ = _megafleet_epoch_s(world, "loop", epochs,
                                      max(reps - 1, 1))
    ratios = [v / loop_s for v in vec_samples]
    sizes = (256, 4096, 32768)
    curve = [vec_s if n == n_uavs else
             _megafleet_epoch_s(_megafleet_world(n), "vectorized",
                                epochs, reps)[0]
             for n in sizes]
    slope = np.polyfit(np.log(sizes), np.log(curve), 1)[0]
    row("megafleet_speedup", Timing(min(ratios), ratios),
        f"vec_over_loop_cost,speedup={loop_s/vec_s:.1f}x "
        f"loop_epoch_ms={loop_s*1e3:.0f} vec_epoch_ms={vec_s*1e3:.1f} "
        f"scaling_exponent={slope:.2f} devices={n_uavs}",
        speedup=loop_s / vec_s, scaling_exponent=float(slope),
        devices=n_uavs)


def scenario_sweep(n_requests=2000):
    """Every registered scenario preset through run_scenario with the
    static roster — the one-command experiment surface as a perf/smoke
    case (execute presets skipped: engine compiles dominate)."""
    from repro.scenarios import get_scenario, run_scenario, scenario_names
    for name in scenario_names():
        sc = get_scenario(name)
        if sc.execute:
            row(f"scenario_{name}", 0.0, "skipped=execute_backend")
            continue
        t0 = time.perf_counter()
        rep = run_scenario(sc, ("greedy_oracle", "device_only"),
                           n_requests=n_requests, seeds=(0,))
        us = (time.perf_counter() - t0) * 1e6
        g = rep.results["greedy_oracle"].mean
        d = rep.results["device_only"].mean
        row(f"scenario_{name}", us,
            f"requests={n_requests} oracle_p95_s={g['p95']:.3f} "
            f"oracle_slo_att={g['slo_attainment']:.3f} "
            f"device_only_slo_att={d['slo_attainment']:.3f}")


def online_adaptation(window=64, iters=50):
    """repro.online: steps/s of the jitted incremental update on a full
    replay window, plus a short drift run's recovery time (epochs from
    the brownout boundary until the adapted controller is back within
    10% of the per-regime greedy oracle)."""
    import jax

    from repro.core.env import env_reset
    from repro.online import OnlineConfig, OnlineLearner
    from repro.policies import build_policy
    from repro.scenarios import get_scenario
    from repro.sim import FleetConfig, simulate

    sc = get_scenario("link-brownout")
    cfg, tables, mids, _ = sc.build_env()
    n = cfg.n_uavs
    a2c = build_policy("a2c", cfg, tables, episodes=sc.episodes,
                       entropy_coef=sc.entropy_coef,
                       batch_envs=sc.batch_envs)
    a2c.train(seed=0, trace=sc.build_train_trace())
    snap = a2c.params

    # 1) raw incremental-update throughput on a synthetic full window
    oc = OnlineConfig(algo="a2c", gate="always")
    ln = OnlineLearner(a2c, oc, mids)
    state = env_reset(cfg, tables, jax.random.key(0),
                      model_ids=jnp.asarray(mids))
    r = np.random.default_rng(0)
    for _ in range(window):
        acts = np.stack([r.integers(0, tables.n_versions, n),
                         r.integers(0, tables.n_cuts, n)], -1)
        ln.observe_transition(state, acts.astype(np.int32),
                              r.normal(size=n), np.ones(n), 0)
    batch = ln.window.tail(window)
    step = ln._update(window)
    params, opt = a2c.params, ln._opt(a2c.params)
    params, opt = step(params, opt, batch["obs"], batch["actions"],
                       batch["logp"], batch["reward"], batch["mask"])
    jax.block_until_ready(jax.tree.leaves(params)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt = step(params, opt, batch["obs"], batch["actions"],
                           batch["logp"], batch["reward"], batch["mask"])
    jax.block_until_ready(jax.tree.leaves(params)[0])
    us = (time.perf_counter() - t0) / iters * 1e6

    # 2) drift recovery through the link-brownout preset's world
    a2c.set_params(snap)
    res = simulate(cfg, tables, a2c, sc.build_trace(),
                   n_requests=sc.n_requests, seed=0,
                   fleet=FleetConfig(slo_s=sc.slo_s), model_ids=mids,
                   schedule=sc.build_schedule(), online=sc.build_online())
    a2c.set_params(snap)
    reg = res.adaptation["regimes"][1]
    onl = res.adaptation["online"]
    rec = reg["recovery_epochs"]
    row("online_adaptation", us,
        f"update_steps_per_s={1e6/us:.1f} window={window} "
        f"scenario={sc.name} "
        f"recovery_epochs={'never' if rec is None else int(rec)} "
        f"regret={reg['regret']:.3f} updates={onl['updates']} "
        f"bursts={onl['bursts']}")


def kernels_interpret():
    from repro.kernels.flash_attention import flash_attention
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, 2, 256, 64)), jnp.float32)
    us = _timeit(lambda: flash_attention(q, k, v, interpret=True), n=3)
    row("flash_attention_interpret", us, "B1_H4_S256_D64,CPU_interpret_mode")


def quant_matmul(M=512, K=512, N=512):
    """int8-vs-bf16 matmul throughput (repro.quant w8a8 path).

    On TPU the int8 MXU path doubles MAC throughput; on this CPU
    container the numbers only sanity-check dispatch overheads, so the
    derived column reports GFLOP/s for both plus the quantization error."""
    from repro.kernels.quant_matmul import quant_matmul_ref
    from repro.quant import quantize, quantize_act
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(r.normal(size=(K, N)) * 0.05, jnp.float32)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    qt = quantize(w, "w8a8")
    xq, xs = quantize_act(x)
    xs_, ws_ = xs.reshape(-1), qt.scale.reshape(-1)

    mm = jax.jit(lambda a, b: a @ b)
    us_bf16 = _timeit(lambda: mm(xb, wb), n=10)
    qmm = jax.jit(lambda a, b, s1, s2: quant_matmul_ref(a, b, s1, s2))
    us_i8 = _timeit(lambda: qmm(xq, qt.q, xs_, ws_), n=10)
    flops = 2.0 * M * K * N
    y = x @ w
    yq = quant_matmul_ref(xq, qt.q, xs_, ws_)
    rel = float(jnp.linalg.norm(yq - y) / jnp.linalg.norm(y))
    row("quant_matmul", us_i8,
        f"MKN={M} int8_gflops={flops/us_i8/1e3:.1f} "
        f"bf16_us={us_bf16:.1f} bf16_gflops={flops/us_bf16/1e3:.1f} "
        f"relerr={rel:.4f}")

    from repro.kernels.quant_matmul import quant_matmul as qmm_pallas
    us_pl = _timeit(lambda: qmm_pallas(xq, qt.q, xs_, ws_, interpret=True),
                    n=2)
    row("quant_matmul_interpret", us_pl, f"MKN={M},CPU_interpret_mode")


def cluster_routing(n_requests=12_000, reps=3):
    """repro.cluster: routed fleet throughput over the heterogeneous
    4-server pool (hetero-4 x near-far, widened (version, cut, server)
    actions, hysteresis autoscaler) — us/epoch per router baseline plus
    the SLO attainment each dispatch rule earns on the same stream."""
    from repro.cluster import (AutoscalerConfig, build_cluster, get_pool,
                               get_topology)
    from repro.core import make_paper_env
    from repro.core.latency import LatencyParams
    from repro.policies import build_policy
    from repro.sim import (AnalyticalBackend, FleetConfig, PoissonTrace,
                           simulate)
    n_uavs = 8
    cluster = build_cluster(get_pool("hetero-4"),
                            get_topology("near-far", n_uavs, 4))
    cfg, tables = make_paper_env(
        n_uavs=n_uavs, slot_seconds=10.0, peak_rps=30.0,
        latency=LatencyParams(server_flops=0.55e12 * n_uavs,
                              bw_max_bps=1e9),
        frames_per_slot=300.0, cluster=cluster)
    mids = np.arange(n_uavs, dtype=np.int32) % tables.n_models
    trace = PoissonTrace(rate_rps=8.0)
    for name in ("round_robin", "join_shortest_queue", "greedy_oracle"):
        pol = build_policy(name, cfg, tables)
        kw = dict(model_ids=mids, n_requests=n_requests, seed=0,
                  backend=AnalyticalBackend(cfg, tables),
                  fleet=FleetConfig(slo_s=2.0),
                  autoscaler=AutoscalerConfig(policy="hysteresis"))
        simulate(cfg, tables, pol, trace, **kw)   # warm (jit compiles)
        samples, dts = [], []
        for _ in range(reps):   # same seed: identical epochs each rep
            t0 = time.perf_counter()
            res = simulate(cfg, tables, pol, trace, **kw)
            dts.append(time.perf_counter() - t0)
            samples.append(dts[-1] / max(res.epochs, 1) * 1e6)
        s = res.summary
        row(f"cluster_routing[{name}]", Timing(min(samples), samples),
            f"per_epoch,req_per_s={res.served / min(dts):.0f} "
            f"slo_att={s['slo_attainment']:.3f} "
            f"p95_s={s['p95']:.3f} "
            f"scale_events={s['scale_events']:.0f} "
            f"mean_replicas={s['mean_replicas']:.2f}")


def build_matrix() -> Matrix:
    """The declarative case matrix (replaces the hand-rolled ALL-list
    dispatch): paper artifacts, system benches, and the fleet-size axis
    behind the devices/sec scaling curve."""
    m = Matrix()
    for fn in (table1_profiles, fig2_accuracy_sweep, fig3_latency_sweep,
               fig4_energy_sweep, table2_cut_selection, baseline_policies,
               a2c_convergence, ablation_a2c, ablation_agents):
        m.add(fn, tags=("paper",))
    for fn in (roofline_suite, hillclimb_variants, serving_decode,
               split_inference, continuous_batching):
        m.add(fn, tags=("system",))
    m.add(scheduler_throughput, tags=("system", "smoke"))
    m.add(fleet_sim, tags=("system", "smoke"),
          axes={"n_uavs": (8, 64, 256)})
    m.add(megafleet_scaling, tags=("system", "smoke"),
          axes={"n_uavs": (256, 4096, 32768, 100_000)})
    m.add(megafleet_speedup, tags=("system", "smoke"))
    m.add(scenario_sweep, tags=("system",))
    m.add(cluster_routing, tags=("system", "smoke"))
    m.add(timeline_overhead, tags=("system", "smoke"))
    m.add(train_throughput, tags=("system", "smoke"))
    m.add(pricing_numpy_throughput, tags=("system", "smoke"))
    m.add(online_adaptation, tags=("system",))
    m.add(kernels_interpret, tags=("system", "smoke"))
    m.add(quant_matmul, tags=("system", "smoke"))
    return m


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated group or case names "
                    "(e.g. fleet_sim or fleet_sim[n_uavs=64])")
    ap.add_argument("--tags", default=None,
                    help="comma-separated tag filter (paper, system, "
                    "smoke)")
    ap.add_argument("--agent", action="store_true",
                    help="run sweeps with trained A2C agents (slow)")
    ap.add_argument("--episodes", type=int, default=200)
    ap.add_argument("--json", default="BENCH_results.json",
                    help="write rows as JSON here ('' disables)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record obs events (spans, metrics, retrace "
                    "accounting) for the benched runs to a JSONL file")
    args = ap.parse_args()
    matrix = build_matrix()
    try:
        cases = matrix.select(
            only=args.only.split(",") if args.only else None,
            tags=args.tags.split(",") if args.tags else None)
    except KeyError as e:
        ap.error(str(e))
    overrides = {
        "a2c_convergence": dict(episodes=args.episodes),
        **{name: dict(use_agent=args.agent, episodes=args.episodes)
           for name in ("fig2_accuracy_sweep", "fig3_latency_sweep",
                        "fig4_energy_sweep", "table2_cut_selection")},
    }
    t_unix = time.time()
    result = runner.run(cases, trace=args.trace,
                        meta={"tool": "benchmarks", "argv": sys.argv[1:]},
                        overrides=overrides)
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({"schema": 2, "unix_time": t_unix,
                       "argv": sys.argv[1:], "errors": result.errors,
                       "git_sha": history.git_sha(),
                       "fingerprint": history.fingerprint(),
                       "rows": result.records}, f, indent=2)
        print(f"wrote {args.json} ({len(result.records)} rows)",
              flush=True)
    if result.errors:
        raise SystemExit(1)   # make ERROR rows visible to CI


if __name__ == "__main__":
    main()
