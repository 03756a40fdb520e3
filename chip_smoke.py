"""Bring-up check of the main path on TPU, at qwen2-0.5b's published widths.

    python chip_smoke.py              # one chip: all four phases below
    python chip_smoke.py --chips 4    # four chips: sharded fleet scan only

One process drives the chip through the library's own entry points, with
random weights from ``--seed``:

1. controller: A2C on the TPU env profiled at full width, then ``decide``;
2. split serving: every quant version at the controller's cut and at a
   fixed cut, against an unsplit float32 jnp reference on the same chip;
3. batching server: requests through ``ContinuousBatchingServer``, and its
   first decode step against the jnp reference;
4. fleet scan: the ``megafleet`` world (100k devices) on ``engine="scan"``.

Each phase checks its results and raises on a failure. Wall times include
compilation and are set-up times, not measurements. The last line printed
is a JSON object naming the device. Without a TPU the script exits nonzero
before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-0.5b"
VERSIONS = ("bf16", "w8", "w4")

# Tolerances on the relative L2 error ||got - ref|| / ||ref|| of logits,
# where ref runs the jnp path at float32 ("highest" matmul precision).
#
# bf16 and w4: the kernel path runs float32 matmuls at the TPU's default
# precision, which rounds operands to bfloat16 (unit roundoff 2^-9); over
# 24 residual layers that is of order 1e-2 relative in the logits. w4
# dequantizes its weights exactly on both paths, so it shares the bound.
SPLIT_TOL = {"bf16": 3e-2, "w4": 3e-2,
             # w8 adds two errors the reference does not share: the int8
             # link quantization of the cut activation (up to amax/254 per
             # row), and int8 activation codes that move one step wherever
             # default precision pushes a value across a rounding boundary.
             "w8": 1e-1}
# First decode step of the batching server: same default-precision bound
# as bf16 above, through prefill, the ring cache and flash decode.
DECODE_TOL = 3e-2
# Sharded vs unsharded fleet scan: per-shard world-noise keys differ, so
# latencies agree only statistically. Means over ~10^6 requests agree to
# ~1%; percentiles come from log bins 3.7% wide, so allow two bins.
FLEET_MEAN_RTOL = 0.02
FLEET_PCT_RTOL = 0.08


def log(msg: str):
    print(msg, flush=True)


def rel_err(got, ref) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def check_logits(name, got, ref, tol):
    import jax.numpy as jnp
    if got.shape != ref.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {ref.shape}")
    if not bool(jnp.all(jnp.isfinite(got))):
        raise AssertionError(f"{name}: non-finite logits")
    err = rel_err(got, ref)
    log(f"  {name}: rel_l2_err={err!r} (tol {tol})")
    if not err <= tol:
        raise AssertionError(f"{name}: rel_l2_err {err} > {tol}")
    return err


def require_kernels(name, jitted, args, want):
    from repro.kernels.ops import compiled_kernels
    have = compiled_kernels(jitted.lower(*args).compile())
    log(f"  {name}: kernels {sorted(have)}")
    if not set(want) <= have:
        raise AssertionError(f"{name}: expected kernels {sorted(want)}, "
                             f"compiled program has {sorted(have)}")


def model_config(reduced: bool):
    from repro.configs import get_config
    cfg = get_config(ARCH)
    return cfg.reduced() if reduced else cfg


def random_tokens(cfg, shape, seed):
    import jax.numpy as jnp
    r = np.random.default_rng(seed)
    return jnp.asarray(r.integers(0, cfg.vocab_size, shape), jnp.int32)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_controller(*, reduced=False, episodes=8, batch_envs=4, slots=3,
                     seed=0):
    """Train A2C for a few updates on the TPU env and decide a few slots.
    Returns the first decision's cut, and a fixed cut (the profile's first
    candidate), both resolved to partition cuts."""
    import jax
    import jax.numpy as jnp
    from repro.core import (A2CConfig, decide, env_reset, env_step,
                            make_tpu_env, resolve_selection, train_agent,
                            transformer_profile)

    cfg = model_config(reduced)
    env_cfg, tables = make_tpu_env([ARCH], reduced=reduced)
    agent, history = train_agent(
        env_cfg, tables, A2CConfig(episodes=episodes, batch_envs=batch_envs),
        seed=seed)
    if len(history) != episodes:
        raise AssertionError(f"{len(history)} updates, expected {episodes}")
    for row in history:
        if not all(np.isfinite(v) for v in row.values()):
            raise AssertionError(f"non-finite training stats: {row}")
    log(f"  trained {episodes} updates x {batch_envs} envs: "
        f"last reward={history[-1]['mean_reward']!r} "
        f"loss={history[-1]['loss']!r}")

    profile = transformer_profile(cfg)
    valid = np.asarray(tables.version_valid)
    state = env_reset(env_cfg, tables, jax.random.key(seed))
    rng = jax.random.key(seed + 1)
    picks = []
    for t in range(slots):
        actions = np.asarray(decide(agent, env_cfg, tables, state))
        if actions.shape != (env_cfg.n_uavs, 2):
            raise AssertionError(f"decide returned {actions.shape}")
        j, k = int(actions[0, 0]), int(actions[0, 1])
        if not (valid[0, j] and 0 <= k < tables.n_cuts):
            raise AssertionError(f"invalid action (version {j}, cut {k})")
        version, cut = resolve_selection(cfg, profile, j, k)
        picks.append((version, cut))
        log(f"  slot {t}: action ({j}, {k}) -> version={version} cut={cut}")
        rng, k_env = jax.random.split(rng)
        state, _, _ = env_step(env_cfg, tables, state, jnp.asarray(actions),
                               k_env)
    _, fixed_cut = resolve_selection(cfg, profile, 0, 0)
    log(f"  fixed cut: action (0, 0) -> cut={fixed_cut}")
    return picks[0][1], fixed_cut


def phase_split_serving(params, cut, fixed_cut, *, reduced=False, batch=4,
                        seq=256, seed=0, check_kernels=True):
    """Every version at ``cut`` and ``fixed_cut`` through the split engine,
    against the unsplit float32 jnp forward of the same version's params
    (for w8 that forward runs ``quant_matmul_ref``)."""
    import jax
    from repro.core import partition
    from repro.kernels import ops as kops
    from repro.models import forward_logits
    from repro.quant import build_version_params, get_version
    from repro.serving import SplitServingEngine

    cfg = model_config(reduced)
    engine = SplitServingEngine(cfg, params, versions=VERSIONS)
    vparams = build_version_params(cfg, params, VERSIONS)
    b = {"tokens": random_tokens(cfg, (batch, seq), seed)}
    cuts = list(dict.fromkeys([tuple(cut), tuple(fixed_cut)]))
    errs = {}
    for v in VERSIONS:
        dtypes = sorted({str(leaf.dtype) for leaf in jax.tree.leaves(
            vparams[v])})
        note = (" (aliases the float32 tree: param_dtype="
                f"{cfg.param_dtype})" if vparams[v] is params else "")
        log(f"  {v}: param dtypes {dtypes}{note}")
        with kops.jnp_reference(), jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda p, x: forward_logits(cfg, p, x))(
                vparams[v], b)
        for c in cuts:
            logits, act_bytes = engine.infer(b, c, v)
            n_act = batch * seq * cfg.d_model
            want = (n_act + batch * seq * 4 if get_version(v).act_bits == 8
                    else n_act * cfg.cdtype.itemsize)
            log(f"  {v} cut={c}: act_bytes={act_bytes}")
            if act_bytes != want:
                raise AssertionError(f"act_bytes {act_bytes} != {want}")
            errs[(v, c)] = check_logits(f"{v} cut={c}", logits, ref,
                                        SPLIT_TOL[v])
        if check_kernels:
            want_k = (("flash_attention", "quant_matmul")
                      if get_version(v).act_bits == 8
                      else ("flash_attention",))
            c = cuts[0]
            act = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.cdtype)
            require_kernels(f"{v} head", jax.jit(
                lambda p, x: partition.run_head(cfg, p, x, c)),
                (vparams[v], b), want_k)
            require_kernels(f"{v} tail", jax.jit(
                lambda p, a, x: partition.run_tail(cfg, p, a, x, c)),
                (vparams[v], act, b), want_k)
        del ref
    return errs


def phase_batching_server(params, *, reduced=False, n_requests=8,
                          prompt_lens=(128, 200), max_new_tokens=16,
                          max_batch=4, cache_len=1024, seed=0,
                          check_kernels=True):
    """Requests through the continuous-batching server; then its first
    decode step on the kernel path against the jnp path."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.models import model as M
    from repro.serving import ContinuousBatchingServer, Request

    cfg = model_config(reduced)
    server = ContinuousBatchingServer(cfg, params, max_batch=max_batch,
                                      cache_len=cache_len)
    r = np.random.default_rng(seed)
    per_len = -(-n_requests // len(prompt_lens))
    for i in range(n_requests):
        plen = prompt_lens[min(i // per_len, len(prompt_lens) - 1)]
        server.submit(Request(rid=i, max_new_tokens=max_new_tokens,
                              tokens=r.integers(0, cfg.vocab_size, plen)))
    done = server.run()
    st = server.stats
    log(f"  completed={st.completed} prefills={st.prefills} "
        f"decode_steps={st.decode_steps} truncated={st.truncated}")
    if len(done) != n_requests or st.completed != n_requests:
        raise AssertionError(f"{len(done)} of {n_requests} completed")
    for req in done:
        if req.truncated or len(req.out) != max_new_tokens:
            raise AssertionError(f"request {req.rid}: {len(req.out)} tokens, "
                                 f"truncated={req.truncated}")
        if not all(0 <= t < cfg.vocab_size for t in req.out):
            raise AssertionError(f"request {req.rid}: token out of range")

    # the server's programs: M.prefill at cache_len, then M.decode_step
    S = prompt_lens[0]
    toks = random_tokens(cfg, (max_batch, S), seed + 1)

    def programs():
        return (jax.jit(lambda p, x: M.prefill(cfg, p, x,
                                                total_len=cache_len)),
                jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t,
                                                           pos)))

    prefill, decode = programs()
    logits0, cache = prefill(params, {"tokens": toks})
    tok = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
    pos = jnp.int32(S)
    logits1, _ = decode(params, cache, tok, pos)
    with kops.jnp_reference(), jax.default_matmul_precision("highest"):
        prefill_ref, decode_ref = programs()
        ref0, cache_ref = prefill_ref(params, {"tokens": toks})
        ref1, _ = decode_ref(params, cache_ref, tok, pos)
    check_logits("prefill logits", logits0, ref0, DECODE_TOL)
    err = check_logits("first decode step logits", logits1, ref1, DECODE_TOL)
    if check_kernels:
        require_kernels("prefill", prefill, (params, {"tokens": toks}),
                        ("flash_attention",))
        require_kernels("decode", decode, (params, cache, tok, pos),
                        ("flash_decode",))
    return err


def fleet_scan(*, devices=100_000, epochs=6, policy="device_only", seed=0,
               shard=False):
    """The megafleet world on the scan engine; checks that every
    presampled request is either served or dropped."""
    from repro.policies import build_policy
    from repro.scenarios import get_scenario
    from repro.sim import FleetConfig, presample_counts, simulate

    sc = get_scenario("megafleet").replace(devices=devices)
    env_cfg, tables, model_ids, _ = sc.build_env()
    pol = build_policy(policy, env_cfg, tables)
    trace = sc.build_trace()
    fl = FleetConfig(slo_s=sc.slo_s, engine="scan", shard=shard,
                     max_epochs=epochs)
    res = simulate(env_cfg, tables, pol, trace, n_requests=sc.n_requests,
                   seed=seed, fleet=fl, model_ids=model_ids)
    # the presampled total, drawn again from the same trace stream
    s_trace, _ = np.random.SeedSequence(seed).spawn(2)
    total = int(presample_counts(trace, np.random.default_rng(s_trace),
                                 devices, sc.slot_seconds, sc.n_requests,
                                 epochs).sum())
    s = res.summary
    count, dropped = int(s["count"]), int(s["dropped"])
    log(f"  {devices} devices x {res.epochs} epochs, shard={shard} "
        f"(mesh of {res.mesh_devices}): served={count} dropped={dropped} "
        f"presampled={total} mean={s['mean']!r} p50={s['p50']!r} "
        f"p95={s['p95']!r} slo_attainment={s['slo_attainment']!r}")
    if count + dropped != total or res.served != total:
        raise AssertionError(f"count {count} + dropped {dropped} != "
                             f"presampled {total}")
    for key in ("mean", "p50", "p95", "energy_j"):
        if not np.isfinite(s[key]):
            raise AssertionError(f"non-finite {key}: {s[key]}")
    return res


def phase_fleet_sharded(chips, *, devices=100_000, epochs=6,
                        policy="device_only", seed=0):
    """The fleet scan sharded over ``chips`` devices against the same
    world, seed and policy unsharded on one device."""
    import jax

    if len(jax.devices()) != chips:
        raise AssertionError(f"{len(jax.devices())} devices, "
                             f"expected {chips}")
    one = fleet_scan(devices=devices, epochs=epochs, policy=policy,
                     seed=seed, shard=False)
    many = fleet_scan(devices=devices, epochs=epochs, policy=policy,
                      seed=seed, shard=True)
    if (one.mesh_devices, many.mesh_devices) != (1, chips):
        raise AssertionError(f"meshes of {one.mesh_devices} and "
                             f"{many.mesh_devices} devices, expected 1 "
                             f"and {chips}")
    a, b = one.summary, many.summary
    for key in ("count", "dropped"):
        if a[key] != b[key]:
            raise AssertionError(f"{key}: {a[key]} != {b[key]}")
    if not np.array_equal(one.selection_hist, many.selection_hist):
        raise AssertionError("selection_hist differs")
    for key, tol in (("mean", FLEET_MEAN_RTOL), ("p50", FLEET_PCT_RTOL),
                     ("p95", FLEET_PCT_RTOL)):
        rel = abs(b[key] - a[key]) / abs(a[key])
        log(f"  {key}: unsharded={a[key]!r} sharded={b[key]!r} "
            f"rel_diff={rel!r} (tol {tol})")
        if not rel <= tol:
            raise AssertionError(f"{key} differs by {rel} > {tol}")


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def run_phase(name, fn, *args, **kw):
    log(f"phase {name}")
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    log(f"phase {name}: passed, {time.perf_counter() - t0!r} s wall "
        "including compilation (set-up time)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: all phases on one chip; 4: the sharded fleet "
                         "scan over four chips and its unsharded reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devs[0].platform!r} devices only")
    if len(devs) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} devices")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device['kind']} x{device['count']} "
        f"(jax {jax.__version__})")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))

    if args.chips == 1:
        from repro.models import init

        cut, fixed_cut = run_phase("1 controller", phase_controller,
                                   seed=args.seed)
        params = init(model_config(False), jax.random.key(args.seed))
        run_phase("2 split serving", phase_split_serving, params, cut,
                  fixed_cut, seed=args.seed)
        run_phase("3 batching server", phase_batching_server, params,
                  seed=args.seed)
        del params
        run_phase("4 fleet scan", fleet_scan, policy="greedy_oracle",
                  seed=args.seed)
    else:
        run_phase("fleet scan sharded", phase_fleet_sharded, args.chips,
                  seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
