"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

Proves the distribution config is coherent without TPU hardware: any
sharding mismatch, compile-time OOM, or unsupported collective is a bug.
Results (memory analysis, cost analysis, collective bytes, jaxpr cost) are
appended to a JSONL cache so reruns skip completed combos.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
# The VERY FIRST lines — before ANY other import — jax locks device count
# on first init. Do NOT set this anywhere global (conftest/pyproject).
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import json
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, SHAPES, get_config
from repro.launch import steps as st
from repro.launch.mesh import make_production_mesh
from repro.analysis.jaxpr_cost import analyze_jaxpr
from repro.analysis.hlo_collectives import collective_bytes
from repro.optim import AdamWConfig

# Perf-iteration variants (EXPERIMENTS.md §Perf). Config-level overrides;
# "mbN" additionally switches the train step to N-way gradient accumulation.
VARIANTS = {
    "baseline": {},
    "mla_absorb": {"mla_absorb": True},
    "bigchunk": {"attn_q_chunk": 2048, "attn_kv_chunk": 4096},
    "hugechunk": {"attn_q_chunk": 4096, "attn_kv_chunk": 8192},
    "mb8": {},
    "mb16": {},
    "noremat": {"train_remat": False},
    "causal_skip": {"attn_causal_skip": True},
    "noremat_skip": {"train_remat": False, "attn_causal_skip": True},
    "hugechunk_skip": {"attn_q_chunk": 4096, "attn_kv_chunk": 8192,
                       "attn_causal_skip": True},
    "fsdp": {"fsdp": True},
    "fsdp_skip": {"fsdp": True, "attn_causal_skip": True},
}

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun.jsonl")


def _dryrun_dtype(cfg):
    """Big models dry-run in bf16 (deployment dtype); small stay f32."""
    return cfg.with_overrides(param_dtype="bfloat16", compute_dtype="bfloat16")


def build_step(cfg, shape_name, variant="baseline"):
    info = SHAPES[shape_name]
    cfg = st.config_for_shape(cfg, shape_name)
    if info["kind"] == "train":
        mb = int(variant[2:].split("_")[0]) if variant.startswith("mb") else 1
        fn = st.make_train_step(cfg, AdamWConfig(), remat=cfg.train_remat,
                                microbatches=mb)
        order = ("params", "opt_state", "batch")
    elif info["kind"] == "prefill":
        fn = st.make_prefill_step(cfg)
        order = ("params", "batch")
    else:
        fn = st.make_serve_step(cfg)
        order = ("params", "cache", "token", "pos")
    return cfg, fn, order


def run_one(arch: str, shape_name: str, mesh_kind: str, *, jaxpr_cost=True,
            variant: str = "baseline"):
    t0 = time.time()
    cfg0 = _dryrun_dtype(get_config(arch)).with_overrides(**VARIANTS[variant])
    cfg, fn, order = build_step(cfg0, shape_name, variant)
    specs = st.input_specs(cfg0, shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    shard = st.step_shardings(cfg0, shape_name, mesh)
    args = [specs[k] for k in order]
    in_sh = [shard[k] for k in order]

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant,
           "devices": int(len(mesh.devices.flat)), "status": "ok"}
    try:
        if jaxpr_cost:
            jc = analyze_jaxpr(jax.make_jaxpr(fn)(*args))
            rec["jaxpr_flops"] = jc["flops"]
            rec["jaxpr_bytes"] = jc["bytes"]
            rec["jaxpr_bytes_min"] = jc["bytes_min"]
            rec["jaxpr_bytes_fused"] = jc["bytes_fused"]
        with mesh:
            jitted = jax.jit(fn, in_shardings=tuple(in_sh))
            lowered = jitted.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        rec["lower_s"] = round(t_lower - t0, 2)
        rec["compile_s"] = round(t_compile - t_lower, 2)
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        rec[k] = int(v)
        except Exception as e:   # noqa: BLE001 - memory analysis best-effort
            rec["memory_analysis_error"] = str(e)[:200]
        try:
            ca = compiled.cost_analysis()
            if ca:
                rec["hlo_flops"] = float(ca.get("flops", -1))
                rec["hlo_bytes"] = float(ca.get("bytes accessed", -1))
        except Exception as e:   # noqa: BLE001
            rec["cost_analysis_error"] = str(e)[:200]
        try:
            hlo = compiled.as_text()
            rec["collectives"] = collective_bytes(hlo)
            rec["hlo_len"] = len(hlo)
        except Exception as e:   # noqa: BLE001
            rec["collectives_error"] = str(e)[:200]
    except Exception as e:       # noqa: BLE001 - record the failure
        rec["status"] = "fail"
        rec["error"] = "".join(traceback.format_exception_only(e))[:2000]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def load_done(path):
    done = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                done[(r["arch"], r["shape"], r["mesh"],
                      r.get("variant", "baseline"))] = r
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    done = {} if args.force else load_done(args.out)
    v = args.variant
    todo = [(a, s, m) for a in archs for s in shapes for m in meshes
            if (a, s, m, v) not in done or done[(a, s, m, v)]["status"] != "ok"]
    print(f"dry-run: {len(todo)} combos to run "
          f"({len(done)} cached in {args.out})", flush=True)
    n_fail = 0
    for a, s, m in todo:
        rec = run_one(a, s, m, variant=args.variant)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        ok = rec["status"] == "ok"
        n_fail += (not ok)
        msg = (f"[{'OK' if ok else 'FAIL'}] {a} x {s} x {m} "
               f"({rec['total_s']}s)")
        if not ok:
            msg += f"\n    {rec['error'][:500]}"
        print(msg, flush=True)
    print(f"done; {n_fail} failures", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
