"""Kernel and model operation counts against hand counts at one small
shape each."""
import os
import sys
from pathlib import Path

# the benchmark's modules and the program, CPU only (no conftest here:
# its module name would collide with the repository's tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for _p in (Path(__file__).resolve().parents[1],
           Path(__file__).resolve().parents[3] / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import harness
import model_cost


def cost(kernel, call):
    return harness.load_module(
        harness.ROOT / "kernel_costs" / f"{kernel}.py").cost(call)


def test_flash_attention_counts_causal_pairs():
    # B=1, H=2, HK=1, S=4, Dh=8: 4*5/2 = 10 causal (query, key) pairs
    ops, nbytes, peak = cost("flash_attention", {
        "B": 1, "H": 2, "HK": 1, "Dh": 8, "Sq": 4, "Skv": 4,
        "causal": True})
    assert ops == 2 * 2 * 8 * 10 * 2          # qk and pv, 2 heads
    # q and o: 2 heads x 4 x 8; k and v: 1 kv head x 4 x 8; float32
    assert nbytes == 4 * (2 * 2 * 4 * 8 + 2 * 1 * 4 * 8)
    assert peak == "bf16_flops"


def test_flash_attention_full_counts_every_pair():
    ops, _, _ = cost("flash_attention", {
        "B": 2, "H": 1, "HK": 1, "Dh": 4, "Sq": 3, "Skv": 5,
        "causal": False})
    assert ops == 4 * 2 * 1 * 4 * 15


def test_quant_matmul_counts():
    ops, nbytes, peak = cost("quant_matmul", {"M": 2, "K": 3, "N": 4})
    assert ops == 2 * 2 * 3 * 4
    # int8 x (6) + int8 w (12) + f32 scales (2 + 4) + f32 out (8)
    assert nbytes == 6 + 12 + 4 * 6 + 4 * 8
    assert peak == "int8_ops"


def test_model_cost_by_hand():
    dm = {"d": 4, "H": 2, "HK": 1, "Dh": 2, "F": 8, "V": 10, "L": 1}
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up/down 3 x 4x8
    lin = 16 + 8 + 8 + 16 + 96
    assert model_cost.linear_params(dm) == lin
    S = 3
    attn = 4 * 1 * 2 * 2 * 6                  # 6 causal pairs
    assert model_cost.prefill(dm, S) == 2 * lin * S + attn + 2 * 4 * 10


def test_roofline_uses_the_larger_bound_per_call():
    cell = harness.find_cell("qwen2-0.5b.split-poisson")
    peaks = {"bf16_flops": 100.0, "int8_ops": 200.0, "hbm_bytes_per_s": 10.0}
    run = harness.Run(cell=cell, peaks=peaks)
    run.kernel_calls = {"quant_matmul": [{"M": 1, "K": 1, "N": 1}]}
    ops, nbytes, _ = cost("quant_matmul", {"M": 1, "K": 1, "N": 1})
    least, bound = run.kernel_bound_s("quant_matmul")
    assert bound == "memory"
    assert least == max(ops / 200.0, nbytes / 10.0)
    run.trace = {"kernel_s": {"quant_matmul": 2 * least}}
    assert abs(run.roofline("quant_matmul") - 50.0) < 1e-9
