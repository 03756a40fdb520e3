"""The Mellum split cell on the CPU, at the program's reduced sizes (both
layer kinds, a window of 8 shorter than the sequences, YaRN on the full
layer, and 4 of 8 experts held where a test sets it):

- the program against ``reference/mellum.py``: split prefill at every
  cut, the float32 tree and w8, last-position logits;
- the share test: the parts that the two halves of the experts give add
  up to the reference's uncut layer;
- the YaRN frequencies against their closed form;
- ``core/transformer_cost.py`` against the benchmark's model-cost count
  at the cell's shapes, and the new kernel costs by hand;
- the cell's driver through the harness (interpret mode, about a second
  of traffic): the contract line, the traced run's per-layer metrics,
  the kernel calls it counts, and the comparison failing an altered
  answer and the control.
"""
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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import mellum_cost
import mellum_weights as MW
import run as entry
from reference import mellum as R

CELL = "mellum2-12b-a2.5b.split-code-poisson"
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
# float32 tree: both sides run float32 at "highest" on the CPU and
# differ only in the order of float32 sums (~1e-6 seen)
TOL_F32 = 1e-5
# w8: the same, except that an activation lying within a few ulp of an
# int8 rounding tie takes the neighbouring code on one side (the inputs
# differ by ~1e-6); one such code moves the logits by ~1e-2 relative
# (0.013 the most seen); the control, int4 codes, moves them by > 0.1
TOL_W8 = 0.03


def driver():
    return harness.load_module(harness.ROOT / "drivers" / "split_moe.py")


def small(held=4, experts=8):
    """The program's reduced configuration with ``held`` of ``experts``
    experts, the reference's sizes, and weights from one seed."""
    from repro.models import abstract_params
    drv = driver()
    sizes = harness.read_json(harness.ROOT / "configs"
                              / "mellum2-12b-a2.5b.json")
    cfg, _ = drv.program_config(sizes, reduced=True)
    cfg = cfg.with_overrides(n_experts=experts, experts_held=held)
    config = drv.reduced_sizes(cfg, sizes)
    dm = R.dims(config)
    w = MW.make_weights(dm, 2 ** 31 + 12345)
    return cfg, config, dm, w, MW.program_params(dm, w, abstract_params(cfg))


@pytest.mark.parametrize("version,ref_version,tol", [
    ("bf16", "f32", TOL_F32), ("w8", "w8", TOL_W8)])
def test_reference_matches_program_at_every_cut(version, ref_version, tol):
    from repro.core.partition import cut_points
    from repro.kernels import ops as kops
    from repro.quant import build_version_params
    from repro.serving import SplitServingEngine

    cfg, config, dm, w, params = small()
    assert dm["W"] < 40 and dm["full"] == (False, False, False, True)
    S = 40
    toks = np.random.default_rng(0).integers(0, dm["V"], S).astype(np.int32)
    rw = R.version_weights(w, ref_version)
    ctl_w = R.version_weights(w, "w4a8" if version == "w8" else "f32")
    with kops.jnp_reference(), jax.default_matmul_precision("highest"):
        eng = SplitServingEngine(cfg, params, versions=(version,))
        eng._vparams.update(jax.jit(lambda p: build_version_params(
            cfg, p, (version,)))(params))
    for cut in cut_points(cfg):
        with kops.jnp_reference(), jax.default_matmul_precision("highest"):
            got, _ = eng.infer({"tokens": jnp.asarray(toks)[None]}, cut,
                               version)
        got = np.asarray(got)[0, -1]
        ref = np.asarray(R.forward(config, rw, toks, rows=[S - 1],
                                   cut=cut[1],
                                   link_int8=(version == "w8")))[0]
        assert R.rel_err(got, ref) < tol, (cut, R.rel_err(got, ref))
        # and the control (one precision step down) is far from both
        ctl = np.asarray(R.forward(
            config, ctl_w, toks, rows=[S - 1], cut=cut[1],
            link_int8=(version == "w8"),
            dtype="float32" if version == "w8" else "bfloat16"))[0]
        assert R.rel_err(ctl, ref) > 3 * tol, cut


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each of two chips holds half of the 8 experts and routes over all
    8: the program's MoE layer on each half (the second half is the same
    layer holding experts 4-7, its router's columns rotated so that they
    come first) gives that chip's part, and the two parts add up to the
    reference's layer holding all 8."""
    from repro.models.moe import apply_moe

    cfg, config, dm, w, _ = small(held=8)
    lw = {k: w[k][0] for k in ("router", "w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.key(3), (1, 40, dm["d"]), jnp.float32)
    half = cfg.with_overrides(experts_held=4)
    parts = []
    with jax.default_matmul_precision("highest"):
        for lo in (0, 4):
            p = {"router": jnp.roll(lw["router"], -lo, axis=1),
                 **{k: lw[k][lo:lo + 4] for k in ("w_gate", "w_up",
                                                  "w_down")}}
            parts.append(np.asarray(apply_moe(half, p, x)[0])[0])
    whole, rows = R._moe(dict(dm, held=8), lw, x[0],
                         jax.lax.Precision.HIGHEST, jnp.float32)
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert int(np.asarray(rows).sum()) == 40 * dm["K"]


def test_yarn_frequencies_match_the_closed_form():
    from repro.configs import get_config
    from repro.models.layers import rope_freqs, yarn_freqs

    cfg = get_config("mellum2-12b-a2.5b")
    y = cfg.rope_yarn
    got = np.asarray(yarn_freqs(128, cfg.rope_theta, y), np.float64)
    want = R.yarn_inv_freq(128, cfg.rope_theta, (
        y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
        y.attention_factor))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the truncated correction range is [18, 35]: below it the plain
    # frequencies, above it the plain ones over the factor 16
    plain = np.asarray(rope_freqs(128, cfg.rope_theta), np.float64)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=2e-6)
    assert np.all((got[19:35] < plain[19:35])
                  & (got[19:35] > plain[19:35] / 16))


def test_transformer_cost_matches_the_benchmark_count():
    """The controller's pricing and the benchmark's model-cost count agree
    at the cell's shapes: the weight matmuls at this chip's share of the
    top k (top_k x held / n_experts per token), and each layer's
    attention at its own window (the pricing charges every token the
    layer's context, min(S, window); the count, the causal pairs)."""
    from repro.core.transformer_cost import block_flops_per_token
    drv = driver()
    config = harness.find_cell(CELL).config
    cfg, dm = drv.program_config(config)
    weights = block_flops_per_token(cfg, weights_only=True)
    score = 4.0 * dm["H"] * dm["Dh"]          # per (query, key) pair
    windows = [None if full else dm["W"] for full in dm["full"]]
    for S in (2048, 8192):
        rows = S * dm["K"] * dm["held"] // dm["E"] * dm["L"]
        attn = sum(score * mellum_cost.pairs(S, w) for w in windows)
        head = 2.0 * dm["d"] * dm["V"]
        assert S * sum(weights) + attn + head == pytest.approx(
            mellum_cost.prefill(dm, S, rows), rel=1e-12)
        priced = block_flops_per_token(cfg, seq_ctx=S)
        assert [p - w for p, w in zip(priced, weights)] == pytest.approx(
            [score * min(S, w or S) for w in windows], rel=1e-12)


def test_new_kernel_costs_by_hand():
    gm = harness.load_module(harness.ROOT / "kernel_costs"
                             / "grouped_matmul.py")
    # experts with 3, 0 and 2 rows, K = 4, N = 5, float32
    ops, nbytes, peak = gm.cost({"rows": [3, 0, 2], "K": 4, "N": 5})
    assert ops == 2 * 5 * 4 * 5
    assert nbytes == 4 * (2 * 4 * 5 + 5 * (4 + 5))
    assert peak == "bf16_flops"
    fw = harness.load_module(harness.ROOT / "kernel_costs"
                             / "flash_attention_window.py")
    # window 2 over 4 positions: pairs (0,0) (1,0) (1,1) (2,1) (2,2)
    # (3,2) (3,3): 7 = 2 * 4 - 1
    ops, nbytes, _ = fw.cost({"B": 1, "H": 2, "HK": 1, "Dh": 8, "Sq": 4,
                              "Skv": 4, "window": 2})
    assert ops == 4 * 2 * 8 * 7
    assert nbytes == 4 * 8 * (2 * 2 * 4 + 2 * 1 * 4)
    assert mellum_cost.pairs(4, 2) == 7 and mellum_cost.pairs(4) == 10


# --------------------------------------------------------------------------
# the driver through the harness
# --------------------------------------------------------------------------

@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")


def small_cell():
    """The cell with its mix cut to CPU sizes (one length, rate, sample)."""
    cell = harness.find_cell(CELL)
    mix = cell.mix
    mix["fields"]["prompt_len"] = {"values": [24]}
    mix.update(rate_rps=6, trace_start_s=0.1, trace_seconds=1.0)
    mix["check"]["per_version"] = 3
    return cell


def measure(trace=False, seconds=1.5):
    return entry.measure(small_cell(), 2 ** 31 + 5, seconds, trace,
                         jax.devices(), PEAKS, reduced=True, t_start=0.0)


def test_contract_line(interpret):
    result, lines, _ = measure()
    assert set(result["metrics"]) == {"infer_p50_ms", "infer_p95_ms",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"], lines
    assert [n for n, _, _ in lines] == ["gap.w8", "rel_err.w8"]


def test_traced_run_reports_layers_and_counts_calls(interpret, monkeypatch):
    drv = driver()
    seen = {}
    record = drv.run_record

    def spy(st, prof, run):
        record(st, prof, run)
        seen.update(st=st, run=run)

    monkeypatch.setattr(drv, "run_record", spy)
    result, _, _ = measure(trace=True)
    assert result["correct"]
    # a CPU trace has no TPU plane: the device readers find nothing
    assert set(result["metrics"]) <= {m["name"] for m in
                                      harness.find_cell(CELL).per_layer}
    st, run = seen["st"], seen["run"]
    n = run.counters["traced_requests"]
    assert n > 0
    calls = run.kernel_calls
    L, full = st.dm["L"], sum(st.dm["full"])
    assert len(calls["grouped_matmul"]) == 3 * L * n
    assert len(calls["flash_attention"]) == full * n
    assert len(calls["flash_attention_window"]) == (L - full) * n
    # w8: four projections a layer and the head
    heads = [c for c in calls["quant_matmul"] if c["N"] == st.dm["V"]]
    assert len(calls["quant_matmul"]) == (4 * L + 1) * len(heads)
    # every traced request routed each token to top_k experts, of which
    # the held ones' rows are counted
    total = sum(sum(c["rows"]) for c in calls["grouped_matmul"]) // 3
    assert 0 < total <= n * L * 24 * st.dm["K"]
    assert run.model_flops > 0


def test_answer_altered_is_not_correct(interpret, monkeypatch):
    from repro.serving import SplitServingEngine

    infer = SplitServingEngine.infer

    def altered(self, batch, cut, version="bf16"):
        logits, nbytes = infer(self, batch, cut, version)
        last = logits[0, -1]
        other = (np.asarray(last).argmax() + 7) % last.shape[-1]
        return logits.at[0, -1, other].add(1e3), nbytes

    monkeypatch.setattr(SplitServingEngine, "infer", altered)
    result, lines, _ = measure()
    assert not result["correct"], lines


def test_control_is_not_correct(interpret):
    """The control in the program's place (each sampled answer replaced
    by the reference one precision step down: int4 codes for w8) fails
    the cell's comparison."""
    import calibrate

    cell = small_cell()
    drv = cell.driver
    st = drv.setup(cell, 2 ** 31 + 9, harness.Spans(), reduced=True)
    drv.window(st, 1.5, None, harness.Compiles.install())
    prog, ctl, passes, _ = calibrate.control(st, cell.limits)
    assert not passes
    assert ctl["rel_err.w8"] > 3 * prog["rel_err.w8"]
