"""repro.quant: quantization roundtrips, the int8 Pallas matmul, version
tables derived from real variants, and split-execution correctness of the
controller's full (version, cut) action."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (evaluate_policy, make_tpu_env, resolve_selection,
                        transformer_profile)
from repro.policies import build_policy
from repro.core.partition import cut_for_layer, cut_points
from repro.kernels.quant_matmul import quant_matmul, quant_matmul_ref
from repro.models import forward_logits, init
from repro.quant import (DEFAULT_VERSIONS, QTensor, accuracy_proxy,
                         build_version_params, dequantize_tree, get_version,
                         quantize, quantize_act, quantize_tree,
                         relative_quant_error, tree_weight_bytes)
from repro.serving import SplitServingEngine
from tests.conftest import make_batch


def _rand(shape, seed=0, scale=1.0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)


# --------------------------------------------------------------------------
# quantize / dequantize
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode,max_rel", [("w8wo", 0.02), ("w8a8", 0.02),
                                          ("w4", 0.15)])
def test_quantize_roundtrip_error(mode, max_rel):
    w = _rand((96, 130), scale=0.1)
    qt = quantize(w, mode)
    rel = float(jnp.linalg.norm(w - qt.dequantize()) / jnp.linalg.norm(w))
    assert rel < max_rel, (mode, rel)
    assert qt.shape == w.shape


def test_int4_packing_is_lossless():
    """Packing two int4 codes per byte must not change the dequantization
    (pack -> unpack is the identity on the codes)."""
    w = _rand((64, 40), seed=3)
    qt = quantize(w, "w4")
    assert qt.q.shape == (32, 40) and qt.q.dtype == jnp.uint8
    from repro.quant.quantize import _QMAX, _pack_int4, _unpack_int4
    codes = _unpack_int4(qt.q)
    assert int(jnp.max(jnp.abs(codes))) <= _QMAX[4]
    np.testing.assert_array_equal(np.asarray(_unpack_int4(_pack_int4(codes))),
                                  np.asarray(codes))


def test_quantized_tree_slices_and_scans():
    """QTensor leaves must survive the stacked-param operations partition
    and model code perform: leading-axis tree slicing."""
    w = _rand((4, 64, 40), seed=1)
    qt = quantize(w, "w4")
    sl = jax.tree.map(lambda a: a[1:3], qt)
    np.testing.assert_allclose(np.asarray(sl.dequantize()),
                               np.asarray(qt.dequantize()[1:3]), rtol=1e-6)


def test_quantize_tree_selects_dense_weights_only():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    qp = quantize_tree(params, "w8wo")
    stack = qp["stacks"]["main"]["blk"]
    assert isinstance(stack["attn"]["wq"], QTensor)
    assert isinstance(stack["mlp"]["w_down"], QTensor)
    # embeddings and norms stay full precision
    assert not isinstance(qp["tok_embed"], QTensor)
    assert not isinstance(stack["norm1"]["scale"], QTensor)
    # dequantize_tree restores plain arrays of the original shapes
    dq = dequantize_tree(qp)
    assert dq["stacks"]["main"]["blk"]["attn"]["wq"].shape \
        == params["stacks"]["main"]["blk"]["attn"]["wq"].shape


def test_quantize_tree_skips_moe_experts():
    """Routed expert weights reuse the dense-MLP leaf names but are
    consumed in float by the grouped matmul — they must stay full
    precision and the quantized MoE model must still run."""
    cfg = get_config("mixtral-8x22b").reduced()
    params = init(cfg, jax.random.key(1))
    qp = quantize_tree(params, "w8wo")
    moe = qp["stacks"]["main"]["blk"]["moe"]
    assert not isinstance(moe["w_gate"], QTensor)
    assert not isinstance(moe["router"], QTensor)
    # attention projections around the MoE are still quantized
    assert isinstance(qp["stacks"]["main"]["blk"]["attn"]["wq"], QTensor)
    batch = make_batch(cfg)
    del batch["targets"]
    full = forward_logits(cfg, params, batch)
    ql = forward_logits(cfg, qp, batch)
    rel = float(jnp.linalg.norm(ql - full) / jnp.linalg.norm(full))
    assert rel < 0.1, rel


@pytest.mark.parametrize("arch", ["mellum2-12b-a2.5b", "qwen2-0.5b"])
def test_version_trees_share_the_leaves_they_do_not_quantize(arch):
    """build_version_params quantizes in one jitted call over the weights
    quantize_tree converts: each quantized tree holds exactly those as
    QTensors, equal to jitted quantize_tree's, and every other leaf (the float
    experts and router, embeddings, norms) is the float tree's own array,
    so the versions hold one copy of it."""
    cfg = get_config(arch).reduced()
    params = init(cfg, jax.random.key(3))
    trees = build_version_params(cfg, params, ("bf16", "w8", "w4"))
    assert trees["bf16"] is params
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for v in ("w8", "w4"):
        want = jax.jit(quantize_tree, static_argnums=1)(
            params, get_version(v).mode)
        got = jax.tree_util.tree_flatten_with_path(
            trees[v], is_leaf=lambda x: isinstance(x, QTensor))[0]
        ref = jax.tree.leaves(want, is_leaf=lambda x: isinstance(x, QTensor))
        assert [p for p, _ in got] == [p for p, _ in flat]
        n_q = 0
        for (path, leaf), (_, x), r in zip(got, flat, ref):
            if isinstance(r, QTensor):
                n_q += 1
                assert isinstance(leaf, QTensor), path
                np.testing.assert_array_equal(leaf.q, r.q)
                np.testing.assert_array_equal(leaf.scale, r.scale)
            else:
                assert leaf is x, path
        assert n_q > 0
    if cfg.n_experts:
        moe = trees["w8"]["stacks"]["main"]["blk"]["moe"]
        assert moe is params["stacks"]["main"]["blk"]["moe"]


def test_quantized_tree_bytes_shrink():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    b16 = tree_weight_bytes(params)
    b8 = tree_weight_bytes(quantize_tree(params, "w8wo"))
    b4 = tree_weight_bytes(quantize_tree(params, "w4"))
    assert b4 < b8 < b16


# --------------------------------------------------------------------------
# int8 matmul kernel
# --------------------------------------------------------------------------

def test_quant_matmul_ref_matches_dequantized_matmul():
    """The int32 accumulation is exact, so the rescaled int8 matmul must
    equal the f32 matmul of the dequantized operands to float tolerance."""
    x = _rand((10, 96), seed=5)
    w = _rand((96, 130), seed=6, scale=0.1)
    qt = quantize(w, "w8a8")
    xq, xs = quantize_act(x)
    got = quant_matmul_ref(xq, qt.q, xs.reshape(-1), qt.scale.reshape(-1))
    want = (xq.astype(jnp.float32) * xs) @ qt.dequantize()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("M,K,N", [(10, 96, 130), (128, 128, 128),
                                   (1, 260, 50)])
def test_quant_matmul_pallas_matches_ref(M, K, N):
    x = _rand((M, K), seed=7)
    w = _rand((K, N), seed=8, scale=0.1)
    qt = quantize(w, "w8a8")
    xq, xs = quantize_act(x)
    ref = quant_matmul_ref(xq, qt.q, xs.reshape(-1), qt.scale.reshape(-1))
    got = quant_matmul(xq, qt.q, xs.reshape(-1), qt.scale.reshape(-1),
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_dense_dispatch_pallas_vs_ref(monkeypatch):
    """layers.dense on a w8a8 leaf: the REPRO_USE_PALLAS=interpret path
    must match the jnp-reference path bit-for-bit (same int8 codes in,
    same int32 accumulation)."""
    from repro.models.layers import dense
    x = _rand((2, 8, 96), seed=9)
    qt = quantize(_rand((96, 64), seed=10, scale=0.1), "w8a8")
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    y_ref = dense(x, qt)
    assert y_ref.shape == (2, 8, 64)
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    y_pl = dense(x, qt)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# version registry -> env tables
# --------------------------------------------------------------------------

def test_version_registry_derives_tables():
    bf16, w8, w4 = (get_version(n) for n in DEFAULT_VERSIONS)
    # accuracy proxy strictly ordered by measured quantization error
    assert accuracy_proxy(bf16) > accuracy_proxy(w8) > accuracy_proxy(w4)
    assert relative_quant_error(16, 0) == 0.0
    # weight shipping ordered by code width; w8a8 halves the MAC cost
    assert bf16.bytes_per_param > w8.bytes_per_param > w4.bytes_per_param
    assert w8.matmul_cost_scale == 0.5 and w4.matmul_cost_scale == 1.0
    assert w8.act_itemsize == 1 and w4.act_itemsize == 2


def test_transformer_profile_tables_from_quant():
    cfg = get_config("qwen2-0.5b")
    prof = transformer_profile(cfg)
    by_name = {v.version: v for v in prof.versions}
    assert set(by_name) == set(DEFAULT_VERSIONS)
    assert by_name["bf16"].accuracy > by_name["w8"].accuracy \
        > by_name["w4"].accuracy
    # w8a8 halves the dense-projection share of FLOPs (scores and other
    # einsum-consumed compute stay full precision)
    assert by_name["bf16"].total_flops / 2 < by_name["w8"].total_flops \
        < by_name["bf16"].total_flops
    # w8 ships int8 cut activations; bf16/w4 ship the compute dtype
    c = by_name["bf16"].cut_points[0]
    act_width = cfg.cdtype.itemsize
    assert by_name["w8"].cut_bytes(c) == pytest.approx(
        by_name["bf16"].cut_bytes(c) / act_width)
    assert by_name["w4"].cut_bytes(c) == by_name["bf16"].cut_bytes(c)
    # weight shipping: only the dense share prices at the code width, so
    # w4 < w8 < bf16 with w4 well under half for a dense-dominated arch
    wb = {n: by_name[n].tail_weight_bytes(c) for n in by_name}
    assert wb["w4"] < wb["w8"] < wb["bf16"]
    assert wb["w4"] < 0.5 * wb["bf16"]


# --------------------------------------------------------------------------
# split execution with quantized versions
# --------------------------------------------------------------------------

def test_split_engine_quantized_versions_match_bf16():
    """bf16 split == full forward exactly; quantized versions track the
    bf16 logits (w8 within the acceptance rtol, w4 within its looser,
    measured-error-priced bound)."""
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    batch = make_batch(cfg)
    del batch["targets"]
    full = forward_logits(cfg, params, batch)
    eng = SplitServingEngine(cfg, params, versions=DEFAULT_VERSIONS)
    for cut in cut_points(cfg):
        lf, bf = eng.infer(batch, cut, "bf16")
        np.testing.assert_allclose(np.asarray(lf), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)
        l8, b8 = eng.infer(batch, cut, "w8")
        rel8 = float(jnp.linalg.norm(l8 - lf) / jnp.linalg.norm(lf))
        assert rel8 < 0.1, (cut, rel8)
        l4, _ = eng.infer(batch, cut, "w4")
        rel4 = float(jnp.linalg.norm(l4 - lf) / jnp.linalg.norm(lf))
        assert rel4 < 0.5, (cut, rel4)
        # w8 ships int8 codes (+ f32 row scales) across the link
        assert b8 < bf


def test_split_engine_w8_pallas_interpret(monkeypatch):
    """The w8a8 trunk runs through the Pallas kernel end-to-end in
    interpret mode and stays close to the jnp-reference path."""
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    batch = make_batch(cfg, B=1, S=8)
    del batch["targets"]
    cut = cut_points(cfg)[0]
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    ref_logits, _ = SplitServingEngine(
        cfg, params, versions=("w8",)).infer(batch, cut, "w8")
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    pl_logits, _ = SplitServingEngine(
        cfg, params, versions=("w8",)).infer(batch, cut, "w8")
    np.testing.assert_allclose(np.asarray(pl_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_ssm_arch_not_spuriously_penalized():
    """A pure-SSM trunk quantizes (almost) nothing, so its quant versions
    must not be charged the dense-probe accuracy error (or FLOP/weight
    discounts) the executable model doesn't exhibit."""
    prof = transformer_profile(get_config("falcon-mamba-7b"))
    by = {v.version: v for v in prof.versions}
    assert by["w4"].accuracy == pytest.approx(by["bf16"].accuracy)
    assert by["w8"].total_flops == pytest.approx(by["bf16"].total_flops)


def test_cut_for_layer_covers_all_archs():
    for arch in ("qwen2-0.5b", "falcon-mamba-7b", "recurrentgemma-2b"):
        cfg = get_config(arch).reduced()
        legal = set(cut_points(cfg))
        prof = transformer_profile(cfg)
        for v in prof.versions:
            for layer in v.cut_points:
                assert cut_for_layer(cfg, layer) in legal


# --------------------------------------------------------------------------
# acceptance: controller decision -> quantized split execution, end to end
# --------------------------------------------------------------------------

def test_tpu_env_modal_selection_executes_quantized():
    """evaluate_policy on a make_tpu_env setup whose version axis is
    {bf16, w8, w4} derived from repro.quant; the modal (version, cut) is
    executed by SplitServingEngine with the matching quantized params."""
    arch = "qwen2-0.5b"
    env_cfg, tables = make_tpu_env([arch], reduced=True, episode_len=16)
    assert tables.n_versions == len(DEFAULT_VERSIONS)
    assert float(jnp.min(tables.tail_weight_bytes)) >= 0.0
    m = evaluate_policy(env_cfg, tables,
                        build_policy("greedy_oracle", env_cfg, tables),
                        jax.random.key(0), episodes=1)
    assert np.isfinite(m["reward"])
    j, k = m["modal_selection"][arch]

    cfg = get_config(arch).reduced()
    prof = transformer_profile(cfg)
    version, cut = resolve_selection(cfg, prof, j, k)
    assert version in DEFAULT_VERSIONS

    params = init(cfg, jax.random.key(0))
    eng = SplitServingEngine(cfg, params, versions=DEFAULT_VERSIONS)
    batch = make_batch(cfg)
    del batch["targets"]
    logits_sel, act_bytes = eng.infer(batch, cut, version)
    logits_bf16, _ = eng.infer(batch, cut, "bf16")
    assert act_bytes > 0
    rel = float(jnp.linalg.norm(logits_sel - logits_bf16)
                / jnp.maximum(jnp.linalg.norm(logits_bf16), 1e-12))
    tol = 0.1 if version in ("bf16", "w8") else 0.5
    assert rel <= tol, (version, rel)
    # the quantized engine's param trees really are quantized
    vp = build_version_params(cfg, params, ("w8",))["w8"]
    assert isinstance(vp["stacks"]["main"]["blk"]["attn"]["wq"], QTensor)


def test_weight_ship_amortization_raises_latency():
    from repro.core.env import env_reset
    from repro.core.env import action_costs
    arch = "qwen2-0.5b"
    cfg0, tables = make_tpu_env([arch], weight_ship_slots=0.0)
    cfg1, _ = make_tpu_env([arch], weight_ship_slots=8.0)
    state = env_reset(cfg0, tables, jax.random.key(0))
    a = jnp.asarray([[2, 0]], jnp.int32)          # w4, earliest cut
    t0 = action_costs(cfg0, tables, state, a)[3]
    t1 = action_costs(cfg1, tables, state, a)[3]
    assert float(t1[0]) > float(t0[0])
