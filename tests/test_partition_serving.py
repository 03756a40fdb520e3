"""Split execution (head/tail) correctness + serving engine behaviour +
kernel-path and hillclimb-variant numerical parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.partition import cut_points, split_forward
from repro.models import decode_step, forward_logits, init, prefill
from repro.serving import ServeConfig, ServingEngine, SplitServingEngine
from tests.conftest import make_batch

ARCHS_SPLIT = ["qwen2-0.5b", "falcon-mamba-7b", "recurrentgemma-2b",
               "deepseek-v2-lite-16b", "llama-3.2-vision-90b",
               "mellum2-12b-a2.5b"]


@pytest.mark.parametrize("arch", ARCHS_SPLIT)
def test_split_forward_equals_full(arch):
    cfg = get_config(arch).reduced()
    params = init(cfg, jax.random.key(0))
    batch = make_batch(cfg)
    del batch["targets"]
    full = forward_logits(cfg, params, batch)
    for cut in cut_points(cfg):
        got = split_forward(cfg, params, batch, cut)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)


def test_serving_engine_generates():
    cfg = get_config("qwen3-0.6b").reduced()
    params = init(cfg, jax.random.key(0))
    eng = ServingEngine(cfg, params, ServeConfig(max_new_tokens=8))
    batch = make_batch(cfg, B=3, S=12)
    del batch["targets"]
    toks = eng.generate(batch)
    assert toks.shape == (3, 8)
    assert int(toks.min()) >= 0 and int(toks.max()) < cfg.vocab_size


def test_serving_greedy_is_deterministic():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    eng = ServingEngine(cfg, params, ServeConfig(max_new_tokens=6))
    batch = make_batch(cfg, B=2, S=10)
    del batch["targets"]
    t1 = np.asarray(eng.generate(batch))
    t2 = np.asarray(eng.generate(batch))
    np.testing.assert_array_equal(t1, t2)


def test_split_serving_activation_bytes():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    eng = SplitServingEngine(cfg, params)
    batch = make_batch(cfg)
    del batch["targets"]
    logits, nbytes = eng.infer(batch, cut_points(cfg)[0])
    B, S = batch["tokens"].shape
    assert nbytes == B * S * cfg.d_model * 4   # f32 activation
    assert logits.shape == (B, S, cfg.vocab_size)


def test_mla_absorb_decode_parity():
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    params = init(cfg, jax.random.key(0))
    batch = make_batch(cfg)
    del batch["targets"]
    _, cache = prefill(cfg, params, batch)
    tok = jnp.asarray([1, 2], jnp.int32)
    l_base, _ = decode_step(cfg, params, cache, tok, jnp.int32(16))
    l_abs, _ = decode_step(cfg.with_overrides(mla_absorb=True), params,
                           cache, tok, jnp.int32(16))
    np.testing.assert_allclose(np.asarray(l_abs), np.asarray(l_base),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch,held", [("mixtral-8x22b", 0),
                                       ("mellum2-12b-a2.5b", 2)])
def test_moe_kernel_path_parity(monkeypatch, arch, held):
    """The dropless MoE on the grouped_matmul kernel (interpret mode) and
    on its jnp oracle give the same logits, all experts held or some."""
    from repro.kernels import ops
    cfg = get_config(arch).reduced().with_overrides(experts_held=held)
    params = init(cfg, jax.random.key(1))
    batch = make_batch(cfg)
    del batch["targets"]
    with ops.jnp_reference():
        f1 = jax.jit(lambda p, b: forward_logits(cfg, p, b))(params, batch)
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    f2 = jax.jit(lambda p, b: forward_logits(cfg, p, b))(params, batch)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               rtol=2e-4, atol=2e-4)


def test_attention_chunk_sizes_do_not_change_results():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    B, S = 1, 4096    # force the chunked path (> threshold)
    toks = (jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) * 13) % cfg.vocab_size
    f1 = forward_logits(cfg, params, {"tokens": toks})
    f2 = forward_logits(cfg.with_overrides(attn_q_chunk=2048,
                                           attn_kv_chunk=4096),
                        params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               rtol=2e-4, atol=2e-4)
