"""Per-Pallas-kernel validation: shape/dtype sweeps, assert_allclose
against the ref.py pure-jnp oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_plan
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels import ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("B,H,HK,Sq,Skv,D", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 128, 128, 64),
    (1, 8, 1, 256, 256, 128),
    (2, 4, 4, 200, 200, 64),      # non-multiple of block
    (1, 2, 1, 64, 320, 64),       # cross-length (non-causal)
    (1, 14, 2, 256, 256, 64),     # qwen2's grouping: G = 7 heads per kv head
    (1, 14, 2, 200, 200, 64),     # ... padded to a block
    (1, 16, 8, 384, 384, 128),    # qwen3's grouping: G = 2, D = 128
    (1, 2, 1, 2048, 2048, 64),    # four kv blocks: dead blocks on both sides
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
# window 96 is narrower than a block; 700 spans two of the 512-key blocks
@pytest.mark.parametrize("causal,window", [(True, None), (True, 96),
                                           (False, None), (True, 700)])
def test_flash_attention_sweep(B, H, HK, Sq, Skv, D, dtype, causal, window):
    if not causal and Sq != Skv:
        pass  # cross-attention-like case still valid
    if causal and Sq != Skv:
        pytest.skip("causal requires aligned positions in this sweep")
    r = np.random.default_rng(hash((B, H, Sq, Skv, D)) % 2**31)
    q = jnp.asarray(r.normal(size=(B, H, Sq, D)), dtype)
    k = jnp.asarray(r.normal(size=(B, HK, Skv, D)), dtype)
    v = jnp.asarray(r.normal(size=(B, HK, Skv, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _live_pairs(plan, Sq_pad, Skv_pad):
    """(q block, kv block) pairs holding a position that may attend,
    found by brute force over every position."""
    q = np.arange(Sq_pad)[:, None]
    k = np.arange(Skv_pad)[None, :]
    ok = np.ones((Sq_pad, Skv_pad), bool)
    if plan.causal:
        ok &= k <= q
    if plan.window is not None:
        ok &= (q - k) < plan.window
    _, _, nq, nk = plan.grid
    return ok.reshape(nq, plan.bq, nk, plan.bk).any(axis=(1, 3))


@pytest.mark.parametrize("B,H,HK,Sq,D,causal,window", [
    (1, 14, 2, 1024, 64, True, None),
    (1, 14, 2, 256, 64, True, None),
    (2, 4, 2, 200, 64, True, 96),
    (1, 2, 1, 2048, 64, True, 700),
    (1, 16, 8, 384, 128, True, None),
    (1, 14, 2, 1024, 64, False, None),
])
def test_flash_plan(B, H, HK, Sq, D, causal, window):
    plan = flash_plan(B, H, HK, Sq, Sq, D, causal, window)
    _, _, nq, nk = plan.grid
    assert plan.grid[:2] == (B, HK)
    live = _live_pairs(plan, nq * plan.bq, nk * plan.bk)
    assert plan.live_steps == B * HK * int(live.sum())
    if not causal and window is None:
        assert plan.live_steps == plan.total_steps
    # steps run in grid order, kv innermost: a dead step names the block
    # the last live step read, or, before a q block's first live step,
    # the block that step will read; either way no new DMA
    lo, hi = plan.kv_range(np.arange(nq), np)
    for i in range(nq):
        for j in range(nk):
            got = int(plan.kv_block(i, j, np))
            if live[i, j]:
                assert lo[i] <= j <= hi[i] and got == j
            elif j > hi[i]:
                assert got == hi[i]
            else:
                assert got == lo[i]


def test_flash_plan_qwen2_steps():
    # qwen2-0.5b: 14 query heads over 2 kv heads (G = 7), D = 64.
    # S = 1024: bq = 128, bk = 512, so 8 q blocks by 2 kv blocks per kv
    # head. q blocks 0-3 (queries 0-511) see only kv block 0; q blocks
    # 4-7 see both: 4 * 1 + 4 * 2 = 12 live of 16, times 2 kv heads.
    plan = flash_plan(1, 14, 2, 1024, 1024, 64, True, None)
    assert (plan.bq, plan.bk, plan.grid) == (128, 512, (1, 2, 8, 2))
    assert (plan.live_steps, plan.total_steps) == (24, 32)
    # S = 256: one 256-key block, 2 q blocks, both live, per kv head
    plan = flash_plan(1, 14, 2, 256, 256, 64, True, None)
    assert plan.total_steps <= 4 and plan.live_steps == 4


@pytest.mark.parametrize("B,S,DI,N", [
    (1, 128, 128, 8), (2, 256, 256, 16), (1, 384, 128, 4),
    (2, 200, 200, 8)])            # padded length and width
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_mamba_scan_sweep(B, S, DI, N, dtype):
    r = np.random.default_rng(1)
    u = jnp.asarray(r.normal(size=(B, S, DI)), dtype)
    dt = jnp.asarray(r.uniform(0.001, 0.1, size=(B, S, DI)), jnp.float32)
    Bm = jnp.asarray(r.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(r.normal(size=(B, S, N)), jnp.float32)
    A = -jnp.exp(jnp.asarray(r.normal(size=(DI, N)), jnp.float32))

    y, h = mamba_scan(u, dt, Bm, Cm, A, interpret=True)

    # reference: plain sequential recurrence
    def seq_ref():
        hh = np.zeros((B, DI, N), np.float32)
        ys = np.zeros((B, S, DI), np.float32)
        un, dtn = np.asarray(u, np.float32), np.asarray(dt)
        Bn, Cn, An = np.asarray(Bm), np.asarray(Cm), np.asarray(A)
        for t in range(S):
            dA = np.exp(dtn[:, t][..., None] * An[None])
            hh = dA * hh + (dtn[:, t] * un[:, t])[..., None] * Bn[:, t][:, None]
            ys[:, t] = np.einsum("bdn,bn->bd", hh, Cn[:, t])
        return ys, hh
    ys, hh = seq_ref()
    np.testing.assert_allclose(np.asarray(y), ys, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), hh, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,S,W", [(1, 128, 256), (2, 256, 512),
                                   (1, 384, 128),
                                   (2, 200, 300)])   # padded length and width
def test_rglru_scan_sweep(B, S, W):
    r = np.random.default_rng(2)
    a = jnp.asarray(r.uniform(0.7, 0.999, size=(B, S, W)), jnp.float32)
    gx = jnp.asarray(r.normal(size=(B, S, W)), jnp.float32)
    y, h = rglru_scan(a, gx, interpret=True)
    yr, hr = ref.rglru_scan_ref(a, gx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


def test_model_level_pallas_parity(monkeypatch):
    """Whole reduced models agree between jnp path and interpret kernels."""
    from repro.configs import get_config
    from repro.models import forward_logits, init

    for name in ("qwen2-0.5b", "falcon-mamba-7b", "recurrentgemma-2b"):
        cfg = get_config(name).reduced()
        if name == "recurrentgemma-2b":
            cfg = cfg.with_overrides(local_window=128)
        params = init(cfg, jax.random.key(0))
        B, S = 2, 256
        toks = (jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) * 7919)
        batch = {"tokens": toks % cfg.vocab_size}
        monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
        want = forward_logits(cfg, params, batch)
        monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
        got = forward_logits(cfg, params, batch)
        monkeypatch.delenv("REPRO_USE_PALLAS")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("B,H,HK,C,D,pos,window", [
    (2, 4, 2, 128, 64, 50, None),    # partially filled cache
    (2, 4, 2, 128, 64, 127, None),   # exactly full
    (1, 8, 1, 256, 64, 300, 128),    # wrapped ring + window
    (2, 2, 2, 200, 32, 450, 96),     # non-multiple cache len, wrapped
    (1, 4, 4, 64, 128, 10, None),    # MHA small
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, H, HK, C, D, pos, window, dtype):
    from repro.kernels.flash_decode import flash_decode
    from repro.models.attention import slot_positions
    from repro.models.attention_core import plain_attention

    r = np.random.default_rng(hash((B, H, C, pos)) % 2**31)
    q = jnp.asarray(r.normal(size=(B, H, D)), dtype)
    k = jnp.asarray(r.normal(size=(B, HK, C, D)), dtype)
    v = jnp.asarray(r.normal(size=(B, HK, C, D)), dtype)
    out = flash_decode(q, k, v, jnp.int32(pos), window=window,
                       interpret=True)
    kv_pos = slot_positions(jnp.int32(pos), C)
    want = plain_attention(
        q[:, None], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        q_positions=jnp.asarray([pos], jnp.int32), kv_positions=kv_pos,
        causal=True, window=window)[:, 0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_model_decode_kernel_parity(monkeypatch):
    from repro.configs import get_config
    from repro.models import decode_step, init, prefill

    cfg = get_config("qwen2-0.5b").reduced()
    params = init(cfg, jax.random.key(0))
    toks = (jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) * 31) \
        % cfg.vocab_size
    _, cache = prefill(cfg, params, {"tokens": toks})
    tok = jnp.asarray([3, 5], jnp.int32)
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    want, _ = decode_step(cfg, params, cache, tok, jnp.int32(16))
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    got, _ = decode_step(cfg, params, cache, tok, jnp.int32(16))
    monkeypatch.delenv("REPRO_USE_PALLAS")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)
