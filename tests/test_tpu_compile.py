"""Compile the main path's Pallas kernels, and the split and decode programs
that call them, for a described TPU v5e (no chip attached) at real widths.

A compile that passes is not a chip run: it shows that the TPU compiler
accepts each kernel's tiling and memory use, and that the models' TPU
programs contain the kernels (``tpu_custom_call``) rather than the jnp
reference.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.ops import compiled_kernels
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.rglru_scan import rglru_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's programs cannot be read back from the persistent
    # compilation cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernels(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled_kernels(compiled)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32


@pytest.mark.parametrize("name,fn,shapes", [
    # qwen2-0.5b MLP up-projection, 4 x 256 tokens
    ("quant_matmul", quant_matmul,
     [((1024, 896), I8), ((896, 4864), I8), ((1024,), F32), ((4864,), F32)]),
    # qwen2-0.5b heads (14 query, 2 kv) at a 2k prefill
    ("flash_attention", flash_attention,
     [((2, 14, 2048, 64), F32), ((2, 2, 2048, 64), F32),
      ((2, 2, 2048, 64), F32)]),
    # batch 4 decoding against a 4k ring cache
    ("flash_decode", flash_decode,
     [((4, 14, 64), F32), ((4, 2, 4096, 64), F32), ((4, 2, 4096, 64), F32),
      ((), I32)]),
    # falcon-mamba-7b: d_inner 8192, state 16; 300 tokens pads the length
    ("mamba_scan", mamba_scan,
     [((1, 300, 8192), F32), ((1, 300, 8192), F32), ((1, 300, 16), F32),
      ((1, 300, 16), F32), ((8192, 16), F32)]),
    # recurrentgemma-2b: lru width 2560, batch 2
    ("rglru_scan", rglru_scan,
     [((2, 300, 2560), F32), ((2, 300, 2560), F32)]),
    # qwen2-0.5b heads at the benchmark cell's longest prompt (B = 1, 1024)
    ("flash_attention", flash_attention,
     [((1, 14, 1024, 64), F32), ((1, 2, 1024, 64), F32),
      ((1, 2, 1024, 64), F32)]),
])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    compiled = _compile(functools.partial(fn, interpret=False), one_chip,
                        *shapes)
    assert _kernels(compiled) == {name}


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Trace the models as on a TPU backend (the tests run on the CPU
    backend, so steer the dispatch here)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_pallas", lambda: "tpu")


def _qwen2_two_layers():
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("qwen2-0.5b").with_overrides(n_layers=2)
    return cfg, jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))


def _placed(tree, one_chip):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), tree)


def test_decode_step_runs_flash_decode(one_chip, tpu_dispatch):
    from repro.models import model as M
    cfg, params = _qwen2_two_layers()
    cache = M.init_cache(cfg, 4, 1024)
    fn = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    compiled = fn.lower(
        _placed(params, one_chip), _placed(cache, one_chip),
        jax.ShapeDtypeStruct((4,), I32, sharding=one_chip),
        jax.ShapeDtypeStruct((), I32, sharding=one_chip)).compile()
    assert _kernels(compiled) == {"flash_decode"}


@pytest.mark.parametrize("version,want", [
    ("bf16", {"flash_attention"}),
    ("w8", {"flash_attention", "quant_matmul"}),
])
def test_split_head_runs_kernels(one_chip, tpu_dispatch, version, want):
    from repro.core import partition
    from repro.quant import build_version_params
    cfg, params = _qwen2_two_layers()
    vp = jax.eval_shape(
        lambda p: build_version_params(cfg, p, (version,))[version], params)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 256), I32,
                                            sharding=one_chip)}
    fn = jax.jit(lambda p, b: partition.run_head(cfg, p, b, ("main", 1)))
    compiled = fn.lower(_placed(vp, one_chip), batch).compile()
    assert _kernels(compiled) == want
