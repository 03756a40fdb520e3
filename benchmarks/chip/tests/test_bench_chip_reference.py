"""The plain reference pinned to the program on the CPU, at the
program's reduced sizes: the program's jnp path (kernels off) on the
benchmark's weights against ``reference/qwen.py``, each version through
a split at layer 1, every position.

Tolerance: relative L2 of 1e-5 on the logits. Both sides run float32 at
"highest" precision on the CPU and quantize under jit, so they differ
only in the order of float32 sums (~1e-6 seen); a wrong head order,
bias, norm, rotation or quantization step moves the logits by 1e-2 or
more. (Quantized eagerly on one side and under jit on the other, a few
int4 codes land on the other side of a rounding tie: ~4e-4.)
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
import model_glue
from reference import qwen as R

TOL = 1e-5


def sizes_of(arch: str) -> dict:
    """A configuration file's contents: the benchmark's own file where a
    cell uses the configuration; else (qwen3-0.6b, whose head_dim 128, 8
    kv heads and qk_norm the reference also follows) the program's own
    sizes in the same keys."""
    path = harness.ROOT / "configs" / f"{arch}.json"
    if path.exists():
        return harness.read_json(path)
    from repro.configs import get_config
    cfg = get_config(arch)
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "intermediate_size": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.n_layers,
            "rope_theta": cfg.rope_theta, "qkv_bias": cfg.qkv_bias,
            "qk_norm": cfg.qk_norm, "tie_word_embeddings": True,
            "program": {"arch": arch, "param_dtype": cfg.param_dtype,
                        "compute_dtype": cfg.compute_dtype}}


@pytest.mark.parametrize("config", ["qwen2-0.5b", "qwen3-0.6b"])
@pytest.mark.parametrize("version,ref_version", [
    ("bf16", "f32"), ("w8", "w8"), ("w4", "w4")])
def test_reference_matches_program(config, version, ref_version):
    from repro.kernels import ops as kops
    from repro.quant import build_version_params
    from repro.serving import SplitServingEngine

    sizes = sizes_of(config)
    cfg, dm = model_glue.program_config(sizes, reduced=True)
    config = model_glue.reduced_sizes(cfg, sizes)
    w, params = model_glue.make_params(cfg, dm, 2 ** 31 + 12345)
    S = 24
    toks = np.random.default_rng(0).integers(0, dm["V"], S).astype(np.int32)
    with kops.jnp_reference(), jax.default_matmul_precision("highest"):
        eng = SplitServingEngine(cfg, params, versions=(version,))
        # the version's tree built in one jitted call, as the split
        # driver builds it
        eng._vparams.update(jax.jit(lambda p: build_version_params(
            cfg, p, (version,)))(params))
        got, _ = eng.infer({"tokens": jnp.asarray(toks)[None]}, ("main", 1),
                           version)
    got = np.asarray(got)[0]
    ref = np.asarray(R.forward(config, R.version_weights(w, ref_version),
                               toks, rows=np.arange(S), cut=1,
                               link_int8=(version == "w8")))
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert err < TOL, err
    # and the control (one precision step down) is far from both
    ctl_version = {"f32": "f32", "w4": "w4", "w8": "w4a8"}[ref_version]
    ctl = np.asarray(R.forward(
        config, R.version_weights(w, ctl_version), toks, rows=np.arange(S),
        cut=1, link_int8=(version == "w8"),
        dtype="float32" if version == "w8" else "bfloat16"))
    assert np.linalg.norm(ctl - ref) / np.linalg.norm(ref) > 100 * TOL


@pytest.mark.parametrize("config", ["qwen2-0.5b"])
def test_configuration_file_matches_the_program(config):
    """The program runs the sizes the configuration file states."""
    sizes = harness.read_json(harness.ROOT / "configs" / f"{config}.json")
    cfg, dm = model_glue.program_config(sizes)
    assert (dm["d"], dm["L"], dm["V"]) == (cfg.d_model, cfg.n_layers,
                                          cfg.vocab_size)
    with pytest.raises(ValueError, match="differ"):
        model_glue.program_config(dict(sizes, num_hidden_layers=2))


def test_rel_err_by_hand():
    assert R.rel_err([3.0, 4.0], [3.0, 4.0]) == 0.0
    assert abs(R.rel_err([3.0, 5.0], [3.0, 4.0]) - 0.2) < 1e-12
