"""CPU rehearsal of chip_smoke.py: every phase at the reduced config with
the Pallas kernels in interpreter mode, and the entry's refusal to run
without a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")


def test_controller_split_and_server_phases(smoke, interpret):
    from repro.models import init

    cut, fixed_cut = smoke.phase_controller(reduced=True, episodes=2,
                                            batch_envs=2, slots=2)
    assert cut[0] == fixed_cut[0] == "main"
    params = init(smoke.model_config(True), jax.random.key(0))
    errs = smoke.phase_split_serving(params, cut, fixed_cut, reduced=True,
                                     batch=2, seq=24, check_kernels=False)
    assert {v for v, _ in errs} == set(smoke.VERSIONS)
    smoke.phase_batching_server(params, reduced=True, n_requests=4,
                                prompt_lens=(8, 12), max_new_tokens=3,
                                max_batch=2, cache_len=32,
                                check_kernels=False)


def test_fleet_phases(smoke):
    res = smoke.fleet_scan(devices=2000, epochs=3, policy="greedy_oracle")
    assert res.epochs == 3 and res.mesh_devices == 1
    smoke.phase_fleet_sharded(len(jax.devices()), devices=2000, epochs=3)


def test_entry_refuses_cpu_before_any_phase(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "phase" not in capsys.readouterr().out
