"""`chip_smoke.py`'s phases on the CPU at toy size (the kernels through
the Pallas interpreter), its refusal to run without a TPU, the FedBuff
counters it pins, and the compile-cache placement its entry points use."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.fl.api import AdapterConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_host_without_tpu(cs, capsys):
    assert cs.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_phase_kernels_toy(cs):
    """A flat leaf of three agg blocks, the last one partial."""
    from repro.kernels.agg.kernel import block_for
    n = 2 * block_for(3, 10**6) + 37
    out = cs.phase_kernels(Ms=(3,), flat_n=n, flat_Ms=(3,), batch=2,
                           interpret=True)
    assert out["agg_tree"][3] <= cs.AGG_TOL
    assert out["agg_flat"]["n"] == n and out["agg_flat"][3] <= cs.AGG_TOL
    assert out["rmsnorm"]["err"] <= cs.RMSNORM_TOL < out["rmsnorm"]["bf16_err"]
    assert max(out["flash"]["err"], out["flash"]["err_highest"]) \
        <= cs.FLASH_TOL < out["flash"]["bf16_err"]


def test_fedbuff_counters_at_full_size_match_pinned(cs):
    """The counters the chip must reproduce: flock191, 36,000 samples,
    one day, M = 96. They are integer protocol state, so this CPU run
    pins them."""
    out = cs.phase_federation(cs.fedbuff_experiment())
    assert out["counters"] == cs.FEDBUFF_CPU_COUNTERS
    assert not out["client_update_tpu_custom_call"]   # oracles off-TPU


def test_phase_federation_fedspace_toy(cs):
    """FedSpace's wiring at toy size; the MLP payload stands in for the
    transformer, whose CPU compile would dominate the suite."""
    exp = cs.fedspace_experiment(
        preset="starlink40", days=0.125, num_train=800, num_val=200,
        num_candidates=64, local_steps=2,
        setup={"pretrain_rounds": 2, "utility_samples": 16,
               "clients_per_round": 4, "clients_per_sample": 4})
    assert exp.adapter.kind == "transformer"
    exp = dataclasses.replace(exp, adapter=AdapterConfig(kind="mlp"))
    out = cs.phase_federation(exp)
    assert out["counters"]["windows_run"] == 12
    assert out["regressor"]["n"] == 16


def test_phase_replan_toy(cs):
    out = cs.phase_replan(preset="starlink40", days=0.25, I0=8,
                          num_candidates=128, steps=4)
    assert out["modes"][0] == "full" and "delta" in out["modes"]


def test_phase_mesh_toy(cs):
    """One CPU device: the mesh path with trivial collectives."""
    out = cs.phase_mesh(preset="starlink40", days=0.125, num_train=800,
                        num_val=200, M=4, I0=4, num_candidates=64)
    assert out["state_identical"] and out["schedule_identical"]


_CACHE_PROBE = """
import os, jax, jax.numpy as jnp
from repro import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(tmp_path, env_dir):
    """`JAX_COMPILATION_CACHE_DIR` wins and is written to; without it the
    cache goes to the fixed in-checkout directory (not written here)."""
    from repro import compile_cache
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        want = str(tmp_path)
    else:
        want = str(compile_cache.DEFAULT_DIR)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [want, want]
    if env_dir:
        assert any(tmp_path.iterdir()), "nothing cached in the env dir"
    assert compile_cache.DEFAULT_DIR == \
        compile_cache.Path(ROOT) / ".jax_cache"
