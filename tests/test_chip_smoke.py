"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself refuses a CPU; these drive its phase functions with
the small CNN task, 4 clients and one round, Pallas kernels in interpret
mode, so its control flow and checks are exercised on every change.
"""
import importlib.util
import math
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(num_clients=4, participation=1.0, K=2, R=2, client_batch=16,
           temperature=4.0, execution="vectorized", client_lr=0.05,
           server_lr=0.05, local_epochs=1, distill_steps=2)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def task(cs):
    return cs.make_task(0, model="cnn", num_clients=4, num_train=240,
                        num_server=64, server_batch=32)


@pytest.fixture(autouse=True)
def force_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")


def test_refuses_a_cpu(cs):
    with pytest.raises(SystemExit, match="no TPU"):
        cs.require_tpu(1)


def test_check_fails_on_nan_and_excess(cs):
    cs.check("ok", 1e-6, 1e-4)
    for bad in (float("nan"), 1e-3):
        with pytest.raises(AssertionError):
            cs.check("bad", bad, 1e-4)
    cs.check_detects("seen", 1e-3, 1e-4)
    for unseen in (float("nan"), 1e-5):
        with pytest.raises(AssertionError, match="planted fault"):
            cs.check_detects("unseen", unseen, 1e-4)


@pytest.mark.parametrize("kd_kernel", ["dense", "flash"])
def test_rounds_phase(cs, task, kd_kernel, capsys):
    out = cs.rounds_phase(task, kd_kernel, 0, rounds=1, **CFG)
    assert len(out["round_s"]) == 1 and out["compiles"] > 0
    assert all(math.isfinite(k) for k in out["kd_loss"])
    assert f"rounds[{kd_kernel}]:" in capsys.readouterr().out


def test_rounds_phase_rejects_a_diverged_model(cs, task):
    with pytest.raises(AssertionError, match="not finite|KD loss"):
        cs.run_rounds(task, 1, 0, **{**CFG, "client_lr": 1e30})


def test_kernels_phase(cs, task):
    errs = cs.kernels_phase(task, 0, compiled=False, K=2, R=2)
    assert set(errs) == {"weight_avg.group", "kd.ensemble_softmax",
                         "kd.kd_loss_fwd", "kd.kd_loss_bwd", "flash.fwd",
                         "flash.fwd_lse", "flash.bwd"}
    assert max(errs.values()) <= cs.KERNEL_RTOL


def test_oracle_phase(cs, task):
    dense = cs.rounds_phase(task, "dense", 0, rounds=1, **CFG)
    assert cs.oracle_phase(task, 0, dense, **CFG) <= cs.ENGINE_RTOL


def test_four_chip_phase_on_the_devices_present(cs, task):
    n = len(jax.devices())
    out = cs.four_chip_phase(task, 0, n_dev=n, fedsdd_rounds=1, **CFG)
    for preset in ("fedsdd", "feddf"):
        assert out[preset]["output_devices"] == n
        assert out[preset]["update_rel_err"] <= cs.SHARDED_RTOL
        assert out[preset]["planted_fault_update_rel_err"] > cs.SHARDED_RTOL
