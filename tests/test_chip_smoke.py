"""chip_smoke.py on the CPU: the device gate, and the script's own
control flow at toy width.  None of this says anything about the chip —
that is what running the script there is for."""

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def restore_telemetry():
    """InferenceServer raises the histogram layer on construction;
    later suites (test_bench_gate's disabled-path bounds) expect their
    default-off world back."""
    from mxnet_tpu import histogram, runtime_stats

    was_on = histogram.is_enabled()
    yield
    from mxnet_tpu import serving

    serving.reset()
    runtime_stats.reset()
    if not was_on:
        histogram.disable()


def test_device_gate_refuses_the_cpu_platform(smoke, capsys):
    """No accelerator: non-zero exit with a one-line reason, after the
    device line and before any phase; no result is printed."""
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert isinstance(e.value.code, str) and e.value.code  # non-zero
    assert "needs 'tpu'" in e.value.code and "\n" not in e.value.code
    out = capsys.readouterr().out
    assert out.startswith("platform: cpu")
    assert "PASS" not in out and '"ok"' not in out


def test_dry_run_drives_train_compile_serve(smoke, capsys,
                                            restore_telemetry):
    """The documented dry-run switch bypasses the gate: the train ->
    compile -> serve phases run at toy width through the same code the
    chip run uses, and the run says it proved nothing and prints no
    result.  (The kernels phase is the interpret-mode path that
    test_attention / test_pallas_* already cover.)"""
    cfg = smoke.TINY
    assert smoke.device_gate(dry_run=True)["platform"] == "cpu"
    net = smoke.build_net(cfg)
    smoke.phase_train_benchmark(cfg, "cpu", net)
    smoke.phase_train_users(cfg, "cpu", net)
    smoke.phase_serve(cfg, "cpu", net)
    out = capsys.readouterr().out
    for phase in ("train/benchmark", "train/users", "serve"):
        assert "PASS %s" % phase in out
    assert '"ok"' not in out


def test_compile_cache_directory_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the code
    sets no other.  Unset: the fixed <checkout>/.jax_cache."""
    import jax

    from mxnet_tpu import util

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert util.enable_compile_cache() == "/x"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert util.enable_compile_cache() == want
    assert util.enable_compile_cache() == want  # same path every run
    assert calls == [("jax_compilation_cache_dir", want)] * 2


def test_phase_failure_is_fatal(smoke):
    with pytest.raises(smoke.SmokeFailure, match="did not fall"):
        smoke.check_losses("x", [1.0, 2.0, 3.0])
    with pytest.raises(smoke.SmokeFailure, match="non-finite"):
        smoke.check_losses("x", [1.0, float("nan"), 0.5])
