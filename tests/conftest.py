"""Test config: force the CPU platform with 8 virtual devices.

Mirrors the reference test strategy (SURVEY.md §4): distributed semantics
are tested with local multi-device processes, like `launch.py -n 4`, but
here via XLA's virtual host devices instead of spawning workers.

Must run before any jax import (pytest imports conftest first).
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
# force exactly 8 devices even when the var is already set (e.g. leaked
# from a dryrun re-exec with a different count): the suite's mesh-shape
# assertions are written for 8
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

if os.environ.get("MXTPU_COV"):
    # dependency-free line coverage (tools/coverage_lite.py): hits are
    # dumped to $MXTPU_COV at exit; report with
    # `python tools/coverage_lite.py report <json>`
    import sys as _sys

    _repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _sys.path.insert(0, os.path.join(_repo, "tools"))
    import coverage_lite

    coverage_lite.start(os.path.join(_repo, "mxnet_tpu"),
                        os.environ["MXTPU_COV"])


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests (multi-device subprocess dryruns, "
        "tutorial/example sweeps); deselect with -m 'not slow' for a "
        "<20-minute tier")


@pytest.fixture()
def ps_server(monkeypatch):
    """In-process PSServer on a random port with the DMLC_*/MXTPU_* env
    a worker-side client reads — shared by test_ps_errors.py and
    test_kvstore_facade.py so server bring-up/teardown lives once."""
    import threading

    from mxnet_tpu.kvstore.ps import PSServer

    srv = PSServer(port=0, num_workers=1)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("MXTPU_PS_PORTS", str(srv.port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    yield srv
    srv._stop.set()


@pytest.fixture(autouse=True)
def _seed():
    """Reproducible-yet-varied tests (reference: tests/python/unittest/
    common.py with_seed decorator).  MXNET_TEST_SEED overrides the
    default, which is how tools/flakiness_checker.py varies trials."""
    import mxnet_tpu as mx

    seed = int(os.environ.get("MXNET_TEST_SEED", 42))
    mx.random.seed(seed)
    np.random.seed(seed)
    yield


def hermetic_subprocess_env(repo=None):
    """Environment for spawning C/embedded-interpreter consumers:
    MXTPU_PYTHONPATH carries everything the embedded interpreter needs,
    and jax stays on CPU."""
    import sys as _sys

    env = dict(os.environ)
    if repo is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["MXTPU_PYTHONPATH"] = ":".join([repo] +
                                       [p for p in _sys.path if p])
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


# Measured-slow tests (r5 durations run: everything >= ~30 s on this
# 1-core container).  Centralized so the tier stays maintainable; the
# multi-process dist/dryrun tests carry @pytest.mark.slow in-place.
# `-m "not slow"` = the fast tier (< ~20 min); full suite = both.
_SLOW_TESTS = {
    "test_dryrun_multichip_16_devices",
    "test_deepspeech_ctc_cer",
    "test_word_lm_ppl_decreases",
    "test_ctc_ocr_converges",
    "test_rcnn_proposal_roialign_pipeline",
    "test_ner_tagger_f1",
    "test_over_int32_elements_smoke",
    "test_matrix_fact_example",
    "test_lstnet_forecast_beats_mean",
    "test_ssd_detects",
    "test_rnn_train_overfit",
    "test_captcha_whole_string_accuracy",
    "test_tutorial_runs[unsupervised_learning/gan.py]",
    "test_bayesian_hmc_toy",
    "test_dec_clustering_refines_kmeans",
    "test_inception_bn_forward_and_param_count",
    "test_inception_bn_nhwc_matches_nchw",
    "test_vaegan_reconstruction_improves",
    "test_reinforce_gridworld_learns",
    "test_bayesian_distilled_sgld",
    "test_conv_rnn_cells_shapes",
    "test_bucketed_lstm_lm_converges",
    "test_sparse_matrix_factorization",
    "test_numeric_gradient_families[<lambda>-shapes2]",
    "test_distributed_training_8dev_mesh",
    "test_train_imagenet_synthetic_smoke",
    "test_ndsb2_crps_volume_regression",
    "test_ndsb1_rec_pipeline_trains",
    "test_models_forward[mobilenetv2_0.25]",
    "test_models_forward[squeezenet1.1]",
    "test_resnet_nhwc_matches_nchw",
    "test_capsnet_routing_converges",
    "test_bayesian_sgld_toy_posterior",
    "test_fcn_segmentation_learns",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
