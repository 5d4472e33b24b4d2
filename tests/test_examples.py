"""Smoke tests for example/ scripts and tools/ (reference:
tests/python/train + tests/nightly launch.py flows, scaled down)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXDIR = os.path.join(REPO, "example")


def _run_example(relpath, argv):
    """Import and run an example's main() in-process (fast: shares jax)."""
    path = os.path.join(EXDIR, relpath)
    sys.path.insert(0, os.path.dirname(path))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3] + "_mod", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        return mod.main(argv)
    finally:
        sys.path.pop(0)


def test_train_mnist_mlp_converges():
    mod = _run_example("image-classification/train_mnist.py",
                       ["--num-epochs", "2", "--batch-size", "64",
                        "--lr", "0.1", "--kv-store", "local"])
    # synthetic MNIST is separable: 2 epochs must beat 0.9
    import mxnet_tpu as mx
    from mxnet_tpu.io.io import MNISTIter

    val = MNISTIter(image="val", batch_size=64, shuffle=False)
    acc = mx.metric.Accuracy()
    mod.score(val, acc)
    assert acc.get()[1] > 0.9, acc.get()


def test_train_imagenet_synthetic_smoke():
    mod = _run_example(
        "image-classification/train_imagenet.py",
        ["--num-epochs", "1", "--batch-size", "16", "--num-examples", "64",
         "--network", "resnet18_v1", "--image-shape", "3,32,32",
         "--kv-store", "local", "--num-classes", "4", "--lr", "0.05"])
    assert mod is not None


def test_benchmark_score_tiny():
    res = _run_example(
        "image-classification/benchmark_score.py",
        ["--networks", "alexnet", "--batch-sizes", "2",
         "--image-shape", "3,64,64", "--num-batches", "2"])
    assert res and res[0][2] > 0


def test_word_lm_ppl_decreases():
    ppls = _run_example("rnn/word_lm/train.py",
                        ["--epochs", "3", "--batch_size", "8",
                         "--bptt", "16", "--nhid", "64", "--emsize", "32",
                         "--lr", "0.01", "--optimizer", "adam",
                         "--dropout", "0.0", "--num-tokens", "4000",
                         "--vocab", "30", "--clip", "5.0"])
    assert ppls[-1] < ppls[0] * 0.7, ppls  # learning happened
    assert ppls[-1] < 5, ppls  # near the 5%-noise floor (vocab 30)


def test_ssd_detects():
    """SSD pipeline end-to-end: MultiBoxPrior/Target (hard-negative
    mining) -> train -> MultiBoxDetection NMS decode (BASELINE config 4)."""
    acc = _run_example("ssd/train.py",
                       ["--epochs", "6", "--num-examples", "192"])
    assert acc >= 0.6, acc


def test_distributed_training_8dev_mesh():
    """Sharded SPMD train step over the 8-device CPU mesh: loss must drop
    (GSPMD grad all-reduce path, BASELINE config 5)."""
    ips = _run_example(
        "distributed_training/train_resnet.py",
        ["--network", "resnet18_v1", "--batch-size", "32",
         "--image-shape", "3,32,32", "--num-classes", "10",
         "--steps", "8", "--dtype", "float32"])
    # the example itself asserts the loss dropped (grads flowed through
    # the sharded step); a returned rate means it reached the end
    assert ips is not None


def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import parse_log

    lines = [
        "Node[0] INFO Epoch[0] Batch [20] Speed: 1000.0 samples/sec accuracy=0.5",
        "Node[0] INFO Epoch[0] Train-accuracy=0.6",
        "Node[0] INFO Epoch[0] Time cost=5.0",
        "Node[0] INFO Epoch[0] Validation-accuracy=0.55",
    ]
    table = parse_log.parse(lines)
    assert table == [(0, 0.6, 0.55, 1000.0, 5.0)]
    sys.path.pop(0)


def test_im2rec_roundtrip(tmp_path):
    pytest.importorskip("PIL")
    from PIL import Image

    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = (np.random.RandomState(i).rand(8, 8, 3) * 255
                   ).astype(np.uint8)
            Image.fromarray(arr).save(root / cls / ("%d.png" % i))
    prefix = str(tmp_path / "data")
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import im2rec

    im2rec.main([prefix, str(root), "--list", "--shuffle", "0"])
    im2rec.main([prefix, str(root)])
    sys.path.pop(0)

    import mxnet_tpu as mx

    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 8, 8), batch_size=2)
    labels = []
    for b in it:
        labels.extend(b.label[0].asnumpy().astype(int).tolist()[:2 - b.pad])
    assert sorted(labels) == [0, 0, 0, 1, 1, 1]


@pytest.mark.slow
def test_launch_dist_sync_kvstore():
    """launch.py -n 2 runs the dist_sync exact-value checks in separate
    processes over jax.distributed (reference: tests/nightly/test_all.sh)."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable,
         os.path.join(REPO, "tests", "dist", "dist_sync_kvstore.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("dist_sync_kvstore OK") == 2, r.stdout + r.stderr


def test_autoencoder_example():
    """example/autoencoder beats a loose reconstruction bar."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "train_ae", os.path.join(REPO, "example", "autoencoder",
                                 "train_ae.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    final, floor = mod.main(["--epochs", "20"])
    assert final < 0.05, (final, floor)


def test_matrix_fact_example():
    """example/recommenders MF: rating MSE drops well under the initial
    ~1.0 (sparse-grad embeddings train)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "matrix_fact", os.path.join(REPO, "example", "recommenders",
                                    "matrix_fact.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mse = mod.main(["--epochs", "8"])
    assert mse < 0.5, mse


def test_gan_example():
    """example/gan: the generator reaches multiple mixture modes."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "train_gan", os.path.join(REPO, "example", "gan", "train_gan.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    coverage = mod.main(["--epochs", "10"])
    assert coverage >= 2, coverage


def test_launch_dist_async_kvstore():
    """launch.py -n 2 -s 2 spawns parameter servers + workers; async PS
    semantics checked exactly (reference: tests/nightly/
    dist_async_kvstore.py)."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "-s", "2", sys.executable,
         os.path.join(REPO, "tests", "dist", "dist_async_kvstore.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("dist_async_kvstore OK") == 2, r.stdout + r.stderr


def test_bucketed_lstm_lm_converges():
    """The canonical symbolic RNN path: BucketSentenceIter +
    BucketingModule + stacked LSTMCell.unroll (reference:
    example/rnn/bucketing/lstm_bucketing.py; BASELINE config 3)."""
    ppl = _run_example("rnn/bucketing/lstm_bucketing.py",
                       ["--num-epochs", "3"])
    # synthetic ring corpus: uniform ppl is 16; the LSTM must learn the
    # transition structure
    assert ppl < 5.0, "val perplexity %.3f did not converge" % ppl


def test_custom_numpy_softmax_converges():
    """Custom-op bridge in anger (reference: example/numpy-ops/
    custom_softmax.py): a host-numpy softmax loss op trains an MNIST
    MLP through Module.fit."""
    acc = _run_example("numpy-ops/custom_softmax.py", ["--num-epochs", "2"])
    assert acc > 0.9, acc


def test_profiler_example_writes_trace():
    """Profiler client end-to-end (reference: example/profiler/):
    chrome trace with the user scopes present."""
    import json

    path = _run_example("profiler/profile_training.py", [])
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    assert len(events) >= 2


def test_runtime_telemetry_example_anatomy():
    """PR-2 telemetry walkthrough (example/profiler/runtime_telemetry.py):
    the trace shows the step anatomy and counters agree with the trace
    (the script asserts misses == trace-miss spans itself)."""
    import json

    from mxnet_tpu import (device_memory, histogram, metrics_timeline,
                           profiler, runtime_stats, stepstats)

    try:
        path = _run_example("profiler/runtime_telemetry.py", [])
    finally:
        profiler.set_state("stop")
        profiler._state["events"] = []
        # the script starts the buffer tracker and turns the timeline on,
        # which turns on the step attribution and the histograms: a later
        # file of this worker (test_bench_gate.py) must find them off
        device_memory.stop()
        metrics_timeline.disable()
        stepstats.disable()
        histogram.disable()
        runtime_stats.reset()
    trace = json.load(open(path))["traceEvents"]
    names = {e["name"] for e in trace}
    assert {"io:next_batch", "trainer:step", "autograd:backward"} <= names
    assert any(e["name"].startswith("dispatch:") for e in trace)


def test_reinforce_gridworld_learns():
    """RL training loop (reference: example/reinforcement-learning/):
    REINFORCE reaches the optimal return on the toy gridworld."""
    ret = _run_example("reinforcement-learning/reinforce_gridworld.py",
                      ["--episodes", "250"])
    assert ret > 1.0, ret  # optimal 3.0; random policy is deeply negative


def test_fgsm_adversary_example():
    """Gradient-w.r.t.-input API family (reference: example/adversary):
    the FGSM attack must dent a trained classifier's accuracy while
    staying inside the L-inf ball."""
    clean, adv = _run_example("adversary/fgsm_mnist.py", ["--epochs", "2"])
    assert clean > 0.9, clean
    assert adv < clean - 0.2, (clean, adv)


def test_multitask_example_converges():
    """Group-symbol multi-head training (reference: example/multi-task):
    joint digit+parity heads both learn through one Module."""
    acc = _run_example("multi-task/multitask_mnist.py", ["--epochs", "2"])
    assert acc > 0.9, acc


def test_text_cnn_converges():
    """Multi-branch conv-over-time Symbol (reference:
    example/cnn_text_classification)."""
    acc = _run_example("cnn_text_classification/text_cnn.py",
                      ["--num-epochs", "4"])
    assert acc > 0.9, acc


def test_binary_rbm_learns():
    """Autograd-free CD-1 training paradigm (reference:
    example/restricted-boltzmann-machine)."""
    first, last = _run_example("restricted-boltzmann-machine/binary_rbm.py",
                              ["--epochs", "2"])
    assert last < first * 0.2, (first, last)


def test_svm_mnist_converges():
    """Margin-loss head family (reference: example/svm_mnist): SVMOutput
    trains to high accuracy with argmax-of-scores predictions."""
    acc = _run_example("svm_mnist/svm_mnist.py", ["--num-epochs", "2"])
    assert acc > 0.9, acc


def test_fcn_segmentation_learns():
    """Deconvolution + Crop skip-connection family (reference:
    example/fcn-xs): per-pixel softmax must clearly beat the ~0.86
    all-background baseline (i.e. actually segment the blobs)."""
    acc = _run_example("fcn-xs/fcn_segmentation.py", ["--num-epochs", "10"])
    assert acc > 0.95, acc


def test_sparse_linear_classification():
    """CSR LibSVM batches + row_sparse gradients + lazy SGD (reference:
    example/sparse/linear_classification)."""
    acc = _run_example("sparse/linear_classification.py",
                       ["--epochs", "20", "--num-examples", "384"])
    assert acc >= 0.85, acc


def test_sparse_matrix_factorization():
    """row_sparse embedding gradients through Trainer's lazy adam
    (reference: example/sparse/matrix_factorization)."""
    rmses = _run_example("sparse/matrix_factorization.py",
                         ["--epochs", "8"])
    assert rmses[-1] < 0.35 * rmses[0], rmses
    assert rmses[-1] < 0.6, rmses


def test_ctc_ocr_converges():
    """CTC alignment learning end-to-end, greedy-decoded (reference:
    example/ctc; the CTC forward+grad are torch-checked in
    tests/test_loss.py)."""
    acc = _run_example("ctc/lstm_ocr.py",
                       ["--model", "dense", "--target-acc", "0.9"])
    assert acc >= 0.75, acc


def test_nce_wordvec_learns_clusters():
    """NCE objective pulls intra-cluster embeddings together
    (reference: example/nce-loss/wordvec.py)."""
    intra, inter = _run_example("nce-loss/wordvec.py", ["--epochs", "6"])
    assert intra - inter >= 0.25, (intra, inter)


def test_neural_style_optimizes_image():
    """Autograd to the INPUT image through a fixed extractor + Gram
    losses (reference: example/neural-style/nstyle.py)."""
    history = _run_example("neural-style/neural_style.py",
                           ["--iters", "80"])
    assert history[-1] < 0.05 * history[0], (history[0], history[-1])


def test_quantization_calibrated_int8():
    """Full calibration flow: stats -> thresholds -> int8 graph ->
    accuracy parity (reference: example/quantization)."""
    fp32_acc, int8_acc = _run_example(
        "quantization/quantize_cnn.py",
        ["--epochs", "4", "--calib-mode", "naive"])
    assert fp32_acc >= 0.9, fp32_acc
    assert int8_acc >= fp32_acc - 0.05, (fp32_acc, int8_acc)


def test_rcnn_proposal_roialign_pipeline():
    """Two-stage detection: RPN -> Proposal (NMS'd ROIs) -> ROIAlign ->
    region head (reference: example/rcnn Faster R-CNN)."""
    iou_rate, cls_acc = _run_example(
        "rcnn/train_rcnn.py",
        ["--num-examples", "96", "--batch-size", "96",
         "--epochs-rpn", "60", "--epochs-head", "220"])
    assert iou_rate >= 0.6, iou_rate
    assert cls_acc >= 0.8, cls_acc


def test_rec2idx_roundtrip(tmp_path):
    """Rebuilt .idx drives random access (reference: tools/rec2idx.py)."""
    from mxnet_tpu.recordio import MXIndexedRecordIO, MXRecordIO

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    import rec2idx

    importlib.reload(rec2idx)
    rec_path = str(tmp_path / "x.rec")
    w = MXRecordIO(rec_path, "w")
    payloads = [bytes([i]) * max(1, i * 3) for i in range(10)]
    for pl in payloads:
        w.write(pl)
    w.close()
    assert rec2idx.main([rec_path]) == 10
    r = MXIndexedRecordIO(str(tmp_path / "x.idx"), rec_path, "r")
    for i in (0, 3, 9, 5):
        assert r.read_idx(i) == payloads[i]
    sys.path.pop(0)


def test_module_api_walkthrough():
    acc = _run_example("module/mnist_mlp.py", ["--epochs", "2"])
    assert acc > 0.9, acc


def test_gluon_walkthrough():
    acc = _run_example("gluon/mnist.py", ["--epochs", "2"])
    assert acc > 0.9, acc


def test_model_parallel_example():
    losses = _run_example("model-parallel/train.py", ["--steps", "20"])
    assert losses[-1] < 0.5 * losses[0], losses


def test_stochastic_depth_example():
    acc = _run_example("stochastic-depth/train.py", ["--epochs", "60"])
    assert acc > 0.85, acc


def test_svrg_example_converges():
    mses = _run_example("svrg_module/train.py", ["--epochs", "10"])
    assert mses[-1] < 0.01 * mses[0], mses


def test_capsnet_routing_converges():
    """Dynamic routing-by-agreement + margin loss (reference:
    example/capsnet, Sabour et al. 2017)."""
    acc = _run_example("capsnet/train.py", ["--epochs", "16"])
    assert acc >= 0.85, acc


def test_ner_tagger_f1():
    """Masked BiLSTM sequence tagging (reference:
    example/named_entity_recognition)."""
    f1 = _run_example("named_entity_recognition/train.py",
                      ["--epochs", "10"])
    assert f1 >= 0.8, f1


def test_ndsb1_rec_pipeline_trains():
    """Full Kaggle plankton workflow: render corpus -> .lst -> im2rec
    .rec -> ImageIter aug -> Module.fit (reference:
    example/kaggle-ndsb1/{gen_img_list,train_dsb}.py)."""
    acc = _run_example("kaggle-ndsb1/train_dsb.py",
                       ["--epochs", "12", "--per-class", "100"])
    assert acc >= 0.7, acc


def test_ndsb2_crps_volume_regression():
    """Frame-differencing CDF regression with the CRPS metric
    (reference: example/kaggle-ndsb2/Train.py)."""
    score, mae = _run_example("kaggle-ndsb2/Train.py",
                              ["--epochs", "5"])
    assert score < 0.05, score
    assert mae < 20.0, mae


def test_chinese_text_cnn_highway():
    """Char-CNN with pre-trained-embedding input path + highway layer
    (reference: example/cnn_chinese_text_classification/text_cnn.py)."""
    acc = _run_example("cnn_chinese_text_classification/text_cnn.py",
                       ["--epochs", "6"])
    assert acc >= 0.75, acc


def test_deepspeech_ctc_cer():
    """Conv+BiLSTM+CTC speech model, greedy decode + CER (reference:
    example/speech_recognition arch_deepspeech.py / stt_metric.py)."""
    rate = _run_example("speech_recognition/deepspeech.py",
                        ["--epochs", "12", "--n-train", "1024"])
    assert rate < 0.25, rate


def test_captcha_whole_string_accuracy():
    """Multi-digit captcha CNN with per-digit softmax heads (reference:
    example/captcha/mxnet_captcha.R)."""
    acc = _run_example("captcha/captcha_net.py",
                       ["--epochs", "5", "--n-train", "2000"])
    assert acc >= 0.8, acc


def test_dsd_prune_and_redensify():
    """Dense-Sparse-Dense training via a pruning SGD subclass
    (reference: example/dsd/sparse_sgd.py, Han et al. 2017)."""
    stats = _run_example("dsd/mlp.py", ["--epochs-per-phase", "2"])
    assert stats["sparse_sparsity"] > 0.7, stats
    assert stats["sparse_acc"] > 0.9, stats      # prune survives
    assert stats["final_acc"] >= 0.95, stats     # D2 recovers dense


def test_dec_clustering_refines_kmeans():
    """Deep Embedded Clustering: layerwise-pretrained autoencoder,
    k-means init, KL(p||q) refinement (reference:
    example/deep-embedded-clustering/dec.py)."""
    acc_kmeans, acc_dec = _run_example("deep-embedded-clustering/dec.py",
                                       [])
    assert acc_dec >= acc_kmeans, (acc_kmeans, acc_dec)
    assert acc_dec > 0.8, acc_dec


def test_vaegan_reconstruction_improves():
    """VAE-GAN with discriminator-feature similarity loss (reference:
    example/vae-gan/vaegan_mxnet.py, Larsen et al. 2016)."""
    mse0, mse1 = _run_example("vae-gan/vaegan.py",
                              ["--epochs", "6", "--n-train", "512"])
    assert mse1 < 0.7 * mse0, (mse0, mse1)


def test_lstnet_forecast_beats_mean():
    """LSTNet CNN+GRU+skip-GRU+AR forecaster (reference:
    example/multivariate_time_series/src/lstnet.py)."""
    score = _run_example("multivariate_time_series/lstnet.py",
                         ["--num-epochs", "3", "--t-len", "1200"])
    assert score < 0.5, score


def test_bayesian_sgld_toy_posterior():
    """SGLD posterior predictive on the BDK toy regression (reference:
    example/bayesian-methods, algos.py SGLD)."""
    rmse = _run_example("bayesian-methods/bdk_demo.py",
                        ["--mode", "toy-sgld", "--iters", "800",
                         "--burn-in", "300"])
    assert rmse < 0.25, rmse


def test_bayesian_hmc_toy():
    """Leapfrog HMC with Metropolis correction (reference:
    example/bayesian-methods, algos.py step_HMC/HMC)."""
    rmse, rate = _run_example("bayesian-methods/bdk_demo.py",
                              ["--mode", "toy-hmc", "--iters", "100",
                               "--burn-in", "40"])
    assert rmse < 0.25, rmse
    assert 0.3 < rate <= 1.0, rate


def test_bayesian_distilled_sgld():
    """Bayesian Dark Knowledge distillation (reference:
    example/bayesian-methods, algos.py DistilledSGLD)."""
    rmse = _run_example("bayesian-methods/bdk_demo.py",
                        ["--mode", "toy-distilled", "--iters", "1200",
                         "--burn-in", "300"])
    assert rmse < 0.25, rmse


def test_bayesian_synthetic_sgld_scan():
    """Welling-Teh mixture posterior as ONE foreach scan (reference:
    example/bayesian-methods bdk_demo.py run_synthetic_SGLD)."""
    dist, samples = _run_example("bayesian-methods/bdk_demo.py",
                                 ["--mode", "synthetic", "--iters", "4000",
                                  "--burn-in", "500"])
    assert dist < 0.8, dist           # chain stays in high-probability region
    assert samples.std(axis=0).min() > 0.02   # and actually moves


def test_bi_lstm_sort_learns():
    """Character-level sorting with a bidirectional LSTM (reference:
    example/bi-lstm-sort/bi-lstm-sort.ipynb)."""
    acc = _run_example("bi-lstm-sort/sort_lstm.py",
                       ["--epochs", "14", "--dataset-size", "2000",
                        "--hidden", "64"])
    assert acc >= 0.7, acc


@pytest.mark.slow
def test_launch_dist_lenet_sync_training_convergence():
    """End-to-end dist TRAINING over the process boundary (reference:
    tests/nightly/dist_lenet.py): class-disjoint shards force real
    gradient exchange — a non-exchanging worker cannot pass the
    full-set accuracy bar — and the sync contract (identical params on
    every worker) is asserted cross-process."""
    from conftest import hermetic_subprocess_env

    env = hermetic_subprocess_env(REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable,
         os.path.join(REPO, "tests", "dist", "dist_lenet.py"), "sync"],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("dist_lenet sync OK") == 2, r.stdout + r.stderr


@pytest.mark.slow
def test_launch_dist_lenet_async_training_convergence():
    """Async variant through spawned PS processes (reference:
    tests/nightly/ dist_lenet-style async runs): convergence bar only —
    updates interleave, so no cross-worker param-equality contract."""
    from conftest import hermetic_subprocess_env

    env = hermetic_subprocess_env(REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "-s", "2", sys.executable,
         os.path.join(REPO, "tests", "dist", "dist_lenet.py"), "async"],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("dist_lenet async OK") == 2, r.stdout + r.stderr
