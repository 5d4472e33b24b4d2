"""The harness on the CPU: the manifest resolves, new cells arrive as new
files only, the last line keeps to the contract, and no chip means no
result.  The tiny fixture configuration is ResNet-18 v1 at 32x32."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import device, manifest  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def test_every_name_in_the_manifest_resolves_to_a_file():
    m = manifest.Manifest(REPO)
    data = m.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    end_to_end = {e["name"] for e in data["end_to_end"]}
    assert "setup_s" in end_to_end
    used = set()
    for workload in data["workloads"]:
        cell = m.cell(workload["name"])
        used.add(workload["config"])
        assert cell.chips in (1, 4)
        assert hasattr(cell.entry(), "build")
        reference = cell.reference()
        assert hasattr(reference, "forward") and hasattr(reference,
                                                         "outputs")
        assert hasattr(reference, cell.config["flops_function"])
        reported = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for metric in cell.per_layer:
            assert callable(cell.reader(metric["name"]).read)
            assert metric["moves"] in reported
    assert used == {c["name"] for c in data["configs"]}
    for config in data["configs"]:
        assert any(config["file"].startswith(p + "/") for p in data["paths"])
    four = sum(w["chips"] == 4 for w in data["workloads"])
    assert four <= max(1, len(data["workloads"]) // 4)
    for metric in data["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1


@pytest.fixture()
def grown_root(tmp_path):
    """A copy of the benchmark that a later change has grown by a
    configuration, a traffic mix, an entry and a per-layer metric, each a
    new file, plus entries in BENCHMARK.json.  No file that was there is
    edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(FIXTURES, "resnet18_v1_tiny.json"),
                os.path.join(root, "benchmark", "configs"))
    shutil.copy(os.path.join(FIXTURES, "train_fused_tiny.json"),
                os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(root, "benchmark", "traffic",
                           "train_fused_tiny.json")) as f:
        traffic = json.load(f)
    traffic["entry"] = "fixture_entry"
    with open(os.path.join(root, "benchmark", "traffic",
                           "train_fixture.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmark", "entries",
                           "fixture_entry.py"), "w") as f:
        f.write("import gluon_train_step\n\n\n"
                "def build(ctx):\n"
                "    return gluon_train_step.Session(ctx)\n")
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "fixture.steps_in_window.py"), "w") as f:
        f.write("def read(obs):\n"
                "    return float(obs['window']['steps'])\n")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "resnet18_v1_tiny", "source": "fixture", "reduced": [],
        "file": "benchmark/configs/resnet18_v1_tiny.json", "why": "fixture"})
    data["workloads"].append({
        "name": "tiny", "config": "resnet18_v1_tiny",
        "traffic": "train_fixture", "chips": 1, "why": "fixture"})
    data["per_layer"].append({
        "name": "fixture.steps_in_window", "unit": "count",
        "better": "higher", "source": "host_clock", "layer": "entry",
        "moves": "train_samples_per_s", "workloads": ["tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    return root


def cpu_gate(chips, root):
    """Stands in for the device gate: the test steers the harness onto the
    CPU here, the program has no option for it."""
    import jax

    return jax.devices()[:chips], device.load_peaks(root)["TPU v5 lite"]


def last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    for earlier in lines[:-1]:
        assert not earlier.startswith("{"), earlier
    return json.loads(lines[-1])


def test_new_cell_from_new_files_and_the_last_line(grown_root, capsys):
    argv = ["--workload", "tiny", "--seed", "5", "--seconds", "1"]
    assert run.main(argv + ["--trace", "1"], gate=cpu_gate,
                    root=grown_root) == 0
    traced = last_line(capsys)
    assert set(traced) == RESULT_KEYS | {"breakdown"}
    assert set(traced["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert traced["correct"] is True and traced["failed"] == 0
    metrics = traced["metrics"]
    # the reader that arrived as a new file was found by its name
    assert metrics["fixture.steps_in_window"]["value"] == traced["attempted"]
    assert metrics["entry.compiles_in_window"]["value"] == 0
    assert metrics["entry.dispatch_ms_per_step"]["value"] > 0
    assert metrics["step.mfu"]["unit"] == "%"
    # a CPU trace has no device plane: those readers find nothing to read
    # and their metrics are left out, not reported as 0
    assert "step.device_ms" not in metrics
    assert "device.idle_share" not in metrics
    assert not set(metrics) & {"train_samples_per_s", "setup_s"}

    assert run.main(argv + ["--trace", "0"], gate=cpu_gate,
                    root=grown_root) == 0
    plain = last_line(capsys)
    assert set(plain) == RESULT_KEYS
    assert set(plain["device"]) == DEVICE_KEYS
    assert set(plain["metrics"]) == {"train_samples_per_s", "peak_hbm_gb",
                                     "setup_s"}
    for value in plain["metrics"].values():
        assert set(value) == {"value", "unit"} and value["value"] > 0
    assert plain["correct"] is True
    assert plain["attempted"] > 0 and plain["attempted"] % 3 == 0


def test_on_a_cpu_the_command_exits_nonzero_without_a_result():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        data["command"] + ["--workload", data["workloads"][0]["name"],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode not in (0, None)
    assert "{" not in done.stdout
    assert "refusing to measure on platform 'cpu'" in done.stderr


def test_an_unknown_workload_is_an_error_not_a_default():
    with pytest.raises(manifest.ManifestError, match="no workloads named"):
        manifest.Manifest(REPO).cell("no_such_cell")


# ------------------------------------------------- the open-loop generator


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


def test_a_stalled_server_lengthens_latency_not_the_schedule():
    """Ten requests due every 10 ms; the server's door blocks the sender for
    35 ms at request 3 and every request takes 1 ms.  Requests 4-6 go out
    late; their latency counts from when they were due."""
    from benchmark.harness import loadgen

    clock = FakeClock()
    due = [0.01 * i for i in range(10)]
    done = {}

    def send(i):
        if i == 3:
            clock.sleep(0.035)
        done[i] = clock.now + 0.001
        return i

    start, records = loadgen.run_open_loop(due, send, clock=clock,
                                           sleep=clock.sleep)
    assert [r[0] for r in records] == due       # the schedule did not move
    summary = loadgen.summarize(records, [done[i] - start
                                          for i in range(10)], 0.015)
    assert summary["attempted"] == 10 and summary["failed"] == 0
    latency, late = summary["latency_s"], summary["late_s"]
    assert latency[0] == pytest.approx(0.001)
    assert latency[3] == pytest.approx(0.036)   # stalled at the door
    assert latency[4] == pytest.approx(0.026)   # sent 25 ms late, + 1 ms
    assert late[4] == pytest.approx(0.025) and late[6] == pytest.approx(0.005)
    assert late[7] == pytest.approx(0.0, abs=1e-9)
    assert summary["within_limit_share"] == pytest.approx(0.7)


def test_a_refused_request_counts_as_failed_and_misses_the_limit():
    from benchmark.harness import loadgen

    clock = FakeClock()

    def send(i):
        if i == 1:
            raise RuntimeError("queue full")
        return i

    _, records = loadgen.run_open_loop([0.0, 0.001, 0.002], send,
                                       clock=clock, sleep=clock.sleep)
    summary = loadgen.summarize(records, [0.001, None, 0.003], 1.0)
    assert summary["failed"] == 1
    assert summary["within_limit_share"] == pytest.approx(2 / 3)


def test_poisson_schedule_is_seeded_and_percentiles_need_ten_beyond():
    import numpy as np

    from benchmark.harness import loadgen

    a = loadgen.poisson_schedule(500.0, 2.0, np.random.RandomState(7))
    b = loadgen.poisson_schedule(500.0, 2.0, np.random.RandomState(7))
    assert (a == b).all() and 800 < len(a) < 1200 and a[-1] < 2.0
    assert (np.diff(a) > 0).all()
    samples = list(range(1000))
    assert loadgen.percentile(samples, 50) == 500
    assert loadgen.percentile(samples, 99) == 989
    with pytest.raises(ValueError, match="ten are needed"):
        loadgen.percentile(samples, 99.9)       # one sample beyond
    with pytest.raises(ValueError, match="ten are needed"):
        loadgen.percentile(samples[:500], 99)   # five beyond
