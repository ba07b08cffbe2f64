"""The harness end to end at a tiny size on the CPU, as the driver calls
it: a new process a run, one JSON object as the last line of standard
output. On one device and on four virtual ones."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchpaths import ROOT

MANIFEST = os.path.join("tests", "benchmarks", "rehearsal.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(workload, *extra, devices=1, cwd=ROOT, trace=0, seed=2 ** 31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace), "--manifest", MANIFEST, *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("workload,devices,trace", [
    ("tiny_resident", 1, 0), ("tiny_dp4_resident", 4, 0),
    ("tiny_hostfed", 1, 1)])
def test_rehearsal_prints_the_contracts_line(workload, devices, trace):
    r = _run(workload, "--rehearse-cpu", devices=devices, trace=trace)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    # the keys the driver reads come first; what follows them is the
    # harness's own, with the numbers compared last
    assert set(list(line)[:5]) == RESULT_KEYS
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, r.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == devices
    assert set(dev) >= {"platform", "kind", "count", "memory_peak_bytes"}
    # a rehearsal never prints a number under a device metric's name
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
    if trace == 0:
        assert set(line["metrics"]) == {"rehearsal.train_img_per_s",
                                        "rehearsal.setup_s"}
        for m in line["metrics"].values():
            assert m["value"] > 0 and set(m) == {"value", "unit"}
    # each number compared stands beside its limit, last on standard error
    tail = [l for l in r.stderr.splitlines() if l.startswith("compared ")]
    assert len(tail) == 9
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    assert r.stderr.strip().splitlines()[-1].startswith("compared ")


def test_without_a_chip_a_run_fails_and_prints_no_result():
    r = _run("tiny_resident")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr and "--rehearse-cpu" in r.stderr


def test_fewer_devices_than_the_cell_asks_for_fails():
    r = _run("tiny_dp4_resident", "--rehearse-cpu", devices=2)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs 4 chips" in r.stderr


def test_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds BENCHMARK.json and the files under ``paths``
    and nothing of the program: no result, another code than 0."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run("tiny_resident", "--rehearse-cpu", cwd=str(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""
