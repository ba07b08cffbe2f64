"""Multi-host launch recipe (reference tools/launch.py ssh mode).

No ssh daemon exists in CI, so the recipe is proven through --dry-run:
the launcher must emit one correct, complete command per host — exactly
what an operator (or a k8s/slurm wrapper) runs on each machine.
"""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(ROOT, "tools", "launch.py")


def _run(args):
    env = dict(os.environ)
    env.pop("MXNET_KVSTORE_SECRET", None)
    r = subprocess.run([sys.executable, LAUNCH] + args,
                       capture_output=True, text=True, timeout=60, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout.strip().splitlines()


def test_multihost_dry_run_emits_one_ssh_command_per_host():
    lines = _run(["-H", "hostA,hostB", "--heartbeat-dir", "/shared/hb",
                  "--dry-run", "python", "train.py", "--kv-store",
                  "dist_sync"])
    assert len(lines) == 2
    # runnable as printed: operator env supplies the secret via stdin
    assert lines[0].startswith(
        "[rank 0 @ hostA] printf '%s\\n' \"$MXNET_KVSTORE_SECRET\" | ssh ")
    assert lines[1].startswith(
        "[rank 1 @ hostB] printf '%s\\n' \"$MXNET_KVSTORE_SECRET\" | ssh ")
    for rank_, line in enumerate(lines):
        # every worker points at host 0's coordinator
        assert "MXNET_COORDINATOR_ADDRESS=hostA:9091" in line
        assert "MXNET_WORKER_RANK=%d" % rank_ in line
        assert "MXNET_NUM_WORKERS=2" in line
        assert "MXNET_HEARTBEAT_DIR=/shared/hb" in line
        # reference-era aliases for v1.x scripts
        assert "DMLC_PS_ROOT_URI=hostA" in line
        assert "DMLC_PS_ROOT_PORT=9091" in line
        assert "DMLC_ROLE=worker" in line
        assert "python train.py --kv-store dist_sync" in line
        # the job secret value must NOT travel in argv (world-readable
        # via /proc/<pid>/cmdline) — it ships on ssh stdin
        assert 'MXNET_KVSTORE_SECRET="' not in line
        assert re.search(r"MXNET_KVSTORE_SECRET=\w", line) is None
        assert "IFS= read -r MXNET_KVSTORE_SECRET" in line


def test_multihost_user_at_host_coordinator_is_dialable():
    lines = _run(["-H", "ubuntu@10.0.0.1,ubuntu@10.0.0.2",
                  "--heartbeat-dir", "/hb", "--dry-run", "cmd"])
    for line in lines:
        # ssh keeps the user@ prefix; the coordinator address must not
        assert "MXNET_COORDINATOR_ADDRESS=10.0.0.1:9091" in line
        assert "DMLC_PS_ROOT_URI=10.0.0.1" in line
        assert "ssh" in line and "ubuntu@10.0.0." in line


def test_multihost_round_robin_when_n_exceeds_hosts():
    lines = _run(["-H", "h0,h1", "-n", "4", "--heartbeat-dir", "/hb",
                  "--dry-run", "cmd"])
    hosts = [li.split("@ ")[1].split("]")[0] for li in lines]
    assert hosts == ["h0", "h1", "h0", "h1"]


def test_multihost_custom_port():
    (line,) = _run(["-H", "tpu-vm-0", "--coordinator-port", "7777",
                    "--heartbeat-dir", "/hb", "--dry-run", "cmd"])
    assert "MXNET_COORDINATOR_ADDRESS=tpu-vm-0:7777" in line


def test_singlehost_dry_run_contract():
    lines = _run(["-n", "2", "--dry-run", "python", "train.py"])
    assert len(lines) == 2
    for rank_, line in enumerate(lines):
        assert "MXNET_WORKER_RANK=%d" % rank_ in line
        assert re.search(r"MXNET_COORDINATOR_ADDRESS=127\.0\.0\.1:\d+",
                         line)
        assert "MXNET_KVSTORE_SECRET" not in line  # never in argv


def test_missing_heartbeat_dir_warns():
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, LAUNCH, "-H", "a,b", "--dry-run", "cmd"],
        capture_output=True, text=True, timeout=60, env=env)
    assert r.returncode == 0
    assert "failure detection" in r.stderr


def _load_launch():
    import importlib.util
    spec = importlib.util.spec_from_file_location("_launch_under_test",
                                                  LAUNCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_local_workers_on_a_chip_host_are_refused(monkeypatch, capsys):
    """-n N on a host with TPU chips: every worker would open every chip
    and all but the first would fail or hang. Refused with a message,
    unless the workers are CPU-pinned by name."""
    launch = _load_launch()
    monkeypatch.setattr(launch, "_local_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    cmd = [sys.executable, "-c", "pass"]
    assert launch.main(["-n", "2", "--max-restarts", "0"] + cmd) == 2
    err = capsys.readouterr().err
    assert "4 TPU chip(s)" in err and "JAX_PLATFORMS=cpu" in err
    # one worker holds all chips; CPU-pinned workers touch none
    assert launch._chip_conflict(1, []) is None
    assert launch._chip_conflict(2, ["JAX_PLATFORMS=cpu"]) is None
    assert launch.main(["-n", "2", "--max-restarts", "0", "--env",
                        "JAX_PLATFORMS=cpu"] + cmd) == 0
    # no chips, nothing to contend for
    monkeypatch.setattr(launch, "_local_chips", lambda: 0)
    assert launch._chip_conflict(2, []) is None
