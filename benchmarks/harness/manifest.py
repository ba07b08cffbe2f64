"""Reads a manifest (``BENCHMARK.json``, or a rehearsal's own file of the
same shape) and resolves a cell: its configuration file, its traffic mix,
the metrics it reports. Everything is found by name; nothing here knows a
cell, a configuration, a mix or a metric."""
from __future__ import annotations

import importlib.util
import json
import os


def load(path):
    with open(path) as f:
        return json.load(f)


def cell(manifest, root, workload):
    """The cell ``workload`` with its configuration read from its file."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError("no workload %r in the manifest (has: %s)"
                       % (workload, sorted(cells)))
    w = dict(cells[workload])
    entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        w["cfg"] = json.load(f)
    return w


def load_cell(manifest_path, root, bench_dir, workload):
    """The cell with everything a runner reads: its configuration, its
    traffic mix, the manifest and where the checkout is."""
    from harness import traffic
    man = load(manifest_path)
    c = cell(man, root, workload)
    c.update(manifest=man, bench_dir=bench_dir, root=root,
             mix=traffic.load_mix(bench_dir, c["traffic"]))
    return c


def metrics_of(manifest, group, workload):
    """The ``end_to_end`` or ``per_layer`` metrics that the cell reports:
    those without a ``workloads`` key, and those that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def layer_reader(bench_dir, name):
    """``read(ctx)`` of ``layer_metrics/<name>.py``."""
    path = os.path.join(bench_dir, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
