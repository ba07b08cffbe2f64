"""``BENCHMARK.json`` (and the rehearsal's manifest of the same shape),
``configs/``, ``traffic/``, ``layer_metrics/``, ``runners/`` and
``references/`` agree: every name resolves, every per-layer metric's cells
report the end-to-end metric it moves, names and units keep to the
permitted characters."""
import json
import os
import re

import pytest

from benchpaths import BENCH_DIR, ROOT

MANIFESTS = ["BENCHMARK.json", "tests/benchmarks/rehearsal.json"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture(params=MANIFESTS)
def man(request):
    return _load(request.param)


def _cells_reporting(man, metric):
    return {w["name"] for w in man["workloads"]
            if "workloads" not in metric or w["name"] in metric["workloads"]}


def test_keys_are_the_contracts(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert "setup_s" in {m["name"] for m in man["end_to_end"]}
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 4)


def test_names_and_units_use_permitted_characters(man):
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[g]]
    names += [w["traffic"] for w in man["workloads"]]
    names += [k for c in man["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for g in ("configs", "workloads"):
        assert len({x["name"] for x in man[g]}) == len(man[g])
    metrics = man["end_to_end"] + man["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_every_name_resolves_to_its_file(man):
    configs = {c["name"]: c for c in man["configs"]}
    used = set()
    for w in man["workloads"]:
        cfg = _load(configs[w["config"]]["file"])
        used.add(w["config"])
        mix = _load(os.path.join("benchmarks", "traffic",
                                 w["traffic"] + ".json"))
        assert mix["warmup_steps"] % cfg["steps_per_dispatch"] == 0
        for kind, key in (("runners", "runner"), ("references", "reference")):
            assert os.path.isfile(os.path.join(
                BENCH_DIR, kind, cfg[key] + ".py")), (kind, cfg[key])
        assert set(cfg["limits"]) <= {
            "loss1_gap", "loss2_gap", "loss3_gap", "rows1_gap", "rows2_gap",
            "rows3_gap", "grad1_gap", "change3_gap", "stats3_gap"}
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    for c in man["configs"]:
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word


def test_per_layer_metrics_have_readers_and_move_what_their_cells_report(man):
    from harness import manifest
    e2e = {m["name"]: m for m in man["end_to_end"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf_md = f.read()
    for m in man["per_layer"]:
        assert callable(manifest.layer_reader(BENCH_DIR, m["name"]))
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        cells = _cells_reporting(man, m)
        assert cells, m["name"]
        assert cells <= _cells_reporting(man, e2e[m["moves"]]), m["name"]
        assert "`%s`" % m["name"] in perf_md, \
            "PERF.md does not list %s" % m["name"]
    for w in man["workloads"]:
        assert manifest.metrics_of(man, "per_layer", w["name"])
        assert len(manifest.metrics_of(man, "end_to_end", w["name"])) >= 2


def test_a_whole_check_fits_its_time(man):
    runs = 2 + 14 * 24
    assert runs * (man["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peaks_and_flops_carry_their_sources():
    from harness import flops, peaks
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks.peaks("TPU v9 imaginary")
    cfg = _load("benchmarks/configs/resnet50_v1_b128_bf16.json")
    # He et al. Table 1: "3.8 x 10^9" multiply-adds for the 50-layer net
    assert flops.forward_macs_per_image(cfg) == 3857973248
    assert flops.train_flops_per_image(cfg) == 6 * 3857973248
    assert "1512.03385" in flops.__doc__ and "4.089e9" in flops.__doc__


def test_reference_parameters_are_the_published_count():
    from references import resnet_v1
    cfg = _load("benchmarks/configs/resnet50_v1_b128_bf16.json")
    args, aux = resnet_v1.param_shapes(cfg)
    count = 0
    for shape in args.values():
        n = 1
        for d in shape:
            n *= d
        count += n
    assert count == 25557032          # ResNet-50, 1000 classes
    assert len(aux) == 2 * 53         # one BatchNorm a convolution


def test_weights_and_batches_follow_the_seed_alone():
    import numpy as np
    from harness import traffic
    from references import resnet_v1
    cfg = dict(_load("tests/benchmarks/configs/resnet18_tiny.json"),
               zero_last_gamma=True)
    mix = _load("benchmarks/traffic/resident_batch.json")
    big = 2 ** 31 + 12345            # the driver's seeds pass 32 signed bits
    a1, x1 = resnet_v1.init_params(cfg, big)
    a2, _ = resnet_v1.init_params(cfg, big)
    a3, _ = resnet_v1.init_params(cfg, big + 1)
    assert all(np.array_equal(a1[k], a2[k]) for k in a1)
    assert not np.array_equal(a1["conv0_weight"], a3["conv0_weight"])
    # every unit starts as the identity: its last BatchNorm's scale is 0
    for name, v in a1.items():
        if name.endswith("_gamma"):
            last = name.endswith("_bn2_gamma") and name.startswith("stage")
            assert float(np.abs(np.asarray(v) - (0.0 if last else 1.0)).max()) == 0
    assert all(float(np.asarray(v).min()) == float(np.asarray(v).max())
               for v in x1.values())
    (d1, l1), = traffic.make_batches(mix, cfg, big)
    (d2, _), = traffic.make_batches(mix, cfg, big)
    (d3, _), = traffic.make_batches(mix, cfg, big + 1)
    assert np.array_equal(d1, d2) and not np.array_equal(d1, d3)
    assert d1.shape == (cfg["batch_size"],) + tuple(cfg["image_shape"])
    rows = np.asarray(d1).reshape(d1.shape[0], -1)
    assert len({r.tobytes() for r in rows}) == len(rows)   # rows all differ
    assert set(np.asarray(l1).tolist()) <= set(range(8))
