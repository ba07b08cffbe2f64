"""The looped-model cell (``ouro_loop4_resident``): its configuration
against the published config.json key by key and the cut's arithmetic, its
analytic operations and bytes, the configuration-driven runner's
arguments, the readers of the heads' and gates' scopes, and a CPU
rehearsal at a tiny configuration as the benchmark's command calls it (the
planted faults are in test_ouro_correct_catches_faults.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

NAME = "ouro_2_6b_l6_s8k_bf16"
CELL = "ouro_loop4_resident"
CONFIG = "benchmarks/configs/%s.json" % NAME
TINY = "tests/benchmarks/configs/ouro_tiny.json"
MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_ouro.json")
# the published config.json (huggingface.co/ByteDance/Ouro-2.6B), less
# ``layer_types``: 48 x "full_attention"
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}
READERS = ["exit_heads_ms.train", "exit_heads_roofline_pct.train"]


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def test_the_cut_holds_509_661_185_parameters():
    """From ``param_shapes``, to the unit:

    | part | parameters |
    | --- | --- |
    | attention: q, k, v, o 2048 x 2048 each (16 heads of 128, 16 KV heads) | 16,777,216 |
    | SwiGLU (5632): 2 x 2048 x 5632 + 5632 x 2048 | 34,603,008 |
    | four sandwich norms | 8,192 |
    | a layer | 51,388,416 |
    | embedding and untied head, 49,152 x 2048 each | 201,326,592 |
    | final norm; exit gate (2048 + a bias) | 2,048; 2,049 |
    | published layers 0-5 | 509,661,185 = 8.15 GB at 16 bytes |
    | published layers 0-7 | 612,438,017 = 9.80 GB at 16 bytes |
    """
    from references import ouro as ref
    cfg = _load(CONFIG)
    count = {k: int(np.prod(s)) for k, s in ref.param_shapes(cfg).items()}
    assert sum(count.values()) == 509661185
    for l in cfg["layers"]:
        layer = {k: n for k, n in count.items() if k.startswith("l%d_" % l)}
        assert sum(layer.values()) == 51388416
        assert sum(n for k, n in layer.items()
                   if "_attn_" in k and k.endswith("_weight")) == 16777216
        assert sum(n for k, n in layer.items() if "_mlp_" in k) == 34603008
        assert sum(n for k, n in layer.items() if k.endswith("_gamma")) \
            == 4 * 2048
    assert count["embed_weight"] == count["head_weight"] == 100663296
    assert (count["final_norm_gamma"], count["exit_gate_weight"]
            + count["exit_gate_bias"]) == (2048, 2049)
    assert round(sum(count.values()) * 16 / 1e9, 2) == 8.15
    eight = dict(cfg, layers=list(range(8)))
    total8 = sum(int(np.prod(s)) for s in ref.param_shapes(eight).values())
    assert total8 == 612438017 and round(total8 * 16 / 1e9, 2) == 9.80


def test_every_published_key_is_unchanged_and_the_cut_is_stated():
    cfg = _load(CONFIG)
    man = _load("BENCHMARK.json")
    entry = {c["name"]: c for c in man["configs"]}[NAME]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == entry["source"] \
        == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    differ = [k for k, v in PUBLISHED.items() if cfg[k] != v]
    assert differ == ["num_hidden_layers"]
    assert cfg["layer_types"] == ["full_attention"] * 48
    # every published width, by its own key
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["vocab_size"]) == (2048, 5632, 128, 49152)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) \
        == (16, 16)
    assert (cfg["rope_theta"], cfg["rms_norm_eps"], cfg["total_ut_steps"]) \
        == (1000000, 1e-06, 4)
    assert cfg["tie_word_embeddings"] is False
    assert cfg["use_sliding_window"] is False and cfg["sliding_window"] is None
    # the cut: published layers 0-5 of 48, each run four times
    assert cfg["num_hidden_layers"] == len(cfg["layers"]) == 6
    assert cfg["layers"] == list(range(6))
    assert cfg["published"]["num_hidden_layers"] == 48
    assert "509,661,185 parameters, 8.15 GB" in cfg["deployment"]
    assert "seven further pipeline stages" in cfg["deployment"]
    assert "16.41 GB" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "exit_entropy_weight", "exit gate bias",
        "next pass reads the normed state", "optimizer", "initial weights"}
    assert cfg["exit_entropy_weight"] == 0.1
    assert "float32" in cfg["compute"] and "exit" in cfg["compute"]
    assert (cfg["batch_size"], cfg["sequence_length"]) == (1, 8192)
    assert cfg["experts_held"] == [0, 0]
    granite = _load("benchmarks/configs/granite_4_0_h_micro_l10_s8k_bf16.json")
    assert cfg["optimizer"] == granite["optimizer"]
    assert cfg["control"] == "fp8_operand"
    assert cfg["device_scopes"] == ["mx/attn/full", "mx/lm_head", "mx/exit"]
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "resident_tokens", 1)
    # the shared head's time, which this cell weights four times a step
    by = {m["name"]: m for m in man["per_layer"]}
    assert by["lm_head_ms.train"]["workloads"] == [
        "kimi_linear_resident", CELL]
    # appended after all that was there
    assert man["configs"][-1]["name"] == NAME
    assert man["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


def test_the_chip_file_compares_the_loss_rows_and_the_training_state():
    """``correct`` reads the first step's loss and rows, the second step's
    rows, the gradient and the change over three steps, each limit with
    the readings it was set from."""
    cfg = _load(CONFIG)
    assert set(cfg["limits"]) == {"loss1_gap", "rows1_gap", "rows2_gap",
                                  "grad1_gap", "change3_gap"}
    for name in cfg["limits"]:
        assert cfg["limits_from"].count(name.split("_")[0] + " ") >= 1, name
    assert "Every fault fails all five" in cfg["limits_from"]


def test_the_runner_hands_the_builder_the_configurations_own_keys():
    import inspect
    from mxnet_tpu.models.ouro import ouro_symbol
    from references import ouro as ref
    from runners import train_lm_cfg
    for rel in (CONFIG, TINY):
        cfg = _load(rel)
        kw = train_lm_cfg.symbol_kwargs(cfg)
        assert set(kw) == set(inspect.signature(ouro_symbol).parameters)
        assert kw["vocab_rows"] == cfg["vocab_size"]
        assert kw["layers"] == tuple(cfg["layers"])
        assert (cfg["runner"], cfg["reference"], cfg["flops"]) \
            == ("train_lm_cfg", "ouro", "flops_ouro")
    sym = train_lm_cfg.build_symbol(_load(TINY))
    assert set(sym.list_arguments()) - {"data", "softmax_label"} \
        == set(ref.param_shapes(_load(TINY)))
    assert sym.list_auxiliary_states() == ["exit_counters"]


def test_flops_and_bytes_come_from_shapes_with_their_source():
    from harness import flops_ouro as flops
    cfg = _load(CONFIG)
    t = cfg["sequence_length"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    head = 49152 * 2048
    assert flops.matmul_params_per_token(cfg) == 4 * (6 * layer + head)
    pairs = t * (t + 1) // 2
    assert flops.attention_flops_per_sequence(cfg, train=False) \
        == 4 * 6 * pairs * 4 * 128 * 16
    assert flops.head_flops_per_sequence(cfg) == 3 * 4 * 2 * head * t
    total = flops.train_flops_per_sample(cfg)
    assert total == 6 * 4 * (6 * layer + head) * t \
        + 3 * 4 * 6 * pairs * 4 * 128 * 16
    assert 100.1e12 < total < 100.3e12
    # the heads a fifth of the step, attention a fifth, products the rest
    assert 0.19 < flops.head_flops_per_sequence(cfg) / total < 0.21
    assert 0.19 < flops.attention_flops_per_sequence(cfg) / total < 0.21
    assert flops.head_bytes_per_sequence(cfg) \
        == 4 * 3 * (49152 + t) * 2048 * 2
    # the heads' roofline: the operations bound it, 100 ms beside 3.4
    assert flops.head_flops_per_sequence(cfg) / 197e12 \
        > 20 * flops.head_bytes_per_sequence(cfg) / 819e9
    assert "2001.08361" in flops.__doc__


def test_the_readers_sum_the_heads_and_the_exit_scopes():
    """A hand-made trace and program text: ``exit_heads_ms.train`` is the
    time under ``mx/lm_head`` and ``mx/exit``, the roofline share the
    heads' least time over it; another cell's configuration, a program
    without the text and none read nothing."""
    from harness import flops_ouro as flops, manifest
    hlo = '''
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%c1, metadata={op_name="jit(step)/jit(main)/mx/lm_head/while/body/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%c2, metadata={op_name="jit(step)/jit(main)/jvp(mx/op/_contrib_LoopExitLoss)/mx/exit/mul"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%c3, metadata={op_name="jit(step)/jit(main)/checkpoint/mx/op/_contrib_ExitGate/mx/exit/reduce_sum"}
  %custom-call.7 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/checkpoint/mx/attn/full/pallas_call"}
  %copy.9 = f32[8]{0} copy(%x)
'''
    mods = [["jit_step(1)", t, 900_000] for t in (0, 10 ** 6, 2 * 10 ** 6,
                                                   3 * 10 ** 6)]
    ops = []
    for t in (10 ** 6, 2 * 10 ** 6):
        ops += [["%fusion.1 = f32[8] fusion(...)", t, 300_000],
                ["%fusion.2 = f32[8] fusion(...)", t + 300_000, 20_000],
                ["%fusion.3 = f32[8] fusion(...)", t + 400_000, 10_000],
                ["%custom-call.7 = bf16[8] custom-call(...)", t + 500_000,
                 200_000],
                ["%copy.9 = f32[8] copy(%x)", t + 800_000, 50_000]]
    cfg = _load(CONFIG)
    ctx = {"trace": {"devices": [{"name": "/device:TPU:0", "ops": ops,
                                  "modules": mods}], "host": []},
           "step_program": "^jit_step", "steps_per_program": 1,
           "hlo_text": hlo, "batch_size": 1, "cfg": cfg,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: manifest.layer_reader(BENCH_DIR, name)(ctx)  # noqa
    assert read("exit_heads_ms.train") == pytest.approx(0.33)
    least = flops.head_flops_per_sequence(cfg) / 197e12
    assert read("exit_heads_roofline_pct.train") == pytest.approx(
        100 * least / 0.33e-3)
    others = [_load("benchmarks/configs/%s.json" % n) for n in (
        "kimi_linear_a3b_ep32_l5_s8k_bf16", "trinity_mini_l5_s8k_bf16",
        "granite_4_0_h_micro_l10_s8k_bf16", "resnet50_v1_b128_bf16")]
    for gone in [{"hlo_text": None}, {"hlo_text": "%a.1 = f32[] add(%x)"},
                 {"cfg": None}, {"peaks": None}] \
            + [{"cfg": c} for c in others]:
        bare = dict(ctx, **gone)
        bare.pop("_scope_classes_of", None)
        for name in READERS:
            if gone == {"peaks": None} and name == "exit_heads_ms.train":
                continue        # a time needs no peak
            assert manifest.layer_reader(BENCH_DIR, name)(bare) is None, \
                (name, list(gone))


def test_weights_follow_the_seed_alone_also_above_2_to_31():
    from references import ouro as ref
    cfg = _load(TINY)
    big = 2 ** 31 + 12345
    w1, w2 = ref.init_params(cfg, big), ref.init_params(cfg, big)
    w3 = ref.init_params(cfg, big + 1)
    assert all(np.array_equal(w1[k], w2[k]) for k in w1)
    assert not np.array_equal(w1["head_weight"], w3["head_weight"])
    assert float(np.asarray(w1["exit_gate_bias"])[0]) == 0.0
    assert float(np.asarray(w1["l3_input_norm_2_gamma"]).min()) == 1.0
    assert 0.015 < float(np.asarray(w1["exit_gate_weight"]).std()) < 0.025


def _rehearse(*extra, trace=0, seed=2 ** 31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", "tiny_ouro_resident", "--seed", str(seed),
         "--seconds", "1", "--trace", str(trace), "--manifest", MANIFEST,
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_the_contracts_line(trace):
    """As the benchmark's command calls it, untraced and traced:
    ``correct`` under the tiny file's limits, the exit op's counters in the
    window, and a traced line (the CPU's trace has no device plane) with
    no device metric."""
    r = _rehearse("--rehearse-cpu", trace=trace)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(list(line)[:5]) == {"correct", "attempted", "failed",
                                   "metrics", "device"}
    assert line["correct"] is True, r.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    cfg = _load(TINY)
    assert set(line["compared"]) == set(cfg["limits"])
    counters = line["window"]["counters"]
    assert counters["loop/passes"] == 4
    assert 1 < counters["exit/mean_step"] < 4
    assert 0 < counters["exit/last_share"] < 1
    if trace:
        assert line["metrics"] == {}
    else:
        assert set(line["metrics"]) == {"rehearsal.train_img_per_s",
                                        "rehearsal.setup_s"}


def test_without_a_chip_the_cell_fails_and_prints_nothing():
    r = _rehearse()
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr
