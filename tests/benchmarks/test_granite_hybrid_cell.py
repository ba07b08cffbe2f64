"""The Mamba-2 / attention hybrid cell (``granite_h_micro_resident``): its
configuration against the catalog's row key by key and the cut's
arithmetic, its analytic FLOPs and bytes, the configuration-driven
runner's arguments, its scope readers, and a CPU rehearsal at a tiny
configuration as the driver calls it (the planted faults are in
test_granite_hybrid_correct_catches_faults.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

NAME = "granite_4_0_h_micro_l10_s8k_bf16"
CELL = "granite_h_micro_resident"
CONFIG = "benchmarks/configs/%s.json" % NAME
TINY = "tests/benchmarks/configs/granite_hybrid_tiny.json"
MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_granite_hybrid.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ["ssm_ms.train", "ssm_roofline_pct.train"]


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def test_the_cut_holds_772_160_448_parameters():
    """From ``param_shapes``, to the unit:

    | part | parameters |
    | --- | --- |
    | Mamba-2 mixer: in_proj 2048 x (4096 + 4352 + 64 = 8512) | 17,432,576 |
    | conv 4352 x 4, its bias 4352; dt_bias, A_log, D 64 each; gated norm 4096 | 26,048 |
    | out_proj 4096 x 2048 | 8,388,608 |
    | a layer's SwiGLU (8192): 2 x 2048 x 8192 + 8192 x 2048 | 50,331,648 |
    | a layer's two RMSNorms | 4,096 |
    | a Mamba layer | 76,182,976 |
    | attention: q, o 2048 x 2048 each; k, v 2048 x 512 each | 10,485,760 |
    | the attention layer (with its SwiGLU and norms) | 60,821,504 |
    | published layers 0-9 (5 Mamba, attention at 5, 4 Mamba) + final norm | 746,470,336 |
    | tied embedding = head, rows 0-12,543 of 100,352 | 25,690,112 |
    | held | 772,160,448 = 12.35 GB at 16 bytes |
    """
    from references import granite_hybrid as ref
    cfg = _load(CONFIG)
    count = {k: int(np.prod(s)) for k, s in ref.param_shapes(cfg).items()}
    assert sum(count.values()) == 772160448

    def layer(l, pick=lambda k: True):
        return sum(n for k, n in count.items()
                   if k.startswith("l%d_" % l) and pick(k))
    mamba = [l for l in cfg["layers"] if cfg["layer_types"][l] == "mamba"]
    assert mamba == [0, 1, 2, 3, 4, 6, 7, 8, 9]
    for l in mamba:
        assert layer(l) == 76182976
        assert count["l%d_mamba_in_weight" % l] == 2048 * 8512 == 17432576
        assert layer(l, lambda k: "_mamba_" in k and k.split("_mamba_")[1] in (
            "conv_weight", "conv_bias", "dt_bias", "A_log", "D",
            "norm_gamma")) == 26048
        assert count["l%d_mamba_out_weight" % l] == 8388608
    for l in cfg["layers"]:
        assert layer(l, lambda k: "_mlp_" in k) == 50331648
        assert layer(l, lambda k: k.endswith(("input_norm_gamma",
                                              "post_attn_norm_gamma"))) == 4096
    assert layer(5, lambda k: "_attn_" in k and "norm" not in k) == 10485760
    assert layer(5) == 60821504
    assert sum(layer(l) for l in cfg["layers"]) + count["final_norm_gamma"] \
        == 746470336
    assert count["embed_weight"] == 12544 * 2048 == 25690112
    assert "head_weight" not in count          # one matrix: tied
    assert round(sum(count.values()) * 16 / 1e9, 2) == 12.35
    # the whole vocabulary would not leave room beside 16.9 GB
    assert sum(count.values()) - count["embed_weight"] + 100352 * 2048 \
        == 951991232


def test_every_published_key_is_unchanged_and_the_cut_is_stated():
    cfg = _load(CONFIG)
    man = _load("BENCHMARK.json")
    entry = {c["name"]: c for c in man["configs"]}[NAME]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["source"] == entry["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(l) for l in f
                   if '"granite-4.0-h-micro"' in l][0]
        assert entry["source"] == row["source_url"]
        differ = [k for k, v in row["config"].items() if cfg[k] != v]
        assert differ == ["num_hidden_layers", "vocab_size"]
    # every published width, by its own key
    assert (cfg["hidden_size"], cfg["shared_intermediate_size"]) \
        == (2048, 8192)
    assert [cfg["mamba_" + k] for k in (
        "n_heads", "d_head", "d_state", "d_conv", "expand", "n_groups",
        "chunk_size")] == [64, 64, 128, 4, 2, 1, 256]
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (32, 8)
    assert [cfg[k] for k in ("embedding_multiplier", "attention_multiplier",
                             "residual_multiplier", "logits_scaling")] \
        == [12, 0.015625, 0.22, 8]
    assert cfg["tie_word_embeddings"] is True
    assert cfg["position_embedding_type"] == "nope"
    # the floors of a cut: one whole period of the 9 : 1 pattern, an eighth
    # of the vocabulary
    assert cfg["num_hidden_layers"] == len(cfg["layers"]) == 10
    assert cfg["layers"] == list(range(10))
    assert [cfg["layer_types"][l] for l in cfg["layers"]] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"] == cfg["layer_types"][:10] * 4
    assert cfg["published"] == {
        "num_hidden_layers": 40, "vocab_size": 100352,
        "parameters": cfg["published"]["parameters"]}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "772,160,448 parameters, 12.35 GB" in cfg["deployment"]
    assert "8 ways" in cfg["deployment"] \
        and "three further pipeline stages" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"optimizer", "initial weights",
                                   "float32 parts", "time_step_limit"}
    assert "float32 accumulation" in cfg["compute"]
    assert "8,192" in cfg["sample"]
    assert (cfg["batch_size"], cfg["sequence_length"]) == (1, 8192)
    trinity = _load("benchmarks/configs/trinity_mini_l5_s8k_bf16.json")
    assert cfg["optimizer"] == trinity["optimizer"]
    assert cfg["control"] == "fp8_operand"
    assert cfg["device_scopes"] == ["mx/ssm", "mx/ssm/conv", "mx/ssm/intra",
                                    "mx/ssm/scan", "mx/attn/full",
                                    "mx/lm_head"]
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "resident_tokens", 1)
    new = [m["name"] for m in man["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == METRICS
    assert [m["name"] for m in man["per_layer"]][-2:] == METRICS
    assert len(man["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


def test_the_runner_hands_the_builder_the_configurations_own_keys():
    """Every argument of the builder comes from the file, under the name
    ``symbol.keys`` or ``symbol.renamed`` gives; the symbol's variables are
    the reference's, the tied matrix once."""
    import inspect
    from mxnet_tpu.models.granite_hybrid import granite_hybrid_symbol
    from references import granite_hybrid as ref
    from runners import train_lm_cfg
    for rel in (CONFIG, TINY):
        cfg = _load(rel)
        kw = train_lm_cfg.symbol_kwargs(cfg)
        assert set(kw) == set(
            inspect.signature(granite_hybrid_symbol).parameters)
        assert kw["vocab_rows"] == cfg["vocab_size"]
        assert kw["layers"] == tuple(cfg["layers"])
        assert cfg["runner"] == "train_lm_cfg"
        assert cfg["reference"] == "granite_hybrid"
        assert cfg["flops"] == "flops_granite_hybrid"
        # ``held_counts`` of the runner reads the key before it looks at
        # the choices, which this model has none of
        assert cfg["experts_held"] == [0, 0]
    sym = train_lm_cfg.build_symbol(_load(TINY))
    assert set(sym.list_arguments()) - {"data", "softmax_label"} \
        == set(ref.param_shapes(_load(TINY)))


def test_flops_and_bytes_come_from_shapes_with_their_source():
    from harness import flops_granite_hybrid as flops
    cfg = _load(CONFIG)
    t = cfg["sequence_length"]
    met = flops.matmul_params_per_token(cfg)
    # everything but the norms, the convolutions, the per-head vectors:
    # 9 x (26,048 + 4,096) + 4,096 + 2,048; the tied matrix once
    assert met == 772160448 - 9 * 30144 - 4096 - 2048 == 771883008
    # a token a layer forward: C B^T 2 x 256 x 128, the masked decays
    # against delta x 2 x 256 x 64 a head, a chunk's state and C against
    # the state carried in 2 x 64 x 128 a head each, 64 heads
    per_token = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    assert per_token == 4259840
    assert flops.ssm_flops_per_sequence(cfg, train=False) \
        == 9 * t * per_token
    assert flops.ssm_flops_per_sequence(cfg) == 3 * 9 * t * per_token
    # x, z 4,096 each, B, C 128 each, dt 64 read and y 4,096 written;
    # as much again backward; bfloat16
    assert flops.ssm_bytes_per_sequence(cfg) \
        == 9 * t * 2 * 2 * (8512 + 4096)
    attn = flops.attention_flops_per_sequence(cfg, train=False)
    assert attn == 33558528 * 4 * 64 * 32
    total = flops.train_flops_per_sample(cfg)
    assert total == 6 * met * t + 3 * 9 * t * per_token + 3 * attn
    assert 39.6e12 < total < 39.8e12
    assert 0.023 < flops.ssm_flops_per_sequence(cfg) / total < 0.025
    # the core's roofline: the operations bound it, 4.8 ms beside 4.5
    assert flops.ssm_flops_per_sequence(cfg) / 197e12 \
        > flops.ssm_bytes_per_sequence(cfg) / 819e9
    assert "2001.08361" in flops.__doc__ and "2405.21060" in flops.__doc__
    # untied, the embedding's rows are a lookup and the head a matrix
    tiny = _load(TINY)
    tied = flops.matmul_params_per_token(tiny)
    assert flops.matmul_params_per_token(
        dict(tiny, tie_word_embeddings=False)) == tied


def test_scope_readers_sum_the_scopes_under_mx_ssm():
    """A hand-made trace and program text: an op's self time goes to the
    longest listed scope its instruction was traced under, and
    ``ssm_ms.train`` is ``mx/ssm`` with the three scopes inside it; a
    program without the text or the scopes, another cell's configuration
    and none read nothing."""
    from harness import flops_granite_hybrid as flops, manifest, scopes_of
    hlo = '''
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/jit(main)/checkpoint/mx/ssm/conv/mul"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kOutput, calls=%c2, metadata={op_name="jit(step)/jit(main)/checkpoint/mx/ssm/while/body/mx/ssm/intra/dot_general"}
  %while.3 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jit(main)/transpose(jvp(mx/ssm))/while"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%c4, metadata={op_name="jit(step)/jit(main)/checkpoint/mx/ssm/while/body/mx/ssm/scan/mul"}
  %custom-call.7 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/checkpoint/mx/attn/full/pallas_call"}
  ROOT %dot.2 = f32[8]{0} dot(%x, %y), metadata={op_name="jit(step)/jit(main)/mx/lm_head/dot_general"}
  %copy.9 = f32[8]{0} copy(%x)
'''
    mods = [["jit_step(1)", t, 900_000] for t in (0, 10 ** 6, 2 * 10 ** 6,
                                                   3 * 10 ** 6)]
    ops = []
    for t in (10 ** 6, 2 * 10 ** 6):
        ops += [["%fusion.1 = bf16[8] fusion(...)", t, 50_000],
                ["%fusion.2 = f32[8] fusion(...)", t + 100_000, 200_000],
                ["%while.3 = (s32[]) while(...)", t + 300_000, 100_000],
                ["%fusion.4 = f32[8] fusion(...)", t + 400_000, 30_000],
                ["%custom-call.7 = bf16[8] custom-call(...)", t + 500_000,
                 60_000],
                ["%dot.2 = f32[8] dot(...)", t + 700_000, 100_000],
                ["%copy.9 = f32[8] copy(%x)", t + 800_000, 50_000]]
    cfg = _load(CONFIG)
    ctx = {"trace": {"devices": [{"name": "/device:TPU:0", "ops": ops,
                                  "modules": mods}], "host": []},
           "step_program": "^jit_step", "steps_per_program": 1,
           "hlo_text": hlo, "batch_size": 1, "cfg": cfg,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: manifest.layer_reader(BENCH_DIR, name)(ctx)  # noqa
    ms = scopes_of.scope_ms(ctx)
    assert ms["mx/ssm/conv"] == pytest.approx(0.05)
    assert ms["mx/ssm/intra"] == pytest.approx(0.2)
    assert ms["mx/ssm"] == pytest.approx(0.1)
    assert ms["mx/ssm/scan"] == pytest.approx(0.03)
    assert ms["mx/attn/full"] == pytest.approx(0.06)
    assert ms["mx/lm_head"] == pytest.approx(0.1)
    assert read("ssm_ms.train") == pytest.approx(0.38)
    least = max(flops.ssm_flops_per_sequence(cfg) / 197e12,
                flops.ssm_bytes_per_sequence(cfg) / 819e9)
    assert read("ssm_roofline_pct.train") == pytest.approx(
        100 * least / 0.38e-3)
    others = [_load("benchmarks/configs/%s.json" % n) for n in (
        "kimi_linear_a3b_ep32_l5_s8k_bf16", "trinity_mini_l5_s8k_bf16",
        "resnet50_v1_b128_bf16")]
    for gone in [{"hlo_text": None}, {"hlo_text": "%a.1 = f32[] add(%x)"},
                 {"cfg": None}, {"peaks": None}] \
            + [{"cfg": c} for c in others]:
        bare = dict(ctx, **gone)
        bare.pop("_scope_classes_of", None)
        for name in METRICS:
            if gone == {"peaks": None} and name == "ssm_ms.train":
                continue        # a time needs no peak
            assert manifest.layer_reader(BENCH_DIR, name)(bare) is None, \
                (name, list(gone))


def test_weights_follow_the_seed_alone_also_above_2_to_31():
    """And start as state-spaces/mamba's ``Mamba2`` starts its own."""
    from references import granite_hybrid as ref
    cfg = _load(TINY)
    big = 2 ** 31 + 12345
    w1, w2 = ref.init_params(cfg, big), ref.init_params(cfg, big)
    w3 = ref.init_params(cfg, big + 1)
    assert all(np.array_equal(w1[k], w2[k]) for k in w1)
    assert not np.array_equal(w1["embed_weight"], w3["embed_weight"])
    a = np.exp(np.asarray(w1["l4_mamba_A_log"]))
    assert a.min() >= 1 and a.max() <= 16
    step = np.log1p(np.exp(np.asarray(w1["l4_mamba_dt_bias"], np.float64)))
    assert step.min() >= 0.99e-3 and step.max() <= 0.101
    assert np.asarray(w1["l4_mamba_D"]).tolist() == [1.0] * 4
    assert float(np.asarray(w1["l4_mamba_norm_gamma"]).min()) == 1.0
    for k in ("l4_mamba_conv_weight", "l4_mamba_conv_bias"):
        assert 0.3 < float(np.abs(np.asarray(w1[k])).max()) <= 0.5
    assert 0.015 < float(np.asarray(w1["l5_attn_q_weight"]).std()) < 0.025


def _rehearse(*extra, trace=0, seed=2 ** 31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", "tiny_granite_resident", "--seed", str(seed),
         "--seconds", "1", "--trace", str(trace), "--manifest", MANIFEST,
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_rehearsal_of_the_cell_prints_the_contracts_line():
    r = _rehearse("--rehearse-cpu")
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(list(line)[:5]) == {"correct", "attempted", "failed",
                                   "metrics", "device"}
    assert line["correct"] is True, r.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"rehearsal.train_img_per_s",
                                    "rehearsal.setup_s"}
    cfg = _load(TINY)
    assert set(line["compared"]) == set(cfg["limits"])
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], name
    sps = line["metrics"]["rehearsal.train_img_per_s"]["value"]
    assert line["window"]["tokens_per_s"] == pytest.approx(
        sps * cfg["sequence_length"])
    assert line["window"]["counters"] == {}        # no expert layer
    tail = [l for l in r.stderr.splitlines() if l.startswith("compared ")]
    assert len(tail) == 12


def test_a_traced_rehearsal_reports_every_per_layer_metric_it_can():
    """``--trace 1`` as the driver calls it: the CPU's trace has no device
    plane, so the line carries no device metric, and it ends well."""
    r = _rehearse("--rehearse-cpu", trace=1)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert not any(k.endswith(("train_img_per_s", "setup_s"))
                   for k in line["metrics"])


def test_without_a_chip_the_cell_fails_and_prints_nothing():
    r = _rehearse()
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr
