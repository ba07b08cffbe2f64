"""The language-model cell (``kimi_linear_resident``): its configuration
against the published one and the cut's arithmetic, its token traffic, its
analytic FLOPs, a CPU rehearsal at a tiny configuration as the driver
calls it (the planted faults are in test_lm_correct_catches_faults.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

CONFIG = "benchmarks/configs/kimi_linear_a3b_ep32_l5_s8k_bf16.json"
TINY = "tests/benchmarks/configs/kimi_linear_tiny.json"
MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_lm.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def test_the_cut_holds_602_434_432_parameters():
    """From ``param_shapes``, to the unit: layer 1 (KDA, dense MLP)
    103,219,872; layers 2, 3, 5 (KDA, shared expert, router) 47,186,848
    each and layer 4 (MLA) 36,787,456, the selection bias among them; 8
    routed experts a layer 56,623,104; embedding and head over 20,480
    rows 94,371,840; the final norm 2,304. Published: 48 B in all, 3 B a
    token, 27 layers, 256 experts, 163,840 rows."""
    from references import kimi_linear as ref
    cfg = _load(CONFIG)
    shapes = ref.param_shapes(cfg)
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    assert sum(count.values()) == 602434432

    def layer(l, routed):
        return sum(n for k, n in count.items() if k.startswith("l%d_" % l)
                   and (("_moe_" in k and "router" not in k) == routed))
    assert layer(1, False) == 103219872
    assert [layer(l, False) for l in (2, 3, 5)] == [47186848] * 3
    assert layer(4, False) == 36787456
    assert [layer(l, True) for l in (2, 3, 4, 5)] == [8 * 7077888] * 4
    assert count["embed_weight"] + count["head_weight"] == 94371840
    assert cfg["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "parameters": cfg["published"]["parameters"]}
    # at 16 bytes a parameter (master, Adam's two moments, the gradient)
    assert round(sum(count.values()) * 16 / 1e9, 2) == 9.64


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    cfg = _load(CONFIG)
    man = _load("BENCHMARK.json")
    entry = {c["name"]: c for c in man["configs"]}[
        "kimi_linear_a3b_ep32_l5_s8k_bf16"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert cfg["source"] == entry["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(l) for l in f
                   if '"Kimi-Linear-48B-A3B-Instruct"' in l][0]
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert cfg[key] == value, key
    # the floors of a cut: a whole period after the dense layer, 8 routed
    # experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == len(cfg["layers"]) == 5
    assert cfg["layers"] == [1, 2, 3, 4, 5]
    kda = cfg["linear_attn_config"]["kda_layers"]
    assert [l in kda for l in cfg["layers"]] == [True, True, True, False,
                                                 True]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] == 8
    assert cfg["num_experts_published"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "32 chips share each layer" in cfg["deployment"]
    assert "1/64" in cfg["expert_load"] and "1/32" in cfg["expert_load"]
    assert set(cfg["assumed"]) >= {"optimizer", "forget gate",
                                   "selection bias", "initial weights",
                                   "batch"}
    assert "8,192" in cfg["sample"]
    assert (cfg["batch_size"], cfg["sequence_length"]) == (1, 8192)
    assert "2 x 8,192" in cfg["assumed"]["batch"]     # what ISSUE 27 asked


def test_tokens_and_weights_follow_the_seed_alone_also_above_2_to_31():
    from harness import token_traffic
    from references import kimi_linear as ref
    cfg = _load(TINY)
    mix = _load("benchmarks/traffic/resident_tokens.json")
    big = 2 ** 31 + 12345
    (d1, l1), = token_traffic.make_token_batches(mix, cfg, big)
    (d2, _), = token_traffic.make_token_batches(mix, cfg, big)
    (d3, _), = token_traffic.make_token_batches(mix, cfg, big + 1)
    (d4, _), = token_traffic.make_token_batches(mix, cfg, 12345)
    d1, l1 = np.asarray(d1), np.asarray(l1)
    assert np.array_equal(d1, d2) and not np.array_equal(d1, d3)
    assert not np.array_equal(d1, d4)        # the bits above 2**31 count
    assert d1.shape == (cfg["batch_size"], cfg["sequence_length"])
    assert d1.dtype == np.float32            # ids the way MXNet feeds them
    assert np.array_equal(d1[:, 1:], l1[:, :-1])      # label = the next id
    assert d1.min() >= 0 and d1.max() < cfg["vocab_size"]
    assert np.array_equal(d1, np.round(d1))
    # Zipf: the most frequent id is 0 and takes the largest share
    ids, counts = np.unique(d1, return_counts=True)
    assert ids[np.argmax(counts)] == 0
    w1, w2 = ref.init_params(cfg, big), ref.init_params(cfg, big)
    w3 = ref.init_params(cfg, big + 1)
    assert all(np.array_equal(w1[k], w2[k]) for k in w1)
    assert not np.array_equal(w1["head_weight"], w3["head_weight"])
    assert float(np.abs(np.asarray(w1["l2_moe_router_bias"])).max()) == 0


def test_flops_come_from_shapes_with_their_source():
    from harness import flops_lm
    cfg = _load(CONFIG)
    t = cfg["sequence_length"]
    met = flops_lm.matmul_params_per_token(cfg)
    # the weights a token meets: everything but the embedding's rows, the
    # norms, the gate's rates and the bias; of the routed experts 8 x 8/256
    assert met == pytest.approx(
        602434432 - 20480 * 2304 - 4 * 8 * 7077888 + 4 * 0.25 * 7077888
        - 11 * 2304 - 512 - 4 * (128 + 32 + 4096) - 4 * 256)
    attn = flops_lm.attention_flops_per_sequence(cfg, train=False)
    assert attn == t * t * (192 + 128) * 32
    assert flops_lm.attention_flops_per_sequence(cfg) == 3 * attn
    kda = flops_lm.kda_flops_per_sequence(cfg, train=False)
    assert kda == 4 * (t // 64) * 32 * (
        2 * 64 * 64 * 128 + 64 * 64 * 256 + 64 * 64 * 128
        + 6 * 64 * 128 * 128)
    total = flops_lm.train_flops_per_sample(cfg)
    assert total == 6 * met * t + 3 * attn + 3 * kda
    assert 0.02 < 3 * kda / total < 0.025     # the core: 2.3 % of the count
    assert "2001.08361" in flops_lm.__doc__
    assert flops_lm.kda_bytes_per_sequence(cfg) \
        == 3 * 4 * t * (5 * 4096 + 32) * 2


def _rehearse(*extra, trace=0, seed=2 ** 31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", "tiny_lm_resident", "--seed", str(seed), "--seconds",
         "1", "--trace", str(trace), "--manifest", MANIFEST, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_rehearsal_of_the_language_model_cell_prints_the_contracts_line():
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
    # samples a second: tokens a second over the tokens of a sequence
    sps = line["metrics"]["rehearsal.train_img_per_s"]["value"]
    assert line["window"]["tokens_per_s"] == pytest.approx(
        sps * cfg["sequence_length"])
    # the counters the step carries: a step's held assignments
    held = line["window"]["counters"]["moe/assignments_held"]
    assert 0 < held <= 2 * 72 * cfg["num_experts_per_token"]
    tail = [l for l in r.stderr.splitlines() if l.startswith("compared ")]
    assert len(tail) == 12


def test_without_a_chip_the_language_model_cell_fails_and_prints_nothing():
    r = _rehearse()
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_scope_readers_add_up_an_ops_time_under_its_instructions_scope():
    """A hand-made trace and program text: an op's self time goes to the
    scope its instruction was traced under, forward, backward or inside a
    loop; an op without a scope goes nowhere; without the text, or on a
    program without scopes, every reader returns nothing."""
    from harness import manifest, scopes
    hlo = '''
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/jit(main)/transpose(jvp(mx/kda))/mul"}
  %while.3 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jit(main)/checkpoint/rematted_computation/mx/kda/while"}
  ROOT %dot.2 = f32[8]{0} dot(%x, %y), metadata={op_name="jit(step)/jit(main)/mx/moe/experts/dot_general"}
  %custom-call.7 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/mx/mla/pallas_call"}
  %copy.9 = f32[8]{0} copy(%x)
'''
    assert scopes.instruction_scopes(hlo) == {
        "fusion.1": "mx/kda", "while.3": "mx/kda",
        "dot.2": "mx/moe/experts", "custom-call.7": "mx/mla"}
    mods = [["jit_step(1)", t, 900_000] for t in (0, 10 ** 6, 2 * 10 ** 6,
                                                   3 * 10 ** 6)]
    ops = []
    for t in (10 ** 6, 2 * 10 ** 6):
        ops += [["%while.3 = (s32[]) while(...)", t, 500_000],
                ["%fusion.1 = f32[8]{0} fusion(%a), kind=kLoop", t + 100_000,
                 200_000],
                ["%dot.2 = f32[8] dot(...)", t + 600_000, 100_000],
                ["%custom-call.7 = bf16[8] custom-call(...)", t + 700_000,
                 50_000],
                ["%copy.9 = f32[8] copy(%x)", t + 800_000, 50_000]]
    ctx = {"trace": {"devices": [{"name": "/device:TPU:0", "ops": ops,
                                  "modules": mods}], "host": []},
           "step_program": "^jit_step", "steps_per_program": 1,
           "hlo_text": hlo, "batch_size": 1,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "cfg": _load(CONFIG), "counters": {
               "moe/assignments_held": 4096.0, "moe/max_expert_tokens": 768.0}}
    read = lambda name: manifest.layer_reader(BENCH_DIR, name)(ctx)  # noqa
    assert read("kda_ms.train") == pytest.approx(0.5)     # the loop's whole
    assert read("moe_ms.train") == pytest.approx(0.1)
    assert read("mla_attn_ms.train") == pytest.approx(0.05)
    assert read("lm_head_ms.train") == 0.0
    assert read("moe_expert_tokens_max_over_mean.train") == 1.5
    from harness import flops_lm
    least = max(flops_lm.kda_flops_per_sequence(ctx["cfg"]) / 197e12,
                flops_lm.kda_bytes_per_sequence(ctx["cfg"]) / 819e9)
    assert read("kda_roofline_pct.train") == pytest.approx(
        100 * least / 0.5e-3)
    assert scopes.scope_top_ops(ctx)["mx/kda"][0][0] == "while"
    for gone in ({"hlo_text": None}, {"hlo_text": "%a.1 = f32[] add(%x)"}):
        bare = dict(ctx, **gone)
        bare.pop("_scope_classes", None)
        for name in ("kda_ms.train", "moe_ms.train", "mla_attn_ms.train",
                     "lm_head_ms.train", "kda_roofline_pct.train",
                     "mla_attn_roofline_pct.train"):
            assert manifest.layer_reader(BENCH_DIR, name)(bare) is None
    assert manifest.layer_reader(
        BENCH_DIR, "moe_expert_tokens_max_over_mean.train")(
            dict(ctx, counters={})) is None
