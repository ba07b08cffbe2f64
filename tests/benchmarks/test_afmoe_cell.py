"""The window / full attention cell (``trinity_mini_resident``): its
configuration against the published one and the cut's arithmetic, its
analytic FLOPs and bytes, the configuration-driven runner's arguments, its
scope readers, and a CPU rehearsal at a tiny configuration as the driver
calls it (the planted faults are in test_afmoe_correct_catches_faults.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchpaths import BENCH_DIR, ROOT

NAME = "trinity_mini_l5_s8k_bf16"
CONFIG = "benchmarks/configs/%s.json" % NAME
TINY = "tests/benchmarks/configs/afmoe_tiny.json"
MANIFEST = os.path.join("tests", "benchmarks", "rehearsal_afmoe.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def test_the_cut_holds_705_474_304_parameters():
    """From ``param_shapes``, to the unit. A layer's attention 27,263,232
    (q, o and the gate 8,388,608 each, k and v 1,048,576 each, two head
    norms of 128) and four norms 8,192; the dense MLP 37,748,736; the
    shared expert 6,291,456, the router 262,144 + 128 of bias, 16 routed
    experts 100,663,296; embedding and head over 25,024 rows 102,498,304;
    the final norm 2,048. Published: 32 layers, 128 experts, 200,192
    rows."""
    from references import afmoe as ref
    cfg = _load(CONFIG)
    count = {k: int(np.prod(s)) for k, s in ref.param_shapes(cfg).items()}
    assert sum(count.values()) == 705474304

    def layer(l, pick=lambda k: True):
        return sum(n for k, n in count.items()
                   if k.startswith("l%d_" % l) and pick(k))
    for l in cfg["layers"]:
        assert layer(l, lambda k: "_attn_" in k and "norm_gamma" not in k
                     or "_attn_q_norm" in k or "_attn_k_norm" in k) \
            == 27263232
        assert layer(l, lambda k: k.split("_", 1)[1] in (
            "attn_norm_gamma", "post_attn_norm_gamma", "ffn_norm_gamma",
            "post_ffn_norm_gamma")) == 8192
    assert layer(1) == 65020160
    assert layer(1, lambda k: "_mlp_" in k) == 37748736
    assert [layer(l) for l in (2, 3, 4, 5)] == [134488448] * 4
    assert layer(2, lambda k: "_shared_" in k) == 6291456
    assert layer(2, lambda k: "router" in k) == 262144 + 128
    assert layer(2, lambda k: "_moe_" in k and "router" not in k) \
        == 16 * 6291456 == 100663296
    assert count["embed_weight"] + count["head_weight"] == 102498304
    assert count["final_norm_gamma"] == 2048
    assert cfg["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 200192,
        "parameters": cfg["published"]["parameters"]}
    # at 16 bytes a parameter (master, Adam's two moments, the gradient)
    assert round(sum(count.values()) * 16 / 1e9, 2) == 11.29


def test_every_published_key_is_unchanged_and_the_cut_is_stated():
    cfg = _load(CONFIG)
    man = _load("BENCHMARK.json")
    entry = {c["name"]: c for c in man["configs"]}[NAME]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert cfg["source"] == entry["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(l) for l in f if '"Trinity-Mini"' in l][0]
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert cfg[key] == value, key
    # the floors of a cut: a whole 3 : 1 period of expert layers after a
    # dense one, 16 routed experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == len(cfg["layers"]) == 5
    assert cfg["layers"] == [1, 2, 3, 4, 5] and cfg["num_dense_layers"] == 2
    assert [cfg["layer_types"][l] for l in cfg["layers"]] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] == 16
    assert cfg["num_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert "512 tokens an expert" in cfg["expert_load"] \
        and "1/8" in cfg["expert_load"]
    assert set(cfg["assumed"]) >= {"optimizer", "selection bias",
                                   "attention", "initial weights", "batch"}
    assert "8,192" in cfg["sample"]
    assert (cfg["batch_size"], cfg["sequence_length"]) == (1, 8192)
    cell = {w["name"]: w for w in man["workloads"]}["trinity_mini_resident"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "resident_tokens", 1)
    new = [m["name"] for m in man["per_layer"]
           if m.get("workloads") == ["trinity_mini_resident"]]
    assert new == ["swa_attn_ms.train", "full_attn_ms.train",
                   "swa_attn_roofline_pct.train",
                   "full_attn_roofline_pct.train"]


def test_the_runner_hands_the_builder_the_configurations_own_keys():
    """Every argument of the builder comes from the file, under the name
    ``symbol.keys`` or ``symbol.renamed`` gives; the symbol's variables are
    the reference's."""
    import inspect
    from mxnet_tpu.models.afmoe import afmoe_symbol
    from references import afmoe as ref
    from runners import train_lm_cfg
    for rel in (CONFIG, TINY):
        cfg = _load(rel)
        kw = train_lm_cfg.symbol_kwargs(cfg)
        assert set(kw) == set(inspect.signature(afmoe_symbol).parameters)
        assert kw["num_experts"] == cfg["num_experts_published"]
        assert kw["vocab_rows"] == cfg["vocab_size"]
        assert kw["layers"] == tuple(cfg["layers"])
    sym = train_lm_cfg.build_symbol(_load(TINY))
    assert set(sym.list_arguments()) - {"data", "softmax_label"} \
        == set(ref.param_shapes(_load(TINY)))


def test_flops_and_bytes_come_from_shapes_with_their_source():
    from harness import flops_afmoe
    cfg = _load(CONFIG)
    t = cfg["sequence_length"]
    # pairs a head: every earlier key, or the 2,048 nearest
    assert flops_afmoe.visible_pairs(t) == 33558528
    assert flops_afmoe.visible_pairs(t, 2048) == 14681088 \
        == sum(min(i + 1, 2048) for i in range(t))
    assert flops_afmoe.visible_pairs(100, 2048) == 5050
    met = flops_afmoe.matmul_params_per_token(cfg)
    # everything but the embedding's rows, the norms and the bias; of the
    # routed experts 8 of 128: one of the 16 held
    assert met == pytest.approx(
        705474304 - 25024 * 2048 - 4 * 15 * 6291456 - 21 * 2048
        - 5 * 256 - 4 * 128)
    assert met == pytest.approx(276.692992e6)
    full = flops_afmoe.attention_flops_per_sequence(cfg, False, train=False)
    swa = flops_afmoe.attention_flops_per_sequence(cfg, True, train=False)
    assert full == 33558528 * 4 * 128 * 32
    assert swa == 4 * 14681088 * 4 * 128 * 32
    total = flops_afmoe.train_flops_per_sample(cfg)
    assert total == 6 * met * t + 3 * full + 3 * swa
    assert 0.24 < 3 * (full + swa) / total < 0.26     # a quarter of 18.1 T
    assert "2001.08361" in flops_afmoe.__doc__
    # a layer, bfloat16: q read and o written forward, q, o and do read
    # and dq written backward, at 32 heads; k and v read twice and their
    # gradients written, at 4
    wide, narrow = 32 * 128, 4 * 128
    assert flops_afmoe.attention_bytes_per_sequence(cfg, False) \
        == t * 2 * (6 * wide + 6 * narrow)
    assert flops_afmoe.attention_bytes_per_sequence(cfg, True) \
        == 4 * t * 2 * (6 * wide + 6 * narrow)


def test_scope_readers_read_the_scopes_the_configuration_lists():
    """A hand-made trace and program text: an op's self time goes to the
    longest listed scope its instruction was traced under; a program
    without the text or without the scopes, and a configuration that lists
    none, read nothing."""
    from harness import flops_afmoe, manifest, scopes_of
    hlo = '''
  %custom-call.1 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/checkpoint/mx/attn/window/pallas_call"}
  %while.3 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jit(main)/transpose(jvp(mx/attn/window))/while"}
  %custom-call.7 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/checkpoint/mx/attn/full/pallas_call"}
  %fusion.2 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/jit(main)/checkpoint/mx/rope/mul"}
  ROOT %dot.2 = f32[8]{0} dot(%x, %y), metadata={op_name="jit(step)/jit(main)/mx/moe/experts/dot_general"}
  %copy.9 = f32[8]{0} copy(%x)
'''
    mods = [["jit_step(1)", t, 900_000] for t in (0, 10 ** 6, 2 * 10 ** 6,
                                                   3 * 10 ** 6)]
    ops = []
    for t in (10 ** 6, 2 * 10 ** 6):
        ops += [["%custom-call.1 = bf16[8] custom-call(...)", t, 100_000],
                ["%while.3 = (s32[]) while(...)", t + 100_000, 300_000],
                ["%custom-call.7 = bf16[8] custom-call(...)", t + 400_000,
                 200_000],
                ["%fusion.2 = bf16[8]{0} fusion(%a), kind=kLoop", t + 600_000,
                 20_000],
                ["%dot.2 = f32[8] dot(...)", t + 700_000, 100_000],
                ["%copy.9 = f32[8] copy(%x)", t + 800_000, 50_000]]
    cfg = _load(CONFIG)
    ctx = {"trace": {"devices": [{"name": "/device:TPU:0", "ops": ops,
                                  "modules": mods}], "host": []},
           "step_program": "^jit_step", "steps_per_program": 1,
           "hlo_text": hlo, "batch_size": 1, "cfg": cfg,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: manifest.layer_reader(BENCH_DIR, name)(ctx)  # noqa
    assert read("swa_attn_ms.train") == pytest.approx(0.4)
    assert read("full_attn_ms.train") == pytest.approx(0.2)
    ms = scopes_of.scope_ms(ctx)
    assert ms["mx/rope"] == pytest.approx(0.02)
    assert ms["mx/moe/experts"] == pytest.approx(0.1)
    assert ms["mx/moe/route"] == 0 and ms["mx/lm_head"] == 0
    assert scopes_of.scope_top_ops(ctx)["mx/attn/window"][0][0] == "while"
    for windowed, name in ((True, "swa_attn_roofline_pct.train"),
                           (False, "full_attn_roofline_pct.train")):
        least = max(
            flops_afmoe.attention_flops_per_sequence(cfg, windowed) / 197e12,
            flops_afmoe.attention_bytes_per_sequence(cfg, windowed) / 819e9)
        assert read(name) == pytest.approx(
            100 * least / (0.4e-3 if windowed else 0.2e-3))
    names = ("swa_attn_ms.train", "full_attn_ms.train",
             "swa_attn_roofline_pct.train", "full_attn_roofline_pct.train")
    kimi = _load("benchmarks/configs/kimi_linear_a3b_ep32_l5_s8k_bf16.json")
    for gone in ({"hlo_text": None}, {"hlo_text": "%a.1 = f32[] add(%x)"},
                 {"cfg": kimi}, {"cfg": None}):
        bare = dict(ctx, **gone)
        bare.pop("_scope_classes_of", None)
        for name in names:
            assert manifest.layer_reader(BENCH_DIR, name)(bare) is None


def test_weights_follow_the_seed_alone_also_above_2_to_31():
    from references import afmoe as ref
    cfg = _load(TINY)
    big = 2 ** 31 + 12345
    w1, w2 = ref.init_params(cfg, big), ref.init_params(cfg, big)
    w3 = ref.init_params(cfg, big + 1)
    assert all(np.array_equal(w1[k], w2[k]) for k in w1)
    assert not np.array_equal(w1["head_weight"], w3["head_weight"])
    # the selection bias is the configuration's, whatever the seed
    assert np.asarray(w1["l2_moe_router_bias"]).tolist() \
        == np.asarray(w3["l5_moe_router_bias"]).tolist() \
        == [4, 0, 2, 2, 0, 0, 0, 0]
    assert not np.any(np.asarray(ref.init_params(
        {k: v for k, v in cfg.items() if k != "selection_bias"},
        big)["l2_moe_router_bias"]))       # without the key: zero
    assert float(np.asarray(w1["l3_attn_q_norm_gamma"]).min()) == 1.0
    assert 0.015 < float(np.asarray(w1["l1_attn_gate_weight"]).std()) < 0.025


@pytest.mark.parametrize("rel", [CONFIG, TINY])
def test_the_selection_bias_holds_this_ranks_share_whatever_the_scores(rel):
    """Every token chooses the experts ``always`` and, for the choices
    left, the best of the held by score: the assignments this rank holds
    are the same on every seed and at every step, also where a score has
    run into 0 or 1. In the committed configuration that is the even
    share, one choice of eight, beside one expert of each other rank."""
    import jax
    from references import afmoe as ref
    cfg = _load(rel)
    d, how = ref.dims(cfg), cfg["selection_bias"]
    (lo, hi), k = d["held"], d["top_k"]
    bias = np.asarray(ref.selection_bias(cfg), np.float32)
    assert bias.shape == (d["router"],)
    assert sorted(np.flatnonzero(bias == 4)) == sorted(how["always"])
    assert np.flatnonzero(bias == 2).tolist() == list(range(lo, hi))
    assert set(bias.tolist()) == {0, 2, 4}
    if rel == CONFIG:
        assert k - len(how["always"]) == k * (hi - lo) // d["router"] == 1
        assert sorted(e // (hi - lo) for e in how["always"]) \
            == list(range(1, d["router"] // (hi - lo)))
    rng = np.random.RandomState(0)
    s = rng.rand(4096, d["router"]).astype(np.float32)
    s[:1024] = rng.randint(0, 2, (1024, d["router"]))   # saturated
    s[1024:2048] = np.round(s[1024:2048], 1)              # ties
    _, chosen = jax.lax.top_k(s + bias, k)
    chosen = np.asarray(chosen)
    held = (chosen >= lo) & (chosen < hi)
    assert (held.sum(-1) == k - len(how["always"])).all()
    assert all((chosen == e).any(-1).all() for e in how["always"])
    best = lo + np.argmax(s[:, lo:hi], -1)
    if k - len(how["always"]) == 1:
        assert (chosen[held] == best).all()


@pytest.mark.parametrize("always", [[2], [0, 1], [8], [0, 0]])
def test_a_selection_bias_that_could_move_the_share_is_refused(always):
    """An expert held here among ``always``, as many of them as choices,
    one that is no expert, one named twice."""
    from references import afmoe as ref
    cfg = dict(_load(TINY), selection_bias={"always": always})
    with pytest.raises(ValueError):
        ref.selection_bias(cfg)


def _rehearse(*extra, trace=0, seed=2 ** 31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", "tiny_afmoe_resident", "--seed", str(seed),
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
    # selection_bias: one of a token's two choices is held, every step
    assert line["window"]["counters"]["moe/assignments_held"] == 2 * 300
    tail = [l for l in r.stderr.splitlines() if l.startswith("compared ")]
    assert len(tail) == 12


def test_without_a_chip_the_cell_fails_and_prints_nothing():
    r = _rehearse()
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_a_program_without_the_builder_fails_at_once_with_its_name():
    """What the cell does on a commit that has no ``models.afmoe``: an
    exit code, a line that says which builder is missing, no traceback to
    read and no result."""
    from runners import train_lm_cfg
    cfg = dict(_load(TINY))
    cfg["symbol"] = dict(cfg["symbol"],
                         builder="mxnet_tpu.models.not_there.some_symbol")
    with pytest.raises(SystemExit) as e:
        train_lm_cfg.build_symbol(cfg)
    assert e.value.code == 1
