"""``harness/scope_cover.py`` and the six readers on it, over a hand-made
program text and reduced trace whose answers are known by construction:
four executions of the step program, so a steady span of one step (the
first and the last execution are the profiler's halves), 780 us of ops."""
import json
import os

import pytest

from benchpaths import BENCH_DIR, ROOT
from harness import flops_dense, manifest, scope_cover

READERS = ["opt_update_ms.train", "opt_update_hbm_pct.train",
           "dense_ms.train", "dense_roofline_pct.train",
           "scope_unattributed_pct.train",
           "moe_rows_visited_over_held.train"]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HLO = '''
HloModule jit_step, is_scheduled=true
%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  ROOT %multiply.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jit(main)/mx/opt/mul"}
}
%fused_computation.2 (p: bf16[8]) -> bf16[8] {
  %convolution.1 = bf16[8]{0} convolution(%p, %p), metadata={op_name="jit(step)/jit(main)/transpose(jvp(mx/op/FullyConnected))/dot_general"}
  %fusion.20 = bf16[8]{0} fusion(%convolution.1), kind=kLoop, calls=%fused_computation.1
  ROOT %subtract.1 = bf16[8]{0} subtract(%p, %fusion.20), metadata={op_name="jit(step)/jit(main)/mx/opt/sub"}
}
%fused_computation.3 (p: bf16[8]) -> bf16[8] {
  ROOT %multiply.3 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jit(main)/jvp(mx/op/RMSNorm)/mul"}
}
ENTRY %main.9 (a: bf16[8], w: f32[8]) -> (f32[8]) {
  %a = bf16[8]{0} parameter(0)
  %w = f32[8]{0:T(128)} parameter(1)
  %m = (f32[8]{0:T(128)}, f32[2,4]{1,0:T(2,128)}) parameter(2)
  %fusion.1 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%c1, metadata={op_name="jit(step)/jit(main)/jvp(mx/op/FullyConnected)/dot_general" stack_frame_id=7}
  %fusion.2 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step)/jit(main)/transpose(jvp(mx/op/FullyConnected))/dot_general"}
  %fusion.3 = bf16[8]{0} fusion(%a), kind=kOutput, calls=%c3, metadata={op_name="jit(step)/jit(main)/checkpoint/rematted_computation/mx/op/_contrib_SwiGLU/...rd,...hd->...rh/dot_general"}
  %while.4 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jit(main)/transpose(jvp(mx/op/_contrib_MoE))/mx/moe/experts/while"}
  %ragged-dot-none.5 = bf16[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %divide_subtract_fusion.6 = (f32[8]{0:T(128)}, bf16[8]{0}) fusion(%w, %a, /*index=2*/%m, %not.defined), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(main)/mx/opt/sub" stack_frame_id=3}, backend_config={"used":[{"size":"f32[99]"}]}
  %convert.7 = bf16[8]{0} convert(%w), metadata={op_name="jit(step)/jit(main)/jvp(mx/cast)/convert_element_type"}
  %fusion.8 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%c8, metadata={op_name="jit(step)/jit(main)/jvp(mx/op/FullyConnectedX)/add"}
  %fusion.9 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%c9, metadata={op_name="jit(step)/jit(main)/jvp(mx/attn/full)/mul"}
  %copy-done.10 = f32[8]{0} copy-done(%cs)
  %fusion.13 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3
  ROOT %tuple.11 = (f32[8]{0}) tuple(%divide_subtract_fusion.6)
}
'''
# (instruction as the trace names it, start inside a step, duration), ns:
# the while holds the grouped product, so its self time is 100 - 40
STEP = [("%fusion.1 = bf16[8]{0} fusion(%a), kind=kOutput", 0, 100_000),
        ("%fusion.2 = bf16[8]{0} fusion(%a), kind=kOutput", 100_000, 150_000),
        ("%fusion.3 = bf16[8]{0} fusion(%a), kind=kOutput", 250_000, 50_000),
        ("%while.4 = (s32[]) while(...)", 300_000, 100_000),
        ("%ragged-dot-none.5 = bf16[8] custom-call(...)", 330_000, 40_000),
        ("%divide_subtract_fusion.6 = f32[8]{0} fusion(%w), kind=kLoop",
         400_000, 200_000),
        ("%convert.7 = bf16[8]{0} convert(%w)", 600_000, 10_000),
        ("%fusion.8 = bf16[8]{0} fusion(%a), kind=kLoop", 610_000, 30_000),
        ("%fusion.9 = bf16[8]{0} fusion(%a), kind=kLoop", 640_000, 60_000),
        ("%copy-done.10 = f32[8]{0} copy-done(%cs)", 700_000, 50_000),
        ("%not-in-the-text.12 = f32[] add(...)", 750_000, 10_000),
        ("%fusion.13 = bf16[8]{0} fusion(%a), kind=kLoop", 760_000, 20_000)]
BUSY_MS = 0.78          # the union: 0-780 us, no gap
# the update that is an op of its own: two results (32 + 16 bytes) and the
# results of its operands (32, 16, a pair of 32 each; one the text lacks)
OPT_BYTES = 48 + 32 + 16 + 64
GAUGES = {"opt/param_bytes": 4e6, "opt/state_bytes": 8e6,
          "dense/flops_fwd": 1e9}
# a configuration's own count of the dense operations: 0.25 GFLOP a
# sequence forward, 4 sequences a step
FWD_FLOPS = 1e9


def _ctx(**over):
    starts = [k * 10 ** 6 for k in range(4)]
    mods = [["jit_step(1)", t, 900_000] for t in starts]
    ops = [[name, t + s, d] for t in starts[1:3] for name, s, d in STEP]
    ctx = {"trace": {"devices": [{"name": "/device:TPU:0", "ops": ops,
                                  "modules": mods}], "host": []},
           "step_program": "^jit_step", "steps_per_program": 1,
           "hlo_text": HLO, "batch_size": 4, "peaks": PEAKS,
           "cfg": {"device_scopes": ["mx/attn/full", "mx/moe/experts"]},
           "counters": {"moe/assignments_held": 8192.0,
                        "moe/rows_visited": 16384.0}}
    ctx.update(over)
    return ctx


def _read(name, ctx):
    return manifest.layer_reader(BENCH_DIR, name)(ctx)


@pytest.fixture
def counts(monkeypatch):
    """The program's gauges and the configuration's count, given."""
    monkeypatch.setattr(scope_cover, "gauge", GAUGES.get)
    monkeypatch.setattr(flops_dense, "forward_flops",
                        lambda cfg, batch: FWD_FLOPS / 4 * batch)


def test_times_add_up_to_the_union_and_every_instruction_goes_to_one_name():
    ctx = _ctx()
    table = scope_cover.rows(ctx)
    assert sum(row[3] for row in table) == pytest.approx(BUSY_MS)
    assert ctx["_scope_cover"] is table and scope_cover.rows(ctx) is table
    parts, with_opt, dense = scope_cover.partition(
        ctx, ctx["cfg"]["device_scopes"])
    assert sum(parts.values()) == pytest.approx(BUSY_MS)
    assert with_opt == {"mx/op/FullyConnected": pytest.approx(0.15)}
    assert dense == pytest.approx({"forward": 0.1, "backward": 0.15,
                                   "recomputed": 0.05})
    assert parts == pytest.approx({
        "mx/op/FullyConnected": 0.25, "mx/op/_contrib_SwiGLU": 0.05,
        "mx/op/_contrib_MoE": 0.06, "mx/opt": 0.2, "mx/cast": 0.01,
        "mx/op/FullyConnectedX": 0.03, "mx/attn/full": 0.06,
        "mx/op/RMSNorm": 0.02,          # no name of its own: by what it holds
        "(no mx/ scope)": 0.1})
    # a name is matched whole, and the longest of those asked for wins
    got = scope_cover.under(ctx, ("mx/op/FullyConnected", "mx/op/_contrib_MoE",
                                  "mx/moe/experts", "mx/op"))
    assert {k: v[0] for k, v in got.items()} == pytest.approx({
        "mx/op/FullyConnected": 0.25, "mx/op/_contrib_MoE": 0.06,
        "mx/moe/experts": 0.0, "mx/op": 0.08})
    assert scope_cover.under(ctx, ("mx/metric",)) is None
    assert scope_cover.under_ms(ctx, ("mx/metric",)) is None
    # a fusion is one op under one name, its own: the update fused into
    # the product that makes its gradient is the product's time, and a
    # fusion without a name of its own is under none
    assert scope_cover.under(ctx, ("mx/opt",)) \
        == {"mx/opt": (pytest.approx(0.2), OPT_BYTES)}
    assert scope_cover.under(ctx, ("mx/op/RMSNorm",)) is None


def test_an_instructions_bytes_are_its_results_and_its_operands_results():
    text = scope_cover.program(HLO)
    assert text["divide_subtract_fusion.6"] == (
        "jit(step)/jit(main)/mx/opt/sub", "jit(step)/jit(main)/mx/opt/mul",
        OPT_BYTES)
    # one array out, one in; a tuple; an operand the text does not define
    assert text["fusion.1"][2] == 16 + 16
    assert text["convert.7"][2] == 16 + 32
    assert text["m"][2] == 64 and text["while.4"][2] == 4
    assert text["fusion.2"][1].splitlines() == [
        "jit(step)/jit(main)/mx/opt/mul", "jit(step)/jit(main)/mx/opt/sub",
        "jit(step)/jit(main)/transpose(jvp(mx/op/FullyConnected))"
        "/dot_general"]
    assert scope_cover._array_bytes(
        "(pred[3]{0}, s32[]{:T(128)}, /*index=2*/f8e4m3fn[2,2], "
        "bf16[4,8]{1,0:T(8,128)(2,1)S(1)})") == 3 + 4 + 4 + 64


def test_an_instruction_without_a_scope_lands_in_the_unattributed_share(
        capsys, counts):
    ctx = _ctx()
    ms, classes, busy = scope_cover.unattributed(ctx)
    assert busy == pytest.approx(BUSY_MS)
    # the compiler's own name, an instruction with no metadata, an op of
    # the trace that the text does not hold
    assert classes == pytest.approx({"ragged-dot-none": 0.04,
                                     "copy-done": 0.05,
                                     "not-in-the-text": 0.01})
    assert ms == pytest.approx(0.1)
    assert _read("scope_unattributed_pct.train", ctx) \
        == pytest.approx(100 * 0.1 / BUSY_MS)
    line = capsys.readouterr().err
    assert line.startswith("scopes ms/step mx/op/FullyConnected=0.250 ")
    assert "mx/op/RMSNorm=0.020" in line
    assert "mx/opt=0.200" in line and "mx/cast=0.010" in line
    assert "| of which hold mx/opt inside: mx/op/FullyConnected=0.150 " \
        "| dense by pass: backward=0.150 forward=0.100 recomputed=0.050 " \
        "| mx/opt moves 0.000 GB of the 0.028 the program counts for " \
        "every update | dense forward TFLOP 0.001, the program's 0.001 " \
        "| no mx/ scope 0.100 of 0.780: copy-done=0.050 " \
        "ragged-dot-none=0.040 not-in-the-text=0.010" in line


def test_the_line_says_what_the_program_and_the_configuration_do_not(
        capsys, monkeypatch):
    # a parent's program has no gauge, a configuration may name no
    # reference: the line leaves a dash, the metric is read all the same
    monkeypatch.setattr(scope_cover, "gauge", {}.get)
    assert _read("scope_unattributed_pct.train", _ctx()) > 0
    assert "mx/opt moves 0.000 GB of the - the program counts for every " \
        "update | dense forward TFLOP -, the program's - |" \
        in capsys.readouterr().err


def test_the_six_readers_read_their_scopes_counts_and_counters(counts):
    ctx = _ctx()
    # the update that is an op of its own: the 0.15 ms of the product
    # another is fused into are the product's
    assert _read("opt_update_ms.train", ctx) == pytest.approx(0.2)
    # the same instruction's bytes over 819 GB/s, against its 0.2 ms
    assert _read("opt_update_hbm_pct.train", ctx) \
        == pytest.approx(100 * (OPT_BYTES / 819e9) / 0.2e-3)
    assert _read("dense_ms.train", ctx) == pytest.approx(0.3)
    assert _read("dense_roofline_pct.train", ctx) \
        == pytest.approx(100 * (3e9 / 197e12) / 0.3e-3)
    assert _read("moe_rows_visited_over_held.train", ctx) == 2.0


@pytest.mark.parametrize("gone", [
    {"hlo_text": None}, {"hlo_text": "%a.1 = f32[] add(%x)"},
    {"hlo_text": HLO.replace("mx/", "my/")},
    {"trace": {"devices": [], "host": []}}, {"counters": {}},
    {"counters": {"moe/assignments_held": 0.0, "moe/rows_visited": 0.0}}])
def test_each_reader_returns_nothing_where_its_input_is_missing(gone, counts):
    ctx = _ctx(**gone)
    for name in READERS:
        if ("counters" in gone) == name.startswith("moe_"):
            assert _read(name, ctx) is None, name


def test_shares_need_their_counts_and_the_peaks(monkeypatch, counts):
    for name in ("opt_update_hbm_pct.train", "dense_roofline_pct.train"):
        assert _read(name, _ctx(peaks=None)) is None      # a rehearsal
        assert _read(name, _ctx()) > 0
    # no metric is computed from a gauge of the program under test
    monkeypatch.setattr(scope_cover, "gauge", {}.get)
    for name in READERS:
        assert _read(name, _ctx()) is not None, name
    # a configuration that is no language model's has no dense count
    monkeypatch.undo()
    assert flops_dense.forward_flops({"num_layers": 18}, 4) is None
    assert _read("dense_roofline_pct.train", _ctx()) is None
    # an update whose shapes the text does not give
    bare = HLO.replace("(f32[8]{0:T(128)}, bf16[8]{0}) fusion(%w, %a, "
                       "/*index=2*/%m, %not.defined)", "() fusion()")
    assert _read("opt_update_ms.train", _ctx(hlo_text=bare)) \
        == pytest.approx(0.2)
    assert _read("opt_update_hbm_pct.train", _ctx(hlo_text=bare)) is None


def test_a_gauge_the_program_lacks_reads_nothing():
    # the parent's program has neither gauge: the registry holds no such
    # series, and asking does not make one
    from mxnet_tpu import telemetry
    assert scope_cover.gauge("opt/no_such_gauge") is None
    assert telemetry.default_registry().get("opt/no_such_gauge") is None
    telemetry.gauge("opt/test_scope_cover").set(3.0)
    assert scope_cover.gauge("opt/test_scope_cover") == 3.0


def test_the_mean_over_chips_and_steps():
    ctx = _ctx()
    one = ctx["trace"]["devices"][0]
    ctx["trace"]["devices"].append(
        {"name": "/device:TPU:1", "modules": one["modules"],
         "ops": [[n, s, d // 2 if n.startswith("%divide") else d]
                 for n, s, d in one["ops"]]})
    # 0.2 ms on one chip, 0.1 on the other; the instruction's bytes once
    assert scope_cover.under(ctx, ("mx/opt",)) \
        == {"mx/opt": (pytest.approx(0.15), OPT_BYTES)}


CONFIGS = {
    # file under the checkout: the weights a token meets in a dense
    # product, counted by hand from the published widths
    "benchmarks/configs/granite_4_0_h_micro_l10_s8k_bf16.json":
        # ten SwiGLUs of 8,192; nine Mamba in (8,512) and out projections;
        # one attention layer's q, o and k, v at 8 heads of 64
        10 * 3 * 8192 * 2048 + 9 * (8512 * 2048 + 2048 * 4096)
        + 2 * 2048 * 2048 + 2 * 512 * 2048,
    "benchmarks/configs/trinity_mini_l5_s8k_bf16.json":
        # five layers' q, gate, o (4,096) and k, v (512); one dense SwiGLU
        # of 6,144; four shared experts of 1,024
        5 * (3 * 4096 * 2048 + 2 * 512 * 2048) + 3 * 6144 * 2048
        + 4 * 3 * 1024 * 2048,
    "benchmarks/configs/kimi_linear_a3b_ep32_l5_s8k_bf16.json":
        # four KDA layers: q, k, v, o (4,096), two gates of rank 128, beta;
        # one MLA layer; one dense SwiGLU of 9,216; four shared experts
        4 * (4 * 4096 * 2304 + 2 * (128 * 2304 + 4096 * 128) + 32 * 2304)
        + (6144 * 2304 + 576 * 2304 + 8192 * 512 + 2304 * 4096)
        + 3 * 9216 * 2304 + 4 * 3 * 1024 * 2304,
}


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.mark.parametrize("rel", sorted(CONFIGS))
def test_the_dense_operations_are_counted_from_the_published_widths(rel):
    cfg = _load(rel)
    assert flops_dense.dense_weights(cfg) == CONFIGS[rel]
    assert flops_dense.forward_flops(cfg, 1) \
        == 2 * CONFIGS[rel] * cfg["sequence_length"]
    assert flops_dense.forward_flops(cfg, 3) \
        == 3 * flops_dense.forward_flops(cfg, 1)


@pytest.mark.parametrize("rel", [
    "tests/benchmarks/configs/granite_hybrid_tiny.json",
    "tests/benchmarks/configs/afmoe_tiny.json",
    "tests/benchmarks/configs/kimi_linear_tiny.json"])
def test_the_harness_counts_what_the_programs_gauge_counts(rel):
    """The count no metric reads, beside the one the metric reads: the
    program's ``dense/flops_fwd`` (every product ``FullyConnected`` and
    ``_contrib_SwiGLU`` trace, from their shapes) is the harness's from
    the configuration, for each of the three models."""
    import mxnet_tpu as mx
    from runners import train_lm_cfg, train_lm_fit
    cfg = _load(rel)
    if cfg["runner"] == "train_lm_cfg":
        sym = train_lm_cfg.build_symbol(cfg)
    else:
        from mxnet_tpu.models.kimi_linear import kimi_linear_symbol
        sym = kimi_linear_symbol(**train_lm_fit.symbol_kwargs(cfg))
    tokens = (cfg["batch_size"], cfg["sequence_length"])
    sym.simple_bind(mx.cpu(), grad_req="write", data=tokens,
                    softmax_label=tokens).forward(is_train=True)
    assert scope_cover.gauge("dense/flops_fwd") \
        == flops_dense.forward_flops(cfg, cfg["batch_size"]) > 0


def test_the_manifest_lists_the_six_after_all_that_was_there():
    """New entries go to the end of their list and nothing that was there
    moves: PR 33's two still follow PR 31's four, each with its one cell."""
    man = manifest.load(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in man["per_layer"]]
    assert names[-6:] == READERS
    assert names[-12:-6] == [
        "swa_attn_ms.train", "full_attn_ms.train",
        "swa_attn_roofline_pct.train", "full_attn_roofline_pct.train",
        "ssm_ms.train", "ssm_roofline_pct.train"]
    by = {m["name"]: m for m in man["per_layer"]}
    lm = ["kimi_linear_resident", "trinity_mini_resident",
          "granite_h_micro_resident"]
    for name in READERS:
        assert by[name]["moves"] == "train_img_per_s"
        assert by[name]["workloads"] == (
            lm[:2] if name.startswith("moe_") else lm), name
    assert by["ssm_ms.train"]["workloads"] == ["granite_h_micro_resident"]
