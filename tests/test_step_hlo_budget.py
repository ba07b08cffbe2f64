"""Chip-free HLO regression budget for the benched fused ResNet-50 step.

The round-5 diagnosis found 766 bf16<->f32 converts (~2.75 Gelem per
direction) in the lowered train step — one f32 round-trip of every BN
activation, fwd and bwd. The bf16-native BatchNorm (ops/nn.py) eliminates
them at the trace level, so the pre-optimization StableHLO —
deterministic on CPU — is the regression surface: if a change
reintroduces per-tensor round-trips, the convert count jumps by hundreds
and the traffic through them by gigaelements, and this test fails
without ever needing the chip.

Budget: <= 230 bf16<->f32 converts (measured 219 at PR 26: 109 small
f32->bf16 casts of BatchNorm's per-channel coefficients, plus one
f32->bf16 in the forward and one bf16->f32 in the backward for each of
the 55 castable parameters), versus 766 before. The count was 111 while
the parameters were cast as ONE flat buffer (concatenate -> convert ->
slice -> reshape); the device trace showed that form costing 34.6 ms of
an 80 ms step in relayouts between the flat buffer and tiled weights
(PERF.md section 6, PR 26), while a per-parameter convert fuses into its
consumer. So the count that matters is the traffic
(`convert_gelems_between`), and `test_no_flat_parameter_buffer` counts
the relayout where the convert used to be counted.
"""
import numpy as np
import pytest

BUDGET = 230

# a parameter buffer worth a relayout: the smallest 3x3 weight of the
# last stage is 2.4 M elements, all castable parameters 25.5 M
FLAT_ELEMS = 1_000_000


@pytest.fixture(scope="module")
def step_stats(resnet_step_text):
    # the lowering itself is the session-scoped `resnet_step_text`
    # fixture (tests/conftest.py), shared with the MXL505 fusion-bytes
    # ratchet in test_lint_clean.py
    from mxnet_tpu import hlo_stats as hs
    return hs.analyze_stablehlo(resnet_step_text)


def test_convert_budget(step_stats):
    from mxnet_tpu import hlo_stats as hs
    n = hs.convert_count_between(step_stats, "f32", "bf16")
    assert n <= BUDGET, (
        "bf16<->f32 converts regressed: %d > budget %d (was 766 before "
        "the bf16-native BatchNorm; pairs=%r). A jump by ~100s means "
        "some path is round-tripping activations through f32 again."
        % (n, BUDGET, step_stats["convert_pairs"]))
    # and the traffic through them stays negligible (< 0.2 Gelem total
    # vs ~5.5 Gelem before)
    assert hs.convert_gelems_between(step_stats, "f32", "bf16") < 0.2


def test_convolutions_stay_bf16(step_stats):
    """Every convolution (fwd + both bwd passes) must hit the MXU in
    bf16 — an f32 conv means the dtype policy broke upstream of it."""
    assert set(step_stats["convolution"]) == {"bf16"}
    assert step_stats["convolution"]["bf16"] >= 150  # 53 convs x 3 passes


def test_no_layout_transposes(step_stats):
    """NCHW stays native: no transpose blowup from the policy change."""
    assert step_stats["transpose_count"] <= 6


def test_no_flat_parameter_buffer(resnet_step_text):
    """No parameter (or gradient) travels through a flat buffer: no
    `concatenate` yields a rank-1 tensor of a million elements or more,
    and no `reshape` takes one. On the chip every reshape between such a
    buffer and a tiled 4-D weight is a relayout through HBM (1.5 ms for
    f32[2359296] -> f32[512,512,3,3]); the grouped parameter cast had 10
    such concatenates and 20 such reshapes in this program."""
    import re
    rank1 = re.compile(r"tensor<(\d+)x[a-z]\w*>")
    bad = []
    for line in resnet_step_text.splitlines():
        operands, _, result = line.rpartition("->")
        if "stablehlo.concatenate" in line:
            side = result
        elif "stablehlo.reshape" in line:
            side = operands
        else:
            continue
        if any(int(n) >= FLAT_ELEMS for n in rank1.findall(side)):
            bad.append(line.strip())
    assert not bad, "%d flat-buffer ops, e.g. %s" % (len(bad), bad[0])
