"""Chip capability tables + MFU math, shared by bench and the tuner.

One home for the numbers that used to be copy-pasted between ``bench.py``
and ``tools/microbench_convs.py`` (bf16 peak FLOP/s per device kind, the
MFU formula) plus the HBM bandwidth table the kernel tuner's chip-free
cost model needs for its roofline term. Import-light on purpose: no jax,
so the mxlint CLI / analysis layer can use it without touching a backend.
"""
from __future__ import annotations

__all__ = ["PEAK_FLOPS", "HBM_GBPS", "ICI_GBPS", "peak_flops",
           "hbm_bytes_per_s", "interconnect_bytes_per_s", "mfu",
           "roofline_seconds", "recommend_request_seconds",
           "speculation_depth",
           "RESNET50_TRAIN_FLOPS_PER_IMG", "DEFAULT_DEVICE_KIND",
           "modelled_device_kind"]

# fwd+bwd ~= 3x fwd MACs * 2 flops/MAC (ResNet-50 @ 224: 4.089 GMACs fwd)
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.089e9

# The chip the repo is measured on. Estimators that must answer on the CPU
# backend (tests, chip-free tools) model this one, and say so by name.
DEFAULT_DEVICE_KIND = "v5e"

# bf16 peak FLOP/s per chip by device-kind substring (first match wins;
# 'v5p' must precede 'v5' so the pod chip doesn't fall into the lite row)
PEAK_FLOPS = [
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12),  # v5 lite (v5e)
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]

# HBM bandwidth (bytes/s) per chip by the same substring scheme — the
# denominator of the tuner's bytes-moved roofline term
HBM_GBPS = [
    ("v6", 1640e9), ("v5p", 2765e9), ("v5", 819e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
]


# Per-chip interconnect (ICI) bandwidth (bytes/s), same substring scheme.
# One link direction's worth — the number the DDP bucket sizer uses to
# amortize per-collective launch latency against transfer time.
ICI_GBPS = [
    ("v6", 3584e9 / 8), ("v5p", 4800e9 / 8), ("v5", 1600e9 / 8),
    ("v4", 2400e9 / 8), ("v3", 656e9 / 8), ("v2", 496e9 / 8),
]


def _lookup(table, device_kind):
    kind = (device_kind or "").lower()
    for sub, val in table:
        if sub in kind:
            return val
    raise KeyError("no peak numbers for device kind %r (known: %s)"
                   % (device_kind, ", ".join(sub for sub, _ in table)))


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s for a device kind string; KeyError if unknown."""
    return _lookup(PEAK_FLOPS, device_kind)


def hbm_bytes_per_s(device_kind: str) -> float:
    """HBM bandwidth in bytes/s for a device kind; KeyError if unknown."""
    return _lookup(HBM_GBPS, device_kind)


def interconnect_bytes_per_s(device_kind: str) -> float:
    """ICI bandwidth in bytes/s for a device kind; KeyError if unknown."""
    return _lookup(ICI_GBPS, device_kind)


def modelled_device_kind() -> str:
    """The device kind a cost estimate should model in this process: the
    attached accelerator's own, or, on the CPU backend, the chip the
    estimate is made for (:data:`DEFAULT_DEVICE_KIND`) — the CPU has no
    row in the tables and gets none."""
    import jax
    dev = jax.devices()[0]
    return DEFAULT_DEVICE_KIND if dev.platform == "cpu" else dev.device_kind


def mfu(flops_per_step: float, step_seconds: float,
        device_kind: str = DEFAULT_DEVICE_KIND) -> float:
    """Model FLOPs utilization: achieved FLOP/s over the chip's bf16 peak."""
    if step_seconds <= 0.0:
        return 0.0
    return (flops_per_step / step_seconds) / peak_flops(device_kind)


def roofline_seconds(flops: float, bytes_moved: float,
                     device_kind: str = DEFAULT_DEVICE_KIND) -> float:
    """Roofline lower bound on one program dispatch: the slower of the
    compute term (flops over bf16 peak) and the memory term (bytes over
    HBM bandwidth). This is the cost table the decode engine's
    admission/retry-after/drain estimates are driven from
    (serve/decode.py) — deliberately the same capability numbers the
    kernel tuner's chip-free cost model uses, not a new heuristic."""
    flops = max(0.0, float(flops))
    bytes_moved = max(0.0, float(bytes_moved))
    return max(flops / peak_flops(device_kind),
               bytes_moved / hbm_bytes_per_s(device_kind))


def speculation_depth(t_draft: float, t_verify, max_k: int = 8,
                      acceptance: float = 0.8) -> int:
    """Optimal speculation depth for a draft/verify decode pipeline.

    Pure math over two step costs — no spec, no jax — so it is
    property-testable chip-free: ``t_draft`` is one draft token-step's
    seconds, ``t_verify`` either a constant verifier cost or a callable
    ``width -> seconds`` (the verifier amortizes one weight read over
    ``k+1`` tokens, so its cost grows sub-linearly in width). Under a
    geometric acceptance model a step of depth k emits
    ``E[k] = (1 - a^(k+1)) / (1 - a)`` expected tokens and costs
    ``k * t_draft + t_verify(k+1)``; the returned k maximizes the rate,
    breaking exact ties toward the SHALLOWER depth (less speculative
    cache churn for the same throughput). Monotone by construction:
    cheaper drafts relative to the verifier never decrease k, and the
    result clamps to ``[1, max_k]`` (callers pass the speculative-window
    capacity of their artifact as ``max_k``)."""
    a = min(max(float(acceptance), 1e-3), 0.999)
    t_draft = max(float(t_draft), 1e-30)
    tv = t_verify if callable(t_verify) else (lambda _w, _c=float(t_verify): _c)
    best_k, best_rate = 1, 0.0
    for kk in range(1, max(1, int(max_k)) + 1):
        expected = (1.0 - a ** (kk + 1)) / (1.0 - a)
        rate = expected / (kk * t_draft + max(float(tv(kk + 1)), 1e-30))
        if rate > best_rate:
            best_k, best_rate = kk, rate
    return best_k


def recommend_request_seconds(gathers: int, dim: int, corpus_rows: int,
                              dtype_bytes: int = 4,
                              device_kind: str = DEFAULT_DEVICE_KIND
                              ) -> float:
    """Roofline floor for ONE recommend request, charged by its GATHER
    count — the unit the `/v1/recommend` admission queue bills in
    (serve/admission.py), because two requests in the same batch bucket
    can differ 100x in embedding rows touched. Two terms through the
    same capability tables everything else uses: the lookup's HBM
    traffic (each gathered row is a random-access ``dim`` stripe read)
    and the corpus scoring matmul (``2 * corpus_rows * dim`` flops per
    request)."""
    gathers = max(1, int(gathers))
    lookup_bytes = gathers * int(dim) * int(dtype_bytes)
    score_flops = 2.0 * int(corpus_rows) * int(dim)
    return roofline_seconds(score_flops, lookup_bytes, device_kind)
