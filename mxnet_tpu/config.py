"""Unified typed config/flag registry.

The reference scatters ~60 runtime knobs as raw ``dmlc::GetEnv`` reads
documented only in docs/faq/env_var.md:35-232, plus per-object
``DMLC_DECLARE_PARAMETER`` kwargs. SURVEY.md §5 prescribes unifying them:
one registry where every flag has a name, type, default, and docstring, is
initialised from the environment once, and can be inspected or overridden
programmatically.

Usage::

    from mxnet_tpu import config
    config.flags.engine_type          # "ThreadedEngine" | "NaiveEngine"
    config.describe()                 # -> list of (name, env, value, doc)
    with config.override(enable_x64=True): ...
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional

__all__ = ["Flag", "flags", "register_flag", "describe", "override",
           "compute_dtype"]


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


class Flag(NamedTuple):
    name: str          # python attribute name
    env: str           # environment variable consulted at startup
    type: Callable     # parser applied to the env string
    default: Any
    doc: str


_REGISTRY: Dict[str, Flag] = {}
_LOCK = threading.Lock()


class _Flags:
    """Attribute-style access to resolved flag values."""

    def __init__(self):
        self._values: Dict[str, Any] = {}
        self._tls = threading.local()

    def _resolve(self, name: str) -> Any:
        flag = _REGISTRY[name]
        raw = os.environ.get(flag.env)
        if raw is None:
            return flag.default
        try:
            return flag.type(raw)
        except (TypeError, ValueError):
            return flag.default

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        overrides = getattr(self._tls, "overrides", None)
        if overrides and name in overrides:
            return overrides[name]
        if name not in self._values:
            if name not in _REGISTRY:
                raise AttributeError("no such flag: %r" % name)
            self._values[name] = self._resolve(name)
        return self._values[name]

    def set(self, name: str, value: Any) -> None:
        if name not in _REGISTRY:
            raise KeyError("no such flag: %r" % name)
        self._values[name] = value

    def reload(self, name: Optional[str] = None) -> None:
        """Re-read flag(s) from the environment."""
        if name is None:
            self._values.clear()
        else:
            self._values.pop(name, None)


flags = _Flags()


def register_flag(name: str, env: str, type: Callable, default: Any,
                  doc: str) -> Flag:
    with _LOCK:
        f = Flag(name, env, type, default, doc)
        _REGISTRY[name] = f
        return f


def describe() -> List[Dict[str, Any]]:
    """Introspect every flag (the env_var.md analog, but queryable)."""
    out = []
    for f in sorted(_REGISTRY.values()):
        out.append({"name": f.name, "env": f.env,
                    "value": getattr(flags, f.name),
                    "default": f.default, "doc": f.doc})
    return out


@contextlib.contextmanager
def override(**kwargs):
    """Thread-local temporary flag overrides."""
    tls = flags._tls
    prev = getattr(tls, "overrides", None)
    merged = dict(prev or {})
    for k in kwargs:
        if k not in _REGISTRY:
            raise KeyError("no such flag: %r" % k)
    merged.update(kwargs)
    tls.overrides = merged
    try:
        yield
    finally:
        tls.overrides = prev


# ---------------------------------------------------------------------------
# Dtype policy
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "f16": "float16", "fp16": "float16", "half": "float16",
    "float16": "float16",
}
_DTYPE_OFF = ("float32", "fp32", "f32", "off", "none", "no")


def compute_dtype(default=None):
    """Resolve the session dtype policy to a jax compute dtype or None.

    ``default`` is what the calling path would use under the ``auto``
    policy — e.g. the fused Module step passes ``jnp.bfloat16`` when the
    optimizer requested ``multi_precision``, the Gluon CachedOp path
    passes ``None`` (run in parameter dtype). An explicit policy
    (``MXNET_COMPUTE_DTYPE=bfloat16`` / ``float16``) wins over the
    default in every path; ``float32``/``off`` forcibly disables the
    downcast. Returns a jnp dtype (cast f32 compute to it) or None (no
    cast).
    """
    val = str(flags.compute_dtype).strip().lower()
    if val in ("", "auto"):
        return default
    if val in _DTYPE_OFF:
        return None
    name = _DTYPE_ALIASES.get(val)
    if name is None:
        raise ValueError(
            "MXNET_COMPUTE_DTYPE=%r not understood (expected auto, "
            "bfloat16, float16, or float32/off)" % val)
    import jax.numpy as jnp  # deferred: keep config importable without jax
    return {"bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Core flags (reference env vars they correspond to are noted in the doc).
# ---------------------------------------------------------------------------
register_flag("enable_x64", "MXNET_ENABLE_X64", _parse_bool, False,
              "Enable float64/int64 JAX dtypes. Off by default: the "
              "reference computes in float32 (mshadow default_real_t) and "
              "f64 is hostile to the TPU MXU.")
register_flag("subgraph_backend", "MXNET_SUBGRAPH_BACKEND", str, "",
              "Partition symbols with this subgraph backend's properties "
              "at bind time. Parity: src/operator/subgraph/.")
register_flag("engine_type", "MXNET_ENGINE_TYPE", str, "ThreadedEngine",
              "Execution engine: ThreadedEngine (async, default) or "
              "NaiveEngine (block after every op; debug). Parity: "
              "src/engine/engine.cc:33-41.")
register_flag("cpu_worker_nthreads", "MXNET_CPU_WORKER_NTHREADS", int, 4,
              "Host thread-pool width for IO decode/augment work "
              "(parity: MXNET_CPU_WORKER_NTHREADS).")
register_flag("exec_bulk_exec_inference", "MXNET_EXEC_BULK_EXEC_INFERENCE",
              _parse_bool, True,
              "Fuse whole inference graphs into one jitted module "
              "(parity: bulked engine segments).")
register_flag("exec_bulk_exec_train", "MXNET_EXEC_BULK_EXEC_TRAIN",
              _parse_bool, True,
              "Fuse forward+backward into one jitted module.")
register_flag("enforce_determinism", "MXNET_ENFORCE_DETERMINISM",
              _parse_bool, False,
              "Restrict nondeterminism (parity: env_var.md:226). XLA:TPU "
              "kernels are deterministic by default; this additionally "
              "refuses to auto-seed the global RNG from entropy "
              "(mxnet_tpu.random._chain).")
register_flag("backward_do_mirror", "MXNET_BACKWARD_DO_MIRROR",
              _parse_bool, False,
              "Gradient mirroring (parity: reference "
              "graph_executor.cc:260-283, docs/faq/env_var.md): trade "
              "FLOPs for activation memory. TPU-native mechanism: the "
              "differentiated graph is wrapped in jax.checkpoint, so the "
              "backward pass recomputes activations instead of keeping "
              "them resident in HBM (~2x batch headroom for ~1.3x "
              "forward FLOPs at the default policy).")
register_flag("mirror_policy", "MXNET_MIRROR_POLICY", str,
              "nothing_saveable",
              "jax.checkpoint_policies policy name used when "
              "MXNET_BACKWARD_DO_MIRROR=1: nothing_saveable (recompute "
              "everything — max memory savings), dots_saveable (keep "
              "matmul outputs), dots_with_no_batch_dims_saveable "
              "(transformer-style).")
register_flag("profiler_autostart", "MXNET_PROFILER_AUTOSTART",
              _parse_bool, False,
              "Start the profiler when mxnet_tpu.profiler is first "
              "imported (parity: env_var.md:179).")
register_flag("module_fused_step", "MXNET_MODULE_FUSED_STEP", _parse_bool,
              True,
              "Route Module training through the fused one-XLA-program "
              "step (fwd+bwd+reduce+optimizer update) when the kvstore is "
              "tpu_sync, or automatically on TPU with a local kvstore. "
              "Off: per-parameter eager updates (reference "
              "update_on_kvstore=False semantics).")
register_flag("trainer_fused_update", "MXNET_TRAINER_FUSED_UPDATE",
              _parse_bool, True,
              "Gluon Trainer.step applies all parameter updates in one "
              "jitted program (one dispatch/step) instead of one eager op "
              "per parameter. Numerically identical to the eager path.")
register_flag("compute_dtype", "MXNET_COMPUTE_DTYPE", str, "auto",
              "Session-wide mixed-precision compute dtype policy, "
              "consulted by the fused Module step, the Gluon "
              "hybridize/CachedOp path, and the fused Trainer update. "
              "'auto' (default): each path keeps its contextual default "
              "(the fused Module step casts to bfloat16 when the "
              "optimizer asked for multi_precision; Gluon blocks run in "
              "the parameter dtype). 'bfloat16'/'float16' (aliases bf16/"
              "fp16/f16/half): cast f32 activations and non-exempt f32 "
              "params to that dtype inside jitted programs — master "
              "weights, optimizer state, and normalization statistics "
              "stay f32. 'float32'/'off'/'none': never downcast, even "
              "where the contextual default would.")
register_flag("kernel_tier", "MXNET_KERNEL_TIER", str, "off",
              "Pallas kernel tier dispatch policy (mxnet_tpu/kernels/). "
              "'off' (default): every op runs its pure-JAX/XLA "
              "implementation. 'safe': dispatch to a hand-written Pallas "
              "kernel only where the eligibility guard passes AND the "
              "tuning cache (tools/kernel_tuning.json) holds a measured "
              "or model-ranked config for the (op, shape-bucket, dtype). "
              "'auto': dispatch wherever the guard passes, using the "
              "tuned config when cached and a heuristic default "
              "otherwise. Read at bind/trace time; ineligible call-sites "
              "always fall back to pure JAX. See docs/tuning.md.")
register_flag("kernel_interpret", "MXNET_KERNEL_INTERPRET", str, "auto",
              "Pallas execution mode for the kernel tier. 'auto' "
              "(default): Mosaic on the 'tpu' backend, interpreter on "
              "'cpu' (tests), an error on any other. '0'/'compiled': force "
              "Mosaic lowering even on a CPU host (used to EXPORT "
              "TPU-platform HLO chip-free; such a program cannot "
              "execute on the host). '1'/'interpret': force interpreter "
              "everywhere (debugging on-chip numerics).")
register_flag("kernel_tuning_cache", "MXNET_KERNEL_TUNING_CACHE", str, "",
              "Path of the kernel-tier tuning cache consulted at trace "
              "time. Empty (default): tools/kernel_tuning.json in the "
              "repo. The cache is versioned JSON written by "
              "tools/autotune.py; a schema/version mismatch invalidates "
              "it wholesale (dispatch falls back to heuristic configs).")
register_flag("engine_depth", "MXNET_ENGINE_DEPTH", int, 2,
              "Bounded in-flight dispatch depth for the async training "
              "loops (Module.fit, gluon.Trainer.step, SPMDTrainStep): up "
              "to this many dispatched steps may be pending on the device "
              "before the host blocks on the oldest. The TPU analog of "
              "the reference ThreadedEngine's bounded pending-op queue. "
              "1 = fully synchronous stepping; 0/negative = unbounded "
              "(host never throttles; device errors surface late).")
register_flag("device_metrics", "MXNET_DEVICE_METRICS", _parse_bool, True,
              "Fold supported eval metrics (acc/top_k/ce/nll/loss) into "
              "the fused train step as device-resident (sum, count) "
              "accumulators, transferring to host only at display/epoch "
              "boundaries. Off: per-batch host update (reference "
              "semantics, one device->host sync per batch).")
register_flag("ddp", "MXNET_DDP", _parse_bool, False,
              "Route dist_sync gradient exchange through the bucketed, "
              "backward-overlapped all-reduce path (parallel/ddp.py): "
              "gradients are partitioned into size-bounded dtype-"
              "homogeneous buckets and reduced with jax.lax.psum inside "
              "the traced step on a 'dp' mesh axis, letting XLA overlap "
              "collectives with remaining backward compute. Off: the "
              "ps-lite-style kvstore push/pull path (one host-mediated "
              "collective per tensor). tools/launch.py --ddp exports "
              "this to every worker.")
register_flag("ddp_axis", "MXNET_DDP_AXIS", str, "dp",
              "Mesh axis name the DDP reducer psums over. Only change "
              "when composing with a custom mesh whose data-parallel "
              "axis is not called 'dp'.")
register_flag("ddp_bucket_mb", "MXNET_DDP_BUCKET_MB", float, 0.0,
              "Gradient bucket size in MiB for the DDP all-reduce path. "
              "0 (default) = auto: sized from the perfmodel interconnect "
              "table so one bucket's transfer time amortizes collective "
              "launch overhead (clamped to [1, 64] MiB). Small values "
              "force many buckets (finer overlap, more launches); one "
              "huge bucket disables overlap entirely.")
register_flag("serve_buckets", "MXNET_SERVE_BUCKETS", str, "1,2,4,8,16,32",
              "Batch-size buckets the online serving runtime "
              "(mxnet_tpu.serve) pads coalesced request batches to, comma "
              "separated ascending. Each bucket lazily compiles one "
              "executable from the artifact (the TensorRT optimization-"
              "profile analog). Only consulted for dynamic-batch "
              "artifacts; fixed-batch artifacts serve at their frozen "
              "batch size.")
register_flag("serve_batch_timeout_ms", "MXNET_SERVE_BATCH_TIMEOUT_MS",
              float, 2.0,
              "Micro-batching window: after the first queued request, "
              "wait up to this long for more requests to coalesce before "
              "dispatching a (possibly padded) device batch. 0 = dispatch "
              "immediately (latency-optimal, throughput-poor).")
register_flag("serve_queue_depth", "MXNET_SERVE_QUEUE_DEPTH", int, 256,
              "Admission-control bound: max requests queued ahead of the "
              "micro-batcher. A submit beyond this is rejected "
              "immediately with a retry-after hint (HTTP 429) instead of "
              "queueing into a timeout storm. 0/negative = unbounded.")
register_flag("serve_timeout_ms", "MXNET_SERVE_TIMEOUT_MS", float, 1000.0,
              "Default per-request deadline. A request still queued when "
              "its deadline passes is expired (never dispatched); the "
              "caller gets DeadlineExceeded (HTTP 504). 0 = no deadline.")
register_flag("serve_sim_batch_s", "MXNET_SERVE_SIM_BATCH_S", float, 0.0,
              "Simulated device time per dispatched predict batch "
              "(seconds), slept inside the timed dispatch window so it "
              "shows up in exec_ms, throughput, and the heartbeat load "
              "signal exactly like real device occupancy. For drills "
              "and capacity rehearsals on hosts without an "
              "accelerator, where a CPU stand-in model finishes in "
              "microseconds: the sleep releases the GIL, so replica "
              "scale-out shows real latency recovery even on a "
              "single-core box. 0 (default) = off.")
register_flag("serve_cache_engines", "MXNET_SERVE_CACHE_ENGINES", int, 8,
              "LRU capacity of the per-bucket executable cache: at most "
              "this many bucket engines stay resident per server. "
              "0/negative = unbounded.")
register_flag("serve_warmup", "MXNET_SERVE_WARMUP", _parse_bool, True,
              "Run one zero-batch through every freshly compiled bucket "
              "engine before it serves traffic, so the first real request "
              "never pays lazy-initialization cost.")
register_flag("serve_drain_timeout_s", "MXNET_SERVE_DRAIN_S", float, 30.0,
              "Graceful-shutdown budget: how long Server.close(drain=True) "
              "waits for queued requests to finish before giving up.")
register_flag("serve_drain_tokens", "MXNET_SERVE_DRAIN_TOKENS", int, 32,
              "Bounded-drain token budget for continuous-batching decode: "
              "on graceful shutdown each active generation may produce at "
              "most this many MORE tokens before it is evicted with a "
              "resumable cursor (HTTP 429 + cursor). Without the bound a "
              "single long max_new_tokens request holds the drain hostage. "
              "0/negative = evict immediately at drain.")
register_flag("serve_decode_window", "MXNET_SERVE_DECODE_WINDOW", int, 16,
              "Decode telemetry window: publish decode/tokens_per_s, "
              "kv_page_occupancy, active_slots and eviction counts every "
              "this many decode steps — all from host-held scheduler "
              "state, zero extra device->host transfers.")
register_flag("embed_cache_rows", "MXNET_EMBED_CACHE_ROWS", int, 1024,
              "Device-resident hot-row capacity of the embedding cache "
              "(embed/cache.py): the served/trained table keeps this "
              "many rows on device and spills the cold tail to the host "
              "store. Size it above the per-step working set; the "
              "embed/cache_hit_rate gauge tells you when it is too "
              "small (docs/embeddings.md cache sizing).")
register_flag("embed_host_budget_mb", "MXNET_EMBED_HOST_BUDGET_MB",
              float, 0.0,
              "Host-memory budget (MiB) for the embedding spill store. "
              "0 (default) = unbounded. When set, the store raises "
              "instead of silently growing past it — the logical table "
              "may exceed this budget only as long as the TOUCHED cold "
              "tail stays inside it.")
register_flag("serve_max_gathers", "MXNET_SERVE_MAX_GATHERS", int, 65536,
              "Admission cap for the /v1/recommend queue in pending "
              "GATHER units (one unit = one embedding row fetched). "
              "Recommend requests are ragged — two requests in the same "
              "batch bucket can differ 100x in rows touched — so the "
              "queue bills and rejects on gather counts, not request "
              "counts (serve/admission.py + perfmodel).")
register_flag("quant_accuracy_budget", "MXNET_QUANT_ACCURACY_BUDGET",
              float, 0.005,
              "Per-bucket accuracy-delta budget for int8 serving: the "
              "bench serving leg (and any caller of the loadgen "
              "accuracy probe) fails the quantized engines when the "
              "top-1 delta vs the f32 reference exceeds this fraction "
              "(default 0.5%). Ratchet like the perf budgets: only "
              "tighten.")
register_flag("fleet_heartbeat_s", "MXNET_FLEET_HEARTBEAT_S", float, 1.0,
              "Replica -> router heartbeat interval (seconds) when "
              "serving with --register. Each beat carries readiness "
              "(liveness != readiness) and the perfmodel-derived load "
              "summary the router's least-loaded policy scores on.")
register_flag("fleet_heartbeat_timeout_s", "MXNET_FLEET_HEARTBEAT_TIMEOUT_S",
              float, 5.0,
              "Router-side liveness: a replica whose last heartbeat is "
              "older than this is marked dead and pulled from rotation "
              "(the HTTP twin of parallel/fault.py's stale heartbeat "
              "files). In-flight decode sessions on a dead replica are "
              "resumed on survivors via their eviction cursors.")
register_flag("fleet_hop_tokens", "MXNET_FLEET_HOP_TOKENS", int, 32,
              "Router generate-path hop size: the router forwards at "
              "most this many tokens per replica round-trip, so it "
              "always holds a recent resume cursor for transparent "
              "migration when the owning replica dies or drains. 0 = "
              "forward the whole budget in one hop (no mid-request "
              "migration checkpointing).")
register_flag("fleet_retry_limit", "MXNET_FLEET_RETRY_LIMIT", int, 3,
              "How many alternate replicas the router tries for one "
              "request after rejections/deaths before propagating the "
              "last error to the client.")
register_flag("fleet_proxy_timeout_s", "MXNET_FLEET_PROXY_TIMEOUT_S",
              float, 60.0,
              "Router-side socket timeout for one proxied replica call "
              "(requests with their own timeout_ms get that + margin "
              "instead). A hop that exceeds it counts as a replica "
              "failure and is retried on a survivor.")
register_flag("fleet_journal_sync_every", "MXNET_FLEET_JOURNAL_SYNC_EVERY",
              int, 8,
              "Fleet write-ahead journal group commit: fsync after this "
              "many appended records (epoch/registration records always "
              "sync immediately). Losing the unsynced tail only costs "
              "resumed sessions a few regenerated-bitwise tokens, so "
              "the hot hop path pays a buffered write, not a disk "
              "round-trip. 1 = fsync every record.")
register_flag("fleet_journal_compact_every",
              "MXNET_FLEET_JOURNAL_COMPACT_EVERY", int, 512,
              "Auto-compact the fleet journal (snapshot + truncate, "
              "checkpoint.py's temp+fsync+rename discipline) after this "
              "many records since the last compaction, bounding replay "
              "to O(snapshot) + one segment.")
register_flag("fleet_lease_interval_s", "MXNET_FLEET_LEASE_INTERVAL_S",
              float, 0.5,
              "How often the primary router refreshes its lease file in "
              "the journal directory. The standby calls the primary "
              "dead only after the lease *content* stops changing for "
              "MXNET_FLEET_LEASE_TIMEOUT_S of monotonic time.")
register_flag("fleet_lease_timeout_s", "MXNET_FLEET_LEASE_TIMEOUT_S",
              float, 3.0,
              "Standby promotion threshold: monotonic seconds without "
              "an observed lease change before the standby replays the "
              "journal, bumps the fencing epoch, and takes over the "
              "primary's address. Must comfortably exceed "
              "MXNET_FLEET_LEASE_INTERVAL_S.")
register_flag("fleet_standby_poll_s", "MXNET_FLEET_STANDBY_POLL_S",
              float, 0.2,
              "How often a --standby router tails the journal and "
              "checks the primary's lease. This is the CAP on the "
              "tailer's capped-exponential idle backoff: a standby "
              "polls immediately after applying records (catch-up "
              "burst) and decays toward this interval while idle.")
register_flag("fleet_journal_segment_mb", "MXNET_FLEET_JOURNAL_SEGMENT_MB",
              int, 64,
              "Rotate the fleet journal to a fresh wal-*.log segment "
              "once the live one exceeds this many MiB (rotation also "
              "happens at open and compaction). Bounds the unit of "
              "cross-host replication and the blast radius of a torn "
              "tail to one segment. 0 disables size-based rotation.")
register_flag("fleet_repl_poll_s", "MXNET_FLEET_REPL_POLL_S",
              float, 0.2,
              "How often a replicating standby (route.py --standby "
              "--replicate-from URL) pulls the primary's journal "
              "manifest. Also the cap on its catch-up/idle backoff; "
              "transient connection failures back off on the shared "
              "supervisor.backoff_delay jittered schedule.")
register_flag("fleet_repl_timeout_s", "MXNET_FLEET_REPL_TIMEOUT_S",
              float, 5.0,
              "Per-request HTTP timeout for journal replication "
              "fetches (manifest, segment bytes, snapshot bootstrap).")
register_flag("autoscale_interval_s", "MXNET_AUTOSCALE_INTERVAL_S",
              float, 2.0,
              "Autoscaler evaluation cadence: every tick it reads the "
              "fleet's federated demand signals (queue-seconds of work "
              "per replica from the perfmodel-derived heartbeats) and "
              "decides scale-up / scale-down / hold.")
register_flag("autoscale_min_replicas", "MXNET_AUTOSCALE_MIN_REPLICAS",
              int, 1,
              "Floor on autoscaler-managed replicas per model: drain "
              "decisions never take a model below this.")
register_flag("autoscale_max_replicas", "MXNET_AUTOSCALE_MAX_REPLICAS",
              int, 4,
              "Ceiling on autoscaler-managed replicas per model: "
              "launch decisions never take a model above this.")
register_flag("autoscale_high_watermark_s",
              "MXNET_AUTOSCALE_HIGH_WATERMARK_S", float, 1.0,
              "Scale-up pressure threshold: mean queued work per "
              "in-rotation replica (seconds, from heartbeat load_s) "
              "above this for autoscale_breach_rounds consecutive "
              "ticks is a scale-up candidate — still gated by the "
              "perfmodel break-even test against "
              "autoscale_startup_cost_s.")
register_flag("autoscale_low_watermark_s",
              "MXNET_AUTOSCALE_LOW_WATERMARK_S", float, 0.1,
              "Scale-down idleness threshold: mean queued work per "
              "in-rotation replica (seconds) below this for "
              "autoscale_breach_rounds consecutive ticks drains the "
              "least-loaded autoscaler-owned replica (graceful: "
              "in-flight finishes, decode sessions migrate bitwise).")
register_flag("autoscale_breach_rounds", "MXNET_AUTOSCALE_BREACH_ROUNDS",
              int, 2,
              "Hysteresis: how many consecutive ticks a watermark must "
              "stay breached before the autoscaler acts. Absorbs "
              "single-tick spikes without thrashing the fleet.")
register_flag("autoscale_cooldown_s", "MXNET_AUTOSCALE_COOLDOWN_S",
              float, 10.0,
              "Minimum wall time between autoscaler actions on one "
              "model (decisions during it journal as held:cooldown). "
              "Must exceed replica warmup so the previous action's "
              "effect is visible in the demand signal before the next "
              "one.")
register_flag("autoscale_startup_cost_s", "MXNET_AUTOSCALE_STARTUP_COST_S",
              float, 2.0,
              "Amortized cost of launching one replica (process spawn "
              "+ artifact load + engine warmup). Scale-up is worth it "
              "only when the projected per-replica queue-drain gain "
              "beats this break-even — the perfmodel-derived guard "
              "against scaling into a spike that ends before the new "
              "replica is warm.")
register_flag("autoscale_page_high_occupancy",
              "MXNET_AUTOSCALE_PAGE_HIGH_OCCUPANCY", float, 0.85,
              "Decode memory-pressure threshold: a fleet whose worst "
              "replica reports kv_page_occupancy above this fraction "
              "counts as a high-watermark breach even when "
              "queue-seconds look calm — long contexts exhaust the KV "
              "page pool well before load_s moves, and scale-out must "
              "land before admission starts stalling on pages.")
register_flag("autoscale_deadline_headroom",
              "MXNET_AUTOSCALE_DEADLINE_HEADROOM", float, 1.0,
              "Tail-latency pressure threshold: worst replica "
              "p99_ms / deadline_ms (request timeout) above this "
              "ratio counts as a high-watermark breach — p99 at the "
              "deadline means the tail is about to turn into expiries, "
              "a signal mean queue pressure cannot see.")
register_flag("telemetry_port", "MXNET_TELEMETRY_PORT", int, 0,
              "Training-side telemetry HTTP listener port "
              "(mxnet_tpu.telemetry.exporters): serves /metrics "
              "(Prometheus text exposition of the run-wide registry), "
              "/metrics.json and /healthz from a daemon thread. 0 "
              "(default) = no listener. Serving replicas don't need "
              "this: serve/http.py exposes the same exposition on its "
              "existing /metrics route.")
register_flag("telemetry_dir", "MXNET_TELEMETRY_DIR", str, "",
              "Directory for crash-surviving telemetry artifacts: the "
              "flight-recorder postmortem JSON written on SIGTERM / "
              "unhandled exception / faultinject kill "
              "(postmortem_rank<R>_pid<P>.json) and, unless overridden "
              "by the dedicated flags, the telemetry JSONL stream and "
              "kernel timing log. Empty (default): postmortem dumping "
              "and the derived paths are disabled — no surprise files, "
              "no altered SIGTERM disposition.")
register_flag("telemetry_jsonl", "MXNET_TELEMETRY_JSONL", str, "",
              "Path of the per-window telemetry JSONL snapshot stream "
              "(one registry snapshot per 16-step telemetry window, "
              "appended — the machine-readable sibling of the chrome "
              "trace). Empty: $MXNET_TELEMETRY_DIR/telemetry.jsonl when "
              "the dir is set, else disabled.")
register_flag("telemetry_flight_len", "MXNET_TELEMETRY_FLIGHT_LEN", int,
              256,
              "Ring-buffer capacity of the flight recorder: how many "
              "recent step-window records survive into a postmortem "
              "dump.")
register_flag("kernel_timings", "MXNET_KERNEL_TIMINGS", str, "",
              "Path of the measured kernel-timing JSONL log the on-chip "
              "tuner appends to (mxnet_tpu/tune/timings.py) and "
              "`tools/autotune.py --recalibrate` fits the chip-free "
              "cost model from. Empty: "
              "$MXNET_TELEMETRY_DIR/kernel_timings.jsonl when the dir "
              "is set, else recording is off.")
register_flag("kernel_cost_model", "MXNET_KERNEL_COST_MODEL", str, "",
              "Path of a recalibrated cost-model weights JSON (written "
              "by `tools/autotune.py --recalibrate --save-model`). When "
              "set and valid, tune.cost_model.default_model() ranks "
              "with these weights instead of the shipped hand-rounded "
              "ones. Empty (default): shipped weights.")
register_flag("data_decode_threads", "MXNET_DATA_DECODE_THREADS", int, 0,
              "Decode/augment worker threads for StreamingDataIter "
              "(mxnet_tpu/data/record_stream.py). 0 (default): fall back "
              "to cpu_worker_nthreads, the same pool width "
              "ImageRecordIter uses.")
register_flag("test_device", "MXNET_TEST_DEVICE", str, "cpu",
              "Device type test_utils.default_context() returns (cpu|tpu) "
              "— the reference's env-switchable default_context (:53).")
register_flag("test_platform", "MXNET_TEST_PLATFORM", str, "cpu",
              "Platform the test suite pins JAX to at session start "
              "(cpu|tpu); read by tests/conftest.py.")
