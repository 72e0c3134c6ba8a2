"""Environment-variable compatibility map (reference: the ~80 documented
vars of docs/static_site/src/pages/api/faq/env_var.md, read via
dmlc::GetEnv at use-site; SURVEY §5.6).

Every load-bearing reference variable is listed with its disposition on
TPU so "is MXNET_X supported?" always has a definite answer:

  honored   — read by this tree at the cited site, same semantics;
  absorbed  — the responsibility moved into XLA/PjRt/jax; the variable is
              accepted but has nothing to configure (the jax-level control
              is named);
  n/a       — device-specific to CUDA/ROCm hardware, no TPU meaning.

`describe()` returns the table; `check(environ)` warns (once) about set
MXNET_* variables that are absorbed/n-a so silent expectation mismatches
surface in logs.
"""
from __future__ import annotations

import logging
import os
from typing import Dict, Tuple

__all__ = ["ENV_VARS", "describe", "check"]

# name -> (disposition, detail)
ENV_VARS: Dict[str, Tuple[str, str]] = {
    "MXNET_ENGINE_TYPE": (
        "honored", "NaiveEngine -> synchronous dispatch with per-op "
        "block_until_ready (ops/registry.py via engine.is_naive)"),
    "MXNET_USE_FUSION": (
        "honored", "gates the Pallas fused kernels (ops/pallas enabled())"),
    "MXNET_SUBGRAPH_BACKEND": (
        "honored", "partitions symbol graphs at bind time (subgraph.py)"),
    "MXNET_PROFILER_AUTOSTART": (
        "honored", "starts the profiler at import (profiler.py)"),
    "MXNET_SAFE_ACCUMULATION": (
        "honored", "always-on behavior: fp16 matmul/conv upcast to f32, "
        "bf16 accumulates f32 natively on the MXU (ops/nn.py _safe_acc); "
        "setting it to 0 has no effect (accuracy is never degraded)"),
    "MXNET_TEST_DEVICE": (
        "honored", "test_utils.default_context device selection"),
    "MXNET_USE_NATIVE_IO": (
        "honored", "0 disables the libmxio C++ decode/augment pipeline and "
        "falls back to the python iterator (io/native.py)"),
    "MXNET_EXEC_BULK_EXEC_TRAIN": (
        "absorbed", "whole graphs compile into ONE XLA executable; there "
        "is no per-segment bulking to tune"),
    "MXNET_EXEC_BULK_EXEC_INFERENCE": (
        "absorbed", "same as MXNET_EXEC_BULK_EXEC_TRAIN"),
    "MXNET_GPU_MEM_POOL_TYPE": (
        "absorbed", "PjRt owns the device allocator; use "
        "XLA_PYTHON_CLIENT_MEM_FRACTION / _PREALLOCATE"),
    "MXNET_GPU_MEM_POOL_RESERVE": (
        "absorbed", "see MXNET_GPU_MEM_POOL_TYPE"),
    "MXNET_GPU_WORKER_NTHREADS": (
        "absorbed", "no per-device worker threads: XLA streams are "
        "scheduled by PjRt"),
    "MXNET_CPU_WORKER_NTHREADS": (
        "absorbed", "host parallelism: preprocess_threads on the data "
        "iterators; XLA CPU uses its own thread pool"),
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": (
        "n/a", "algorithm selection is the XLA compiler's job; no "
        "cuDNN/MIOpen find-mode on TPU"),
    "MXNET_KVSTORE_USETREE": (
        "absorbed", "collective topology is XLA's ICI routing"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (
        "absorbed", "no PS key sharding: gradients allreduce whole over "
        "DCN (parallel/dist.py)"),
    "MXNET_ENABLE_GPU_P2P": ("n/a", "ICI is always peer-to-peer"),
    "MXNET_ENGINE_INFO": (
        "absorbed", "dependency logging: use JAX_LOG_COMPILES / "
        "jax.profiler traces"),
    "OMP_NUM_THREADS": (
        "honored", "read by XLA:CPU's Eigen pool and OpenCV (libmxio)"),
    "DMLC_ROLE": ("honored", "launcher contract (tools/launch.py)"),
    "DMLC_PS_ROOT_URI": (
        "honored", "rendezvous address (parallel/dist.py init_from_env)"),
    "DMLC_PS_ROOT_PORT": ("honored", "see DMLC_PS_ROOT_URI"),
    "DMLC_NUM_WORKER": ("honored", "process count (parallel/dist.py)"),
    "DMLC_WORKER_ID": ("honored", "process rank (parallel/dist.py)"),
    "DMLC_NUM_SERVER": (
        "absorbed", "no parameter-server role in the SPMD design"),
    "PS_VERBOSE": ("absorbed", "see DMLC_NUM_SERVER"),
    # fault-tolerance layer (docs/FAULT_TOLERANCE.md) — TPU-native vars
    # with no reference counterpart
    "MX_FAULT_SPEC": (
        "honored", "fault-injection harness: crash / crash-write / "
        "torn-write / slow-write specs with rank=/shard=/if-restart= "
        "qualifiers (fault.py, hooks in checkpoint.py; torn-write:shard=R "
        "corrupts one rank's shard file of a sharded checkpoint)"),
    "MX_CKPT_SHARDED": (
        "honored", "default AsyncCheckpointer(sharded=) — shard-granular "
        "(format 2) checkpoints: every rank writes only its own shards, "
        "zero collectives on the save path (checkpoint.py, "
        "docs/FAULT_TOLERANCE.md §Shard-granular checkpoints)"),
    "MX_CKPT_SHARD_WAIT_S": (
        "honored", "seconds the leader rank waits for peer shard commit "
        "markers before publishing a sharded checkpoint step (default 60; "
        "the preemption save_now path caps it at 2s) (checkpoint.py)"),
    "MX_RENDEZVOUS_TIMEOUT": (
        "honored", "seconds a (re)started rank retries "
        "jax.distributed.initialize with backoff (parallel/dist.py)"),
    "MX_RESTART_COUNT": (
        "honored", "gang incarnation index exported by tools/launch.py "
        "--max-restarts; read by fault.py if-restart= and resume logic"),
    "MX_ELASTIC": (
        "honored", "exported (=1) to workers by tools/launch.py --elastic "
        "so they know the supervisor may re-rendezvous them at a "
        "different world size (docs/FAULT_TOLERANCE.md §Elastic resize)"),
    "MX_PREV_NUM_PROCS": (
        "honored", "previous world size, exported by the --elastic "
        "supervisor on the FIRST incarnation after a gang resize; "
        "parallel/dist.py records the telemetry `resize` event off it "
        "(the segment marker trace_report/mem_report key on) and worker "
        "resume logic knows the restored checkpoint needs resharding"),
    # launcher contract (tools/launch.py exports; parallel/dist.py reads) —
    # TPU-native spellings of the DMLC_* variables above
    "MX_COORDINATOR": (
        "honored", "host:port of the jax.distributed coordination service "
        "(parallel/dist.py init_from_env)"),
    "MX_NUM_PROCS": (
        "honored", "gang process count (parallel/dist.py init_from_env)"),
    "MX_PROC_ID": (
        "honored", "this process's gang rank (parallel/dist.py, fault.py "
        "rank= qualifier, telemetry.py stream naming)"),
    "MX_FORCE_CPU": (
        "honored", "pin workers to the CPU jax backend (tools/launch.py "
        "--force-cpu exports it; parallel/dist.py honors it)"),
    # fused optimizer apply + bucketed allreduce (docs/PERFORMANCE.md)
    "MX_FUSED_UPDATE": (
        "honored", "0 disables the fused optimizer apply (one jitted "
        "update call for all dense params) and pins the per-param "
        "Updater path (optimizer/fused.py get_updater)"),
    "MX_ALLREDUCE_BUCKET_MB": (
        "honored", "gradient-allreduce bucket cap in MB (default 32): "
        "per-param pushpulls coalesce into flat buckets this large so "
        "one collective moves many grads; 0 disables bucketing "
        "(parallel/dist.py bucket_cap_bytes, kvstore.py push_bucketed)"),
    # async step pipeline (docs/PERFORMANCE.md §Async pipeline)
    "MX_ASYNC_INFLIGHT": (
        "honored", "bounded in-flight dispatch window: how many "
        "dispatched-but-unforced steps may be pending before dispatch "
        "blocks on the oldest (default 2; unset, DataParallelStep.step, "
        "whose handle pins one scalar, grows its window to at most 8 while "
        "a step ends under 1.25 s after its dispatch; 0 = synchronous, "
        "every step forced at dispatch).  Read per step call by "
        "parallel/async_loss.py; honored by DataParallelStep.step (lazy "
        "AsyncLoss), gluon Trainer.step and module.Module.update (step "
        "fences)"),
    # inference serving: continuous batching + paged KV cache
    # (docs/SERVING.md)
    "MX_SERVE_SLOTS": (
        "honored", "fixed decode-slot count of the serving engine — the "
        "in-flight batch width of the ONE compiled decode step (default "
        "8; serving/engine.py ServingEngine)"),
    "MX_SERVE_PAGE_SIZE": (
        "honored", "tokens per KV-cache page (default 16): the paged "
        "pool granularity requests allocate/free in "
        "(serving/paged_cache.py)"),
    "MX_SERVE_POOL_PAGES": (
        "honored", "total pages in the per-layer KV pools (default 0 = "
        "auto: slots * ceil(max_len/page_size) + 1, every slot can reach "
        "max_len); the engine raises when active requests exhaust it "
        "(serving/engine.py _ensure_pages)"),
    "MX_SERVE_QUEUE": (
        "honored", "request-queue bound (default 256; 0 = unbounded): a "
        "full queue rejects submits loudly — the serving backpressure "
        "surface (serving/scheduler.py)"),
    "MX_SERVE_STREAM_EVERY": (
        "honored", "decode steps per stream boundary (default 4): token "
        "readback, EOS eviction and mid-flight admission happen at this "
        "cadence — the host never blocks per token "
        "(serving/engine.py)"),
    "MX_SERVE_FLASH": (
        "honored", "paged-attention path: 'auto' (default) and 0 take the "
        "XLA gather path — the bitwise-parity path — on every platform; "
        "1 forces the Pallas ragged paged kernel (interpret-mode tests; "
        "on a TPU it does not lower through Mosaic and fails at compile "
        "time until ROADMAP A4 rewrites it page-blocked) "
        "(serving/engine.py _serve_fused)"),
    # serving front door (docs/SERVING.md §Front door / §Sampling /
    # §Prefix cache / §Speculative decoding) — everything defaults OFF
    # or to the greedy parity pin
    "MX_SERVE_SAMPLING": (
        "honored", "1 builds the engine with per-slot sampling state "
        "(temperature/top-k/top-p/RNG as device decode state; default 0 "
        "= greedy-only, trace and fingerprint unchanged); a "
        "temperature-0 request on a sampling engine is still BITWISE "
        "greedy (serving/engine.py)"),
    "MX_SERVE_SPEC_K": (
        "honored", "speculative decoding draft depth (default 0 = off): "
        "a host-side draft proposes up to K tokens and ONE compiled "
        "(\"verify\", K) dispatch checks them all — greedy output stays "
        "bitwise identical, sampling stays distribution-identical "
        "(serving/engine.py, serving/speculative.py)"),
    "MX_SERVE_PREFIX_CACHE": (
        "honored", "1 enables the copy-on-write prefix cache (default "
        "0): identical (source, forced-prefix) requests fork refcounted "
        "KV pages + reuse prefill rows instead of recomputing; entries "
        "are weight-generation-stamped and drop at a hot-swap flip "
        "(serving/engine.py, serving/scheduler.py PrefixCache)"),
    "MX_SERVE_PREFIX_ENTRIES": (
        "honored", "prefix-cache LRU bound (default 64 entries); under "
        "pool pressure entries also evict before any live request is "
        "preempted (serving/engine.py _ensure_pages)"),
    "MX_SERVE_PREFIX_CHUNK": (
        "honored", "tokens per (\"ingest\", K) teacher-forcing dispatch "
        "when a prefix misses the cache (default 8): one executable "
        "reused for any prefix length (serving/engine.py "
        "_ingest_prefix)"),
    "MX_SERVE_PORT": (
        "honored", "replica HTTP port: N binds N+rank (0/unset = "
        "ephemeral); the bound port is advertised via "
        "serve-port-<rank>.json under MX_TELEMETRY_DIR for router "
        "discovery (serving/router.py ReplicaServer)"),
    "MX_SERVE_ROUTER_PORT": (
        "honored", "router bind port (0/unset = ephemeral) for the "
        "multi-replica front door (serving/router.py Router)"),
    "MX_SERVE_HOST": (
        "honored", "bind host for replica servers and the router "
        "(default 127.0.0.1; 0.0.0.0 exposes them cross-host) "
        "(serving/router.py)"),
    "MX_SERVE_HEALTH_SEC": (
        "honored", "router health-poll cadence in seconds (default 2.0): "
        "each tick re-discovers portfiles and probes every replica's "
        "/healthz — dead replicas leave rotation, recovered/undrained "
        "ones rejoin (serving/router.py Router)"),
    "MX_SERVE_TEMPERATURE": (
        "honored", "fleet-wide default sampling temperature applied at "
        "the HTTP layer when a /generate body omits it (default 0 = "
        "greedy; never consulted inside the engine) "
        "(serving/router.py)"),
    "MX_SERVE_TOP_K": (
        "honored", "fleet-wide default top-k for /generate bodies that "
        "omit it (default 0 = off) (serving/router.py)"),
    "MX_SERVE_TOP_P": (
        "honored", "fleet-wide default nucleus top-p for /generate "
        "bodies that omit it (default 1.0 = off) (serving/router.py)"),
    # serving SLO counters (docs/SERVING.md §SLO telemetry; visible live
    # via the metrics endpoint and in the launch.py gang merge)
    "MX_SERVE_SLO_TTFT_MS": (
        "honored", "submission->first-token SLO in ms (queue wait "
        "INCLUDED — the user-visible TTFT; 0/unset = no SLO): a "
        "completed request whose TTFT exceeds it bumps "
        "mx_serve_slo_violations_total{stage=\"ttft\"} and records a "
        "serve_slo_violation event (telemetry.record_serve_request)"),
    "MX_SERVE_SLO_TPOT_MS": (
        "honored", "time-per-output-token SLO in ms (decode wall / "
        "tokens; 0/unset = no SLO): violations bump "
        "mx_serve_slo_violations_total{stage=\"tpot\"} "
        "(telemetry.record_serve_request)"),
    # fleet-wide request tracing (docs/OBSERVABILITY.md §Request tracing)
    "MX_RQTRACE": (
        "honored", "0/false/off disables serving request tracing end to "
        "end — no trace minting, no X-MX-Trace header, no /tracez "
        "bookkeeping (serving/router.py rqtrace_enabled; default on; "
        "the bench lever for the rqtrace_overhead <2% gate)"),
    "MX_RQTRACE_SAMPLE": (
        "honored", "head-based sampling rate in [0,1] for request "
        "traces (default 1.0): unsampled requests skip span emission "
        "on the hot path but are measured anyway — an error or TTFT "
        "SLO breach records their spans retroactively (late_sampled), "
        "so the tail is never lost (serving/router.py mint_trace)"),
    "MX_RQTRACE_TRACEZ_K": (
        "honored", "how many completed request trees the /tracez rings "
        "keep — the Router's fleet-level ring and each rank's "
        "telemetry.recent_requests ring (default 32) "
        "(serving/router.py + telemetry.py)"),
    "MX_RQTRACE_STRAGGLER_X": (
        "honored", "tools/serve_report.py labels a replica a straggler "
        "(and attributes its cause-less slow requests to it) when its "
        "mean decode ms/token exceeds this multiple of the fleet "
        "median (default 2.0)"),
    # live metrics endpoint (docs/OBSERVABILITY.md §Live metrics)
    "MX_METRICS_PORT": (
        "honored", "per-rank HTTP /metrics /healthz /statusz endpoint "
        "(metrics_server.py): unset/off = disabled (default); 0/auto = "
        "ephemeral port advertised via metrics-port-<R>.json next to "
        "the heartbeat (tools/launch.py --metrics-port discovers it for "
        "the merged gang /metrics); N>0 = bind N+rank"),
    "MX_METRICS_HOST": (
        "honored", "bind address of the live metrics endpoint (default "
        "127.0.0.1; set 0.0.0.0 to expose it to a cross-host scraper) "
        "(metrics_server.py)"),
    # runtime telemetry (docs/OBSERVABILITY.md)
    "MX_TELEMETRY_DIR": (
        "honored", "enables the telemetry recorder: one rank-<R>.jsonl "
        "event stream + heartbeat-<R>.json per rank under this directory "
        "(telemetry.py; polled by tools/launch.py)"),
    "MX_TELEMETRY_FLUSH_SEC": (
        "honored", "seconds between background flushes of buffered "
        "telemetry events to the JSONL sink (telemetry.py; default 1.0)"),
    "MX_HEARTBEAT_SEC": (
        "honored", "min seconds between heartbeat-file writes; the "
        "launch.py supervisor flags a rank stale after 5x this "
        "(telemetry.py + tools/launch.py; default 5.0)"),
    "MX_TELEMETRY_RETRACE_LIMIT": (
        "honored", "distinct jit signatures one executor may accumulate "
        "before the retrace-storm warning fires (telemetry.py; default 5)"),
    # gang-wide trace analysis (docs/OBSERVABILITY.md §Tracing & analysis)
    "MX_TELEMETRY_SPANS": (
        "honored", "0 disables span tracing (the nested "
        "span_begin/span_end events threaded through "
        "DataParallelStep.step, kvstore.push_bucketed, FusedUpdater, "
        "checkpoints, and the async ring: about ten events a step, five "
        "of them DataParallelStep.step's) while keeping step events and "
        "heartbeats; default on whenever the recorder is on or a "
        "jax.profiler session is live, where the spans also land in the "
        "trace as mx:<name> (telemetry.py spans_enabled)"),
    "MX_TRACE_EXPORT": (
        "honored", "default off; 1/true exports a merged Chrome/Perfetto "
        "trace.json (rank 0) plus per-rank OpenMetrics metrics-<R>.prom "
        "snapshots into MX_TELEMETRY_DIR at process exit, any other "
        "value names the target directory (telemetry.py "
        "_trace_export_target)"),
    "MX_TRACE_WINDOW": (
        "honored", "sliding window of newest steady steps tools/"
        "trace_report.py uses for the per-rank skew table (default 20)"),
    "MX_TRACE_STRAGGLER_PCT": (
        "honored", "trace_report.py flags a rank slower (step-wall rule) "
        "or idler (idle-gap rule) than the best rank by more than this "
        "percent (default 25)"),
    "MX_TRACE_HEARTBEAT_GAP_SEC": (
        "honored", "trace_report.py flags stretches where a rank's event "
        "stream went silent longer than this many seconds (default 30)"),
    # unified parallelism Plan + analytic auto-sharding planner
    # (docs/PERFORMANCE.md §Plan & planner)
    "MX_PLAN": (
        "honored", "parallelism-layout override for the analytic "
        "planner: 'auto' (default) picks the argmin of the cost model "
        "over every legal dp*tp*pp*sp factorization; 'dp'/'tp'/'pp'/"
        "'sp' pin the corresponding axis family; 'ring'/'ulysses' "
        "additionally select the SP attention mechanism "
        "(parallel/planner.py plan_for)"),
    # precision subsystem: graph-level AMP, traced loss scaling, int8
    # serving (docs/PRECISION.md)
    "MX_AMP": (
        "honored", "enables the graph-level AMP cast pass for compiled "
        "steps built without an explicit Plan.precision: bf16/bfloat16/1 "
        "or fp16/float16 (fp16 defaults dynamic loss scaling on); read "
        "ONCE at step construction and recorded on the Plan "
        "(precision/config.py PrecisionConfig.from_env)"),
    "MX_AMP_POLICY": (
        "honored", "inline-JSON override of the AMP op-class lists: "
        '{"low": [...], "widen": [...], "dtype": ...} — low-class ops '
        "compute in the AMP dtype, widen-class ops force f32 "
        "(precision/config.py AmpPolicy)"),
    "MX_LOSS_SCALE": (
        "honored", "traced dynamic loss scaling config under MX_AMP: "
        "'dynamic' (or 1), a fixed scale float (static), or 0/off; "
        "unset = on for fp16, off for bf16.  All scale/overflow/skip "
        "transitions run inside the compiled step as device values "
        "(precision/loss_scale.py)"),
    "MX_QUANTIZE": (
        "honored", "int8 (or 1) routes maybe_quantize_adapter to build a "
        "calibrated int8 serving adapter — Dense/Conv in the traced "
        "decode/prefill graphs lower onto the ops/quantization.py int8 "
        "primitives; the quant config joins the executable's "
        "fingerprint (precision/quantize.py)"),
    "MX_QUANT_CALIB": (
        "honored", "calibration mode for MX_QUANTIZE: naive (per-layer "
        "min/max, default) or entropy (KL-optimal threshold over a "
        "streaming histogram) (precision/quantize.py; calibrators from "
        "contrib/quantization.py)"),
    "MX_SERVE_INT4": (
        "honored", "int4 (or 1) routes maybe_int4_adapter to build a "
        "weight-only int4 serving adapter: Dense/Conv weights packed 2 "
        "per byte with group-wise f16 scales, dequantized in-trace "
        "inside the engine's compiled decode/prefill bodies — ~0.14x "
        "weight bytes, no calibration; rejected if MX_QUANTIZE is also "
        "set (precision/quantize.py)"),
    "MX_QUANT_GROUP": (
        "honored", "group size for MX_SERVE_INT4's group-wise int4 "
        "scales (default 32, must be even): one f16 scale per group of "
        "weights along the input dim — smaller groups trade bytes for "
        "accuracy (contrib/quantization._quantize_weight_int4_np)"),
    # pass pipeline (docs/PRECISION.md §Pass pipeline; passes/)
    "MX_PASSES": (
        "honored", "comma-separated per-pass toggles applied to every "
        "constructed pass pipeline: 'name' asserts the pass type is "
        "registered, '-name' disables that pass where present (the "
        "disabled pass contributes nothing to the trace or the pipeline "
        "fingerprint — bitwise the pass-less program); unknown names "
        "raise listing the registered set (passes/pipeline.py "
        "apply_env_toggles)"),
    "MX_PALLAS_FUSED": (
        "honored", "fused-kernel substitution pass (ops/pallas/"
        "registry.py): auto (default) substitutes registered Pallas "
        "kernels for their op-class only where they compile natively "
        "(TPU, MXNET_USE_FUSION on); 1 forces the pass (interpret-mode "
        "kernels — the CPU test path); 0 pins the stock op "
        "implementations (passes/builtin.fused_kernels_from_env)"),
    # memory & compile observability (docs/OBSERVABILITY.md §Memory)
    "MX_MEMWATCH": (
        "honored", "device-memory watchdog riding the telemetry "
        "recorder (memwatch.py): on by default whenever MX_TELEMETRY_DIR "
        "is set; 0 disables the whole subsystem — sampling, compile "
        "accounting (incl. the analysis retrace), and OOM post-mortems; "
        "'full' additionally captures compiled memory_analysis() "
        "temp/arg/output bytes per executable at the cost of one "
        "duplicate XLA compile each"),
    "MX_MEMWATCH_EVERY": (
        "honored", "memory-sample cadence: one live-array census + "
        "device memory_stats snapshot every N step-boundary "
        "observations (default 10; memwatch.on_step — checkpoint "
        "save/load always samples)"),
    "MX_MEMWATCH_LEAK_WINDOW": (
        "honored", "sliding-window length of the monotonic-growth leak "
        "detector (default 12 samples; memwatch.py sample(), also the "
        "default verdict window of tools/mem_report.py)"),
}

_warned = False


def describe() -> str:
    width = max(len(k) for k in ENV_VARS) + 2
    lines = [f"{'Variable':<{width}}{'Disposition':<12}Detail"]
    for name, (disp, detail) in sorted(ENV_VARS.items()):
        lines.append(f"{name:<{width}}{disp:<12}{detail}")
    return "\n".join(lines)


def check(environ=None) -> None:
    """Log (once) any set MXNET_* variable that has no effect here."""
    global _warned
    if _warned:
        return
    _warned = True
    environ = environ if environ is not None else os.environ
    for name, value in environ.items():
        if not name.startswith("MXNET_"):
            continue
        disp, detail = ENV_VARS.get(name, (None, None))
        if disp in ("absorbed", "n/a"):
            logging.getLogger("mxnet_tpu").info(
                "env var %s=%s has no effect on TPU (%s): %s",
                name, value, disp, detail)
        elif disp is None:
            logging.getLogger("mxnet_tpu").info(
                "env var %s is not recognized by mxnet_tpu (see "
                "mxnet_tpu.env_vars.describe())", name)
