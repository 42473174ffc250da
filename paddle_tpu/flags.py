"""Global flag registry.

TPU-native analogue of the reference's gflags layer
(/root/reference/paddle/fluid/platform/flags.cc:33-359 and
pybind/global_value_getter_setter.cc): a typed, env-overridable registry of
runtime flags, settable from Python via ``set_flags``/``get_flags``.

Unlike the reference (where flags are C++ globals exported through pybind),
flags here live in one Python-side registry and are consulted by the runtime
pieces (executor, allocator-stats, nan checks, determinism) at trace/run time.
Environment variables of the form ``FLAGS_<name>`` override defaults at import.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional


@dataclass
class _FlagSpec:
    name: str
    default: Any
    type: type
    help: str
    on_change: Optional[Callable[[Any], None]] = None


class FlagRegistry:
    """Thread-safe typed flag registry with env-var overrides."""

    def __init__(self) -> None:
        self._specs: Dict[str, _FlagSpec] = {}
        self._values: Dict[str, Any] = {}
        self._lock = threading.RLock()

    def define(self, name: str, default: Any, help: str = "",
               on_change: Optional[Callable[[Any], None]] = None) -> None:
        with self._lock:
            if name in self._specs:
                raise ValueError(f"flag '{name}' already defined")
            spec = _FlagSpec(name, default, type(default), help, on_change)
            self._specs[name] = spec
            value = default
            env = os.environ.get("FLAGS_" + name)
            if env is not None:
                value = self._parse(spec, env)
            self._values[name] = value

    @staticmethod
    def _parse(spec: _FlagSpec, text: str) -> Any:
        if spec.type is bool:
            return text.strip().lower() in ("1", "true", "yes", "on")
        if spec.type is int:
            return int(text)
        if spec.type is float:
            return float(text)
        return text

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            spec = self._specs.get(name)
            if spec is None:
                raise KeyError(f"unknown flag '{name}'")
            if spec.type is not type(value):
                if spec.type is float and isinstance(value, int):
                    value = float(value)
                elif isinstance(value, str):
                    value = self._parse(spec, value)
                else:
                    raise TypeError(
                        f"flag '{name}' expects {spec.type.__name__}, got "
                        f"{type(value).__name__}")
            self._values[name] = value
            if spec.on_change is not None:
                spec.on_change(value)

    def get(self, name: str) -> Any:
        with self._lock:
            if name not in self._values:
                raise KeyError(f"unknown flag '{name}'")
            return self._values[name]

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._specs)

    def describe(self, name: str) -> str:
        with self._lock:
            return self._specs[name].help


GLOBAL_FLAGS = FlagRegistry()


def define_flag(name: str, default: Any, help: str = "",
                on_change: Optional[Callable[[Any], None]] = None) -> None:
    GLOBAL_FLAGS.define(name, default, help, on_change)


def set_flags(flags: Dict[str, Any]) -> None:
    """Set multiple flags; mirrors ``fluid.set_flags``."""
    for k, v in flags.items():
        GLOBAL_FLAGS.set(k, v)


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    return {n: GLOBAL_FLAGS.get(n) for n in names}


# ---------------------------------------------------------------------------
# Core runtime flags (analogues of reference flags.cc where meaningful on TPU)
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "After each jitted step, scan outputs for NaN/Inf "
            "(ref: FLAGS_check_nan_inf, platform/flags.cc:44).")
define_flag("benchmark", False,
            "Block on each step for accurate timing "
            "(ref: FLAGS_benchmark, framework/operator.cc:1022).")
define_flag("deterministic", False,
            "Force deterministic XLA lowering choices "
            "(ref: FLAGS_cudnn_deterministic, platform/flags.cc:98).")
define_flag("allocator_strategy", "xla",
            "Host staging allocator strategy (xla | arena). 'arena' "
            "routes DeviceLoader feeds through core.arena."
            "HostStagingArena: recycled page-aligned host blocks, zero "
            "steady-state mallocs (ref: allocator_strategy flags.cc, "
            "auto_growth_best_fit_allocator.cc). Accelerator backends "
            "only — the CPU client zero-copy-aliases aligned arrays.")
define_flag("eager_delete_tensor_gb", 0.0,
            "Retained-buffer GC threshold for host staging arena.")
define_flag("matmul_precision", "default",
            "jax matmul precision: default | float32 | tensorfloat32 | "
            "highest. bf16 MXU passes use 'default'.")
define_flag("use_pallas_kernels", True,
            "Route hot ops (attention, layer_norm, the routed experts' "
            "grouped matmul) through Pallas "
            "kernels when on TPU (master switch; per-kernel flags "
            "below). [structural] The switch itself only enables "
            "routing; each routed kernel carries its own evidence "
            "class on its own flag.")
define_flag("optimizer_moment_dtype", "float32",
            "Storage dtype for Adam-family first/second moments "
            "(float32 | bfloat16). [assumed — conservative] fp32 is "
            "the safe default; the bf16 win is a hypothesis no chip "
            "run has tested. bfloat16 halves "
            "optimizer-state HBM "
            "traffic (~1.3 GB/step on BERT-base); update math still "
            "runs in fp32 and the fp32 master weights are unaffected, "
            "so the only loss is ~0.4% relative rounding on stored "
            "m/v. Read at optimizer init. (ref capability: "
            "multi_precision / master-weight family.)")
define_flag("fused_softmax_xent", False,
            "Fuse BERT's masked-LM head (hidden->vocab projection) "
            "with its softmax cross-entropy into one Pallas loss-"
            "region kernel (kernels/fused_softmax_xent.py): online "
            "log-sum-exp over vocab chunks, so the [B, T, V] logits "
            "tensor never exists in HBM in either direction "
            "(custom_vjp backward recomputes chunks and fuses dlogits "
            "into dh/dW/db). [assumed — conservative] Off: it has "
            "never run on a chip.")
define_flag("use_pallas_layer_norm", True,
            "Use the Pallas layer_norm kernel (subject to the master "
            "switch). [measured] r5 chip A/B at the best BERT config "
            "(bert_b8_spl8_xlaln pair): Pallas LN 129.3k vs XLA LN "
            "128.9k tok/s (+0.3%, within noise) — kept on; the XLA "
            "fallback is one flag away.")
define_flag("flash_attention_min_seq", 8192,
            "Key-sequence length at or above which EVAL attention "
            "routes to the Pallas flash kernel. [measured+structural] "
            "r5 chip sweep (d128 fwd): flash/XLA = 0.86/0.93/1.01/1.00 "
            "at seq 1k/2k/4k/8k — speed parity from 4k, no win below, "
            "so the eval gate stays at the MEMORY bound (the XLA "
            "path's [T, T] fp32 scores are HBM-scale at 8k+: B1 H12 "
            "T16k fp32 ≈ 12.9 GB on a 16 GB v5e). Narrow head dims "
            "(d%8) keep a separate fixed 8192 eval floor "
            "(kernels._NARROW_HEAD_EVAL_MIN_SEQ) this flag does not "
            "move. Ring/Ulysses long-context paths use the kernel "
            "directly, not via this gate.")
define_flag("flash_attention_min_seq_train", 512,
            "Training-mode flash gate (0 = use "
            "flash_attention_min_seq). [measured] r5 chip sweep (d64 "
            "fwd+bwd with dropout, 512 tiles): flash beats XLA "
            "1.18x/1.58x/2.08x at seq 1k/2k/4k, and the IN-MODEL "
            "bert_b8_flash512 A/B settled seq 512 itself: 127.2k vs "
            "121.1k tok/s (+5.1%) on the full BERT b8 train step — the "
            "gate sits at the lowest measured win. The memory argument "
            "(XLA backward re-materializes [B, H, T, T] fp32 probs, "
            "~6.4 GB at B8 T4096) independently caps the XLA path.")
define_flag("flash_block_q", 0,
            "Flash kernel query-tile size (rows of the online-softmax "
            "block). 0 = the kernel module's built-in BLOCK_Q (512, "
            "measured r5). Sweep lever for the flash_train capture "
            "stages; clamped to the sequence length.")
define_flag("flash_block_k", 0,
            "Flash kernel key-tile size (columns scanned per "
            "fori_loop iteration). 0 = built-in BLOCK_K (512, measured "
            "r5); sweep "
            "lever, clamped like flash_block_q.")
define_flag("transformer_remat", False,
            "Rematerialize each TransformerEncoder layer in the "
            "backward (jax.checkpoint). [assumed — conservative] Off "
            "until the bert_b{32,64}_remat stages measure it: "
            "~1/3 more FLOPs for O(layers) "
            "less activation HBM. A/B lever for large-batch training "
            "where XLA otherwise spills. (ref capability: "
            "recompute/checkpointing strategy, fleet "
            "DistributedStrategy.recompute.)")
define_flag("resnet_block_remat", False,
            "Rematerialize each residual block in the backward "
            "(jax.checkpoint per block, BN stats threaded explicitly "
            "through the boundary). [assumed — conservative] Off "
            "pending the resnet_remat chip A/B: the r5 profile says "
            "the step is HBM-bound with conv fusions at HBM peak, so "
            "recompute FLOPs are cheap relative to the activation "
            "round-trips they remove — the opposite regime from BERT, "
            "where remat measured -29%.")
define_flag("resnet_space_to_depth_stem", False,
            "Rewrite the ResNet 7x7/s2 stem conv as an exact 4x4/s1 "
            "conv over space-to-depth-folded 12-channel input (the "
            "MLPerf TPU trick: 3 input channels waste MXU lanes). NHWC "
            "only; checkpoints unchanged. [assumed — conservative] Off "
            "pending the resnet_nhwc_b128_s2d chip A/B.")
define_flag("batch_norm_single_pass", True,
            "Compute training-mode BatchNorm statistics as "
            "E[x^2]-E[x]^2 with fp32 accumulation (sibling reductions "
            "XLA fuses into ONE read of the activation) instead of "
            "jnp.mean followed by the data-dependent jnp.var pass. "
            "[measured] r5 chip A/B (resnet_bn1pass vs "
            "resnet_nhwc_b128_perleaf, identical pinning): 2455.9 vs "
            "2262.7 img/s (+8.5%) — the first ResNet lever to move "
            "beyond noise, exactly where the profile pointed (BN-stat "
            "loop fusions ~1/5 of the step). Accuracy: fp32 "
            "accumulation + clamp bound the E[x^2]-E[x]^2 "
            "cancellation; BN inputs are ~unit-scale.")
define_flag("use_fast_rng", True,
            "On TPU, use the hardware RngBitGenerator PRNG ('rbg') for "
            "jax.random keys instead of threefry: initialisation and "
            "small draws. Dropout masks draw nothing from it (they hash "
            "a seed folded from the key where the mask is used), so its "
            "worth to a train step is [assumed], not measured in this "
            "tree; streams are still splittable/foldable but not "
            "bit-identical to threefry.")
define_flag("profile_dir", "",
            "If set, write xplane profiler traces under this directory.")
define_flag("log_level", 0, "Framework VLOG level (0 = off).")
define_flag("selected_devices", "",
            "Comma-separated device ordinals to use (ref: "
            "FLAGS_selected_gpus).")
define_flag("io_threadpool_size", 4,
            "Worker threads for the host data pipeline "
            "(ref: FLAGS_io_threadpool_size).")
define_flag("fuse_parameter_groups_size", 32 * 1024 * 1024,
            "Gradient coalescing bucket size in bytes for DP fusion "
            "(ref: FLAGS_fuse_parameter_groups_size).")


def _enable_metrics_changed(value) -> None:
    # keep the observability module's cached fast-path bool in sync
    # (lazy import: observability imports this module)
    from .observability import metrics as _obs_metrics
    was = _obs_metrics.enabled()
    _obs_metrics.set_enabled(bool(value))
    if was and not value:
        # the step timeline is taken down and says what it saw
        from .observability import tracer as _obs_tracer
        _obs_tracer.tracer().timeline_off()


define_flag("enable_metrics", False,
            "Master switch for the observability subsystem: metrics "
            "registry writes, host span tracing, and per-call jit "
            "cache-hit accounting. Off = near-free early return on "
            "every instrumented hot path (trace-time-only accounting "
            "like recompile counts stays on — it costs nothing per "
            "step). (ref capability: monitor.h stats + "
            "Enable/DisableProfiler.)",
            on_change=_enable_metrics_changed)
define_flag("metrics_port", 0,
            "TCP port for the live observability HTTP exporter "
            "(observability/server.py). 0 (default) = bind an "
            "EPHEMERAL port — the chosen port is published via the "
            "observability_server_port gauge and one log line, so "
            "parallel runs never collide; a negative value disables "
            "the exporter. When FLAGS_enable_metrics is on, "
            "hapi.Model.fit and inference.Server start (idempotently "
            "share) a daemon-threaded stdlib HTTP server exposing "
            "/metrics (Prometheus text), /healthz (device liveness + "
            "train heartbeat), /varz (full JSON snapshot incl. "
            "program cards), /trace?ms=N (on-demand chrome-trace "
            "window), /goodput (wall-time ledger) and /flight (event "
            "ring buffer). (ref capability: monitor/stat export "
            "surface.)")
define_flag("program_analytics", True,
            "Harvest compiled-program analytics (XLA cost_analysis + "
            "memory_analysis) into per-function program cards on every "
            "jit trace while FLAGS_enable_metrics is on. The harvest "
            "runs lowered.compile() a second time per traced signature "
            "— a trace-time-only cost, zero steady-state overhead — "
            "and feeds the achieved-FLOPs gauge on /metrics. Off skips "
            "harvesting entirely.")
define_flag("anomaly_spike_factor", 10.0,
            "Anomaly sentinel spike threshold: a watched series (loss, "
            "grad norm) whose value exceeds this factor times its "
            "running EWMA (after a short warmup) is counted in "
            "anomalies_total and logged to events.jsonl under "
            "FLAGS_trace_dir. NaN/Inf are always flagged. 0 disables "
            "spike detection (NaN/Inf detection stays on).")
define_flag("straggler_factor", 0.0,
            "Multi-host straggler threshold: during a sharded fit, "
            "per-host step wall times are all_gather-exchanged every "
            "few steps (async, via jax.debug.callback — never a host "
            "sync) and a host whose step time exceeds this factor "
            "times the fleet median increments "
            "straggler_events_total{host=} and logs a flight-recorder "
            "event. 0 (default) disables the exchange entirely; 1.5 "
            "is a reasonable production starting point.")


define_flag("fleet_push_interval_s", 2.0,
            "Seconds between fleet-federation snapshot pushes from a "
            "worker's FleetReporter to the rank-0 aggregator "
            "(observability/fleet.py). The reporter starts when the "
            "observability exporter comes up and PT_FLEET_AGGREGATOR "
            "is set (launch_procs/launch_elastic set it); a push is "
            "one stdlib HTTP POST and a failed push is counted "
            "(fleet_push_failures_total), never raised.")
define_flag("fleet_stale_after_s", 15.0,
            "The /fleet/health endpoint marks a host stale (and "
            "answers HTTP 503) when its last snapshot push is older "
            "than this many seconds — a SIGKILLed worker flips the "
            "fleet unhealthy while its last snapshot keeps serving in "
            "the merged /fleet view. 0 disables staleness (hosts are "
            "then only unhealthy if they pushed health.ok=false).")


def _tsdb_ring_changed(value) -> None:
    from .observability import tsdb as _obs_tsdb
    _obs_tsdb.ring().resize(int(value))


define_flag("tsdb_ring", 512,
            "Per-series capacity of the in-process time-series ring "
            "(observability/tsdb.py): each watched metric keeps the "
            "last N sampler snapshots (monotonic-stamped) so windowed "
            "rate()/increase()/quantile_over_window() — and therefore "
            "SLO burn-rate evaluation — are answerable locally. "
            "Rotation-style eviction, oldest out first; memory bound "
            "is watched-series count times this.",
            on_change=_tsdb_ring_changed)
define_flag("tsdb_interval_s", 1.0,
            "Seconds between tsdb sampler ticks (observability/"
            "tsdb.py): each tick snapshots every watched metric from "
            "the registry into its ring and re-evaluates the SLO "
            "alert state machines (observability/slo.py). The sampler "
            "thread starts with the observability exporter; the "
            "interval is re-read every tick so live set_flags() "
            "changes apply.")
define_flag("slo_window_scale", 1.0,
            "Multiplier on every SLO burn-rate window "
            "(observability/slo.py): the fast 5m/1h and slow 30m/6h "
            "pairs all scale by this, so tests and chaos drills can "
            "run the production alert arithmetic in seconds (e.g. "
            "0.01 makes the fast pair 3s/36s). 1.0 in production.")


def _request_ring_changed(value) -> None:
    from .observability import reqtrace as _obs_reqtrace
    _obs_reqtrace.ring().resize(int(value))


define_flag("serving_request_ring", 256,
            "Capacity of the inference server's per-request span ring "
            "(observability/reqtrace.py): the last N request trace "
            "records — trace id, the five lifecycle timestamps "
            "(ingress/dequeue/assembly/dispatch/reply) and the derived "
            "serving_*_ms spans — served at /requests?n= on the "
            "observability exporter.",
            on_change=_request_ring_changed)


def _flight_buffer_changed(value) -> None:
    from .observability import flight as _obs_flight
    _obs_flight.recorder().resize(int(value))


define_flag("flight_buffer_events", 512,
            "Capacity of the crash flight recorder's in-process ring "
            "buffer (observability/flight.py): the last N structured "
            "events (step markers, recompiles, anomalies, ledger "
            "transitions, stragglers) kept for the /flight endpoint "
            "and dumped to flight_<ts>.jsonl under FLAGS_trace_dir on "
            "SIGTERM/uncaught exception/exit.",
            on_change=_flight_buffer_changed)
define_flag("health_heartbeat_timeout_s", 300.0,
            "The /healthz endpoint reports unhealthy (HTTP 503) when a "
            "training heartbeat exists but is older than this many "
            "seconds — a wedged fit() loop reads unhealthy while the "
            "process is still up. 0 disables the staleness check.")


def _stack_sample_hz_changed(value) -> None:
    from .observability import stacks as _obs_stacks
    _obs_stacks.sampler().apply_rate(value)


define_flag("stack_sample_hz", 0.0,
            "Ticks per second of the continuous stack-sampling "
            "profiler (observability/stacks.py): each tick folds "
            "every Python thread's stack into a bounded profile "
            "(collapsed-text + Chrome flame export at /stacks). "
            "0 (the default) disables sampling; the rate is re-read "
            "every tick so live set_flags() changes apply. Measured "
            "self-overhead is exported as "
            "stack_sampler_overhead_ratio.",
            on_change=_stack_sample_hz_changed)
define_flag("stack_profile_max", 512,
            "Cap on distinct folded stacks the sampling profiler "
            "keeps (observability/stacks.py): new stacks past the "
            "cap aggregate into a per-thread [overflow] bucket and "
            "count stack_profile_dropped_total, so a deep-recursion "
            "or codegen-heavy workload cannot grow the profile "
            "unboundedly.")
define_flag("hang_check_interval_s", 1.0,
            "Seconds between hang-monitor ticks (observability/"
            "stacks.py): the monitor watches for a *live* wedge — a "
            "serving engine whose current step is stalled (engine "
            "step stamps) or a training heartbeat past "
            "FLAGS_health_heartbeat_timeout_s — and captures + "
            "classifies all thread stacks while the hang is in "
            "progress, recording a hang_diagnosis flight event "
            "naming the culprit frame. <= 0 disables the monitor.")
def _compile_cache_dir_changed(value) -> None:
    # apply immediately when set programmatically; env-set values are
    # applied by the entry points (fit / to_static / Predictor) since
    # define() does not fire on_change (lazy import: sysconfig is
    # standalone)
    if value:
        from . import sysconfig as _sysconfig
        _sysconfig.apply_compile_cache_flag()


define_flag("compile_cache_dir", "",
            "Persistent on-disk XLA compilation cache directory "
            "(jax_compilation_cache_dir), applied by hapi.Model.fit, "
            "jit.to_static and inference.Predictor/Server. A second "
            "process of the same fit loads its executables from here "
            "instead of cold-compiling; the goodput ledger then books "
            "dispatch compile time to jit_compile_cache_hit instead of "
            "jit_compile_cold, and compile_cache_hits_total / "
            "compile_cache_misses_total count the cache traffic. "
            "Empty (default) = no persistent cache and all compile "
            "time books as cold. tools/compile_cache_report.py is the "
            "proof drill.",
            on_change=_compile_cache_dir_changed)
define_flag("trace_dir", "",
            "If set, observability.export_all()/Model.fit write the "
            "host chrome-trace (host_trace.json) and metrics snapshot "
            "(metrics.json) under this directory at train end; "
            "tools/trace_report.py reads it. (ref: chrome-trace "
            "profiler output path, profiler.h:208.)")
define_flag("checkpoint_verify", True,
            "Verify checkpoint integrity on load: require the COMMIT "
            "marker and check each leaf's recorded CRC32 before "
            "deserializing (io.load / AsyncCheckpointer.restore). Off "
            "skips the CRC pass (size and existence checks stay on — "
            "they are free). Corrupt or uncommitted checkpoints are "
            "skipped by restore with a fallback to the newest intact "
            "one, counted in checkpoint_corrupt_total.")
define_flag("serving_queue_deadline_ms", 0,
            "Inference server load shedding: a queued request older "
            "than this many milliseconds when the batcher picks it up "
            "is answered with an error instead of being served "
            "(counted in requests_shed_total and the native "
            "serving.shed_total stat). 0 (default) disables shedding. "
            "Age is measured from when the server first dequeues the "
            "request off the native transport.")
define_flag("kv_block_size", 16,
            "LLM serving (serving_llm): tokens per KV-cache block. "
            "The paged allocator hands out cache memory in fixed "
            "blocks of this many token slots; the ragged paged "
            "attention kernel scans one block per grid step, so this "
            "is also its K/V tile length. Read when an LLMEngine is "
            "constructed (pool geometry is baked into the compiled "
            "decode step; changing it needs a new engine).")
define_flag("kv_pool_blocks", 64,
            "LLM serving (serving_llm): total KV-cache blocks in the "
            "preallocated per-layer HBM pools — the hard capacity of "
            "the paged allocator (kv_block_size tokens each, shared "
            "by every running sequence). When a sequence cannot grow, "
            "the scheduler preempts the youngest running sequence "
            "back to the waiting queue (recompute-on-readmit), "
            "counted in kv_blocks_preempted_total. Read at LLMEngine "
            "construction.")
define_flag("max_decode_batch", 8,
            "LLM serving (serving_llm): max sequences decoding "
            "concurrently — the continuous-batching scheduler admits "
            "waiting prefills only while the running set is below "
            "this AND the pool has blocks for the prompt. Read every "
            "scheduler step, so it can be retuned on a live server.")
define_flag("kv_admission_watermark", 0.0,
            "LLM serving overload control: admission-time KV "
            "watermark as a fraction of kv_pool_blocks. A new "
            "sequence is rejected at add_request when the projected "
            "peak block demand of all live sequences plus its own "
            "(blocks for prompt + max_new_tokens) would exceed "
            "watermark * pool — fail-fast with a retry-after hint "
            "instead of admit-then-preempt-thrash. Rejections are "
            "counted in llm_admission_rejected_total. 0 (default) "
            "disables the gate; admitted load can then exceed the "
            "pool and is handled by preemption.")
define_flag("tenant_fair_share", False,
            "LLM serving multi-tenancy: weighted fair-share "
            "admission. Off (default), the waiting queue is strictly "
            "FCFS across every tenant. On, each admission slot goes "
            "to the head of the tenant queue with the LOWEST "
            "weight-normalized token-second service (cumulative "
            "resident context-length x wall-seconds / "
            "FLAGS_tenant_weights weight), FCFS *within* each tenant, "
            "so one tenant's prompt flood can no longer starve the "
            "rest. A tenant returning from idle is floored to the "
            "current minimum service so it cannot replay its idle "
            "time as a monopoly. Victim selection under pool "
            "pressure is always (priority class asc, admission seq "
            "desc) — preempt-lowest-class, youngest within class — "
            "and a grower never evicts a higher class than its own. "
            "Read every scheduler pass, so it can be flipped on a "
            "live server.")
define_flag("tenant_weights", "",
            "LLM serving multi-tenancy: fair-share weights as "
            "'tenant=weight,tenant=weight' (e.g. "
            "'premium-corp=10,scraper=1'). Tenants not listed weigh "
            "1.0; weight 0 means the tenant runs only when every "
            "weighted tenant is idle (it still progresses then — "
            "the starvation floor). Malformed entries are skipped, "
            "not fatal. Read per admission pass under "
            "FLAGS_tenant_fair_share.")
define_flag("tenant_kv_budget", "",
            "LLM serving multi-tenancy: per-tenant KV-block budgets "
            "as 'tenant=fraction,tenant=fraction' of kv_pool_blocks "
            "(e.g. 'bulk-ingest=0.5'). A tenant at its budget is "
            "rejected at add_request with a retry-after hint "
            "(llm_admission_rejected_total{tenant=}) even when the "
            "global kv_admission_watermark still has room — bulk "
            "load exhausts bulk's budget, never the pool premium "
            "needs. Unlisted tenants are uncapped. Read per "
            "admission gate.")
define_flag("tenant_label_max", 16,
            "Metric-cardinality bound for the {tenant=} label on "
            "serving counters (requests_shed_total, "
            "llm_admission_rejected_total, router_shed_total, "
            "llm_tenant_admitted_total, llm_tenant_active): the "
            "first N distinct tenant ids keep verbatim labels, the "
            "rest share 16 stable crc32 overflow buckets "
            "(serving_llm/tenancy.py). Read per label lookup.")
define_flag("serving_drain_deadline_s", 5.0,
            "Graceful drain budget for inference.Server. When a "
            "drain starts (SIGTERM under Server.serve_forever, or "
            "Server.drain()), new requests are refused immediately "
            "(tensor requests error-replied, streams shed with a "
            "terminal frame) and in-flight generations may keep "
            "decoding for up to this many seconds; sequences still "
            "running at the deadline are cancelled with a terminal "
            "negative-status frame so no client is left hanging.")
define_flag("kv_prefix_sharing", False,
            "LLM serving (serving_llm): copy-on-write shared-prefix "
            "KV reuse. The paged allocator refcounts physical blocks "
            "and satisfies the already-resident prefix of a new "
            "sequence's prompt (hash-of-full-blocks index plus a "
            "partial-tail match against live sequences) by bumping "
            "refcounts instead of popping the free list; prefill "
            "skips recomputing the shared tokens "
            "(kv_prefix_hit_tokens_total), the first divergent write "
            "copies the shared block to a private one in-pool "
            "(kv_cow_copies_total), and free() only returns "
            "refcount-0 blocks. The admission watermark projects "
            "post-sharing demand, so shared-prefix floods admit ~N "
            "times more streams. Off [assumed]: serving has not been "
            "measured on a chip.")
define_flag("prefill_chunk_tokens", 0,
            "LLM serving (serving_llm): chunked prefill. When > 0, "
            "prefill runs in chunks of this many tokens (floored to "
            "a kv_block_size multiple), ONE chunk per engine step "
            "interleaved with the decode tick — a long prompt no "
            "longer spikes every running stream's TPOT. A sequence "
            "joins the decode batch only when its last chunk lands; "
            "preempting it mid-prefill resets to its last shared or "
            "cached block. 0 (default) prefills whole prompts in one "
            "step — 0 [assumed]: serving has not been measured on a "
            "chip (~256 is the expected setting). Read "
            "every step, so it can be retuned on a live server.")
define_flag("llm_stall_factor", 10.0,
            "LLM engine stall watchdog: an engine step (or the gap "
            "since the last step while sequences are active) longer "
            "than this factor times the EWMA step time marks the "
            "engine stalled — a forced llm_engine_stalled flight "
            "event plus llm_engine_stalled_total, and /healthz "
            "reports the serving section unhealthy (HTTP 503). A "
            "floor of 0.5s avoids flapping on scheduler jitter. 0 "
            "disables the watchdog.")
define_flag("speculative_k", 0,
            "LLM serving (serving_llm): speculative decoding. When "
            "> 0, a small draft model proposes up to this many tokens "
            "per running sequence per engine step; the target model "
            "verifies every window in ONE batched ragged multi-query "
            "paged-attention step and commits the longest accepted "
            "prefix plus the target's bonus token (temperature 0 and "
            "the position-keyed sampler make the output token-for-"
            "token identical to non-speculative decode). Draft K/V "
            "written past the accepted point is rolled back via the "
            "allocator's truncate_to (llm_spec_*_tokens_total, "
            "llm_spec_accept_rate, llm_spec_verify_ms). 0 (default) "
            "disables — 0 [assumed]: serving has not been measured on "
            "a chip. Read every step, so it can be retuned "
            "on a live server.")
define_flag("speculative_draft_layers", 1,
            "LLM serving (serving_llm): transformer layers of the "
            "auto-built draft model used when speculative_k > 0 and "
            "LLMEngine was given no draft_model (same hidden/head/"
            "vocab geometry as the target, this many layers). Read "
            "when the draft is first built (once per engine).")
define_flag("speculative_draft_tie_embeddings", True,
            "LLM serving (serving_llm): share the target model's "
            "token and position embedding tables with the auto-built "
            "draft model (the output head is tied to the input "
            "embedding, so this ties it too) — the standard "
            "memory-free draft head. Only consulted when the engine "
            "builds its own draft (draft_model=None).")


def _llm_seqtrace_ring_changed(value) -> None:
    from .observability import seqtrace as _obs_seqtrace
    _obs_seqtrace.ring().resize(int(value))


define_flag("llm_seqtrace_ring", 256,
            "Capacity of the finished per-sequence lifecycle-timeline "
            "ring (observability/seqtrace.py): the last N terminal "
            "sequence timelines — queued/admitted/prefill_chunk/"
            "cow_copy/preempted/spec_window/token events, each "
            "monotonic-stamped, plus the wire trace id — served at "
            "/llm/seqs on the observability exporter and joined "
            "against step records by tools/serving_report.py. "
            "Rotation-style eviction (oldest out first); timelines "
            "ending in error/cancelled/shed are also dumped to the "
            "flight recorder so post-mortems survive the ring.",
            on_change=_llm_seqtrace_ring_changed)


def _llm_step_ring_changed(value) -> None:
    from .observability import stepprof as _obs_stepprof
    _obs_stepprof.ring().resize(int(value))


define_flag("llm_step_ring", 256,
            "Capacity of the LLM engine step-record ring "
            "(observability/stepprof.py): the last N step profiles — "
            "per-phase durations (admit/prefill/decode/spec_verify "
            "plus sample/scatter sub-segments), batch composition, "
            "KV-pool snapshot, prefix-hit and speculative-accept "
            "deltas, stall verdict — served at /llm/steps together "
            "with the live in-flight step (begin stamps + current "
            "phase). Rotation-style eviction, oldest out first.",
            on_change=_llm_step_ring_changed)


define_flag("router_failover_budget", 2,
            "Front-door router (serving_llm/router.py): maximum "
            "mid-stream failovers per client stream. A stream that "
            "already delivered tokens is resumed on a surviving "
            "backend (prompt+delivered re-issued with the sample "
            "offset, bitwise-exact continuation) at most this many "
            "times before the router gives up with a terminal error "
            "that names the delivered count. Read per failover "
            "decision.")
define_flag("router_retry_budget", 2,
            "Front-door router: maximum re-sends of an UNSTARTED "
            "(zero tokens delivered) stream or idempotent tensor "
            "request to another backend after a connect/deadline "
            "failure. Started streams never consume this — they fail "
            "over instead (never blind-resent). Read per retry "
            "decision.")
define_flag("router_retry_backoff_s", 0.05,
            "Front-door router: base of the jittered exponential "
            "backoff slept before each unstarted-request retry "
            "(actual sleep is base * 2^(attempt-1) * uniform[0.5,1) "
            "— full-jitter, so N clients retrying a blip don't "
            "stampede the survivor). 0 disables the sleep (tests). "
            "Read per retry.")
define_flag("router_breaker_threshold", 3,
            "Front-door router: consecutive connect/deadline "
            "failures (data path or probe) that trip a backend's "
            "circuit breaker closed -> open. Drain refusals and "
            "admission rejections are NOT failures — they park the "
            "backend as draining/saturated without touching the "
            "breaker. Read lazily per breaker decision.")
define_flag("router_breaker_backoff_s", 0.5,
            "Front-door router: open-state backoff of a freshly "
            "tripped circuit breaker — how long the backend is left "
            "alone before the single half-open probe. Doubles on "
            "every re-open (failed probe) up to "
            "FLAGS_router_breaker_backoff_max_s; any success resets "
            "it. Read lazily per breaker decision.")
define_flag("router_breaker_backoff_max_s", 30.0,
            "Front-door router: cap on the doubling open-state "
            "breaker backoff, bounding how stale a recovered "
            "backend's exile can get. Read lazily per breaker "
            "decision.")
define_flag("router_probe_interval_s", 1.0,
            "Front-door router: period of the backend health-probe "
            "thread (PTSC STATS round trip reading serving.draining, "
            "plus an optional exporter GET /healthz). Probe failures "
            "feed the breaker; a tripped breaker's backend is probed "
            "again only after its backoff (the half-open single "
            "probe). Read per probe cycle.")
define_flag("router_backend_deadline_s", 30.0,
            "Front-door router: per-chunk deadline on router->backend "
            "streams and total deadline on proxied tensor requests. A "
            "backend silent past this is treated as dead: breaker "
            "failure plus retry (unstarted) or deterministic failover "
            "(started). Read per backend attempt.")
define_flag("router_prefix_affinity", False,
            "Front-door router: prefix-affinity pick(). On, the "
            "router hashes each prompt's leading FULL KV blocks "
            "(FLAGS_kv_block_size tokens each) and routes to the "
            "backend that most recently served the longest matching "
            "prefix (LRU placement memory, longest match wins), so "
            "shared-prefix traffic lands where its blocks are "
            "already hot and FLAGS_kv_prefix_sharing hits multiply "
            "fleet-wide (kv_prefix_hit_tokens_total). No affinity "
            "match falls back to least-loaded by live stream count "
            "(round-robin order breaking ties). Off (default) keeps "
            "pure round-robin. Read per stream dispatch.")


def _fault_spec_changed(value) -> None:
    # (re)arm the chaos-injection registry; lazy import mirrors
    # _enable_metrics_changed (testing.faults imports this module)
    from .testing import faults as _faults
    _faults.configure(value or None)


define_flag("fault_spec", "",
            "Deterministic chaos-injection spec "
            "(paddle_tpu.testing.faults; grammar in "
            "docs/fault_tolerance.md). Comma-separated entries "
            "'point[:key=value]...', e.g. "
            "'ckpt_write:p=1:at=2,sigterm:step=7,loader:exc=OSError'. "
            "Injection points: ckpt_write (checkpoint writer, per "
            "leaf), loader (fit data fetch), train_step (before each "
            "dispatch), sigterm (self-delivers SIGTERM). Empty "
            "(default) disarms every point — the hit() hook is a "
            "near-free early return. Used by tools/chaos_drill.py.",
            on_change=_fault_spec_changed)
define_flag("skip_nonfinite_steps", True,
            "Compile a finiteness guard into TrainStep/ShardedTrainStep:"
            " when any gradient leaf is NaN/Inf the whole "
            "optimizer/buffer update is discarded in-graph (lax select,"
            " no host sync) and the step is counted in "
            "nonfinite_steps_total instead of poisoning the weights — "
            "the reference's amp_check_finite_and_scale semantics, "
            "applied to every precision (fp16 runs additionally get "
            "GradScaler backoff). Costs one fused isfinite reduction "
            "per gradient leaf. Read at train-step construction.")
define_flag("rollback_budget", 2,
            "Divergence-watchdog rollback budget for one "
            "hapi.Model.fit(ckpt_dir=...) run: when the watchdog trips "
            "(a NaN/spike streak on the loss, FLAGS_divergence_streak),"
            " fit restores the newest intact checkpoint and replays — "
            "at most this many times; the next trip after the budget "
            "is exhausted raises. 0 disables rollback (the watchdog "
            "still counts anomalies). Rollback needs "
            "FLAGS_enable_metrics (the loss probes feed the watchdog).")
define_flag("rollback_lr_factor", 1.0,
            "Learning-rate multiplier applied on divergence-rollback "
            "re-entry (e.g. 0.5 halves the LR after each rollback) — "
            "compiled in as a runtime scalar, so the first rollback "
            "retraces the step once. 1.0 leaves the LR untouched.")
define_flag("divergence_streak", 5,
            "Consecutive anomalous loss samples (NaN/Inf or EWMA spike "
            "per FLAGS_anomaly_spike_factor) before the divergence "
            "watchdog declares the run diverged and fit rolls back to "
            "the newest intact checkpoint. A clean sample resets the "
            "streak.")
define_flag("recompile_warn_threshold", 8,
            "Warn (once per function) when one jit entry point has "
            "been traced for at least this many distinct input "
            "signatures — a recompilation storm usually means "
            "unpadded/unbucketed input shapes. 0 disables the "
            "warning.")
