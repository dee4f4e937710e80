"""paddle_tpu.observability — the unified observability plane.

One metrics plane for the whole framework (SURVEY §2.7 profiler tier,
grown into a production-style plane):

- :mod:`.metrics`          typed Counter/Gauge/Histogram registry
- :mod:`.compile_tracker`  ``tracked_jit`` XLA compile accounting
- :mod:`.runlog`           structured JSONL run-log emitter
- :mod:`.export`           Prometheus text + JSON snapshot exporters
- :mod:`.tracing`          per-request span traces, blame attribution,
  Perfetto chrome-trace export and windowed SLO burn rate

``paddle_tpu.monitor`` (the STAT_* counter API) is a thin shim over the
registry here, so every existing ``stat_add``/``stat_time`` call site
reports into the same plane that ``GET /metrics`` scrapes.
"""

from __future__ import annotations

from . import compile_tracker, export, metrics, runlog, tracing
from .compile_tracker import (RecompileWarning, compile_totals, compiles,
                              reset_compiles, tracked_jit)
from .export import prometheus_text, snapshot, validate_prometheus_text
from .metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .runlog import log_event, recent

#: well-known instruments, rendered into the README's generated
#: "Observability" section by tools/sync_readme.py — keep descriptions
#: here, next to the code that emits them
INSTRUMENT_DOCS = {
    "xla_compiles{fn=...}":
        "counter — XLA compiles per tracked_jit site (executor_step, "
        "parallel_executor_step, decode_step[_paged], "
        "verify_step_paged{k=...}, serving_prefill_paged{bucket=...}, "
        "to_static, to_static_multi_step, zero_train_step{stage=...})",
    "xla_trace_ms{fn=...} / xla_lower_ms{fn=...} / "
    "xla_backend_compile_ms{fn=...}":
        "counters — ms a tracked_jit site spent tracing its function "
        "(exclusive of tracked sites traced inside it), lowering to an "
        "MLIR module and in the backend's compilation or the persistent "
        "cache's retrieval (JAX's own monitoring events; programs built "
        "outside every site are fn=\"(untracked)\")",
    "xla_cache_hits{fn=...} / xla_cache_misses{fn=...}":
        "counters — programs of a site the persistent compile cache "
        "served / compiled and wrote (a warm process reads 0 misses)",
    "serving_ttft_seconds{engine=...}":
        "histogram — time to first token of completed serving requests",
    "serving_tpot_seconds{engine=...}":
        "histogram — mean time per output token of completed requests",
    "serving_kv_blocks_used{engine=...}":
        "gauge — physical KV blocks referenced (paged serving; "
        "includes the trash block and prefix-cache holds)",
    "serving_kv_blocks_free{engine=...}":
        "gauge — physical KV blocks on the free list (paged serving)",
    "serving_kv_dequant_max_abs_err{engine=...}":
        "gauge — high-water max-abs int8 KV dequantization error over "
        "rows written by the compiled steps (quantization drift watch)",
    "serving_mesh_devices{engine=...}":
        "gauge — devices an engine's compiled steps span (data x model "
        "serving-mesh size; 1 for a single-device engine)",
    "serving_replicas{router=...}":
        "gauge — data-parallel engine replicas behind a ReplicaRouter",
    "serving_queue_depth{router=..., replica=...}":
        "gauge — requests queued + active per routed engine replica "
        "(the router's least-loaded routing signal)",
    "serving_slo_attainment{engine=...}":
        "gauge — fraction of completed requests whose first token met "
        "the TTFT deadline (engines running with "
        "FLAGS_serving_slo_ttft_ms; the goodput numerator)",
    "serving_shed_total{engine=..., reason=..., priority=...}":
        "counter — requests shed, by reason (queue_full | slo | "
        "deadline | preempted | fault | drain) and priority class; "
        "submit-time rejections included",
    "serving_weight_version{engine=...}":
        "gauge — live weight hot-swaps applied to an engine's model "
        "(0 = the weights it was built with; bumps once per "
        "swap_weights call, per replica in a rolling router swap)",
    "serving_prefix_affinity_hits{router=...} / _misses{router=...}":
        "counters — DisaggRouter routing decisions that landed on the "
        "prefill worker already holding the request's longest cached "
        "prefix vs fell back to least-loaded (the fleet-wide prefix "
        "index; FLAGS_serving_prefix_affinity)",
    "serving_handoff_queue_depth{router=...}":
        "gauge — finished prefills waiting for a decode worker to "
        "adopt their KV blocks (bounded by "
        "FLAGS_serving_handoff_queue; full = prefill backpressure)",
    "serving_disagg_workers{router=..., role=...}":
        "gauge — single-role workers in a disaggregated fleet, by "
        "role (prefill | decode)",
    "serving_replica_state{router=..., replica=..., state=...}":
        "gauge — 1 on a replica's current health-state series "
        "(healthy | suspect | dead | recovering), 0 on the others; "
        "driven by the per-replica strike watchdog "
        "(FLAGS_serving_replica_strikes)",
    "serving_rehomed_total{router=...}":
        "counter — requests recovered off a killed replica/worker "
        "onto a live peer (queued re-routes + in-flight re-prefills "
        "and block-table splices); the third term of the accounting "
        "identity completed + shed + rehomed == offered",
    "serving_canceled_total{engine=..., reason=...}":
        "counter — requests canceled mid-lifecycle, by reason (client "
        "| disconnect | deadline | hedge_lose | duplicate); every "
        "cancel reclaims its KV blocks and LoRA pin at whatever stage "
        "it caught the request (queued | prefill | decode | handoff), "
        "the fourth term of the accounting identity completed + "
        "rehomed + shed + canceled == offered",
    "serving_hedges_total{router=..., outcome=...}":
        "counter — hedged prefills, by outcome (fired: a hedge copy "
        "was dispatched; win: the hedge produced first token first; "
        "lose: the primary beat it and the hedge was canceled) — "
        "volume bounded by the FLAGS_serving_hedge_budget token "
        "bucket, losers torn down leak-free via cancel",
    "serving_retry_budget_remaining":
        "gauge — tokens left in the shared fleet-wide RetryBudget "
        "(successes at budgeted sites deposit "
        "FLAGS_retry_budget_ratio, every retry withdraws 1; an empty "
        "bucket sheds would-be retries as backpressure instead of "
        "letting correlated failures storm)",
    "serving_breaker_state{router=..., replica=...}":
        "gauge — per-replica circuit breaker: 0 closed (routing "
        "normally), 1 open (error rate over "
        "FLAGS_serving_breaker_threshold in the last "
        "FLAGS_serving_breaker_window steps; replica skipped by the "
        "router), 0.5 half-open (cooldown elapsed, one probe admitted)",
    "serving_traced_total":
        "counter — requests that carried a per-request trace (sampled "
        "in by FLAGS_serving_trace; the trace is host-side marks on "
        "the engine clock whose spans decompose TTFT/E2E into "
        "queue | prefill | decode | handoff | rehome components — an "
        "accounting identity, see observability/tracing.py)",
    "sanitizer_lock_acquires":
        "counter — lock acquisitions instrumented by the concurrency "
        "sanitizer (FLAGS_sanitize_locks): every outermost acquire of "
        "a make_lock() lock records held->acquired order edges; "
        "inversions and guarded-state violations are read back via "
        "analysis.sanitizer_report()",
    "serving_slo_burn_rate{window=...}":
        "gauge — per-window SLO error-budget burn rate from "
        "tracing.window_snapshots: (1 - window attainment) / "
        "(1 - SLO target); 1.0 burns the budget exactly at the "
        "allowed rate, >1 eats into it, 0 is a clean window (the "
        "tools/soak.py per-window report)",
    "zero_param_bytes_per_device{stage=...} / "
    "zero_opt_bytes_per_device{stage=...}":
        "gauges — max over devices of resident parameter / "
        "optimizer-state bytes for the last zero_train_step state "
        "(the ZeRO memory win: opt bytes ~ 1/dp at stage >= 1)",
    "STAT_serving_kv_quant_writes / _rows":
        "counters — int8-quantizing step dispatches and KV rows "
        "quantized through them",
    "STAT_serving_prefix_hits / _misses":
        "counters — paged admissions that reused >=1 prefix-cached KV "
        "block vs prefilled from scratch (token-granular rates in "
        "ServingEngine.stats())",
    "serving_lora_adapters_loaded{engine=...}":
        "gauge — tenant LoRA adapters currently resident in an "
        "engine's paged adapter pool (page 0 = base never counts)",
    "serving_kv_blocks_used{tier=host} / _free{tier=host}":
        "gauges — host-RAM KV tier occupancy of the fleet-shared "
        "HostBlockStore (int8-at-rest blocks holding demoted prefix "
        "chains and finished-session rows); the device-pool series "
        "carry tier=device so capacity dashboards stack the two tiers",
    "serving_kv_migrations{dir=...}":
        "counter — KV blocks migrated between tiers by the "
        "TierManager, by direction (demote: device->host, promote: "
        "host->device); pure host-side block surgery, zero compiles "
        "either way",
    "serving_sessions_resident / _host / _resumed":
        "gauges — multi-turn session accounting in the fleet-shared "
        "SessionStore: sessions currently holding device rows "
        "(resident), sessions parked with host-resident context "
        "between turns (host), and cumulative submit(session=...) "
        "resumes that re-prefilled only their unshared suffix "
        "(resumed)",
    "STAT_serving_lora_loads / _evictions":
        "counters — adapter pool writes: load_adapter / evict_adapter "
        "calls that landed (both zero-recompile by construction)",
    "STAT_serving_*":
        "counters — admission/token/shed/speculative accounting from "
        "the serving engine (see the Serving section)",
    "STAT_fault_<site>":
        "counters — one per injected fault firing (see Fault tolerance)",
    "STAT_guardian_*":
        "counters — TrainGuardian NaN-skips and rollbacks",
    "<name>  /  <name>_calls, <name>_ms":
        "any monitor.stat_add counter / monitor.stat_time histogram "
        "(calls + total ms derived from it)",
}

#: run-log event kinds emitted by the framework itself
EVENT_DOCS = {
    "train_step": "executor/guardian training step: step, loss, "
                  "step_time_ms, examples_per_sec",
    "guardian_skip": "TrainGuardian skipped a non-finite step",
    "guardian_rollback": "TrainGuardian restored a checkpoint",
    "serving_admit": "request admitted into a KV slot (bucket, "
                     "prompt_tokens, shared_tokens reused from the "
                     "prefix cache)",
    "serving_finish": "request retired (tokens, ttft_ms, tpot_ms; + "
                      "deadline_met under a TTFT SLO)",
    "serving_shed": "request shed (reason: queue_full | slo | deadline "
                    "| preempted | fault | drain; priority class)",
    "serving_spec": "speculative decoding round (proposed, accepted)",
    "serving_kv_quant": "int8 KV dequantization error reached a new "
                        "high-water mark (max_abs_err, rows)",
    "serving_route": "ReplicaRouter placed a request (request, "
                     "replica, depth, kv_blocks_free)",
    "serving_drain": "ReplicaRouter stopped admissions and began "
                     "draining (replicas, queued)",
    "serving_drain_done": "ReplicaRouter drain finished (shed: "
                          "requests given up on while draining)",
    "serving_autoscale": "AutoscalePolicy changed the replica count "
                         "(replicas_from, replicas_to, retiring)",
    "serving_weight_swap": "live weight hot-swap applied to a running "
                           "engine (engine, version, params, "
                           "reset_costs) — the train→serve publish "
                           "step; zero new compiles by construction",
    "serving_request": "one arrival at the serving front door (t, "
                       "prompt, max_new_tokens, priority; + "
                       "temperature/top_k/top_p/seed/stop/json_mode/"
                       "tenant when non-default) — the replayable "
                       "record tools/trace_convert.py turns into a "
                       "loadgen trace",
    "serving_lora_load": "tenant LoRA adapter pool write (engine, "
                         "adapter, page; evicted=true marks an "
                         "eviction) — data-not-constants, zero new "
                         "compiles like serving_weight_swap",
    "serving_handoff": "disaggregated KV handoff (stage=export: a "
                       "prefill worker emitted the record; "
                       "stage=adopt: a decode worker spliced/copied "
                       "it in — `copied` marks cross-pool)",
    "serving_drain_replica": "ReplicaRouter drained one replica out "
                             "of the set (replica, rerouted, "
                             "replicas_left); its queued requests "
                             "re-homed onto live peers",
    "serving_worker_kill": "DisaggRouter tore a worker down (role, "
                           "worker, shed, rerouted) — the chaos "
                           "teardown path, leak-free by contract",
    "serving_replica_kill": "ReplicaRouter lost a replica (replica, t, "
                            "rehomed, shed, replicas_left, cause: "
                            "kill | strikes | fault) — queued work "
                            "re-homed, in-flight decodes re-prefill "
                            "from committed tokens on a survivor; the "
                            "replayable half of a chaos schedule",
    "serving_replica_recover": "ReplicaRouter brought a replacement "
                               "replica up (replica, t, restarts) — "
                               "same geometry, so recovery reuses the "
                               "compiled steps (zero new XLA "
                               "compiles)",
    "serving_cancel": "request canceled mid-lifecycle (request, stage: "
                      "queued | prefill | decode | handoff, reason: "
                      "client | disconnect | deadline | hedge_lose | "
                      "duplicate) — all KV/LoRA holds reclaimed at the "
                      "point of cancel",
    "serving_hedge": "hedged prefill dispatched (request, primary, "
                     "hedge, predicted_ttft_ms) — the straggler "
                     "mitigation; resolution lands as a hedge_win/"
                     "hedge_lose trace mark and a serving_cancel of "
                     "the loser",
    "serving_kv_demote": "TierManager moved cold device prefix "
                         "entries into the host tier (entries, "
                         "blocks, dedup: chains the fleet-shared "
                         "store already held) — the off-step-path "
                         "LRU demotion sweep",
    "serving_kv_promote": "TierManager rebuilt a host-resident prefix "
                          "chain on device (blocks, tokens) — "
                          "promotion-on-demand at acquire()/affinity "
                          "time, all-or-nothing under pool pressure",
    "serving_session_resume": "submit(session=...) resumed a parked "
                              "conversation (session, stored_tokens, "
                              "prompt_tokens) — only the unshared "
                              "suffix re-prefills, token-identically",
    "fault_injected": "deterministic fault fired (site, fault_kind)",
    "recompile_warning": "tracked function exceeded "
                         "FLAGS_warn_recompiles (fn, signature)",
}


def counter(name: str, help_str: str = "") -> Counter:
    """Get-or-create a counter in the default registry."""
    return metrics.DEFAULT.counter(name, help_str)


def gauge(name: str, help_str: str = "") -> Gauge:
    """Get-or-create a gauge in the default registry."""
    return metrics.DEFAULT.gauge(name, help_str)


def histogram(name: str, help_str: str = "", buckets=None) -> Histogram:
    """Get-or-create a histogram in the default registry."""
    return metrics.DEFAULT.histogram(name, help_str, buckets=buckets)


__all__ = [
    "metrics", "compile_tracker", "runlog", "export", "tracing",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
    "tracked_jit", "compiles", "compile_totals", "reset_compiles",
    "RecompileWarning",
    "log_event", "recent",
    "prometheus_text", "snapshot", "validate_prometheus_text",
    "counter", "gauge", "histogram",
    "INSTRUMENT_DOCS", "EVENT_DOCS",
]
