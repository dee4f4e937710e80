"""Global flags registry — the TPU-native analog of the reference's
gflags plane (paddle/fluid/platform/flags.cc, exposed to Python via
pybind/global_value_getter_setter.cc as paddle.set_flags/get_flags,
python/paddle/fluid/framework.py:5576,5599).

Flags are typed, documented at definition, overridable from the
environment (``FLAGS_<name>``, read at first access), and settable at
runtime via :func:`set_flags`. Unknown names raise ValueError, matching
the reference's enforce behavior.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict

_lock = threading.Lock()
_defs: Dict[str, dict] = {}
_values: Dict[str, Any] = {}
# bumped on every set_flags; compile caches (Executor, jit.to_static)
# fold it into their keys so flag changes retrace instead of silently
# reusing a computation lowered under the old flag values
_version = 0


def version() -> int:
    with _lock:
        return _version


def _coerce(value, typ):
    if typ is bool:
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    return typ(value)


def define_flag(name: str, default, help_str: str = ""):
    """Register a flag (framework-internal, like a C++ DEFINE_*)."""
    with _lock:
        if name in _defs:
            return
        _defs[name] = {"default": default, "type": type(default),
                       "help": help_str}


def _unknown_flag_error(names) -> ValueError:
    """A typo must fail loudly — silently creating/ignoring flag state
    hides misconfiguration (e.g. ``check_nan_if`` for ``check_nan_inf``).
    Suggest the closest registered names."""
    import difflib
    with _lock:
        known = sorted(_defs)
    hints = []
    for n in names:
        close = difflib.get_close_matches(n, known, n=1)
        if close:
            hints.append(f"did you mean {close[0]!r}?")
    hint = (" " + " ".join(hints)) if hints else ""
    return ValueError(
        f"unknown flag(s) {sorted(names)!r}.{hint} "
        f"({len(known)} flags registered; "
        f"paddle_tpu.flags.list_flags() enumerates them)")


def get_flags(names):
    """Return {name: value} for a flag name or list of names."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for name in names:
        if name not in _defs:
            raise _unknown_flag_error([name])
        with _lock:
            if name in _values:
                out[name] = _values[name]
                continue
            env = os.environ.get("FLAGS_" + name)
            d = _defs[name]
            val = _coerce(env, d["type"]) if env is not None else d["default"]
            _values[name] = val
            out[name] = val
    return out


def set_flags(flags: Dict[str, Any]):
    """Set flags at runtime: ``set_flags({'check_nan_inf': True})``.
    Atomic: either every entry applies or none does."""
    global _version
    unknown = [n for n in flags if n not in _defs]
    if unknown:
        raise _unknown_flag_error(unknown)
    coerced = {n: _coerce(v, _defs[n]["type"]) for n, v in flags.items()}
    with _lock:
        _values.update(coerced)
        _version += 1


def get_flag(name: str):
    return get_flags(name)[name]


def list_flags() -> Dict[str, dict]:
    """All registered flags with metadata (help/default/current)."""
    with _lock:
        return {n: {**d, "current": _values.get(n, d["default"])}
                for n, d in _defs.items()}


# Core flags (analog of platform/flags.cc definitions)
define_flag("check_nan_inf", False,
            "Scan every op output for NaN/Inf during execution "
            "(ref platform/flags.cc:44).")
define_flag("use_pallas_attention", True,
            "Lower fused_attention_qkv to the Pallas flash-attention "
            "kernel when the shapes allow it.")
define_flag("use_pallas_layer_norm", False,
            "Lower layer_norm to the fused Pallas kernel (default off: "
            "XLA's fusion is competitive at small hidden sizes).")
define_flag("pallas_min_seq", 1024,
            "Minimum sequence length before attention switches from the "
            "XLA-composed form to the Pallas flash kernel.")
define_flag("pallas_flash_block_q", 512,
            "Flash-attention q-block size (tuning knob; clipped to the "
            "largest power-of-two divisor of seq).")
define_flag("pallas_flash_block_k", 512,
            "Flash-attention k-block size (tuning knob).")
define_flag("check_program", False,
            "Run the static Program verifier (framework/analysis.py) "
            "once per program at its first executor/compiler compile; "
            "ERROR diagnostics abort the run with block/op locations "
            "instead of an opaque tracer error. Default off in "
            "production; tests/conftest.py turns it on for the suite.")
define_flag("sanitize_locks", False,
            "Swap the serving/observability locks for instrumented "
            "wrappers (analysis/concurrency.py): record the per-thread "
            "lock-acquisition-order graph, report lock-order inversions "
            "(potential deadlock cycles) with held-lock witnesses, and "
            "enforce the declared guarded-state registry — a write to "
            "a '# guarded-by' attribute without its lock raises "
            "GuardedStateError. Pure host-side instrumentation: zero "
            "overhead when off (plain threading locks), zero effect on "
            "compiled steps when on.")
define_flag("check_ir_passes", False,
            "Verify the Program IR after every pass in a "
            "PassManager.apply pipeline; a failure names the offending "
            "pass. The safety net for IR-rewriting passes (fusion, "
            "sharding, recompute).")
define_flag("check_shapes", False,
            "Add static shape/dtype inference (abstract interpretation, "
            "paddle_tpu/analysis/) to the verifier suite wherever it "
            "runs (Program.verify, FLAGS_check_program first-compile, "
            "FLAGS_check_ir_passes): a mis-shaped program fails before "
            "any XLA trace with a Diagnostic naming the op and the "
            "mismatched dims. Off by default — it abstractly executes "
            "every block twice (dynamic-batch probing).")

# Resilience plane (paddle_tpu/resilience): fault injection + retry +
# guardian knobs. All deterministic so chaos runs replay exactly.
define_flag("fault_spec", "",
            "Deterministic fault-injection spec, "
            "'site:kind[@trigger];...' (grammar in "
            "resilience/injector.py). Empty = every fault_point is a "
            "no-op. PADDLE_TPU_FAULT_SPEC is honored when the flag is "
            "unset.")
define_flag("fault_seed", 0,
            "Seed for probabilistic fault triggers and retry jitter — "
            "same spec + seed replays the same faults.")
define_flag("retry_max_attempts", 5,
            "RetryPolicy: attempts before giving up (first try "
            "included).")
define_flag("retry_base_delay", 0.05,
            "RetryPolicy: first backoff delay in seconds (doubles per "
            "retry).")
define_flag("retry_max_delay", 2.0,
            "RetryPolicy: per-retry backoff cap in seconds.")
define_flag("retry_deadline", 30.0,
            "RetryPolicy: wall-clock budget in seconds across all "
            "attempts of one call.")
define_flag("retry_budget_ratio", 0.1,
            "Fleet-wide RetryBudget: retry tokens earned per "
            "successful call (the classic 'retries may add at most "
            "this fraction of extra load'). Budgeted sites "
            "(serving.route / serving.handoff / serving.replica) "
            "withdraw one token per retry attempt; an empty bucket "
            "turns the retry into an immediate RetryError, so "
            "correlated failures shed as backpressure instead of "
            "amplifying into a retry storm.")
define_flag("retry_budget_reserve", 10.0,
            "Fleet-wide RetryBudget: tokens the shared bucket starts "
            "with (and its refill cap is 10x this floor), so isolated "
            "early failures still retry before any successes have "
            "funded the budget.")
define_flag("guardian_max_skip", 3,
            "TrainGuardian: consecutive NaN/Inf steps tolerated as "
            "batch skips before rolling back to the latest "
            "checkpoint.")
define_flag("ps_heartbeat_timeout", 30.0,
            "Seconds without a heartbeat before a PS server reports a "
            "worker dead (heart_beat_monitor analog; was hardcoded in "
            "ps/rpc.py).")
define_flag("ps_connect_timeout", 30.0,
            "Deadline in seconds for a PS client to reach a server "
            "that is still binding its port (workers routinely start "
            "first).")
define_flag("ps_socket_timeout", 90.0,
            "PS client socket timeout in seconds; must exceed the "
            "server's worst-case in-handler park (the 60 s barrier "
            "wait) so a slow barrier can't strand a reply.")
define_flag("ps_prefer_native", True,
            "make_server: try the C++ PS server first, falling back "
            "to the Python one when the toolchain is unavailable.")

# Distributed training plane (paddle_tpu/distributed).
define_flag("zero_stage", 0,
            "distributed.zero.zero_train_step default ZeRO stage: "
            "0 = optimizer state replicated (plain to_static "
            "semantics), 1 = optimizer moments sharded over the data "
            "axis, 2 = gradients reduce-scattered onto the same "
            "shards as well (grads enter and leave the compiled step "
            "data-sharded).")

# Serving plane (paddle_tpu/serving): continuous-batching inference
# engine geometry + admission control. Constructor arguments override;
# the flags are the deployment-config surface.
define_flag("serving_max_slots", 8,
            "ServingEngine: KV-cache slots = max in-flight requests "
            "decoded per step (the fixed decode batch axis).")
define_flag("serving_max_len", 256,
            "ServingEngine: per-slot KV capacity (prompt + generated); "
            "must not exceed the model's max_position_embeddings.")
define_flag("serving_max_queue", 64,
            "ServingEngine admission control: waiting requests beyond "
            "this are rejected with QueueFullError (backpressure; "
            "counted as STAT_serving_rejected).")
define_flag("serving_prefill_buckets", "16,32,64,128",
            "Comma-separated prompt-length buckets: prefill pads each "
            "prompt to the smallest bucket >= its length, so prefill "
            "compiles once per bucket instead of once per length.")
define_flag("serving_max_new_tokens", 32,
            "ServingEngine: default per-request new-token budget when "
            "submit() does not specify one.")
define_flag("serving_idle_wait", 0.05,
            "ServingEngine background loop: seconds to wait for new "
            "submissions when no request is queued or in flight.")
define_flag("serving_spec_tokens", 0,
            "Speculative decoding: draft tokens K proposed per slot "
            "per step by the n-gram self-drafter; the verify step "
            "scores all K+1 positions in one fixed-shape forward and "
            "commits the accepted prefix (greedy output stays "
            "token-identical to K=0). 0 disables speculation (one "
            "token per decode step). Each request reserves K rows of "
            "slot headroom, so prompt + max_new_tokens + K must fit "
            "in serving_max_len.")
define_flag("serving_spec_ngram", 3,
            "Speculative decoding: longest suffix n-gram the "
            "self-drafter matches against the request's own "
            "prompt+generated context when proposing draft tokens "
            "(falls back to shorter n-grams, then to repeating the "
            "last token).")
define_flag("serving_dispatch_threads", 0,
            "Router dispatch concurrency: ReplicaRouter / DisaggRouter "
            "step their replicas from a bounded thread pool of this "
            "size instead of the serial per-engine loop (engines are "
            "stepped concurrently; health strikes, hedging and "
            "deadline reaping stay at step boundaries with identical "
            "semantics). 0 (default) = serial stepping, byte-identical "
            "scheduling order.")
define_flag("serving_block_size", 16,
            "Paged serving: KV rows per block. Smaller blocks waste "
            "less memory on partial blocks and share shorter "
            "prefixes; larger blocks shrink the block table and the "
            "gather fan-in.")
define_flag("serving_num_blocks", 0,
            "Paged serving: physical KV blocks in the pool per layer "
            "(block 0 is reserved as the trash block for "
            "padding/overflow writes). 0 = auto-size to "
            "max_slots * ceil(max_len/block_size) + 1, enough for "
            "every slot at worst-case length; set it lower to "
            "oversubscribe memory and rely on short requests + "
            "prefix sharing (admission blocks head-of-line when the "
            "pool runs dry).")
define_flag("serving_kv_dtype", "f32",
            "Paged serving KV pool element type: 'f32', 'bf16' (half "
            "the bytes, plain cast), or 'int8' (quarter the bytes: "
            "per-block-per-head absmax scales stored alongside the "
            "pools, quantize on block_scatter_write, dequantize "
            "inside the attention kernel/reference). Smaller KV bytes "
            "per block => more blocks at a fixed pool budget => more "
            "concurrent requests. Greedy top-1 output on the bench "
            "models is unchanged; the max-abs dequant error is "
            "tracked per engine (serving_kv_dequant_max_abs_err).")
define_flag("serving_prefix_cache", True,
            "Paged serving: cache full prompt blocks under a rolling "
            "token-prefix hash so a repeated system prompt prefills "
            "once and later requests reference its blocks "
            "(copy-on-write at a partially shared boundary block). "
            "Idle entries are evicted LRU under pool pressure.")
define_flag("serving_mesh", "",
            "Tensor-parallel serving mesh as 'DATAxMODEL' (e.g. '1x2': "
            "1-way data x 2-way model parallel within one engine "
            "replica). Model params and the paged KV pool are placed "
            "with NamedSharding on a ('data', 'model') mesh — attention "
            "heads / MLP hidden sharded on 'model' per "
            "SERVING_TP_RULES — and prefill/decode/verify run under "
            "pjit with explicit in/out shardings. Host-side block "
            "tables stay replicated plain inputs so block remapping "
            "and prefix sharing never retrace. Empty (default) keeps "
            "the engine single-device.")
define_flag("serving_replicas", 1,
            "Data-parallel serving replicas fronted by ReplicaRouter: "
            "submit() routes each request to the least-loaded replica "
            "(predicted TTFT from queue depth + free KV blocks), with "
            "shed/drain semantics riding the resilience plane's "
            "RetryPolicy at the serving.route fault site. 1 (default) "
            "means a single engine with no router in front.")
define_flag("serving_slo_ttft_ms", 0.0,
            "SLO-aware admission: target time-to-first-token in ms. "
            "When > 0, submit() predicts the newcomer's TTFT from live "
            "queue depth, measured per-bucket prefill cost, and the "
            "decode batch's TPOT (EWMA), and sheds the submission when "
            "the prediction exceeds this budget (QueueFullError with "
            "reason='slo' and a predicted-TTFT-derived retry_after_s); "
            "queued requests whose deadline already passed are shed "
            "before prefill instead of wasting a dispatch. 0 (default) "
            "keeps the blunt depth-only backpressure. Admission is "
            "pure host arithmetic: no new compiled surface either way.")
define_flag("serving_slo_prefill_ms", 0.0,
            "TTFT predictor: pinned per-bucket prefill cost in ms. 0 "
            "(default) learns an EWMA from this engine's measured "
            "prefill dispatches; pin it for deterministic admission "
            "decisions (loadgen replay, tests).")
define_flag("serving_slo_tpot_ms", 0.0,
            "TTFT predictor: pinned per-output-token decode cost in "
            "ms. 0 (default) learns an EWMA from measured decode/"
            "verify steps; pin it for deterministic admission.")
define_flag("serving_priority_preempt", True,
            "Priority classes (submit(priority=), lower = more "
            "urgent): allow an urgent submission that would otherwise "
            "be shed (queue full / predicted SLO miss) to preempt-shed "
            "queued strictly-lower-priority work instead. Requests "
            "within a class keep FIFO order either way.")
define_flag("serving_autoscale", "",
            "ReplicaRouter autoscaling bounds as 'MIN:MAX' replicas "
            "(e.g. '1:4'). When set, the router consults an "
            "AutoscalePolicy each step — scale up on queue-depth / "
            "free-KV-block / SLO-attainment pressure, scale down by "
            "draining the emptiest replica when load subsides. "
            "Replicas share one placed model, so scaling reuses the "
            "compiled steps instead of retracing. Empty (default) "
            "disables autoscaling.")
define_flag("serving_disagg", "",
            "Disaggregated serving fleet topology as 'PxD' (e.g. "
            "'1x2'): P prefill-only workers feed D decode-only workers "
            "through a bounded handoff queue (DisaggRouter in "
            "serving/disagg.py). Prefill and decode stop sharing a "
            "batch, so TTFT no longer inherits decode-batch jitter; "
            "the KV handoff is a host-side block-table splice on "
            "co-located pools. Empty (default) keeps symmetric "
            "replicas.")
define_flag("serving_prefix_affinity", True,
            "DisaggRouter: route each request to the prefill worker "
            "whose KV pool already holds its longest cached prefix "
            "(fleet-wide rolling-hash prefix index), falling back to "
            "least-loaded on a miss. Off = pure least-loaded routing; "
            "hit rates then stop compounding across workers.")
define_flag("serving_handoff_queue", 16,
            "DisaggRouter: bound on the prefill->decode handoff queue. "
            "A full queue backpressures prefill workers (they stop "
            "admitting) instead of buffering unbounded finished "
            "prefills whose KV blocks are pinned until adoption.")
define_flag("serving_lora_rank", 0,
            "Multi-tenant paged LoRA: rank of the per-tenant low-rank "
            "adapters (serving/lora.py LoRAPool). > 0 builds the "
            "engine with an adapter pool whose stacked factors are "
            "plain inputs to the compiled steps — per-row adapter "
            "pages are gathered inside the step (the BlockKVCache "
            "block-table trick applied to weights), so base and "
            "per-tenant rows mix in one batch of one executable and "
            "loading/evicting adapters never recompiles. 0 disables "
            "(no pool, no lora step input). Constructor state read "
            "once, like the SLO knobs.")
define_flag("serving_lora_max_adapters", 4,
            "Multi-tenant paged LoRA: adapter pages in the pool "
            "(tenants resident at once; +1 all-zero base page is "
            "added internally). A load into a full pool raises until "
            "an adapter is evicted; eviction refuses while in-flight "
            "requests still pin the page (the KV-block refcount "
            "discipline applied to weights).")
define_flag("serving_replica_strikes", 3,
            "ReplicaRouter failure detection: consecutive unproductive "
            "steps (a step() that raised, or did no work while the "
            "replica held queued/active requests) before a replica is "
            "declared dead. One strike marks it suspect (deprioritized "
            "in routing); reaching the limit marks it dead — excluded "
            "from routing and, under serving_auto_restart, replaced. "
            "A productive step clears the strikes.")
define_flag("serving_auto_restart", True,
            "ReplicaRouter recovery policy: when a replica is declared "
            "dead (strike watchdog or a serving.replica `error`/`drop` "
            "fault), spawn a same-geometry replacement before tearing "
            "the dead one down — queued work re-homes onto live peers, "
            "in-flight decodes re-prefill from their committed tokens, "
            "and the replacement reuses the compiled steps (zero new "
            "XLA compiles). False leaves the fleet one replica "
            "smaller (kill without restart).")
define_flag("serving_hedge_ms", 0.0,
            "ReplicaRouter hedged prefill (the Dean & Barroso "
            "tail-at-scale move): when a submission's assigned "
            "replica predicts a TTFT beyond this many ms, dispatch a "
            "hedge copy to the second-best healthy replica after the "
            "same delay — first first-token wins, the loser is "
            "canceled with every KV block and LoRA pin reclaimed. "
            "0 (default) disables hedging; a negative value derives "
            "the threshold live from the traced fleet's TTFT p95 "
            "(observability.tracing), so the hedge line tracks the "
            "tail it is trimming. Pure host-side queue surgery: "
            "predict_serving_compiles(hedge=N) is a validated no-op.")
define_flag("serving_hedge_budget", 0.05,
            "ReplicaRouter hedged prefill: token-bucket bound on "
            "duplicated work — each offered submission deposits this "
            "many hedge tokens and each dispatched hedge spends one, "
            "so hedges never exceed budget * offered (+1 initial "
            "allowance). 0 refuses all hedges even when "
            "serving_hedge_ms arms them.")
define_flag("serving_breaker_window", 20,
            "ReplicaRouter per-replica circuit breaker: recent step "
            "outcomes (ok / raised) remembered per replica. The "
            "breaker complements the strike watchdog: strikes need "
            "consecutive failures, the breaker trips on failure RATE "
            "over this window, so a replica flapping between ok and "
            "error stops receiving traffic before it ever reaches "
            "the strike limit. 0 disables the breaker.")
define_flag("serving_breaker_threshold", 0.5,
            "ReplicaRouter per-replica circuit breaker: failure "
            "fraction over the outcome window (with at least half "
            "the window observed) that opens the breaker — an open "
            "replica is skipped by routing like a draining one.")
define_flag("serving_breaker_cooldown_s", 5.0,
            "ReplicaRouter per-replica circuit breaker: seconds "
            "(engine clock) an open breaker holds before going "
            "half-open — one probe routes through; success closes "
            "the breaker, failure re-opens it for another cooldown.")
define_flag("serving_host_tier", False,
            "Host-RAM KV block tier (serving/kv_tier.py): attach a "
            "TierManager over a pinned numpy HostBlockStore so cold "
            "prefix chains and finished-session rows demote out of "
            "the device pool (int8-at-rest) and promote back on "
            "demand, and submit(session=...) resumes a demoted "
            "conversation token-identically. Routers build ONE "
            "fleet-shared store across replicas and roles. Migration "
            "is host-side block-table surgery over eager pool writes "
            "— predict_serving_compiles(host_tier=True) is a "
            "validated no-op.")
define_flag("serving_host_blocks", 256,
            "Host-RAM KV tier capacity in blocks (per fleet-shared "
            "HostBlockStore). Blocks are stored as int8 codes + "
            "per-block-per-head f32 absmax scales regardless of the "
            "device pool's kv_dtype, so a host gigabyte holds ~4x "
            "the f32 sessions; the store evicts idle chains LRU "
            "(leaf-first) under pressure.")
define_flag("serving_demote_idle_ms", 0.0,
            "Host-tier demotion sweep cadence (engine clock ms): a "
            "device prefix entry must sit cold (cache-only, no live "
            "request references) across a full window of this length "
            "before the between-steps sweep demotes it to the host "
            "store — 0 demotes cold entries at every step (the "
            "maximum-capacity setting loadgen's returning-users gate "
            "runs with). Only read when a kv_tier is attached.")

# Observability plane (paddle_tpu/observability): metrics registry,
# XLA compile tracker, structured run log, Prometheus export.
define_flag("warn_recompiles", 0,
            "XLA compile tracker: when > 0, emit a structured "
            "RecompileWarning (with the offending abstract shape/dtype "
            "signature) whenever a tracked_jit function compiles more "
            "than this many times — catches the recompile-per-token "
            "class of bug at the first occurrence. 0 disables.")
define_flag("runlog_dir", "",
            "Directory for the structured JSONL run log "
            "(observability.log_event); one runlog-<pid>.jsonl per "
            "process. Empty (default) keeps events in memory only.")
define_flag("runlog_max_mb", 64.0,
            "Size cap in MB for the active run-log file; on overflow "
            "it rotates to <name>.1 (replacing the previous one), so a "
            "process writes at most two caps of disk.")
define_flag("serving_trace", 1.0,
            "Per-request distributed tracing sampling fraction "
            "(observability/tracing.py): each admitted request is "
            "sampled in/out by a deterministic hash of its request id "
            "— 1.0 (default) traces everything, 0 disables. Traced "
            "requests record host-side span marks (submit/admit/"
            "first_token/export/adopt/kill/finish) on the engine "
            "clock; blame attribution, Perfetto export and the "
            "/v1/requests/<id> endpoint read them. Pure host "
            "bookkeeping: zero compiled surface either way "
            "(predict_serving_compiles(tracing=...) is a validated "
            "no-op).")
define_flag("serving_trace_keep", 512,
            "Finished-trace retention ring (like the runlog's "
            "rotation): the most recent N completed/shed traces stay "
            "queryable via GET /v1/requests/<id> and the exporters; "
            "older ids 404. Active (in-flight) traces are never "
            "evicted.")
