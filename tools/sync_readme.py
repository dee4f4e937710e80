#!/usr/bin/env python
"""Regenerate README's generated fragments from their sources of truth.

Three rounds in a row the hand-written README headline drifted from the
measured artifact; this makes the artifacts the single source of truth:

    python tools/sync_readme.py          # rewrite generated fragments
    python tools/sync_readme.py --check  # exit 1 on drift (CI gate)

Three fragments are generated, everything else stays hand-written:
  - the GPT flagship headline bullet (from the latest BENCH_r*.json)
  - the "Static program checks" list between the
    `<!-- BEGIN GENERATED: verifier-checks -->` markers (from
    framework/analysis.py:ANALYSIS_CHECKS +
    analysis/lifecycle.py:CHECK_DOCS + the registered flags)
  - the "Fault tolerance" section between the
    `<!-- BEGIN GENERATED: fault-tolerance -->` markers (from
    resilience/injector.py:FAULT_SITES + the registered flags)
  - the "Serving" section between the
    `<!-- BEGIN GENERATED: serving -->` markers (from the registered
    `FLAGS_serving_*` flags + the serving fault sites)
  - the "Train→serve loop" section between the
    `<!-- BEGIN GENERATED: train-serve -->` markers (from the
    registered `FLAGS_zero_*` flags)
  - the "Observability" section between the
    `<!-- BEGIN GENERATED: observability -->` markers (from
    observability.INSTRUMENT_DOCS / EVENT_DOCS + the registered flags)
"""

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def latest_bench():
    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not paths:
        raise SystemExit("no BENCH_r*.json artifact found")
    # newest artifact that actually carries a perf record — serving/soak
    # records (e.g. the chaos-soak frontier) have neither "parsed" nor
    # "tail" and don't feed the MFU headline
    for path in reversed(paths):
        with open(path) as f:
            data = json.load(f)
        if "parsed" in data or "tail" in data:
            return path, data.get("parsed") or json.loads(
                data["tail"].strip().splitlines()[-1])
    raise SystemExit("no BENCH_r*.json artifact with a perf record found")


_FLAGSHIP_NAMES = {
    "gpt2_345m_mfu": "GPT-2 345M",
    "gpt2-medium_mfu": "GPT-2 345M",
    "gpt2-1p1b_mfu": "GPT-2-class 1.1B (d=128)",
    "gpt2-1p3b_mfu": "GPT-2-class 1.3B (d=128)",
}


def headline(parsed, src):
    toks = parsed.get("tokens_per_sec_per_chip")
    metric = parsed.get("metric")
    name = _FLAGSHIP_NAMES.get(metric, metric or "flagship")
    via = ("the Pallas flash-attention kernels + per-block recompute + "
           "grads-internal trace-once compiled train step"
           if "1p" in (metric or "") else
           "the Pallas flash-attention kernels + trace-once compiled "
           "train step")
    return (
        f"- {name} training at **{parsed['value']:.2f}% MFU** "
        f"(batch {parsed['batch']}, seq {parsed['seq']}, bf16, bf16 AdamW "
        f"moments; {toks / 1000:.1f}k tokens/s/chip) — "
        f"{parsed['vs_baseline']:.2f}x the 40% north-star target — via "
        f"{via}. "
        f"[generated from {os.path.basename(src)}]"
    )


def sync_headline(text, check):
    """Returns (new_text, drift_message_or_None)."""
    src, parsed = latest_bench()
    if parsed.get("metric") not in _FLAGSHIP_NAMES:
        print(f"latest artifact is {parsed.get('metric')}, not a GPT "
              "flagship; headline left alone")
        return text, None
    want = headline(parsed, src)
    # the generated bullet: starts "- GPT-2 345M training" and ends with
    # the "[generated from ...]" stamp (possibly wrapped over lines)
    pat = re.compile(
        r"- GPT[^\n]*training at[^\n]*(?:\n(?!-)[^\n]*)*")
    m = pat.search(text)
    if not m:
        raise SystemExit("README GPT headline bullet not found")
    current = m.group(0)
    # wrap the generated line to the README's 78-col style
    import textwrap
    wrapped = "\n".join(textwrap.wrap(
        want, width=76, initial_indent="", subsequent_indent="  "))
    if current.strip() == wrapped.strip():
        print("README headline in sync")
        return text, None
    if check:
        return text, (
            "README headline DRIFTS from the bench artifact:\n"
            f"  readme: {' '.join(current.split())[:100]}...\n"
            f"  artifact: {' '.join(wrapped.split())[:100]}...")
    print(f"README headline updated from {os.path.basename(src)}")
    return text[:m.start()] + wrapped + text[m.end():], None


_CHECKS_BEGIN = "<!-- BEGIN GENERATED: verifier-checks -->"
_CHECKS_END = "<!-- END GENERATED: verifier-checks -->"
_VERIFIER_FLAGS = ("check_program", "check_ir_passes", "check_shapes")


def render_checks_block():
    """The verifier-check list, from the live check registry + flags."""
    import textwrap
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu import flags
    from paddle_tpu.analysis.lifecycle import CHECK_DOCS
    from paddle_tpu.framework.analysis import ANALYSIS_CHECKS

    def bullet(head, body):
        return "\n".join(textwrap.wrap(
            f"- {head} — {body}", width=76, subsequent_indent="  "))

    lines = ["Checks (`Program.verify(checks=[...])` selects a subset):",
             ""]
    lines += [bullet(f"`{name}`", cd.description)
              for name, cd in ANALYSIS_CHECKS.items()]
    lines += [
        "",
        "Serving concurrency & lifecycle (`analysis.lifecycle`, the",
        "static half of the concurrency plane — the runtime half is the",
        "`FLAGS_sanitize_locks` sanitizer below): an AST dataflow pass",
        "over the serving sources models the KV/LoRA resource APIs as",
        "obligation effects (acquire creates, release discharges,",
        "export_row *moves* ownership into the handoff record, storing/",
        "returning a handle escapes it to the holder's lifecycle) and",
        "interprets each function over a path-merging abstract state",
        "that follows raise edges and except handlers; a companion pass",
        "checks every write to `# guarded-by: <lock>` attributes happens",
        "under `with self.<lock>:` (declarations inherit across",
        "subclasses; `# holds: <lock>` asserts a caller-held lock,",
        "`# unguarded-ok: <reason>` waives one site). Checks:",
        "",
    ]
    lines += [bullet(f"`{name}`", doc)
              for name, doc in CHECK_DOCS.items()]
    lines += ["", "Flags:", ""]
    defs = flags.list_flags()
    for name in _VERIFIER_FLAGS + ("sanitize_locks",):
        d = defs[name]
        lines.append(bullet(
            f"`FLAGS_{name}` (default `{d['default']}`)", d["help"]))
    lines += ["", "Command line:", ""]
    lines.append(bullet(
        "`python tools/lint_program.py --books --shapes [--json]`",
        "the CI sweep: verifier + static shape/dtype inference over the "
        "eight book programs (exit 1 on ERROR diagnostics; `--json` for "
        "structured output)."))
    lines.append(bullet(
        "`python tools/lint_sharding.py --preset gpt_tp --mesh dp=2,mp=2`",
        "GSPMD sharding-rule lint (`distributed.sharding."
        "lint_sharding_rules`): dead rules, shadowed regexes, "
        "`_fit_spec` replicated fallbacks, unknown mesh axes, and the "
        "per-device parameter-memory estimate — no devices needed "
        "(the mesh is plain axis sizes)."))
    lines.append(bullet(
        "`python tools/lint_serving.py --strict [--json]`",
        "the serving concurrency/lifecycle lint over "
        "engine/router/disagg/kv_cache/lora (`analysis.lifecycle."
        "lint_serving`); `--strict` fails on warnings too, and "
        "`--baseline tools/lint_serving_baseline.json` carries "
        "justified findings — every entry needs a one-line "
        "justification, stale entries warn so the baseline only "
        "shrinks (it ships empty)."))
    lines.append(bullet(
        "`FLAGS_sanitize_locks=1 python tools/soak.py ... "
        "--expect-sanitizer-clean`",
        "the runtime half under chaos: every `make_lock()` lock "
        "records held->acquired order edges (cycles = potential "
        "deadlocks, recorded not raised) and `declare_guarded` "
        "attributes raise `GuardedStateError` on writes without the "
        "declared lock; the soak gate requires zero cycles and zero "
        "violations through kills/restarts/re-homes, and "
        "`analysis.sanitizer_report()` feeds the "
        "`sanitizer_lock_acquires` counter."))
    return "\n".join(lines)


def sync_checks_block(text, check):
    """Returns (new_text, drift_message_or_None)."""
    try:
        b = text.index(_CHECKS_BEGIN) + len(_CHECKS_BEGIN)
        e = text.index(_CHECKS_END)
    except ValueError:
        raise SystemExit("README verifier-checks markers not found")
    current = text[b:e].strip("\n")
    want = render_checks_block()
    if current == want:
        print("README verifier-checks block in sync")
        return text, None
    if check:
        return text, ("README verifier-checks block DRIFTS from "
                      "framework/analysis.py — rerun tools/sync_readme.py")
    print("README verifier-checks block regenerated")
    return text[:b] + "\n" + want + "\n" + text[e:], None


_FAULT_BEGIN = "<!-- BEGIN GENERATED: fault-tolerance -->"
_FAULT_END = "<!-- END GENERATED: fault-tolerance -->"
_FAULT_FLAGS = ("fault_spec", "fault_seed", "retry_max_attempts",
                "retry_base_delay", "retry_max_delay", "retry_deadline",
                "retry_budget_ratio", "retry_budget_reserve",
                "guardian_max_skip", "ps_heartbeat_timeout",
                "ps_connect_timeout", "ps_socket_timeout")


def render_fault_block():
    """Fault-injection sites + resilience flags, from the live
    registries (resilience/injector.py and paddle_tpu/flags.py)."""
    import textwrap
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu import flags
    from paddle_tpu.resilience import FAULT_SITE_DOCS

    def bullet(head, body):
        return "\n".join(textwrap.wrap(
            f"- {head} — {body}", width=76, subsequent_indent="  "))

    lines = [
        "A fault spec is a `;`-separated list of `site:kind[@trigger]`",
        "rules (e.g. `ps.rpc.call:drop@0.05;exec.step:nan@17`), installed",
        "via `FLAGS_fault_spec` or `PADDLE_TPU_FAULT_SPEC`; unset means",
        "every `fault_point` is a no-op. Triggers: absent = every call,",
        "`@N` = exactly the N-th call (0-based), `@N+` = from the N-th",
        "on, `@p` (float with a dot) = probability p from a PRNG seeded",
        "by (`FLAGS_fault_seed`, site, rule index) — the same spec +",
        "seed always injects the same faults — and the virtual-time",
        "pair `@t>Ns` / `@t>Ns+`: fire once (or on every call) after N",
        "seconds have elapsed on the injector's clock. The clock",
        "defaults to `time.monotonic`; `resilience.set_time_source` (or",
        "`fault_scope(..., time_source=...)`) points it at a virtual",
        "clock, so a kill schedule like",
        "`serving.replica:error@t>1800s;serving.replica:error@t>3600s`",
        "replays byte-identically inside a simulated soak",
        "(tools/soak.py). Kinds: `drop` (connection",
        "loss), `error` (OSError), `preempt` (SystemExit, the in-process",
        "preemption analog), `kill` (hard `os._exit`), and the",
        "caller-interpreted `nan` / `corrupt` / `skip`.",
        "",
        "Injection sites:",
        "",
    ]
    lines += [bullet(f"`{site}`", doc)
              for site, doc in FAULT_SITE_DOCS.items()]
    lines += [
        "",
        "Every injected fault counts `STAT_fault_<site>`, every retry",
        "`STAT_retry_<site>`, and every guardian recovery a",
        "`STAT_guardian_*` counter (`paddle_tpu.monitor`), so the chaos",
        "suite (`pytest -m chaos`, tools/ci.sh step 4) asserts recovery",
        "was observed, not just survived.",
        "",
        "Flags:",
        "",
    ]
    defs = flags.list_flags()
    for name in _FAULT_FLAGS:
        d = defs[name]
        lines.append(bullet(
            f"`FLAGS_{name}` (default `{d['default']}`)", d["help"]))
    return "\n".join(lines)


def sync_fault_block(text, check):
    """Returns (new_text, drift_message_or_None)."""
    try:
        b = text.index(_FAULT_BEGIN) + len(_FAULT_BEGIN)
        e = text.index(_FAULT_END)
    except ValueError:
        raise SystemExit("README fault-tolerance markers not found")
    current = text[b:e].strip("\n")
    want = render_fault_block()
    if current == want:
        print("README fault-tolerance block in sync")
        return text, None
    if check:
        return text, ("README fault-tolerance block DRIFTS from "
                      "resilience/injector.py + flags — rerun "
                      "tools/sync_readme.py")
    print("README fault-tolerance block regenerated")
    return text[:b] + "\n" + want + "\n" + text[e:], None


_SERVING_BEGIN = "<!-- BEGIN GENERATED: serving -->"
_SERVING_END = "<!-- END GENERATED: serving -->"


def render_serving_block():
    """Serving-engine config + fault surface, from the live registries
    (paddle_tpu/flags.py `serving_*` + resilience/injector.py serving
    sites) — the deployment-config doc can't drift from the code."""
    import textwrap
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu import flags
    from paddle_tpu.resilience import FAULT_SITE_DOCS

    def bullet(head, body):
        return "\n".join(textwrap.wrap(
            f"- {head} — {body}", width=76, subsequent_indent="  "))

    lines = [
        "`paddle_tpu.serving.ServingEngine` batches requests at",
        "iteration granularity: each step admits queued prompts into",
        "free KV-cache slots (prefill padded to a length bucket, one",
        "compile per bucket, whose rows follow the bucket's length: the",
        "model's token budget a dispatch over the bucket, GPT's 512",
        "tokens, from one row for a bucket of 512 or more to",
        "`max_slots` for a short one — so a long prompt's dispatch",
        "computes the prompt it admitted, a burst of short ones shares",
        "a dispatch and its read of the weights, and a group of more",
        "same-bucket admissions than the rows goes out as several",
        "dispatches in admission order) and runs one batched",
        "decode over every occupied slot (one compile, total).",
        "`engine.stats()` gives `prefill_rows_live` /",
        "`prefill_rows_computed` (`STAT_serving_prefill_rows_live` /",
        "`_computed`): of the rows the prefill dispatches computed,",
        "those that were an admitted prompt's; an operator reads a low",
        "share as short prompts arriving one at a time (padding up to",
        "the bucket's rows), and a share near 100 with a high prefill",
        "time as prompts long enough to be compute-bound;",
        "`prefill_tokens_live` / `prefill_tokens_computed`",
        "(`STAT_serving_prefill_tokens_live` / `_computed`) count the",
        "same in positions: prompt tokens against the `rows x bucket`",
        "positions the dispatches computed, which is what a model with",
        "recurrent layers pays for (its scan runs over the padding",
        "too). `state_bytes` / `state_rows_live` are the recurrent",
        "state the cache holds beside its blocks (`[max_slots, ...]`",
        "arrays a layer for a model whose seam declares a",
        "`StateKind`; 0 for GPT and Mellum) and the rows of it that",
        "are a request's now. KV",
        "memory is block-paged: a",
        "fixed pool of `[num_blocks, heads, block_size, head_dim]` KV",
        "blocks per layer, host-side per-request block tables fed to",
        "the jitted steps as plain inputs (block remapping never",
        "retraces), a ref-counted allocator, and a rolling-hash prefix",
        "cache — a shared system prompt prefills once and later",
        "requests reference its full blocks (copy-on-write at the",
        "boundary block), prefilling only their unshared suffix.",
        "Physical block 0 is a permanently-allocated trash block that",
        "backs table padding and absorbs overflow writes. Pool",
        "exhaustion holds the head-of-line request (FIFO order is part",
        "of the equivalence oracle) until retirements free blocks. With",
        "`FLAGS_serving_spec_tokens` = K > 0 the decode becomes",
        "draft–verify speculative decoding: an n-gram self-drafter",
        "proposes K tokens per slot from the request's own generated",
        "suffix (no second model), one fixed-shape verify forward",
        "scores all K+1 positions, the accepted prefix commits to the",
        "slot's KV cache and the rejected tail's write offset rolls",
        "back — greedy output stays token-identical to K=0. `submit()`",
        "returns a request handle; `results()` collects them;",
        "`serving.ServingHTTPServer` is the JSON front end",
        "(`POST /v1/generate` — with an optional integer `priority`",
        "field — `GET /v1/stats`, `GET /health`; 429 on admission",
        "backpressure carries a `Retry-After` header sized by the",
        "engine's predicted-TTFT model and a `reason` in the body).",
        "Per-phase latency lands in `monitor.stats()` as",
        "`STAT_serving_prefill_ms` / `STAT_serving_decode_ms` /",
        "`STAT_serving_verify_ms` over `_calls`: one observation a",
        "dispatch that was committed, from its dispatch to its tokens on",
        "the host (the device's time included; the SLO gate's cost",
        "estimates are fed by the same readings, see \"Profiler spans\");",
        "acceptance as",
        "`STAT_serving_spec_proposed` / `STAT_serving_spec_accepted`;",
        "`engine.stats()` (merged into `GET /v1/stats`) adds",
        "time-to-first-token and time-per-output-token percentiles",
        "(`ttft_p50_ms` / `ttft_p99_ms` / `tpot_p50_ms` /",
        "`tpot_p99_ms`), the speculative `spec_acceptance_rate`, and",
        "the block-pool accounting (`kv_blocks_used` /",
        "`kv_blocks_free`, also exported as gauges on `GET /metrics`)",
        "plus token-granular `prefix_hit_rate` from",
        "`STAT_serving_prefix_hits` / `_misses`.",
        "",
        "The KV pools have one owner at a time. Every paged entry",
        "(prefill, decode, verify) is handed the pools",
        "donated: it writes its KV rows in place, in the layout the",
        "pool arrived in — 64 rows and under (a decode or verify step)",
        "as one `dynamic_update_slice` per row, more (a prompt, a decode",
        "step of 128 requests) as one kernel over the chunks of blocks",
        "the rows touch (`ops/pallas/pool_write.py`) — the arrays",
        "handed in are deleted by the call, and the engine binds the",
        "returned pools (`cache.set_arrays`) before anything reads the",
        "cache again; nobody keeps a pool array across a step.",
        "`STAT_serving_pool_inplace` counts the dispatches whose input",
        "pools were in fact deleted, and `engine.stats()` gives",
        "`pool_dispatches` / `pool_inplace` / `pool_inplace_share`",
        "(1.0 unless the backend ignores donation, in which case the",
        "pools are copied as before). A step that raises after its",
        "pools were consumed sheds what was running, rebuilds zeroed",
        "pools with the prefix cache flushed",
        "(`STAT_serving_pool_rebuilds`) and keeps serving; engines that",
        "share the pool shed their rows when they see its epoch move.",
        "",
        "The paged read is picked by the query block's shape, not by a",
        "flag: a decode or verify block (at most",
        "`gpt.PAGED_KERNEL_MAX_ROWS` = 8 rows a request) goes through",
        "the `ops.pallas.paged_attention` kernel — the pools stay in",
        "HBM, and for each request the kernel copies the blocks its",
        "length stands on (`ceil((pos + rows) / block_size)` of its",
        "table's entries, eight whole `[heads, block_size, d]` blocks a",
        "compute step, the next step's copies in flight) and runs the",
        "online softmax over them in float32; nothing table-sized is",
        "gathered, a released slot costs one block. A prefill bucket",
        "composes gather -> masked-softmax attention from the block",
        "pool in XLA (`ops.attention_ops.block_attention`). The two",
        "are token-identical by construction and CI oracle, and",
        "`engine.stats()` gives `kv_blocks_live` / `kv_blocks_table`:",
        "the share of the table the decode steps' rows stood on.",
        "Independently, `FLAGS_serving_kv_dtype=int8` quantizes the KV",
        "pool to int8 codes with per-block-per-head absmax scales (~4x",
        "more KV positions in the same pool bytes): writes go through a",
        "quantizing scatter whose scales only grow — committed codes",
        "never drift when quieter rows land later — and both reads",
        "apply the identical `codes * scale / 127` dequantization.",
        "The engine reports the high-water dequantization error as",
        "`kv_quant_max_abs_err` in `stats()` and as the",
        "`serving_kv_dequant_max_abs_err` gauge on `GET /metrics`.",
        "",
        "Scaling is two orthogonal axes. `FLAGS_serving_mesh=DxM` (or",
        "`ServingEngine(mesh=...)`) runs ONE engine tensor-parallel on a",
        "`(\"data\", \"model\")` device mesh: params and the paged KV",
        "pool are placed with `NamedSharding` under the `serving_tp`",
        "rule table (attention heads / MLP hidden split on `model`;",
        "the pool's heads axis likewise), and every compiled step runs",
        "under pjit with explicit in/out shardings while the host-side",
        "block tables stay replicated plain inputs — block remapping",
        "still never retraces. Tokens are bit-identical to the",
        "single-device engine (the 1x1 mesh is a CI oracle; a real",
        "head-split is exercised on the virtual-device mesh).",
        "`FLAGS_serving_replicas=N` (or `serving.ReplicaRouter`) is the",
        "data-parallel axis: N engine replicas behind one `submit()`,",
        "routed least-loaded by queue depth with free KV blocks as the",
        "tiebreak; full replicas shed through the same `QueueFullError`",
        "429 path, and `drain()` stops admissions and runs every",
        "replica to idle for rolling deploys. Replicas share the model",
        "and therefore the per-model unified step-compile cache — N",
        "replicas compile each step once, total, and a mesh engine pays",
        "exactly one extra compile per step kind (its entries are keyed",
        "on the mesh), an invariant `analysis.recompile` predicts and",
        "the serving tests assert against observed counts.",
        "`engine.stats()` reports `mesh_shape`; `router.stats()` adds",
        "per-replica queue depths and free blocks; `GET /metrics` grows",
        "`serving_mesh_devices`, `serving_replicas` and per-replica",
        "`serving_queue_depth` gauges, and the run log records",
        "`serving_route` / `serving_drain` events.",
        "",
        "Admission is SLO-aware. With `FLAGS_serving_slo_ttft_ms` > 0",
        "(or `ServingEngine(slo_ttft_ms=...)`) every `submit()` first",
        "predicts the request's time-to-first-token from live state —",
        "queue depth in prefill dispatches of the bucket's rows, the",
        "per-bucket prefill cost, and",
        "a decode time-per-output-token EWMA (pin both via",
        "`slo_prefill_ms` / `slo_tpot_ms` for deterministic tests) —",
        "and rejects requests that cannot meet the deadline instead of",
        "queueing doomed work; the 429 carries a `Retry-After` sized by",
        "that same prediction. Requests carry an integer priority class",
        "(lower = more urgent, default 1, FIFO within a class); when",
        "the queue is full, an urgent arrival preemptively sheds the",
        "newest strictly-lower-priority queued request",
        "(`FLAGS_serving_priority_preempt`), and queued requests whose",
        "deadline has already expired are shed before ever reaching",
        "prefill. Every loss is accounted: `engine.stats()` reports",
        "per-reason shed counts (`queue_full | slo | deadline |",
        "preempted | fault | drain`) plus `slo_attainment` — the",
        "fraction of completed requests whose first token met the",
        "deadline, i.e. the goodput numerator — exported as the",
        "`serving_shed_total{reason=,priority=}` counter and",
        "`serving_slo_attainment` gauge on `GET /metrics`. All of this",
        "is host-side queue surgery: zero new XLA compiles, an",
        "invariant `analysis.recompile.predict_serving_compiles`",
        "encodes and CI asserts. On the router, `FLAGS_serving_autoscale",
        "=MIN:MAX` (or an `AutoscalePolicy`) grows/shrinks the replica",
        "set from mean queue depth with hysteresis + cooldown —",
        "retiring replicas drain in the background, admissions route",
        "around them — and `drain()` returns the count of requests shed",
        "while giving up. `tools/loadgen.py` closes the loop: an",
        "open-loop (arrivals don't wait on completions) load generator",
        "with Poisson / bursty (Markov-modulated) / diurnal arrival",
        "processes, mixed prompt/output-length and priority",
        "distributions, and fully replayable seeds — same seed, byte-",
        "identical arrival trace and identical admit/shed decisions. It",
        "drives an engine or router directly (no HTTP in the loop) and",
        "reports goodput (SLO-met completions/s), attainment, per-",
        "reason sheds, TTFT/TPOT percentiles, and leaked KV blocks",
        "(must be zero). CI runs a seeded clean + chaos-crossover gate.",
        "",
        "Prefill and decode can also split into dedicated roles.",
        "`FLAGS_serving_disagg=PxD` (or `serving.DisaggRouter`) runs a",
        "disaggregated fleet: P prefill workers admit and prefill,",
        "then hand each request off through a bounded queue",
        "(`FLAGS_serving_handoff_queue`; a full queue backpressures",
        "admission instead of buffering unboundedly) to D decode",
        "workers as an ownership-transfer record — the request, its",
        "first token, and its physical KV blocks. Co-located roles",
        "share one block pool, so adoption is a zero-copy ref-count",
        "splice of the exported block table; cross-pool adoption is an",
        "all-or-nothing block copy that releases the source blocks",
        "only once every destination block is committed. Routing is",
        "prefix-affine (`FLAGS_serving_prefix_affinity`): a fleet-wide",
        "rolling-hash index over published prefix chains steers each",
        "prompt to the prefill worker already holding its longest",
        "cached prefix (verified against the worker's live pool before",
        "use, so stale entries can't misroute), falling back to least-",
        "loaded. The split adds ZERO compiles — both roles reuse the",
        "per-model step cache, which keys on geometry, never role —",
        "an invariant `predict_serving_compiles(disagg=...)` encodes",
        "and CI asserts, alongside the token-identity oracle against",
        "the symmetric `ReplicaRouter` (prefix affinity on and off,",
        "speculative K>0, int8 KV). `router.stats()` reports handoff",
        "and affinity counters plus the fleet prefix hit rate;",
        "`GET /metrics` grows `serving_disagg_workers`,",
        "`serving_handoff_queue_depth` and",
        "`serving_prefix_affinity_hits`; the run log records",
        "`serving_handoff` events, and `serving_request` arrival",
        "events feed `tools/trace_convert.py`, which turns any run log",
        "into a replayable trace for `tools/loadgen.py --replay` —",
        "re-run production arrivals against a different topology,",
        "byte-identical. Chaos is first-class: the `serving.handoff`",
        "fault site sheds or retries cleanly, and",
        "`kill_prefill_worker()` re-homes queued work, purges the dead",
        "worker's affinity entries and sheds in-flight handoffs with",
        "zero leaked blocks.",
        "",
        "Decoding is per-request *data* on the same compiled engine.",
        "Every `submit()` (and `POST /v1/generate`) accepts",
        "`temperature` / `top_k` / `top_p` / `stop` / `seed` /",
        "`json_mode` — a `serving.DecodeParams` per request — and the",
        "engine batches them into fixed-shape per-slot tensors fed to",
        "the jitted steps as plain inputs, so greedy, sampled and",
        "constrained rows mix freely in one batch of one executable:",
        "zero new compiles, an invariant",
        "`predict_serving_compiles(sampling=...)` encodes and CI",
        "asserts. Per-request `jax.random` keys derive from the seed",
        "alone and advance functionally inside the step (fixed fan-out",
        "per row per step), so sampled output is a pure function of",
        "the request — engine restarts, replica routing and the",
        "disaggregated fleet replay the same bytes, and `temperature",
        "0` rows stay bit-identical to the pre-sampling engine.",
        "A step whose live rows are all greedy does not pay for the",
        "sampler: the top-k / top-p chain and the draws sit under one",
        "`lax.cond` on \"does any row sample\" inside the one compiled",
        "step (a device value read off the step's own `samp` input: no",
        "flag, no second executable), so such a step runs the argmax",
        "and the key split alone, and one sampled row in the batch",
        "brings the chain back for every row, as before.",
        "`STAT_serving_sampler_skipped` counts the decode/verify",
        "dispatches whose batch was all greedy, and `engine.stats()`",
        "gives `sampler_dispatches` / `sampler_skipped`.",
        "The step's inputs stay on the device: sampling parameters,",
        "the zero mask, block tables and LoRA pages are sent again",
        "only when the batch's membership,",
        "a table (`cache.tables_version`) or a grammar cursor changed,",
        "and the next step's tokens and keys are the last step's own",
        "outputs while the batch stands (the request's host-side key",
        "stays authoritative). Nothing selects this: a step re-sends",
        "what the engine observes to have changed. `engine.stats()`",
        "gives `inputs_dispatches` / `inputs_resident` (dispatches",
        "that copied nothing to the device but their own tokens /",
        "lengths; `STAT_serving_inputs_resident`).",
        "Speculative decoding verifies sampled rows by rejection",
        "sampling: the committed-token law matches non-speculative",
        "sampling exactly (greedy rows keep the prefix-match rule,",
        "token-identical). `json_mode` is constrained decoding:",
        "construct the engine with a `serving.JsonGrammar` (a",
        "char-level pushdown over an explicit id -> string token",
        "table; `json_token_strings(vocab)` is the canonical one) and",
        "masked rows emit syntactically valid JSON by construction —",
        "the budget-aware mask only opens transitions completable",
        "within the request's remaining tokens. Multi-tenant LoRA",
        "applies the block-table trick to weights:",
        "`FLAGS_serving_lora_rank` > 0 builds a paged",
        "`serving.LoRAPool` of per-tenant low-rank adapter factors",
        "(page 0 = base, all-zero), requests name a `tenant`, and the",
        "per-slot page ids plus the pool arrays ride the compiled",
        "steps as two more plain inputs — per-row adapter deltas are",
        "gathered inside the step, so tenants share one engine, one",
        "KV pool and one executable. `load_adapter()` /",
        "`evict_adapter()` are functional pool writes at runtime",
        "(eviction refuses while a tenant has in-flight requests;",
        "`leaked()` must be zero after drain, chaos included);",
        "routers auto-create one shared pool across replicas and",
        "roles, resolving tenants by name so page ids never travel.",
        "`engine.stats()` reports per-tenant goodput under `tenants`",
        "and the adapter roster under `lora`; `GET /metrics` grows",
        "the `serving_lora_adapters_loaded` gauge; the run log",
        "records `serving_lora_load` events; and",
        "`tools/loadgen.py --tenant-mix base:0.5,acme:0.3,zeta:0.2",
        "--sample-frac 0.5 --lora-rank 2` drives the mixed-tenant",
        "sampled workload with per-tenant goodput in the report and a",
        "`--expect-zero-new-compiles` gate.",
        "",
        "The request lifecycle is robust end to end. `cancel(rid)` (or",
        "`DELETE /v1/requests/<id>`; a broken client pipe cancels too)",
        "terminates a request at whatever stage it has reached —",
        "queued, mid-prefill, awaiting handoff, or mid-decode —",
        "releasing every KV block and LoRA pin, purging affinity",
        "entries and deduping re-homed copies; it is idempotent and",
        "pure host-side queue/slot surgery (zero new compiles,",
        "`predict_serving_compiles(cancel=N)` is a validated no-op),",
        "and the accounting identity extends to `completed + rehomed +",
        "shed + canceled == offered`. `submit(deadline_ms=...)` is a",
        "hard end-to-end deadline carried through handoffs and",
        "re-homes: every stage boundary and every between-steps reap",
        "sweep enforces it, so an expired request is canceled — not",
        "completed — within one step and its slot admits waiting work",
        "in that same step. Tail latency is hedged",
        "(`FLAGS_serving_hedge_ms`; negative = auto from the live TTFT",
        "p95): when the router predicts a slow first token it arms a",
        "hedge, fires a clone to the second-best replica after the",
        "delay, takes whichever first token lands first and cancels",
        "the loser leak-free (`canceled{reason=hedge_lose}`), with",
        "fired volume bounded by a `FLAGS_serving_hedge_budget` token",
        "bucket (`fired <= 1 + budget * offered`). Retries on the",
        "serving hot paths (`serving.route | serving.handoff |",
        "serving.replica`) share one fleet-wide `RetryBudget`",
        "(`FLAGS_retry_budget_*`): successes fund retries, correlated",
        "failure drains the bucket and sheds would-be storms as",
        "backpressure, and a per-replica circuit breaker stops routing",
        "to repeat offenders. Observability rides along:",
        "`serving_canceled_total{reason=}`,",
        "`serving_hedges_total{outcome=}` and",
        "`serving_retry_budget_remaining` on `GET /metrics`,",
        "`serving_cancel` / `serving_hedge` run-log events, and",
        "cancel / hedge / hedge_win / hedge_lose trace marks.",
        "`tools/loadgen.py --closed-loop N --abandon-frac F` makes a",
        "seeded subset of clients hang up mid-decode (abandonment",
        "rides the trace, so replays reproduce the cancels byte-",
        "identically), `--straggler I:MS --hedge-ms D` races hedges",
        "against a deterministic slow replica, and CI gates the lot:",
        "hedged goodput must beat unhedged under a straggler + chaos",
        "kill + 10% abandonment at zero leaks and zero new compiles,",
        "and the soak re-asserts the extended identity and the hedge",
        "budget envelope.",
        "",
        "Session capacity scales past HBM through the host-RAM KV",
        "tier (`FLAGS_serving_host_tier`, serving/kv_tier.py): a",
        "fleet-shared `serving.HostBlockStore` parks cold prefix",
        "chains in pinned host memory, int8-at-rest on the same",
        "absmax grid the device pool quantizes with, behind a",
        "refcounted allocator whose `leaked()` must read zero after",
        "drain just like the device pool's. A `serving.TierManager`",
        "demotes idle chains between steps (LRU, leaf-first,",
        "double-buffered staging copies off the step path; cadence",
        "via `FLAGS_serving_demote_idle_ms`), promotes them back",
        "all-or-nothing on demand at admission, and dedups fleet-wide",
        "— two workers demoting the same system prompt store it once.",
        "`submit(session=...)` turns that into resumable",
        "conversations: the engine stores each finished turn's",
        "context in a `serving.SessionStore`, prepends it to the next",
        "turn, and re-prefills only the unshared suffix, so a",
        "demoted conversation resumes *token-identically* (spec K>0,",
        "int8 device KV and LoRA tenant pins included) and concurrent",
        "sessions are bounded by host blocks, not device blocks.",
        "Routers build ONE tier across replicas and roles, the fleet",
        "prefix index keeps a killed worker's entries alive as",
        "host-tier markers whenever the chain is still promotable,",
        "and migration faults (`serving.migrate`) retry per",
        "`RetryPolicy` without leaking either tier. Every migration",
        "is host-side numpy/block surgery —",
        "`predict_serving_compiles(host_tier=True, sessions=N)` is a",
        "validated no-op. `GET /metrics` grows",
        "`serving_kv_migrations{dir=}`, tier-labelled block gauges",
        "and `serving_sessions_{resident,host,resumed}`; the run log",
        "records `serving_kv_demote` / `serving_kv_promote` /",
        "`serving_session_resume`; and `tools/loadgen.py",
        "--returning-frac F --turns-per-session A:B --host-blocks N`",
        "drives seeded multi-turn sessions with idle gaps (session",
        "rows ride the trace for byte-identical replay) and gates",
        "resumed sessions, zero leaks on both tiers, zero new",
        "compiles after warmup, and peak concurrent sessions above",
        "the device pool's block count.",
        "",
        "Flags:",
        "",
    ]
    defs = flags.list_flags()
    for name in sorted(defs):
        if name.startswith("serving_"):
            d = defs[name]
            lines.append(bullet(
                f"`FLAGS_{name}` (default `{d['default']}`)", d["help"]))
    lines += [
        "",
        "Tuning `FLAGS_serving_spec_tokens`: each verify step scores",
        "K+1 positions whether or not the drafts are accepted, so the",
        "win is `(1 + K * acceptance_rate)` tokens per step against a",
        "step that costs slightly more than plain decode. Watch",
        "`spec_acceptance_rate` in `GET /v1/stats`: repetitive or",
        "templated traffic (code, markup, retrieval-augmented answers)",
        "sustains 0.5+ and profits from K of 4-8; low-entropy-free chat",
        "traffic near 0.2 wants K of 2-3 or 0. Each request reserves K",
        "rows of slot headroom, so `prompt + max_new_tokens + K` must",
        "fit in `FLAGS_serving_max_len`.",
        "",
        "Fault sites (see Fault tolerance for the spec grammar):",
        "",
    ]
    lines += [bullet(f"`{site}`", doc)
              for site, doc in FAULT_SITE_DOCS.items()
              if site.startswith("serving.")]
    return "\n".join(lines)


def sync_serving_block(text, check):
    """Returns (new_text, drift_message_or_None)."""
    try:
        b = text.index(_SERVING_BEGIN) + len(_SERVING_BEGIN)
        e = text.index(_SERVING_END)
    except ValueError:
        raise SystemExit("README serving markers not found")
    current = text[b:e].strip("\n")
    want = render_serving_block()
    if current == want:
        print("README serving block in sync")
        return text, None
    if check:
        return text, ("README serving block DRIFTS from the serving "
                      "flag/site registries — rerun tools/sync_readme.py")
    print("README serving block regenerated")
    return text[:b] + "\n" + want + "\n" + text[e:], None


_TRAINSERVE_BEGIN = "<!-- BEGIN GENERATED: train-serve -->"
_TRAINSERVE_END = "<!-- END GENERATED: train-serve -->"
_TRAINSERVE_FLAGS = ("zero_stage",)


def render_trainserve_block():
    """ZeRO optimizer plane + live weight hot-swap, with the
    `zero_*` flag rows pulled from the live flag registry."""
    import textwrap
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu import flags

    def bullet(head, body):
        return "\n".join(textwrap.wrap(
            f"- {head} — {body}", width=76, subsequent_indent="  "))

    lines = [
        "Training and serving close into one loop: train with the",
        "optimizer state ZeRO-sharded across the data axis, publish the",
        "weights through a checkpoint, and hot-swap them into a",
        "*running* `ServingEngine` without draining requests or paying",
        "a single new XLA compile.",
        "",
        "`paddle_tpu.distributed.zero.zero_train_step(fn, layers=...,",
        "optimizers=..., mesh=..., stage=...)` is a drop-in for",
        "`jit.to_static` that implements ZeRO-1/2 purely with",
        "pjit/`NamedSharding` — no `shard_map`, no hand-written",
        "collectives. `sharding.opt_state_shardings(...)` assigns each",
        "Adam moment a `PartitionSpec` with the data axis added to its",
        "first divisible free dimension (`zero_partition_spec`), so",
        "GSPMD materializes each device's 1/dp optimizer shard and",
        "inserts the gather; stage 2 additionally annotates gradients",
        "with the same specs, turning the grad all-reduce into a",
        "reduce-scatter. Undivisible tensors fall back to their base",
        "spec (replicated moments), scalars (`_lr`, Adam step counts)",
        "stay replicated, and tensor-parallel param rules compose:",
        "moments shard on BOTH the TP axis and the data axis. The",
        "wrapper publishes live per-device byte accounting",
        "(`zero_opt_bytes` / `zero_opt_bytes_per_device` gauges,",
        "measured from `addressable_shards`, plus",
        "`zero.byte_report(...)`), and",
        "`tools/lint_sharding.py --zero-stage N` folds the same",
        "estimate into the lint report before any training run.",
        "",
        "The serve half: `zero.save_train_state(saver, layers,",
        "optimizers, step)` gathers the sharded optimizer state and",
        "writes one `CheckpointSaver` checkpoint (params under",
        "`param/<name>`, moments under `opt<i>/<key>`, the ZeRO stage",
        "in metadata); `zero.weights_from_checkpoint(state)` strips it",
        "back to a `{name: array}` mapping; and",
        "`ServingEngine.swap_weights(weights, reset_costs=True)`",
        "installs the new weights between engine steps under the step",
        "lock — names/shapes validated, arrays re-placed onto the",
        "engine's mesh per the `serving_tp` rules, the admission",
        "controller's learned cost model optionally reset. Because",
        "every compiled prefill/decode/verify step takes the params as",
        "a donated *input* (not a closure constant), the unified step",
        "cache is untouched: a swap costs ZERO new compiles —",
        "`analysis.predict_serving_compiles(..., weight_swaps=N)` is a",
        "validated no-op — and the next step serves the new weights.",
        "`ReplicaRouter.swap_weights(...)` rolls the swap across",
        "replicas one engine at a time (drain-free; stragglers keep",
        "serving the old version until their turn). Each swap bumps the",
        "`serving_weight_version` gauge and logs a",
        "`serving_weight_swap` run-log event.",
        "",
        "`tools/zero_smoke.py` (CI gate) trains 2 ZeRO steps at dp=2,",
        "asserts per-device optimizer bytes ~1/2 of total with",
        "loss-for-loss parity against the unsharded baseline, then",
        "publishes and hot-swaps into a live engine asserting",
        "token-correct output and 0 compiles.",
        "",
        "Flags:",
        "",
    ]
    defs = flags.list_flags()
    for name in _TRAINSERVE_FLAGS:
        d = defs[name]
        lines.append(bullet(
            f"`FLAGS_{name}` (default `{d['default']}`)", d["help"]))
    return "\n".join(lines)


def sync_trainserve_block(text, check):
    """Returns (new_text, drift_message_or_None)."""
    try:
        b = text.index(_TRAINSERVE_BEGIN) + len(_TRAINSERVE_BEGIN)
        e = text.index(_TRAINSERVE_END)
    except ValueError:
        raise SystemExit("README train-serve markers not found")
    current = text[b:e].strip("\n")
    want = render_trainserve_block()
    if current == want:
        print("README train-serve block in sync")
        return text, None
    if check:
        return text, ("README train-serve block DRIFTS from the "
                      "zero/flag registries — rerun "
                      "tools/sync_readme.py")
    print("README train-serve block regenerated")
    return text[:b] + "\n" + want + "\n" + text[e:], None


_OBS_BEGIN = "<!-- BEGIN GENERATED: observability -->"
_OBS_END = "<!-- END GENERATED: observability -->"
_OBS_FLAGS = ("warn_recompiles", "runlog_dir", "runlog_max_mb",
              "serving_trace", "serving_trace_keep")


def render_observability_block():
    """Instrument inventory + run-log event kinds + flags, from the
    live registries (observability.INSTRUMENT_DOCS / EVENT_DOCS and
    paddle_tpu/flags.py)."""
    import textwrap
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu import flags, observability

    def bullet(head, body):
        return "\n".join(textwrap.wrap(
            f"- {head} — {body}", width=76, subsequent_indent="  "))

    lines = [
        "`paddle_tpu.observability` is the one metrics plane the whole",
        "framework reports into: a thread-safe registry of typed",
        "Counter / Gauge / Histogram instruments (fixed log-scale",
        "buckets, so p50/p95/p99 are derivable without storing",
        "samples), an XLA compile tracker wrapping every `jax.jit`",
        "entry point (`observability.compiles()` gives per-site compile",
        "counts, wall time, the abstract shape/dtype signature that",
        "triggered each compile, and the account by stage: `trace_ms`,",
        "`lower_ms`, `compile_ms`, `programs`, `cache_hits`,",
        "`cache_misses`, `cache_retrieval_ms`, with what was built",
        "outside every site under `(untracked)`;",
        "`observability.compile_totals()` sums them over the process,",
        "and `engine.stats()` / `GET /v1/stats` carries those sums as",
        "`programs_built`, `programs_trace_ms`, `programs_lower_ms`,",
        "`programs_compile_ms`, `programs_cache_hits`,",
        "`programs_cache_misses`; with the profiler on a call that built",
        "a program leaves one `program.build` span, args `site`,",
        "`trace_ms`, `lower_ms`, `compile_ms`, `cache_hit`, and every",
        "trace runs under a `program.trace` span, which the device trace",
        "shows too), a structured JSONL run log",
        "(`observability.log_event(kind, **fields)`), and exporters:",
        "`observability.prometheus_text()` served at `GET /metrics` on",
        "`ServingHTTPServer`, `observability.snapshot()` embedded in",
        "`BENCH_*.json`, and counter/histogram summaries appended to",
        "`profiler.stop_profiler()`'s table. The `monitor.stat_*` API",
        "is a shim over the same registry.",
        "",
        "Per-request tracing rides on top",
        "(`paddle_tpu.observability.tracing`): every sampled request",
        "(`FLAGS_serving_trace`, default everything) carries its id",
        "from `submit()` through admit / prefill / handoff / decode /",
        "re-home / finish-or-shed as host-side `(kind, t, track)` marks",
        "on the engine's own clock (wall or the soak harness's virtual",
        "clock — never a jit input, so tracing is a validated",
        "zero-compile no-op: `predict_serving_compiles(...,",
        "tracing=True)`). A kill stitches the survivor's spans onto the",
        "original trace, so a re-homed request is ONE timeline whose",
        "re-home penalty is its own blame component. `tracing.blame()`",
        "decomposes each finished request's E2E into queue | prefill |",
        "decode | handoff | rehome components that sum *exactly* to the",
        "measured E2E (and the prefix up to the first token exactly to",
        "TTFT) — an accounting identity, not an approximation;",
        "`blame_summary()` aggregates fleet-wide shares, p95s and the",
        "component that dominates the E2E-p95 tail.",
        "`export_chrome_trace()` writes a Perfetto-loadable chrome",
        "trace — one named track per engine/replica/role, one flow per",
        "request stitching its spans across tracks — and",
        "`export_spans_jsonl()` the same spans as JSONL; both",
        "canonicalize ids and track names so two same-seed virtual-",
        "clock runs export byte-identical files (a CI flake guard).",
        "`python tools/trace_summary.py TRACE --blame` prints the",
        "component blame table from either export;",
        "`GET /v1/requests/<id>` on `ServingHTTPServer` serves one",
        "request's live timeline + blame (404 once evicted from the",
        "`FLAGS_serving_trace_keep` ring); and",
        "`tracing.window_snapshots(...)` folds finished traces into",
        "per-window TTFT histograms, SLO attainment and burn rate",
        "(`(1 - attainment) / (1 - target)`) — the",
        "`serving_slo_burn_rate` gauge and the per-window report of",
        "`tools/soak.py --trace-out`.",
        "",
        "Instruments:",
        "",
    ]
    lines += [bullet(f"`{name}`", doc)
              for name, doc in observability.INSTRUMENT_DOCS.items()]
    lines += [
        "",
        "Run-log event kinds (one JSON line each, stamped with a",
        "monotonic `seq`/`ts`/`mono`; summarize with",
        "`python tools/trace_summary.py <runlog.jsonl>`, which also",
        "reads the profiler's chrome-trace JSON):",
        "",
    ]
    lines += [bullet(f"`{kind}`", doc)
              for kind, doc in observability.EVENT_DOCS.items()]
    lines += [
        "",
        "Example scrape:",
        "",
        "```",
        "$ curl -s localhost:$PORT/metrics | grep -m4 -E 'serving|compiles'",
        "# TYPE STAT_serving_tokens counter",
        "STAT_serving_tokens 128",
        "# TYPE xla_compiles counter",
        'xla_compiles{bucket="16",fn="serving_prefill_paged"} 1',
        "```",
        "",
        "Flags:",
        "",
    ]
    defs = flags.list_flags()
    for name in _OBS_FLAGS:
        d = defs[name]
        lines.append(bullet(
            f"`FLAGS_{name}` (default `{d['default']}`)", d["help"]))
    return "\n".join(lines)


def sync_observability_block(text, check):
    """Returns (new_text, drift_message_or_None)."""
    try:
        b = text.index(_OBS_BEGIN) + len(_OBS_BEGIN)
        e = text.index(_OBS_END)
    except ValueError:
        raise SystemExit("README observability markers not found")
    current = text[b:e].strip("\n")
    want = render_observability_block()
    if current == want:
        print("README observability block in sync")
        return text, None
    if check:
        return text, ("README observability block DRIFTS from the "
                      "observability/flag registries — rerun "
                      "tools/sync_readme.py")
    print("README observability block regenerated")
    return text[:b] + "\n" + want + "\n" + text[e:], None


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--check", action="store_true",
                   help="fail on drift instead of rewriting")
    args = p.parse_args()

    readme = os.path.join(REPO, "README.md")
    with open(readme) as f:
        text = f.read()
    orig = text
    drifts = []
    for sync in (sync_headline, sync_checks_block, sync_fault_block,
                 sync_serving_block, sync_trainserve_block,
                 sync_observability_block):
        text, drift = sync(text, args.check)
        if drift:
            drifts.append(drift)
    if drifts:
        print("\n".join(drifts))
        return 1
    if text != orig:
        with open(readme, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
