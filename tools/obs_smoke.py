#!/usr/bin/env python
"""CI observability gate: tiny train + serving smoke under the run log.

Asserts, end to end through the observability plane:
  - a guarded training run (with one injected-NaN batch) emits
    train_step / guardian_skip / fault_injected run-log events;
  - a serving run emits serving_admit / serving_finish events;
  - the compile tracker reports decode_step_paged compile-count == 1
    and the batched same-bucket paged prefill dispatched exactly once
    (the PR 3/4 invariants, regression-locked via the new plane);
  - a repeated prompt scores a prefix-cache hit (STAT_serving_prefix_hits)
    without adding a single compile;
  - rerunning the same workload with FLAGS_serving_kv_dtype=int8 (the
    paged kernel in interpret mode over the quantized KV pool) stays
    token-identical, retraces each site exactly
    once (flags-version keying), and the merged two-phase recompile
    prediction still equals the live tracker;
  - the same workload through two ReplicaRouter replicas (shared model
    => shared step cache: two replicas compile like one engine) and
    through a 1x1 ("data", "model") serving mesh (new mesh cache key:
    exactly one more compile per site) stays token-identical, with the
    merged four-phase prediction still equal to the tracker;
  - a seeded bursty loadgen run through an engine with SLO-aware
    admission (constructor-arg SLO/pins/priorities, never set_flags)
    completes with goodput > 0, zero leaked KV blocks and ZERO new
    compiles — and the recompile predictor agrees the admission
    parameters are no-ops;
  - the same workload through a 1 prefill x 2 decode DisaggRouter
    fleet stays token-identical with ZERO new compiles (role-split
    engines share the symmetric engines' step cache), scores a
    prefix-affinity routing hit on the repeated prompt, leaks no KV
    blocks, and matches the predictor's ``disagg`` no-op claim;
  - a kill -> re-home -> restart episode on a 2-replica router: the
    killed replica's work finishes token-identically on the survivor,
    health states and re-home counters publish to /metrics and the
    run log, the tracker does not move, and the predictor agrees
    replica_kills/restarts/rehomed are no-ops;
  - a live weight hot-swap (``swap_weights``) into the still-warm
    loadgen engine adds zero compiles, decodes the new weights'
    greedy tokens, and matches the predictor's ``weight_swaps``
    no-op claim;
  - mixed greedy / sampled / JSON-constrained / two-tenant-LoRA
    traffic on one engine (pool geometry via set_flags = one fresh
    phase like the int8 one): the json_mode row decodes to valid JSON,
    tenants diverge from base, a mid-flight ``load_adapter`` and the
    whole second wave add ZERO compiles, the per-phase compile delta
    equals the predictor's claim (``sampling`` recipes are validated
    no-ops, ``lora`` geometry is one retrace), and neither KV blocks
    nor adapter pages leak;
  - per-request tracing (FLAGS_serving_trace, default-on) on a traced
    burst through a fresh engine: every finished request's blame
    decomposition sums exactly to its measured E2E (the accounting
    identity in paddle_tpu/observability/tracing.py), the chrome-trace
    export is a Perfetto-loadable document with one flow per request,
    GET /v1/requests/<id> serves the span timeline (and 404s unknown
    ids), and the predictor agrees ``tracing`` never compiles —
    per-phase predicted counts equal the live tracker;
  - the static serving lint (``analysis.lint_serving``) reports zero
    findings on the shipped fleet, and replaying the loadgen workload
    under ``FLAGS_sanitize_locks=1`` keeps goodput within 5% of the
    plain run, records zero lock-order cycles / guarded-state
    violations over nonzero instrumented acquires, and matches the
    predictor's ``sanitize`` no-op claim (predicted == observed);
  - a cancel/hedge episode on a hedging 2-replica router (one hedge
    race fired and won against a deterministic straggler; cancels at
    the queued and mid-decode stages plus the race's loser) leaks
    nothing, logs serving_cancel / serving_hedge events, mints the
    canceled/hedge/retry-budget metrics, and matches the predictor's
    ``cancel``/``hedge`` no-op claims (predicted == observed);
  - a host-KV-tier session episode (FLAGS_serving_host_tier, explicit
    ``kv_tier=``): a two-turn session is demoted to host RAM by the
    idle sweep, resumed token-identically (the resumed turn equals
    replaying the stored conversation as a plain prompt), drains both
    tiers leak-free, logs serving_kv_demote / serving_kv_promote /
    serving_session_resume events, mints the migration/session
    metrics, and matches the predictor's ``host_tier``/``sessions``
    validated-no-op claim (predicted == observed);
  - a device-resident decode-megastep episode
    (FLAGS_serving_megastep=4 + FLAGS_serving_dispatch_ahead): N
    decode iterations per compiled dispatch stay token-identical to a
    megastep=1 engine at the same flags version, the decode plane
    traces exactly its TWO predicted surfaces (the megastep entry and
    the single-token fallback a caps-exceeding stop list forces), no
    KV blocks leak, and predict_serving_compiles(megastep=4) equals
    the live tracker;
  - GET /metrics on ServingHTTPServer parses as Prometheus text and
    carries serving, fault, compile, KV block-pool, attention-impl,
    int8-quantization, SLO-admission and tracing metrics;
  - tools/trace_summary.py consumes the emitted JSONL run log.

Run from the repo root:  JAX_PLATFORMS=cpu python tools/obs_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="obs_smoke_")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import layers, monitor, observability
    from paddle_tpu.framework import (Executor, Program, Scope,
                                      program_guard, unique_name)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import runlog
    from paddle_tpu.optimizer import SGDOptimizer
    from paddle_tpu.resilience import TrainGuardian, fault_scope
    from paddle_tpu.serving import ServingEngine, ServingHTTPServer

    pt.set_flags({"runlog_dir": tmp})

    # -- tiny train under the guardian, with one injected NaN batch ----
    main_p, startup = Program(), Program()
    main_p.random_seed = startup.random_seed = 5
    with program_guard(main_p, startup), unique_name.guard():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        SGDOptimizer(0.1).minimize(loss)
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    pt.set_flags({"check_nan_inf": True})
    try:
        with fault_scope("exec.step:nan@3"):
            guardian = TrainGuardian(exe, main_p, scope)
            for _ in range(5):
                xb = rng.rand(8, 4).astype(np.float32)
                yb = (xb.sum(1, keepdims=True) +
                      rng.rand(8, 1).astype(np.float32) * 0.1)
                guardian.step(feed={"x": xb, "y": yb},
                              fetch_list=[loss.name])
    finally:
        pt.set_flags({"check_nan_inf": False})
    assert guardian.skipped == 1, guardian.skipped
    print(f"   train: {guardian.steps_done} steps, "
          f"{guardian.skipped} NaN skip")

    # -- serving smoke: 3 same-bucket prompts through 3 slots ----------
    pt.seed(7)
    cfg = GPTConfig(vocab_size=97, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, max_slots=3, max_len=32,
                        buckets=[8, 16], max_queue=16, block_size=4)
    prompts = [rng.randint(1, 97, size=n).tolist() for n in (3, 5, 7)]
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.step()
    prefill_calls = monitor.stat_get("STAT_serving_prefill_calls")
    assert prefill_calls == 1, (
        f"expected ONE batched prefill dispatch, saw {prefill_calls}")
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)

    comp = observability.compiles()
    assert comp["decode_step_paged"]["count"] == 1, \
        comp.get("decode_step_paged")
    assert comp["serving_prefill_paged{bucket=8}"]["count"] == 1, comp
    assert comp["decode_step_paged"]["last_signature"], \
        "no compile signature"
    print(f"   compile tracker: decode_step_paged=1, "
          f"prefill_paged{{bucket=8}}=1 ({len(comp)} tracked sites)")

    # -- prefix-cache reuse: repeat a prompt, expect a hit -------------
    rep = eng.submit(prompts[2], max_new_tokens=4)
    eng.run_until_idle()
    assert rep.state == "done" and rep.output_ids == reqs[2].output_ids
    hits = monitor.stat_get("STAT_serving_prefix_hits")
    assert hits >= 1, f"repeated prompt scored no prefix hit ({hits})"
    comp2 = observability.compiles()
    assert comp2["decode_step_paged"]["count"] == 1, \
        "prefix reuse must not retrace decode"
    print(f"   prefix cache: repeat hit ({hits} hit admissions), "
          f"0 new compiles")

    # -- static recompile prediction == observed compile tracker ------
    # The same workload, predicted before-the-fact by the abstract
    # model in paddle_tpu/analysis/recompile.py: round 1 admits the
    # three prompts together, round 2 re-submits prompts[2] (whose
    # full-block prefix is published by then). Predicted tracked_jit
    # counts must equal the observed ones, both directions.
    from paddle_tpu.analysis import (merge_compile_counts,
                                     predict_serving_compiles)
    workload = [[(p, 4) for p in prompts], [(prompts[2], 4)]]
    predicted = predict_serving_compiles(
        workload, buckets=[8, 16], max_len=32, block_size=4)
    observed = {site: c["count"] for site, c in comp2.items()
                if site.startswith(("serving_", "decode_", "verify_"))}
    assert predicted == observed, (
        f"recompile prediction drifted from the live tracker:\n"
        f"  predicted {predicted}\n  observed  {observed}")
    print(f"   recompile predictor: {predicted} == observed")

    # -- int8 phase: same workload, the paged kernel over the quantized
    # KV pool. set_flags bumps the flags version, so each site retraces
    # exactly once; outputs must stay token-identical and the merged
    # two-phase prediction must equal the tracker.
    pt.set_flags({"serving_kv_dtype": "int8"})
    try:
        eng2 = ServingEngine(model, max_slots=3, max_len=32,
                             buckets=[8, 16], max_queue=16, block_size=4)
        reqs2 = [eng2.submit(p, max_new_tokens=4) for p in prompts]
        eng2.run_until_idle()
        rep2 = eng2.submit(prompts[2], max_new_tokens=4)
        eng2.run_until_idle()
        for a, b in zip(reqs + [rep], reqs2 + [rep2]):
            assert a.output_ids == b.output_ids, (
                f"int8 diverged on request {b.id}: "
                f"{a.output_ids} vs {b.output_ids}")
        st2 = eng2.stats()
        assert st2["kv_dtype"] == "int8"
        assert st2["kv_quant_max_abs_err"] > 0.0, st2
        writes = monitor.stat_get("STAT_serving_kv_quant_writes")
        assert writes >= 1, writes
        predicted2 = predict_serving_compiles(
            workload, buckets=[8, 16], max_len=32, block_size=4,
            kv_dtype="int8")
        merged = merge_compile_counts(predicted, predicted2)
        comp3 = observability.compiles()
        observed3 = {site: c["count"] for site, c in comp3.items()
                     if site.startswith(("serving_", "decode_",
                                         "verify_"))}
        assert merged == observed3, (
            f"two-phase recompile prediction drifted:\n"
            f"  predicted {merged}\n  observed  {observed3}")
        print(f"   int8: token-identical, max_abs_err="
              f"{st2['kv_quant_max_abs_err']}, merged prediction == "
              f"observed")
    finally:
        pt.set_flags({"serving_kv_dtype": "f32"})

    # -- mesh + replica phase: the same workload on (a) two data-
    # parallel replicas behind the ReplicaRouter and (b) a 1x1
    # ("data", "model") serving mesh. The finally above bumped the
    # flags version, so the router's engines retrace each site once
    # (one phase) — but BOTH replicas share the model and therefore
    # the unified step cache, so two replicas add the counts of ONE
    # engine (the n_replicas invariant). The mesh engine's steps live
    # under a new mesh cache key: one more compile per site (a fourth
    # phase). Outputs must stay token-identical throughout, and the
    # four-phase merged prediction must equal the live tracker.
    from paddle_tpu.distributed.sharding import serving_mesh
    from paddle_tpu.serving import ReplicaRouter
    router = ReplicaRouter(model, n_replicas=2, max_slots=3,
                           max_len=32, buckets=[8, 16], max_queue=16,
                           block_size=4)
    reqs3 = [router.submit(p, max_new_tokens=4) for p in prompts]
    router.run_until_idle()
    rep3 = router.submit(prompts[2], max_new_tokens=4)
    router.run_until_idle()
    for a, b in zip(reqs + [rep], reqs3 + [rep3]):
        assert a.output_ids == b.output_ids, (
            f"routed replica diverged on request {b.id}: "
            f"{a.output_ids} vs {b.output_ids}")
    st3 = router.stats()
    assert st3["replicas"] == 2 and len(st3["queue_depths"]) == 2, st3
    predicted3 = predict_serving_compiles(
        workload, buckets=[8, 16], max_len=32, block_size=4,
        n_replicas=2)

    mesh = serving_mesh(1, 1)
    eng4 = ServingEngine(model, max_slots=3, max_len=32,
                         buckets=[8, 16], max_queue=16, block_size=4,
                         mesh=mesh)
    reqs4 = [eng4.submit(p, max_new_tokens=4) for p in prompts]
    eng4.run_until_idle()
    rep4 = eng4.submit(prompts[2], max_new_tokens=4)
    eng4.run_until_idle()
    for a, b in zip(reqs + [rep], reqs4 + [rep4]):
        assert a.output_ids == b.output_ids, (
            f"mesh engine diverged on request {b.id}: "
            f"{a.output_ids} vs {b.output_ids}")
    st4 = eng4.stats()
    assert st4["mesh_shape"] == [1, 1], st4
    predicted4 = predict_serving_compiles(
        workload, buckets=[8, 16], max_len=32, block_size=4,
        mesh_shape=(1, 1))
    merged4 = merge_compile_counts(predicted, predicted2, predicted3,
                                   predicted4)
    comp4 = observability.compiles()
    observed4 = {site: c["count"] for site, c in comp4.items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
    assert merged4 == observed4, (
        f"mesh-phase recompile prediction drifted:\n"
        f"  predicted {merged4}\n  observed  {observed4}")
    print(f"   mesh phase: 2 replicas + 1x1 mesh token-identical, "
          f"merged prediction == observed ({observed4})")

    # -- loadgen phase: SLO-aware admission adds ZERO compiles --------
    # A bursty open-loop workload on a virtual clock through an engine
    # with predictive admission (SLO + pinned costs + priority mix —
    # all constructor args, never set_flags, so the flags version and
    # the warm step cache survive). Prompt lengths stay inside the
    # already-compiled bucket: the tracker must not move at all, and
    # the predictor must agree that admission parameters are no-ops.
    from tools.loadgen import LoadGen, VirtualClock
    vc = VirtualClock()
    eng5 = ServingEngine(model, max_slots=3, max_len=32,
                         buckets=[8, 16], max_queue=16, block_size=4,
                         clock=vc.now, slo_ttft_ms=40.0,
                         slo_prefill_ms=4.0, slo_tpot_ms=1.0)
    lg = LoadGen(mode="bursty", rate=60.0, duration=1.0, seed=3,
                 vocab_size=97, prompt_tokens=(3, 7),
                 new_tokens=(2, 4),
                 priority_mix={0: 0.2, 1: 0.6, 2: 0.2})
    report = lg.run(eng5, clock=vc, step_cost_ms=4.0)
    assert report["offered"] > 0 and report["completed"] > 0, report
    assert report["exceptions"] == 0, report
    assert report["leaked_kv_blocks"] == 0, report
    assert report["slo_attainment"] is not None, report
    assert len(report["decisions"]) == report["offered"]
    comp5 = observability.compiles()
    observed5 = {site: c["count"] for site, c in comp5.items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
    assert observed5 == observed4, (
        f"SLO-aware admission must add ZERO compiles:\n"
        f"  before {observed4}\n  after  {observed5}")
    lg_workload = [[(list(a.prompt), a.max_new_tokens)
                    for a in lg.schedule()]]
    plain_pred = predict_serving_compiles(
        lg_workload, buckets=[8, 16], max_len=32, block_size=4)
    slo_pred = predict_serving_compiles(
        lg_workload, buckets=[8, 16], max_len=32, block_size=4,
        slo_ttft_ms=40.0, priority_classes=[0, 1, 2],
        autoscale=(1, 2))
    assert slo_pred == plain_pred, (slo_pred, plain_pred)
    print(f"   loadgen: {report['completed']}/{report['offered']} done "
          f"(goodput {report['goodput_per_s']}/s, attainment "
          f"{report['slo_attainment']}, shed {report['shed_total']}), "
          f"0 new compiles")

    # -- disagg phase: P/D role split adds ZERO compiles --------------
    # (Before the hot-swap phase: swap_weights mutates the shared
    # model in place, so the old-weight reference outputs only hold
    # until then.) The same workload through a 1 prefill x 2 decode
    # DisaggRouter at the same geometry: both roles reuse the
    # symmetric engines' compiled steps (the step cache keys on
    # geometry, never role), the KV handoff is host-side block
    # surgery, and re-submitting prompts[2] scores a prefix-affinity
    # routing hit. Token-identical, tracker frozen, predictor agrees
    # disagg is a no-op, zero leaks.
    from paddle_tpu.serving import DisaggRouter
    fleet = DisaggRouter(model, n_prefill=1, n_decode=2, max_slots=3,
                         max_len=32, buckets=[8, 16], max_queue=16,
                         block_size=4)
    reqs7 = [fleet.submit(p, max_new_tokens=4) for p in prompts]
    fleet.run_until_idle()
    rep7 = fleet.submit(prompts[2], max_new_tokens=4)
    fleet.run_until_idle()
    for a, b in zip(reqs + [rep], reqs7 + [rep7]):
        assert a.output_ids == b.output_ids, (
            f"disagg fleet diverged on request {b.id}: "
            f"{a.output_ids} vs {b.output_ids}")
    st7 = fleet.stats()
    assert st7["prefill_workers"] == 1 and st7["decode_workers"] == 2
    assert st7["handoffs_adopted"] >= len(prompts), st7
    assert st7["affinity_hits"] >= 1, st7
    comp7 = observability.compiles()
    observed7 = {site: c["count"] for site, c in comp7.items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
    assert observed7 == observed5, (
        f"disaggregated roles must add ZERO compiles:\n"
        f"  before {observed5}\n  after  {observed7}")
    disagg_pred = predict_serving_compiles(
        workload, buckets=[8, 16], max_len=32, block_size=4,
        disagg=(1, 2))
    assert disagg_pred == predicted, (disagg_pred, predicted)
    pools = {}
    for e in fleet.engines:
        pools[id(e.cache.pool)] = e.cache
    for cache in pools.values():
        cache.flush_prefix_cache()
        assert cache.allocator.leaked() == 1   # trash block only
    print(f"   disagg: 1x2 fleet token-identical, "
          f"{st7['handoffs_adopted']} handoffs "
          f"({st7['affinity_hits']} affinity hits), 0 new compiles, "
          f"0 leaked blocks")

    # -- fault-tolerance phase: kill -> re-home -> restart ------------
    # (Still before the hot-swap phase: the reference outputs hold
    # only while the shared model carries the old weights.) Load every
    # request onto replica 0, kill it: the queued work re-homes onto
    # the survivor and finishes token-identical. Then restart the
    # survivor in place. Kill + restart + re-home are host-side row
    # surgery over already-compiled buckets, so the tracker must not
    # move — and the predictor must agree the counts are no-ops.
    router9 = ReplicaRouter(model, n_replicas=2, max_slots=3,
                            max_len=32, buckets=[8, 16], max_queue=16,
                            block_size=4)
    reqs9 = [router9.engines[0].submit(p, max_new_tokens=4)
             for p in prompts]
    info9 = router9.kill_replica(0)
    assert info9["rehomed"] == len(prompts) and info9["shed"] == 0, \
        info9
    router9.run_until_idle()
    for a, b in zip(reqs, reqs9):
        assert a.output_ids == b.output_ids, (
            f"re-homed request {b.id} diverged: "
            f"{a.output_ids} vs {b.output_ids}")
    assert all(r.rehomed for r in reqs9)
    router9.restart_replica(0)
    router9.run_until_idle()
    st9 = router9.stats()
    assert st9["kills"] == 2 and st9["restarts"] == 1, st9
    assert st9["rehomed"] == len(prompts), st9
    assert st9["replicas"] == 1, st9   # restart replaces in place
    assert all(h in ("healthy", "recovering")
               for h in st9["health"]), st9
    ids9 = [r.id for r in router9.results()]
    assert len(ids9) == len(set(ids9)) == len(prompts)
    for e in router9.engines + router9._retiring:
        e.cache.flush_prefix_cache()
        assert e.cache.allocator.leaked() == 1   # trash block only
    comp9 = observability.compiles()
    observed9 = {site: c["count"] for site, c in comp9.items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
    assert observed9 == observed7, (
        f"kill/re-home/restart must add ZERO compiles:\n"
        f"  before {observed7}\n  after  {observed9}")
    ft_pred = predict_serving_compiles(
        workload, buckets=[8, 16], max_len=32, block_size=4,
        n_replicas=2, replica_kills=2, restarts=1,
        rehomed=len(prompts))
    assert ft_pred == predicted3, (ft_pred, predicted3)
    print(f"   fault tolerance: kill -> {info9['rehomed']} re-homed "
          f"token-identical -> restart, health {st9['health']}, "
          f"0 new compiles (predicted == observed)")

    # -- hot-swap phase: live weight swap adds ZERO compiles ----------
    # Publish fresh weights into the still-warm loadgen engine: the
    # compiled steps take weights as explicit jit inputs, so the
    # tracker must not move, post-swap traffic must decode the NEW
    # model's greedy tokens, and the predictor must agree that
    # weight_swaps is a no-op.
    from paddle_tpu.models.generation import greedy_search
    pt.seed(23)
    swap_model = GPTForCausalLM(cfg)
    swap_model.eval()
    version = eng5.swap_weights(
        {n: p.value for n, p in swap_model.named_parameters()})
    assert version == 1 and eng5.weight_version == 1
    p_swap = rng.randint(1, 97, size=5).tolist()
    r_swap = eng5.submit(p_swap, max_new_tokens=4)
    eng5.run_until_idle()
    comp6 = observability.compiles()
    observed6 = {site: c["count"] for site, c in comp6.items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
    assert observed6 == observed9, (
        f"live weight swap must add ZERO compiles:\n"
        f"  before {observed9}\n  after  {observed6}")
    ref_swap = greedy_search(swap_model, np.asarray([p_swap]),
                             max_new_tokens=4,
                             cache_len=32)[0].tolist()
    assert r_swap.output_ids == ref_swap, (
        "post-swap tokens != new-weight greedy")
    swap_pred = predict_serving_compiles(
        lg_workload, buckets=[8, 16], max_len=32, block_size=4,
        weight_swaps=1)
    assert swap_pred == plain_pred, (swap_pred, plain_pred)
    print(f"   hot swap: v{version} live, tokens match the new "
          f"weights, 0 new compiles (predicted == observed)")

    # -- decoding phase: sampling-as-data + multi-tenant paged LoRA ---
    # set_flags bumps the flags version (like the int8 phase) and the
    # adapter pool joins the step cache key, so the lora-shaped steps
    # retrace exactly once; after that first wave, mixed greedy /
    # sampled / json-constrained / multi-tenant traffic — including a
    # mid-flight load_adapter — must never move the tracker again, and
    # the predictor must agree sampling recipes are no-ops while the
    # lora geometry is one fresh phase.
    from paddle_tpu.serving import (JsonGrammar, json_token_strings,
                                    make_adapter)
    grammar = JsonGrammar(json_token_strings(97))
    # fresh baseline: the hot-swap phase's offline greedy reference
    # traced the dense decode_step after its own snapshot
    base8 = {site: c["count"]
             for site, c in observability.compiles().items()
             if site.startswith(("serving_", "decode_", "verify_"))}
    pt.set_flags({"serving_lora_rank": 2,
                  "serving_lora_max_adapters": 2})
    try:
        eng8 = ServingEngine(model, max_slots=3, max_len=32,
                             buckets=[8, 16], max_queue=16,
                             block_size=4, grammar=grammar)
        eng8.load_adapter("acme", make_adapter(cfg, 2, seed=1,
                                               scale=0.5))
        r_base = eng8.submit(prompts[2], max_new_tokens=4)
        r_samp = eng8.submit(prompts[1], max_new_tokens=4,
                             temperature=0.9, top_k=8, seed=11)
        r_acme = eng8.submit(prompts[2], max_new_tokens=4,
                             tenant="acme")
        eng8.run_until_idle()
        assert r_acme.output_ids != r_base.output_ids, (
            "tenant adapter did not change the decode")
        wave1 = {site: c["count"]
                 for site, c in observability.compiles().items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
        eng8.load_adapter("zeta", make_adapter(cfg, 2, seed=2,
                                               scale=0.5))
        r_json = eng8.submit(prompts[0], max_new_tokens=8,
                             json_mode=True)
        r_zeta = eng8.submit(prompts[2], max_new_tokens=4,
                             tenant="zeta")
        eng8.run_until_idle()
        doc = grammar.decode(r_json.tokens)
        json.loads(doc)   # valid JSON by construction
        assert r_zeta.output_ids != r_acme.output_ids, (
            "tenants decoded identically")
        wave2 = {site: c["count"]
                 for site, c in observability.compiles().items()
                 if site.startswith(("serving_", "decode_", "verify_"))}
        assert wave2 == wave1, (
            f"mixed decode traffic + adapter load must add ZERO "
            f"compiles:\n  before {wave1}\n  after  {wave2}")
        delta8 = {site: n - base8.get(site, 0)
                  for site, n in wave2.items()
                  if n - base8.get(site, 0)}
        workload8 = [[(prompts[2], 4), (prompts[1], 4),
                      (prompts[2], 4)],
                     [(prompts[0], 8), (prompts[2], 4)]]
        predicted8 = predict_serving_compiles(
            workload8, buckets=[8, 16], max_len=32, block_size=4,
            sampling=[(0.9, 8, 1.0)], lora=(2, 2))
        assert delta8 == predicted8, (
            f"decoding-phase recompile prediction drifted:\n"
            f"  predicted {predicted8}\n  observed  {delta8}")
        st8 = eng8.stats()
        assert set(st8["lora"]["loaded"]) == {"acme", "zeta"}, \
            st8["lora"]
        assert st8["lora"]["leaked_pages"] == 0, st8["lora"]
        assert st8["json_grammar"] is True, st8
        assert set(st8["tenants"]) == {"base", "acme", "zeta"}, (
            st8["tenants"])
        assert eng8.lora_pool.leaked() == 0
        eng8.cache.flush_prefix_cache()
        assert eng8.cache.allocator.leaked() == 1   # trash block only
        print(f"   decoding: sampled/json/2-tenant mix on one engine, "
              f"json doc {doc!r} valid, 0 new compiles after the lora "
              f"phase ({delta8} == predicted)")
    finally:
        pt.set_flags({"serving_lora_rank": 0})

    # -- tracing phase: spans, blame identity, Perfetto, debug API ----
    # FLAGS_serving_trace defaults to 1.0, so every request above was
    # already traced — host-side (kind, t, track) marks on the engine
    # clock, never a jit input. Reset the ring and run a traced burst
    # on a fresh engine at the warm geometry: the decoding phase's
    # finally bumped the flags version, so each site retraces exactly
    # once (a fresh phase, like the int8 one) and the per-phase
    # delta must equal the predictor's claim WITH tracing=True — which
    # must itself equal the prediction without it (the no-op family).
    # Every finished request's blame components must sum exactly to
    # its measured E2E, the chrome export must be a Perfetto document
    # with flow events stitching each request across tracks, and the
    # HTTP debug endpoint must serve the timeline.
    from paddle_tpu.observability import tracing
    tracing.reset()
    baseT = {site: c["count"]
             for site, c in observability.compiles().items()
             if site.startswith(("serving_", "decode_", "verify_"))}
    engT = ServingEngine(model, max_slots=3, max_len=32,
                         buckets=[8, 16], max_queue=16, block_size=4)
    reqsT = [engT.submit(p, max_new_tokens=4) for p in prompts]
    engT.run_until_idle()
    assert all(r.state == "done" for r in reqsT)
    for r in reqsT:
        info = tracing.get(r.id)
        assert info is not None and info["outcome"] == "done", info
        gap = abs(sum(info["blame_ms"].values()) - info["e2e_ms"])
        assert gap < 1e-6, (
            f"blame identity broke on request {r.id}: components "
            f"{info['blame_ms']} vs e2e {info['e2e_ms']} (gap {gap})")
    docT = tracing.export_chrome_trace()
    spansT = [e for e in docT["traceEvents"] if e.get("ph") == "X"]
    flowsT = [e for e in docT["traceEvents"]
              if e.get("ph") in ("s", "t", "f")]
    assert spansT and len(flowsT) >= 1, (len(spansT), len(flowsT))
    assert {e["args"]["request"] for e in spansT} == \
        set(range(len(reqsT))), spansT
    afterT = {site: c["count"]
              for site, c in observability.compiles().items()
              if site.startswith(("serving_", "decode_", "verify_"))}
    deltaT = {site: n - baseT.get(site, 0) for site, n in afterT.items()
              if n - baseT.get(site, 0)}
    burstT = [[(p, 4) for p in prompts]]
    predT = predict_serving_compiles(
        burstT, buckets=[8, 16], max_len=32, block_size=4,
        tracing=True)
    assert predT == predict_serving_compiles(
        burstT, buckets=[8, 16], max_len=32, block_size=4), (
        "tracing must be a predictor no-op")
    assert deltaT == predT, (
        f"tracing-phase recompile prediction drifted:\n"
        f"  predicted {predT}\n  observed  {deltaT}")
    srvT = ServingHTTPServer(engT, port=0)
    srvT.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srvT.port}/v1/requests/"
                f"{reqsT[0].id}", timeout=10) as r:
            assert r.status == 200
            got = json.loads(r.read().decode())
        assert got["outcome"] == "done" and got["marks"], got
        assert got["blame_ms"] == tracing.get(reqsT[0].id)["blame_ms"]
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srvT.port}/v1/requests/999999",
                timeout=10)
            raise AssertionError("unknown request id must 404")
        except urllib.error.HTTPError as e404:
            assert e404.code == 404, e404.code
    finally:
        srvT.stop()
    print(f"   tracing: {len(reqsT)} traced requests, blame sums == "
          f"E2E, {len(spansT)} spans / {len(flowsT)} flow events, "
          f"/v1/requests/<id> 200+404, {deltaT} == predicted")

    # -- sanitize phase: the concurrency sanitizer is free ------------
    # Replay the loadgen workload with FLAGS_sanitize_locks=1: every
    # engine/router/metrics lock becomes a SanitizedLock recording
    # order edges and guarded-state writes. The flag gates pure host
    # bookkeeping, so (a) the predictor says sanitize=True compiles
    # NOTHING new (validated no-op, like tracing) and the fresh-phase
    # delta equals that prediction, (b) goodput stays within 5% of the
    # plain loadgen run on the same virtual-clock schedule, and (c)
    # the report comes back with zero cycles, zero violations, and
    # nonzero instrumented acquires. The static half must agree the
    # fleet is clean: lint_serving() returns zero findings.
    from paddle_tpu.analysis import concurrency as ccz
    from paddle_tpu.analysis import lint_serving as lint_serving_fn
    lint_res = lint_serving_fn()
    assert not lint_res.diagnostics, (
        f"lint_serving found issues in the shipped fleet: "
        f"{[str(d) for d in lint_res.diagnostics]}")
    ccz.reset()
    baseS = {site: c["count"]
             for site, c in observability.compiles().items()
             if site.startswith(("serving_", "decode_", "verify_"))}
    pt.set_flags({"sanitize_locks": True})
    try:
        vcS = VirtualClock()
        engS = ServingEngine(model, max_slots=3, max_len=32,
                             buckets=[8, 16], max_queue=16,
                             block_size=4, clock=vcS.now,
                             slo_ttft_ms=40.0, slo_prefill_ms=4.0,
                             slo_tpot_ms=1.0)
        lgS = LoadGen(mode="bursty", rate=60.0, duration=1.0, seed=3,
                      vocab_size=97, prompt_tokens=(3, 7),
                      new_tokens=(2, 4),
                      priority_mix={0: 0.2, 1: 0.6, 2: 0.2})
        reportS = lgS.run(engS, clock=vcS, step_cost_ms=4.0)
        sanS = ccz.report()
    finally:
        pt.set_flags({"sanitize_locks": False})
    assert reportS["exceptions"] == 0, reportS
    assert reportS["leaked_kv_blocks"] == 0, reportS
    assert reportS["completed"] > 0, reportS
    assert abs(reportS["goodput_per_s"] - report["goodput_per_s"]) \
        <= 0.05 * report["goodput_per_s"], (
        f"sanitized goodput {reportS['goodput_per_s']}/s strayed >5% "
        f"from plain {report['goodput_per_s']}/s")
    assert sanS["enabled"] and sanS["lock_acquires"] > 0, sanS
    # the fresh engine's queue + step locks (the registry lock predates
    # the flag flip, so it stays plain in-process)
    assert sanS["locks_tracked"] >= 2, sanS
    assert sanS["cycles"] == [], sanS["cycles"]
    assert sanS["violations"] == [], sanS["violations"]
    afterS = {site: c["count"]
              for site, c in observability.compiles().items()
              if site.startswith(("serving_", "decode_", "verify_"))}
    deltaS = {site: n - baseS.get(site, 0) for site, n in afterS.items()
              if n - baseS.get(site, 0)}
    predS = predict_serving_compiles(
        lg_workload, buckets=[8, 16], max_len=32, block_size=4,
        slo_ttft_ms=40.0, sanitize=True)
    assert predS == predict_serving_compiles(
        lg_workload, buckets=[8, 16], max_len=32, block_size=4,
        slo_ttft_ms=40.0), "sanitize must be a predictor no-op"
    assert deltaS == predS, (
        f"sanitize-phase recompile prediction drifted:\n"
        f"  predicted {predS}\n  observed  {deltaS}")
    print(f"   sanitize: lint_serving clean, "
          f"{sanS['lock_acquires']} sanitized acquires over "
          f"{sanS['locks_tracked']} locks ({sanS['order_edges']} "
          f"order edges), 0 cycles / 0 violations, goodput "
          f"{reportS['goodput_per_s']}/s ~ plain "
          f"{report['goodput_per_s']}/s, {deltaS} == predicted")

    # -- cancel/hedge phase: request lifecycle is host-side -----------
    # Cancellation is pure queue/slot surgery and a hedge clone lands
    # in the primary's already-warm prefill bucket, so a fresh phase
    # (the sanitize finally bumped the flags version) that cancels at
    # the queued AND decode stages and races one real hedge must
    # retrace exactly what the plain workload would: the predictor
    # says ``cancel=``/``hedge=`` are no-ops and the live tracker must
    # agree. The race's loser is canceled leak-free, the shared
    # RetryBudget gauge goes live for the /metrics scrape below, and
    # the run log grows serving_cancel / serving_hedge events.
    import time as _time

    from paddle_tpu.serving import ReplicaRouter
    baseC = {site: c["count"]
             for site, c in observability.compiles().items()
             if site.startswith(("serving_", "decode_", "verify_"))}
    rtC = ReplicaRouter(model, n_replicas=2, max_slots=3, max_len=32,
                        buckets=[8, 16], max_queue=16, block_size=4,
                        hedge_ms=5.0)
    # deterministic straggler: replica 0 predicts slow (pinned prefill
    # cost) and IS slow (its first steps do nothing), so the hedge
    # fires after the 5 ms delay and the clone on replica 1 wins
    slowC = rtC.engines[0]
    slowC._prefill_ms_pin = 500.0
    _orig_stepC = slowC.step
    _skipC = {"n": 0}

    def _lazy_stepC():
        _skipC["n"] += 1
        if _skipC["n"] <= 8:
            return False
        return _orig_stepC()
    slowC.step = _lazy_stepC
    rh = rtC.submit([1, 2, 3, 4], max_new_tokens=4)
    _time.sleep(0.01)        # let the hedge delay lapse
    for _ in range(400):
        rtC.step()
        if rh.done:
            break
    assert rh.state == "done", (rh.state, rh.error)
    slowC.step = _orig_stepC
    slowC._prefill_ms_pin = 0.0
    hstC = rtC.stats()["hedges"]
    assert hstC["fired"] == 1 and hstC["wins"] == 1, hstC
    r_q = rtC.submit([5, 6, 7, 8], max_new_tokens=4)
    outq = rtC.cancel(r_q.id)
    assert outq is not None and outq["stage"] == "queued", outq
    assert rtC.cancel(r_q.id) is None   # double-cancel: no-op
    r_d = rtC.submit([2, 3, 4, 5], max_new_tokens=8)
    for _ in range(400):
        rtC.step()
        if r_d.first_token_at is not None:
            break
    outd = rtC.cancel(r_d.id, reason="client")
    assert outd is not None and outd["stage"] == "decode", outd
    rtC.run_until_idle()
    for e in rtC.engines:
        e.cache.flush_prefix_cache()
        assert e.cache.allocator.leaked() == 1, (  # trash block only
            e.cache.allocator.leaked())
    cstC = rtC.stats()["canceled"]
    assert cstC.get("hedge_lose") == 1 and cstC.get("client") == 2, cstC
    afterC = {site: c["count"]
              for site, c in observability.compiles().items()
              if site.startswith(("serving_", "decode_", "verify_"))}
    deltaC = {site: n - baseC.get(site, 0)
              for site, n in afterC.items() if n - baseC.get(site, 0)}
    burstC = [[([1, 2, 3, 4], 4), ([2, 3, 4, 5], 8)]]
    predC = predict_serving_compiles(
        burstC, buckets=[8, 16], max_len=32, block_size=4,
        n_replicas=2, cancel=3, hedge=1)
    assert predC == predict_serving_compiles(
        burstC, buckets=[8, 16], max_len=32, block_size=4,
        n_replicas=2), "cancel/hedge must be predictor no-ops"
    assert deltaC == predC, (
        f"cancel/hedge-phase recompile prediction drifted:\n"
        f"  predicted {predC}\n  observed  {deltaC}")
    from paddle_tpu.resilience.retry import default_budget
    assert default_budget().remaining() > 0
    print(f"   cancel/hedge: hedge fired+won, canceled {cstC} "
          f"(queued + mid-decode + hedge loser), 0 leaked blocks, "
          f"retry budget {default_budget().remaining():.1f} tokens, "
          f"{deltaC} == predicted")

    # -- host-tier phase: session parking is host-side numpy ----------
    # Enabling the host KV tier bumps the flags version (a fresh
    # phase), but every demotion/promotion is host-side numpy surgery:
    # the predictor says ``host_tier=``/``sessions=`` are validated
    # no-ops and the live tracker must agree. A two-turn session is
    # demoted off device by the idle sweep, resumed from host RAM, and
    # the resumed turn must be token-identical to replaying the stored
    # conversation as a plain prompt. Both tiers drain leak-free.
    from paddle_tpu.serving.kv_tier import HostBlockStore, TierManager
    baseT = {site: c["count"]
             for site, c in observability.compiles().items()
             if site.startswith(("serving_", "decode_", "verify_"))}
    pt.set_flags({"serving_host_tier": True, "serving_host_blocks": 64})
    storeT = HostBlockStore(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                            block_size=4, num_blocks=64)
    tierT = TierManager(storeT, demote_idle_ms=0.0)
    engT = ServingEngine(model, max_slots=2, max_len=32,
                         buckets=[8, 16], max_queue=16, block_size=4,
                         kv_tier=tierT)
    # round 1 warms BOTH prefill buckets, so the resume suffix lands
    # warm no matter how much of the context promotion covers
    tT1 = [3, 1, 4]
    fillT = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8]
    rT1 = engT.submit(tT1, max_new_tokens=4, session="obs")
    rF = engT.submit(fillT, max_new_tokens=4)
    engT.run_until_idle()
    assert rT1.state == "done" and rF.state == "done"
    for _ in range(3):          # idle sweep demotes the cold chains
        engT.step()
    stT = tierT.stats()
    assert stT["sessions_host"] == 1, stT
    assert stT["migrated_demote_blocks"] > 0, stT
    tT2 = [1, 5]
    rT2 = engT.submit(tT2, max_new_tokens=4, session="obs")
    engT.run_until_idle()
    assert rT2.state == "done"
    stT = tierT.stats()
    assert stT["sessions_resumed"] == 1, stT
    assert stT["migrated_promote_blocks"] > 0, stT
    # token identity: the resumed turn equals replaying the stored
    # conversation (turn-1 full sequence + turn-2 prompt) sessionless
    ctxT = rT1.output_ids + tT2
    rT3 = engT.submit(ctxT, max_new_tokens=4)
    engT.run_until_idle()
    assert rT3.state == "done" and rT3.output_ids == rT2.output_ids, (
        rT3.output_ids, rT2.output_ids)
    engT.run_until_idle()
    engT.cache.flush_prefix_cache()
    assert engT.cache.allocator.leaked() == 1, (  # trash block only
        engT.cache.allocator.leaked())
    tierT.flush()
    assert tierT.leaked() == 0, tierT.leaked()
    afterT = {site: c["count"]
              for site, c in observability.compiles().items()
              if site.startswith(("serving_", "decode_", "verify_"))}
    deltaT = {site: n - baseT.get(site, 0)
              for site, n in afterT.items() if n - baseT.get(site, 0)}
    workloadT = [[(tT1, 4), (fillT, 4)], [(ctxT, 4)], [(ctxT, 4)]]
    predT = predict_serving_compiles(
        workloadT, buckets=[8, 16], max_len=32, block_size=4,
        host_tier=True, sessions=1)
    assert predT == predict_serving_compiles(
        workloadT, buckets=[8, 16], max_len=32, block_size=4), \
        "host_tier/sessions must be predictor no-ops"
    assert deltaT == predT, (
        f"host-tier-phase recompile prediction drifted:\n"
        f"  predicted {predT}\n  observed  {deltaT}")
    print(f"   host tier: demote {stT['migrated_demote_blocks']} / "
          f"promote {stT['migrated_promote_blocks']} blocks, resume "
          f"token-identical, 0 leaks both tiers, {deltaT} == predicted")

    # -- megastep phase: device-resident decode megasteps -------------
    # FLAGS_serving_megastep=N runs N decode iterations inside ONE
    # compiled dispatch (lax.scan carrying the paged pools, early-exit
    # state as data) and FLAGS_serving_dispatch_ahead enqueues
    # megastep k+1 against the un-synced carries while k executes.
    # The decode plane has exactly TWO compile surfaces under N > 1:
    # decode_megastep_paged{n=N}, plus the single-token fallback the
    # scheduler drops to whenever a megastep is unsafe for the whole
    # batch — driven here by a request whose stop list exceeds the
    # device stop-table caps. This burst exercises both, tokens must
    # equal a megastep=1 engine's at the same flags version (which
    # itself adds ZERO compiles: the fallback already retraced
    # decode_step_paged), and the per-phase delta must equal
    # predict_serving_compiles(megastep=4).
    from paddle_tpu.serving.decoding import STOP_MAX_SEQS
    baseM = {site: c["count"]
             for site, c in observability.compiles().items()
             if site.startswith(("serving_", "decode_", "verify_"))}
    pt.set_flags({"serving_megastep": 4,
                  "serving_dispatch_ahead": True,
                  "serving_host_tier": False})
    try:
        engM = ServingEngine(model, max_slots=3, max_len=32,
                             buckets=[8, 16], max_queue=16,
                             block_size=4)
        big_stops = [[90 + j] for j in range(STOP_MAX_SEQS + 1)]
        reqsM = [engM.submit(p, max_new_tokens=8) for p in prompts]
        reqsM.append(engM.submit(prompts[0], max_new_tokens=8,
                                 stop=big_stops))
        engM.run_until_idle()
        assert all(r.state == "done" for r in reqsM)
        stM = engM.stats()
        assert stM["megastep"] == 4 and stM["dispatch_ahead"], stM
        assert stM["ahead_hits"] + stM["ahead_misses"] >= 1, stM
        eng1 = ServingEngine(model, max_slots=3, max_len=32,
                             buckets=[8, 16], max_queue=16,
                             block_size=4, megastep=1,
                             dispatch_ahead=False)
        reqs1 = [eng1.submit(p, max_new_tokens=8) for p in prompts]
        reqs1.append(eng1.submit(prompts[0], max_new_tokens=8,
                                 stop=big_stops))
        eng1.run_until_idle()
        for a, b in zip(reqsM, reqs1):
            assert a.output_ids == b.output_ids, (
                f"megastep=4 diverged on request {a.id}: "
                f"{a.output_ids} vs {b.output_ids}")
        engM.cache.flush_prefix_cache()
        assert engM.cache.allocator.leaked() == 1  # trash block only
        afterM = {site: c["count"]
                  for site, c in observability.compiles().items()
                  if site.startswith(("serving_", "decode_",
                                      "verify_"))}
        deltaM = {site: n - baseM.get(site, 0)
                  for site, n in afterM.items()
                  if n - baseM.get(site, 0)}
        workloadM = [[(p, 8) for p in prompts] + [(prompts[0], 8)]]
        predM = predict_serving_compiles(
            workloadM, buckets=[8, 16], max_len=32, block_size=4,
            megastep=4)
        assert deltaM == predM, (
            f"megastep-phase recompile prediction drifted:\n"
            f"  predicted {predM}\n  observed  {deltaM}")
        print(f"   megastep: N=4 + dispatch-ahead token-identical to "
              f"N=1 ({stM['ahead_hits']} ahead hits / "
              f"{stM['ahead_misses']} misses), both decode surfaces "
              f"traced, {deltaM} == predicted")
    finally:
        pt.set_flags({"serving_megastep": 1,
                      "serving_dispatch_ahead": False})

    # -- /metrics scrape ----------------------------------------------
    srv = ServingHTTPServer(eng, port=0)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
    finally:
        srv.stop()
    n = observability.validate_prometheus_text(text)
    for needle in ("STAT_serving_tokens", "STAT_fault_exec_step",
                   "STAT_guardian_skipped", "xla_compiles",
                   "serving_ttft_seconds", "serving_kv_blocks_used",
                   "serving_kv_blocks_free", "STAT_serving_prefix_hits",
                   "serving_kv_dequant_max_abs_err",
                   "STAT_serving_kv_quant_writes", "serving_mesh_devices",
                   "serving_replicas", "serving_queue_depth",
                   "serving_slo_attainment", "serving_shed_total",
                   "serving_weight_version",
                   "serving_prefix_affinity_hits",
                   "serving_handoff_queue_depth",
                   "serving_disagg_workers",
                   "serving_lora_adapters_loaded",
                   "STAT_serving_lora_loads",
                   "serving_replica_state",
                   "serving_rehomed_total",
                   "STAT_serving_rehomed",
                   "serving_traced_total",
                   "sanitizer_lock_acquires",
                   "serving_canceled_total",
                   "serving_hedges_total",
                   "serving_retry_budget_remaining",
                   "serving_kv_migrations",
                   "serving_sessions_resident",
                   "serving_sessions_host",
                   "serving_sessions_resumed"):
        assert needle in text, f"/metrics missing {needle}"
    print(f"   /metrics: {n} samples, valid Prometheus text")

    # -- run log consumed by trace_summary ----------------------------
    runlog.close()
    path = os.path.join(tmp, f"runlog-{os.getpid()}.jsonl")
    kinds = set()
    with open(path) as f:
        for line in f:
            kinds.add(json.loads(line)["kind"])
    for k in ("train_step", "guardian_skip", "fault_injected",
              "serving_admit", "serving_finish", "serving_weight_swap",
              "serving_request", "serving_handoff",
              "serving_lora_load", "serving_replica_kill",
              "serving_replica_recover", "serving_cancel",
              "serving_hedge", "serving_kv_demote",
              "serving_kv_promote", "serving_session_resume",
              "serving_megastep"):
        assert k in kinds, f"run log missing {k!r} events (got {kinds})"
    from tools import trace_summary
    rc = trace_summary.main([path, "--top", "5"])
    assert rc == 0
    print(f"   run log: {sorted(kinds)} -> trace_summary ok")
    print("observability gate PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
