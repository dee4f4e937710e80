"""ReplicaRouter — data parallelism across serving-engine replicas.

Tensor parallelism *within* an engine is :class:`ServingEngine`'s mesh
path (``FLAGS_serving_mesh``); this module is the axis orthogonal to
it: N engine replicas behind one ``submit()`` front door
(``FLAGS_serving_replicas``), each replica an independent scheduler
with its own KV pool and queue. The replicas share one model object,
so the unified per-model step cache (``models.generation.step_entry``)
means N replicas still compile each step exactly once — scaling out
replicas multiplies throughput, not XLA compiles (the
``analysis.recompile`` predictor encodes exactly this: ``n_replicas``
is a cache-key component that does NOT change per-phase counts).

Routing is least-loaded by predicted time-to-first-token: a request
lands on the replica minimizing (queued + active requests) and, on a
tie, maximizing free KV blocks — queue depth is the dominant TTFT term
(every queued request costs a prefill dispatch ahead of yours) and a
dry block pool blocks admission head-of-line. A replica whose queue is
full is skipped; when every replica is full the router sheds the
submission with :class:`QueueFullError` (the HTTP 429 path).

Resilience: every routing attempt passes the ``serving.route`` fault
site through ``RetryPolicy.from_flags("serving.route")`` — drop/error
retry, exhaustion and injected ``skip`` shed the submission through
the same backpressure exit as a full queue. Requests already placed on
a replica are never touched by router faults. ``drain()`` stops
admissions (subsequent submits shed) and runs every replica to idle —
the rolling-deploy exit — returning how many queued requests were shed
on the way down.

Autoscaling (``FLAGS_serving_autoscale`` = "MIN:MAX" or an
:class:`AutoscalePolicy` instance): each ``step()`` the router
consults the policy against the same signals the metrics registry
exports — mean queue depth per replica, the tightest replica's free
KV-block fraction, and aggregate SLO attainment — and scales the
replica set inside [MIN, MAX]. Scale-up constructs a new engine on
the shared model (the unified step cache means no new XLA compiles);
scale-down retires the emptiest replica: it stops receiving routes
but keeps stepping until its in-flight work drains, then drops.
Decisions are cooldown-limited so one burst doesn't thrash the set.
"""

from __future__ import annotations

import itertools
from collections import deque
from typing import Dict, List, Optional, Sequence

from .. import monitor as _monitor
from ..analysis import concurrency as _ccz
from .. import observability as _obs
from ..observability import runlog as _runlog
from ..observability import tracing as _tracing
from ..resilience.injector import InjectedFault, fault_point
from ..resilience.retry import RetryError, RetryPolicy
from .engine import QueueFullError, Request, ServingEngine
from .seam import served_with

#: per-replica health states (the serving_replica_state gauge family)
HEALTH_STATES = ("healthy", "suspect", "dead", "recovering")

#: routing preference per state: healthy/recovering route normally,
#: suspect only when nothing healthier has room, dead never
_HEALTH_RANK = {"healthy": 0, "recovering": 0, "suspect": 1, "dead": 2}


def _parse_autoscale(text: str):
    """'MIN:MAX' -> (min, max) replica bounds, None when empty."""
    text = str(text).strip()
    if not text:
        return None
    try:
        lo, hi = (int(p) for p in text.split(":"))
    except Exception:
        raise ValueError(
            f"serving_autoscale must be 'MIN:MAX', got {text!r}")
    if lo < 1 or hi < lo:
        raise ValueError(
            f"serving_autoscale bounds need 1 <= MIN <= MAX, got {text!r}")
    return lo, hi



class AutoscalePolicy:
    """Replica-count policy over the router's live load signals.

    ``decide(router)`` returns the target replica count, one step up
    or down at a time inside [min_replicas, max_replicas]:

    - scale UP when the mean (queued + active) per replica exceeds
      ``queue_high``, when the tightest replica's free KV-block
      fraction drops under ``kv_free_low``, or when aggregate SLO
      attainment (engines running with a TTFT SLO) falls under
      ``attainment_low`` while there is queued work;
    - scale DOWN when the mean depth sits under ``queue_low`` and
      attainment (if measured) is healthy.

    The router applies decisions at most once per ``cooldown_steps``
    scheduler iterations, and drains a retiring replica before
    dropping it — scale-down never sheds in-flight work.
    """

    def __init__(self, min_replicas: int = 1, max_replicas: int = 4,
                 queue_high: float = 4.0, queue_low: float = 1.0,
                 kv_free_low: float = 0.1,
                 attainment_low: float = 0.95,
                 cooldown_steps: int = 20):
        if min_replicas < 1 or max_replicas < min_replicas:
            raise ValueError(
                "AutoscalePolicy needs 1 <= min_replicas <= "
                f"max_replicas, got {min_replicas}..{max_replicas}")
        if queue_low > queue_high:
            raise ValueError("queue_low must be <= queue_high")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.queue_high = float(queue_high)
        self.queue_low = float(queue_low)
        self.kv_free_low = float(kv_free_low)
        self.attainment_low = float(attainment_low)
        self.cooldown_steps = int(cooldown_steps)

    def decide(self, router: "ReplicaRouter") -> int:
        n = len(router.engines)
        depths = [router._depth(e) for e in router.engines]
        mean_depth = sum(depths) / n
        free_frac = min(
            e.cache.blocks_free / max(1, e.cache.num_blocks)
            for e in router.engines)
        att = router._slo_attainment()
        pressured = (mean_depth > self.queue_high or
                     free_frac < self.kv_free_low or
                     (att is not None and att < self.attainment_low
                      and mean_depth > self.queue_low))
        if pressured and n < self.max_replicas:
            return n + 1
        if (mean_depth < self.queue_low and n > self.min_replicas and
                (att is None or att >= self.attainment_low)):
            return n - 1
        return n


class ReplicaRouter:
    """Spread ``submit()`` over N data-parallel :class:`ServingEngine`
    replicas.

    Either pass prebuilt ``engines`` (advanced: heterogeneous
    geometries), or a ``model`` plus ``n_replicas`` (default
    ``FLAGS_serving_replicas``) and any :class:`ServingEngine`
    constructor keywords, which every replica shares. The replicas
    share the model — and therefore the compiled steps.
    """

    _router_ids = itertools.count()

    def __init__(self, model=None, n_replicas: Optional[int] = None,
                 engines: Optional[Sequence[ServingEngine]] = None,
                 autoscale=None, hedge_ms: Optional[float] = None,
                 hedge_budget: Optional[float] = None,
                 dispatch_threads: Optional[int] = None,
                 **engine_kwargs):
        from .. import flags as _flags
        g = _flags.get_flags(["serving_replicas", "serving_autoscale",
                              "serving_replica_strikes",
                              "serving_auto_restart",
                              "serving_hedge_ms",
                              "serving_hedge_budget",
                              "serving_breaker_window",
                              "serving_breaker_threshold",
                              "serving_breaker_cooldown_s",
                              "serving_dispatch_threads"])
        self._strike_limit = max(1, int(g["serving_replica_strikes"]))
        self._auto_restart = bool(g["serving_auto_restart"])
        # hedged prefill (Dean & Barroso tail-at-scale): 0 = off,
        # > 0 = fixed threshold/delay ms, < 0 = auto-derive from the
        # traced fleet TTFT p95 (tracing.ttft_p95_ms)
        self._hedge_ms = float(hedge_ms if hedge_ms is not None
                               else g["serving_hedge_ms"])
        self._hedge_budget_frac = float(
            hedge_budget if hedge_budget is not None
            else g["serving_hedge_budget"])
        if self._hedge_budget_frac < 0:
            raise ValueError(
                "serving_hedge_budget must be >= 0, got "
                f"{self._hedge_budget_frac}")
        # per-replica circuit breaker config (0 window disables)
        self._brk_window_n = max(0, int(g["serving_breaker_window"]))
        self._brk_threshold = float(g["serving_breaker_threshold"])
        self._brk_cooldown = float(g["serving_breaker_cooldown_s"])
        # threaded replica dispatch (0 = the serial loop, byte-identical
        # scheduling): step() fans _step_replica over a bounded
        # persistent worker pool so one slow replica's device dispatch
        # doesn't serialize the fleet's step. Per-replica health /
        # breaker state is only ever touched by the one worker stepping
        # that replica, and reaping/hedging/autoscale stay on the
        # caller's thread at the step boundary, so supervision
        # semantics match the serial loop exactly.
        self._dispatch_threads = int(
            dispatch_threads if dispatch_threads is not None
            else g["serving_dispatch_threads"])
        if self._dispatch_threads < 0:
            raise ValueError(
                "dispatch_threads must be >= 0, got "
                f"{self._dispatch_threads}")
        self._step_pool = None   # lazily-built ThreadPoolExecutor
        if autoscale is None:
            bounds = _parse_autoscale(g["serving_autoscale"])
            if bounds is not None:
                autoscale = AutoscalePolicy(min_replicas=bounds[0],
                                            max_replicas=bounds[1])
        self._autoscale: Optional[AutoscalePolicy] = autoscale
        self._model = model
        self._engine_kwargs = dict(engine_kwargs)
        if model is not None and \
                "lora_pool" not in self._engine_kwargs:
            # multi-tenant fleets share ONE adapter pool: tenants load
            # once and resolve by name on every replica (autoscale
            # replicas inherit it through the saved kwargs)
            gl = _flags.get_flags(["serving_lora_rank",
                                   "serving_lora_max_adapters"])
            rank = self._engine_kwargs.get("lora_rank")
            rank = int(rank if rank is not None
                       else gl["serving_lora_rank"])
            if rank > 0:
                from .lora import LoRAPool
                mx = self._engine_kwargs.get("lora_max_adapters")
                self._engine_kwargs["lora_pool"] = LoRAPool(
                    served_with(model, "lora", "lora_rank > 0").lora_config,
                    rank,
                    int(mx if mx is not None
                        else gl["serving_lora_max_adapters"]))
        if model is not None and \
                "kv_tier" not in self._engine_kwargs:
            # same sharing shape for the host KV tier: ONE fleet-wide
            # HostBlockStore + TierManager, so a chain demoted by any
            # replica is promotable by every other (a shared system
            # prompt is materialized once per fleet, not once per
            # pool) and sessions resume on whichever replica the
            # router picks. Scale-ups and restarts inherit it through
            # the saved kwargs; a killed replica's device refs die
            # with its pool while its host chains stay promotable.
            gt = _flags.get_flags(["serving_host_tier",
                                   "serving_host_blocks",
                                   "serving_block_size"])
            if gt["serving_host_tier"]:
                from .kv_tier import HostBlockStore, TierManager
                cfg = served_with(model, "host_tier",
                               "FLAGS_serving_host_tier").cache_kinds[0]
                bs = self._engine_kwargs.get("block_size")
                bs = int(bs if bs is not None
                         else gt["serving_block_size"])
                self._engine_kwargs["kv_tier"] = TierManager(
                    HostBlockStore(
                        len(cfg.layers), cfg.kv_heads, cfg.head_dim,
                        block_size=bs,
                        num_blocks=int(gt["serving_host_blocks"])))
        engine_kwargs = self._engine_kwargs
        if engines is not None:
            if model is not None or engine_kwargs:
                raise ValueError(
                    "pass either prebuilt engines= or model= + engine "
                    "kwargs, not both")
            if autoscale is not None:
                raise ValueError(
                    "autoscaling needs model= construction (the router "
                    "builds scale-up replicas itself); prebuilt "
                    "engines= cannot autoscale")
            self.engines: List[ServingEngine] = list(engines)  # guarded-by: _lock
            if not self.engines:
                raise ValueError("engines must be non-empty")
        else:
            if model is None:
                raise ValueError("ReplicaRouter needs model= or engines=")
            n = int(n_replicas if n_replicas is not None
                    else g["serving_replicas"])
            if n < 1:
                raise ValueError(f"n_replicas must be >= 1, got {n}")
            if autoscale is not None:
                n = min(max(n, autoscale.min_replicas),
                        autoscale.max_replicas)
            self.engines = [ServingEngine(model, **engine_kwargs)
                            for _ in range(n)]  # guarded-by: _lock
        # the fleet-shared host KV tier (None when off) — also
        # reachable as engines[i].kv_tier; prebuilt engines carry
        # their own
        self.kv_tier = (engine_kwargs.get("kv_tier") or
                        getattr(self.engines[0], "kv_tier", None))
        self._draining = False              # guarded-by: _lock
        self._lock = _ccz.make_lock("router._lock")
        self._retiring: List[ServingEngine] = []  # guarded-by: _lock
        self._scale_ups = 0                 # guarded-by: _lock
        self._scale_downs = 0               # guarded-by: _lock
        self._steps_since_scale = 0         # guarded-by: _lock
        self._kills = 0                     # guarded-by: _lock
        self._restarts = 0                  # guarded-by: _lock
        self._rehomed = 0                   # guarded-by: _lock
        # serving.replica round-robin victim cursor
        self._victim_rr = 0                 # guarded-by: _lock
        # hedged-prefill registry: primary request id -> pending hedge
        # record; the token bucket starts at 1.0 and earns
        # hedge_budget per offered request, so fired hedges can never
        # exceed 1 + hedge_budget * offered
        self._hedges: Dict[int, dict] = {}  # guarded-by: _lock
        self._hedge_tokens = 1.0            # guarded-by: _lock
        self._hedge_fired = 0               # guarded-by: _lock
        self._hedge_wins = 0                # guarded-by: _lock
        self._hedge_loses = 0               # guarded-by: _lock
        self._hedge_dup_tokens = 0          # guarded-by: _lock
        rid = str(next(ReplicaRouter._router_ids))
        self._rid = rid
        for eng in self.engines:
            self._init_health(eng)
        self._rehomed_counter = _obs.counter(
            "serving_rehomed_total",
            "requests recovered off a killed replica onto a live peer"
            ).labels(router=rid)
        self._hedge_ctr = _obs.counter(
            "serving_hedges_total",
            "hedged prefills, by outcome (fired | win | lose); volume "
            "bounded by the FLAGS_serving_hedge_budget token bucket, "
            "losers canceled leak-free")
        self._replicas_gauge = _obs.gauge(
            "serving_replicas",
            "data-parallel engine replicas behind this ReplicaRouter"
            ).labels(router=rid)
        self._replicas_gauge.set(len(self.engines))
        self._depth_gauges = [
            _obs.gauge(
                "serving_queue_depth",
                "requests queued + active on one routed engine replica"
                ).labels(router=rid, replica=str(i))
            for i in range(len(self.engines))]
        self._update_depth_gauges()
        self._update_state_gauges()
        # construction writes above precede the declaration and are
        # exempt; everything after must hold _lock to write these
        _ccz.declare_guarded(self, {
            "_draining": "_lock", "_scale_ups": "_lock",
            "_scale_downs": "_lock", "_steps_since_scale": "_lock",
            "_kills": "_lock", "_restarts": "_lock",
            "_rehomed": "_lock", "_victim_rr": "_lock",
            "_hedges": "_lock", "_hedge_tokens": "_lock",
            "_hedge_fired": "_lock", "_hedge_wins": "_lock",
            "_hedge_loses": "_lock", "_hedge_dup_tokens": "_lock",
        })

    # ------------------------------------------------------------ health
    def _init_health(self, eng: ServingEngine):
        eng._health = "healthy"
        eng._strikes = 0
        # circuit-breaker state rides the engine like _health/_strikes:
        # a rolling window of step outcomes, tripping on error RATE
        # (the strikes watchdog needs CONSECUTIVE failures — a replica
        # failing every other step never strikes out but still poisons
        # its share of traffic; the breaker catches exactly that)
        eng._brk_window = deque(maxlen=max(1, self._brk_window_n))
        eng._brk_state = "closed"
        eng._brk_opened_at = 0.0

    def _update_state_gauges(self):
        for i, eng in enumerate(self.engines):
            for state in HEALTH_STATES:
                _obs.gauge(
                    "serving_replica_state",
                    "1 on a replica's current health-state series "
                    "(healthy | suspect | dead | recovering)"
                    ).labels(router=self._rid, replica=str(i),
                             state=state).set(
                        1 if eng._health == state else 0)
            if self._brk_window_n > 0:
                _obs.gauge(
                    "serving_breaker_state",
                    "per-replica circuit breaker: 0 closed, 1 open "
                    "(error rate tripped; replica skipped by routing), "
                    "0.5 half-open (one probe admitted)"
                    ).labels(router=self._rid, replica=str(i)).set(
                        {"closed": 0.0, "open": 1.0,
                         "half-open": 0.5}[eng._brk_state])

    def _step_replica(self, eng: ServingEngine) -> bool:
        """One supervised step: an exception, or no progress while the
        replica holds work, is a strike; strikes mark it suspect and —
        at FLAGS_serving_replica_strikes — dead. A productive step
        clears the strikes (and graduates a recovering replacement to
        healthy)."""
        try:
            worked = eng.step()
        except Exception:
            worked = False
            eng._strikes += 1
            self._note_breaker(eng, False)
        else:
            if worked:
                eng._strikes = 0
                if eng._health in ("suspect", "recovering"):
                    eng._health = "healthy"
                self._note_breaker(eng, True)
            elif self._depth(eng) > 0:
                eng._strikes += 1
                self._note_breaker(eng, False)
        if eng._strikes >= self._strike_limit:
            eng._health = "dead"
        elif eng._strikes >= 1 and eng._health == "healthy":
            eng._health = "suspect"
        return worked

    def _note_breaker(self, eng: ServingEngine, ok: bool):
        """Feed one step outcome into the replica's breaker window.
        Closed: trips open when the windowed failure rate reaches
        FLAGS_serving_breaker_threshold with at least half the window
        observed. Open: cools down FLAGS_serving_breaker_cooldown_s of
        engine-clock time, then half-opens. Half-open: one probe —
        success closes (window reset), failure re-opens."""
        if self._brk_window_n <= 0:
            return
        now = eng._clock()
        if eng._brk_state == "open":
            if now - eng._brk_opened_at >= self._brk_cooldown:
                eng._brk_state = "half-open"
            return
        if eng._brk_state == "half-open":
            if ok:
                eng._brk_state = "closed"
                eng._brk_window.clear()
            else:
                eng._brk_state = "open"
                eng._brk_opened_at = now
            return
        w = eng._brk_window
        w.append(bool(ok))
        if len(w) >= max(1, self._brk_window_n // 2):
            rate = 1.0 - sum(w) / len(w)
            if rate >= self._brk_threshold:
                eng._brk_state = "open"
                eng._brk_opened_at = now

    def _reap_dead(self):
        """Tear down replicas the watchdog declared dead: restart them
        under FLAGS_serving_auto_restart (model= construction), kill
        them outright otherwise. The last replica is never torn down
        without a replacement — a fleet of zero serves nobody."""
        for eng in [e for e in list(self.engines)
                    if e._health == "dead"]:
            if eng not in self.engines:
                continue
            idx = self.engines.index(eng)
            if self._auto_restart and self._model is not None:
                self.restart_replica(idx, cause="strikes")
            elif len(self.engines) > 1:
                self.kill_replica(idx, cause="strikes")
            else:
                # can't restart (prebuilt engines) and can't lose the
                # last replica: put it back on probation
                eng._strikes = 0
                eng._health = "suspect"

    def _check_replica_fault(self):
        """The serving.replica fault site, once per router step:
        `error`/`drop` crash one replica (round-robin victim) and
        recover it per the auto-restart policy; `skip` kills without
        restart (permanent capacity loss, bounded at one replica)."""
        action = None
        try:
            if fault_point("serving.replica") == "skip":
                action = "kill"
        except InjectedFault:
            action = "crash"
        if action is None:
            return
        with self._lock:
            victim = self._victim_rr % len(self.engines)
            self._victim_rr += 1
        if action == "crash" and self._auto_restart and \
                self._model is not None:
            self.restart_replica(victim, cause="fault")
        elif len(self.engines) > 1:
            self.kill_replica(victim, cause="fault")

    # ----------------------------------------------------------- routing
    def _depth(self, eng: ServingEngine) -> int:
        with eng._lock:
            return len(eng._queue) + len(eng._active)

    def _shed_total(self, eng: ServingEngine) -> int:
        with eng._lock:
            return sum(eng._shed_by_reason.values())

    def _slo_attainment(self) -> Optional[float]:
        """Aggregate goodput fraction over replicas running with a
        TTFT SLO: sum(slo_met) / sum(completed). None when no replica
        has an SLO or nothing completed yet."""
        met = done = 0
        for eng in self.engines + self._retiring:
            if not eng.slo_ttft_ms:
                continue
            with eng._lock:
                met += eng._slo_met
                done += eng._completed
        return (met / done) if done else None

    def _update_depth_gauges(self):
        while len(self._depth_gauges) < len(self.engines):
            self._depth_gauges.append(_obs.gauge(
                "serving_queue_depth",
                "requests queued + active on one routed engine replica"
                ).labels(router=self._rid,
                         replica=str(len(self._depth_gauges))))
        for g, eng in zip(self._depth_gauges, self.engines):
            g.set(self._depth(eng))
        for g in self._depth_gauges[len(self.engines):]:
            g.set(0)

    def _route_attempt(self, prompt, max_new_tokens, eos_token_id,
                       priority, _log_request=True,
                       **decode_kwargs) -> Request:
        kind = fault_point("serving.route")
        if kind == "skip":
            _monitor.stat_add("STAT_serving_route_shed")
            raise QueueFullError(
                "submission shed by injected fault at serving.route",
                reason="fault")
        # least-loaded among the healthiest: health rank first (suspect
        # replicas only catch overflow, dead ones are skipped below),
        # then queue depth (each queued request is a prefill ahead of
        # yours -> the dominant TTFT term), free KV blocks as the
        # tiebreak, lowest index last for determinism
        order = sorted(
            range(len(self.engines)),
            key=lambda i: (_HEALTH_RANK[self.engines[i]._health],
                           self._depth(self.engines[i]),
                           -self.engines[i].cache.blocks_free, i))
        last_err: Optional[QueueFullError] = None
        for i in order:
            eng = self.engines[i]
            if eng._health == "dead":
                last_err = QueueFullError(
                    f"replica {i} is dead", reason="fault")
                continue
            if getattr(eng, "draining", False):
                # a draining replica sheds everything it's offered;
                # skipping it here is what re-routes the request to a
                # peer with capacity instead of dropping it
                last_err = QueueFullError(
                    f"replica {i} is draining", reason="drain")
                continue
            if getattr(eng, "_brk_state", "closed") == "open":
                # breaker tripped on error rate: skipped like a
                # draining replica until the cooldown half-opens it
                # (half-open admits this request as the probe)
                last_err = QueueFullError(
                    f"replica {i} breaker is open", reason="fault")
                continue
            try:
                req = eng.submit(prompt, max_new_tokens=max_new_tokens,
                                 eos_token_id=eos_token_id,
                                 priority=priority,
                                 _log_request=_log_request,
                                 **decode_kwargs)
            except QueueFullError as e:
                last_err = e
                continue
            req._routed_to = eng
            _monitor.stat_add("STAT_serving_routed")
            _runlog.log_event("serving_route", request=req.id,
                              replica=i, depth=self._depth(eng),
                              kv_blocks_free=eng.cache.blocks_free)
            self._depth_gauges[i].set(self._depth(eng))
            return req
        _monitor.stat_add("STAT_serving_route_shed")
        raise last_err if last_err is not None else QueueFullError(
            "every replica queue is full")

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               priority: Optional[int] = None,
               _log_request: bool = True, **decode_kwargs) -> Request:
        """Route one request to the least-loaded replica; returns its
        :class:`Request` handle. ``priority`` passes through to the
        chosen engine's admission, as do the per-request decoding
        fields (``temperature``/``top_k``/``top_p``/``stop``/``seed``/
        ``json_mode``/``tenant`` — see :meth:`ServingEngine.submit`);
        tenants resolve on whichever replica admits, which is why
        multi-tenant fleets share one ``lora_pool=`` via engine
        kwargs. Raises :class:`QueueFullError` when every replica
        sheds (or the router is draining) and ValueError for geometry
        no replica can hold."""
        with self._lock:
            if self._draining:
                raise QueueFullError("router is draining: submissions "
                                     "are shed for rolling shutdown",
                                     reason="drain")
        try:
            req = RetryPolicy.from_flags("serving.route").call(
                self._route_attempt, prompt, max_new_tokens,
                eos_token_id, priority, _log_request, **decode_kwargs)
        except RetryError as e:
            _monitor.stat_add("STAT_serving_route_shed")
            raise QueueFullError(
                f"routing retries exhausted: {e}", reason="fault") from e
        if self._hedge_ms != 0.0:
            with self._lock:
                # every offered request funds the hedge bucket, so
                # fired hedges <= 1 + hedge_budget * offered by
                # construction (spend is 1.0 per fire, at fire time)
                self._hedge_tokens += self._hedge_budget_frac
            self._maybe_arm_hedge(req, prompt, dict(
                max_new_tokens=max_new_tokens,
                eos_token_id=eos_token_id, priority=priority,
                **decode_kwargs))
        return req

    # ----------------------------------------------------- hedged prefill
    def _hedge_threshold_ms(self) -> Optional[float]:
        """The active hedge threshold/delay in ms: the flag when fixed
        (> 0), the traced fleet TTFT p95 when auto (< 0, None until
        enough traced requests finished), None when hedging is off."""
        if self._hedge_ms > 0:
            return self._hedge_ms
        if self._hedge_ms < 0:
            return _tracing.ttft_p95_ms()
        return None

    def _routable(self, but: Optional[ServingEngine] = None
                  ) -> List[ServingEngine]:
        return [e for e in self.engines
                if e is not but and e._health != "dead"
                and not getattr(e, "draining", False)
                and getattr(e, "_brk_state", "closed") != "open"]

    def _maybe_arm_hedge(self, req: Request, prompt, kwargs: dict):
        """Arm a hedge for a just-routed request whose assigned
        replica's predicted TTFT exceeds the threshold: after the
        threshold delay, if the primary still has no first token, a
        clone is dispatched to the second-best healthy replica (first
        first-token wins, the loser is canceled leak-free)."""
        thr = self._hedge_threshold_ms()
        if thr is None or thr <= 0:
            return
        eng = getattr(req, "_routed_to", None)
        if eng is None or not self._routable(but=eng):
            return            # nowhere to hedge to
        pred = eng.predict_ttft_ms(len(prompt))
        if pred <= thr:
            return
        with self._lock:
            self._hedges[req.id] = {
                "req": req, "primary": eng, "clone": None,
                "won": False, "prompt": [int(t) for t in prompt],
                "kwargs": kwargs, "pred_ms": pred,
                "fire_at": eng._clock() + thr / 1e3}

    def _fire_due_hedges(self):
        """Dispatch every armed hedge whose delay elapsed while the
        primary is still tokenless; disarm hedges whose primary
        produced or retired in time. Budget-gated: each fire spends
        one token from the offered-funded bucket — a dry bucket drops
        the hedge (the primary just runs unhedged)."""
        with self._lock:
            if not self._hedges:
                return
            now = self.engines[0]._clock()
            due = []
            for rid, h in list(self._hedges.items()):
                if h["clone"] is not None:
                    continue   # fired; resolution handles it
                req = h["req"]
                if req.state in ("done", "shed", "canceled") or \
                        req.first_token_at is not None:
                    del self._hedges[rid]   # beat the threshold
                    continue
                if now < h["fire_at"]:
                    continue
                if self._hedge_tokens < 1.0:
                    del self._hedges[rid]   # budget dry: no hedge
                    continue
                self._hedge_tokens -= 1.0
                due.append(h)
        for h in due:
            self._dispatch_hedge(h)

    def _dispatch_hedge(self, h: dict):
        """Submit the hedge copy to the best routable replica other
        than the primary. The clone is router-internal (never appears
        in results()/reports); a failed dispatch refunds the token."""
        req = h["req"]
        peers = sorted(self._routable(but=h["primary"]),
                       key=lambda p: (_HEALTH_RANK[p._health],
                                      self._depth(p),
                                      -p.cache.blocks_free))
        clone = None
        for peer in peers:
            try:
                clone = peer.submit(h["prompt"], _log_request=False,
                                    **h["kwargs"])
            except (QueueFullError, ValueError):
                continue
            clone._hedge_clone = True
            clone._routed_to = peer
            break
        with self._lock:
            if clone is None:
                self._hedges.pop(req.id, None)
                self._hedge_tokens += 1.0   # refund: nothing fired
                return
            h["clone"] = clone
            self._hedge_fired += 1
        self._hedge_ctr.labels(router=self._rid, outcome="fired").inc()
        _monitor.stat_add("STAT_serving_hedges")
        _runlog.log_event("serving_hedge", request=req.id,
                          hedge=clone.id,
                          predicted_ttft_ms=round(h["pred_ms"], 3))
        t = self.engines[0]._clock()
        _tracing.mark(req.id, "hedge", t, h["primary"].trace_track)

    def _count_hedge(self, outcome: str, dup_tokens: int = 0):
        with self._lock:
            if outcome == "win":
                self._hedge_wins += 1
            else:
                self._hedge_loses += 1
            self._hedge_dup_tokens += int(dup_tokens)
        self._hedge_ctr.labels(router=self._rid, outcome=outcome).inc()

    def _mirror_clone(self, req: Request, clone: Request):
        """Graft the winning clone's result onto the caller-visible
        primary handle (detach-canceled when the clone won): tokens,
        timing and terminal state, then release the waiter."""
        req.tokens = list(clone.tokens)
        req.first_token_at = clone.first_token_at
        req.token_at = list(clone.token_at)
        req.finished_at = clone.finished_at
        req.error = clone.error
        req.shed_reason = clone.shed_reason
        req.state = clone.state
        req._done.set()

    def _resolve_hedges(self):
        """Settle fired hedges: first first-token wins. A losing clone
        is canceled through the engine cancel path (zero leaked
        blocks); a losing *primary* is detach-canceled (resources
        reclaimed, handle kept open) and the clone's result is
        mirrored onto it once the clone retires."""
        with self._lock:
            items = list(self._hedges.items())
        for rid, h in items:
            req, clone = h["req"], h["clone"]
            if clone is None:
                continue
            if h["won"]:
                # waiting for the winning clone to retire -> mirror
                if clone.state in ("done", "shed", "canceled"):
                    self._mirror_clone(req, clone)
                    with self._lock:
                        self._hedges.pop(rid, None)
                continue
            p_first, c_first = req.first_token_at, clone.first_token_at
            p_term = req.state in ("done", "shed", "canceled")
            c_term = clone.state in ("done", "shed", "canceled")
            if p_first is not None and (c_first is None or
                                        p_first <= c_first):
                # primary won (ties break to the primary): tear the
                # clone down wherever it is
                _tracing.mark(clone.id, "hedge_lose",
                              self.engines[0]._clock(),
                              getattr(clone, "_routed_to",
                                      h["primary"]).trace_track)
                self._cancel_on_engines(clone.id, "hedge_lose")
                self._count_hedge("lose",
                                  dup_tokens=len(clone.tokens))
                with self._lock:
                    self._hedges.pop(rid, None)
            elif c_first is not None:
                # the hedge won: reclaim the primary's seat now (its
                # queue position / slot), mirror when the clone ends
                _tracing.mark(clone.id, "hedge_win", c_first,
                              getattr(clone, "_routed_to",
                                      h["primary"]).trace_track)
                self._cancel_on_engines(req.id, "hedge_lose",
                                        _finalize=False)
                self._count_hedge("win", dup_tokens=len(req.tokens))
                h["won"] = True
                if c_term:
                    self._mirror_clone(req, clone)
                    with self._lock:
                        self._hedges.pop(rid, None)
            elif c_term:
                # clone died without a token (shed/fault): hedge lost,
                # the primary continues unhedged
                self._count_hedge("lose")
                with self._lock:
                    self._hedges.pop(rid, None)
            elif p_term:
                # primary retired without a token (shed / canceled
                # externally): the pair is moot — tear the clone down
                self._cancel_on_engines(clone.id, "duplicate")
                self._count_hedge("lose",
                                  dup_tokens=len(clone.tokens))
                with self._lock:
                    self._hedges.pop(rid, None)

    # ------------------------------------------------------ cancellation
    def _cancel_on_engines(self, rid: int, reason: str,
                           _finalize: bool = True) -> Optional[dict]:
        """Try the cancel on every engine (live + retiring) until one
        holds the request — the fleet-level dedupe: a re-homed request
        appears in several engines' books but the shared Request
        object is canceled exactly once, wherever its resources
        actually live."""
        for eng in list(self.engines) + list(self._retiring):
            res = eng.cancel(rid, reason=reason, _finalize=_finalize)
            if res is not None:
                return res
        return None

    def cancel(self, rid: int, reason: str = "client"
               ) -> Optional[dict]:
        """Cancel request ``rid`` anywhere in the fleet — queued or
        in-flight on any replica, re-homed copies deduped — releasing
        its KV blocks and LoRA pin. If the request has a pending or
        fired hedge, the whole pair is torn down (the clone cancels as
        reason="duplicate" — never a double release: each side's
        resources are released by its own engine exactly once).
        Returns ``{"id", "stage", "reason"}`` or None for unknown /
        already-finished requests."""
        rid = int(rid)
        with self._lock:
            h = self._hedges.pop(rid, None)
        res = self._cancel_on_engines(rid, reason)
        if h is not None and h["clone"] is not None:
            clone = h["clone"]
            if self._cancel_on_engines(clone.id, "duplicate") \
                    is not None:
                self._count_hedge("lose",
                                  dup_tokens=len(clone.tokens))
        return res

    # ----------------------------------------------------- LoRA adapters
    def load_adapter(self, name: str, state) -> int:
        """Load a tenant adapter across the fleet: once per distinct
        pool, so replicas sharing one ``lora_pool=`` (the recommended
        multi-tenant shape — pass it via engine kwargs) pay a single
        load and per-replica pools each get a copy. Returns the page
        id on the last pool written."""
        pools: list = []
        page = None
        for eng in list(self.engines) + list(self._retiring):
            if eng.lora_pool is None:
                raise ValueError(
                    "replica has no LoRA pool; construct the router "
                    "with lora_rank > 0 or a shared lora_pool=")
            if any(eng.lora_pool is p for p in pools):
                continue
            pools.append(eng.lora_pool)
            page = eng.load_adapter(name, state)
        return page

    def evict_adapter(self, name: str) -> int:
        """Evict a tenant adapter from every distinct pool; refuses
        (ValueError) while any replica's in-flight work pins it."""
        pools: list = []
        page = None
        for eng in list(self.engines) + list(self._retiring):
            if eng.lora_pool is None or \
                    any(eng.lora_pool is p for p in pools):
                continue
            pools.append(eng.lora_pool)
            page = eng.evict_adapter(name)
        if page is None:
            raise ValueError("no replica has a LoRA pool")
        return page

    # -------------------------------------------------------- autoscale
    def _add_replica(self):  # holds: _lock
        eng = ServingEngine(self._model, **self._engine_kwargs)
        self._init_health(eng)
        self.engines.append(eng)

    def _maybe_autoscale(self):
        """Apply one cooldown-limited policy decision: grow the set on
        pressure, or move the emptiest replica to the retiring list
        (it keeps stepping, receives no routes, and drops once idle —
        in-flight work is never shed by scale-down)."""
        # the policy consults per-replica depth under eng._lock while
        # we hold _lock — a router._lock -> engine._lock order edge;
        # acyclic, because engine code never reaches back for _lock
        with self._lock:
            for eng in list(self._retiring):
                if eng.idle:
                    self._retiring.remove(eng)
            self._steps_since_scale += 1
            if self._steps_since_scale < self._autoscale.cooldown_steps:
                return
            n = len(self.engines)
            target = self._autoscale.decide(self)
            if target == n:
                return
            if target > n:
                for _ in range(target - n):
                    self._add_replica()
                self._scale_ups += 1
                _monitor.stat_add("STAT_serving_autoscale_up")
            else:
                idx = min(range(n),
                          key=lambda i: (self._depth(self.engines[i]), i))
                self._retiring.append(self.engines.pop(idx))
                self._scale_downs += 1
                _monitor.stat_add("STAT_serving_autoscale_down")
            self._steps_since_scale = 0
            replicas_to = len(self.engines)
            retiring = len(self._retiring)
        self._replicas_gauge.set(replicas_to)
        _runlog.log_event("serving_autoscale", replicas_from=n,
                          replicas_to=replicas_to,
                          retiring=retiring)

    # ---------------------------------------------------------- stepping
    def _dispatch_pool(self):
        """The persistent bounded worker pool for threaded dispatch,
        built on first use and shut down by :meth:`stop`."""
        if self._step_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._step_pool = ThreadPoolExecutor(
                max_workers=self._dispatch_threads,
                thread_name_prefix=f"router{self._rid}-dispatch")
        return self._step_pool

    def step(self) -> bool:
        """One scheduler iteration on every replica — retiring ones
        included, so scale-down drains rather than sheds — under the
        strike watchdog (an unproductive replica turns suspect, then
        dead and torn down/replaced), then one autoscale decision
        (deterministic test/benchmark path). Returns whether any
        replica worked.

        With ``FLAGS_serving_dispatch_threads`` > 0 (or the
        ``dispatch_threads=`` constructor override) the per-replica
        steps run concurrently from a bounded worker pool instead of
        the serial loop: each replica's device work overlaps its
        peers' Python scheduling. The barrier at the end of the
        fan-out keeps every fleet-level transition — strike reaping,
        hedge resolution, autoscale — at the step boundary, exactly
        where the serial loop applies them."""
        self._check_replica_fault()
        self._fire_due_hedges()
        worked = False
        if self._dispatch_threads > 0:
            pool = self._dispatch_pool()
            futs = [pool.submit(self._step_replica, eng)
                    for eng in list(self.engines)]
            futs += [pool.submit(eng.step)
                     for eng in list(self._retiring)]
            err = None
            for f in futs:
                try:
                    worked = bool(f.result()) or worked
                except Exception as e:   # match serial: first raiser
                    err = err or e       # propagates after the barrier
            self._reap_dead()
            if err is not None:
                raise err
        else:
            for eng in list(self.engines):
                if eng in self.engines:  # not torn down this iteration
                    worked = self._step_replica(eng) or worked
            self._reap_dead()
            for eng in list(self._retiring):
                worked = eng.step() or worked
        self._resolve_hedges()
        if self._autoscale is not None:
            self._maybe_autoscale()
        self._update_depth_gauges()
        self._update_state_gauges()
        return worked

    @property
    def idle(self) -> bool:
        return all(eng.idle
                   for eng in list(self.engines) + list(self._retiring))

    def run_until_idle(self, max_steps: int = 10_000) -> int:
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"router not idle after {max_steps} steps")
        self._update_depth_gauges()
        return steps

    def drain(self, max_steps: int = 10_000) -> int:
        """Stop admissions and run every replica to idle (rolling
        deploy / shutdown). Later ``submit()`` calls shed with
        :class:`QueueFullError`; already-queued requests finish unless
        their own admission sheds them on the way down (expired TTFT
        deadlines, injected faults). Returns how many requests were
        shed while draining — previously they silently vanished from
        the accounting; now they also land on
        ``STAT_serving_drain_shed``."""
        with self._lock:
            self._draining = True
        engines = list(self.engines) + list(self._retiring)
        before = sum(self._shed_total(e) for e in engines)
        _runlog.log_event("serving_drain",
                          replicas=len(self.engines),
                          queued=[self._depth(e) for e in engines])
        self.run_until_idle(max_steps)
        _monitor.stat_add("STAT_serving_drained")
        shed = sum(self._shed_total(e) for e in engines) - before
        if shed:
            _monitor.stat_add("STAT_serving_drain_shed", shed)
        _runlog.log_event("serving_drain_done", shed=shed)
        return shed

    def _rehome_queued(self, src: ServingEngine,
                       peers: Sequence[ServingEngine]) -> int:
        """Move ``src``'s still-queued requests onto the least-loaded
        live peers via ``adopt_request``; requests no peer can take are
        shed (reason="drain") through ``src`` so the accounting
        identity holds. Returns how many were re-homed."""
        moved = 0
        t_kill = src._clock()
        for req in src.take_queued():
            _tracing.mark(req.id, "kill", t_kill, src.trace_track)
            placed = False
            for peer in sorted(
                    (p for p in peers
                     if not getattr(p, "draining", False)),
                    key=lambda p: (self._depth(p),
                                   -p.cache.blocks_free)):
                if peer.adopt_request(req):
                    placed = True
                    moved += 1
                    _monitor.stat_add("STAT_serving_rerouted")
                    break
            if not placed:
                src._shed(req, QueueFullError(
                    "no live replica could adopt the request during "
                    "drain", reason="drain"), reason="drain")
        return moved

    def drain_replica(self, index: int) -> int:
        """Drain ONE replica out of the set (targeted scale-down /
        maintenance): it stops receiving routes and submissions, its
        queued-but-unadmitted requests are re-routed onto live peers
        with capacity (shed reason="drain" only when no peer can take
        them), and it moves to the retiring list where it keeps
        stepping until its in-flight work finishes. Returns how many
        queued requests were re-homed."""
        with self._lock:
            if not 0 <= index < len(self.engines):
                raise IndexError(
                    f"replica index {index} out of range "
                    f"(have {len(self.engines)})")
            if len(self.engines) == 1:
                raise ValueError(
                    "cannot drain the last replica; use drain() for "
                    "full shutdown")
            eng = self.engines.pop(index)
            eng.draining = True
            self._retiring.append(eng)
        moved = self._rehome_queued(eng, self.engines)
        self._replicas_gauge.set(len(self.engines))
        self._update_depth_gauges()
        _runlog.log_event("serving_drain_replica", replica=index,
                          rerouted=moved,
                          replicas_left=len(self.engines))
        return moved

    def kill_replica(self, index: int, cause: str = "kill") -> dict:
        """Crash ONE replica (chaos / failure handling): unlike
        :meth:`drain_replica` it does not get to finish in-flight
        work. Its KV rows and LoRA pins are released on the spot (zero
        leaks), queued requests re-home onto live peers through the
        ``drain_replica`` adoption path, and in-flight decodes are
        requeued *with their committed tokens*: the adopting survivor
        re-prefills ``request.context`` and continues token-identically
        (greedy) / law-identically (sampled — the per-request RNG key
        travels with the request). Requests no live peer can adopt are
        shed. Every recovered request is marked ``rehomed`` — the third
        term of ``completed + shed + rehomed == offered``. Returns
        ``{"rehomed", "shed", "replicas_left"}``."""
        with self._lock:
            if not 0 <= index < len(self.engines):
                raise IndexError(
                    f"replica index {index} out of range "
                    f"(have {len(self.engines)})")
            if len(self.engines) == 1:
                raise ValueError(
                    "cannot kill the last replica; restart_replica "
                    "replaces one in place")
            eng = self.engines.pop(index)
            eng.draining = True
            eng._health = "dead"
            self._retiring.append(eng)
        # strip in-flight work off the dead scheduler under its step
        # lock: release its rows and adapter pins, requeue each request
        # with tokens intact for re-prefill on a survivor
        displaced: List[Request] = []
        with eng._step_lock:
            for row, req in sorted(eng._active.items(),
                                   key=lambda kv: kv[1].id):
                del eng._active[row]
                eng.cache.release_row(row)
                if req._lora_held:
                    if eng.lora_pool is not None:
                        eng.lora_pool.release(req.tenant)
                    req._lora_held = False
                req.state = "queued"
                req.slot = None
                displaced.append(req)
        rehomed = shed = 0
        t_kill = eng._clock()
        for req in sorted(displaced + eng.take_queued(),
                          key=lambda r: r.id):
            # the kill mark opens the re-home span on the dead
            # replica's track; the adopting peer's admit closes it
            _tracing.mark(req.id, "kill", t_kill, eng.trace_track)
            if req.hard_deadline is not None and \
                    t_kill > req.hard_deadline:
                # deadline enforcement rides through re-homes: expired
                # work is canceled here, never adopted (its blocks and
                # pins were already stripped above)
                eng._finalize_cancel(req, "queued", "deadline")
                continue
            placed = False
            for peer in sorted(
                    (p for p in self.engines
                     if not getattr(p, "draining", False)
                     and p._health != "dead"),
                    key=lambda p: (self._depth(p),
                                   -p.cache.blocks_free)):
                if peer.adopt_request(req):
                    placed = True
                    break
            if placed:
                req.rehomed = True
                rehomed += 1
                _monitor.stat_add("STAT_serving_rehomed")
                self._rehomed_counter.inc()
            else:
                eng._shed(req, QueueFullError(
                    "no live replica could adopt the request after "
                    f"replica {index} was killed", reason="drain"),
                    reason="drain")
                shed += 1
        # the dead replica's prefix cache holds block refs on its pool;
        # drop them unless a live engine shares that pool (prebuilt
        # engines on one kv_pool)
        if not any(p.cache.pool is eng.cache.pool
                   for p in self.engines):
            eng.cache.flush_prefix_cache()
        with self._lock:
            self._kills += 1
            self._rehomed += rehomed
        _monitor.stat_add("STAT_serving_replica_killed")
        self._replicas_gauge.set(len(self.engines))
        self._update_depth_gauges()
        self._update_state_gauges()
        _runlog.log_event("serving_replica_kill", replica=index,
                          cause=cause, t=round(eng._clock(), 6),
                          rehomed=rehomed, shed=shed,
                          replicas_left=len(self.engines))
        return {"rehomed": rehomed, "shed": shed,
                "replicas_left": len(self.engines)}

    def restart_replica(self, index: int, cause: str = "restart"
                        ) -> dict:
        """Replace replica ``index`` with a fresh same-geometry engine:
        the replacement (state ``recovering``, healthy on its first
        productive step) joins the set *before* the old replica is
        killed, so re-homed work can land on it immediately and even a
        sole replica can be restarted. Same geometry + the per-model
        unified step cache means the replacement compiles NOTHING new.
        Returns :meth:`kill_replica`'s accounting dict."""
        if self._model is None:
            raise ValueError(
                "restart_replica needs model= construction (prebuilt "
                "engines= routers cannot build replacements)")
        replacement = ServingEngine(self._model, **self._engine_kwargs)
        self._init_health(replacement)
        replacement._health = "recovering"
        with self._lock:
            if not 0 <= index < len(self.engines):
                raise IndexError(
                    f"replica index {index} out of range "
                    f"(have {len(self.engines)})")
            self.engines.insert(index + 1, replacement)
        info = self.kill_replica(index, cause=cause)
        with self._lock:
            self._restarts += 1
            restarts = self._restarts
        _monitor.stat_add("STAT_serving_replica_restarted")
        _runlog.log_event("serving_replica_recover", replica=index,
                          t=round(replacement._clock(), 6),
                          restarts=restarts)
        return info

    def swap_weights(self, state, *, reset_costs: bool = True
                     ) -> List[int]:
        """Rolling weight hot-swap across the fleet: every replica —
        retiring ones included, they still finish requests — swaps in
        turn via ``ServingEngine.swap_weights``, each under its own step
        lock, with the others serving throughout. No ``drain()``, no
        admission pause: the fleet is briefly mixed-version (normal for
        a rolling deploy; per-replica ``serving_weight_version`` gauges
        show the wavefront) and converges within one pass. Returns the
        per-replica versions after the swap."""
        with self._lock:
            engines = list(self.engines) + list(self._retiring)
        return [eng.swap_weights(state, reset_costs=reset_costs)
                for eng in engines]

    def results(self, reqs=None, timeout: Optional[float] = None
                ) -> List[Request]:
        """Wait for requests across all replicas, submission order."""
        if reqs is not None:
            out = list(reqs)
        else:
            # a re-homed request lives in both the drained source's and
            # the adopting peer's book-keeping — dedupe by request id
            seen: dict = {}
            for eng in self.engines + self._retiring:
                for r in eng.results():
                    if r._hedge_clone:
                        continue   # router-internal hedge copy
                    seen.setdefault(r.id, r)
            return sorted(seen.values(), key=lambda r: r.id)
        for r in out:
            if not r.wait(timeout):
                raise TimeoutError(
                    f"request {r.id} not finished within {timeout}s")
        return out

    def start(self):
        for eng in self.engines:
            eng.start()

    def stop(self):
        for eng in self.engines + self._retiring:
            eng.stop()
        if self._step_pool is not None:
            self._step_pool.shutdown(wait=True)
            self._step_pool = None

    def stats(self) -> dict:
        """Router-level view: replica count, per-replica queue depths
        and free KV blocks, the (shared) mesh shape, aggregate
        goodput/shed counters across replicas (completed, slo_met,
        per-reason sheds, slo_attainment), the autoscale posture when
        enabled, and each replica's full ``stats()`` dict under
        ``per_replica``."""
        # snapshot router-owned counters and the replica lists under
        # _lock (the HTTP scrape thread calls this concurrently with
        # kill/restart/autoscale mutating them), then read per-engine
        # state lock-by-lock with _lock released — no nesting
        with self._lock:
            live = list(self.engines)
            retiring = list(self._retiring)
            draining = self._draining
            kills = self._kills
            restarts = self._restarts
            rehomed = self._rehomed
            scale_ups = self._scale_ups
            scale_downs = self._scale_downs
            hedges = {"fired": self._hedge_fired,
                      "wins": self._hedge_wins,
                      "loses": self._hedge_loses,
                      "dup_tokens": self._hedge_dup_tokens,
                      "tokens": round(self._hedge_tokens, 6),
                      "pending": len(self._hedges)}
        engines = live + retiring
        depths = [self._depth(e) for e in live]
        shed: dict = {}
        canceled: dict = {}
        completed = slo_met = 0
        tenants: dict = {}
        for e in engines:
            with e._lock:
                completed += e._completed
                slo_met += e._slo_met
                for k, v in e._shed_by_reason.items():
                    shed[k] = shed.get(k, 0) + v
                for k, v in e._canceled_by_reason.items():
                    canceled[k] = canceled.get(k, 0) + v
                for name, (c, el, m) in e._tenant_stats.items():
                    t = tenants.setdefault(name, [0, 0, 0])
                    t[0] += c
                    t[1] += el
                    t[2] += m
        out = {
            "replicas": len(live),
            "draining": draining,
            "mesh_shape": (None if live[0].mesh_shape is None
                           else list(live[0].mesh_shape)),
            "queue_depths": depths,
            "kv_blocks_free": [e.cache.blocks_free for e in live],
            "health": [e._health for e in live],
            "kills": kills,
            "restarts": restarts,
            "rehomed": rehomed,
            "completed": completed,
            "slo_met": slo_met,
            "slo_attainment": self._slo_attainment(),
            "shed": shed,
            "shed_total": sum(shed.values()),
            "canceled": canceled,
            "canceled_total": sum(canceled.values()),
            "per_replica": [e.stats() for e in live],
        }
        if self._hedge_ms != 0.0:
            out["hedges"] = hedges
        if self._dispatch_threads > 0:
            out["dispatch_threads"] = self._dispatch_threads
        if self._brk_window_n > 0:
            out["breaker"] = [e._brk_state for e in live]
        if tenants:
            # fleet-wide per-tenant goodput + SLO attainment, summed
            # across replicas (tenants resolve by name everywhere)
            out["tenants"] = {
                name: {"completed": c,
                       "slo_met": m,
                       "slo_attainment": (round(m / e, 4) if e
                                          else None)}
                for name, (c, e, m) in sorted(tenants.items())}
        if self._autoscale is not None:
            out["autoscale"] = {
                "min_replicas": self._autoscale.min_replicas,
                "max_replicas": self._autoscale.max_replicas,
                "scale_ups": scale_ups,
                "scale_downs": scale_downs,
                "retiring": len(retiring),
            }
        return out
