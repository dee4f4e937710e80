"""Disaggregated prefill/decode serving — a fleet of single-role
engines behind one ``submit()`` front door.

The :class:`~paddle_tpu.serving.router.ReplicaRouter` (PR 9) scales
*symmetric* replicas: every engine runs both phases, so a long prefill
wave stalls the decode batch behind it and TTFT inherits decode-batch
jitter. Production fleets (DistServe, Splitwise) split the roles:

- :class:`PrefillEngine` only admits — it runs the bucketed batched
  prompt pass, emits the first generated token (prefill-logits argmax,
  exactly as the symmetric engine does), then *exports* the request:
  the row's block table plus its ``len(prompt)`` committed KV blocks
  leave the cache as an ownership-transfer record
  (``BlockKVCache.export_row``) and enter the fleet's bounded
  :class:`HandoffQueue`.
- :class:`DecodeEngine` only decodes — each step it adopts what the
  queue holds: a record whose blocks live in its own
  :class:`~paddle_tpu.serving.kv_cache.BlockPool` (co-located roles)
  splices in as pure host-side bookkeeping
  (``import_row`` — zero ref changes, zero bytes moved), a record from
  a foreign pool copies its committed blocks through the destination
  allocator (``adopt_row``), after which the source refs drop. Either
  way ``BlockAllocator.leaked()`` stays exact across the handoff.
- :class:`DisaggRouter` owns the fleet: P prefill workers feed D
  decode workers through the queue, whose bound backpressures
  admission (a full queue means prefill stops admitting rather than
  pinning unbounded finished prefills).

Routing gains **fleet-wide prefix affinity** (``FLAGS_serving_prefix_
affinity``): the router keeps a rolling-hash prefix index — the same
``hash((parent_key, chunk))`` chain the pool-level prefix cache
publishes under (``kv_cache.prefix_chain_keys``) — mapping chain keys
to the prefill worker that last prefilled that prefix. A request walks
its own chain deepest-first and routes to the indexed worker (verified
against the worker's actual cache; a stale entry still routes there so
queued same-prefix bursts coalesce), falling back to least-loaded on a
miss. Hit rates compound across the fleet instead of fragmenting
per-replica; ``serving_prefix_affinity_{hits,misses}`` count the
routing decisions and the existing ``serving_kv_blocks_*`` gauges keep
accounting for the blocks themselves.

Every compiled step is shared with the symmetric path: the unified
per-model step cache (``models.generation.step_entry``) keys on
geometry, never on role, so a disaggregated fleet at the same
geometry adds **zero XLA compiles** — ``analysis.recompile.
predict_serving_compiles(disagg=...)`` encodes exactly this, and the
fleet's output is token-identical to a symmetric router on the same
seeded workload (greedy argmax does not care which chip ran it).

Chaos: ``kill_prefill_worker`` tears a prefill worker down mid-flight
— queued requests re-route to surviving workers, in-flight prefills
and undelivered handoff records shed with every block reference
released — and ``kill_decode_worker`` does the same for the decode
role: every in-flight decode's block table is exported off the dead
worker and re-homed onto a survivor (``import_row`` splice when they
share a pool, ``adopt_row`` copy + source-ref release otherwise), so
generation continues token-identically where capacity allows. The
``serving.handoff`` fault site injects drops at adoption time,
retried via ``RetryPolicy.from_flags``, and handoff records that
outlive their TTFT deadline in the queue are shed with their block
references released instead of silently adopted.
"""

from __future__ import annotations

import itertools
import threading
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Tuple

from .. import monitor as _monitor
from ..analysis import concurrency as _ccz
from .. import observability as _obs
from ..observability import runlog as _runlog
from ..observability import tracing as _tracing
from ..resilience.injector import fault_point
from ..resilience.retry import RetryError, RetryPolicy
from .engine import QueueFullError, Request, ServingEngine, _Shed
from .seam import served_with
from .kv_cache import prefix_chain_keys


def parse_disagg(text: str) -> Optional[Tuple[int, int]]:
    """'PxD' -> (n_prefill, n_decode), None when empty."""
    text = str(text).strip()
    if not text:
        return None
    try:
        p, d = (int(s) for s in text.lower().split("x"))
    except Exception:
        raise ValueError(
            f"serving_disagg must be 'PxD' (e.g. '1x2'), got {text!r}")
    if p < 1 or d < 1:
        raise ValueError(
            f"serving_disagg needs at least 1 worker per role, "
            f"got {text!r}")
    return p, d



class _HostTierAffinity:
    """Sentinel value in the fleet prefix index: the worker that last
    prefilled this chain is dead, but the chain itself is resident in
    the fleet-shared host KV tier — still reachable, because ANY live
    prefill worker can promote it from host RAM. Routing resolves the
    marker to the least-loaded live worker; the next publish replaces
    it with that worker."""

    def __repr__(self):
        return "<host-tier>"


_HOST_TIER = _HostTierAffinity()


class _Handoff:
    """One finished prefill in flight between roles: the request, the
    exported block record (which *owns* the blocks' references until
    adopted or released), and the prefill worker that produced it —
    the chaos path sheds a killed worker's undelivered records by
    matching on ``src``."""

    __slots__ = ("req", "rec", "src")

    def __init__(self, req: Request, rec: dict, src: "PrefillEngine"):
        self.req = req
        self.rec = rec
        self.src = src


class HandoffQueue:
    """Bounded FIFO between the prefill and decode roles.

    The bound is the backpressure contract: when full, prefill workers
    stop admitting (their finished-but-undelivered work would pin KV
    blocks indefinitely otherwise). Decode workers ``take`` the oldest
    record they can adopt — optionally filtered, so a co-located
    worker prefers records it can splice for free.
    """

    def __init__(self, bound: int):
        if bound < 1:
            raise ValueError(f"handoff bound must be >= 1, got {bound}")
        self.bound = int(bound)
        self._items: deque = deque()     # guarded-by: _lock
        self._lock = _ccz.make_lock("handoff._lock")
        _ccz.declare_guarded(self, {"_items": "_lock"})

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    @property
    def room(self) -> int:
        with self._lock:
            return self.bound - len(self._items)

    def put(self, item: _Handoff) -> bool:
        with self._lock:
            if len(self._items) >= self.bound:
                return False
            self._items.append(item)
            return True

    def take(self, match=None) -> Optional[_Handoff]:
        """Remove and return the oldest item (matching ``match`` when
        given), or None."""
        with self._lock:
            for i, item in enumerate(self._items):
                if match is None or match(item):
                    del self._items[i]
                    return item
            return None

    def take_by_id(self, rid: int) -> Optional[_Handoff]:
        """Remove and return the record for request ``rid`` (None when
        it is not queued here) — the cancellation path; the caller owns
        the record's block references from then on."""
        with self._lock:
            for i, item in enumerate(self._items):
                if item.req.id == rid:
                    del self._items[i]
                    return item
            return None

    def put_back(self, item: _Handoff):
        """Return an item taken but not adoptable right now to the
        front, preserving FIFO order for the next attempt."""
        with self._lock:
            self._items.appendleft(item)

    def evict_from(self, src: "PrefillEngine") -> List[_Handoff]:
        """Remove every undelivered record a (killed) prefill worker
        produced; the caller owns shedding them + their block refs."""
        with self._lock:
            mine = [it for it in self._items if it.src is src]
            self._items = deque(
                it for it in self._items if it.src is not src)
            return mine


class PrefillEngine(ServingEngine):
    """The admit-only role: bucketed batched prefill, then export.

    ``step()`` admits (one prefill dispatch per bucket — the compiled
    functions are the symmetric engine's, shared through the unified
    step cache) and immediately exports every still-running row into
    the handoff queue; rows free the moment the export record exists,
    so a prefill worker's row count bounds its *per-step* admission
    batch, not its lifetime concurrency. Requests that finish on their
    prefill token (``max_new_tokens == 1`` or an EOS first token)
    never hand off — they completed here.

    Backpressure: no admission happens while the handoff queue is full
    or earlier exports are still waiting to enqueue (``_pending``).
    """

    trace_role = "prefill"

    def __init__(self, model, handoff: HandoffQueue, **kwargs):
        super().__init__(model, **kwargs)
        self._handoff = handoff
        self._pending: deque = deque()  # guarded-by: _step_lock

    def _flush_pending(self) -> int:  # holds: _step_lock
        moved = 0
        while self._pending:
            if not self._handoff.put(self._pending[0]):
                break
            self._pending.popleft()
            moved += 1
        return moved

    def _stage_running(self) -> int:  # holds: _step_lock
        """Export every running row into ``_pending`` (deterministic
        request-id order so seeded runs replay exactly)."""
        staged = 0
        for row in sorted(self._active,
                          key=lambda r: self._active[r].id):
            req = self._active.pop(row)
            rec = self.cache.export_row(row)
            req.slot = None          # in flight between roles
            if req._lora_held:
                # the adapter pin is engine-local (page ids never
                # travel); the decode side re-acquires by tenant name
                self.lora_pool.release(req.tenant)
                req._lora_held = False
            self._pending.append(_Handoff(req, rec, self))
            _tracing.mark(req.id, "export", self._clock(),
                          self.trace_track)
            staged += 1
            if _runlog.enabled():
                _runlog.log_event(
                    "serving_handoff", request=req.id, stage="export",
                    engine=self._eid, blocks=len(rec["blocks"]),
                    length=rec["length"])
        return staged

    def step(self) -> bool:
        with self._step_lock:
            worked = self._flush_pending() > 0
            if not self._pending and self._handoff.room > 0:
                worked = bool(self._admit()) or worked
                worked = self._stage_running() > 0 or worked
                worked = self._flush_pending() > 0 or worked
            if self.kv_tier is not None:
                self._demote_sweep()
            self._blocks_used_g.set(self.cache.blocks_used)
            self._blocks_free_g.set(self.cache.blocks_free)
            return worked

    @property
    def idle(self) -> bool:
        with self._lock:
            queued = bool(self._queue)
        return not queued and not self._active and not self._pending

    def shed_pending(self, reason: str = "fault") -> int:  # holds: _step_lock
        """Shed every exported-but-undelivered record, releasing its
        block references — the killed-worker cleanup path."""
        shed = 0
        while self._pending:
            item = self._pending.popleft()
            item.rec["pool"].release_blocks(item.rec["blocks"])
            self._shed(item.req, _Shed(
                "prefill worker torn down before handoff"),
                reason=reason)
            shed += 1
        return shed

    def cancel_pending(self, rid: int,
                       reason: str = "client") -> Optional[dict]:
        """Cancel one staged-but-undelivered export: the record owns
        its block references until it reaches the handoff queue, so a
        cancel here releases them directly (the LoRA pin was already
        dropped at export time)."""
        with self._step_lock:
            for i, item in enumerate(self._pending):
                if item.req.id == rid:
                    del self._pending[i]
                    item.rec["pool"].release_blocks(
                        item.rec["blocks"])
                    self._finalize_cancel(item.req, "handoff", reason)
                    return {"id": rid, "stage": "handoff",
                            "reason": reason}
        return None


class DecodeEngine(ServingEngine):
    """The decode-only role: adopt handoffs, then batched decode (or
    speculative draft–verify) — the same compiled steps the symmetric
    engine uses, at the same geometry, so the role split costs zero
    XLA compiles.

    Adoption prefers records whose blocks already live in this
    worker's pool (co-located prefill: ``import_row``, a free splice)
    and falls back to cross-pool block copies (``adopt_row``). A
    record that doesn't fit right now (no free row / dry pool) stays
    queued with its references intact — that *is* the backpressure.
    """

    trace_role = "decode"

    def __init__(self, model, handoff: HandoffQueue, **kwargs):
        super().__init__(model, **kwargs)
        self._handoff = handoff
        self.adopted = 0          # guarded-by: _step_lock
        self.adopted_copies = 0   # guarded-by: _step_lock
        _ccz.declare_guarded(self, {"adopted": "_step_lock",
                                    "adopted_copies": "_step_lock"})

    def submit(self, *a, **k):
        raise RuntimeError(
            "DecodeEngine does not accept submissions; submit through "
            "the DisaggRouter (prefill workers feed this engine)")

    def _handoff_attempt(self, item: _Handoff) -> Optional[int]:  # holds: _step_lock
        """Adopt one record; None = no capacity (leave it queued).
        The ``serving.handoff`` fault site injects here: ``skip``
        sheds the request, drop/error retries per RetryPolicy."""
        kind = fault_point("serving.handoff")
        if kind == "skip":
            raise _Shed("injected shed at serving.handoff")
        if item.rec["epoch"] != item.rec["pool"].epoch:
            raise _Shed("the record's KV went with its rebuilt pool")
        same_pool = item.rec["pool"] is self.cache.pool
        row = (self.cache.import_row(item.rec) if same_pool
               else self.cache.adopt_row(item.rec))
        if row is None:
            return None
        if not same_pool:
            # the copy is done; drop the record's source references
            item.rec["pool"].release_blocks(item.rec["blocks"])
            self.adopted_copies += 1
        return row

    def _adopt_handoffs(self) -> int:  # holds: _step_lock
        """Drain what fits: same-pool records first (free splices),
        then cross-pool copies, oldest first within each class."""
        adopted = 0
        for match in (lambda it: it.rec["pool"] is self.cache.pool,
                      None):
            while self.cache.num_free > 0:
                item = self._handoff.take(match)
                if item is None:
                    break
                if item.req.hard_deadline is not None and \
                        self._clock() > item.req.hard_deadline:
                    # hard (client-patience) expiry in the queue is a
                    # cancel, not a shed: the client is gone, so the
                    # record's exported references release here and
                    # the request exits as canceled-not-completed
                    item.rec["pool"].release_blocks(
                        item.rec["blocks"])
                    self._finalize_cancel(item.req, "handoff",
                                          "deadline")
                    continue
                if item.req.deadline is not None and \
                        self._clock() > item.req.deadline:
                    # a record that outlived its TTFT deadline in the
                    # queue used to be adopted anyway — decode cycles
                    # spent on a request the SLO already gave up on,
                    # and its blocks pinned the whole time. Shed it
                    # with the exported references released (the
                    # record owns them until adoption; the LoRA pin
                    # was already dropped at export)
                    item.rec["pool"].release_blocks(item.rec["blocks"])
                    self._shed(item.req, _Shed(
                        "handoff outlived its TTFT deadline in the "
                        "queue"), reason="deadline")
                    continue
                try:
                    row = RetryPolicy.from_flags(
                        "serving.handoff").call(
                            self._handoff_attempt, item)
                except (_Shed, RetryError) as e:
                    item.rec["pool"].release_blocks(
                        item.rec["blocks"])
                    self._shed(item.req, e)
                    continue
                if row is None:      # no space: keep refs, retry later
                    self._handoff.put_back(item)
                    break
                if item.req.tenant and self.lora_pool is not None:
                    # re-pin the tenant's page in THIS engine's pool
                    # (an adapter evicted mid-handoff sheds here)
                    try:
                        self.lora_pool.acquire(item.req.tenant)
                        item.req._lora_held = True
                    except ValueError as e:
                        self.cache.release_row(row)
                        self._shed(item.req, _Shed(str(e)))
                        continue
                item.req.slot = row
                self._active[row] = item.req
                self.adopted += 1
                adopted += 1
                _tracing.mark(item.req.id, "adopt", self._clock(),
                              self.trace_track)
                _monitor.stat_add("STAT_serving_handoffs")
                if _runlog.enabled():
                    _runlog.log_event(
                        "serving_handoff", request=item.req.id,
                        stage="adopt", engine=self._eid, slot=row,
                        copied=not (item.rec["pool"]
                                    is self.cache.pool))
        return adopted

    def adopt_step(self) -> bool:
        """The admission half of :meth:`step`: reap hard-expired slots
        (their rows free up for this very step's adoptions), then drain
        adoptable handoffs. Split out so the threaded disagg router can
        run this serially — a cross-pool adoption derefs the *source*
        prefill worker's pool (``release_blocks`` above), which is not
        safe concurrently with another worker allocating on it — while
        fanning the decode halves out in parallel."""
        with self._step_lock:
            reaped = self._reap_expired()
            worked = self._adopt_handoffs() > 0
            return bool(worked or reaped)

    def decode_step(self) -> bool:
        """The compute half of :meth:`step`: one decode dispatch
        (``_decode_any``) plus the host-tier demote sweep and pool
        gauges. Only touches this engine's own pool and
        internally-locked shared planes (LoRA pool, tier manager,
        metrics), so the threaded router may run decode halves of
        workers with *distinct* pools concurrently."""
        with self._step_lock:
            produced = self._decode_any()
            if self.kv_tier is not None:
                self._demote_sweep()
            self._blocks_used_g.set(self.cache.blocks_used)
            self._blocks_free_g.set(self.cache.blocks_free)
            return bool(produced)

    def step(self) -> bool:
        worked = self.adopt_step()
        return self.decode_step() or worked


class DisaggRouter:
    """One ``submit()`` front door over a disaggregated fleet: P
    :class:`PrefillEngine` workers feed D :class:`DecodeEngine`
    workers through a bounded :class:`HandoffQueue`.

    ``colocate=True`` (default) pairs decode worker ``j`` with prefill
    worker ``j % P``'s :class:`BlockPool` — the handoff is then a pure
    block-table splice. ``colocate=False`` gives every worker its own
    pool (the multi-host shape) and handoffs copy committed blocks
    through the destination allocator.

    The interface mirrors :class:`ReplicaRouter` (``submit`` /
    ``step`` / ``run_until_idle`` / ``drain`` / ``results`` /
    ``stats`` / ``start`` / ``stop``) so ``tools/loadgen.py`` drives
    either interchangeably.
    """

    _router_ids = itertools.count()

    # fleet-wide affinity index bound: entries are (int key -> engine)
    # pairs, evicted LRU — big enough to cover every prefix the pools
    # can physically cache, small enough to never matter in memory
    AFFINITY_CAP = 8192

    def __init__(self, model, n_prefill: Optional[int] = None,
                 n_decode: Optional[int] = None,
                 prefix_affinity: Optional[bool] = None,
                 handoff_queue: Optional[int] = None,
                 colocate: bool = True,
                 dispatch_threads: Optional[int] = None,
                 **engine_kwargs):
        from .. import flags as _flags
        # the roles hand rows of one kind of cache to each other
        served_with(model, "disaggregation", "DisaggRouter")
        g = _flags.get_flags(["serving_disagg",
                              "serving_prefix_affinity",
                              "serving_handoff_queue",
                              "serving_dispatch_threads"])
        if n_prefill is None or n_decode is None:
            dims = parse_disagg(g["serving_disagg"])
            if dims is None:
                dims = (1, 1)
            n_prefill = int(n_prefill if n_prefill is not None
                            else dims[0])
            n_decode = int(n_decode if n_decode is not None
                           else dims[1])
        if n_prefill < 1 or n_decode < 1:
            raise ValueError(
                f"need at least 1 worker per role, got "
                f"{n_prefill} prefill x {n_decode} decode")
        self.prefix_affinity = bool(
            prefix_affinity if prefix_affinity is not None
            else g["serving_prefix_affinity"])
        bound = int(handoff_queue if handoff_queue is not None
                    else g["serving_handoff_queue"])
        self._handoff = HandoffQueue(bound)
        self._model = model
        if "lora_pool" not in engine_kwargs:
            # one shared adapter pool for the whole fleet: the prefill
            # side releases its pin on export, the decode side
            # re-acquires by tenant name on adoption — page ids never
            # cross the role boundary, pool pages do (they're the same
            # arrays object)
            gl = _flags.get_flags(["serving_lora_rank",
                                   "serving_lora_max_adapters"])
            rank = engine_kwargs.get("lora_rank")
            rank = int(rank if rank is not None
                       else gl["serving_lora_rank"])
            if rank > 0:
                from .lora import LoRAPool
                mx = engine_kwargs.get("lora_max_adapters")
                engine_kwargs = dict(engine_kwargs)
                engine_kwargs["lora_pool"] = LoRAPool(
                    served_with(model, "lora", "lora_rank > 0").lora_config,
                    rank,
                    int(mx if mx is not None
                        else gl["serving_lora_max_adapters"]))
        if "kv_tier" not in engine_kwargs:
            # one host tier across BOTH roles: a chain demoted by any
            # prefill or decode worker is promotable by every other,
            # and it outlives any one worker's pool (the crash-safe
            # half of the fleet prefix index below)
            gt = _flags.get_flags(["serving_host_tier",
                                   "serving_host_blocks",
                                   "serving_block_size"])
            if gt["serving_host_tier"]:
                from .kv_tier import HostBlockStore, TierManager
                cfg = served_with(model, "host_tier",
                               "FLAGS_serving_host_tier").cache_kinds[0]
                bs = engine_kwargs.get("block_size")
                bs = int(bs if bs is not None
                         else gt["serving_block_size"])
                engine_kwargs = dict(engine_kwargs)
                engine_kwargs["kv_tier"] = TierManager(
                    HostBlockStore(
                        len(cfg.layers), cfg.kv_heads, cfg.head_dim,
                        block_size=bs,
                        num_blocks=int(gt["serving_host_blocks"])))
        self.kv_tier = engine_kwargs.get("kv_tier")
        self.prefills: List[PrefillEngine] = [
            PrefillEngine(model, self._handoff, **engine_kwargs)
            for _ in range(n_prefill)]
        self.decodes: List[DecodeEngine] = []
        for j in range(n_decode):
            kw = dict(engine_kwargs)
            if colocate:
                kw["kv_pool"] = \
                    self.prefills[j % n_prefill].cache.pool
            self.decodes.append(
                DecodeEngine(model, self._handoff, **kw))
        self.colocate = bool(colocate)
        # threaded fleet dispatch (0 = the serial loop, byte-identical
        # scheduling): prefill steps fan out in parallel (each prefill
        # worker owns a private pool), then — after a barrier — the
        # adoption sweeps run serially (cross-pool adoption derefs the
        # source pool) and the decode dispatches fan out grouped by
        # pool identity (colocate aliases several decode workers to
        # one prefill pool; same pool -> same worker thread).
        self._dispatch_threads = int(
            dispatch_threads if dispatch_threads is not None
            else g["serving_dispatch_threads"])
        if self._dispatch_threads < 0:
            raise ValueError(
                "dispatch_threads must be >= 0, got "
                f"{self._dispatch_threads}")
        self._step_pool = None   # lazily-built ThreadPoolExecutor
        self._killed: List[ServingEngine] = []  # guarded-by: _lock
        self._rehomed = 0                       # guarded-by: _lock
        self._draining = False                  # guarded-by: _lock
        self._lock = _ccz.make_lock("disagg._lock")
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # chain key -> PrefillEngine that last prefilled that prefix
        self._affinity: "OrderedDict[int, PrefillEngine]" = \
            OrderedDict()                       # guarded-by: _lock
        rid = str(next(DisaggRouter._router_ids))
        self._rid = rid
        self._aff_hits = _obs.counter(
            "serving_prefix_affinity_hits",
            "requests routed to the prefill worker already holding "
            "their longest cached prefix (fleet prefix index)"
            ).labels(router=rid)
        self._aff_misses = _obs.counter(
            "serving_prefix_affinity_misses",
            "requests routed least-loaded because no live worker held "
            "any of their prefix (or the index was stale)"
            ).labels(router=rid)
        self._handoff_gauge = _obs.gauge(
            "serving_handoff_queue_depth",
            "finished prefills waiting for a decode worker to adopt "
            "their KV blocks (bounded; full = prefill backpressure)"
            ).labels(router=rid)
        self._handoff_gauge.set(0)
        self._rehomed_counter = _obs.counter(
            "serving_rehomed_total",
            "requests recovered off a killed replica/worker onto a "
            "live peer").labels(router=rid)
        _obs.gauge(
            "serving_disagg_workers",
            "single-role workers in this disaggregated fleet, by role"
            ).labels(router=rid, role="prefill").set(n_prefill)
        _obs.gauge(
            "serving_disagg_workers",
            "single-role workers in this disaggregated fleet, by role"
            ).labels(router=rid, role="decode").set(n_decode)
        _ccz.declare_guarded(self, {
            "_rehomed": "_lock", "_draining": "_lock",
            "_killed": "_lock", "_affinity": "_lock"})

    # ----------------------------------------------------------- routing
    @property
    def engines(self) -> List[ServingEngine]:
        """All live workers, prefill first — the duck-typed surface
        loadgen and the leak checks walk."""
        return list(self.prefills) + list(self.decodes)

    @property
    def _retiring(self) -> List[ServingEngine]:
        # interface parity with ReplicaRouter (loadgen walks this)
        return list(self._killed)

    def _depth(self, eng: ServingEngine) -> int:
        with eng._lock:
            return len(eng._queue) + len(eng._active)

    def _least_loaded(self) -> List[int]:
        return sorted(
            (i for i, e in enumerate(self.prefills)
             if not e.draining),
            key=lambda i: (self._depth(self.prefills[i]),
                           -self.prefills[i].cache.blocks_free, i))

    def _affinity_pick(self, prompt: Sequence[int],
                       keys: Sequence[int]) -> Optional[int]:
        """Deepest indexed chain key whose worker is alive — verified
        against the worker's actual pool (a stale hit still routes
        there: queued same-prefix requests coalesce and re-publish)."""
        for key in reversed(keys):
            # the index is shared with every submitting thread and the
            # kill path — reads, LRU bumps and publishes all take the
            # router lock (an unlocked move_to_end on the OrderedDict
            # corrupts its internal linkage under contention)
            with self._lock:
                eng = self._affinity.get(key)
                if eng is _HOST_TIER:
                    if self.kv_tier is None or \
                            not self.kv_tier.has_chain(key):
                        continue
                    self._affinity.move_to_end(key)
                    idx = None  # resolved to a live worker below
                elif eng is None or eng.draining or \
                        eng not in self.prefills:
                    continue
                else:
                    self._affinity.move_to_end(key)
                    idx = self.prefills.index(eng)
            if idx is None:
                # host-tier marker: the chain is promotable by ANY live
                # worker, so the least-loaded one takes it — its next
                # publish replaces the marker with a live entry
                order = self._least_loaded()
                if not order:
                    return None
                self._aff_hits.add(1)
                _monitor.stat_add("STAT_serving_affinity_hits")
                return order[0]
            if eng.cache.match_prefix_blocks(prompt) > 0:
                self._aff_hits.add(1)
                _monitor.stat_add("STAT_serving_affinity_hits")
            else:
                self._aff_misses.add(1)
                _monitor.stat_add("STAT_serving_affinity_misses")
            return idx
        return None

    def _publish_affinity(self, keys: Sequence[int],
                          eng: "PrefillEngine"):
        with self._lock:
            for key in keys:
                self._affinity[key] = eng
                self._affinity.move_to_end(key)
            while len(self._affinity) > self.AFFINITY_CAP:
                self._affinity.popitem(last=False)

    def _route_attempt(self, prompt, max_new_tokens, eos_token_id,
                       priority, **decode_kwargs) -> Request:
        kind = fault_point("serving.route")
        if kind == "skip":
            _monitor.stat_add("STAT_serving_route_shed")
            raise QueueFullError(
                "submission shed by injected fault at serving.route",
                reason="fault")
        keys: List[int] = []
        order = self._least_loaded()
        if not order:
            raise QueueFullError("no live prefill worker", reason="drain")
        if self.prefix_affinity:
            bs = self.prefills[0].cache.block_size
            keys = prefix_chain_keys(prompt, bs)
            pick = self._affinity_pick(prompt, keys) if keys else None
            if pick is None and keys:
                self._aff_misses.add(1)
                _monitor.stat_add("STAT_serving_affinity_misses")
            if pick is not None:
                order = [pick] + [i for i in order if i != pick]
        last_err: Optional[QueueFullError] = None
        for i in order:
            eng = self.prefills[i]
            try:
                req = eng.submit(prompt, max_new_tokens=max_new_tokens,
                                 eos_token_id=eos_token_id,
                                 priority=priority, _log_request=False,
                                 **decode_kwargs)
            except QueueFullError as e:
                last_err = e
                continue
            _monitor.stat_add("STAT_serving_routed")
            _runlog.log_event("serving_route", request=req.id,
                              replica=i, depth=self._depth(eng),
                              kv_blocks_free=eng.cache.blocks_free,
                              role="prefill")
            if self.prefix_affinity and keys:
                self._publish_affinity(keys, eng)
            return req
        _monitor.stat_add("STAT_serving_route_shed")
        raise last_err if last_err is not None else QueueFullError(
            "every prefill worker queue is full")

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               priority: Optional[int] = None,
               _log_request: bool = True, **decode_kwargs) -> Request:
        """Route one request to a prefill worker — prefix-affine when
        the fleet index knows the prompt's prefix, least-loaded
        otherwise. Decode capacity is reached through the handoff
        queue, never directly. Per-request decoding fields
        (``temperature``/``top_k``/``top_p``/``stop``/``seed``/
        ``json_mode``/``tenant``) pass through to the prefill engine
        and travel with the handoff — the RNG key, grammar cursor and
        tenant name live on the Request, so a sampled or constrained
        stream continues bit-exactly across the role boundary."""
        with self._lock:
            if self._draining:
                raise QueueFullError("router is draining: submissions "
                                     "are shed for rolling shutdown",
                                     reason="drain")
        if _log_request and _runlog.enabled():
            prompt = [int(t) for t in prompt]
            extra = {}
            for k in ("temperature", "top_k", "top_p", "seed",
                      "json_mode", "tenant"):
                v = decode_kwargs.get(k)
                if v:
                    extra[k] = v
            if decode_kwargs.get("stop"):
                extra["stop"] = [list(s)
                                 for s in decode_kwargs["stop"]]
            _runlog.log_event(
                "serving_request",
                t=round(self.prefills[0]._clock(), 6), prompt=prompt,
                max_new_tokens=int(
                    max_new_tokens if max_new_tokens is not None
                    else self.prefills[0].default_max_new_tokens),
                priority=int(priority if priority is not None else 1),
                router=self._rid, **extra)
        try:
            return RetryPolicy.from_flags("serving.route").call(
                self._route_attempt, prompt, max_new_tokens,
                eos_token_id, priority, **decode_kwargs)
        except RetryError as e:
            _monitor.stat_add("STAT_serving_route_shed")
            raise QueueFullError(
                f"routing retries exhausted: {e}", reason="fault") from e

    # ----------------------------------------------------- LoRA adapters
    def load_adapter(self, name: str, state) -> int:
        """Load a tenant adapter once per distinct pool (the default
        fleet shares one). Returns the page id on the last pool."""
        pools: list = []
        page = None
        for eng in self.engines:
            if eng.lora_pool is None:
                raise ValueError(
                    "fleet has no LoRA pool; construct with "
                    "lora_rank > 0 (FLAGS_serving_lora_rank)")
            if any(eng.lora_pool is p for p in pools):
                continue
            pools.append(eng.lora_pool)
            page = eng.load_adapter(name, state)
        return page

    def evict_adapter(self, name: str) -> int:
        """Evict a tenant adapter from every distinct pool; refuses
        (ValueError) while in-flight work anywhere pins it."""
        pools: list = []
        page = None
        for eng in self.engines:
            if eng.lora_pool is None or \
                    any(eng.lora_pool is p for p in pools):
                continue
            pools.append(eng.lora_pool)
            page = eng.evict_adapter(name)
        if page is None:
            raise ValueError("fleet has no LoRA pool")
        return page

    # ------------------------------------------------------ cancellation
    def cancel(self, rid: int, reason: str = "client"
               ) -> Optional[dict]:
        """Cancel request ``rid`` wherever it lives in the fleet:
        queued or mid-prefill on a prefill worker, staged for export,
        sitting in the handoff queue (the record's block references
        release here), or mid-decode on a decode worker. The Request
        object is shared across every engine's bookkeeping, so exactly
        one stage holds its resources — the first hit wins and the
        walk stops (a re-homed copy can never double-release). Returns
        ``{"id", "stage", "reason"}`` or None for unknown/finished
        ids. Pure host-side: zero new compiles
        (``predict_serving_compiles(cancel=N)``)."""
        rid = int(rid)
        with self._lock:
            engines = (list(self.prefills) + list(self.decodes)
                       + list(self._killed))
        req = None
        for eng in engines:
            with eng._lock:
                req = next((r for r in eng._all if r.id == rid), None)
            if req is not None:
                break
        if req is None or req.state in ("done", "shed", "canceled"):
            return None
        out = None
        # queued / mid-prefill-wave / mid-decode — whichever engine
        # actually holds the queue entry or the slot
        for eng in engines:
            out = eng._cancel_request(req, reason)
            if out is not None:
                break
        if out is None:
            # staged exports (finished prefill waiting for queue room)
            for eng in engines:
                if isinstance(eng, PrefillEngine):
                    out = eng.cancel_pending(rid, reason)
                    if out is not None:
                        break
        if out is None:
            # in flight between the roles
            item = self._handoff.take_by_id(rid)
            if item is not None:
                item.rec["pool"].release_blocks(item.rec["blocks"])
                item.src._finalize_cancel(item.req, "handoff", reason)
                out = {"id": rid, "stage": "handoff", "reason": reason}
        if out is not None:
            self._purge_affinity(req.prompt)
        return out

    def _purge_affinity(self, prompt: Sequence[int]) -> int:
        """Drop stale fleet-index entries for a canceled prompt's
        prefix chain: an entry is purged when its worker is gone or no
        longer holds any cached block of the prefix (entries whose
        worker still holds the prefix stay — other requests share
        it)."""
        if not self.prefix_affinity or not self.prefills:
            return 0
        bs = self.prefills[0].cache.block_size
        keys = prefix_chain_keys(prompt, bs)
        purged = 0
        with self._lock:
            for key in keys:
                eng = self._affinity.get(key)
                if eng is None:
                    continue
                if eng is _HOST_TIER:
                    # marker entries stay while the chain is resident
                    # in the host tier — still reachable fleet-wide
                    if self.kv_tier is None or \
                            not self.kv_tier.has_chain(key):
                        del self._affinity[key]
                        purged += 1
                    continue
                if eng not in self.prefills or \
                        eng.cache.match_prefix_blocks(prompt) == 0:
                    del self._affinity[key]
                    purged += 1
        return purged

    # ---------------------------------------------------------- stepping
    def _dispatch_pool(self):
        """The persistent bounded worker pool for threaded dispatch,
        built on first use and shut down by :meth:`stop`."""
        if self._step_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._step_pool = ThreadPoolExecutor(
                max_workers=self._dispatch_threads,
                thread_name_prefix=f"disagg{self._rid}-dispatch")
        return self._step_pool

    @staticmethod
    def _await_all(futs) -> bool:
        worked = False
        err = None
        for f in futs:
            try:
                worked = bool(f.result()) or worked
            except Exception as e:     # barrier first, raise after
                err = err or e
        if err is not None:
            raise err
        return worked

    def step(self) -> bool:
        """One fleet iteration: every prefill worker (admission +
        export), then every decode worker (adoption + decode), in
        fixed order — the deterministic test/benchmark path.

        With ``FLAGS_serving_dispatch_threads`` > 0 (or the
        ``dispatch_threads=`` constructor override) the per-worker
        steps fan out over a bounded pool in three phases: prefill
        steps in parallel (private pools), a barrier so every export
        is visible, the adoption sweeps serially on the calling thread
        (a cross-pool adoption releases blocks on the *source*
        prefill pool — unsafe concurrently with its other users), then
        the decode dispatches in parallel grouped by pool identity."""
        if self._dispatch_threads > 0:
            pool = self._dispatch_pool()
            worked = self._await_all(
                [pool.submit(eng.step) for eng in list(self.prefills)])
            decodes = list(self.decodes)
            for eng in decodes:
                worked = eng.adopt_step() or worked
            groups: dict = {}
            for eng in decodes:
                groups.setdefault(id(eng.cache.pool), []).append(eng)

            def _run_group(group):
                w = False
                for eng in group:
                    w = eng.decode_step() or w
                return w

            worked = self._await_all(
                [pool.submit(_run_group, grp)
                 for grp in groups.values()]) or worked
        else:
            worked = False
            for eng in list(self.prefills):
                worked = eng.step() or worked
            for eng in list(self.decodes):
                worked = eng.step() or worked
        self._handoff_gauge.set(len(self._handoff))
        return worked

    @property
    def idle(self) -> bool:
        return (len(self._handoff) == 0 and
                all(e.idle for e in self.prefills) and
                all(e.idle for e in self.decodes))

    def run_until_idle(self, max_steps: int = 10_000) -> int:
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"disagg fleet not idle after {max_steps} steps "
                    f"({len(self._handoff)} handoffs queued)")
        return steps

    def drain(self, max_steps: int = 10_000) -> int:
        """Stop admissions and run the fleet to idle; returns how many
        requests were shed on the way down."""
        with self._lock:
            self._draining = True
        engines = self.engines + self._retiring
        def _shed_total(e):
            with e._lock:
                return sum(e._shed_by_reason.values())
        before = sum(_shed_total(e) for e in engines)
        _runlog.log_event("serving_drain",
                          replicas=len(engines),
                          queued=[self._depth(e) for e in engines])
        self.run_until_idle(max_steps)
        _monitor.stat_add("STAT_serving_drained")
        shed = sum(_shed_total(e) for e in engines) - before
        if shed:
            _monitor.stat_add("STAT_serving_drain_shed", shed)
        _runlog.log_event("serving_drain_done", shed=shed)
        return shed

    # ------------------------------------------------------------- chaos
    def kill_prefill_worker(self, index: int) -> dict:
        """Tear one prefill worker down mid-flight (chaos): queued
        requests re-route to surviving prefill workers with capacity,
        in-flight prefills and undelivered handoff records shed with
        every block reference released, and the fleet prefix index
        forgets the worker. Returns the cleanup accounting."""
        with self._lock:
            if not 0 <= index < len(self.prefills):
                raise IndexError(
                    f"prefill worker {index} out of range "
                    f"(have {len(self.prefills)})")
            if len(self.prefills) == 1:
                # no survivor can take the queue: everything sheds
                pass
            eng = self.prefills.pop(index)
            eng.draining = True
            self._killed.append(eng)
        # forget the worker in the affinity index — EXCEPT entries
        # whose prefix chain is resident in the fleet-shared host
        # tier: those chains outlive the worker (any survivor can
        # promote them), so purging the entry would orphan a chain
        # that is still reachable. Convert to the host-tier marker
        # instead; drop only what is actually unreachable.
        kept = 0
        with self._lock:
            for key in [k for k, v in self._affinity.items()
                        if v is eng]:
                if self.kv_tier is not None and \
                        self.kv_tier.has_chain(key):
                    self._affinity[key] = _HOST_TIER
                    kept += 1
                else:
                    del self._affinity[key]
        # undelivered handoff records: shed + release their refs
        shed = 0
        for item in self._handoff.evict_from(eng):
            item.rec["pool"].release_blocks(item.rec["blocks"])
            eng._shed(item.req, _Shed(
                "prefill worker killed before handoff delivery"))
            shed += 1
        with eng._step_lock:
            shed += eng.shed_pending()
            # mid-prefill actives: row + blocks released through the
            # normal retirement path
            for row, req in list(eng._active.items()):
                del eng._active[row]
                eng.cache.release_row(row)
                eng._shed(req, _Shed("prefill worker killed"))
                shed += 1
        # still-queued requests re-home onto survivors
        rerouted = 0
        t_kill = eng._clock()
        for req in eng.take_queued():
            _tracing.mark(req.id, "kill", t_kill, eng.trace_track)
            placed = False
            for i in self._least_loaded():
                if self.prefills[i].adopt_request(req):
                    placed = True
                    rerouted += 1
                    _monitor.stat_add("STAT_serving_rerouted")
                    break
            if not placed:
                eng._shed(req, QueueFullError(
                    "no surviving prefill worker could adopt the "
                    "request", reason="drain"), reason="drain")
                shed += 1
        # the prefix cache's own refs would read as leaks of a dead
        # worker; flush unless a co-located decode still shares the
        # pool (then its lifecycle owns them)
        if not any(d.cache.pool is eng.cache.pool
                   for d in self.decodes):
            eng.cache.flush_prefix_cache()
        _monitor.stat_add("STAT_serving_worker_killed")
        _runlog.log_event("serving_worker_kill", role="prefill",
                          worker=index, shed=shed, rerouted=rerouted,
                          affinity_kept=kept,
                          t=round(eng._clock(), 6),
                          prefills_left=len(self.prefills))
        return {"shed": shed, "rerouted": rerouted,
                "affinity_kept": kept,
                "prefills_left": len(self.prefills)}

    def kill_decode_worker(self, index: int) -> dict:
        """Tear one decode worker down mid-decode (chaos): every
        in-flight request's row leaves the dead worker as an
        ownership-transfer record (``export_row``) and re-homes onto
        a surviving decode worker — a free block-table splice when
        they share a :class:`BlockPool` (co-located fleets), a block
        copy through the survivor's allocator otherwise, after which
        the source references drop. The request then continues
        decoding token-identically (its RNG key, grammar cursor and
        committed tokens travel on the Request). A row no survivor
        has room for sheds with every reference released; LoRA pins
        move with the request (released on the dead worker,
        re-acquired by tenant name on the survivor). Refuses to kill
        the last decode worker — the handoff queue would never drain
        again. Returns the cleanup accounting."""
        with self._lock:
            if not 0 <= index < len(self.decodes):
                raise IndexError(
                    f"decode worker {index} out of range "
                    f"(have {len(self.decodes)})")
            if len(self.decodes) == 1:
                raise ValueError(
                    "cannot kill the last decode worker; the handoff "
                    "queue would never drain")
            eng = self.decodes.pop(index)
            eng.draining = True
            eng._health = "dead"
            self._killed.append(eng)
        rehomed = shed = 0
        with eng._step_lock:
            for row in sorted(eng._active,
                              key=lambda r: eng._active[r].id):
                req = eng._active.pop(row)
                _tracing.mark(req.id, "kill", eng._clock(),
                              eng.trace_track)
                if req._lora_held:
                    eng.lora_pool.release(req.tenant)
                    req._lora_held = False
                rec = eng.cache.export_row(row)
                req.slot = None
                # same-pool survivors first: those re-homes are free
                # splices; within a class, least-loaded
                order = sorted(
                    self.decodes,
                    key=lambda p: (
                        0 if rec["pool"] is p.cache.pool else 1,
                        self._depth(p), -p.cache.blocks_free))
                for peer in order:
                    same_pool = rec["pool"] is peer.cache.pool
                    row2 = (peer.cache.import_row(rec) if same_pool
                            else peer.cache.adopt_row(rec))
                    if row2 is None:
                        continue
                    if not same_pool:
                        # the copy is done; drop the source references
                        rec["pool"].release_blocks(rec["blocks"])
                    if req.tenant and peer.lora_pool is not None:
                        try:
                            peer.lora_pool.acquire(req.tenant)
                            req._lora_held = True
                        except ValueError as e:
                            peer.cache.release_row(row2)
                            eng._shed(req, _Shed(str(e)))
                            shed += 1
                            break
                    req.slot = row2
                    peer._active[row2] = req
                    req.rehomed = True
                    _tracing.mark(req.id, "adopt", peer._clock(),
                                  peer.trace_track)
                    rehomed += 1
                    _monitor.stat_add("STAT_serving_rehomed")
                    self._rehomed_counter.inc()
                    if _runlog.enabled():
                        _runlog.log_event(
                            "serving_handoff", request=req.id,
                            stage="adopt", engine=peer._eid,
                            slot=row2, copied=not same_pool)
                    break
                else:
                    # no survivor had room (every import/adopt came
                    # back None, leaving the record's references
                    # intact) — shed with everything released
                    rec["pool"].release_blocks(rec["blocks"])
                    eng._shed(req, QueueFullError(
                        "no surviving decode worker could adopt the "
                        "row", reason="drain"), reason="drain")
                    shed += 1
        # the dead worker's prefix-cache refs would read as leaks
        # unless a live engine still shares (and thus owns) the pool
        if not any(e.cache.pool is eng.cache.pool
                   for e in self.prefills + self.decodes):
            eng.cache.flush_prefix_cache()
        with self._lock:
            self._rehomed += rehomed
        _monitor.stat_add("STAT_serving_worker_killed")
        _runlog.log_event("serving_worker_kill", role="decode",
                          worker=index, shed=shed, rerouted=rehomed,
                          t=round(eng._clock(), 6),
                          decodes_left=len(self.decodes))
        return {"rehomed": rehomed, "shed": shed,
                "decodes_left": len(self.decodes)}

    # ---------------------------------------------------------- plumbing
    def swap_weights(self, state, *, reset_costs: bool = True
                     ) -> List[int]:
        """Rolling weight hot-swap across both roles (same contract as
        ``ReplicaRouter.swap_weights``)."""
        with self._lock:
            engines = self.engines + self._retiring
        return [eng.swap_weights(state, reset_costs=reset_costs)
                for eng in engines]

    def results(self, reqs=None, timeout: Optional[float] = None
                ) -> List[Request]:
        """Wait for requests, submission order. Requests live in the
        prefill workers' ``_all`` (submission lands there; adoption
        moves only the KV, not the bookkeeping), deduped by id in case
        a re-routed request was adopted into a second worker's list."""
        if reqs is None:
            seen: Dict[int, Request] = {}
            for eng in self.prefills + self._killed:
                with eng._lock:
                    for r in eng._all:
                        seen.setdefault(r.id, r)
            out = sorted(seen.values(), key=lambda r: r.id)
            for r in out:
                if not r.wait(timeout):
                    raise TimeoutError(
                        f"request {r.id} not finished within {timeout}s")
            return out
        out = list(reqs)
        for r in out:
            if not r.wait(timeout):
                raise TimeoutError(
                    f"request {r.id} not finished within {timeout}s")
        return out

    def start(self):
        """One scheduler thread for the whole fleet: co-located roles
        share BlockPool state, so a single stepper keeps every
        host-side mutation on one thread (the same reason one engine
        has one step lock)."""
        if self._thread is not None:
            return
        self._stop_evt.clear()

        def _loop():
            idle_wait = self.prefills[0].idle_wait
            while not self._stop_evt.is_set():
                if not self.step():
                    self._stop_evt.wait(idle_wait)

        self._thread = threading.Thread(target=_loop, daemon=True,
                                        name="serving-disagg")
        self._thread.start()

    def stop(self):
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if self._step_pool is not None:
            self._step_pool.shutdown(wait=True)
            self._step_pool = None

    def stats(self) -> dict:
        """Fleet view: per-role worker counts and queue depths, the
        handoff queue, affinity counters, and the pool-level prefix
        hit rate aggregated over *unique* pools (co-located roles
        share one — double counting would flatter the rate)."""
        engines = self.engines + self._retiring
        shed: dict = {}
        canceled: dict = {}
        completed = 0
        for e in engines:
            with e._lock:
                completed += e._completed
                for k, v in e._shed_by_reason.items():
                    shed[k] = shed.get(k, 0) + v
                for k, v in e._canceled_by_reason.items():
                    canceled[k] = canceled.get(k, 0) + v
        pools = {}
        for e in engines:
            pools[id(e.cache.pool)] = e.cache.pool
        hits = sum(p.prefix_hits for p in pools.values())
        misses = sum(p.prefix_misses for p in pools.values())
        dead_decodes = [e for e in self._killed
                        if isinstance(e, DecodeEngine)]
        adopted = sum(d.adopted for d in self.decodes + dead_decodes)
        copies = sum(d.adopted_copies
                     for d in self.decodes + dead_decodes)
        tenants: dict = {}
        for e in engines:
            with e._lock:
                for name, (c, el, m) in e._tenant_stats.items():
                    t = tenants.setdefault(name, [0, 0, 0])
                    t[0] += c
                    t[1] += el
                    t[2] += m
        # router-owned mutable state under the router lock — stats()
        # is scraped from the HTTP thread while kills/re-homes run
        with self._lock:
            draining = self._draining
            rehomed = self._rehomed
            affinity_entries = len(self._affinity)
        out = {
            "prefill_workers": len(self.prefills),
            "decode_workers": len(self.decodes),
            "colocated": self.colocate,
            "draining": draining,
            "handoff_queued": len(self._handoff),
            "handoff_bound": self._handoff.bound,
            "handoffs_adopted": adopted,
            "handoffs_copied": copies,
            "prefix_affinity": self.prefix_affinity,
            "affinity_hits": int(self._aff_hits.value),
            "affinity_misses": int(self._aff_misses.value),
            "affinity_index_entries": affinity_entries,
            "fleet_prefix_hits": hits,
            "fleet_prefix_misses": misses,
            "fleet_prefix_hit_rate": (
                round(hits / (hits + misses), 4)
                if hits + misses else None),
            "completed": completed,
            "rehomed": rehomed,
            "shed": shed,
            "shed_total": sum(shed.values()),
            "canceled": canceled,
            "canceled_total": sum(canceled.values()),
            "dispatch_threads": self._dispatch_threads,
            "queue_depths": [self._depth(e) for e in self.prefills],
            "kv_blocks_free": [e.cache.blocks_free
                               for e in self.prefills],
            "per_prefill": [e.stats() for e in self.prefills],
            "per_decode": [e.stats() for e in self.decodes],
        }
        if tenants:
            # fleet-wide per-tenant goodput: a request completes on
            # exactly one engine (the decode side), so summing across
            # roles never double-counts
            out["tenants"] = {
                name: {"completed": c,
                       "slo_met": m,
                       "slo_attainment": (round(m / e, 4) if e
                                          else None)}
                for name, (c, e, m) in sorted(tenants.items())}
        return out
