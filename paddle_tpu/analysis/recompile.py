"""Static recompile prediction for the jitted entry points.

The observability plane (PR 5) *observes* XLA compiles after the fact
via ``tracked_jit``; this module *predicts* them before any trace, by
mirroring the two compile-cache keying disciplines in the codebase:

- the executor's per-``run()`` cache key
  (``executor.py``: program identity+version, sorted feed
  name/shape/dtype signature, fetch names, scope identity+name-set,
  flags version) — :class:`ExecutorCompilePredictor`;
- the serving engine's geometry-keyed entries (one prefill compile per
  length bucket, one decode/verify compile total) including the paged
  prefix cache's effect on which bucket a prompt's unshared suffix
  lands in — :func:`predict_serving_compiles`.

The serving tests cross-check a prediction against the live
``observability.compiles()`` counts (predicted == observed, e.g.
``tests/test_abstract_interp.py``), so drift between this model and the
engine's real admission logic fails a test rather than rotting silently.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "RecompilePredictor", "ExecutorCompilePredictor",
    "feed_signature", "predict_serving_compiles",
    "merge_compile_counts",
]


def feed_signature(feeds: Dict[str, Any]) -> Tuple:
    """Normalize a feed dict to the executor's cache signature: sorted
    ``(name, shape, dtype)`` triples. Values may be arrays or
    ``(shape, dtype)`` pairs."""
    sig = []
    for k, v in feeds.items():
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is None and isinstance(v, (tuple, list)) and len(v) == 2:
            shape, dtype = v
        sig.append((k, tuple(int(d) for d in (shape or ())), str(dtype)))
    return tuple(sorted(sig))


class RecompilePredictor:
    """Generic site-keyed signature tracker: ``observe(site, sig)``
    returns True when that (site, signature) pair would trace fresh,
    mirroring how ``tracked_jit`` attributes compiles to sites."""

    def __init__(self):
        self._seen: Dict[str, Set[Tuple]] = {}
        self._counts: Dict[str, int] = {}

    def observe(self, site: str, signature: Tuple) -> bool:
        sigs = self._seen.setdefault(site, set())
        if signature in sigs:
            return False
        sigs.add(signature)
        self._counts[site] = self._counts.get(site, 0) + 1
        return True

    def predicted_counts(self) -> Dict[str, int]:
        return dict(self._counts)


class ExecutorCompilePredictor(RecompilePredictor):
    """Predicts ``executor_step`` compiles for a sequence of
    ``Executor.run`` calls, using the same key fields as the executor's
    build cache. Identity fields (program, scope) are taken as the
    objects themselves; pass the flags version explicitly if a run
    changes flags mid-sequence."""

    SITE = "executor_step"

    def would_compile(self, program, feeds: Dict[str, Any],
                      fetch_list: Sequence[str] = (),
                      scope=None, *,
                      flags_version: Optional[int] = None,
                      mesh_shape: Optional[Tuple[int, ...]] = None
                      ) -> bool:
        """``mesh_shape``: the device-mesh geometry a run compiles
        under (None = single device) — a different mesh is a different
        executable even when program/feeds/scope all match, so it is a
        cache-key component like the flags version."""
        if flags_version is None:
            from .. import flags as _flags
            flags_version = _flags.version()
        scope_names = (frozenset(scope.all_var_names())
                       if scope is not None else frozenset())
        key = (id(program), getattr(program, "_version", 0),
               feed_signature(feeds),
               tuple(str(f) for f in fetch_list),
               id(scope), scope_names, flags_version,
               None if mesh_shape is None else
               tuple(int(d) for d in mesh_shape))
        return self.observe(self.SITE, key)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


def _parse_buckets(buckets: Sequence[int], max_len: int) -> List[int]:
    # mirror of serving.engine._parse_buckets
    bs = sorted({int(b) for b in buckets})
    bs = [b for b in bs if 0 < b <= max_len]
    if not bs or bs[-1] != max_len:
        bs.append(max_len)
    return bs


def _bucket_for(buckets: Sequence[int], length: int) -> int:
    for b in buckets:
        if length <= b:
            return b
    return buckets[-1]


def predict_serving_compiles(
        request_rounds: Iterable[Sequence[Tuple[Sequence[int], int]]], *,
        buckets: Sequence[int], max_len: int,
        block_size: int = 16, prefix_cache: bool = True,
        spec_tokens: int = 0, kv_dtype: str = "f32",
        mesh_shape: Optional[Tuple[int, int]] = None,
        n_replicas: int = 1,
        slo_ttft_ms: float = 0.0,
        priority_classes: Optional[Sequence[int]] = None,
        autoscale: Optional[Tuple[int, int]] = None,
        weight_swaps: int = 0,
        replica_kills: int = 0,
        restarts: int = 0,
        rehomed: int = 0,
        cancel: int = 0,
        hedge: int = 0,
        disagg: Optional[Tuple[int, int]] = None,
        sampling: Optional[Sequence[Tuple[float, int, float]]] = None,
        lora: Optional[Tuple[int, int]] = None,
        tracing: Optional[float] = None,
        sanitize: bool = False,
        host_tier: bool = False,
        sessions: int = 0) -> Dict[str, int]:
    """Predict the engine's ``tracked_jit`` compile counts for a
    serving workload, before running it.

    ``request_rounds`` is a list of admission rounds; each round is a
    list of ``(prompt_token_ids, max_new_tokens)`` pairs admitted
    together. Rounds matter because the paged prefix cache only
    publishes a prompt's blocks *after* its prefill completes — two
    identical prompts in one round share nothing, the same pair split
    across rounds shares every full block.

    Model (mirrors ``serving/engine.py`` + ``serving/kv_cache.py``):

    - prefill compiles once per length bucket hit; the engine
      buckets the *unshared suffix* ``len(prompt) - shared`` where
      ``shared = min(matched_blocks * block_size, len(prompt) - 1)``
      (the last prompt token is always recomputed to emit the first
      output token);
    - decode (``decode_step_paged``) compiles once iff any request
      needs tokens beyond the one its prefill emits
      (``max_new_tokens > 1``) — with ``spec_tokens`` K > 0 the engine
      takes the verify path exclusively, so the compile lands on
      ``verify_step_paged{k=K}`` instead.

    ``kv_dtype`` (``FLAGS_serving_kv_dtype``) is part of the compiled
    steps' cache key — the step caches are keyed on the flags version,
    and the int8 pool changes every step's input signature — but it does
    NOT change the per-site compile counts *within* one settings phase:
    the same sites trace the same number of times whichever pool dtype
    they trace with. A workload that flips settings mid-run is
    two phases; predict each phase separately and sum the site counts
    with :func:`merge_compile_counts` (that is exactly how
    ``tracked_jit`` accumulates counts across retraces at one site).

    ``mesh_shape`` (``FLAGS_serving_mesh``: the (data, model) serving
    mesh an engine's steps compile under) and ``n_replicas``
    (``FLAGS_serving_replicas``: data-parallel engines behind a
    ReplicaRouter) are the two scale-out cache-key components. Like
    ``kv_dtype``, neither changes per-site counts within
    a phase: a mesh engine's entries live under a *new* unified-cache
    key (one extra compile per site — a separate phase to merge), while
    replicas share one model and therefore one step cache, so N
    replicas compile each step once, total — ``n_replicas`` never
    multiplies counts, which is precisely the invariant worth asserting
    statically.

    ``slo_ttft_ms`` (``FLAGS_serving_slo_ttft_ms``: predicted-TTFT
    admission), ``priority_classes`` (the distinct ``Request.priority``
    values a workload carries) and ``autoscale`` (``(min, max)``
    router replica bounds, ``FLAGS_serving_autoscale``) are validated
    no-ops by design: admission, preemptive shedding, deadline sheds
    and replica scaling are all host-side queue surgery — they decide
    *which* requests reach the compiled steps, never what those steps
    trace. The parameters exist so the predictor's signature mirrors
    the engine's and so the zero-new-compiles contract is itself
    regression-tested (predict with them == predict without).

    ``weight_swaps`` (``ServingEngine.swap_weights`` calls interleaved
    anywhere in the workload) joins that family: compiled steps take
    the weights as explicit jit inputs with an unchanged abstract
    shape/dtype/sharding signature, so N live hot-swaps trace nothing —
    the train→serve loop's zero-new-compiles contract, statically.

    ``replica_kills`` / ``restarts`` / ``rehomed`` (the fault-
    tolerance plane: ``ReplicaRouter.kill_replica`` /
    ``restart_replica`` calls and requests re-homed off dead
    replicas/workers anywhere in the workload) are validated no-ops
    for three distinct reasons, all load-bearing: a *kill* is pure
    host-side teardown (rows released, queue re-routed — nothing
    traces); a *restart* builds the replacement engine against the
    same model at the same geometry, so every step it will ever run
    is already in the unified per-model step cache; and a *re-homed*
    request re-prefills its committed context on the survivor — the
    adoption path refuses any context longer than the largest bucket
    (the router sheds it instead), so re-homing can only ever hit
    buckets ``warmup()`` already compiled, never widen the surface.
    N kill/restart/re-home cycles therefore predict the same counts
    as zero — the soak harness's degradation contract, statically.

    ``cancel`` / ``hedge`` (the request-lifecycle robustness plane:
    ``engine.cancel``/``router.cancel`` calls — client disconnects,
    hard-deadline expiries, hedge-loser teardowns — and hedged
    prefills dispatched by the router anywhere in the workload) are
    validated no-ops for complementary reasons: a *cancel* is pure
    host-side reclamation — the slot leaves ``_active``, its blocks
    deref, the LoRA pin releases, counters bump — nothing ever reaches
    a compiled step; a *hedge* submits a clone of an already-admitted
    prompt, and a clone's prompt length lands in the same prefill
    bucket its primary warmed (identical tokens, identical bucket), so
    the duplicate dispatch replays a cached trace by construction. N
    cancels and M hedges therefore predict the same counts as zero —
    the cancellation/hedging soak's zero-new-compiles contract,
    statically.

    ``disagg`` (``FLAGS_serving_disagg``: a ``(n_prefill, n_decode)``
    disaggregated fleet behind a ``DisaggRouter``) is the newest
    member of the validated-no-op family: prefill-only and decode-only
    engine roles call the *same* compiled steps at the same geometry —
    the unified step cache keys on geometry, never on role — the KV
    handoff is host-side block-table surgery, and prefix-affinity
    routing only changes *which* pool a prompt lands in (if anything
    it makes this predictor's single-prefix-cache model MORE accurate,
    since affinity concentrates shared prefixes the way one shared
    cache would). Splitting P+D workers therefore adds zero compiles
    over a symmetric fleet.

    ``sampling`` (the distinct per-request ``(temperature, top_k,
    top_p)`` recipes a workload carries — ``FLAGS`` have no say here,
    sampling is per-request data) is a validated no-op for the same
    reason the SLO family is: the compiled steps take one fixed-shape
    per-slot ``samp`` tuple (temperatures, top-k/top-p cutoffs, RNG
    keys, additive mask rows) as a plain jit input, so a batch mixing
    greedy, sampled, and grammar-masked rows traces NOTHING beyond the
    all-greedy baseline — sampling-as-data, never compile keys. JSON-
    constrained rows ride the same mask input; stop sequences are
    host-side suffix checks. Ten thousand distinct recipes predict the
    same counts as none.

    ``lora`` (``(rank, max_adapters)``, ``FLAGS_serving_lora_rank`` /
    ``_max_adapters``: the paged multi-tenant adapter pool) behaves
    like ``mesh_shape``: the pool geometry joins the step cache key —
    an engine built with a pool compiles its steps once under the new
    key (a separate phase to merge when you enable it mid-run) — but
    within a phase it's a validated no-op: per-row adapter pages are
    gathered *inside* the step from one more fixed-shape input, so
    adapter loads, evictions and any per-tenant traffic mix trace
    nothing.

    ``tracing`` (``FLAGS_serving_trace``: the per-request distributed-
    tracing sampling fraction in [0, 1], or True for fully sampled) is
    the purest no-op of the family: a trace is an ordered list of
    host-side ``(kind, t, track)`` marks appended around the compiled
    dispatches — timestamps read from the engine clock, never passed
    into any jitted function, no shape, dtype or donation anywhere
    near the step cache. Tracing every request predicts the same
    counts as tracing none.

    ``sanitize`` (``FLAGS_sanitize_locks``: the concurrency
    sanitizer) is a validated no-op like ``tracing``: the sanitizer
    swaps host-side ``threading`` locks for instrumented wrappers and
    checks guarded-state writes in ``__setattr__`` — pure Python
    control flow around the compiled dispatches, with no tensor,
    shape, dtype or donation anywhere near the step cache. Running
    the whole fleet under the sanitizer predicts the same counts as
    running it bare.

    ``host_tier`` / ``sessions`` (``FLAGS_serving_host_tier``: the
    host-RAM KV block tier, and the number of distinct
    ``submit(session=...)`` conversations a workload carries) are
    validated no-ops because every migration is host-side numpy
    surgery on pool *state*, never on compiled functions: demotion
    stages cold blocks through pinned staging buffers and quantizes
    them int8-at-rest with the numpy mirror of the device grid,
    promotion writes them back with a functional ``.at[dst].set``
    whose output shape/dtype equals the pool's (an update to a jit
    *input*, not a new trace), and a resumed session re-prefills only
    its unshared suffix — which lands in a bucket the original turn
    already warmed, by construction. A million sessions tiered
    through host RAM therefore predict the same counts as none —
    the concurrent-session capacity contract, statically.
    """
    if kv_dtype not in ("f32", "bf16", "int8"):
        raise ValueError(f"kv_dtype must be one of ('f32', 'bf16', "
                         f"'int8'), got {kv_dtype!r}")
    if mesh_shape is not None:
        dims = tuple(int(d) for d in mesh_shape)
        if len(dims) != 2 or any(d < 1 for d in dims):
            raise ValueError(
                f"mesh_shape must be a (data, model) pair of positive "
                f"ints, got {mesh_shape!r}")
    if int(n_replicas) < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    if float(slo_ttft_ms) < 0:
        raise ValueError(
            f"slo_ttft_ms must be >= 0, got {slo_ttft_ms}")
    if priority_classes is not None:
        pris = [int(p) for p in priority_classes]
        if not pris or any(p < 0 for p in pris):
            raise ValueError(
                f"priority_classes must be a non-empty sequence of "
                f"ints >= 0, got {priority_classes!r}")
    if autoscale is not None:
        lo, hi = (int(b) for b in autoscale)
        if not (1 <= lo <= hi):
            raise ValueError(
                f"autoscale bounds must satisfy 1 <= min <= max, got "
                f"{autoscale!r}")
    if int(weight_swaps) < 0:
        raise ValueError(
            f"weight_swaps must be >= 0, got {weight_swaps}")
    for val, name in ((replica_kills, "replica_kills"),
                      (restarts, "restarts"), (rehomed, "rehomed"),
                      (cancel, "cancel"), (hedge, "hedge")):
        if int(val) < 0:
            raise ValueError(f"{name} must be >= 0, got {val}")
    if disagg is not None:
        p, d = (int(n) for n in disagg)
        if p < 1 or d < 1:
            raise ValueError(
                f"disagg must be (n_prefill >= 1, n_decode >= 1), got "
                f"{disagg!r}")
    if sampling is not None:
        from ..serving.decoding import DecodeParams
        for rec in sampling:
            t, k, p = rec
            DecodeParams(temperature=float(t), top_k=int(k),
                         top_p=float(p))   # range-validates, else raises
    if lora is not None:
        rank, max_adapters = (int(n) for n in lora)
        if rank < 1 or max_adapters < 1:
            raise ValueError(
                f"lora must be (rank >= 1, max_adapters >= 1), got "
                f"{lora!r}")
    if tracing is not None:
        frac = 1.0 if tracing is True else float(tracing)
        if not (0.0 <= frac <= 1.0):
            raise ValueError(
                f"tracing must be a sampling fraction in [0, 1] (or "
                f"True = 1.0), got {tracing!r}")
    if sanitize not in (True, False):
        raise ValueError(
            f"sanitize must be a bool (FLAGS_sanitize_locks is "
            f"on/off), got {sanitize!r}")
    if host_tier not in (True, False):
        raise ValueError(
            f"host_tier must be a bool (FLAGS_serving_host_tier is "
            f"on/off), got {host_tier!r}")
    if int(sessions) < 0:
        raise ValueError(f"sessions must be >= 0, got {sessions}")
    if sessions and not host_tier:
        raise ValueError(
            "sessions requires host_tier=True (submit(session=...) "
            "needs the host KV tier to park a conversation)")
    bks = _parse_buckets(buckets, max_len)
    counts: Dict[str, int] = {}
    seen_buckets: Set[int] = set()
    published: Set[Tuple] = set()   # rolling chains of full-block chunks
    needs_decode = False

    for round_reqs in request_rounds:
        round_published: List[Tuple[int, ...]] = []
        for prompt, max_new_tokens in round_reqs:
            prompt = tuple(int(t) for t in prompt)
            shared = 0
            if prefix_cache:
                matched, chain = 0, ()
                for i in range(len(prompt) // block_size):
                    chain = (chain,
                             prompt[i * block_size:(i + 1) * block_size])
                    if chain not in published:
                        break
                    matched += 1
                shared = min(matched * block_size, len(prompt) - 1)
                round_published.append(prompt)
            b = _bucket_for(bks, len(prompt) - shared)
            if b not in seen_buckets:
                seen_buckets.add(b)
                counts[f"serving_prefill_paged{{bucket={b}}}"] = 1
            if max_new_tokens > 1:
                needs_decode = True
        # prefix publication happens post-prefill, i.e. between rounds
        for prompt in round_published:
            chain: Tuple = ()
            for i in range(len(prompt) // block_size):
                chain = (chain, prompt[i * block_size:(i + 1) * block_size])
                published.add(chain)

    if needs_decode:
        if spec_tokens > 0:
            counts[f"verify_step_paged{{k={spec_tokens}}}"] = 1
        else:
            counts["decode_step_paged"] = 1
    return counts


def merge_compile_counts(*phase_counts: Dict[str, int]) -> Dict[str, int]:
    """Sum per-site compile counts across settings phases (e.g. an
    xla/f32 warm-up followed by a pallas/int8 run after ``set_flags``
    bumped the flags version): ``tracked_jit`` keeps one counter per
    site name across retraces, so the observed count at each site is
    the sum of the per-phase predictions."""
    merged: Dict[str, int] = {}
    for counts in phase_counts:
        for site, n in counts.items():
            merged[site] = merged.get(site, 0) + int(n)
    return merged
