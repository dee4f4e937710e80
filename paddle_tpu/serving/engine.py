"""ServingEngine — continuous-batching inference on a paged KV cache.

Iteration-level scheduling (the Orca design point): the unit of work is
one *step*, not one request. Each step first admits queued requests
into free cache slots (a shape-bucketed prefill per admission), then
runs ONE batched decode over all occupied slots. A request that
finishes mid-batch releases its slot immediately and the next queued
request takes it on the following step — the decode batch never drains
to let stragglers finish.

Compile surfaces, all fixed-shape:

- decode: ``models.generation.decode_step_paged(model)`` at batch =
  ``max_slots`` — every step of every request, one XLA executable. It
  is dispatched **one ahead of its fetch**: step k+1 goes to the device
  from step k's device outputs (tokens, keys, pools) and host
  arithmetic (every live row one position further) before the host
  waits for k, so fetch, commit and launch run under the device's
  time. A step's rows are committed or dropped one by one (a request
  that finished, was cancelled or shed meanwhile has its row dropped)
  and no step is dispatched twice. An admission does not break the
  chain: a prefill group is a flight too, the step after it is
  dispatched behind it from its device outputs (the prompt program
  merges its rows' first tokens and keys into what the step in flight
  left), and only then does the host fetch and commit its first
  tokens; a step with a grammar row, or after a group with a row
  whose first token the host draws (sampled, grammar), is built from
  the host once what was before it landed (``ServingEngine._decode``;
  ``stats()``: ``ahead_dispatches`` / ``ahead_rows_committed`` /
  ``ahead_rows_dropped`` / ``admit_ahead_dispatches``). Tokens are
  counted when the host has them.
  A dispatch of any kind is stamped once at each of its edges
  (:class:`_Stamps`: the readings its spans take anyway), and the
  stamps feed its spans (``flight=<id>``), the step account in
  ``stats()`` and the SLO cost estimates (:meth:`_landed`);
- verify (``FLAGS_serving_spec_tokens`` = K > 0): speculative
  decoding replaces the one-token decode with
  ``models.generation.verify_step_paged(model, K)`` — an on-host n-gram
  self-drafter proposes K tokens per slot from the request's own
  generated suffix, one fixed-shape forward scores all K+1 positions,
  and the accepted prefix commits to the cache while the rejected
  tail's write offset rolls back. Greedy output is token-identical to
  K=0 (the correctness oracle); throughput gains scale with the
  drafter's acceptance rate (``STAT_serving_spec_*``). One XLA
  executable, compiled once per engine geometry like decode;
- prefill: one jitted function per prompt-length *bucket*
  (``FLAGS_serving_prefill_buckets``), whose rows follow the bucket's
  length: the model's token budget a dispatch over the bucket
  (``seam.ServedModel.prefill_rows``), from one row for a long bucket
  to ``max_slots`` for a short one. Prompts are right-padded to the
  smallest bucket that fits and the queued same-bucket admissions of
  a step ride that function ``rows`` at a time, in admission order
  (batch rows past the admitted count are padding), so a fleet of
  arbitrary-length prompts compiles ``len(buckets)`` times and a long
  prompt does not pay for ``max_slots - 1`` rows of padding
  (``stats()``: ``prefill_rows_live`` / ``prefill_rows_computed``).
  Padding is sound
  because the position mask hides rows past the true length and
  decode overwrites them in place — same reuse idea as
  CompiledProgram's keyed ``_cache`` (compiler.py), keyed here by
  shape bucket instead of program.

KV memory comes from one manager,
:class:`~paddle_tpu.serving.kv_cache.BlockKVCache`: a fixed pool of
block_size-row KV blocks per layer, per-request host-side block tables
shipped into the compiled steps as fixed-shape inputs
(``decode_step_paged`` / ``verify_step_paged`` /
``serving_prefill_paged``, each compiling exactly once), a ref-counted
allocator, and a rolling-hash prefix cache so a shared system prompt
prefills once and later admissions reference its blocks (copy-on-write
at a partially shared boundary block; only the unshared prompt *suffix*
runs through the bucketed prefill). A request pays blocks for prompt +
max_new_tokens + K, not a full ``max_len`` row; when the pool runs dry
admission blocks head-of-line (FIFO preserved) and queue backpressure
sheds via QueueFullError/429.

Mesh sharding (``FLAGS_serving_mesh`` / the ``mesh=`` argument): the
engine runs tensor-parallel within one replica on a ``("data",
"model")`` mesh — params placed per
``distributed.sharding.SERVING_TP_RULES`` (attention heads / MLP
hidden on ``"model"``), pool layers head-sharded on ``"model"``, and
every compiled step running under pjit with explicit in/out shardings.
Tokens, positions and block tables stay replicated plain inputs, so
admission, prefix sharing and COW remain pure host work that never
retraces. Data parallelism *across* engines is
:class:`~paddle_tpu.serving.router.ReplicaRouter`'s job.

Resilience: ``serving.submit`` faults reject a submission at admission
(backpressure path); ``serving.step`` faults fire once per prefill
attempt and per decode attempt — drop/error retry through RetryPolicy
(exhaustion sheds the affected requests, never the whole engine),
``skip`` sheds the request being prefilled / skips one decode
iteration; ``serving.alloc`` faults fire per block-table acquisition
attempt, shedding that request with every taken block unwound.
Counters land in monitor.stats() as ``STAT_serving_*``.

Admission (``FLAGS_serving_slo_ttft_ms`` > 0): instead of the blunt
queue-depth gate alone, ``submit()`` predicts the newcomer's TTFT
from live host state — queue depth ahead of it, free decode slots,
the per-bucket prefill dispatch cost, and the decode batch's
per-token pace (costs pinned via ``FLAGS_serving_slo_prefill_ms`` /
``_tpot_ms`` or learned as EWMAs over measured dispatches, each timed
to its tokens on the host, not to its launch) — and
sheds the submission when the prediction exceeds the SLO, with the
prediction echoed back as the 429 Retry-After hint. Requests carry an
integer priority class (lower = more urgent, FIFO within a class);
an urgent submission that would otherwise be shed may preempt-shed
queued strictly-lower-priority work, and queued requests whose TTFT
deadline already passed are shed before prefill rather than wasting a
dispatch. All of it is host arithmetic over host state: no new
compiled surface, zero retraces — but the knobs are constructor/flag
state read once at engine construction, NOT runtime ``set_flags``
targets (that would bump the flags version and retrace every step).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter, deque
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import flags as _flags
from .. import monitor as _monitor
from ..analysis import concurrency as _ccz
from .. import observability as _obs
from .. import profiler as _profiler
from ..observability import compile_tracker as _ct
from ..observability import runlog as _runlog
from ..observability import tracing as _tracing
from ..dygraph.tape import no_grad
from ..dygraph.tensor import Tensor
from ..distributed.sharding import (SERVING_TP_RULES, kv_pool_shardings,
                                    mesh_cache_key, parse_serving_mesh,
                                    serving_mesh)
from ..models.generation import (draft_ngram, step_entry,
                                 verify_step_paged)
from ..resilience.injector import fault_point
from ..resilience.retry import RetryError, RetryPolicy
from .decoding import (DecodeParams, StopMatcher, request_key,
                       sample_first)
from .kv_cache import BlockKVCache
from .seam import served
from .kv_tier import HostBlockStore, TierManager
from .lora import LoRAPool


class QueueFullError(RuntimeError):
    """Admission control shed this submission. Callers back off (HTTP
    maps it to 429) instead of queueing unboundedly.

    ``reason`` says which gate fired — "queue_full" (depth
    backpressure), "slo" (predicted TTFT beyond
    FLAGS_serving_slo_ttft_ms), or "fault" (injected serving.submit
    fault) — and ``retry_after_s`` is the engine's predicted-TTFT-
    derived backoff hint (whole seconds, >= 1), which the HTTP front
    end surfaces verbatim as the Retry-After header."""

    def __init__(self, msg: str, reason: str = "queue_full",
                 retry_after_s: Optional[int] = None):
        super().__init__(msg)
        self.reason = reason
        self.retry_after_s = retry_after_s


class _Shed(Exception):
    """Internal: this request is dropped by fault policy (injected
    `skip`, or retry exhaustion). Not an OSError on purpose — it must
    NOT be retried."""


class _PoolsLost(Exception):
    """A paged entry failed after it had consumed the KV pools: the
    engine has shed what was running and rebuilt zeroed pools."""


class _SkipStep(Exception):
    """Internal: skip one decode iteration (injected `skip` at
    serving.step during decode); requests stay live."""


#: the step account of ``ServingEngine.stats()``: counts, and sums in ms
_ACCOUNT_KEYS = (
    "decode_flights", "decode_host_ms", "decode_wait_ms",
    "decode_device_ms", "prefill_flights", "prefill_host_ms",
    "prefill_wait_ms", "rounds", "round_ms", "rounds_over_100ms",
    "rounds_over_1s", "stalled_ms", "first_tokens", "ttft_ms",
    "queue_wait_ms", "kv_blocks_live", "kv_blocks_table")


class _Stamps:
    """One dispatch the device was given, a decode step (single or
    verify) or a prefill group, from its dispatch to its commit.

    ``id`` is its place in the engine's one sequence of dispatches, which
    is the order the device runs them in; the spans of one dispatch carry
    it as ``flight=id``. The five ``t_*`` are the readings of its edges,
    ``time.perf_counter_ns`` as the ``RecordEvent``s that bound those
    edges took them (no clock is read for a stamp): ``t_dispatch``
    entering the span that builds its inputs, ``t_launched`` the first
    reading after its entry returned (the close of ``serving.decode`` /
    ``.verify`` / ``.prefill``, or the next dispatch's ``t_dispatch``
    where one span holds two; until that event closes it stands at
    ``t_dispatch``), ``t_fetch`` / ``t_fetched`` around the host's wait
    for its tokens, ``t_committed`` at the end of its commit."""

    __slots__ = ("id", "t_dispatch", "t_launched", "t_fetch", "t_fetched",
                 "t_committed")

    def __init__(self, id_: int):
        self.id = id_
        self.t_dispatch = self.t_launched = 0
        self.t_fetch = self.t_fetched = self.t_committed = 0


class _Flight(NamedTuple):
    """One decode step the device was given and whose tokens the host
    has not fetched. The pools (and counters) it returns are bound when
    it is dispatched; these are the outputs its commit reads."""
    #: (slot, request, the request's count of tokens when the step's
    #: own token is the next one): whom it decodes for. A row is
    #: committed iff the slot still holds that request at that count.
    rows: tuple
    nxt: object         # [b] i32 next tokens, on the device
    keys: object        # [b, 2] u32 advanced keys, on the device
    qerr: object
    ahead: bool         # dispatched before the step before it was fetched
    stamps: _Stamps     # its id and the readings of its edges


class _Prefill(NamedTuple):
    """One prefill group the device was given and whose first tokens the
    host has not fetched: a flight like a decode step. The pools it
    returns are bound when it is dispatched, and its rows stand in
    ``_active`` from then on; these are the outputs its commit reads."""
    bucket: int
    live: tuple         # (request, cache row, shared prompt tokens) a row
    lg: object          # [rows, vocab] logits at each row's last token
    #: the tokens and keys of every slot as the step behind this group
    #: takes them, on the device: what the group was given (the outputs of
    #: the dispatch before it) with its rows' first tokens and its
    #: requests' keys written over their slots by the prompt program
    nxt: object         # [b] i32
    keys: object        # [b, 2] u32
    qerr: object
    behind: bool        # dispatched behind a decode step in flight
    followed: bool      # a decode step was dispatched behind it, unfetched
    stamps: _Stamps


class Request:
    """One generation request's lifecycle record.

    States: queued -> running -> done, with shed as the fault exit
    (queued/running -> shed) and canceled as the client exit
    (queued/running -> canceled: a disconnect, an expired hard
    deadline, or a hedge resolution tore the request down mid-flight,
    reclaiming its KV blocks and LoRA pin at whatever stage it had
    reached). ``output_ids`` is prompt + generated tokens (EOS
    included when hit), matching ``greedy_search`` row semantics token
    for token.

    ``priority`` is an integer class, lower = more urgent (default 1);
    requests within one class keep FIFO order. ``now`` lets the engine
    stamp timestamps from its own clock (virtual time in loadgen
    replays); default is the wall clock. When the engine runs with a
    TTFT SLO, ``deadline`` is the absolute clock time the first token
    must land by, and a shed request records why in ``shed_reason``.

    ``decode`` is the request's :class:`~paddle_tpu.serving.decoding.
    DecodeParams` recipe (default = plain greedy, the token-identity
    oracle) and ``tenant`` names its LoRA adapter in the engine's
    :class:`~paddle_tpu.serving.lora.LoRAPool` ("" = base weights).
    ``_key`` is the request-local PRNG key — derived from the seed
    alone and advanced functionally by the compiled steps, so it
    travels with the request across restarts and disaggregated
    handoffs and the sampled stream replays byte-identically.
    """

    _ids = itertools.count()

    def __init__(self, prompt: Sequence[int], max_new_tokens: int,
                 eos_token_id: Optional[int], priority: int = 1,
                 now: Optional[float] = None, decode=None,
                 tenant: str = ""):
        self.id = next(Request._ids)
        self.prompt: List[int] = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.priority = int(priority)
        self.decode = decode if decode is not None else DecodeParams()
        self.tenant = str(tenant)
        self._key = request_key(self.decode.seed)
        # incremental stop-sequence automaton, fed once per committed
        # token in _append_token (O(1) amortized; replaces the old
        # O(len^2) full-suffix scan). It travels with the object
        # through adopts and re-homes.
        self._stop = (StopMatcher(self.decode.stop_sequences)
                      if self.decode.stop_sequences else None)
        self._cursor = None        # JsonCursor when json_mode is on
        self._lora_held = False    # this request pins its tenant page
        self.rehomed = False       # recovered from a killed replica
        # host-tier conversation id (submit(session=...)): _finish
        # publishes this request's full context into the prefix cache
        # and the SessionStore so the next turn resumes off the chain
        self.session: Optional[str] = None
        self._session_counted = False  # resident-session gauge held
        self._hedge_clone = False  # router-internal hedge copy: never
        #                            surfaced in results()/reports
        # absolute engine-clock time after which the request is
        # canceled wherever it is (client patience, carried through
        # handoffs and re-homes); None = no hard deadline. Distinct
        # from `deadline` (the TTFT SLO bound, an admission-quality
        # signal that sheds queued work but never kills decodes).
        self.hard_deadline: Optional[float] = None
        self.tokens: List[int] = []
        self.state = "queued"
        self.slot: Optional[int] = None
        self.error: Optional[BaseException] = None
        self.shed_reason: Optional[str] = None
        self.submitted_at = (time.perf_counter() if now is None
                             else float(now))
        self.deadline: Optional[float] = None
        # engine-clock stamp of the admission whose prefill gave the
        # first token (the trace's "admit" mark, the same float)
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        # engine-clock stamp of every committed token, one clock read
        # per commit shared by all the tokens it commits (so the
        # accepted drafts of one verify step share a stamp);
        # token_at[0] is first_token_at, the same float
        self.token_at: List[float] = []
        self.finished_at: Optional[float] = None
        self._done = threading.Event()

    @property
    def output_ids(self) -> List[int]:
        return self.prompt + self.tokens

    @property
    def context(self) -> List[int]:
        """The committed context — prompt plus generated-so-far. The
        admit paths prefill over THIS (not the bare prompt), so a
        request re-homed mid-decode from a killed replica resumes by
        re-prefilling its committed tokens on the survivor: the next
        argmax/sample is exactly what the dead replica's decode would
        have produced. Fresh requests have no tokens, making this the
        plain prompt (zero behavior change)."""
        return self.prompt + self.tokens

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def latency(self) -> Optional[float]:
        """Submit-to-finish wall seconds (None while in flight)."""
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    @property
    def ttft(self) -> Optional[float]:
        """Time-to-first-token: submit to first generated token,
        seconds (None before the prefill lands)."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def tpot(self) -> Optional[float]:
        """Time-per-output-token: mean seconds per generated token
        after the first (None until finished with >= 2 tokens)."""
        if self.finished_at is None or self.first_token_at is None or \
                len(self.tokens) < 2:
            return None
        return (self.finished_at - self.first_token_at) / \
            (len(self.tokens) - 1)

    @property
    def deadline_met(self) -> Optional[bool]:
        """Whether the first token landed inside the TTFT deadline:
        None when no SLO was active or the verdict is still open,
        False for a shed request (its first token never arrives)."""
        if self.deadline is None:
            return None
        if self.first_token_at is None:
            return False if self.state == "shed" else None
        return self.first_token_at <= self.deadline

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def __repr__(self):
        return (f"Request(id={self.id}, state={self.state!r}, "
                f"prompt={len(self.prompt)} toks, "
                f"generated={len(self.tokens)})")


def _parse_buckets(text: str, max_len: int) -> List[int]:
    """Flag string -> sorted bucket lengths, clipped to the cache
    capacity, with max_len itself as the terminal bucket so every
    admissible prompt has a home."""
    buckets = sorted({int(tok) for tok in str(text).split(",") if
                      tok.strip()})
    buckets = [b for b in buckets if 0 < b <= max_len]
    if not buckets or buckets[-1] != max_len:
        buckets.append(max_len)
    return buckets


class ServingEngine:
    """Front door: ``submit()`` returns a :class:`Request` handle,
    ``results()`` collects them; call ``start()`` for a background
    scheduler thread or drive ``step()`` / ``run_until_idle()``
    yourself (tests do the latter for determinism).

    Geometry/admission knobs come from the ``FLAGS_serving_*`` plane;
    constructor arguments override per instance.
    """

    _engine_ids = itertools.count()

    #: track-label prefix in exported traces; the disaggregated roles
    #: override with "prefill"/"decode" so Perfetto shows one named
    #: track per replica/role
    trace_role = "engine"

    @property
    def trace_track(self) -> str:
        return f"{self.trace_role}{self._eid}"

    def __init__(self, model, max_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 max_queue: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 spec_tokens: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_dtype: Optional[str] = None,
                 mesh=None,
                 slo_ttft_ms: Optional[float] = None,
                 slo_prefill_ms: Optional[float] = None,
                 slo_tpot_ms: Optional[float] = None,
                 priority_preempt: Optional[bool] = None,
                 clock=None, kv_pool=None,
                 lora_rank: Optional[int] = None,
                 lora_max_adapters: Optional[int] = None,
                 lora_pool=None, grammar=None, kv_tier=None):
        g = _flags.get_flags(["serving_max_slots", "serving_max_len",
                              "serving_max_queue",
                              "serving_prefill_buckets",
                              "serving_max_new_tokens",
                              "serving_idle_wait",
                              "serving_spec_tokens",
                              "serving_spec_ngram",
                              "serving_block_size",
                              "serving_num_blocks",
                              "serving_prefix_cache",
                              "serving_kv_dtype",
                              "serving_mesh",
                              "serving_slo_ttft_ms",
                              "serving_slo_prefill_ms",
                              "serving_slo_tpot_ms",
                              "serving_priority_preempt",
                              "serving_lora_rank",
                              "serving_lora_max_adapters",
                              "serving_host_tier",
                              "serving_host_blocks"])
        self.model = model
        # everything the engine knows of the model: serving/seam.py
        spec = self.spec = served(model)
        self.max_slots = int(max_slots if max_slots is not None
                             else g["serving_max_slots"])
        self.max_len = int(max_len if max_len is not None
                           else g["serving_max_len"])
        if self.max_len > spec.max_positions:
            raise ValueError(
                f"serving max_len {self.max_len} exceeds the model's "
                f"max_position_embeddings={spec.max_positions}")
        self.max_queue = int(max_queue if max_queue is not None
                             else g["serving_max_queue"])
        self.default_max_new_tokens = int(g["serving_max_new_tokens"])
        self.default_eos_token_id = eos_token_id
        self.idle_wait = float(g["serving_idle_wait"])
        # SLO-aware admission: 0 disables (depth-only backpressure).
        # These are constructor/flag state read ONCE — never set_flags
        # mid-run to change them, that would bump the flags version and
        # retrace every compiled step (the admission logic itself is
        # host-only and compiles nothing).
        self.slo_ttft_ms = float(slo_ttft_ms if slo_ttft_ms is not None
                                 else g["serving_slo_ttft_ms"])
        if self.slo_ttft_ms < 0:
            raise ValueError(
                f"slo_ttft_ms must be >= 0, got {self.slo_ttft_ms}")
        self._prefill_ms_pin = float(
            slo_prefill_ms if slo_prefill_ms is not None
            else g["serving_slo_prefill_ms"])
        self._tpot_ms_pin = float(slo_tpot_ms if slo_tpot_ms is not None
                                  else g["serving_slo_tpot_ms"])
        if self._prefill_ms_pin < 0 or self._tpot_ms_pin < 0:
            raise ValueError("pinned predictor costs must be >= 0")
        self.priority_preempt = bool(
            priority_preempt if priority_preempt is not None
            else g["serving_priority_preempt"])
        self._clock = clock if clock is not None else time.perf_counter
        # measured cost estimates feeding predict_ttft_ms when no pin
        # is set: per-bucket prefill dispatch ms + a global fallback,
        # and per-output-token decode ms (EWMA over steps)
        self._prefill_ewma: Dict[int, float] = {}
        self._prefill_ewma_all: Optional[float] = None
        self._tpot_ewma: Optional[float] = None
        self._shed_by_reason: Dict[str, int] = {}   # guarded-by: _lock
        self._canceled_by_reason: Dict[str, int] = {}  # guarded-by: _lock
        self._slo_met = 0                           # guarded-by: _lock
        self.spec_tokens = int(spec_tokens if spec_tokens is not None
                               else g["serving_spec_tokens"])
        self.spec_ngram = int(g["serving_spec_ngram"])
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {self.spec_tokens}")
        if self.spec_tokens:
            spec.require("speculative", f"spec_tokens={self.spec_tokens}")
        if self.spec_tokens >= self.max_len:
            raise ValueError(
                f"spec_tokens {self.spec_tokens} leaves no room in "
                f"max_len={self.max_len} slots")
        self.buckets = (_parse_buckets(g["serving_prefill_buckets"],
                                       self.max_len)
                        if buckets is None else
                        _parse_buckets(",".join(map(str, buckets)),
                                       self.max_len))
        self.kv_dtype = str(kv_dtype if kv_dtype is not None
                            else g["serving_kv_dtype"])
        if spec.kv_dtype is not None:
            # the model states its pools' dtype; only an explicit other
            # one is refused (the flag's default is not a request)
            if kv_dtype is not None and str(kv_dtype) != spec.kv_dtype:
                if str(kv_dtype) == "int8":
                    spec.require("int8_pool", "kv_dtype='int8'")
                raise ValueError(
                    f"{spec.family} keeps its KV pools in "
                    f"{spec.kv_dtype!r}; kv_dtype={kv_dtype!r} was asked")
            self.kv_dtype = spec.kv_dtype
        elif self.kv_dtype == "int8":
            spec.require("int8_pool", "kv_dtype='int8'")
        if mesh is None:
            dims = parse_serving_mesh(g["serving_mesh"])
            if dims is not None:
                mesh = serving_mesh(*dims)
        if mesh is not None:
            spec.require("mesh", "mesh= / FLAGS_serving_mesh")
        if mesh is not None and \
                tuple(mesh.axis_names) != ("data", "model"):
            raise ValueError(
                f"serving mesh axes must be ('data', 'model'), got "
                f"{tuple(mesh.axis_names)}")
        self.mesh = mesh
        self.mesh_shape = (None if mesh is None else
                           tuple(int(s) for s in mesh.devices.shape))
        want_prefix = bool(prefix_cache if prefix_cache is not None
                           else g["serving_prefix_cache"])
        if want_prefix:
            spec.require("prefix_cache", "prefix_cache=True / "
                         "FLAGS_serving_prefix_cache")
        if kv_pool is not None:
            spec.require("disaggregation", "kv_pool=")
            # co-located disaggregated roles share one physical pool:
            # geometry comes from the pool (not the flags) so the
            # sharing cache cannot drift from what the blocks are
            if self.mesh is not None:
                raise ValueError(
                    "kv_pool sharing and mesh placement are mutually "
                    "exclusive — the pool is placed once by the engine "
                    "that built it")
            if kv_dtype is None:
                self.kv_dtype = kv_pool.kv_dtype
            self.cache = BlockKVCache.for_model(
                spec, self.max_slots, self.max_len,
                block_size=kv_pool.block_size, num_blocks=0,
                prefix_cache=want_prefix,
                kv_dtype=self.kv_dtype, pool=kv_pool)
        else:
            self.cache = BlockKVCache.for_model(
                spec, self.max_slots, self.max_len,
                block_size=int(block_size if block_size is not None
                               else g["serving_block_size"]),
                num_blocks=int(num_blocks if num_blocks is not None
                               else g["serving_num_blocks"]),
                prefix_cache=want_prefix,
                kv_dtype=self.kv_dtype)
        # Multi-tenant paged LoRA: a pool of per-tenant adapter pages
        # fed to the compiled steps as one more fixed-shape input (the
        # lora geometry joins the step-cache key like kv_dtype, but
        # page remapping / load / evict are pure data — zero retraces).
        # An explicit lora_pool= shares one pool across engines (the
        # router/disagg shape); tenants resolve by NAME per step, so
        # page ids never travel between engines.
        rank = int(lora_rank if lora_rank is not None
                   else g["serving_lora_rank"])
        if lora_pool is not None or rank > 0:
            spec.require("lora", "lora_rank > 0 / lora_pool=")
        if lora_pool is not None:
            self.lora_pool = lora_pool
        elif rank > 0:
            self.lora_pool = LoRAPool(
                spec.lora_config, rank,
                int(lora_max_adapters if lora_max_adapters is not None
                    else g["serving_lora_max_adapters"]))
        else:
            self.lora_pool = None
        self._lora_shape = (None if self.lora_pool is None
                            else self.lora_pool.shape_key)
        # Host-RAM KV tier (serving/kv_tier.py): an explicit kv_tier=
        # shares one TierManager across engines (the router/disagg
        # fleet shape, exactly like lora_pool=); FLAGS_serving_host_tier
        # builds a per-engine one. Migration is host-side block surgery
        # plus eager pool writes — zero compiled surfaces join the step
        # cache (predict_serving_compiles(host_tier=True) is a no-op).
        if kv_tier is not None or g["serving_host_tier"]:
            spec.require("host_tier",
                         "kv_tier= / FLAGS_serving_host_tier")
        if kv_tier is not None:
            self.kv_tier = kv_tier
        elif g["serving_host_tier"]:
            full = spec.cache_kinds[0]
            self.kv_tier = TierManager(HostBlockStore(
                len(full.layers), full.kv_heads, full.head_dim,
                block_size=self.cache.block_size,
                num_blocks=int(g["serving_host_blocks"])))
        else:
            self.kv_tier = None
        if self.kv_tier is not None:
            self.kv_tier.attach(self.cache)
        # first-seen-cold timestamps feeding the between-steps demotion
        # sweep (FLAGS_serving_demote_idle_ms); step-lock-owned like
        # _active, mutated in place so no guarded rebinding
        self._cold_since: Dict[int, float] = {}
        # JSON-constrained decoding: a JsonGrammar whose per-request
        # cursors produce the additive [vocab] mask rows. Constructor
        # state like the SLO knobs — json_mode submissions without it
        # are rejected with guidance.
        self.grammar = grammar
        if self.grammar is not None and \
                self.grammar.vocab_size != spec.vocab:
            raise ValueError(
                f"grammar vocab {self.grammar.vocab_size} != model "
                f"vocab {spec.vocab}")
        self._vocab = int(spec.vocab)
        if self.mesh is not None:
            self._place_on_mesh()
        self._queue: deque = deque()          # guarded-by: _lock
        self._active: Dict[int, Request] = {}  # guarded-by: _step_lock
        self._all: List[Request] = []         # guarded-by: _lock
        # a draining engine refuses new submissions (reason="drain");
        # routers skip it when routing and may re-home its queue via
        # take_queued()/adopt_request() on a live peer. Deliberately
        # NOT lock-guarded: a single bool flipped by the router and
        # read racily by submit (a stale read sheds one request late,
        # which the drain loop absorbs).
        self.draining = False
        self._lock = _ccz.make_lock("engine._lock")  # queue + _all
        self._step_lock = _ccz.make_lock(
            "engine._step_lock")             # one scheduler at a time
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._prefill_fns: Dict[int, dict] = {}   # bucket len -> entry
        # completed-request latency distributions live in the shared
        # metrics plane as fixed-bucket histograms (one label series
        # per engine instance): constant memory however many requests
        # retire, and the same numbers surface on GET /metrics
        eid = str(next(ServingEngine._engine_ids))
        self._eid = eid
        self._ttft_hist = _obs.histogram(
            "serving_ttft_seconds",
            "time to first token of completed requests (s)"
            ).labels(engine=eid)
        self._tpot_hist = _obs.histogram(
            "serving_tpot_seconds",
            "mean time per output token of completed requests (s)"
            ).labels(engine=eid)
        self._completed = 0                         # guarded-by: _lock
        # shed accounting: one counter family, labelled by why and by
        # the victim's priority class — the /metrics view of stats()'s
        # per-reason dict (submit-time rejections included)
        self._shed_ctr = _obs.counter(
            "serving_shed_total",
            "requests shed, by reason (queue_full|slo|deadline|"
            "preempted|fault|drain) and priority class")
        self._cancel_ctr = _obs.counter(
            "serving_canceled_total",
            "requests canceled mid-lifecycle, by reason (client|"
            "disconnect|deadline|hedge_lose|duplicate); every cancel "
            "reclaims its KV blocks and LoRA pin at whatever stage it "
            "caught the request")
        self._slo_gauge = None
        if self.slo_ttft_ms:
            self._slo_gauge = _obs.gauge(
                "serving_slo_attainment",
                "fraction of completed requests whose first token met "
                "the TTFT SLO (FLAGS_serving_slo_ttft_ms)"
                ).labels(engine=eid)
        # scheduler-owned accounting — written only with the step lock
        # held (step()/kill paths), scraped by stats() under the same
        self._spec_proposed = 0     # guarded-by: _step_lock
        self._spec_accepted = 0     # guarded-by: _step_lock
        self._prefix_hit_reqs = 0   # guarded-by: _step_lock
        self._prefix_miss_reqs = 0  # guarded-by: _step_lock
        self._blocks_used_g = _obs.gauge(
            "serving_kv_blocks_used",
            "physical KV blocks currently referenced (paged "
            "serving; includes the trash block and prefix-cache "
            "holds)").labels(engine=eid, tier="device")
        self._blocks_free_g = _obs.gauge(
            "serving_kv_blocks_free",
            "physical KV blocks on the free list (paged serving)"
            ).labels(engine=eid, tier="device")
        self._blocks_used_g.set(self.cache.blocks_used)
        self._blocks_free_g.set(self.cache.blocks_free)
        _obs.gauge(
            "serving_mesh_devices",
            "devices this engine's compiled steps span (data x model "
            "mesh size; 1 for a single-device engine)"
            ).labels(engine=eid).set(
                1 if self.mesh is None else self.mesh.devices.size)
        # per-tenant outcomes ("" keys base traffic): completed and
        # SLO-met counts, surfaced in stats()["tenants"] — the
        # per-tenant attainment the router/loadgen aggregate
        self._tenant_stats: Dict[str, List[int]] = {}  # guarded-by: _lock
        self._lora_gauge = None
        if self.lora_pool is not None:
            self._lora_gauge = _obs.gauge(
                "serving_lora_adapters_loaded",
                "LoRA adapters resident in this engine's paged "
                "adapter pool (base page excluded)").labels(engine=eid)
            self._lora_gauge.set(len(self.lora_pool.loaded))
        self._weight_version = 0          # guarded-by: _step_lock
        self._weight_version_g = _obs.gauge(
            "serving_weight_version",
            "live weight hot-swaps applied to this engine's model "
            "(0 = the weights it was built with)").labels(engine=eid)
        self._weight_version_g.set(0)
        self._step_no = 0                 # guarded-by: _step_lock
        # the single decode step is dispatched one ahead of its fetch:
        # the step in flight between two _decode calls (_Flight), the
        # steps dispatched before the step before them was fetched, and
        # their rows that were committed / computed for nobody
        self._flight = None               # guarded-by: _step_lock
        # the prefill groups of this round whose first tokens are not
        # fetched yet (_Prefill), in dispatch order: the decode step is
        # dispatched behind them first. Empty between two rounds
        self._prefills: List[_Prefill] = []   # guarded-by: _step_lock
        self._admit_ahead_dispatches = 0  # guarded-by: _step_lock
        # the engine's one sequence of dispatches (a _Stamps' id), and
        # when the last of them to be fetched was: the device was busy
        # with it until then, whatever was dispatched behind it
        self._dispatch_seq = 0            # guarded-by: _step_lock
        self._fetched_last = (0, 0)       # (id, t_fetched ns)
        # the step account of stats(): monotone sums (ms) and counts,
        # fed by the flights' stamps, profiler on or off
        self._account = dict.fromkeys(    # guarded-by: _step_lock
            _ACCOUNT_KEYS, 0)
        self._ahead_dispatches = 0        # guarded-by: _step_lock
        self._ahead_rows_committed = 0    # guarded-by: _step_lock
        self._ahead_rows_dropped = 0      # guarded-by: _step_lock
        # paged dispatches, and those after which the pools handed in
        # were deleted (donated: the KV rows were written in place)
        self._pool_dispatches = 0         # guarded-by: _step_lock
        self._pool_inplace = 0            # guarded-by: _step_lock
        self._pool_epoch = self.cache.pool.epoch
        # decode/verify dispatches, and those whose batch was all
        # greedy: the step's lax.cond took the branch without the
        # sampler (decoding._where_any_sampled)
        self._sampler_dispatches = 0      # guarded-by: _step_lock
        self._sampler_skipped = 0         # guarded-by: _step_lock
        # the step entries' operands as the device holds them, each
        # beside the host state it was made from (_resident), and the
        # last commit's (progress, tokens, keys) carried outputs
        # (_carried): a dispatch re-sends only what changed since.
        # Mutated in place / rebound under the step lock like _active
        self._res: Dict[str, tuple] = {}
        self._carry = None                # guarded-by: _step_lock
        self._resent = False              # did this dispatch re-send?
        # the model's device counters (seam: ``counters``): the decode
        # step's last argument and last result, read by stats() only
        self._counted = jnp.zeros((len(self.spec.counters),), jnp.float32) \
            if self.spec.counters else None   # guarded-by: _step_lock
        self._repl = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl = NamedSharding(self.mesh, PartitionSpec())
        # of the dispatches _sampler_dispatches counts, those that
        # copied nothing to the device but the step's own tokens /
        # lengths
        self._inputs_resident = 0         # guarded-by: _step_lock
        # rows the prefill dispatches carried live, and rows they
        # computed (the rest was padding)
        self._prefill_rows_live = 0       # guarded-by: _step_lock
        self._prefill_rows_computed = 0   # guarded-by: _step_lock
        # prompt tokens those rows held, and the positions (rows x
        # bucket) the dispatches computed: a recurrence pays for every
        # padded position, which attention's mask does not
        self._prefill_tokens_live = 0     # guarded-by: _step_lock
        self._prefill_tokens_computed = 0  # guarded-by: _step_lock
        # what the model says a prefill dispatch read (seam:
        # ``prompt_counts``), by name; added to in place under the step
        # lock, and empty for a model that declares none
        self._prompt_counted = Counter()
        self._qerr_max = 0.0              # guarded-by: _step_lock
        self._qerr_gauge = None
        if self.kv_dtype == "int8":
            self._qerr_gauge = _obs.gauge(
                "serving_kv_dequant_max_abs_err",
                "max abs int8 KV dequantization error observed over "
                "rows written by this engine's compiled steps"
                ).labels(engine=eid)
            self._qerr_gauge.set(0.0)
        # dynamic half of the `# guarded-by:` declarations above: under
        # FLAGS_sanitize_locks a rebinding write to any of these without
        # the named lock held raises GuardedStateError. Construction
        # writes precede this call and are exempt by design.
        _ccz.declare_guarded(self, {
            "_queue": "_lock", "_all": "_lock", "_completed": "_lock",
            "_slo_met": "_lock", "_shed_by_reason": "_lock",
            "_canceled_by_reason": "_lock",
            "_tenant_stats": "_lock",
            "_active": "_step_lock", "_spec_proposed": "_step_lock",
            "_spec_accepted": "_step_lock",
            "_prefix_hit_reqs": "_step_lock",
            "_prefix_miss_reqs": "_step_lock",
            "_weight_version": "_step_lock",
            "_qerr_max": "_step_lock",
            "_flight": "_step_lock",
            "_prefills": "_step_lock",
            "_admit_ahead_dispatches": "_step_lock",
            "_ahead_dispatches": "_step_lock",
            "_ahead_rows_committed": "_step_lock",
            "_ahead_rows_dropped": "_step_lock",
            "_pool_dispatches": "_step_lock",
            "_pool_inplace": "_step_lock",
            "_sampler_dispatches": "_step_lock",
            "_sampler_skipped": "_step_lock",
            "_carry": "_step_lock",
            "_counted": "_step_lock",
            "_inputs_resident": "_step_lock",
            "_prefill_rows_live": "_step_lock",
            "_prefill_rows_computed": "_step_lock",
            "_prefill_tokens_live": "_step_lock",
            "_prefill_tokens_computed": "_step_lock",
        })

    # -------------------------------------------------------------- mesh
    def _place_on_mesh(self):
        """Pin params and the block pools to the serving mesh: params
        per ``SERVING_TP_RULES`` (heads / MLP hidden column-parallel on
        ``"model"``), pool layers with their heads axis on ``"model"``.
        Param placement runs once per (model, mesh) — data-parallel
        replicas sharing one model reuse the placed params and the
        compiled steps instead of re-placing per engine."""
        from jax.sharding import NamedSharding
        mesh, mkey = self.mesh, mesh_cache_key(self.mesh)
        if getattr(self.model, "_serving_mesh_placed", None) != mkey:
            for name, p in self.model.named_parameters():
                spec = SERVING_TP_RULES.spec_for(name, p.value.shape,
                                                 mesh)
                p.value = jax.device_put(p.value,
                                         NamedSharding(mesh, spec))
            self.model._serving_mesh_placed = mkey
        pools = self.cache.arrays()
        self.cache.set_arrays([
            tuple(jax.device_put(a, sh) for a, sh in zip(layer, shs))
            for layer, shs in zip(pools, kv_pool_shardings(mesh, pools))])

    # ------------------------------------------------- weight hot-swap
    def swap_weights(self, state, *, reset_costs: bool = True) -> int:
        """Swap the live model weights in place — the serve half of the
        train→serve loop: a training job publishes a checkpoint into
        this *running* engine between iterations, no drain, no restart.

        ``state`` maps dotted ``named_parameters()`` names to arrays
        (numpy/jnp/Tensor — e.g. ``zero.weights_from_checkpoint``'s
        output); every live parameter must be present with its exact
        shape. Because compiled steps take the weights as explicit jit
        inputs (``models/generation.param_leaves``), the new values ride
        into the *existing* executables as data: the unified step cache
        is untouched and the compile tracker observes **zero new
        compiles**. The assignment happens under the step lock, so
        in-flight requests see a clean cut between steps: tokens decoded
        before the swap came from the old weights, tokens after from the
        new — KV entries written by the old weights are intentionally
        kept (the continual-pretraining contract; restart the request
        for a pure-new-weights answer).

        Under a mesh the new arrays are placed per ``SERVING_TP_RULES``
        first, preserving the resident layout. ``reset_costs`` drops the
        learned prefill/TPOT EWMAs afterwards (pins stay): the new
        weights' dispatch costs re-learn from fresh observations while
        admission predictions stay monotone (they fall back to the
        global/pinned costs, never to garbage). Returns the new weight
        version (also on the ``serving_weight_version`` gauge).
        """
        named = list(self.model.named_parameters())
        known = {name for name, _ in named}
        unknown = sorted(set(state) - known)
        missing = sorted(known - set(state))
        if unknown or missing:
            raise ValueError(
                f"swap_weights state does not match the live model: "
                f"missing {missing[:3]}{'...' if len(missing) > 3 else ''}, "
                f"unknown {unknown[:3]}{'...' if len(unknown) > 3 else ''}")
        staged = []
        for name, p in named:
            v = state[name]
            v = getattr(v, "value", v)
            v = jnp.asarray(v, p.value.dtype)
            if tuple(v.shape) != tuple(p.value.shape):
                raise ValueError(
                    f"swap_weights: {name!r} has shape "
                    f"{tuple(v.shape)}, live model expects "
                    f"{tuple(p.value.shape)} — a different architecture "
                    "needs a new engine, not a swap")
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                spec = SERVING_TP_RULES.spec_for(name, p.value.shape,
                                                 self.mesh)
                v = jax.device_put(v, NamedSharding(self.mesh, spec))
            staged.append((p, v))
        from ..models.generation import model_trace_lock
        with self._step_lock, model_trace_lock(self.model):
            # the trace lock keeps the cut clean fleet-wide: a sibling
            # replica mid-trace holds borrowed tracers in these same
            # Parameters, and its restore would silently undo the swap
            self._drain()   # the step in flight is the old weights' last
            for p, v in staged:
                p.value = v
            self._weight_version += 1
            version = self._weight_version
        self._weight_version_g.set(version)
        _runlog.log_event("serving_weight_swap", engine=self._eid,
                          version=version, params=len(staged),
                          reset_costs=bool(reset_costs))
        _monitor.stat_add("STAT_serving_weight_swaps")
        if reset_costs:
            self.reset_cost_estimates()
        return version

    @property
    def weight_version(self) -> int:
        """Hot-swaps applied so far (0 = construction weights)."""
        return self._weight_version

    # ------------------------------------------------- LoRA adapters
    def load_adapter(self, name: str, state) -> int:
        """Load (or hot-reload) a tenant's LoRA adapter into the pool —
        the ``swap_weights`` machinery applied to a pool page: the
        write is a functional update on the pool arrays, which the
        compiled steps take as plain inputs, so the step cache is
        untouched and the compile tracker observes zero new compiles.
        Runs under the step lock for a clean cut between steps.
        Returns the adapter's page id (engine-local; requests carry
        the tenant *name*)."""
        if self.lora_pool is None:
            raise ValueError(
                "engine has no LoRA pool; construct with lora_rank > 0 "
                "(FLAGS_serving_lora_rank) or pass lora_pool=")
        with self._step_lock:
            page = self.lora_pool.load(name, state)
        self._lora_gauge.set(len(self.lora_pool.loaded))
        _runlog.log_event("serving_lora_load", engine=self._eid,
                          adapter=name, page=page)
        _monitor.stat_add("STAT_serving_lora_loads")
        return page

    def evict_adapter(self, name: str) -> int:
        """Evict a tenant's adapter, freeing its pool page. Refuses
        (ValueError) while in-flight requests still pin the page —
        drain that tenant first, the same discipline that keeps KV
        blocks leak-free."""
        if self.lora_pool is None:
            raise ValueError("engine has no LoRA pool")
        with self._step_lock:
            page = self.lora_pool.evict(name)
        self._lora_gauge.set(len(self.lora_pool.loaded))
        _runlog.log_event("serving_lora_load", engine=self._eid,
                          adapter=name, page=page, evicted=True)
        _monitor.stat_add("STAT_serving_lora_evictions")
        return page

    # --------------------------------------------------- TTFT prediction
    _EWMA_ALPHA = 0.3

    def _ewma(self, old: Optional[float], new: float) -> float:
        if old is None:
            return new
        return (1.0 - self._EWMA_ALPHA) * old + self._EWMA_ALPHA * new

    def _note_prefill_ms(self, bucket: int, ms: float):
        self._prefill_ewma[bucket] = self._ewma(
            self._prefill_ewma.get(bucket), ms)
        self._prefill_ewma_all = self._ewma(self._prefill_ewma_all, ms)

    def _note_tpot_ms(self, ms: float):
        self._tpot_ewma = self._ewma(self._tpot_ewma, ms)

    def _prefill_cost_ms(self, bucket: int) -> float:
        """Estimated cost of one prefill dispatch for this bucket, from
        its dispatch to its first tokens on the host: the pinned value
        when set, else the measured EWMA (global fallback before this
        bucket's first dispatch; 0 before any)."""
        if self._prefill_ms_pin:
            return self._prefill_ms_pin
        v = self._prefill_ewma.get(bucket, self._prefill_ewma_all)
        return 0.0 if v is None else v

    def _tpot_cost_ms(self) -> float:
        """Estimated time of one output token of a running request: the
        pinned value when set, else the EWMA of the device's time for a
        decode step over the tokens it committed a row (0 before any)."""
        if self._tpot_ms_pin:
            return self._tpot_ms_pin
        return self._tpot_ewma if self._tpot_ewma is not None else 0.0

    def reset_cost_estimates(self):
        """Drop the learned EWMA costs (pins stay). Call after a
        warmup pass that paid XLA compiles, so admission predictions
        reflect what a steady-state dispatch takes on the device
        (:meth:`_landed` says what a sample is) instead of trace time."""
        self._prefill_ewma.clear()
        self._prefill_ewma_all = None
        self._tpot_ewma = None

    def predict_ttft_ms(self, prompt_len: int = 1,
                        queue_ahead: Optional[int] = None) -> float:
        """First-order TTFT prediction for a would-be submission, in
        ms, from live host state only: queue depth ahead of it, free
        decode slots, the per-bucket prefill cost, and the decode
        batch's per-token pace. Monotone non-decreasing in queue depth
        — the property the SLO gate and Retry-After rely on.

        Model: requests ahead prefill in dispatches of the rows the
        newcomer's bucket has (``ceil(q / rows)`` dispatches before
        ours: a request that is the k-th dispatch of its group waits k
        of them), and the newcomer waits
        ``ceil(max(0, q + 1 - free) / max_slots)``
        generation rounds for a slot, each lasting one mean new-token
        budget at the current TPOT. Costs come from pins
        (``slo_prefill_ms`` / ``slo_tpot_ms``) or measured EWMAs; with
        neither (a cold engine) the prediction is optimistically 0 and
        the first dispatches teach it."""
        if queue_ahead is None:
            with self._lock:
                queue_ahead = len(self._queue)
        return self._predict_ttft_ms(int(queue_ahead), int(prompt_len))

    def _predict_ttft_ms(self, q: int, prompt_len: int) -> float:
        bucket = self._bucket_for(max(1, min(prompt_len, self.max_len)))
        prefill = self._prefill_cost_ms(bucket)
        tpot = self._tpot_cost_ms()
        live = list(self._active.values())
        budgets = [r.max_new_tokens for r in live]
        budgets += [r.max_new_tokens for r in list(self._queue)[:q]]
        mean_budget = (sum(budgets) / len(budgets) if budgets
                       else self.default_max_new_tokens)
        free = max(0, self.max_slots - len(live))
        waves_ahead = -(-q // self.spec.prefill_rows(bucket,
                                                     self.max_slots))
        rounds = -(-max(0, q + 1 - free) // self.max_slots)
        return (waves_ahead + 1) * prefill + rounds * mean_budget * tpot

    def _retry_after_s(self, pred_ms: float) -> int:
        """Whole-second backoff hint for a shed submission: the
        predicted TTFT when the model has estimates, else the idle
        wait; always >= 1 (Retry-After semantics)."""
        if pred_ms > 0:
            return max(1, int(-(-pred_ms // 1e3)))
        return max(1, int(-(-self.idle_wait // 1)))

    def _count_shed(self, reason: str, priority: int):
        with self._lock:
            self._shed_by_reason[reason] = \
                self._shed_by_reason.get(reason, 0) + 1
        self._shed_ctr.labels(engine=self._eid, reason=reason,
                              priority=str(priority)).inc()

    def _pick_victims(self, priority: int, n: int,
                      exclude: Sequence[Request] = ()) -> List[Request]:
        """(holding self._lock) Queued requests a priority-``priority``
        submission may preempt: strictly lower-priority (numerically
        greater) classes only — worst class first, newest first within
        a class — never peers or betters."""
        pool = [r for r in self._queue
                if r.priority > priority and r not in exclude]
        pool.sort(key=lambda r: (-r.priority, -r.id))
        return pool[:max(0, n)]

    # ------------------------------------------------------------ submit
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               priority: Optional[int] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               stop: Optional[Sequence[Sequence[int]]] = None,
               seed: Optional[int] = None,
               json_mode: Optional[bool] = None,
               tenant: Optional[str] = None,
               decode: Optional[DecodeParams] = None,
               deadline_ms: Optional[float] = None,
               session: Optional[str] = None,
               _log_request: bool = True) -> Request:
        """Queue a generation request; returns its handle immediately.

        ``priority`` is an integer class, lower = more urgent (default
        1); FIFO within a class. Raises ValueError for geometry the
        cache cannot hold and QueueFullError when admission sheds the
        submission — depth backpressure or, with a TTFT SLO configured,
        a predicted TTFT beyond budget (the error carries ``reason``
        and a ``retry_after_s`` hint). With preemption enabled, a
        submission that would otherwise be shed may instead shed queued
        strictly-lower-priority work to make room.

        Per-request decoding rides along as *data*, never as compile
        keys: ``temperature``/``top_k``/``top_p``/``seed`` select the
        sampling law (all-defaults = greedy, byte-identical to the
        pre-sampling engine), ``stop`` is a tuple of token-id stop
        sequences checked host-side, ``json_mode`` masks decoding to
        the engine's JSON ``grammar``, and ``tenant`` names a loaded
        LoRA adapter whose pool page the compiled step gathers for
        this row. Invalid combinations raise ValueError (HTTP 400):
        ``json_mode`` without a grammar or with speculative decoding
        enabled, ``tenant`` without a LoRA pool or naming an adapter
        that is not loaded. ``decode=`` passes a prebuilt
        :class:`DecodeParams` instead of the individual fields.

        ``deadline_ms`` is the client's patience: a hard end-to-end
        deadline (engine-clock ms from submission) after which the
        request is *canceled* wherever it is — queued, mid-prefill or
        mid-decode — instead of burning slots for a caller that has
        given up. It rides the Request through handoffs and re-homes.
        Unlike the TTFT SLO deadline it never affects admission
        prediction; None (the default) keeps today's run-to-completion
        behavior.

        ``session`` names a conversation in the host KV tier
        (requires FLAGS_serving_host_tier or an engine constructed
        with ``kv_tier=``): when the SessionStore holds a context for
        this id, it is prepended to ``prompt`` so the request resumes
        token-identically off the stored chain — the prefix cache (or
        a host->device promotion) covers the shared part and only the
        unshared suffix re-prefills. On finish the full context is
        saved back and the chain demotes to host RAM between turns,
        so idle conversations hold zero device blocks."""
        if deadline_ms is not None and float(deadline_ms) <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {deadline_ms}")
        mnt = int(max_new_tokens if max_new_tokens is not None
                  else self.default_max_new_tokens)
        eos = (eos_token_id if eos_token_id is not None
               else self.default_eos_token_id)
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        sid = str(session) if session is not None else None
        stored_ctx = None
        if sid is not None:
            if not sid:
                raise ValueError("session id must be non-empty")
            if self.kv_tier is None:
                raise ValueError(
                    "submit(session=...) requires the host KV tier: "
                    "set FLAGS_serving_host_tier or construct the "
                    "engine with kv_tier=")
            stored_ctx = self.kv_tier.session_context(sid)
            if stored_ctx:
                # resume: prepend the stored conversation so the
                # rolling-hash chain matches what the previous turn
                # published — geometry validation below sees the full
                # context, and admission re-prefills only the suffix
                # past whatever the prefix cache / promotion covers
                prompt = stored_ctx + prompt
        if mnt < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {mnt}")
        if decode is not None:
            if any(v is not None for v in (temperature, top_k, top_p,
                                           stop, seed, json_mode)):
                raise ValueError(
                    "pass either decode= or individual sampling "
                    "fields, not both")
            params = decode
        else:
            try:
                stops = tuple(tuple(int(t) for t in s)
                              for s in (stop or ()))
            except TypeError:
                raise ValueError(
                    "stop must be a list of token-id sequences, e.g. "
                    "[[5, 6]], not a flat list of ids")
            # DecodeParams.__post_init__ validates ranges (negative
            # temperature/top_k, top_p outside [0, 1], ...)
            params = DecodeParams(
                temperature=float(temperature) if temperature is not None
                else 0.0,
                top_k=int(top_k) if top_k is not None else 0,
                top_p=float(top_p) if top_p is not None else 0.0,
                stop_sequences=stops,
                seed=int(seed) if seed is not None else 0,
                json_mode=bool(json_mode) if json_mode is not None
                else False)
        tenant = str(tenant) if tenant is not None else ""
        if params.json_mode:
            if self.grammar is None:
                raise ValueError(
                    "json_mode requires an engine constructed with a "
                    "grammar= (see serving.decoding.JsonGrammar)")
            if self.spec_tokens > 0:
                raise ValueError(
                    "json_mode cannot combine with speculative decoding "
                    "(FLAGS_serving_spec_tokens > 0): the delta draft "
                    "proposes unmasked tokens")
        if tenant:
            if self.lora_pool is None:
                raise ValueError(
                    "tenant= requires a LoRA pool; construct the engine "
                    "with lora_rank > 0 (FLAGS_serving_lora_rank)")
            self.lora_pool.page_of(tenant)  # unknown adapter -> ValueError
        if len(prompt) + mnt + self.spec_tokens > self.max_len:
            # speculative decoding reserves spec_tokens rows of slot
            # headroom: the verify step scatter-writes K+1 rows at the
            # current offset, and XLA would *clamp* an out-of-range
            # write back onto committed rows instead of failing
            spec = (f" + spec_tokens ({self.spec_tokens})"
                    if self.spec_tokens else "")
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({mnt})"
                f"{spec} exceeds slot capacity max_len={self.max_len}")
        need = self.cache.blocks_needed(
            len(prompt) + mnt + self.spec_tokens)
        if need > self.cache.num_blocks - 1:  # minus trash block
            raise ValueError(
                f"request needs {need} KV blocks but the pool only "
                f"has {self.cache.num_blocks - 1} usable; raise "
                "FLAGS_serving_num_blocks or shorten the request")
        pr = int(priority if priority is not None else 1)
        now = self._clock()
        if _log_request and _runlog.enabled():
            # the replayable arrival record (tools/trace_convert.py):
            # everything loadgen needs to re-offer this exact request.
            # Routers log one fleet-level event themselves and pass
            # _log_request=False so fan-out doesn't duplicate arrivals.
            extra = {}
            if not params.is_default:
                extra.update(temperature=params.temperature,
                             top_k=params.top_k, top_p=params.top_p,
                             seed=params.seed)
                if params.stop_sequences:
                    extra["stop"] = [list(s)
                                     for s in params.stop_sequences]
                if params.json_mode:
                    extra["json_mode"] = True
            if tenant:
                extra["tenant"] = tenant
            if sid is not None:
                extra["session"] = sid
            _runlog.log_event("serving_request", t=round(now, 6),
                              prompt=prompt, max_new_tokens=mnt,
                              priority=pr, engine=self._eid, **extra)
        if self.draining:
            _monitor.stat_add("STAT_serving_rejected")
            self._count_shed("drain", pr)
            raise QueueFullError("engine is draining; resubmit to a "
                                 "live replica", reason="drain",
                                 retry_after_s=self._retry_after_s(0.0))
        # raising kinds reject this submission pre-queue; `skip` sheds
        # it through the same backpressure exit as a full queue
        kind = fault_point("serving.submit")
        if kind == "skip":
            _monitor.stat_add("STAT_serving_rejected")
            self._count_shed("fault", pr)
            raise QueueFullError("submission shed by injected fault at "
                                 "serving.submit", reason="fault",
                                 retry_after_s=self._retry_after_s(0.0))
        req = Request(prompt, mnt, eos, priority=pr, now=now,
                      decode=params, tenant=tenant)
        req.session = sid
        if params.json_mode:
            req._cursor = self.grammar.start()
        if self.slo_ttft_ms:
            req.deadline = now + self.slo_ttft_ms / 1e3
        if deadline_ms is not None:
            req.hard_deadline = now + float(deadline_ms) / 1e3
        reject = None          # (reason, predicted_ms) when shedding
        victims: List[Request] = []
        with self._lock:
            q = len(self._queue)
            if q >= self.max_queue:
                if self.priority_preempt:
                    victims = self._pick_victims(
                        pr, q - self.max_queue + 1)
                if q - len(victims) >= self.max_queue:
                    reject = ("queue_full",
                              self._predict_ttft_ms(q, len(prompt)))
            if reject is None and self.slo_ttft_ms:
                pred = self._predict_ttft_ms(q - len(victims),
                                             len(prompt))
                while pred > self.slo_ttft_ms and self.priority_preempt:
                    more = self._pick_victims(pr, 1, exclude=victims)
                    if not more:
                        break
                    victims.extend(more)
                    pred = self._predict_ttft_ms(q - len(victims),
                                                 len(prompt))
                if pred > self.slo_ttft_ms:
                    reject = ("slo", pred)
            if reject is None:
                for v in victims:
                    self._queue.remove(v)
                self._queue.append(req)
                self._all.append(req)
            else:
                victims = []   # rejected anyway: preempt nothing
        for v in victims:
            self._shed(v, _Shed(f"preempted by priority-{pr} request "
                                f"{req.id}"), reason="preempted")
        if reject is not None:
            reason, pred = reject
            _monitor.stat_add("STAT_serving_rejected")
            self._count_shed(reason, pr)
            if reason == "queue_full":
                msg = (f"serving queue full ({self.max_queue} waiting); "
                       "retry later or raise FLAGS_serving_max_queue")
            else:
                msg = (f"predicted TTFT {pred:.0f}ms exceeds SLO "
                       f"{self.slo_ttft_ms:.0f}ms; retry later or shed")
            raise QueueFullError(msg, reason=reason,
                                 retry_after_s=self._retry_after_s(pred))
        _monitor.stat_add("STAT_serving_submitted")
        if sid is not None:
            req._session_counted = True
            self.kv_tier.session_started(sid)
            if stored_ctx:
                self.kv_tier.session_resumed(
                    sid, len(stored_ctx), len(prompt) - len(stored_ctx))
        _tracing.begin(req.id, req.submitted_at, self.trace_track,
                       prompt_tokens=len(req.prompt),
                       max_new_tokens=req.max_new_tokens,
                       priority=req.priority, tenant=req.tenant)
        self._wake.set()
        return req

    def take_queued(self) -> List["Request"]:
        """Pop every still-queued (not yet admitted) request — the
        drain/re-route path: a router moves these onto live peers via
        :meth:`adopt_request` instead of letting them die with this
        engine. The requests stay in ``_all`` here so their handles
        keep resolving for whoever holds them."""
        with self._lock:
            out = list(self._queue)
            self._queue.clear()
        return out

    def adopt_request(self, req: "Request") -> bool:
        """Enqueue an already-validated request re-routed from a
        draining peer. Depth backpressure only (no SLO re-prediction —
        the request was admitted once already); returns False when the
        queue is full so the router can try the next peer."""
        if self.draining:
            return False
        if len(req.prompt) + req.max_new_tokens + self.spec_tokens > \
                self.max_len:
            return False  # peer geometry differs; not adoptable here
        if req.tokens and len(req.context) > self.buckets[-1]:
            # a re-homed mid-decode request re-prefills its committed
            # context; one that outgrew the largest bucket would force
            # a fresh compile, so it is not adoptable (the router sheds
            # it) — re-homing never widens the compiled surface
            return False
        with self._lock:
            if len(self._queue) >= self.max_queue:
                return False
            self._queue.append(req)
            self._all.append(req)
        self._wake.set()
        return True

    # ----------------------------------------------------------- prefill
    def _bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.max_len  # unreachable: submit() validated length

    def _prefill_entry(self, bucket: int) -> dict:
        """The jitted prompt-suffix pass for one length bucket
        (compiled on first use, reused for every admission that pads
        to it), ONE program a bucket, of ``rows = spec.prefill_rows(
        bucket, max_slots)`` rows: the model's token budget over the
        bucket's length, so a long bucket computes the one prompt it
        admitted and a short one shares a dispatch (and a read of the
        weights) between up to ``max_slots``; a group of more
        same-bucket admissions than ``rows`` goes out as several
        dispatches in admission order. It writes KV through per-row
        block tables into the shared pools. Maps
        ``(ids [rows, bucket] i32, last [rows] i32,
        pos [rows] i32, tables [rows, T] i32, pools, merge)``
        to each row's logits at its true last token, the updated pools
        and, merged by the program itself, what the decode step behind
        it takes: ``merge`` is ``(tokens [max_slots] i32, keys
        [max_slots, 2] u32, slots [rows] i32, row_keys [rows, 2] u32)``,
        the next tokens and keys the dispatch before this one left on
        the device and each row's cache slot and request key; the last
        two results are those tokens with each row's greedy first token
        at its slot and those keys with each row's key at its slot, so
        the step behind a prefill is built without a fetch and without
        a program of its own (:meth:`_rows_ahead`). ``pos`` is each
        row's write offset (its shared-prefix
        length — 0 without a prefix hit), so a prefix-cached prompt
        only computes its unshared suffix; rows past the admitted
        count are padding the caller discards. Cached in the model's
        unified ``step_entry`` cache keyed by the rows, the full pool
        geometry, KV dtype, and mesh — one compile per key,
        so engine restarts with the same geometry (benchmark reruns,
        rolling deploys) reuse the executable. Under a mesh the
        pass runs with explicit in/out shardings: pools keep
        their heads axis on ``"model"``; ids/last/pos/tables stay
        replicated plain inputs so block remapping never retraces."""
        key = ("prefill_paged", bucket,
               self.spec.prefill_rows(bucket, self.max_slots),
               self.max_slots, self.max_len,
               self.cache.block_size, self.cache.num_blocks,
               self.kv_dtype,
               mesh_cache_key(self.mesh))
        lora_shape = self._lora_shape
        if lora_shape is not None:
            key = key + ("lora", tuple(lora_shape))
        model, mesh, kv_dtype = self.model, self.mesh, self.kv_dtype
        spec = self.spec

        def _build():
            from ..models.generation import (_borrowed_params,
                                             _inject_params)

            def _prefill(params, ids, last, pos, tables, pools,
                         merge=None, lora=None):
                from ..models.generation import (_kernel_layout,
                                                 _unwrap_pools)
                with no_grad(), _borrowed_params(model, params), \
                        _kernel_layout(model, mesh):
                    lg, newp = spec.prefill_logits(ids, last, pos, tables,
                                                   pools, lora)
                pools_out, qerr = _unwrap_pools(newp)
                if merge is None:
                    return lg, pools_out, qerr
                # each row's greedy first token over its slot of the
                # tokens the dispatch before this one left, its request's
                # key over its slot of the keys (a padding row's slot is
                # out of range: dropped)
                tokens, keys, slots, row_keys = merge
                first = jnp.argmax(lg, axis=-1).astype(tokens.dtype)
                return (lg, pools_out, qerr,
                        tokens.at[slots].set(first, mode="drop"),
                        keys.at[slots].set(row_keys, mode="drop"))

            # the entry owns ``pools`` (argument 5 here), like the
            # step entries: generation.POOLS_DONATED
            jit_kwargs = {"donate_argnums": (5,)}
            if mesh is not None:
                from ..models.generation import (_mesh_param_shardings,
                                                 _mesh_step_shardings)
                repl, pools_sh = _mesh_step_shardings(model, mesh,
                                                      kv_dtype)
                in_sh = (_mesh_param_shardings(model, mesh),
                         repl, repl, repl, repl, pools_sh, repl)
                if lora_shape is not None:
                    in_sh = in_sh + (repl,)
                jit_kwargs.update(
                    in_shardings=in_sh,
                    out_shardings=(repl, pools_sh, repl, repl, repl))
            fn = _inject_params(
                model, _ct.tracked_jit("serving_prefill_paged", _prefill,
                                       labels={"bucket": str(bucket)},
                                       **jit_kwargs))
            return {"fn": fn, "traces": fn.traces}

        ent = step_entry(model, key, _build)
        self._prefill_fns[bucket] = ent
        return ent

    def _alloc_attempt(self, req: Request, need: int):
        """One block-table acquisition attempt (the serving.alloc fault
        site): returns ``(row, shared) | None`` from the cache, raises
        _Shed on an injected `skip` (simulated allocator failure)."""
        kind = fault_point("serving.alloc")
        if kind == "skip":
            raise _Shed("injected allocator failure for request "
                        f"{req.id}")
        if self.kv_tier is not None:
            # promotion-on-demand: pull any host-resident continuation
            # of this context's chain back up before acquiring — the
            # promoted blocks republish as device prefix entries, so
            # acquire() shares them like any warm prefix. Idempotent
            # under retry (an already-promoted chain is a device hit,
            # not a second copy), and a failed/skipped promotion just
            # means a longer re-prefill.
            self.kv_tier.promote(self.cache, req.context)
        return self.cache.acquire(req.context, need)

    def _prefill_group_attempt(self, bucket: int,
                               group):  # holds: _step_lock
        """One batched prefill attempt for the same-bucket admissions
        of one dispatch (at most the rows of the bucket's entry);
        ``group`` rows are ``(req, row, shared)``. The
        fault site fires once per request per attempt (preserving the
        per-request `skip`-sheds-one semantics); surviving requests
        share one dispatch of the bucket's compiled function, which
        merges their first tokens and keys into what the dispatch
        before it left on the device (:meth:`_head`). Returns
        ``(live, shed, (logits, new_pools, qerr, tokens, keys) |
        None)``."""
        live, shed = [], []
        for rec in group:
            kind = fault_point("serving.step")
            if kind == "skip":
                shed.append((rec, _Shed("injected skip during prefill "
                                        f"of request {rec[0].id}")))
            else:
                live.append(rec)
        if not live:
            return live, shed, None
        n = self.spec.prefill_rows(bucket, self.max_slots)
        self._prefill_rows_live += len(live)
        self._prefill_rows_computed += n
        _monitor.stat_add("STAT_serving_prefill_rows_live", len(live))
        _monitor.stat_add("STAT_serving_prefill_rows_computed", n)
        tokens = sum(len(req.context) - shared for req, _, shared in live)
        self._prefill_tokens_live += tokens
        self._prefill_tokens_computed += n * bucket
        _monitor.stat_add("STAT_serving_prefill_tokens_live", tokens)
        _monitor.stat_add("STAT_serving_prefill_tokens_computed",
                          n * bucket)
        if self.spec.prompt_counts is not None:
            self._prompt_counted.update(self.spec.prompt_counts(
                bucket, n, max(len(req.context) - shared
                               for req, _, shared in live)))
        ids = np.zeros((n, bucket), np.int32)
        last = np.zeros(n, np.int32)
        pos = np.zeros(n, np.int32)
        tables = self.cache.table_rows([row for _, row, _ in live], n)
        pages = np.zeros(n, np.int32)
        slots = np.full(n, self.max_slots, np.int32)  # padding: dropped
        row_keys = np.zeros((n, 2), np.uint32)
        for i, (req, row, shared) in enumerate(live):
            suffix = req.context[shared:]
            ids[i, :len(suffix)] = suffix
            last[i] = len(suffix) - 1
            pos[i] = shared
            slots[i] = row
            row_keys[i] = req._key
            if self.lora_pool is not None and req.tenant:
                pages[i] = self.lora_pool.page_of(req.tenant)
        fn = self._prefill_entry(bucket)["fn"]
        args = (jnp.asarray(ids), jnp.asarray(last),
                jnp.asarray(pos), jax.tree_util.tree_map(jnp.asarray,
                                                         tables),
                self.cache.arrays(),
                self._head() + (jnp.asarray(slots), jnp.asarray(row_keys)))
        if self.lora_pool is not None:
            args = args + ((jnp.asarray(pages), self.lora_pool.arrays),)
        return live, shed, self._call_paged(fn, args, args[4])

    def _pop_candidates(self, limit: int):
        """Pop up to ``limit`` queued requests in admission order —
        (priority class, submission id), which is strict FIFO when
        every request uses the default class (the token-identity
        oracle's ordering) — shedding any whose TTFT deadline already
        passed (reason="deadline") instead of spending a prefill
        dispatch on work that can no longer meet its SLO. Returns
        ``(candidates, n_expired)``. Requests whose *hard* deadline
        (client patience) lapsed in the queue are canceled here, the
        queued leg of the every-stage-boundary enforcement."""
        out: List[Request] = []
        expired: List[Request] = []
        hard_expired: List[Request] = []
        now = self._clock()
        with self._lock:
            if len(self._queue) > 1 and \
                    any(r.priority != self._queue[0].priority
                        for r in self._queue):
                self._queue = deque(sorted(
                    self._queue, key=lambda r: (r.priority, r.id)))
            while len(out) < limit and self._queue:
                req = self._queue.popleft()
                if req.hard_deadline is not None and \
                        now > req.hard_deadline:
                    hard_expired.append(req)
                elif req.deadline is not None and now > req.deadline:
                    expired.append(req)
                else:
                    out.append(req)
        for req in expired:
            self._shed(req, _Shed("TTFT deadline expired in queue for "
                                  f"request {req.id}"),
                       reason="deadline")
        for req in hard_expired:
            self._finalize_cancel(req, "queued", "deadline")
        return out, len(expired) + len(hard_expired)

    def _admit_round(self):  # holds: _step_lock
        """One admission pass: pop queued requests in admission
        order (FIFO within a priority class), acquire a block table
        for each (prefix-cache reuse first), group by the unshared
        *suffix*'s bucket, one batched prefill per group. Pool
        exhaustion requeues the head-of-line request (and all behind
        it — intra-class FIFO order is part of the equivalence oracle)
        until retirements free blocks. Returns (consumed, admitted)."""
        with _profiler.RecordEvent("serving.schedule") as sched:
            candidates, expired = self._pop_candidates(
                self.cache.num_free)
            if not candidates:
                return expired, 0
            acquired = []   # (req, row, shared)
            back: List[Request] = []
            for req in candidates:
                if back:          # head-of-line blocked: keep FIFO order
                    back.append(req)
                    continue
                need = (len(req.prompt) + req.max_new_tokens +
                        self.spec_tokens)
                try:
                    res = RetryPolicy.from_flags("serving.alloc").call(
                        self._alloc_attempt, req, need)
                except _Shed as e:
                    self._shed(req, e)
                    continue
                except RetryError as e:
                    self._shed(req, e)
                    continue
                if res is None:
                    back.append(req)   # pool dry: wait for retirements
                    continue
                if req.tenant and self.lora_pool is not None:
                    # pin the tenant's adapter page for the request's
                    # lifetime (released in _finish/_shed); an adapter
                    # evicted between submit and admit sheds here
                    try:
                        self.lora_pool.acquire(req.tenant)
                        req._lora_held = True
                    except ValueError as e:
                        self.cache.release_row(res[0])
                        self._shed(req, _Shed(str(e)))
                        continue
                acquired.append((req, res[0], res[1]))
            if back:
                with self._lock:
                    self._queue.extendleft(reversed(back))
            consumed = expired + len(candidates) - len(back)
            if not acquired:
                return consumed, 0
            groups: Dict[int, List] = {}
            for rec in acquired:
                req, row, shared = rec
                groups.setdefault(
                    self._bucket_for(len(req.context) - shared),
                    []).append(rec)
            # known only now, so kept in the in-process event alone
            sched.args = {"admitted": len(acquired)}
        admitted = 0
        epoch = self.cache.pool.epoch
        for bucket in sorted(groups):
            # a dispatch carries as many prompts as the bucket's entry
            # has rows (the model's token budget over the bucket: fewer
            # the longer it is), the rest follow in admission order
            n = self.spec.prefill_rows(bucket, self.max_slots)
            for k in range(0, len(groups[bucket]), n):
                part = groups[bucket][k:k + n]
                if self.cache.pool.epoch != epoch:
                    # an earlier dispatch of this round lost the pools,
                    # and with them the prefix blocks a row acquired
                    # before it shares
                    stale = [rec for rec in part if rec[2]]
                    for req, row, _ in stale:
                        self.cache.release_row(row)
                        self._shed(req, _PoolsLost(
                            f"the shared prefix of request {req.id} "
                            f"went with the pools an earlier dispatch "
                            f"of its round lost"))
                    part = [rec for rec in part if rec not in stale]
                    if not part:
                        continue
                admitted += self._prefill_group(bucket, part)
        return consumed, admitted

    def _prefill_group(self, bucket: int,
                       group) -> int:  # holds: _step_lock
        """Dispatch one group of an admission round (:class:`_Stamps`):
        the pools it returns are bound at once, as every entry's are, and
        its rows stand in ``_active`` from here, so whatever is
        dispatched next queues behind it on the device and counts them
        in. Its first tokens are fetched and committed by
        :meth:`_land_prefill`: at once where a row's first token is the
        host's to draw from the logits (a sampled or grammar row) or
        where a step decodes several tokens (drafts: they are built
        from tokens the host holds), else after the decode
        step behind the round's groups is on the device
        (:meth:`_decode_attempt`). Nothing of this is configured: it is
        read off the admitted requests. Returns rows admitted."""
        t_adm = self._clock()
        for g_req, _row, _shared in group:
            _tracing.mark(g_req.id, "admit", t_adm, self.trace_track)
            g_req.admitted_at = t_adm
        # queued on the device behind the decode step in flight, if any
        st, behind = self._stamps(), self._flight is not None
        try:
            with _profiler.RecordEvent("serving.prefill",
                                       {"flight": st.id}) as ev:
                live, shed, out = RetryPolicy.from_flags(
                    "serving.step").call(
                        self._prefill_group_attempt, bucket, group)
        except (RetryError, _PoolsLost) as e:
            for req, row, _ in group:
                self.cache.release_row(row)
                self._shed(req, e)
            return 0
        st.t_dispatch, st.t_launched = ev.t0, ev.t1
        for (req, row, _), err in shed:
            self.cache.release_row(row)
            self._shed(req, err)
        if not live:
            return 0
        lg, pools, qerr, nxt, keys = out
        self.cache.set_arrays(pools)
        for req, row, _ in live:
            req.slot = row
            req.state = "running"
            self._active[row] = req
        pf = _Prefill(bucket, tuple(live), lg, nxt, keys, qerr, behind,
                      False, st)
        if not self.spec_tokens and all(
                req.decode.is_greedy and req._cursor is None
                for req, _, _ in live):
            self._prefills.append(pf)
        else:
            self._land_prefills()   # the groups before it, in their order
            self._land_prefill(pf)
        return len(live)

    def _land_prefills(self):  # holds: _step_lock
        """Fetch and commit the round's prefill groups, in the order
        they were dispatched in."""
        while self._prefills:
            self._land_prefill(self._prefills.pop(0))
        if not self._active:
            self._drain()    # nobody is left for the step behind them

    def _land_prefill(self, pf: _Prefill):  # holds: _step_lock
        """Fetch one dispatched prefill group's first tokens and commit
        them, row by row: a row stands iff its slot still holds the
        request it was admitted for (one shed since, with the pools an
        earlier fetch lost, is dropped). The dispatch-to-fetched time is
        the bucket's sample for the SLO estimate (:meth:`_landed`), and
        ``serving.prefill_step`` is the group from its dispatch to its
        first tokens committed, whatever the host did between them. A
        group that failed on the device is found here, at its fetch: its
        pools were bound at its dispatch and went with it, and with them
        whatever was dispatched behind it (:meth:`_pools_lost`)."""
        st = pf.stamps
        live = [(i, req, row, shared)
                for i, (req, row, shared) in enumerate(pf.live)
                if self._active.get(row) is req]
        if not live:
            return
        with _profiler.RecordEvent("serving.prefill.fetch",
                                   {"flight": st.id}) as ev:
            try:
                first = np.asarray(pf.nxt)   # the host waits for the device
            except Exception as e:
                self._pools_lost(e)
                return
        st.t_fetch, st.t_fetched = ev.t0, ev.t1
        with _profiler.RecordEvent("serving.prefill.commit",
                                   {"flight": st.id}) as ev:
            now = self._clock()     # the commit's one stamp
            self._note_qerr(pf.qerr, sum(len(req.context) - shared
                                         for _, req, _, shared in live))
            for i, req, row, shared in live:
                ctx = req.context
                self.cache.commit_prefill(row, len(ctx))
                self.cache.insert_prefix(row, ctx)
                if shared:
                    self._prefix_hit_reqs += 1
                    _monitor.stat_add("STAT_serving_prefix_hits")
                else:
                    self._prefix_miss_reqs += 1
                    _monitor.stat_add("STAT_serving_prefix_misses")
                _monitor.stat_add("STAT_serving_prefills")
                _runlog.log_event("serving_admit", request=req.id,
                                  bucket=pf.bucket, slot=row,
                                  prompt_tokens=len(req.prompt),
                                  shared_tokens=shared)
                if req.first_token_at is not None:
                    # a re-homed request re-prefilled its committed
                    # context: the original trace resumes decoding
                    # here instead of re-stamping a first token
                    _tracing.mark(req.id, "resume", self._clock(),
                                  self.trace_track)
                # the first generated token comes from the prefill
                # logits (same argmax greedy_search takes after ITS
                # prefill; sampled/masked rows draw from them instead)
                self._append_token(
                    req, self._take_first(req, first, pf.lg, i), now)
        st.t_committed = ev.t1
        if pf.followed:
            self._admit_ahead_dispatches += 1
            _monitor.stat_add("STAT_serving_admit_ahead_dispatches")
        self._landed(st, len(live), ahead=pf.behind, bucket=pf.bucket)
        _profiler.record_span(
            "serving.prefill_step", st.t_dispatch / 1e9,
            (st.t_committed - st.t_dispatch) / 1e9,
            {"bucket": pf.bucket, "rows": len(live), "flight": st.id})

    def _take_first(self, req: Request, first: np.ndarray, lg,
                    i: int) -> int:
        """The request's first generated token from its prefill-logits
        row ``i``: the program's argmax for plain greedy rows, read at
        the request's slot of the merged tokens ``first`` (the oracle's
        fast path), a host-side :func:`sample_first` draw for sampled or
        grammar-masked rows — same law the compiled steps apply, so a
        restart replays identically."""
        p = req.decode
        if p.is_greedy and req._cursor is None:
            return int(first[req.slot])
        mask_row = None
        if req._cursor is not None:
            mask_row = req._cursor.mask_row(
                req.max_new_tokens - len(req.tokens))
        tok, req._key = sample_first(np.asarray(lg[i]), p, req._key,
                                     mask_row)
        return tok

    def _admit(self, commit: bool = True) -> int:
        """Fill free slots from the queue (batched, one prefill
        dispatch per bucket per round). Returns how many requests were
        admitted; keeps going while progress frees more slots (e.g. a
        request that finishes on its prefill token). With ``commit``
        off the groups whose first tokens can wait stay in
        ``_prefills`` for the caller to land (:meth:`step`: behind the
        decode step, which is dispatched first), and a slot such a
        commit frees is refilled a round later."""
        admitted = 0
        while True:
            popped, n = self._admit_round()
            if commit:
                self._land_prefills()
            admitted += n
            if not popped:
                return admitted

    def _unfetched(self):  # holds: _step_lock
        """The last dispatch whose outputs the host has not fetched: a
        prefill group of this round, else the decode step in flight,
        else None."""
        return self._prefills[-1] if self._prefills else self._flight

    def _head(self):  # holds: _step_lock
        """``(tokens [max_slots] i32, keys [max_slots, 2] u32)`` on the
        device, as the next dispatch finds them: the outputs of the last
        dispatch that is not fetched yet (:meth:`_unfetched`), else what
        the last commit carried, else built from the requests and sent.
        A slot none of these decoded for holds what nothing reads."""
        after = self._unfetched()
        if after is not None:
            return after.nxt, after.keys
        last, keys = self._carried()
        return self._tokens_arg(last), self._keys_arg(keys)

    # ------------------------------------------------------------ decode
    def _send(self, host):  # holds: _step_lock
        """The one host->device copy of a decode / verify operand (an
        array, or a tuple of arrays sent together), placed as the
        entries' ``in_shardings`` name it: replicated over the
        serving mesh, so that no call re-shards it, else on the default
        device. Everything else a dispatch is given is an array the
        device already holds."""
        if self._repl is None:
            return jax.device_put(host)
        return jax.device_put(host, self._repl)

    def _resident(self, name: str, state, build):  # holds: _step_lock
        """Operand ``name`` as the device holds it. ``state`` is what
        the engine observes of the host state the operand mirrors (the
        batch's membership, a version); the operand is built
        (``build()``, on the host) and sent again only when that is no
        longer what the resident copy was made from."""
        held = self._res.get(name)
        if held is None or held[0] != state:
            held = self._res[name] = (state, self._send(build()))
            self._resent = True
        return held[1]

    def _forget_inputs(self):  # holds: _step_lock
        """Drop every resident operand, so that the next dispatch
        rebuilds and sends all of its inputs: what every step did
        before they were kept, and what the tests compare the resident
        path with. Nothing on the serving path calls it: a step whose
        batch, tables or keys changed re-sends what changed."""
        self._res.clear()
        self._carry = None

    def _batch(self):  # holds: _step_lock
        """Which request sits in which slot: what the per-request
        operands (sampling parameters, LoRA pages) are
        valid against."""
        return tuple((slot, req.id) for slot, req in self._active.items())

    def _progress(self):  # holds: _step_lock
        """:meth:`_batch` with each request's count of tokens: what the
        last dispatch's carried outputs (next tokens, advanced keys)
        are valid against. A request that left and came back, or that
        anything but the last commit advanced, does not match."""
        return tuple((slot, req.id, len(req.tokens))
                     for slot, req in self._active.items())

    def _carried(self):  # holds: _step_lock
        """``(tokens, keys)``: the last dispatch's next tokens (None
        after a verify) and advanced keys, as the device holds them,
        while the batch still stands where its commit left it; else
        ``(None, None)``."""
        carry = self._carry
        if carry is not None and carry[0] == self._progress():
            return carry[1], carry[2]
        return None, None

    def _tokens_arg(self, carried):  # holds: _step_lock
        """Each row's last token: the last step's own output
        (``carried``) while the batch stands as it left it, else built
        from the requests and sent. An empty row's entry is never read
        (its writes land in the trash block, as ever)."""
        if carried is not None:
            return carried
        tokens = np.zeros(self.max_slots, np.int32)
        for slot, req in self._active.items():
            tokens[slot] = req.tokens[-1]
        return self._send(tokens)

    def _keys_arg(self, carried):  # holds: _step_lock
        """Each row's key: the last step's ``new_keys`` (``carried``)
        while the batch stands as its commit left it, else gathered from
        the requests, whose host-side key is the authoritative one, and
        sent."""
        if carried is not None:
            return carried
        keys = np.zeros((self.max_slots, 2), np.uint32)
        for slot, req in self._active.items():
            keys[slot] = req._key
        self._resent = True
        return self._send(keys)

    def _tables_arg(self):  # holds: _step_lock
        """The block tables, sent again only after the cache wrote one
        (bind, release, handoff: ``tables_version``); a copy, because
        the cache writes its array in place."""
        return self._resident("tables", self.cache.tables_version,
                              self.cache.tables_arg)

    def _build_samp(self, keys):  # holds: _step_lock
        """The per-slot sampling-as-data tuple for one compiled step:
        fixed-shape plain inputs ``(temperature [b] f32, top_k [b] i32,
        top_p [b] f32, keys [b, 2] u32, mask [b, vocab] f32)``, each as
        the device already holds it unless what it mirrors changed.
        Temperature, top-k and top-p are rebuilt from the active
        requests when the batch's membership changed (empty slots stay
        at the all-zero neutral row, greedy, so padding rows reproduce
        the pre-sampling argmax bit-for-bit). The keys are the last
        step's ``new_keys`` while the batch stands as that step's
        commit left it (``keys``, :meth:`_carried`), else (None)
        gathered from the requests, whose host-side key stays the
        authoritative one. The mask is
        one resident zero array; only a step with a grammar-cursored
        row builds and sends a real one: that row's additive JSON mask
        for the *next* position, budget-aware so the emitted document
        always closes in time."""
        b, V = self.max_slots, self._vocab

        def consts():
            temp = np.zeros(b, np.float32)
            tk = np.zeros(b, np.int32)
            tp = np.zeros(b, np.float32)
            for slot, req in self._active.items():
                p = req.decode
                temp[slot] = p.temperature
                tk[slot] = p.top_k
                tp[slot] = p.top_p
            return temp, tk, tp

        temp, tk, tp = self._resident("samp", self._batch(), consts)
        keys = self._keys_arg(keys)
        cursored = [(slot, req) for slot, req in self._active.items()
                    if req._cursor is not None]
        if cursored:
            mask = np.zeros((b, V), np.float32)
            for slot, req in cursored:
                remaining = req.max_new_tokens - len(req.tokens)
                req._cursor.mask_row(remaining, out=mask[slot])
            mask = self._send(mask)
            self._resent = True
        else:
            mask = self._resident("mask", (b, V),
                                  lambda: np.zeros((b, V), np.float32))
        return temp, tk, tp, keys, mask

    def _writeback_keys(self, new_keys):
        """Persist each active row's advanced RNG key back onto its
        request — the authoritative key lives host-side on the Request
        (it travels with disagg handoffs and engine restarts); the
        device array it was read from is what the next step takes while
        the batch is unchanged (:meth:`_carried`). Advancement is
        request-local (a fixed per-row split fan-out), so replaying the
        same request through any batch composition draws the same
        stream."""
        if not self._active:
            return
        arr = np.asarray(new_keys)
        for slot, req in self._active.items():
            req._key = arr[slot].copy()

    def _lora_args(self):  # holds: _step_lock
        """The per-step LoRA input ``(page_ids [b] i32, pool arrays)``:
        each active row's tenant resolved by NAME to its current pool
        page (safe against eviction — in-flight requests pin their
        page), empty/base rows on the all-zero base page 0. The page
        ids are sent again when the batch's membership changed or the
        pool loaded or evicted an adapter."""
        def pages():
            ids = np.zeros(self.max_slots, np.int32)
            for slot, req in self._active.items():
                if req.tenant:
                    ids[slot] = self.lora_pool.page_of(req.tenant)
            return ids

        return (self._resident("lora", (self._batch(),
                                        self.lora_pool.version), pages),
                self.lora_pool.arrays)

    def _shed_active(self, err: BaseException):  # holds: _step_lock
        """Shed every running request and free its row."""
        for slot, req in list(self._active.items()):
            del self._active[slot]
            self.cache.release_row(slot)
            self._shed(req, err)

    def _call_paged(self, fn, args, pools):  # holds: _step_lock
        """Dispatch one paged entry. The entry owns ``pools`` (one of
        ``args``, fresh from ``cache.arrays()`` or a previous entry's
        result): the call deletes them, and the caller binds the
        returned pools before anything reads the cache's arrays again.
        ``STAT_serving_pool_inplace`` counts the dispatches after which
        they were in fact deleted (all of them, unless the backend
        ignores donation: then the pools are copied as before).

        An entry that raises once the pools are gone has taken every
        row's KV with it: what was running is shed, the pool is rebuilt
        zeroed with its prefix cache flushed, and :class:`_PoolsLost`
        tells the caller that this dispatch produced nothing. A raise
        that left the pools alone passes through (the retry policy's
        business, as before)."""
        try:
            out = fn(*args)
        except Exception as e:
            if not pools[0][0].is_deleted():
                raise
            self._pools_lost(e)
            raise _PoolsLost(f"KV pools consumed by a failed step: {e}"
                             ) from e
        self._pool_dispatches += 1
        if pools[0][0].is_deleted():
            self._pool_inplace += 1
            _monitor.stat_add("STAT_serving_pool_inplace")
        return out

    def _pools_lost(self, err: BaseException):  # holds: _step_lock
        """The pools' contents went with a failed step: shed what was
        running (and the step in flight and the prefill groups not
        fetched yet, whose tokens are nobody's now), and start from
        zeroed pools with the prefix cache flushed."""
        self._flight = None
        self._prefills = []
        self._shed_active(err)
        self.cache.rebuild_pools()
        self._pool_epoch = self.cache.pool.epoch
        _monitor.stat_add("STAT_serving_pool_rebuilds")
        _runlog.log_event("serving_pool_rebuild", error=str(err))

    def _note_dispatch(self):  # holds: _step_lock
        """Count one decode / verify dispatch, whether every live row
        was greedy, and whether its inputs were resident.
        The first is what the step's ``lax.cond`` on "does any row
        sample" reads on the device from the ``samp`` this batch was
        given: ``sampler_skipped / sampler_dispatches`` in
        :meth:`stats` is the share of dispatches that ran the argmax
        alone, without the processor chain and the draws. The second:
        ``inputs_resident / inputs_dispatches`` is the share that
        copied nothing to the device but the step's own small arrays
        (tokens, lengths), every other operand being the array the
        device held."""
        self._sampler_dispatches += 1
        if all(req.decode.is_greedy for req in self._active.values()):
            self._sampler_skipped += 1
            _monitor.stat_add("STAT_serving_sampler_skipped")
        if not self._resent:
            self._inputs_resident += 1
            _monitor.stat_add("STAT_serving_inputs_resident")

    def _stamps(self) -> _Stamps:  # holds: _step_lock
        """The next dispatch's id, and room for its stamps."""
        self._dispatch_seq += 1
        return _Stamps(self._dispatch_seq)

    def _landed(self, st: _Stamps, rows: int, tokens_a_row: float = 1.0,
                ahead: bool = False,
                bucket: Optional[int] = None):  # holds: _step_lock
        """One dispatch is committed: its stamps, the readings its spans
        took, feed the step account of :meth:`stats`, the SLO estimates,
        the phase's ``STAT_*`` timer and the ``serving.flight`` span.
        ``ahead``: it was dispatched behind a decode step in flight;
        ``bucket`` says it was a prefill group.

        The device runs dispatches in the order of their ids and was
        busy with the last one fetched until that fetch returned. So a
        decode step took the device from ``max(t_launched, the last
        fetch's return)`` to ``t_fetched``: exactly, if the host waited
        for it; at most, if its tokens were there already (the account
        takes the bound as it is). Flights are fetched in the order of
        their ids but for a prefill group fetched at once (a row's first
        token is the host's to draw): the step in flight it overtook
        ended unobserved and feeds no estimate. The TPOT sample is that
        time over the tokens the step committed a row; the prefill sample
        is dispatch to fetched, the time behind a step in flight
        included, which is what the next arrival pays too."""
        a = self._account
        host = (st.t_launched - st.t_dispatch
                + st.t_committed - st.t_fetched) / 1e6
        wait = (st.t_fetched - st.t_fetch) / 1e6
        turnaround = (st.t_fetched - st.t_dispatch) / 1e6
        last_id, last_fetched = self._fetched_last
        self._fetched_last = (st.id, st.t_fetched)
        args = {"flight": st.id, "ahead": int(ahead), "rows": rows,
                "prefill": int(bucket is not None)}
        if bucket is not None:
            a["prefill_flights"] += 1
            a["prefill_host_ms"] += host
            a["prefill_wait_ms"] += wait
            self._note_prefill_ms(bucket, turnaround)
            stat = "STAT_serving_prefill"
            args["bucket"] = bucket
        else:
            # with speculation on, every decode step is a verify
            stat = ("STAT_serving_verify" if self.spec_tokens
                    else "STAT_serving_decode")
            device = (st.t_fetched - max(st.t_launched, last_fetched)) / 1e6
            a["decode_flights"] += 1
            a["decode_host_ms"] += host
            a["decode_wait_ms"] += wait
            a["decode_device_ms"] += device
            a["kv_blocks_live"] += self.cache.blocks_live()
            a["kv_blocks_table"] += self.cache.tables.size
            if last_id < st.id and tokens_a_row:
                self._note_tpot_ms(device / tokens_a_row)
        _monitor.stat_observe(stat, turnaround)
        _profiler.record_span("serving.flight", st.t_dispatch / 1e9,
                              (st.t_committed - st.t_dispatch) / 1e9, args)

    def _step_args(self, st: _Stamps, tokens=None,
                   after=None):  # holds: _step_lock
        """The inputs of one decode or verify dispatch after the
        params: ``(tokens, lengths, tables, pools, samp[, lora])``,
        built under the ``serving.decode.inputs`` span whose entry is
        ``st``'s ``t_dispatch``.
        A decode step's ``tokens`` are the last step's own output while
        the batch is unchanged; a verify's ``[b, K+1]`` tree comes from
        the host. ``lengths`` is one small copy a step (a copy, because
        the cache advances its array in place); everything else is
        resident (:meth:`_resident`) and re-sent only when changed.

        ``after``: the last dispatch that this one is queued behind
        before the host has fetched it, the step in flight or the last
        prefill group of the round (:meth:`_rows_ahead` said it can
        be). Its tokens and keys are that dispatch's outputs as the
        device holds them; a row of the step in flight stands one
        further than the cache has committed and a row of a prefill
        group at its context's length (``ahead_lengths``, which moves
        the window kinds first: a table that moved is re-sent here)."""
        with _profiler.RecordEvent(
                "serving.decode.inputs",
                {"flight": st.id, "ahead": int(after is not None)}) as ev:
            self._resent = False
            if after is None:
                last, keys = self._carried()
                lengths = self.cache.lengths.copy()
            else:
                last, keys = after.nxt, after.keys
                fl = self._flight
                lengths = self.cache.ahead_lengths(
                    [slot for slot, req, _ in (fl.rows if fl else ())
                     if self._active.get(slot) is req],
                    [(row, len(req.context)) for pf in self._prefills
                     for req, row, _ in pf.live
                     if self._active.get(row) is req])
            args = (self._tokens_arg(last) if tokens is None
                    else self._send(tokens),
                    self._send(lengths), self._tables_arg(),
                    self.cache.arrays(), self._build_samp(keys))
            if self._lora_shape is not None:
                args = args + (self._lora_args(),)
        st.t_dispatch = st.t_launched = ev.t0
        return args

    def _launch(self, after=None, rows=None):  # holds: _step_lock
        """Dispatch one decode step, for every running request or, behind
        the dispatch ``after`` whose outputs the host has not fetched
        (the step in flight, or the round's last prefill group), for
        ``rows``. The step owns
        the pools (and the model's counters) from here: what it returns
        of them is bound at once, so whatever is dispatched next, a
        prefill or the step after, queues behind it on the device.
        -> the :class:`_Flight` whose tokens are still to be fetched."""
        fn = self.spec.decode_entry(self.mesh, self.kv_dtype,
                                    self._lora_shape)["fn"]
        if after is None:
            rows = tuple((slot, req, len(req.tokens))
                         for slot, req in self._active.items())
        st = self._stamps()
        args = self._step_args(st, after=after)
        if self._counted is not None:
            args = args + (self._counted,)
        out = self._call_paged(fn, args, args[3])
        self._note_dispatch()
        nxt, _, arrays, qerr, new_keys, *counted = out
        self.cache.set_arrays(arrays)
        if counted:
            self._counted, = counted
        if after is not None:
            # the rows it computes for nobody: they finish by budget at
            # ``after``'s commit, which the host knows already
            dropped = len(self._active) - len(rows)
            self._ahead_dispatches += 1
            self._ahead_rows_dropped += dropped
            _monitor.stat_add("STAT_serving_ahead_dispatches")
            _monitor.stat_add("STAT_serving_ahead_misses", dropped)
        return _Flight(rows, nxt, new_keys, qerr, after is not None, st)

    def _rows_ahead(self, fl: Optional[_Flight]):  # holds: _step_lock
        """The rows of the step after ``fl`` (the step in flight, or
        None) and the round's prefill groups, if that step can be
        dispatched before they are fetched; else None. It can when
        everything it needs is on the device or known to the host: every
        running request's last token is where :meth:`_head` reads it, as
        a row of ``fl``, a row of a prefill group not fetched yet, or,
        with no step in flight, on the host, from which the head was
        then built (one a prefill committed with ``fl`` in flight has
        its token on the host alone, and joins the step after), none
        decodes under a grammar (its mask for the next position is built
        from the token not delivered yet), and some request goes on (one
        that reaches its budget with the token in flight does not: its
        row is computed and dropped). Nothing here is configured: it is
        read off the batch."""
        held = {} if fl is None else {slot: (req, n)
                                      for slot, req, n in fl.rows}
        for pf in self._prefills:
            held.update((row, (req, len(req.tokens)))
                        for req, row, _ in pf.live)
        rows = []
        for slot, req in self._active.items():
            if req._cursor is not None:
                return None
            n = len(req.tokens)
            if slot in held:
                if held[slot] != (req, n):
                    return None
                n += 1          # the token in flight comes first
            elif fl is not None:
                return None
            if n < req.max_new_tokens:
                rows.append((slot, req, n))
        if not rows:
            return None
        return tuple(rows)

    def _decode_attempt(self):  # holds: _step_lock
        """Dispatch what this round can: the step to commit, unless it
        is in flight already, and the step after it (and after the
        round's prefill groups), ahead of their fetches. Where that
        step cannot be built from what the device holds and none is in
        flight, the step is the host's to build: the round's prefill
        groups are committed first, for their tokens. A retry after a
        raise finds the first in ``_flight`` and does not dispatch it
        again. -> the step dispatched ahead, or None."""
        kind = fault_point("serving.step")
        if kind == "skip":
            raise _SkipStep("injected skip of one decode iteration")
        first = rows = None
        if self._flight is not None or self._prefills:
            rows = self._rows_ahead(self._flight)
        if rows is None and self._flight is None:
            self._land_prefills()
            if not self._active:
                return None
            first = self._flight = self._launch()
            rows = self._rows_ahead(first)
        if rows is None:
            return None
        ahead = self._launch(self._unfetched(), rows)
        self._prefills = [pf._replace(followed=True)
                          for pf in self._prefills]
        if first is not None:
            # two launches under one serving.decode: the first had
            # returned by the time the second's inputs were begun
            first.stamps.t_launched = ahead.stamps.t_dispatch
        return ahead

    def _note_qerr(self, qerr, rows: int):  # holds: _step_lock
        """Surface an int8 step's max-abs dequantization error: bump
        the quant write counters and ratchet the drift gauge (+ one
        run-log event per new high-water mark). No-op — and no device
        sync — for float pools (the steps return an exact 0.0)."""
        if self.kv_dtype != "int8" or qerr is None:
            return
        _monitor.stat_add("STAT_serving_kv_quant_writes")
        _monitor.stat_add("STAT_serving_kv_quant_rows", int(rows))
        e = float(qerr)
        if e > self._qerr_max:
            self._qerr_max = e
            if self._qerr_gauge is not None:
                self._qerr_gauge.set(e)
            if _runlog.enabled():
                _runlog.log_event("serving_kv_quant",
                                  max_abs_err=round(e, 6), rows=int(rows))

    def _decode(self) -> int:  # holds: _step_lock
        """One batched decode over every occupied slot, dispatched one
        ahead of its fetch: the step this round commits is in flight
        since the last round (or is dispatched now, from the host's
        state, when none is and the round's prefill groups do not carry
        one: a batch with a grammar row, an admission whose first token
        the host drew), the step after it and after the round's prefill
        groups is dispatched from their device outputs **before** the
        host waits for them (:meth:`_rows_ahead`), and fetch and commit
        then run under the device's time. Each step is dispatched once
        and each of its rows committed or dropped on its own
        (:meth:`_land`), so
        a request's state, KV rows or recurrent record, advances once a
        committed token. An injected skip dispatches and commits
        nothing: what is in flight stays there. Returns how many tokens
        were produced (0 when idle/skipped)."""
        if not self._active:
            return 0
        seq = self._dispatch_seq
        try:
            with _profiler.RecordEvent("serving.decode") as ev:
                ahead = RetryPolicy.from_flags("serving.step").call(
                    self._decode_attempt)
                # known only now, so kept in the in-process event alone
                ev.args = {"launches": self._dispatch_seq - seq}
        except (_SkipStep, _PoolsLost):
            return 0
        except RetryError as e:
            # the step itself is unrecoverable: shed the affected
            # requests, keep the engine alive for new submissions
            self._shed_active(e)
            self._flight = None
            self._prefills = []
            return 0
        if self._dispatch_seq > seq:
            # the span's close is the first reading after the round's
            # last launch returned
            last = self._flight if ahead is None else ahead
            last.stamps.t_launched = ev.t1
        fl, self._flight = self._flight, ahead
        produced = 0 if fl is None else self._land(fl)
        if not self._active:
            self._drain()    # nobody is left for the step in flight
        return produced

    def _land(self, fl: _Flight) -> int:  # holds: _step_lock
        """Fetch one dispatched step's tokens and commit them, row by
        row: a row stands iff its slot still holds the request it was
        computed for, at the count of tokens it was computed at. A row
        whose request finished at the step before (by EOS or a stop
        sequence, which only that step's commit could tell), or was
        cancelled, reaped or shed since, is dropped: its KV row or state
        update lies at or beyond the committed length of a slot that has
        been released, where nothing reads before a prefill rewrites it
        (the invariant that covers bucket padding, rejected drafts and
        the trash block). The other rows' tokens stand. Returns the
        tokens committed."""
        live = [(slot, req) for slot, req, n in fl.rows
                if self._active.get(slot) is req and len(req.tokens) == n]
        st = fl.stamps
        if fl.ahead:
            dropped = len(fl.rows) - len(live)
            self._ahead_rows_committed += len(live)
            self._ahead_rows_dropped += dropped
            _monitor.stat_add("STAT_serving_ahead_hits", len(live))
            _monitor.stat_add("STAT_serving_ahead_misses", dropped)
        if not live:
            return 0
        with _profiler.RecordEvent("serving.decode.fetch",
                                   {"flight": st.id}) as ev:
            try:
                nxt = np.asarray(fl.nxt)  # the host waits for the device
            except Exception as e:
                # the step failed on the device, after its dispatch had
                # bound the pools it returns: they went with it, and
                # with them whatever was dispatched behind it
                self._pools_lost(e)
                return 0
        st.t_fetch, st.t_fetched = ev.t0, ev.t1
        with _profiler.RecordEvent(
                "serving.decode.commit",
                {"tokens": len(live), "flight": st.id}) as ev:
            now = self._clock()       # the commit's one stamp
            self._note_qerr(fl.qerr, len(live))
            # the authoritative key is the request's (_writeback_keys)
            keys = np.asarray(fl.keys)
            for slot, req in live:
                req._key = keys[slot].copy()
                self.cache.advance(slot, 1)
                self._append_token(req, int(nxt[slot]), now)
            # the next step's tokens and keys, while the batch stands:
            # not if it holds a request this step did not decode for
            mine = dict(live)
            stands = all(mine.get(slot) is req
                         for slot, req in self._active.items())
            self._carry = (self._progress(), fl.nxt, fl.keys) \
                if stands else None
        st.t_committed = ev.t1
        # one step commits one token a row: its time is a TPOT sample
        self._landed(st, len(live), ahead=fl.ahead)
        return len(live)

    def _drain(self) -> int:  # holds: _step_lock
        """Fetch and commit the step in flight, if there is one, and
        dispatch nothing: before anything that rebinds or reads the
        pools outside the steps' order, or when the next step is not
        this path's to dispatch. Returns the tokens committed."""
        fl, self._flight = self._flight, None
        return 0 if fl is None else self._land(fl)

    def _decode_any(self) -> int:  # holds: _step_lock
        """Route one decode round: the draft-verify step when
        speculation is on, else the single step, which is dispatched
        one ahead of its fetch (:meth:`_decode`). Whatever runs is one
        ``serving.decode_step`` span, from building the step's tokens
        to its last commit; an idle engine records none."""
        if self.cache.pool.epoch != self._pool_epoch:
            # a co-located engine's failed step took the shared pool's
            # contents (see _call_paged): this engine's rows went too
            self._pool_epoch = self.cache.pool.epoch
            self._shed_active(_PoolsLost(
                "shared KV pool rebuilt by a co-located engine"))
        if not self._active:
            self._drain()
            return 0
        with _profiler.RecordEvent(
                "serving.decode_step",
                {"active": len(self._active), "n": self.spec_tokens + 1}):
            if self.spec_tokens:
                return self._spec_decode()
            return self._decode()

    # ------------------------------------------------- speculative decode
    def _verify_attempt(self, tokens: np.ndarray):
        kind = fault_point("serving.step")
        if kind == "skip":
            raise _SkipStep("injected skip of one verify iteration")
        fn = verify_step_paged(self.model, self.spec_tokens, self.mesh,
                               self.kv_dtype, self._lora_shape)["fn"]
        st = self._stamps()
        args = self._step_args(st, tokens)
        out = self._call_paged(fn, args, args[3])
        self._note_dispatch()
        return st, out

    def _spec_decode(self) -> int:  # holds: _step_lock
        """One speculative draft–verify step over every occupied slot:
        draft K tokens per slot from its own generated suffix, score
        all K+1 positions in one compiled forward, commit the accepted
        prefix (plus the model's one guaranteed next token) and roll
        the rejected tail's write offset back. Returns tokens produced
        (anywhere from len(active) to (K+1)*len(active))."""
        if not self._active:
            return 0
        K = self.spec_tokens
        tokens = np.zeros((self.max_slots, K + 1), np.int32)
        for slot, req in self._active.items():
            d = draft_ngram(req.prompt + req.tokens, K, self.spec_ngram)
            tokens[slot, 0] = req.tokens[-1]
            tokens[slot, 1:] = d
        n_active = len(self._active)
        try:
            with _profiler.RecordEvent("serving.verify") as ev:
                st, out = RetryPolicy.from_flags(
                    "serving.step").call(self._verify_attempt, tokens)
                ev.args = {"launches": 1}
        except (_SkipStep, _PoolsLost):
            return 0
        except RetryError as e:
            self._shed_active(e)
            return 0
        st.t_launched = ev.t1
        nxt, _, arrays, qerr, accept, new_keys = out
        with _profiler.RecordEvent("serving.decode.fetch",
                                   {"flight": st.id}) as fetch:
            nxt = np.asarray(nxt)
            accept = np.asarray(accept)
        st.t_fetch, st.t_fetched = fetch.t0, fetch.t1
        with _profiler.RecordEvent("serving.decode.commit",
                                   {"flight": st.id}) as commit:
            now = self._clock()          # the commit's one stamp
            self._note_qerr(qerr, (K + 1) * len(self._active))
            self.cache.set_arrays(arrays)
            self._writeback_keys(new_keys)
            produced = 0
            for slot, req in list(self._active.items()):
                # the verify wrote K+1 rows at this slot's offset;
                # commit them optimistically, then trim to what was
                # accepted
                self.cache.advance(slot, K + 1)
                committed = accepted = 0
                for i in range(K + 1):
                    tok = int(nxt[slot, i])
                    self._append_token(req, tok, now)
                    committed += 1
                    produced += 1
                    if req.state != "running":
                        break    # finished (EOS / budget) mid-verify
                    if i == K or not bool(accept[slot, i]):
                        break    # out of drafts / first rejection
                    accepted += 1
                self._spec_proposed += K
                self._spec_accepted += accepted
                _monitor.stat_add("STAT_serving_spec_proposed", K)
                _monitor.stat_add("STAT_serving_spec_accepted",
                                  accepted)
                if _runlog.enabled():
                    _runlog.log_event("serving_spec", request=req.id,
                                      proposed=K, accepted=accepted)
                if req.state == "running":
                    # reject the unaccepted tail: roll the write offset
                    # back so the next step overwrites those rows
                    self.cache.rollback(slot, K + 1 - committed)
            commit.args = {"tokens": produced, "flight": st.id}
            # the keys carry over; a tree of K+1 tokens is drafted anew
            self._carry = (self._progress(), None, new_keys)
        st.t_committed = commit.t1
        # per-output-token pace: the step's time on the device spread
        # over the tokens each slot actually committed this step
        self._landed(st, n_active, produced / n_active)
        return produced

    # -------------------------------------------------------- lifecycle
    def _append_token(self, req: Request, token: int, now: float):
        """Commit one token; ``now`` is the commit's one clock read."""
        req.tokens.append(token)
        if req.first_token_at is None:
            req.first_token_at = now
            # the mark reuses the stamp so the blame prefix up to
            # first_token equals the measured TTFT exactly
            _tracing.mark(req.id, "first_token", now, self.trace_track)
            # TTFT from inside, split at the admission: the prefill part
            # holds the wait behind a decode step in flight
            ttft = (now - req.submitted_at) * 1e3
            queue = (req.admitted_at - req.submitted_at) * 1e3
            a = self._account
            a["first_tokens"] += 1
            a["ttft_ms"] += ttft
            a["queue_wait_ms"] += queue
            _profiler.record_span(
                "serving.ttft", req.submitted_at, now - req.submitted_at,
                {"request": req.id, "queue_ms": queue,
                 "prefill_ms": ttft - queue})
        elif req.token_at:
            # the program's own inter-token time, on the clock of every
            # other span (nothing is recorded with the profiler off);
            # tokens that share a commit are 0 apart
            _profiler.record_span("serving.token_gap", req.token_at[-1],
                                  now - req.token_at[-1],
                                  {"request": req.id})
        req.token_at.append(now)
        _monitor.stat_add("STAT_serving_tokens")
        if req._stop is not None:
            # advance the incremental matcher over the committed token
            # (O(1) amortized); _hit_stop below just reads the latch
            req._stop.feed(token)
        if req._cursor is not None:
            # advance the grammar pushdown over the committed token;
            # a structurally-complete document retires the request
            # (the budget-aware mask guarantees this lands in time)
            req._cursor.advance(token)
            if req._cursor.at_end:
                self._finish(req)
                return
        if (req.eos_token_id is not None and
                token == req.eos_token_id) or \
                len(req.tokens) >= req.max_new_tokens or \
                self._hit_stop(req):
            self._finish(req)

    def _hit_stop(self, req: Request) -> bool:
        """Host-side stop-sequence check; the matched stop tokens stay
        in the output (OpenAI-style truncation is the caller's choice —
        the engine reports what it committed). Reads the request's
        incremental KMP matcher (fed per committed token in
        :meth:`_append_token`): O(1) per check, where the old
        full-suffix rescan was O(len^2) over a request's lifetime.
        ``state == len(pattern)`` in the automaton holds exactly when
        the pattern is a suffix of the generated tokens, so the verdict
        is identical token for token."""
        return req._stop is not None and req._stop.hit

    def _finish(self, req: Request):  # holds: _step_lock
        if req.slot is not None:
            self._active.pop(req.slot, None)
            if req.session is not None and self.kv_tier is not None:
                # publish the finished conversation's full blocks into
                # the prefix cache before the row's refs drop: the
                # between-steps sweep demotes the now-cold chain to
                # host RAM, and the next turn resumes off it
                self.cache.insert_prefix(req.slot, req.context)
            self.cache.release_row(req.slot)
            req.slot = None
        if req._lora_held:
            self.lora_pool.release(req.tenant)
            req._lora_held = False
        req.state = "done"
        req.finished_at = self._clock()
        ttft, tpot = req.ttft, req.tpot
        if ttft is not None:
            self._ttft_hist.observe(ttft)
        if tpot is not None:
            self._tpot_hist.observe(tpot)
        met = req.deadline_met
        with self._lock:
            self._completed += 1
            if met:
                self._slo_met += 1
            completed, slo_met = self._completed, self._slo_met
            # [completed, slo-eligible, slo-met]: attainment only
            # counts requests that carried a TTFT deadline
            ts = self._tenant_stats.setdefault(req.tenant or "base",
                                               [0, 0, 0])
            ts[0] += 1
            if met is not None:
                ts[1] += 1
                if met:
                    ts[2] += 1
        if self._slo_gauge is not None and completed:
            self._slo_gauge.set(slo_met / completed)
        _monitor.stat_add("STAT_serving_completed")
        _runlog.log_event(
            "serving_finish", request=req.id, tokens=len(req.tokens),
            ttft_ms=None if ttft is None else round(ttft * 1e3, 3),
            tpot_ms=None if tpot is None else round(tpot * 1e3, 3),
            deadline_met=met)
        if req.session is not None and self.kv_tier is not None:
            self.kv_tier.session_save(req.session, req.context)
            if req._session_counted:
                req._session_counted = False
                self.kv_tier.session_released(req.session)
        _tracing.finish(req.id, req.finished_at, self.trace_track,
                        "done")
        req._done.set()

    def _shed(self, req: Request, err: BaseException,
              reason: str = "fault"):
        if req._lora_held:
            self.lora_pool.release(req.tenant)
            req._lora_held = False
        req.slot = None
        req.state = "shed"
        req.error = err
        req.shed_reason = reason
        req.finished_at = self._clock()
        _monitor.stat_add("STAT_serving_shed")
        self._count_shed(reason, req.priority)
        _runlog.log_event("serving_shed", request=req.id,
                          reason=reason, priority=req.priority,
                          error=str(err))
        if req._session_counted and self.kv_tier is not None:
            req._session_counted = False
            self.kv_tier.session_released(req.session)
        _tracing.finish(req.id, req.finished_at, self.trace_track,
                        "shed", reason=reason)
        req._done.set()

    # ------------------------------------------------------ cancellation
    def cancel(self, rid: int, reason: str = "client",
               _finalize: bool = True) -> Optional[dict]:
        """Terminate request ``rid`` at whatever stage it has reached —
        queued or in a slot (mid-prefill-wave / mid-decode) — releasing
        its KV row and LoRA pin. Pure host-side queue/slot surgery: no
        compiled surface is touched (``predict_serving_compiles(
        cancel=N)`` is a validated no-op). Returns ``{"id", "stage",
        "reason"}`` on success, None for unknown or already-terminal
        requests (idempotent: double-cancel is a no-op, not a
        double-release).

        ``_finalize=False`` is the router-internal detached mode for a
        hedge primary whose clone won: resources are reclaimed and the
        cancel is accounted, but the caller-visible handle is left
        open so the winner's tokens can be mirrored onto it before
        ``_done`` fires."""
        rid = int(rid)
        with self._lock:
            req = next((r for r in self._all if r.id == rid), None)
        if req is None or req.state in ("done", "shed", "canceled"):
            return None
        return self._cancel_request(req, reason, _finalize=_finalize)

    def _cancel_request(self, req: Request, reason: str,
                        _finalize: bool = True) -> Optional[dict]:
        """Stage-dispatch half of :meth:`cancel`: pull the request out
        of the queue (stage ``queued``) or its slot (stage ``prefill``
        before the first token, ``decode`` after), then discharge."""
        stage = None
        with self._lock:
            try:
                self._queue.remove(req)
                stage = "queued"
            except ValueError:
                pass       # not queued (admitted, or mid-admission)
        if stage is None:
            with self._step_lock:
                slot = req.slot
                if slot is not None and self._active.get(slot) is req:
                    del self._active[slot]
                    self.cache.release_row(slot)
                    req.slot = None
                    stage = ("decode" if req.first_token_at is not None
                             else "prefill")
        if stage is None:
            # terminal already, or inside the admission instant of a
            # concurrent step (it will run to completion normally) —
            # nothing is held here, so there is nothing to reclaim
            return None
        self._finalize_cancel(req, stage, reason, _finalize)
        return {"id": req.id, "stage": stage, "reason": reason}

    def _finalize_cancel(self, req: Request, stage: str, reason: str,
                         finalize: bool = True):
        """Discharge a canceled request's remaining holds and account
        the cancel. The KV row was already released by the caller (the
        stage-specific surgery); this releases the LoRA pin, bumps the
        counters/trace/run-log, and (unless detached) flips the handle
        terminal. Safe under ``_step_lock`` — takes ``_lock`` in the
        same step_lock -> lock order ``_finish`` established."""
        if req._lora_held:
            self.lora_pool.release(req.tenant)
            req._lora_held = False
        with self._lock:
            self._canceled_by_reason[reason] = \
                self._canceled_by_reason.get(reason, 0) + 1
        self._cancel_ctr.labels(engine=self._eid, reason=reason).inc()
        _monitor.stat_add("STAT_serving_canceled")
        now = self._clock()
        _runlog.log_event("serving_cancel", request=req.id,
                          stage=stage, reason=reason,
                          tokens=len(req.tokens))
        _tracing.mark(req.id, "cancel", now, self.trace_track)
        _tracing.finish(req.id, now, self.trace_track, "canceled",
                        reason=reason)
        if req._session_counted and self.kv_tier is not None:
            req._session_counted = False
            self.kv_tier.session_released(req.session)
        if finalize:
            req.state = "canceled"
            req.shed_reason = reason
            req.finished_at = now
            req._done.set()

    def _reap_expired(self) -> int:  # holds: _step_lock
        """Between-steps hard-deadline sweep: cancel every active slot
        whose request's ``hard_deadline`` has passed — expired work is
        canceled-not-completed, so a dead client never burns a decode
        slot past its patience. Runs before admission so the freed
        slots are reusable in the same step. Returns cancels."""
        now = self._clock()
        n = 0
        for slot, req in list(self._active.items()):
            hd = req.hard_deadline
            if hd is not None and now > hd:
                del self._active[slot]
                self.cache.release_row(slot)
                req.slot = None
                stage = ("decode" if req.first_token_at is not None
                         else "prefill")
                self._finalize_cancel(req, stage, "deadline")
                n += 1
        return n

    # --------------------------------------------------------- stepping
    def step(self) -> bool:
        """One scheduler iteration: admit into free slots (batched
        per-bucket prefill), then one batched decode — or, with
        speculation on, one draft–verify multi-token step. Returns
        whether any work happened."""
        with self._step_lock:
            self._step_no += 1
            with _profiler.RecordEvent(
                    "serving.engine_step",
                    {"step": self._step_no, "active": len(self._active),
                     "queued": len(self._queue)}) as ev:
                # hard-deadline sweep first: a request that expired
                # since the last step is canceled within one step and
                # its slot is free for this step's admissions
                reaped = self._reap_expired()
                admitted = self._admit(commit=False)
                produced = self._decode_any()
                # the round's prefill groups, fetched under the time of
                # the decode step dispatched behind them
                self._land_prefills()
                if self.kv_tier is not None:
                    self._demote_sweep()
                self._blocks_used_g.set(self.cache.blocks_used)
                self._blocks_free_g.set(self.cache.blocks_free)
                worked = bool(admitted or produced or reaped)
            if worked:
                # a round's time by the span's own readings; a stall of
                # seconds is a count and a sum in the interval it fell in
                a, ms = self._account, (ev.t1 - ev.t0) / 1e6
                a["rounds"] += 1
                a["round_ms"] += ms
                if ms > 100.0:
                    a["rounds_over_100ms"] += 1
                    a["stalled_ms"] += ms
                if ms > 1000.0:
                    a["rounds_over_1s"] += 1
            return worked

    def _demote_sweep(self):  # holds: _step_lock
        """Between-steps host-tier demotion: prefix entries that have
        sat cold (refcount 1 — no live request, no resident child pin)
        across a full FLAGS_serving_demote_idle_ms window move to the
        host store; 0 demotes cold entries at every step. Runs after
        the decode dispatch so the copies drain while the device
        crunches the next batch — demotion never blocks a decode."""
        pool = self.cache.pool
        idle_ms = self.kv_tier.demote_idle_ms
        eligible = None
        if idle_ms > 0:
            now = self._clock()
            cold = {k for k, e in pool._prefix.items()
                    if pool.allocator.refcount[e.block] == 1}
            for k in list(self._cold_since):
                if k not in cold:
                    del self._cold_since[k]
            for k in cold:
                self._cold_since.setdefault(k, now)
            eligible = {k for k, t0 in self._cold_since.items()
                        if (now - t0) * 1e3 >= idle_ms}
            if not eligible:
                return
        elif not any(pool.allocator.refcount[e.block] == 1
                     for e in pool._prefix.values()):
            return      # nothing is cold: nothing to copy out
        # the copies read the pools outside the steps' order
        self._drain()
        entries, _blocks = self.kv_tier.demote(self.cache,
                                               keys=eligible)
        if entries and eligible is not None:
            for k in eligible:
                self._cold_since.pop(k, None)

    def stats(self) -> dict:
        """Per-engine serving metrics: time-to-first-token and
        time-per-output-token percentiles of completed requests, plus
        the speculative acceptance counters. Percentiles come from this
        engine's fixed-bucket Histogram series in the observability
        plane (constant memory — no raw-sample window); None until
        observations exist. The HTTP front end merges this into
        ``GET /v1/stats``.

        **The step account** (``_ACCOUNT_KEYS``) is kept whether the
        profiler is on or off, from the readings the step's spans take
        anyway (:class:`_Stamps`, :meth:`_landed`): counts, and sums in
        ms that only grow, so the difference between two calls is what
        happened between them. A flight is one dispatch that was
        committed (a decode step, single or verify; a prefill group).

        - ``decode_flights``; ``decode_host_ms``: the host's own work on
          them, dispatch to launched plus fetched to committed;
          ``decode_wait_ms``: the host blocked in their fetch;
          ``decode_device_ms``: the device's time for them as the host
          can bound it, each step from the later of its launch and the
          fetch before it (of either kind) to its own fetch's return.
          That is exact while the host waits for every step
          (``decode_wait_ms`` a step well above 0); a step whose tokens
          were there when the host came, after a prefill dispatched
          behind it was fetched first or on a host slower than the
          device, adds its bound, nearly 0, so the mean a step is then a
          floor.
        - ``prefill_flights``, ``prefill_host_ms``, ``prefill_wait_ms``:
          the same for prefill groups. A group's fetch comes after the
          fetch of the decode step that was in flight ahead of it, so
          its wait is the prompt's own time on the device (a group
          fetched at once, for a first token the host draws, waits for
          that step too); ``admit_ahead_dispatches`` of them were
          fetched with the decode step behind them on the device.
        - ``rounds``: calls of :meth:`step` that did work; ``round_ms``
          their time; ``rounds_over_100ms`` / ``rounds_over_1s`` those
          that took longer, and ``stalled_ms`` the time of the rounds
          over 100 ms (where a prefill group takes that long, its rounds
          are among them).
        - ``first_tokens``: requests that got their first token;
          ``ttft_ms`` their submission-to-first-token time and
          ``queue_wait_ms`` the part of it before admission, on the
          engine's clock (``ttft_p50_ms`` above counts a request when it
          completes, these when the token lands).
        - ``kv_blocks_live``: the table entries the committed decode
          steps' rows stood on, ``ceil(length / block_size)`` summed over
          the rows at each commit; ``kv_blocks_table``: the entries of
          the whole table, ``slots x T`` a step. Their ratio is the share
          of the table a decode step's attention had to read (the paged
          kernel copies the live blocks and no others; a dead slot costs
          it one block, which this leaves out).

        **The program account** is the PROCESS's and not this engine's
        alone (``observability.compile_totals()``: every ``tracked_jit``
        site and what was built outside them), sums that only grow:
        ``programs_built`` (executables compiled or retrieved),
        ``programs_trace_ms``, ``programs_lower_ms``,
        ``programs_compile_ms`` (the backend's compilation or the
        persistent cache's retrieval), ``programs_cache_hits`` and
        ``programs_cache_misses`` (compiled and written: a warm replica
        reads 0). It moves only while a program is traced or built."""
        def pct(hist, q):
            v = hist.quantile(q)
            return None if v is None else round(v * 1e3, 3)

        # scheduler-owned state is snapshotted under the step lock: a
        # scrape racing step() used to read _active/_spec_*/_qerr_max/
        # _prefix_*_reqs bare and could see a half-updated round (e.g.
        # spec_accepted bumped, spec_proposed not yet). The two locks
        # are taken sequentially, never nested, so no order edge.
        with self._step_lock:
            active = len(self._active)
            spec_proposed = self._spec_proposed
            spec_accepted = self._spec_accepted
            qerr_max = self._qerr_max
            prefix_hit_reqs = self._prefix_hit_reqs
            prefix_miss_reqs = self._prefix_miss_reqs
            ahead_dispatches = self._ahead_dispatches
            admit_ahead_dispatches = self._admit_ahead_dispatches
            ahead_rows_committed = self._ahead_rows_committed
            ahead_rows_dropped = self._ahead_rows_dropped
            pool_dispatches = self._pool_dispatches
            pool_inplace = self._pool_inplace
            sampler_dispatches = self._sampler_dispatches
            sampler_skipped = self._sampler_skipped
            inputs_resident = self._inputs_resident
            prefill_rows_live = self._prefill_rows_live
            prefill_rows_computed = self._prefill_rows_computed
            prefill_tokens_live = self._prefill_tokens_live
            prefill_tokens_computed = self._prefill_tokens_computed
            prompt_counted = dict(self._prompt_counted)
            account = dict(self._account)
        with self._lock:
            completed = self._completed
            slo_met = self._slo_met
            shed = dict(self._shed_by_reason)
            canceled = dict(self._canceled_by_reason)
            queued = len(self._queue)
            tenants = {k: list(v) for k, v in self._tenant_stats.items()}
        out = {
            "ttft_p50_ms": pct(self._ttft_hist, 0.50),
            "ttft_p99_ms": pct(self._ttft_hist, 0.99),
            "tpot_p50_ms": pct(self._tpot_hist, 0.50),
            "tpot_p99_ms": pct(self._tpot_hist, 0.99),
            "latency_samples": completed,
            "spec_tokens": self.spec_tokens,
            "completed": completed,
            "queue_depth": queued,
            "active": active,
            # per-reason sheds incl. submit-time rejections — the
            # stats() view of serving_shed_total{reason=,priority=}
            "shed": shed,
            "shed_total": sum(shed.values()),
            # per-reason cancels — the stats() view of
            # serving_canceled_total{reason=}; the fourth term of
            # completed + rehomed + shed + canceled == offered
            "canceled": canceled,
            "canceled_total": sum(canceled.values()),
        }
        if self.slo_ttft_ms:
            out["slo_ttft_ms"] = self.slo_ttft_ms
            out["slo_met"] = slo_met
            out["slo_attainment"] = (round(slo_met / completed, 4)
                                     if completed else None)
            out["predicted_ttft_ms"] = round(
                self.predict_ttft_ms(), 3)
        if self.spec_tokens:
            out["spec_proposed"] = spec_proposed
            out["spec_accepted"] = spec_accepted
            out["spec_acceptance_rate"] = (
                round(spec_accepted / spec_proposed, 4)
                if spec_proposed else None)
        # single decode steps dispatched before the step before them was
        # fetched (of ``sampler_dispatches`` when nothing else decodes),
        # and their rows: committed, or computed for a request that had
        # finished or left by then
        out["ahead_dispatches"] = ahead_dispatches
        # prefill groups (of ``prefill_flights``) whose first tokens were
        # fetched after the decode step behind them was dispatched
        out["admit_ahead_dispatches"] = admit_ahead_dispatches
        out["ahead_rows_committed"] = ahead_rows_committed
        out["ahead_rows_dropped"] = ahead_rows_dropped
        # decode/verify dispatches, and those whose batch was all
        # greedy (the step skipped the sampler on the device)
        out["sampler_dispatches"] = sampler_dispatches
        out["sampler_skipped"] = sampler_skipped
        # the same dispatches, and those whose operands were all
        # resident: nothing copied to the device but the step's own
        # tokens / lengths
        out["inputs_dispatches"] = sampler_dispatches
        out["inputs_resident"] = inputs_resident
        # rows the prefill dispatches computed, and those of them that
        # were a prompt's (the rest was padding up to the bucket's rows)
        out["prefill_rows_computed"] = prefill_rows_computed
        out["prefill_rows_live"] = prefill_rows_live
        # the positions those dispatches computed (rows x bucket), and
        # those of them that were a prompt's tokens
        out["prefill_tokens_computed"] = prefill_tokens_computed
        out["prefill_tokens_live"] = prefill_tokens_live
        # what the model counts of those dispatches (the seam's
        # ``prompt_counts``: none for most models)
        out.update(prompt_counted)
        # the step account (_ACCOUNT_KEYS; the docstring says what each is)
        out.update(account)
        # the program account, the process's
        built = _ct.compile_totals()
        out["programs_built"] = built["programs"]
        out.update({f"programs_{stage}": built[stage] for stage in (
            "trace_ms", "lower_ms", "compile_ms", "cache_hits",
            "cache_misses")})
        out["kv_dtype"] = self.kv_dtype
        out["mesh_shape"] = (None if self.mesh_shape is None
                             else list(self.mesh_shape))
        if self.kv_dtype == "int8":
            out["kv_quant_max_abs_err"] = round(qerr_max, 6)
        if tenants:
            # per-tenant completion + SLO attainment ("base" = no-LoRA
            # traffic); the router sums these across replicas
            out["tenants"] = {
                name: {"completed": c,
                       "slo_met": m,
                       "slo_attainment": (round(m / e, 4) if e
                                          else None)}
                for name, (c, e, m) in sorted(tenants.items())}
        if self.lora_pool is not None:
            out["lora"] = {
                "rank": self.lora_pool.rank,
                "max_adapters": self.lora_pool.max_adapters,
                "loaded": self.lora_pool.loaded,
                "leaked_pages": self.lora_pool.leaked(),
            }
        if self.grammar is not None:
            out["json_grammar"] = True
        if self.kv_tier is not None:
            # fleet-shared numbers when the tier is shared: every
            # attached engine reports the same store/session totals
            out["kv_tier"] = self.kv_tier.stats()
        c = self.cache
        # blocks held now by layer kind, and the blocks window layers
        # returned behind their windows while their request lived
        out.update(c.kind_stats())
        # recurrent state beside the blocks: the bytes the cache holds
        # for it (0 for a model with none) and the rows that are a
        # request's now
        out["state_bytes"] = c.state_bytes
        out["state_rows_live"] = c.state_rows_live
        # what a token keeps beside K and V (``seam.CacheKind.extra``),
        # by name: a model with none leaves these out
        out.update({f"{name}_bytes": n
                    for name, n in c.pool.extra_bytes.items()})
        if self.spec.counters:
            # the model's counters, kept on the device by its steps and
            # fetched here only (the steps' own fetch is their tokens)
            with self._step_lock:
                values = np.asarray(self._counted)
            out.update({name: float(v) for name, v
                        in zip(self.spec.counters, values)})
        hit_t, miss_t = c.prefix_hits, c.prefix_misses
        out.update({
            "block_size": c.block_size,
            "num_blocks": c.num_blocks,
            "kv_blocks_used": c.blocks_used,
            "kv_blocks_free": c.blocks_free,
            "prefix_cache": c.prefix_cache_enabled,
            "prefix_entries": c.prefix_entries,
            # request-granular (an admission that reused >=1 block
            # is a hit) and token-granular (prompt tokens whose KV
            # came from the cache vs were prefilled)
            "prefix_hit_requests": prefix_hit_reqs,
            "prefix_miss_requests": prefix_miss_reqs,
            "prefix_hit_tokens": hit_t,
            "prefix_miss_tokens": miss_t,
            "prefix_hit_rate": (round(hit_t / (hit_t + miss_t), 4)
                                if hit_t + miss_t else None),
            # paged dispatches, and the share of them that wrote
            # their KV rows in place (the pools handed in were
            # deleted by the call): 1.0 unless the backend ignores
            # donation
            "pool_dispatches": pool_dispatches,
            "pool_inplace": pool_inplace,
            "pool_inplace_share": (
                round(pool_inplace / pool_dispatches, 4)
                if pool_dispatches else None),
        })
        return out

    @property
    def idle(self) -> bool:
        with self._lock:
            queued = bool(self._queue)
        return not queued and not self._active

    def run_until_idle(self, max_steps: int = 10_000):
        """Drive the scheduler inline until queue and slots drain
        (the deterministic test/benchmark path — no thread)."""
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"serving engine not idle after {max_steps} steps "
                    f"({len(self._active)} active, "
                    f"{len(self._queue)} queued)")
        return steps

    def results(self, reqs: Optional[Sequence[Request]] = None,
                timeout: Optional[float] = None) -> List[Request]:
        """Wait for the given requests (default: every request ever
        submitted) and return them in submission order."""
        with self._lock:
            reqs = list(self._all) if reqs is None else list(reqs)
        for r in reqs:
            if not r.wait(timeout):
                raise TimeoutError(
                    f"request {r.id} not finished within {timeout}s")
        return reqs

    # ------------------------------------------------- background thread
    def start(self):
        """Run the scheduler on a daemon thread (the HTTP deployment
        mode); idle waits are bounded by FLAGS_serving_idle_wait."""
        if self._thread is not None:
            return
        self._stop_evt.clear()

        def _loop():
            while not self._stop_evt.is_set():
                if not self.step():
                    self._wake.wait(self.idle_wait)
                    self._wake.clear()

        self._thread = threading.Thread(target=_loop, daemon=True,
                                        name="serving-engine")
        self._thread.start()

    def stop(self):
        self._stop_evt.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        with self._step_lock:
            self._drain()   # what the device was given is committed
