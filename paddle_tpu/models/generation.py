"""Decoding utilities: greedy / sampling / beam search for causal LMs.

Capability analog of the reference's beam-search machinery
(operators/beam_search_op.cc, beam_search_decode_op.cc and fluid
layers/rnn.py BeamSearchDecoder) — redesigned without LoD: the beam is a
dense [batch*beam] axis, KV caches ride along it, and each step is
ordinary top-k over [batch, beam*vocab] scores.

Decoding runs on a **fixed-capacity padded KV cache** (the model's
``cache_pos`` path): every per-step call has ONE shape —
``tokens [b], positions [b], cache [b, h, capacity, d]`` — so the
jitted step function compiles exactly once and serves every step of
every request at that shape. The old concat-cache loop grew the key
axis each step, forcing an XLA recompile per generated token.
``decode_step(model)`` exposes the per-model compiled step (and its
trace counter, asserted ==1 in tests); it is the oracle
``paddle_tpu.serving`` is compared against. The engine's own steps
(``decode_step_paged`` and friends) run the same forward through
per-row block tables into a shared block pool.

``verify_step_paged(model, k)`` is the speculative-decoding step: one
fixed-shape forward scores K+1 positions (the last committed token
plus K drafts from ``draft_ngram``), so a serving step can commit up
to K+1 tokens while staying on a single compiled executable.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..dygraph.tape import no_grad
from ..dygraph.tensor import Tensor

_TRACE_LOCK_GUARD = threading.Lock()


def model_trace_lock(model) -> threading.RLock:
    """The per-model lock every step trace and parameter read/write
    shares. :func:`_borrowed_params` assigns *tracers* into the eager
    Parameters for the duration of a trace — a mutation of shared model
    state. When router replicas step from a thread pool
    (``FLAGS_serving_dispatch_threads``), a peer reading
    :func:`param_leaves` (or ``swap_weights`` writing) mid-trace would
    see those tracers leak out of their trace (UnexpectedTracerError)
    or, worse, have its swap silently undone by the borrow's restore.
    Traces hold this lock for their whole borrow window; dispatches
    only hold it for the instantaneous param snapshot, so compiled
    steps on sibling replicas still overlap. Reentrant because nested
    borrows happen inside one trace (prefill tracing the shared
    sampler)."""
    lk = getattr(model, "_step_trace_lock", None)
    if lk is None:
        with _TRACE_LOCK_GUARD:
            lk = getattr(model, "_step_trace_lock", None)
            if lk is None:
                lk = model._step_trace_lock = threading.RLock()
    return lk


def _t(x, dtype=jnp.int32):
    return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x, dtype),
                                                  stop_gradient=True)


def param_leaves(model):
    """Current parameter arrays of ``model`` in ``named_parameters()``
    order — the explicit leading jit input of every compiled step.

    Weights used to be closed over as trace-time constants; threading
    them as inputs instead is what makes a live
    ``ServingEngine.swap_weights`` visible to already-compiled
    executables: same abstract shape/dtype/sharding signature, so the
    step cache entry (and its compile count) is untouched.
    """
    return [p.value for _, p in model.named_parameters()]


@contextmanager
def _borrowed_params(model, values):
    """Assign (traced) arrays into the eager Parameters for the duration
    of a trace, restoring the concrete values after — the same
    restore-on-exit contract ``jit.to_static`` keeps for its state spec,
    so a mid-trace raise never leaves the model holding dead tracers.

    Holds :func:`model_trace_lock` for the whole borrow window: while
    the Parameters carry tracers, no other thread may snapshot
    (:func:`param_leaves`) or swap them."""
    with model_trace_lock(model):
        params = [p for _, p in model.named_parameters()]
        saved = [p.value for p in params]
        try:
            for p, v in zip(params, values):
                p.value = v
            yield
        finally:
            for p, v in zip(params, saved):
                p.value = v


def _inject_params(model, raw):
    """Wrap a compiled step so callers keep the param-free signature:
    the wrapper prepends the model's *current* parameter arrays on every
    call (post-swap weights ride in as data, not as constants).

    The ``Parameter`` objects are listed once, when the entry is built
    (the walk of ``named_parameters()`` is what a call used to pay for:
    290 leaves of a 24-layer model); a call reads each one's ``value``
    as it stands, so a ``swap_weights``, a ``set_value`` or a
    ``set_state_dict`` is seen by the next call with nothing to
    invalidate. What the list assumes, the entry assumed already: its
    trace borrowed these objects and its mesh ``in_shardings`` were
    fitted to them, so a model that gains or replaces a ``Parameter``
    object needs a new entry either way.

    The snapshot happens under :func:`model_trace_lock` so it can never
    observe a sibling thread's mid-trace borrowed tracers; the compiled
    call itself runs outside the lock (a first-call trace re-enters it
    through ``_borrowed_params``), keeping threaded replica dispatch
    concurrent."""
    params = [p for _, p in model.named_parameters()]
    lock = model_trace_lock(model)

    def fn(*args):
        with lock:
            values = [p.value for p in params]
        return raw(values, *args)
    fn.traces = raw.traces
    # the tracked jit itself, for reading the compiled program:
    # fn.raw.lower(param_leaves(model), *args).compile().as_text()
    fn.raw = raw
    return fn


def _mesh_param_shardings(model, mesh):
    """NamedSharding per ``named_parameters()`` entry under the serving
    mesh — the same ``SERVING_TP_RULES`` fit ``_place_on_mesh`` used to
    put the params there, so the jit in_shardings always agree with the
    resident layout and a swap's ``device_put`` keeps them."""
    from jax.sharding import NamedSharding
    from ..distributed.sharding import SERVING_TP_RULES
    return [NamedSharding(mesh, SERVING_TP_RULES.spec_for(
                name, p.value.shape, mesh))
            for name, p in model.named_parameters()]


def _kernel_layout(model, mesh):
    """The ``kernel_sharding`` context a paged step traces its model
    call under: on a serving mesh the Pallas kernels run per chip on
    that chip's heads (the pools' ``"model"`` axis, with the same
    divisibility fallback as :func:`_mesh_step_shardings`); without a
    mesh it changes nothing."""
    from ..ops.pallas.utils import kernel_sharding
    if mesh is None:
        return kernel_sharding(None)
    return kernel_sharding(mesh, heads=_heads_axis(model, mesh))


def _heads_axis(model, mesh):
    """``"model"`` when the mesh's model axis divides the head count,
    else None (heads whole on every chip)."""
    from ..serving.seam import served
    heads = served(model).cache_kinds[0].kv_heads
    return "model" if heads % mesh.shape["model"] == 0 else None


def step_entry(model, key, build):
    """The one compile cache for every per-model step executable.

    Serving and decoding used to keep three ad-hoc caches
    (``_prefill_entry*`` dicts on the engine, ``decode_step*`` /
    ``verify_step*`` attributes here); they are unified behind this
    single ``model._step_compile_cache`` dict so a cache entry's
    identity is its full key — (step kind, geometry, bucket/K,
    kv_dtype, mesh) — and "exactly one compile per key" is
    one invariant instead of three. ``build()`` makes the entry (a dict
    with at least ``fn``/``traces``); entries are validated against the
    flag-plane version, so ``set_flags`` invalidates every step at once
    (same contract the recompile predictor models).
    """
    from .. import flags as _flags
    # under the model trace lock: two threaded replicas missing the
    # cache at once would otherwise both build (and later both trace)
    # the same entry, breaking the one-compile-per-key contract
    with model_trace_lock(model):
        cache = getattr(model, "_step_compile_cache", None)
        if cache is None:
            cache = model._step_compile_cache = {}
        ent = cache.get(key)
        if ent is not None and ent["flags_version"] == _flags.version():
            return ent
        ent = build()
        ent.setdefault("flags_version", _flags.version())
        cache[key] = ent
        return ent


def _mesh_step_shardings(model, mesh, kv_dtype: str):
    """(replicated, per-layer pool shardings) for a paged step under
    ``mesh``. Pools shard the heads axis on ``"model"`` (replicated
    fallback when the head count doesn't divide, mirroring
    ``distributed.sharding.kv_pool_shardings`` so jit shardings always
    agree with the engine's ``device_put`` placement); everything else
    — tokens, positions, block tables, logits, qerr — is replicated
    host-visible state."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ax = _heads_axis(model, mesh)
    repl = NamedSharding(mesh, P())
    pool = NamedSharding(mesh, P(None, ax, None, None))
    scale = NamedSharding(mesh, P(None, ax))
    layer = ((pool, pool, scale, scale) if kv_dtype == "int8"
             else (pool, pool))
    from ..serving.seam import served
    n_layers = served(model).num_layers
    return repl, [layer for _ in range(n_layers)]


def decode_step(model):
    """The per-model compiled decode step for fixed-capacity caches.

    Returns ``{"fn": jitted, "traces": {"count": n}}`` where ``fn`` maps
    ``(tokens [b] i32, pos [b] i32, caches [(k, v) arrays], samp)`` to
    ``(next_tokens [b] i32, last_logits [b, V], new_caches,
    new_keys [b, 2] u32)``: it writes each row's token at that row's
    cache offset, attends under the position mask, and picks each row's
    next token through the shared ``serving.decoding`` sampler. ``samp``
    is the per-row sampling-as-data tuple ``(temperature, top_k, top_p,
    keys, mask)`` — plain fixed-shape inputs, never compile keys, so
    greedy, sampled and mask-constrained rows share this one executable
    in the same batch (``decoding.neutral_samp`` rows reproduce the
    pre-sampling argmax bit-for-bit). ``traces["count"]`` increments
    once per XLA trace — the compile-count==1 contract is asserted in
    tests.

    Cached in the unified :func:`step_entry` cache, keyed by the
    flag-plane version so a ``set_flags`` retraces (same contract as
    jit.to_static). Parameters thread through as explicit jit inputs
    (injected by the wrapper from the model's live values), so a
    ``swap_weights`` takes effect without a retrace.
    """
    from ..observability import compile_tracker as _ct
    from ..serving.decoding import sample_tokens

    def _build():
        def _step(params, tokens, pos, caches, samp):
            with no_grad(), _borrowed_params(model, params):
                tcaches = [(Tensor(k, stop_gradient=True),
                            Tensor(v, stop_gradient=True))
                           for k, v in caches]
                logits, newc = model(_t(tokens[:, None]), cache=tcaches,
                                     cache_pos=pos)
            lg = logits.value[:, -1]
            nxt, new_keys = sample_tokens(lg, samp)
            return (nxt, lg, [(c[0].value, c[1].value) for c in newc],
                    new_keys)

        fn = _inject_params(model, _ct.tracked_jit("decode_step", _step))
        return {"fn": fn, "traces": fn.traces}

    return step_entry(model, ("decode",), _build)


#: Every paged step entry takes ``pools`` as argument 4 of its jitted
#: function (after params, two per-row inputs and the tables) and owns
#: it: the caller's arrays are deleted by the call and the returned
#: pools take their place. With the in-place writes of
#: ``ops.attention_ops.block_scatter_write`` (row updates at 64 rows and
#: under, one kernel over the touched chunks above: both in the layout
#: the pool arrived in) that is what keeps a decode step AND a prompt
#: from copying every pool; a caller that still needs the old pools
#: (nobody does on the serving path) copies them first.
POOLS_DONATED = {"donate_argnums": (4,)}


def _wrap_pools(pools):
    """Lift raw per-layer pool tuples into Tensors, generically over
    the tuple width: (k, v) float pools or (k, v, k_scale, v_scale)
    int8 pools — the attention layer dispatches on the width."""
    return [tuple(Tensor(a, stop_gradient=True) for a in layer)
            for layer in pools]


def _unwrap_pools(newp):
    """Strip Tensors from a forward's returned caches and split off
    the quantization-error scalar int8 layers append (5th element):
    returns ``(pools, max_qerr)`` with ``max_qerr`` the max over
    layers (exact 0.0 for float pools, so the step's return structure
    is identical across KV dtypes)."""
    qerr = jnp.zeros((), jnp.float32)
    pools = []
    for layer in newp:
        vals = [t.value for t in layer]
        if len(vals) == 5:
            qerr = jnp.maximum(qerr, vals[4])
            vals = vals[:4]
        pools.append(tuple(vals))
    return pools, qerr


def decode_step_paged(model, mesh=None, kv_dtype: str = "f32",
                      lora_shape=None, counters: bool = False):
    """The block-paged sibling of :func:`decode_step`.

    Returns ``{"fn": jitted, "traces": {"count": n}}`` where ``fn``
    maps ``(tokens [b] i32, pos [b] i32, tables [b, T] i32, pools
    [per-layer block arrays], samp)`` to ``(next_tokens [b] i32,
    last_logits [b, V], new_pools, max_qerr, new_keys [b, 2] u32)``.
    Identical semantics to ``decode_step`` — each row's token is
    written at its own offset, now routed through the row's block
    table into the shared [num_blocks, h, block_size, d] pools — with
    the same compile-once contract: pools, tables AND the per-row
    ``samp`` sampling tuple are fixed-shape jit inputs, so block
    remapping (admission, prefix sharing, COW) and per-request
    decoding recipes never retrace. Pools are (k, v) pairs or int8
    (k, v, k_scale, v_scale) 4-tuples; ``max_qerr`` is the int8
    path's max-abs dequantization error over the rows written this
    step (0.0 for float pools).

    The step **owns** ``pools`` (:data:`POOLS_DONATED`, as do the
    verify and the engine's paged prefill entries): the
    arrays passed in are deleted by the call, the rows are written in
    place, and ``new_pools`` is what the caller holds from then on.

    With ``lora_shape`` = (rank, pages) the step gains one more input:
    ``lora = (page_ids [b] i32, pool_arrays)`` from a
    ``serving.lora.LoRAPool`` — per-row adapter pages gathered inside
    the step (the block-table trick applied to weights). The lora
    geometry joins the cache key (pool shapes depend on it, exactly
    like ``kv_dtype``), but page remapping, loads and evictions are
    pure data: zero retraces.

    With ``mesh`` (a ``("data", "model")`` serving mesh) the step runs
    under pjit with explicit in/out shardings: pools keep their heads
    axis on ``"model"``, tokens/positions/tables/samp (and lora pages)
    stay replicated plain inputs. ``kv_dtype`` only matters under a
    mesh (it picks the pool tuple width for the sharding pytree); the
    mesh geometry is part of the cache key so each mesh compiles
    exactly once.

    With ``counters`` (a model whose seam names device counters) the step
    takes one more input after ``samp`` and returns one more result: the
    float32 vector of the model's counters, which the model's call
    (``counters=``) adds this step's counts to. The engine carries it from
    step to step and reads it in ``stats()`` only.
    """
    from ..distributed.sharding import mesh_cache_key
    from ..observability import compile_tracker as _ct
    from ..serving.decoding import sample_tokens
    mkey = mesh_cache_key(mesh)
    if counters and (mesh is not None or lora_shape is not None):
        raise ValueError("a decode step with device counters runs on one "
                         "chip and without LoRA pages")

    def _build():
        def _impl(params, tokens, pos, tables, pools, samp, lora,
                  counted=None):
            extra = {} if counted is None else {"counters": counted}
            with no_grad(), _borrowed_params(model, params), \
                    _kernel_layout(model, mesh):
                logits, newp, *counted = model(
                    _t(tokens[:, None]), cache=_wrap_pools(pools),
                    cache_pos=pos, block_tables=tables, lora=lora,
                    **extra)
            lg = logits.value[:, -1]
            nxt, new_keys = sample_tokens(lg, samp)
            pools_out, qerr = _unwrap_pools(newp)
            return (nxt, lg, pools_out, qerr, new_keys, *counted)

        if counters:
            def _step(params, tokens, pos, tables, pools, samp, counted):
                return _impl(params, tokens, pos, tables, pools, samp,
                             None, counted)
        elif lora_shape is None:
            def _step(params, tokens, pos, tables, pools, samp):
                return _impl(params, tokens, pos, tables, pools, samp,
                             None)
        else:
            def _step(params, tokens, pos, tables, pools, samp, lora):
                return _impl(params, tokens, pos, tables, pools, samp,
                             lora)

        jit_kwargs = dict(POOLS_DONATED)
        if mesh is not None:
            repl, pools_sh = _mesh_step_shardings(model, mesh, kv_dtype)
            in_sh = (_mesh_param_shardings(model, mesh),
                     repl, repl, repl, pools_sh, repl)
            if lora_shape is not None:
                in_sh = in_sh + (repl,)
            jit_kwargs.update(
                in_shardings=in_sh,
                out_shardings=(repl, repl, pools_sh, repl, repl))
        fn = _inject_params(
            model, _ct.tracked_jit("decode_step_paged", _step,
                                   **jit_kwargs))
        return {"fn": fn, "traces": fn.traces}

    key = (("decode_paged",) if mkey is None
           else ("decode_paged", mkey, kv_dtype))
    if lora_shape is not None:
        key = key + ("lora", tuple(lora_shape))
    if counters:
        key = key + ("counters",)
    return step_entry(model, key, _build)


def verify_step_paged(model, spec_tokens: int, mesh=None,
                      kv_dtype: str = "f32", lora_shape=None):
    """The compiled draft–verify step of speculative decoding: one
    fixed-shape forward scores the last committed token plus K drafts
    (``tokens [b, K+1]``: ``tokens[:, 0]`` is what a plain decode step
    would feed, ``tokens[:, 1:]`` the drafts for the positions after
    it) through per-row block tables, writing all K+1 rows at
    ``pos..pos+K``; then ``decoding.verify_tokens`` picks
    ``(chosen, accept)`` per row — greedy prefix match on temp==0 rows
    (``chosen = argmax``, ``accept = argmax == draft``: token-identical
    to plain greedy), rejection sampling on sampled rows, so every
    emitted token is an exact draw from the non-speculative
    distribution. Entries past a row's first rejection are garbage by
    construction: the caller commits the accepted prefix on the host
    and rolls the row's write offset back, and the rejected
    rows are stale pool contents past the row's valid length, hidden
    by the position mask (blocks stay reserved, so rollback across a
    block boundary is pure host-side length arithmetic). Compiled
    once per (model, K, mesh[, lora geometry]). Returns shaped like
    :func:`decode_step_paged`: ``(chosen [b, K+1] i32, logits
    [b, K+1, V], new_pools, max_qerr, accept [b, K] bool,
    new_keys [b, 2] u32)``. ``mesh`` / ``kv_dtype`` / ``lora_shape``
    behave exactly as in :func:`decode_step_paged`.
    """
    from ..distributed.sharding import mesh_cache_key
    from ..serving.decoding import verify_tokens
    k = int(spec_tokens)
    if k < 1:
        raise ValueError(
            f"verify_step_paged needs spec_tokens >= 1, got {k}")
    mkey = mesh_cache_key(mesh)

    def _build():
        def _impl(params, tokens, pos, tables, pools, samp, lora):
            with no_grad(), _borrowed_params(model, params), \
                    _kernel_layout(model, mesh):
                logits, newp = model(_t(tokens), cache=_wrap_pools(pools),
                                     cache_pos=pos, block_tables=tables,
                                     lora=lora)
            lg = logits.value                            # [b, K+1, V]
            nxt, accept, new_keys = verify_tokens(lg, tokens[:, 1:], samp)
            pools_out, qerr = _unwrap_pools(newp)
            return nxt, lg, pools_out, qerr, accept, new_keys

        if lora_shape is None:
            def _step(params, tokens, pos, tables, pools, samp):
                return _impl(params, tokens, pos, tables, pools, samp,
                             None)
        else:
            def _step(params, tokens, pos, tables, pools, samp, lora):
                return _impl(params, tokens, pos, tables, pools, samp,
                             lora)

        from ..observability import compile_tracker as _ct
        jit_kwargs = dict(POOLS_DONATED)
        if mesh is not None:
            repl, pools_sh = _mesh_step_shardings(model, mesh, kv_dtype)
            in_sh = (_mesh_param_shardings(model, mesh),
                     repl, repl, repl, pools_sh, repl)
            if lora_shape is not None:
                in_sh = in_sh + (repl,)
            jit_kwargs.update(
                in_shardings=in_sh,
                out_shardings=(repl, repl, pools_sh, repl, repl, repl))
        fn = _inject_params(
            model, _ct.tracked_jit("verify_step_paged", _step,
                                   labels={"k": str(k)}, **jit_kwargs))
        return {"fn": fn, "traces": fn.traces}

    key = (("verify_paged", k) if mkey is None
           else ("verify_paged", k, mkey, kv_dtype))
    if lora_shape is not None:
        key = key + ("lora", tuple(lora_shape))
    return step_entry(model, key, _build)


def draft_ngram(context, k: int, max_ngram: int = 3):
    """N-gram self-drafting (prompt-lookup decoding): propose ``k``
    draft tokens by matching the longest suffix n-gram of ``context``
    (prompt + generated so far) against its own earlier occurrences
    and copying what followed — no second model, and very accurate on
    repetitive/structured tails, which is where speculation pays.

    Tries n-grams from ``max_ngram`` down to 1, preferring the most
    recent match; a short continuation is cycled up to ``k`` (periodic
    text keeps its period); with no match at all the last token is
    repeated. Pure host-side list work, O(len * max_ngram) per call.
    """
    ctx = [int(t) for t in context]
    n_ctx = len(ctx)
    for n in range(min(int(max_ngram), n_ctx - 1), 0, -1):
        pat = ctx[n_ctx - n:]
        for j in range(n_ctx - n - 1, -1, -1):
            if ctx[j:j + n] == pat:
                cont = ctx[j + n:j + n + k]
                if cont:
                    while len(cont) < k:
                        cont = cont + cont
                    return cont[:k]
    return [ctx[-1]] * k


def _prefill(model, ids: np.ndarray, capacity: int):
    """Eager prompt pass into a fresh fixed cache. Returns
    (last_logits [b, V] jnp, caches [(k, v) jnp arrays])."""
    from ..serving.seam import require_gpt
    cfg = require_gpt(model, "decoding on the fixed-capacity cache "
                      "(greedy_search / sample / beam_search)")
    if capacity > cfg.max_position_embeddings:
        raise ValueError(
            f"cache capacity {capacity} exceeds max_position_embeddings="
            f"{cfg.max_position_embeddings}; raise it in the GPTConfig "
            "or shorten prompt/max_new_tokens")
    b, s0 = ids.shape
    if s0 > capacity:
        raise ValueError(f"prompt length {s0} exceeds cache capacity "
                         f"{capacity}")
    cache = model.gpt.gen_fixed_cache(b, capacity)
    logits, cache = model(_t(ids), cache=cache, cache_pos=0)
    return logits.value[:, -1], [(kv[0].value, kv[1].value)
                                 for kv in cache]


@no_grad()
def greedy_search(model, input_ids, max_new_tokens: int = 16,
                  eos_token_id: Optional[int] = None,
                  cache_len: Optional[int] = None):
    """Greedy decode with the fixed-capacity KV cache; returns
    [b, s+new] ids (numpy). ``cache_len`` pins the cache capacity
    (default prompt+max_new) — serving equivalence tests pass the
    engine's ``max_len`` so both sides run the identical computation."""
    ids = np.asarray(input_ids)
    b, s0 = ids.shape
    cap = int(cache_len if cache_len is not None
              else s0 + max_new_tokens)
    if cap < s0 + max_new_tokens:
        raise ValueError(
            f"cache_len {cap} < prompt {s0} + max_new_tokens "
            f"{max_new_tokens}")
    logits, arrays = _prefill(model, ids, cap)
    step = decode_step(model)["fn"]
    from ..serving.decoding import neutral_samp
    samp = neutral_samp(b, int(logits.shape[-1]))
    out = [ids]
    done = np.zeros(b, bool)
    cur = np.asarray(jnp.argmax(logits, -1)).reshape(b, 1)
    pos = jnp.full((b,), s0, jnp.int32)
    for t in range(max_new_tokens):
        if eos_token_id is not None:
            cur = np.where(done[:, None], eos_token_id, cur)
            done |= (cur[:, 0] == eos_token_id)
        out.append(cur.astype(ids.dtype))
        if eos_token_id is not None and done.all():
            break
        if t == max_new_tokens - 1:
            break
        nxt, _, arrays, _ = step(jnp.asarray(cur[:, 0], jnp.int32), pos,
                                 arrays, samp)
        pos = pos + 1
        cur = np.asarray(nxt).reshape(b, 1)
    return np.concatenate(out, axis=1)


@no_grad()
def sample(model, input_ids, max_new_tokens: int = 16,
           temperature: float = 1.0, top_k: int = 0, top_p: float = 0.0,
           seed: int = 0, cache_len: Optional[int] = None):
    """Temperature / top-k / top-p sampling decode (fixed-capacity
    cache; the same compiled step as greedy — the per-row ``samp``
    tuple carries the params as data, so offline ``sample()`` and the
    serving engine share one source of sampling math:
    :func:`paddle_tpu.serving.decoding.sample_tokens`)."""
    from ..serving.decoding import DecodeParams, sample_tokens
    # Validate eagerly with the shared param object.
    params = DecodeParams(temperature=float(temperature),
                          top_k=int(top_k), top_p=float(top_p),
                          seed=int(seed))
    ids = np.asarray(input_ids)
    b, s0 = ids.shape
    cap = int(cache_len if cache_len is not None
              else s0 + max_new_tokens)
    if cap < s0 + max_new_tokens:
        raise ValueError(
            f"cache_len {cap} < prompt {s0} + max_new_tokens "
            f"{max_new_tokens}")
    lg, arrays = _prefill(model, ids, cap)
    step = decode_step(model)["fn"]
    vocab = int(lg.shape[-1])
    temp = jnp.full((b,), params.temperature, jnp.float32)
    tk = jnp.full((b,), params.top_k, jnp.int32)
    tp = jnp.full((b,), params.top_p, jnp.float32)
    mask = jnp.zeros((b, vocab), jnp.float32)
    keys = jnp.asarray(
        jax.random.split(jax.random.PRNGKey(params.seed), b), jnp.uint32)
    # First token: sample the prefill logits with the same primitive
    # the jitted step uses.
    nxt, keys = sample_tokens(lg, (temp, tk, tp, keys, mask))
    cur = np.asarray(nxt).reshape(b, 1)
    out = [ids]
    pos = jnp.full((b,), s0, jnp.int32)
    for t in range(max_new_tokens):
        out.append(cur.astype(ids.dtype))
        if t == max_new_tokens - 1:
            break
        nxt, _, arrays, keys = step(
            jnp.asarray(cur[:, 0], jnp.int32), pos, arrays,
            (temp, tk, tp, keys, mask))
        pos = pos + 1
        cur = np.asarray(nxt).reshape(b, 1)
    return np.concatenate(out, axis=1)


@no_grad()
def beam_search(model, input_ids, beam_size: int = 4,
                max_new_tokens: int = 16,
                length_penalty: float = 1.0,
                eos_token_id: Optional[int] = None,
                cache_len: Optional[int] = None):
    """Beam search decode; returns (ids [b, s+new], scores [b]).

    The beam lives on a dense batch*beam axis (no LoD): fixed caches
    expand once after the prompt, each step is log-softmax + top-k over
    [b, beam*vocab], then a row gather re-orders the beam axis of every
    cache array (the beam_search_op "select parents" step).
    """
    ids = np.asarray(input_ids)
    b, s0 = ids.shape
    k = beam_size
    cap = int(cache_len if cache_len is not None
              else s0 + max_new_tokens)
    if cap < s0 + max_new_tokens:
        raise ValueError(
            f"cache_len {cap} < prompt {s0} + max_new_tokens "
            f"{max_new_tokens}")

    logits, arrays = _prefill(model, ids, cap)
    step = decode_step(model)["fn"]
    from ..serving.decoding import neutral_samp
    samp = neutral_samp(b * k, int(logits.shape[-1]))
    lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    vocab = lp.shape[-1]
    # seed beams with the top-k first tokens
    top = np.argsort(-lp, axis=-1)[:, :k]                   # [b, k]
    scores = np.take_along_axis(lp, top, -1)                # [b, k]
    tokens = top.reshape(b * k, 1)
    # expand caches along the beam axis (rows are independent slots)
    arrays = [(jnp.repeat(kv[0], k, axis=0), jnp.repeat(kv[1], k, axis=0))
              for kv in arrays]
    seqs = np.concatenate([np.repeat(ids, k, axis=0), tokens], axis=1)
    done = np.zeros((b, k), bool)
    pos = jnp.full((b * k,), s0, jnp.int32)

    for t in range(1, max_new_tokens):
        _, lg, arrays, _ = step(jnp.asarray(tokens[:, 0], jnp.int32),
                                pos, arrays, samp)
        pos = pos + 1
        lg = np.asarray(lg)                                 # [b*k, V]
        lg = lg - lg.max(-1, keepdims=True)
        lp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
        lp = lp.reshape(b, k, vocab)
        if eos_token_id is not None:
            # finished beams only extend with EOS at no cost
            frozen = np.full((vocab,), -1e9, lp.dtype)
            frozen[eos_token_id] = 0.0
            lp = np.where(done[..., None], frozen, lp)
        total = scores[..., None] + lp                      # [b, k, V]
        flat = total.reshape(b, k * vocab)
        best = np.argsort(-flat, axis=-1)[:, :k]            # [b, k]
        scores = np.take_along_axis(flat, best, -1)
        parent = best // vocab                              # [b, k]
        tok = (best % vocab).astype(ids.dtype)
        # reorder beam-major state by parent
        gidx = (np.arange(b)[:, None] * k + parent).reshape(-1)
        seqs = np.concatenate([seqs[gidx], tok.reshape(b * k, 1)], 1)
        arrays = [(kv[0][gidx], kv[1][gidx]) for kv in arrays]
        if eos_token_id is not None:
            done = np.take_along_axis(done, parent, 1) | \
                (tok == eos_token_id)
            if done.all():
                break
        tokens = tok.reshape(b * k, 1)

    lengths = seqs.shape[1] - s0
    final = scores / (lengths ** length_penalty)
    best_beam = final.argmax(-1)                            # [b]
    pick = np.arange(b) * k + best_beam
    return seqs[pick], final[np.arange(b), best_beam]
