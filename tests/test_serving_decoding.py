"""Per-request decoding: sampling-as-data, constrained JSON, paged LoRA.

The decoding subsystem's contract, locked at tier 1:

- defaults reproduce the pre-sampling engine exactly (greedy oracle,
  including speculative K=2 and the int8 KV pool);
- sampled output is a pure function of the request (seed, params,
  prompt) — engine restarts, replica routing and the disaggregated
  fleet all replay the same bytes, and different seeds diverge;
- speculative verify is rejection sampling: the committed-token law
  matches what non-speculative decode samples from (seeded
  statistical check at the primitive level — spec changes the sample
  *path*, never the distribution);
- json_mode output is valid JSON by construction, greedy or sampled;
- per-tenant LoRA rows diverge from base and from each other while
  sharing one engine and one KV pool, with zero leaked adapter pages
  or KV blocks even under injected chaos;
- none of it adds a decode compile: sampling params, stop sequences,
  grammar masks and adapter pages are all step *data*;
- a step whose rows are all greedy does not run the sampler: the
  processor chain and the draws sit under one ``lax.cond`` on "does
  any row sample", greedy rows are ``argmax(logits + mask)`` bit for
  bit, sampled and mixed batches are token-, accept- and key-identical
  to the chain inlined without the ``cond`` (kept here as the oracle),
  and the engine counts the dispatches that took the cheap branch.
"""

import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability
from paddle_tpu.models.generation import greedy_search
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (DecodeParams, DisaggRouter, JsonGrammar,
                                ReplicaRouter, ServingEngine,
                                json_token_strings, make_adapter)
from paddle_tpu.serving import decoding
from paddle_tpu.serving.decoding import (NEG_MASK, process_logits,
                                         request_key, sample_tokens,
                                         split_keys, verify_tokens)

VOCAB = 97


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = GPTConfig(vocab_size=VOCAB, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, size=n).tolist() for n in sizes]


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("buckets", [8, 16])
    kw.setdefault("max_queue", 16)
    kw.setdefault("block_size", 4)
    return ServingEngine(model, **kw)


SAMPLED = dict(temperature=0.8, top_k=8, top_p=0.95)


def _run(target, prompts, **kw):
    reqs = [target.submit(p, max_new_tokens=5, **kw) for p in prompts]
    target.run_until_idle()
    assert all(r.state == "done" for r in reqs), \
        [(r.state, r.error) for r in reqs]
    return [r.output_ids for r in reqs]


# ------------------------------------------------------------- oracle
def test_greedy_oracle_with_spec_k2(model):
    """Default params through a speculative (K=2) engine == plain
    greedy_search, token for token — rejection-sampled verify reduces
    to the prefix-match rule on temp==0 rows."""
    prompts = _prompts((3, 7, 5))
    eng = _engine(model, spec_tokens=2)
    outs = _run(eng, prompts)
    for p, out in zip(prompts, outs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=5,
                            cache_len=eng.max_len)[0].tolist()
        assert out == ref


def test_greedy_oracle_int8_kv(model):
    """Default params on the int8-quantized KV pool still match the
    f32 offline greedy on this model (and the sampling machinery adds
    no drift on temp==0 rows)."""
    prompts = _prompts((3, 5))
    eng = _engine(model, kv_dtype="int8")
    outs = _run(eng, prompts)
    for p, out in zip(prompts, outs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=5,
                            cache_len=eng.max_len)[0].tolist()
        assert out == ref


# ------------------------------------------------------- determinism
def test_sampled_restart_byte_identity(model):
    """Sampled output is a pure function of (request, seed): a fresh
    engine replays the same bytes; a different seed diverges."""
    prompts = _prompts((4, 6, 5))
    a = _run(_engine(model), prompts, seed=11, **SAMPLED)
    b = _run(_engine(model), prompts, seed=11, **SAMPLED)
    assert a == b
    c = _run(_engine(model), prompts, seed=12, **SAMPLED)
    assert a != c, "seed change did not move any sampled output"


def test_sampled_symmetric_vs_router_vs_disagg(model):
    """One engine, a 2-replica router and a 1x2 disaggregated fleet
    decode identical bytes for identical sampled submissions — the
    request-local key schedule never sees slots, engines or roles."""
    prompts = _prompts((4, 6, 5, 7), seed=3)
    kw = dict(seed=21, **SAMPLED)
    sym = _run(_engine(model), prompts, **kw)
    router = ReplicaRouter(model, n_replicas=2, max_slots=2,
                           max_len=32, buckets=[8, 16], max_queue=16,
                           block_size=4)
    assert _run(router, prompts, **kw) == sym
    fleet = DisaggRouter(model, n_prefill=1, n_decode=2, max_slots=2,
                         max_len=32, buckets=[8, 16], max_queue=16,
                         block_size=4)
    assert _run(fleet, prompts, **kw) == sym


def test_sampled_spec_restart_byte_identity(model):
    """Speculative sampled decode is deterministic too: same seed +
    same K replays byte-identically across engine restarts."""
    prompts = _prompts((4, 6))
    a = _run(_engine(model, spec_tokens=2), prompts, seed=9, **SAMPLED)
    b = _run(_engine(model, spec_tokens=2), prompts, seed=9, **SAMPLED)
    assert a == b


# ------------------------------------- rejection-sampling distribution
def test_spec_verify_matches_nonspec_distribution():
    """The committed first token of a rejection-sampled verify follows
    the same law the non-speculative sampler draws from — measured
    empirically against the analytic target (seeded, no wall-clock or
    OS entropy anywhere)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    n, k, vocab = 8192, 2, 8
    row = (rng.randn(vocab) * 1.5).astype(np.float32)

    def samp_for(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        return (jnp.full((n,), 0.9, jnp.float32),
                jnp.zeros((n,), jnp.int32),
                jnp.full((n,), 0.95, jnp.float32),
                jnp.asarray(keys, jnp.uint32),
                jnp.zeros((n, vocab), jnp.float32))

    target = np.asarray(jax.nn.softmax(process_logits(
        jnp.asarray(row)[None, :], jnp.full((1,), 0.9, jnp.float32),
        jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 0.95, jnp.float32))[0]))

    logits = jnp.tile(jnp.asarray(row), (n, 1))
    toks, _ = sample_tokens(logits, samp_for(1))
    # drafts: a plausible drafter (the greedy token) — acceptance is
    # high, which is exactly where a biased rule would show
    drafts = jnp.full((n, k), int(np.argmax(row)), jnp.int32)
    chosen, accept, _ = verify_tokens(
        jnp.tile(jnp.asarray(row), (n, k + 1, 1)), drafts, samp_for(2))

    def tv(tokens):
        hist = np.bincount(np.asarray(tokens), minlength=vocab) / n
        return 0.5 * float(np.abs(hist - target).sum())

    assert tv(toks) < 0.05, "non-spec sampler drifted from target"
    assert tv(chosen[:, 0]) < 0.05, \
        "rejection-sampled verify drifted from the target law"
    # the drafter is plausible, so a healthy share must be accepted
    assert 0.05 < float(np.asarray(accept[:, 0]).mean()) < 1.0


# ------------------------------------------------------------ grammar
def test_json_mode_valid_by_construction(model):
    grammar = JsonGrammar(json_token_strings(VOCAB))
    eng = _engine(model, grammar=grammar)
    greedy = eng.submit(_prompts((4,))[0], max_new_tokens=8,
                        json_mode=True)
    sampled = eng.submit(_prompts((5,))[0], max_new_tokens=8,
                         json_mode=True, seed=4, **SAMPLED)
    eng.run_until_idle()
    for r in (greedy, sampled):
        assert r.state == "done", (r.state, r.error)
        json.loads(grammar.decode(r.tokens))   # or it isn't JSON


def test_json_mode_rejections(model):
    eng = _engine(model)   # no grammar
    with pytest.raises(ValueError, match="grammar"):
        eng.submit([1, 2, 3], json_mode=True)
    spec = _engine(model, spec_tokens=2,
                   grammar=JsonGrammar(json_token_strings(VOCAB)))
    with pytest.raises(ValueError, match="spec"):
        spec.submit([1, 2, 3], json_mode=True)


# ----------------------------------------------------- stop sequences
def test_stop_sequences_truncate(model):
    prompts = _prompts((5,))
    eng = _engine(model)
    [full] = _run(eng, prompts)
    gen = full[len(prompts[0]):]
    assert len(gen) >= 2
    stop = gen[:2]
    req = eng.submit(prompts[0], max_new_tokens=5, stop=[stop])
    eng.run_until_idle()
    # the stop tokens stay in the output; nothing follows them
    assert req.tokens == stop
    with pytest.raises(ValueError, match="stop"):
        eng.submit(prompts[0], stop=[1, 2])   # flat list, not nested


def test_stop_matcher_equals_naive_rescan():
    """Property: the incremental KMP matcher agrees with the O(len^2)
    full-suffix rescan at every step of random streams."""
    from paddle_tpu.serving.decoding import StopMatcher
    rng = np.random.RandomState(11)
    for trial in range(20):
        pats = [rng.randint(0, 4, size=rng.randint(1, 5)).tolist()
                for _ in range(rng.randint(1, 5))]
        m = StopMatcher(pats)
        hist = []
        for tok in rng.randint(0, 4, size=40):
            hist.append(int(tok))
            got = m.feed(tok)
            naive = any(len(h := hist) >= len(p) and
                        h[-len(p):] == list(p) for p in pats)
            # hit latches; the naive check is per-position
            if naive:
                assert got, (pats, hist)
            if not m.hit:
                assert not naive, (pats, hist)


# --------------------------------------------------------- validation
def test_decode_params_validation(model):
    for bad in (dict(temperature=-0.1), dict(top_k=-1),
                dict(top_p=1.5), dict(top_p=-0.2)):
        with pytest.raises(ValueError):
            DecodeParams(**bad)
    eng = _engine(model)
    with pytest.raises(ValueError):
        eng.submit([1, 2], temperature=-1.0)
    with pytest.raises(ValueError, match="tenant"):
        eng.submit([1, 2], tenant="acme")   # no adapter pool
    with pytest.raises(ValueError):
        eng.submit([1, 2], decode=DecodeParams(temperature=0.5),
                   temperature=0.7)   # decode= excludes the fields


# --------------------------------------------------------------- lora
def test_lora_tenants_diverge_share_one_pool(model):
    cfg = model.gpt.cfg
    eng = _engine(model, lora_rank=2, lora_max_adapters=2)
    eng.load_adapter("acme", make_adapter(cfg, 2, seed=1, scale=0.5))
    eng.load_adapter("zeta", make_adapter(cfg, 2, seed=2, scale=0.5))
    p = _prompts((5,))[0]
    base = eng.submit(p, max_new_tokens=5)
    acme = eng.submit(p, max_new_tokens=5, tenant="acme")
    zeta = eng.submit(p, max_new_tokens=5, tenant="zeta")
    eng.run_until_idle()
    outs = [base.output_ids, acme.output_ids, zeta.output_ids]
    assert len({tuple(o) for o in outs}) == 3, outs
    with pytest.raises(ValueError, match="acme"):
        eng.submit(p, tenant="ghost")
    assert eng.lora_pool.leaked() == 0
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1   # trash block only
    st = eng.stats()
    assert set(st["lora"]["loaded"]) == {"acme", "zeta"}
    assert set(st["tenants"]) == {"base", "acme", "zeta"}


def test_lora_zero_leaks_under_chaos(model):
    """Tenant traffic with injected submit/alloc faults: every shed or
    failed admission must release its adapter page and KV blocks."""
    from paddle_tpu.resilience import fault_scope
    from paddle_tpu.serving import QueueFullError
    cfg = model.gpt.cfg
    eng = _engine(model, lora_rank=2, lora_max_adapters=2)
    eng.load_adapter("acme", make_adapter(cfg, 2, seed=1, scale=0.5))
    prompts = _prompts((4, 6, 5, 7, 4, 6), seed=5)
    with fault_scope("serving.submit:skip@0.3;serving.alloc:skip@0.3",
                     seed=13):
        for i, p in enumerate(prompts):
            try:
                eng.submit(p, max_new_tokens=4,
                           tenant="acme" if i % 2 else "")
            except QueueFullError:
                pass
            eng.step()
        eng.run_until_idle()
    assert eng.lora_pool.leaked() == 0
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1
    # an adapter pinned by an active request refuses eviction
    eng.lora_pool.acquire("acme")
    with pytest.raises(ValueError, match="pinned"):
        eng.evict_adapter("acme")
    eng.lora_pool.release("acme")
    assert eng.evict_adapter("acme") >= 1


# ---------------------------------------------------- compile budget
def test_mixed_decode_traffic_adds_zero_compiles(model):
    """After one greedy wave, sampled / stop / json traffic moves the
    compile tracker not at all — sampling is data."""
    grammar = JsonGrammar(json_token_strings(VOCAB))
    eng = _engine(model, grammar=grammar)
    _run(eng, _prompts((4, 6)))
    before = {s: c["count"] for s, c in observability.compiles().items()
              if s.startswith(("serving_", "decode_", "verify_"))}
    eng.submit(_prompts((5,))[0], max_new_tokens=4, seed=3, **SAMPLED)
    eng.submit(_prompts((6,))[0], max_new_tokens=4, json_mode=True)
    eng.submit(_prompts((7,))[0], max_new_tokens=4, stop=[[1]])
    eng.run_until_idle()
    after = {s: c["count"] for s, c in observability.compiles().items()
             if s.startswith(("serving_", "decode_", "verify_"))}
    assert after == before, (before, after)


def test_request_key_ignores_everything_but_seed():
    a, b = request_key(42), request_key(42)
    assert a.dtype == np.uint32 and a.shape == (2,)
    assert np.array_equal(a, b)
    assert not np.array_equal(request_key(42), request_key(43))


def test_a_seeds_key_touches_the_device_once_a_process(monkeypatch):
    """``submit`` builds a request's key, and ``jax.random.PRNGKey`` is a
    program whose fetch waits behind the decode step in flight: the second
    request of a seed (every greedy one has seed 0) computes nothing, and
    gets an array of its own."""
    import jax
    calls, real = [], jax.random.PRNGKey
    monkeypatch.setattr(jax.random, "PRNGKey",
                        lambda seed: (calls.append(seed), real(seed))[1])
    a, b = request_key(987654321), request_key(987654321)
    assert calls == [987654321]
    assert a is not b and np.array_equal(a, b)
    assert np.array_equal(a, np.asarray(real(987654321), np.uint32))
    a[0] ^= 1
    assert np.array_equal(request_key(987654321), b)


# ------------------------------- greedy steps skip the sampler (PR 28)

def _oracle_sample_tokens(logits, samp):
    """``sample_tokens`` as it was before the ``cond``: the chain and
    the draw computed for every row of every step."""
    import jax
    import jax.numpy as jnp
    temp, top_k, top_p, keys, mask = samp
    lgm = logits + mask
    greedy = jnp.argmax(lgm, axis=-1).astype(jnp.int32)
    proc = process_logits(lgm, temp, top_k, top_p)
    carry, sub = split_keys(keys)
    drawn = jax.vmap(jax.random.categorical)(sub, proc).astype(jnp.int32)
    return jnp.where(temp > 0, drawn, greedy), carry


def _oracle_verify_tokens(logits, drafts, samp):
    """``verify_tokens`` as it was before the ``cond``."""
    import jax
    import jax.numpy as jnp
    temp, top_k, top_p, keys, mask = samp
    rows, kp1, vocab = logits.shape
    k = kp1 - 1
    neg = jnp.asarray(NEG_MASK, logits.dtype)
    lgm = logits + mask[:, None, :]
    greedy = jnp.argmax(lgm, axis=-1).astype(jnp.int32)
    rep = lambda x: jnp.repeat(x, kp1)
    proc = process_logits(lgm.reshape(rows * kp1, vocab), rep(temp),
                          rep(top_k), rep(top_p)).reshape(rows, kp1, vocab)
    carry, sub = split_keys(keys)
    subs = jax.vmap(lambda kk: jax.random.split(kk, 2 * kp1))(sub)
    ukeys, ckeys = subs[:, :kp1], subs[:, kp1:]
    probs = jax.nn.softmax(proc, axis=-1)
    bonus = jax.vmap(jax.random.categorical)(
        ckeys[:, k], proc[:, k]).astype(jnp.int32)
    if k == 0:
        chosen = jnp.where(temp[:, None] > 0, bonus[:, None], greedy)
        return chosen, jnp.zeros((rows, 0), bool), carry
    draft_p = jnp.take_along_axis(
        probs[:, :k], drafts[..., None].astype(jnp.int32), axis=-1)[..., 0]
    u = jax.vmap(jax.vmap(jax.random.uniform))(ukeys[:, :k])
    accept_s = u < draft_p
    resid = jnp.where(jax.nn.one_hot(drafts, vocab, dtype=bool),
                      neg, proc[:, :k])
    resample = jax.vmap(jax.vmap(jax.random.categorical))(
        ckeys[:, :k], resid).astype(jnp.int32)
    chosen_s = jnp.where(accept_s, drafts.astype(jnp.int32), resample)
    chosen_s = jnp.concatenate([chosen_s, bonus[:, None]], axis=1)
    sampled = (temp > 0)[:, None]
    chosen = jnp.where(sampled, chosen_s, greedy)
    accept = jnp.where(sampled, accept_s, greedy[:, :k] == drafts)
    return chosen, accept, carry


# one recipe a row: (temperature, top_k, top_p); None = a greedy row
_RECIPES = {
    "all_greedy": [None] * 6,
    "mixed": [None, (0.7, 0, 0.0), None, None, (1.3, 8, 0.95), None],
    "one_sampled": [None, None, None, (1.0, 5, 0.0), None, None],
    "all_sampled": [(0.7, 0, 0.0), (1.0, 5, 0.0), (0.9, 0, 0.8),
                    (1.3, 8, 0.95), (0.2, 1, 0.0), (2.0, 0, 0.5)],
}
_V = 61


def _batch(kind, k, grammar_mask=False, seed=0):
    """``(logits [rows, k+1, V], drafts [rows, k], samp)`` for one
    recipe list; the drafts are the greedy token on even positions (so
    some accept) and a random one on odd."""
    import jax.numpy as jnp
    recipes = _RECIPES[kind]
    rows = len(recipes)
    rng = np.random.RandomState(seed)
    logits = (rng.randn(rows, k + 1, _V) * 2.0).astype(np.float32)
    mask = np.zeros((rows, _V), np.float32)
    if grammar_mask:
        # rows 0 and 3 may emit only a few tokens, as a JSON cursor
        # allows; the argmax of the bare logits is banned on both
        for r in (0, 3):
            mask[r] = NEG_MASK
            mask[r, rng.choice(_V, size=5, replace=False)] = 0.0
            mask[r, logits[r].argmax(-1)] = NEG_MASK
    best = (logits + mask[:, None, :]).argmax(-1)
    drafts = rng.randint(0, _V, size=(rows, k)).astype(np.int32)
    drafts[:, ::2] = best[:, :k][:, ::2]
    temp = np.array([r[0] if r else 0.0 for r in recipes], np.float32)
    tk = np.array([r[1] if r else 0 for r in recipes], np.int32)
    tp = np.array([r[2] if r else 0.0 for r in recipes], np.float32)
    keys = np.stack([request_key(100 + seed + i) for i in range(rows)])
    samp = tuple(jnp.asarray(x) for x in (temp, tk, tp, keys, mask))
    return jnp.asarray(logits), jnp.asarray(drafts), samp


def _primitives(jaxpr, into_cond):
    """Names of every primitive of ``jaxpr`` and of the programs its
    equations carry, those under a ``cond`` only if ``into_cond``."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _primitives(inner, into_cond)
    return names


_SAMPLER_ONLY = {"sort", "cumsum", "random_bits"}


@pytest.mark.parametrize("fn", ["sample", "verify_k0", "verify_k3"])
def test_the_sampler_is_under_one_cond(fn):
    """(a) Structure: one ``cond`` at the top level, and no sort,
    cumsum or random draw anywhere outside it; the argmax and the key
    split are outside, the sampler inside."""
    import jax
    k = {"sample": 0, "verify_k0": 0, "verify_k3": 3}[fn]
    logits, drafts, samp = _batch("mixed", k)
    if fn == "sample":
        closed = jax.make_jaxpr(sample_tokens)(logits[:, 0], samp)
    else:
        closed = jax.make_jaxpr(verify_tokens)(logits, drafts, samp)
    top = [e.primitive.name for e in closed.jaxpr.eqns]
    assert top.count("cond") == 1, top
    outside = set(_primitives(closed.jaxpr, into_cond=False))
    assert not outside & _SAMPLER_ONLY, outside & _SAMPLER_ONLY
    assert {"argmax", "random_split"} <= outside
    inside = set(_primitives(closed.jaxpr, into_cond=True)) - outside
    assert _SAMPLER_ONLY <= inside, inside


@pytest.mark.parametrize("k", [0, 3])
@pytest.mark.parametrize("grammar_mask", [False, True],
                         ids=["zero_mask", "grammar_mask"])
def test_all_greedy_batches_are_the_argmax_bit_for_bit(grammar_mask, k):
    """(b) No row samples: tokens are ``argmax(logits + mask)``,
    accepts are ``argmax == draft``, and the carried keys are the one
    unconditional split — under jit and eagerly."""
    import jax
    logits, drafts, samp = _batch("all_greedy", k, grammar_mask)
    mask = np.asarray(samp[4])
    want = (np.asarray(logits) + mask[:, None, :]).argmax(-1)
    want_keys = np.asarray(split_keys(samp[3])[0])
    if grammar_mask:
        assert (want[0] != np.asarray(logits)[0].argmax(-1)).all()
    for wrap in (jax.jit, lambda f: f):
        toks, carry = wrap(sample_tokens)(logits[:, 0], samp)
        assert toks.dtype == np.int32
        assert np.array_equal(np.asarray(toks), want[:, 0])
        assert np.array_equal(np.asarray(carry), want_keys)
        chosen, accept, carry = wrap(verify_tokens)(logits, drafts, samp)
        assert chosen.dtype == np.int32 and accept.dtype == bool
        assert np.array_equal(np.asarray(chosen), want)
        assert accept.shape == (len(want), k)
        assert np.array_equal(np.asarray(accept),
                              want[:, :k] == np.asarray(drafts))
        assert np.array_equal(np.asarray(carry), want_keys)


@pytest.mark.parametrize("mode", ["jit", "eager"])
@pytest.mark.parametrize("kind", ["mixed", "one_sampled", "all_sampled"])
def test_sampled_batches_draw_what_the_inlined_chain_draws(kind, mode):
    """(c) A batch with any sampled row computes what it computed
    before the ``cond``: tokens and carry keys of every row, over
    temperature, top-k, top-p and a grammar mask."""
    import jax
    wrap = jax.jit if mode == "jit" else (lambda f: f)
    for seed, grammar_mask in ((0, False), (1, True)):
        logits, _, samp = _batch(kind, 0, grammar_mask, seed)
        toks, carry = wrap(sample_tokens)(logits[:, 0], samp)
        want, want_carry = wrap(_oracle_sample_tokens)(logits[:, 0], samp)
        assert np.array_equal(np.asarray(toks), np.asarray(want))
        assert np.array_equal(np.asarray(carry), np.asarray(want_carry))
        greedy = np.asarray(samp[0]) == 0
        best = (np.asarray(logits[:, 0]) + np.asarray(samp[4])).argmax(-1)
        assert np.array_equal(np.asarray(toks)[greedy], best[greedy])


@pytest.mark.parametrize("mode", ["jit", "eager"])
@pytest.mark.parametrize("k", [0, 3])
@pytest.mark.parametrize("kind", ["mixed", "one_sampled", "all_sampled"])
def test_sampled_verify_accepts_what_the_inlined_chain_accepts(kind, k,
                                                               mode):
    """(c) The same for the speculative verify: chosen tokens, accepts
    and carry keys of every row equal the chain's without the ``cond``."""
    import jax
    wrap = jax.jit if mode == "jit" else (lambda f: f)
    for seed, grammar_mask in ((0, False), (1, True)):
        logits, drafts, samp = _batch(kind, k, grammar_mask, seed)
        got = wrap(verify_tokens)(logits, drafts, samp)
        want = wrap(_oracle_verify_tokens)(logits, drafts, samp)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert np.array_equal(np.asarray(g), np.asarray(w))
    if k and kind == "all_sampled":
        accept = np.asarray(got[1])
        assert accept.any() and not accept.all()


@pytest.mark.parametrize("spec_tokens", [0, 2])
def test_engine_counts_the_dispatches_that_skipped_the_sampler(
        spec_tokens):
    """(e) ``sampler_skipped / sampler_dispatches``: 1 for an
    all-greedy run; one sampled request among greedy ones takes it down
    by exactly the dispatches that request was live in; and one decode
    (verify) executable served both runs."""
    from paddle_tpu import monitor
    from paddle_tpu.models.generation import (decode_step_paged,
                                              verify_step_paged)
    pt.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_layers=2, num_heads=4, ffn_hidden_size=64))
    m.eval()
    eng = _engine(m, max_slots=4, spec_tokens=spec_tokens)
    stat0 = monitor.stat_get("STAT_serving_sampler_skipped")
    _run(eng, _prompts((4, 6, 5)))
    st = eng.stats()
    assert st["sampler_dispatches"] > 0
    assert st["sampler_skipped"] == st["sampler_dispatches"]
    assert monitor.stat_get("STAT_serving_sampler_skipped") - stat0 == \
        st["sampler_skipped"]

    greedy = [eng.submit(p, max_new_tokens=24)
              for p in _prompts((4, 6, 5), seed=1)]
    sampled = eng.submit(_prompts((5,), seed=2)[0], max_new_tokens=4,
                         seed=9, **SAMPLED)
    eng.run_until_idle()
    assert all(r.state == "done" for r in greedy + [sampled])
    st2 = eng.stats()
    ran_sampler = ((st2["sampler_dispatches"] - st["sampler_dispatches"])
                   - (st2["sampler_skipped"] - st["sampler_skipped"]))
    # the first token comes from the prefill, the rest one (or, with
    # drafts accepted, up to K+1) a dispatch
    steps = len(sampled.tokens) - 1
    if spec_tokens:
        assert -(-steps // (spec_tokens + 1)) <= ran_sampler <= steps
    else:
        # and one more: the step dispatched ahead of the commit that ends
        # the sampled request still carries its row (dropped)
        assert steps == 3 and ran_sampler == steps + 1
        assert st2["ahead_rows_dropped"] > 0
    assert st2["sampler_skipped"] > st["sampler_skipped"]
    assert monitor.stat_get("STAT_serving_sampler_skipped") - stat0 == \
        st2["sampler_skipped"]
    entry = (verify_step_paged(m, spec_tokens) if spec_tokens
             else decode_step_paged(m))
    assert entry["traces"]["count"] == 1
