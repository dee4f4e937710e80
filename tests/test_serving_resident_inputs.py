"""The decode step's inputs stay on the device (PR 30).

``ServingEngine`` keeps every operand of a paged decode / verify
dispatch resident and re-sends one only when the host state it
mirrors changed: sampling parameters and LoRA pages on a
change of the batch's membership, the block tables on the cache's
``tables_version``, a real mask only while a grammar cursor is live, and
tokens and keys not at all while the batch stands as the last commit
left it (the step's own outputs are its next inputs).

The contracts under test:

- **one behaviour**: a scripted scenario run as is and run again with
  the resident state dropped before every step
  (``engine._forget_inputs()``: every operand rebuilt from the host and
  sent, what every step did before) gives the same token streams, the
  same request keys, the same ``cache.lengths`` and ``cache.tables``
  after every step. The scenarios cross every event that changes what a
  resident operand mirrors;
- **a steady step copies nothing but its lengths**: counted at
  ``engine._send``, the one helper the step operands are sent through;
- **the counter says so**: ``inputs_resident / inputs_dispatches`` reads
  what a scenario implies;
- **no second program**: a device array where a host array was passed
  costs neither a trace nor an executable.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.models.generation import (decode_step_paged,
                                          verify_step_paged)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import fault_scope
from paddle_tpu.serving import ServingEngine, make_adapter
from paddle_tpu.serving.decoding import JsonGrammar, json_token_strings

VOCAB = 97
SAMPLED = dict(temperature=0.8, top_k=12, top_p=0.9)


def _make_model(seed):
    pt.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _make_model(7)


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, size=n).tolist() for n in sizes]


def _engine(model, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 48)
    kw.setdefault("buckets", [8, 16])
    kw.setdefault("max_queue", 16)
    kw.setdefault("block_size", 4)
    return ServingEngine(model, **kw)


class Driver:
    """Steps one engine through a script and keeps, after every step,
    what the two runs of a scenario must agree on."""

    def __init__(self, eng, forget):
        self.eng, self.forget = eng, forget
        self.reqs, self.log = [], []

    def submit(self, prompt, **kw):
        self.reqs.append(self.eng.submit(prompt, **kw))
        return self.reqs[-1]

    def step(self, n=1):
        for _ in range(n):
            if self.forget:
                with self.eng._step_lock:
                    self.eng._forget_inputs()
            self.eng.step()
            self.log.append((
                [(r.state, tuple(r.tokens),
                  tuple(int(x) for x in np.asarray(r._key).ravel()))
                 for r in self.reqs],
                self.eng.cache.lengths.tolist(),
                self.eng.cache.tables.tolist()))

    def until_idle(self, limit=200):
        while not self.eng.idle:
            self.step()
            limit -= 1
            assert limit > 0, "the scenario never drained"


# ------------------------------------------------------------ scenarios
# each builds its engine, runs its script through a Driver and returns
# the driver; asserts inside pin that the event it is named for happened

def admit_finish_by_length_and_stop(model, forget, monkeypatch):
    """A decodes alone, B and C are admitted into free slots mid-decode,
    A finishes by length, B by a stop sequence, C by EOS; D takes a
    freed slot."""
    pa, pb, pc, pd = _prompts((5, 7, 6, 4), seed=1)
    probe = _engine(model)
    rb = probe.submit(pb, max_new_tokens=8)
    rc = probe.submit(pc, max_new_tokens=8, seed=8, **SAMPLED)
    probe.run_until_idle()
    # C ends at the last of its tokens that none before it equals
    k = max(i for i, t in enumerate(rc.tokens) if t not in rc.tokens[:i])
    assert k >= 2, rc.tokens
    d = Driver(_engine(model), forget)
    a = d.submit(pa, max_new_tokens=9)
    d.step(3)
    b = d.submit(pb, max_new_tokens=8, stop=[rb.tokens[3:5]])
    c = d.submit(pc, max_new_tokens=8, eos_token_id=rc.tokens[k], seed=8,
                 **SAMPLED)
    d.step(4)
    dd = d.submit(pd, max_new_tokens=5)
    d.until_idle()
    assert [r.state for r in d.reqs] == ["done"] * 4
    assert len(a.tokens) == 9 and len(dd.tokens) == 5
    assert b.tokens == rb.tokens[:5] and c.tokens == rc.tokens[:k + 1]
    return d


def cancel_mid_decode(model, forget, monkeypatch):
    pa, pb = _prompts((6, 5), seed=2)
    d = Driver(_engine(model), forget)
    a = d.submit(pa, max_new_tokens=10)
    b = d.submit(pb, max_new_tokens=10, seed=5, **SAMPLED)
    d.step(3)
    assert d.eng.cancel(a.id) is not None
    d.until_idle()
    assert a.state == "canceled" and b.state == "done"
    return d


def shed_at_prefill(model, forget, monkeypatch):
    """The second request's prefill is skipped (an injected fault) and
    the request shed while the first decodes on."""
    pa, pb, pc = _prompts((6, 5, 7), seed=3)
    with fault_scope("serving.step:skip@3"):
        d = Driver(_engine(model), forget)
        a = d.submit(pa, max_new_tokens=8)   # calls 0 (prefill), 1
        d.step(2)                            # call 2
        b = d.submit(pb, max_new_tokens=8)   # call 3: shed
        d.step(2)
        c = d.submit(pc, max_new_tokens=4)
        d.until_idle()
    assert (a.state, b.state, c.state) == ("done", "shed", "done")
    return d


def sampled_beside_greedy(model, forget, monkeypatch):
    pa, pb, pc = _prompts((5, 6, 7), seed=4)
    d = Driver(_engine(model), forget)
    d.submit(pa, max_new_tokens=10)
    s = d.submit(pb, max_new_tokens=7, seed=11, **SAMPLED)
    d.step(4)
    t = d.submit(pc, max_new_tokens=6, seed=12, temperature=1.3)
    d.until_idle()
    assert s.state == t.state == "done"
    return d


def json_row_arrives_and_leaves(model, forget, monkeypatch):
    """The mask is a real one only while the grammar-cursored row is
    live, and the resident zero array again after it left."""
    grammar = JsonGrammar(json_token_strings(VOCAB))
    pa, pj = _prompts((5, 4), seed=5)
    d = Driver(_engine(model, grammar=grammar), forget)
    a = d.submit(pa, max_new_tokens=16)
    d.step(3)
    j = d.submit(pj, max_new_tokens=6, json_mode=True)
    d.step(2)
    assert j.state == "running" and j._cursor is not None
    d.until_idle()
    assert a.state == j.state == "done"
    import json as _json
    _json.loads(grammar.decode(j.tokens))
    return d


def prefix_hit_with_copy_on_write(model, forget, monkeypatch):
    """B's prompt is A's first two blocks: one shared whole, the
    boundary block privatised (its last token runs through prefill)."""
    pa = _prompts((11,), seed=6)[0]
    d = Driver(_engine(model), forget)
    d.submit(pa, max_new_tokens=8)
    d.step(3)
    hits = d.eng.cache.prefix_hits
    b = d.submit(pa[:8], max_new_tokens=6)
    d.step(1)
    assert d.eng.cache.prefix_hits - hits == 7     # 7 % block_size != 0
    d.until_idle()
    assert b.state == "done"
    return d


def speculative_verify_with_rollback(model, forget, monkeypatch):
    pa, pb = _prompts((6, 5), seed=7)
    d = Driver(_engine(model, spec_tokens=2), forget)
    d.submit(pa, max_new_tokens=10)
    d.submit(pb, max_new_tokens=9, seed=3, **SAMPLED)
    d.step(3)
    d.submit(_prompts((4,), seed=8)[0], max_new_tokens=6)
    d.until_idle()
    st = d.eng.stats()
    assert st["spec_proposed"] > st["spec_accepted"]   # tails rolled back
    return d


def lora_row_and_a_load(model, forget, monkeypatch):
    cfg = model.gpt.cfg
    eng = _engine(model, lora_rank=2, lora_max_adapters=2)
    eng.load_adapter("acme", make_adapter(cfg, 2, seed=1, scale=0.5))
    pa, pb, pc = _prompts((5, 6, 4), seed=10)
    d = Driver(eng, forget)
    d.submit(pa, max_new_tokens=9)
    d.submit(pb, max_new_tokens=9, tenant="acme")
    d.step(3)
    eng.load_adapter("zeta", make_adapter(cfg, 2, seed=2, scale=0.5))
    z = d.submit(pc, max_new_tokens=5, tenant="zeta")
    d.until_idle()
    assert z.state == "done" and eng.lora_pool.leaked() == 0
    return d


def swap_weights_between_steps(model, forget, monkeypatch):
    own = _make_model(7)      # the swap must not reach the other cases
    other = {n: p.value for n, p in _make_model(21).named_parameters()}
    pa, pb = _prompts((6, 5), seed=11)
    d = Driver(_engine(own), forget)
    a = d.submit(pa, max_new_tokens=10)
    d.submit(pb, max_new_tokens=10, seed=4, **SAMPLED)
    d.step(3)
    before = tuple(a.tokens)
    d.eng.swap_weights(other)
    d.until_idle()
    probe = _engine(own)
    [ref] = [probe.submit(pa, max_new_tokens=10)]
    probe.run_until_idle()
    assert tuple(a.tokens[:len(before)]) == before
    assert a.tokens != ref.tokens    # the new weights were read
    return d


def retry_after_a_step_fault(model, forget, monkeypatch):
    pa, pb = _prompts((5, 6), seed=12)
    monitor.reset()
    with fault_scope("serving.step:drop@4"):
        d = Driver(_engine(model), forget)
        d.submit(pa, max_new_tokens=8)
        d.submit(pb, max_new_tokens=8, seed=6, **SAMPLED)
        d.until_idle()
        assert monitor.stat_get("STAT_retry_serving.step") >= 1
    assert [r.state for r in d.reqs] == ["done", "done"]
    return d


def pools_lost(model, forget, monkeypatch):
    """A decode that raises after it consumed its pools sheds what ran;
    the next request is served from rebuilt pools."""
    pa, pb, pc = _prompts((5, 7, 6), seed=13)
    d = Driver(_engine(model), forget)
    a = d.submit(pa, max_new_tokens=8)
    d.step(2)
    ent = decode_step_paged(model)
    real = ent["fn"]

    def consume_then_raise(*args):
        real(*args)
        raise RuntimeError("device fault after the pools were donated")

    monkeypatch.setitem(ent, "fn", consume_then_raise)
    b = d.submit(pb, max_new_tokens=8)
    # the round that admits b commits the step in flight and dispatches
    # none (b's token is on the host); the next one dispatches, and fails
    d.step(2)
    monkeypatch.setitem(ent, "fn", real)
    c = d.submit(pc, max_new_tokens=6, seed=9, **SAMPLED)
    d.until_idle()
    assert (a.state, b.state, c.state) == ("shed", "shed", "done")
    return d


SCENARIOS = [admit_finish_by_length_and_stop, cancel_mid_decode,
             shed_at_prefill, sampled_beside_greedy,
             json_row_arrives_and_leaves, prefix_hit_with_copy_on_write,
             speculative_verify_with_rollback, lora_row_and_a_load,
             swap_weights_between_steps, retry_after_a_step_fault,
             pools_lost]


@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda f: f.__name__)
def test_resident_and_rebuilt_inputs_are_one_behaviour(
        scenario, model, monkeypatch):
    kept = scenario(model, False, monkeypatch)
    rebuilt = scenario(model, True, monkeypatch)
    assert len(kept.log) == len(rebuilt.log) > 3
    for n, (x, y) in enumerate(zip(kept.log, rebuilt.log)):
        assert x == y, f"the two runs part at step {n}"
    # the kept run had resident dispatches (or the case shows nothing);
    # the rebuilt run none but a step dispatched ahead in the round of a
    # step built from the host, which feeds on the device's arrays by
    # construction
    st, st0 = kept.eng.stats(), rebuilt.eng.stats()
    assert st["inputs_dispatches"] == st0["inputs_dispatches"] > 0
    assert st["inputs_resident"] > st0["inputs_resident"]
    assert st0["inputs_resident"] <= st0["ahead_dispatches"]
    assert st0["inputs_resident"] < st0["inputs_dispatches"] / 2
    kept.eng.cache.flush_prefix_cache()
    assert kept.eng.cache.allocator.leaked() == 1    # trash block only


# ------------------------------------------------- what a step copies

def _count_sends(eng, monkeypatch):
    """Record, for every ``engine._send``, the bytes of each array."""
    sent, real = [], eng._send

    def send(host):
        sent.extend(int(np.asarray(a).nbytes)
                    for a in jax.tree_util.tree_leaves(host))
        return real(host)

    monkeypatch.setattr(eng, "_send", send)
    return sent


def test_a_steady_step_copies_its_lengths_and_nothing_else(
        model, monkeypatch):
    eng = _engine(model)
    sent = _count_sends(eng, monkeypatch)
    reqs = [eng.submit(p, max_new_tokens=12, **kw) for p, kw in zip(
        _prompts((5, 7), seed=20), ({}, dict(seed=3, **SAMPLED)))]
    eng.step()        # prefill both, first decode: everything is sent
    first = list(sent)
    assert max(first) == eng.max_slots * VOCAB * 4    # the zero mask
    for _ in range(6):
        del sent[:]
        eng.step()
        assert sent == [eng.max_slots * 4], sent      # lengths, i32
        assert max(sent) <= 64
    assert all(r.state == "running" for r in reqs)
    # a third request joins: the small per-request operands and the
    # tables go again, the mask does not
    eng.submit(_prompts((4,), seed=21)[0], max_new_tokens=4)
    del sent[:]
    eng.step()      # its prefill and, behind it, the step it joins (its
    #                 first token and key are merged on the device: PR 48)
    assert 1 < len(sent) and max(sent) < eng.max_slots * VOCAB * 4
    del sent[:]
    eng.step()      # and the batch stands again
    assert sent == [eng.max_slots * 4], sent
    # a verify's tree of K+1 tokens comes from the host, its keys do not
    spec = _engine(model, spec_tokens=2)
    sent = _count_sends(spec, monkeypatch)
    spec.submit(_prompts((6,), seed=22)[0], max_new_tokens=12)
    spec.step()
    del sent[:]
    spec.step()
    assert sorted(sent) == [spec.max_slots * 4, spec.max_slots * 3 * 4]


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_the_counter_reads_what_the_scenario_implies(model, kind):
    """Two requests admitted together, then a steady batch until both
    end on the same step: only the first dispatch sends its operands."""
    monitor.reset()
    kw = {"decode": {}, "verify": dict(spec_tokens=2)}[kind]
    eng = _engine(model, **kw)
    for p in _prompts((5, 7), seed=30):
        eng.submit(p, max_new_tokens=7)
    eng.run_until_idle()
    st = eng.stats()
    assert st["inputs_dispatches"] == st["sampler_dispatches"]
    assert st["inputs_resident"] == st["inputs_dispatches"] - 1
    if kind == "decode":
        assert st["inputs_dispatches"] == 6      # 7 tokens, 1 by prefill
    assert monitor.stat_get("STAT_serving_inputs_resident") == \
        st["inputs_resident"]
    # a fresh batch: one more dispatch that sends
    eng.submit(_prompts((6,), seed=31)[0], max_new_tokens=3)
    eng.run_until_idle()
    st2 = eng.stats()
    assert (st2["inputs_dispatches"] - st2["inputs_resident"]) == 2


def test_resident_operands_cost_no_second_trace_or_executable():
    """Host arrays on the first dispatch of a batch, the device's own
    arrays after: one trace and one executable an entry, single step
    and verify alike."""
    m = _make_model(5)      # entries of its own, so the counts are these
    for kw, entry in (({}, lambda: decode_step_paged(m)),
                      (dict(spec_tokens=2),
                       lambda: verify_step_paged(m, 2))):
        eng = _engine(m, **kw)
        eng.submit(_prompts((5,), seed=40)[0], max_new_tokens=9)
        eng.submit(_prompts((6,), seed=41)[0], max_new_tokens=9,
                   seed=2, **SAMPLED)
        eng.step()
        eng.step()
        eng.submit(_prompts((4,), seed=42)[0], max_new_tokens=4)
        eng.run_until_idle()
        ent = entry()
        assert eng.stats()["inputs_resident"] > 0
        assert ent["traces"]["count"] == 1
        assert ent["fn"].raw.jitted._cache_size() == 1


def test_a_table_write_moves_the_version_and_nothing_else_does():
    from paddle_tpu.serving.kv_cache import BlockKVCache
    c = BlockKVCache(2, 4, 8, max_slots=2, max_len=16, block_size=4)
    v0 = c.tables_version
    row, _ = c.acquire(list(range(1, 7)), 10)
    assert c.tables_version == v0 + 1
    before = c.tables.copy()
    c.commit_prefill(row, 6)
    c.advance(row, 3)
    c.rollback(row, 2)
    c.insert_prefix(row, list(range(1, 7)))
    assert c.tables_version == v0 + 1
    assert (c.tables == before).all()
    rec = c.export_row(row)
    assert c.tables_version == v0 + 2
    row2 = c.import_row(rec)
    assert c.tables_version == v0 + 3 and (c.tables[row2] ==
                                           before[row]).all()
    c.release_row(row2)
    assert c.tables_version == v0 + 4
    assert (c.tables == c.TRASH).all()


def test_under_a_mesh_resident_operands_are_replicated(model):
    """What the entries' ``in_shardings`` name: a resident operand that
    carried another sharding would be re-sharded on every call."""
    from paddle_tpu.distributed.sharding import serving_mesh
    m = _make_model(7)
    eng = _engine(m, mesh=serving_mesh(1, 2))
    plain = _engine(model)
    p = _prompts((6,), seed=50)[0]
    r = eng.submit(p, max_new_tokens=8, seed=4, **SAMPLED)
    ref = plain.submit(p, max_new_tokens=8, seed=4, **SAMPLED)
    eng.step()
    eng.step()
    held = [a for _, v in eng._res.values()
            for a in jax.tree_util.tree_leaves(v)]
    held += [eng._carry[1], eng._carry[2]]
    assert len(held) >= 7
    assert all(a.sharding.is_equivalent_to(eng._repl, a.ndim)
               for a in held)
    eng.run_until_idle()
    plain.run_until_idle()
    assert r.tokens == ref.tokens
    assert eng.stats()["inputs_resident"] == 6
