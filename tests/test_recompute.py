"""fleet.utils.recompute — dygraph activation rematerialization.

Parity target: python/paddle/distributed/fleet/utils/recompute.py
(RecomputeFunction). The TPU design runs the segment under jax.checkpoint
inside one tape op; these tests pin (1) gradient equality with the
non-recomputed graph, (2) parameter discovery through the abstract probe,
(3) the GPT recompute config end-to-end, (4) rng-replay stability with
dropout inside the segment."""

import collections
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, jit, observability
from paddle_tpu.distributed.fleet.utils import recompute
from paddle_tpu.models import (GPT_CONFIGS, LAGUNA_CONFIGS, GPTForCausalLM,
                               LagunaForCausalLM)
from paddle_tpu.observability import compile_tracker as ct
from paddle_tpu.ops.pallas.utils import FLASH_RESIDUAL_NAMES

RNG = np.random.RandomState(3)


def _grads(params):
    return [np.asarray(p.grad.value) for p in params]


def _clear(params):
    for p in params:
        p.clear_grad()


def test_recompute_grads_match_eager():
    m1 = pt.nn.Linear(6, 6)
    m2 = pt.nn.Linear(6, 3)
    params = m1.parameters() + m2.parameters()
    x = RNG.randn(4, 6).astype(np.float32)

    out = m2(pt.nn.functional.relu(m1(pt.dygraph.to_tensor(x))))
    (out ** 2).mean().backward()
    ref = _grads(params)
    _clear(params)

    h = recompute(lambda a: pt.nn.functional.relu(m1(a)),
                  pt.dygraph.to_tensor(x))
    (m2(h) ** 2).mean().backward()
    got = _grads(params)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


def test_recompute_multi_arg_multi_out():
    m = pt.nn.Linear(5, 5)
    a = pt.dygraph.to_tensor(RNG.randn(3, 5).astype(np.float32))
    b = pt.dygraph.to_tensor(RNG.randn(3, 5).astype(np.float32))
    a.stop_gradient = False
    b.stop_gradient = False

    def seg(x, y):
        h = m(x) + y
        return h, h * 2.0

    o1, o2 = recompute(seg, a, b)
    (o1.mean() + o2.mean()).backward()
    assert m.parameters()[0].grad is not None
    assert a.grad is not None and b.grad is not None
    np.testing.assert_allclose(np.asarray(b.grad.value), 3.0 / b.size,
                               rtol=1e-5)


def test_recompute_in_to_static_trains():
    m1 = pt.nn.Linear(6, 6)
    m2 = pt.nn.Linear(6, 1)
    opt = pt.optimizer.SGD(learning_rate=0.2,
                           parameters=m1.parameters() + m2.parameters())
    x = RNG.randn(8, 6).astype(np.float32)
    y = RNG.randn(8, 1).astype(np.float32)

    @pt.jit.to_static(layers=[m1, m2], optimizers=[opt])
    def step(xb, yb):
        h = recompute(lambda a: pt.nn.functional.relu(m1(a)),
                      pt.dygraph.to_tensor(xb))
        loss = ((m2(h) - pt.dygraph.to_tensor(yb)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    l0 = float(step(x, y).numpy())
    for _ in range(30):
        l1 = float(step(x, y).numpy())
    assert l1 < l0 * 0.3, (l0, l1)


def test_gpt_recompute_config_loss_parity():
    """gpt2-tiny with cfg.recompute=True computes the same loss/grads as
    the stored-activation path."""
    import dataclasses

    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM

    cfg = GPT_CONFIGS["gpt2-tiny"]
    ids = RNG.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    m_plain = GPTForCausalLM(cfg)
    m_rc = GPTForCausalLM(dataclasses.replace(cfg, recompute=True))
    m_rc.set_state_dict(m_plain.state_dict())

    l_plain = m_plain(pt.dygraph.to_tensor(ids),
                      labels=pt.dygraph.to_tensor(labels))
    l_rc = m_rc(pt.dygraph.to_tensor(ids),
                labels=pt.dygraph.to_tensor(labels))
    np.testing.assert_allclose(float(l_rc.numpy()), float(l_plain.numpy()),
                               rtol=1e-5)

    l_plain.backward()
    l_rc.backward()
    gp = {p.name.split(".")[-1] + str(i): p.grad
          for i, p in enumerate(m_plain.parameters())}
    for i, p in enumerate(m_rc.parameters()):
        ref = m_plain.parameters()[i].grad
        assert (p.grad is None) == (ref is None)
        if p.grad is not None:
            np.testing.assert_allclose(
                np.asarray(p.grad.value), np.asarray(ref.value),
                rtol=2e-4, atol=2e-6)


def test_recompute_with_dropout_rng_replay():
    """Dropout inside the segment: the rng draw must replay identically
    in the rematerialized backward — grads stay consistent with the
    actually-sampled mask (checked via grad of a linear-in-x segment:
    d/dx(mean(dropout(x))) equals mask/keep/size)."""
    x = pt.dygraph.to_tensor(RNG.randn(64, 64).astype(np.float32))
    x.stop_gradient = False
    drop = pt.nn.Dropout(0.5)
    drop.train()

    out = recompute(lambda a: drop(a), x)
    out.mean().backward()
    g = np.asarray(x.grad.value) * x.size
    # upscale_in_train: grad is 1/keep where kept, 0 where dropped
    vals = np.unique(np.round(g, 4))
    assert set(vals).issubset({0.0, 2.0}), vals
    kept = (g > 0).mean()
    assert 0.3 < kept < 0.7
    # and the forward mask agrees with the gradient's mask
    fwd_mask = (np.asarray(out.value) != 0)
    np.testing.assert_array_equal(fwd_mask, g > 0)


# ------------------------------------------------------------------------
# A recomputed segment keeps its flash kernel's output and log-sum-exp
# (PR 53): the checkpoint's policy saves the two arrays the kernel's vjp
# names, the op's forward keeps the vjp and its grad op calls it, so the
# backward rebuilds q, k and v but does not run the forward kernel again.
# ``_KEEP_FLASH = None`` is the bare ``jax.checkpoint`` the segment ran
# under before.

# the package re-exports the function under the module's name
rc = importlib.import_module("paddle_tpu.distributed.fleet.utils.recompute")

SEQ = 32
TINY = GPT_CONFIGS["gpt2-tiny"]
LAGUNA = LAGUNA_CONFIGS["laguna-tiny"]


@pytest.fixture
def flash_from_seq_32():
    """The flash kernels take over at the toy sequences too."""
    was = flags.get_flag("pallas_min_seq")
    pt.set_flags({"pallas_min_seq": SEQ})
    yield
    pt.set_flags({"pallas_min_seq": was})


@pytest.fixture(params=["keep_flash", "bare"])
def policy(request, monkeypatch):
    """Both checkpoints: the module's one policy, and none (the parent's)."""
    if request.param == "bare":
        monkeypatch.setattr(rc, "_KEEP_FLASH", None)
    return request.param


def _gpt_block():
    return GPTForCausalLM(TINY).gpt.blocks[0], TINY.hidden_size


def _laguna_layer(layer):
    blk = LagunaForCausalLM(LAGUNA).model.layers[layer]
    assert (blk.attn.window > 0) == (layer == 1)
    assert blk.attn.q > blk.attn.kv                          # grouped KV
    return blk, LAGUNA.hidden_size


SEGMENTS = {"gpt_block": _gpt_block,
            "laguna_full_layer": lambda: _laguna_layer(0),
            "laguna_window_layer": lambda: _laguna_layer(1)}


def _loss_and_grads(block, x):
    """Eager loss and every gradient of ``recompute(block, x)``."""
    xt = pt.dygraph.to_tensor(x)
    xt.stop_gradient = False
    _clear(block.parameters())
    out = recompute(block, xt)
    out = out[0] if isinstance(out, tuple) else out   # a sparse layer's stats
    loss = (out ** 2).mean()
    loss.backward()
    grads = [xt.grad] + [p.grad for p in block.parameters()]
    assert all(g is not None for g in grads)
    return [np.asarray(loss.value)] + [np.asarray(g.value) for g in grads]


@pytest.mark.parametrize("segment", sorted(SEGMENTS))
def test_kept_flash_residuals_leave_loss_and_gradients_bit_equal(
        flash_from_seq_32, monkeypatch, segment):
    pt.seed(11)
    block, width = SEGMENTS[segment]()
    x = np.random.RandomState(11).randn(2, SEQ, width).astype(np.float32)
    got = _loss_and_grads(block, x)
    monkeypatch.setattr(rc, "_KEEP_FLASH", None)
    want = _loss_and_grads(block, x)
    assert len(got) == len(want) > 4
    for g, w in zip(got, want):
        assert np.abs(w).max() > 0
        np.testing.assert_array_equal(g, w)


def _kernels(jaxpr, out=None):
    """Counter of the ``pallas_call`` names in a jaxpr and all it holds."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernels(sub, out)
    return out


def _gradient_jaxpr(model, ids):
    """The jaxpr of loss and every parameter gradient through the tape."""
    params = model.parameters()
    labels = jnp.roll(ids, -1, axis=1)

    def grads(values):
        old = [p.value for p in params]
        try:
            for p, v in zip(params, values):
                p.value = v
            model.clear_gradients()
            loss = model(pt.dygraph.to_tensor(ids),
                         labels=pt.dygraph.to_tensor(labels))
            loss.backward()
            return loss.value, [p.grad.value for p in params]
        finally:
            for p, v in zip(params, old):
                p.value = v
            model.clear_gradients()
    return jax.make_jaxpr(grads)([p.value for p in params]).jaxpr


# kernel -> calls in the gradient of a recomputed model: a forward kernel
# once a block under the policy and twice under a bare checkpoint, each
# backward kernel once a block under both
KERNELS = {
    "gpt2-tiny": (lambda: GPTForCausalLM(
        dataclasses.replace(TINY, recompute=True)), TINY.vocab_size,
        {"flash_fwd": 2}, {"flash_bwd_dq": 2, "flash_bwd_dkv": 2}),
    "laguna-tiny": (lambda: LagunaForCausalLM(
        dataclasses.replace(LAGUNA, recompute=True)), LAGUNA.vocab_size,
        {"flash_fwd_full": 1, "flash_fwd_win": 2},
        {"flash_bwd_dq_full": 1, "flash_bwd_dkv_full": 1,
         "flash_bwd_dq_win": 2, "flash_bwd_dkv_win": 2}),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_the_gradient_runs_the_forward_kernel_once_a_block(
        flash_from_seq_32, policy, name):
    build, vocab, forward, backward = KERNELS[name]
    ids = jnp.asarray(np.random.RandomState(2).randint(0, vocab, (2, SEQ)),
                      jnp.int32)
    got = {k: v for k, v in _kernels(_gradient_jaxpr(build(), ids)).items()
           if k.startswith("flash")}
    twice = 1 if policy == "keep_flash" else 2
    assert got == {**{k: twice * v for k, v in forward.items()}, **backward}


class _Recorded(rc._Segment):
    made = []

    def __init__(self, *a):
        super().__init__(*a)
        _Recorded.made.append(self)


def _saved(function, x, monkeypatch):
    """Shapes of what a recomputed ``function(x)`` keeps for its backward."""
    monkeypatch.setattr(rc, "_Segment", _Recorded)
    monkeypatch.setattr(_Recorded, "made", [])
    xt = pt.dygraph.to_tensor(x)
    xt.stop_gradient = False
    recompute(function, xt)
    seg, = _Recorded.made
    return sorted((a.shape, str(a.dtype))
                  for a in jax.tree_util.tree_leaves(seg.vjp))


def test_a_segment_without_a_flash_call_saves_what_it_saved_before(
        monkeypatch):
    m1, m2 = pt.nn.Linear(6, 6), pt.nn.Linear(6, 6)
    x = RNG.randn(4, 6).astype(np.float32)

    def mlp(a):
        return m2(pt.nn.functional.relu(m1(a)))
    kept = _saved(mlp, x, monkeypatch)
    monkeypatch.setattr(rc, "_KEEP_FLASH", None)
    assert kept == _saved(mlp, x, monkeypatch)
    # the segment's input and the parameters its backward reads (the last
    # bias is not one of them): no activation
    assert kept == [((4, 6), "float32"), ((6,), "float32"),
                    ((6, 6), "float32"), ((6, 6), "float32")]


def test_a_segment_with_a_flash_call_saves_its_output_and_log_sum_exp(
        flash_from_seq_32, monkeypatch):
    pt.seed(5)
    block, width = _gpt_block()
    x = RNG.randn(2, SEQ, width).astype(np.float32)
    kept = collections.Counter(_saved(block, x, monkeypatch))
    monkeypatch.setattr(rc, "_KEEP_FLASH", None)
    bare = collections.Counter(_saved(block, x, monkeypatch))
    heads, d = TINY.num_heads, TINY.hidden_size // TINY.num_heads
    assert kept - bare == collections.Counter(
        {((2, heads, SEQ, d), "float32"): 1,
         ((2, heads, 1, SEQ), "float32"): 1})
    assert not bare - kept


def test_the_two_names_are_placed_by_the_flash_vjp_and_by_nothing_else():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.asarray(RNG.randn(1, 2, SEQ, 16), jnp.float32)

    def names(fn):
        return sorted(e.params["name"] for e in jax.make_jaxpr(fn)(q).eqns
                      if e.primitive.name == "name")
    loss = lambda a: flash_attention(a, a, a, causal=True).sum()  # noqa: E731
    assert names(loss) == []             # the primal a served prompt runs
    assert names(jax.grad(loss)) == sorted(FLASH_RESIDUAL_NAMES)
    windowed = lambda a: flash_attention(                         # noqa: E731
        a, a[:, :1], a[:, :1], causal=True, window=8, tag="win").sum()
    assert names(jax.grad(windowed)) == sorted(FLASH_RESIDUAL_NAMES)


def test_a_recomputed_flash_model_trains_under_to_static(flash_from_seq_32):
    pt.seed(7)
    model = GPTForCausalLM(dataclasses.replace(TINY, recompute=True))
    opt = pt.optimizer.SGD(learning_rate=0.5, parameters=model.parameters())
    ids = RNG.randint(0, TINY.vocab_size, (4, SEQ)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    @pt.jit.to_static(layers=[model], optimizers=[opt])
    def step(i, l):
        loss = model(i, labels=l)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    l0 = float(step(ids, labels).numpy())
    for _ in range(15):
        l1 = float(step(ids, labels).numpy())
    assert l1 < l0 * 0.7, (l0, l1)


# ---- PR 50's refusal, held by a test: one policy object, no more traces

EIGHT = dataclasses.replace(TINY, num_layers=8, recompute=True)


def test_every_segment_is_checkpointed_under_the_one_policy_object(
        monkeypatch):
    seen = []
    real = jax.checkpoint

    def spy(fn, **kw):
        seen.append(kw.get("policy"))
        return real(fn, **kw)
    monkeypatch.setattr(jax, "checkpoint", spy)
    model = GPTForCausalLM(EIGHT)
    ids = RNG.randint(0, TINY.vocab_size, (2, 16)).astype(np.int32)
    model(pt.dygraph.to_tensor(ids))
    assert len(seen) == 8
    assert all(p is rc._KEEP_FLASH and p is not None for p in seen)


def test_eight_recomputed_blocks_cost_one_trace_a_program_at_their_site(
        policy, monkeypatch):
    """The step's site traces once, for its one program (since PR 60 the
    optimizer's state is made before the first trace: to PR 59 it appeared
    after the first step and a second program was built) whatever the
    policy, and a segment's python runs twice a trace: the probe for its
    parameters and the checkpoint (the parent's backward op, ``jax.vjp``
    over the forward lowering, entered the checkpoint a second time)."""
    from paddle_tpu.models.gpt import GPTBlock
    runs, forward = [], GPTBlock.forward
    monkeypatch.setattr(GPTBlock, "forward",
                        lambda self, x: runs.append(1) or forward(self, x))
    pt.seed(9)
    model = GPTForCausalLM(EIGHT)
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    ids = RNG.randint(0, TINY.vocab_size, (2, 16)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    def train(i, l):
        loss = model(i, labels=l)
        model.clear_gradients()
        loss.backward()
        opt.step()
        return loss
    train.__name__ = f"eight_blocks_{policy}"
    step = jit.to_static(train, layers=[model], optimizers=[opt],
                         retain_grads=False)
    losses = [float(step(ids, labels).numpy()) for _ in range(3)]
    assert losses[-1] < losses[0]
    site = observability.compiles()[
        ct._qualname("to_static", {"py_fn": train.__name__})]
    assert site["count"] == 1 and site["programs"] == 1
    assert len(runs) == 2 * 8 * site["count"]


def test_the_kept_vjp_serves_nested_repeated_and_partial_backward():
    """The grad op calls the vjp its forward kept: a segment inside a
    segment, a second ``backward`` over a retained graph (it accumulates),
    ``pt.grad`` of an input, an output nobody differentiates, and a
    forward under ``no_grad`` (no vjp is taken) all read what the plain
    graph reads."""
    m1, m2 = pt.nn.Linear(6, 6), pt.nn.Linear(6, 6)
    params = m1.parameters() + m2.parameters()
    x = pt.dygraph.to_tensor(RNG.randn(4, 6).astype(np.float32))
    x.stop_gradient = False

    def inner(a):
        h = pt.nn.functional.relu(m1(a))
        return h, h * 2.0                   # the second output is unused

    def outer(a):
        return m2(recompute(inner, a)[0])
    (m2(inner(x)[0]) ** 2).mean().backward()
    want = _grads(params + [x])
    _clear(params + [x])

    loss = (recompute(outer, x) ** 2).mean()
    loss.backward(retain_graph=True)
    for g, w in zip(_grads(params + [x]), want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    loss.backward()
    np.testing.assert_allclose(np.asarray(x.grad.value), 2 * want[-1],
                               rtol=1e-5, atol=1e-6)
    gx, = pt.grad((recompute(outer, x) ** 2).mean(), [x])
    np.testing.assert_allclose(np.asarray(gx.value), want[-1], rtol=1e-5,
                               atol=1e-6)
    with pt.no_grad():
        assert recompute(outer, x).stop_gradient
