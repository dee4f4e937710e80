"""The hard-label cross-entropy reads its logits once each way (PR 56).

``softmax_with_cross_entropy`` computes its loss from three reductions
over the logits in the dtype they arrive in (float32 statistics) and has a
grad lowering of its own, ``g * (softmax - onehot)``. The oracle is the
lowering the op had to PR 55, kept HERE: ``log_softmax`` +
``take_along_axis``, differentiated by ``jax.vjp`` as the registry's
generic grad did. The op alone is held to it in float32 and in bfloat16;
a GPT under AMP O2 is held to it eagerly, under ``jit.to_static`` and
under ``zero_train_step(stage=2)`` on four virtual devices (the old
lowering monkeypatched in for the comparison); a static-graph program
that records the op trains as it did.

That the compiled step WRITES no float32 array of the logits' size is a
property of the TPU compiler's fusions and is held where that compiler
is: ``tests/test_chip_compile.py`` (the CPU backend computes a bfloat16
product in float32 and fuses nothing into a product's input, so its HLO
says nothing about the chip's).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import amp, jit
from paddle_tpu.distributed import zero
from paddle_tpu.framework import unique_name
from paddle_tpu.models.gpt import GPT_CONFIGS, GPTForCausalLM
from paddle_tpu.ops import registry
from paddle_tpu.optimizer import SGD

OP = "softmax_with_cross_entropy"


def old_lowering(ctx, ins, attrs):
    """The op's lowering to PR 55, word for word."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1)
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    logp = jax.nn.log_softmax(logits, axis=axis)
    softmax = jnp.exp(logp)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logits.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis)
        valid = lbl != ignore_index
        n_class = logits.shape[axis]
        safe_lbl = jnp.clip(jnp.where(valid, lbl, 0), 0, n_class - 1)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(safe_lbl, axis).astype(jnp.int32),
            axis=axis)
        loss = jnp.where(jnp.expand_dims(valid, axis), -picked, 0.0)
    return {"Softmax": [softmax], "Loss": [loss]}


def oracle(logits, label, g, attrs):
    """(Loss, Softmax, Logits@GRAD) of the old lowering under jax.vjp."""
    def fwd(z):
        out = old_lowering(None, {"Logits": [z], "Label": [label]}, attrs)
        return out["Loss"][0], out["Softmax"][0]
    loss, vjp, softmax = jax.vjp(fwd, logits, has_aux=True)
    return loss, softmax, vjp(g.astype(loss.dtype))[0]


def the_op(logits, label, g, attrs):
    ctx = registry.LoweringContext()
    ins = {"Logits": [logits], "Label": [label]}
    out = registry.execute(ctx, OP, ins, attrs)
    grad = registry.execute(ctx, OP + "_grad", {**ins, "Loss@GRAD": [g]},
                            attrs)
    return out["Loss"][0], out["Softmax"][0], grad["Logits@GRAD"][0]


def _case(name):
    """-> (logits, label, attrs) of one float32 case; every case gets a
    non-uniform ``Loss@GRAD`` of its loss's shape."""
    rng = np.random.RandomState(56)
    z = (3.0 * rng.randn(12, 40)).astype(np.float32)
    lab = rng.randint(0, 40, (12,)).astype(np.int64)
    attrs = {"soft_label": False, "ignore_index": -100, "axis": -1}
    if name == "ignored_labels":
        lab[[1, 5, 11]] = -100
    elif name == "other_ignore_index":
        lab[[0, 7]] = 3
        attrs["ignore_index"] = 3
    elif name == "label_out_of_range":
        lab[2], lab[9] = 47, -5
    elif name == "axis_not_last":
        z = (3.0 * rng.randn(4, 40, 6)).astype(np.float32)
        lab = rng.randint(0, 40, (4, 6)).astype(np.int64)
        lab[1, 2] = -100
        attrs["axis"] = 1
    elif name == "axis_not_last_label_keeps_it":
        z = (3.0 * rng.randn(4, 40, 6)).astype(np.float32)
        lab = rng.randint(0, 40, (4, 1, 6)).astype(np.int64)
        attrs["axis"] = 1
    elif name == "trailing_label_dimension":
        lab = lab.reshape(12, 1)
        lab[4, 0] = -100
    elif name == "large_logits":
        z = z * 40.0        # exp overflows without the row maximum
    else:
        assert name == "plain"
    return jnp.asarray(z), jnp.asarray(lab), attrs


CASES = ["plain", "ignored_labels", "other_ignore_index",
         "label_out_of_range", "axis_not_last",
         "axis_not_last_label_keeps_it", "trailing_label_dimension",
         "large_logits"]


@pytest.fixture(scope="module")
def float32_results():
    out = {}
    for name in CASES:
        z, lab, attrs = _case(name)
        shape = list(z.shape)
        shape[attrs["axis"]] = 1
        g = jnp.asarray(np.random.RandomState(7).uniform(
            0.2, 1.5, shape).astype(np.float32))
        # float32 carries 1e-6 of a logit of ~10: the exponent's argument,
        # and so every result, is exact to that share of the largest one
        out[name] = (the_op(z, lab, g, attrs), oracle(z, lab, g, attrs),
                     1e-6 * max(1.0, float(jnp.abs(z).max()) / 10.0))
    return out


@pytest.mark.parametrize("which", ["Loss", "Softmax", "Logits@GRAD"])
@pytest.mark.parametrize("name", CASES)
def test_in_float32_the_op_is_the_old_lowering(float32_results, name, which):
    got, want, atol = float32_results[name]
    i = ["Loss", "Softmax", "Logits@GRAD"].index(which)
    assert got[i].shape == want[i].shape and got[i].dtype == want[i].dtype
    np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[i]),
                               rtol=0, atol=atol)


def test_an_ignored_row_gets_no_loss_and_no_gradient(float32_results):
    loss, _, grad = float32_results["ignored_labels"][0]
    for row in (1, 5, 11):
        assert float(loss[row, 0]) == 0.0
        assert not np.asarray(grad[row]).any()


@pytest.fixture(scope="module")
def bfloat16_results():
    rng = np.random.RandomState(3)
    z = jnp.asarray((4.0 * rng.randn(16, 512)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    lab = jnp.asarray(rng.randint(0, 512, (16, 1)).astype(np.int64))
    lab = lab.at[3, 0].set(-100)
    attrs = {"soft_label": False, "ignore_index": -100, "axis": -1}
    g = jnp.asarray(rng.uniform(0.2, 1.5, (16, 1)).astype(np.float32))
    return (the_op(z, lab, g, attrs),
            oracle(z.astype(jnp.float32), lab, g, attrs), z)


def test_bfloat16_logits_give_a_float32_loss(bfloat16_results):
    """The statistics are float32 whatever arrives: the loss is the
    float32 oracle's on the same rounded logits (a bfloat16 loss near 7
    would stand 0.03 off)."""
    (loss, softmax, _), (want, _, _), z = bfloat16_results
    assert loss.dtype == jnp.float32 and softmax.dtype == z.dtype
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want),
                               rtol=0, atol=1e-3)


def test_bfloat16_logits_get_a_gradient_rounded_once(bfloat16_results):
    (_, _, grad), (_, _, want), z = bfloat16_results
    assert grad.dtype == z.dtype
    want = np.asarray(want)
    # one rounding to bfloat16 (8 bits of mantissa): half a unit in the
    # last place is 2**-9 of the value
    np.testing.assert_allclose(np.asarray(grad, np.float32), want,
                               rtol=2.0 ** -8, atol=1e-30)


def test_a_cotangent_on_softmax_is_refused():
    z, lab, attrs = _case("plain")
    ins = {"Logits": [z], "Label": [lab], "Loss@GRAD": [jnp.ones((12, 1))],
           "Softmax@GRAD": [jnp.ones_like(z)]}
    with pytest.raises(NotImplementedError, match="cotangent on Loss only"):
        registry.execute(registry.LoweringContext(), OP + "_grad", ins, attrs)


def test_soft_labels_keep_their_lowering_and_the_generic_gradient():
    rng = np.random.RandomState(5)
    z = jnp.asarray(rng.randn(6, 10).astype(np.float32))
    soft = jax.nn.softmax(jnp.asarray(rng.randn(6, 10).astype(np.float32)))
    attrs = {"soft_label": True, "axis": -1}
    g = jnp.asarray(rng.uniform(0.5, 1.5, (6, 1)).astype(np.float32))
    got, want = the_op(z, soft, g, attrs), oracle(z, soft, g, attrs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# -- a GPT under AMP O2, in the three ways a step is run --------------------

MODES = ["eager", "to_static", "zero2_dp4"]


def _gpt_step(mode):
    """One step of ``gpt2-tiny`` on a fixed batch -> (loss, {parameter:
    gradient}); the same seed gives the same weights every call."""
    with unique_name.guard():
        pt.seed(56)
        model = GPTForCausalLM(GPT_CONFIGS["gpt2-tiny"])
        opt = SGD(learning_rate=0.0, parameters=model.parameters())
    model.train()

    def train_step(ids, labels):
        with amp.auto_cast(level="O2"):
            loss = model(ids, labels=labels)
        model.clear_gradients()
        loss.backward()
        opt.step()
        return loss

    rng = np.random.RandomState(1)
    ids = rng.randint(0, 1024, (4, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    labels[:, -1] = -100
    step = train_step
    if mode == "to_static":
        step = jit.to_static(train_step, layers=[model], optimizers=[opt])
    elif mode == "zero2_dp4":
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
        step = zero.zero_train_step(
            train_step, layers=[model], optimizers=[opt], mesh=mesh,
            stage=2, arg_specs=(P("dp"), P("dp")))
    if mode == "eager":
        loss = step(pt.to_tensor(ids), pt.to_tensor(labels))
    else:
        loss = step(ids, labels)
    grads = {name: np.asarray(p.grad.value, np.float32)
             for name, p in model.named_parameters()}
    return float(np.asarray(loss.value, np.float32)), grads


@pytest.fixture(scope="module")
def gpt_steps():
    """Every mode's step with the op as it stands and with the old
    lowering in its place (and no grad lowering, so the registry's generic
    vjp serves it, as to PR 55)."""
    new = {mode: _gpt_step(mode) for mode in MODES}
    d = registry.OPS[OP]
    was, grad = d.lowering, registry.OPS.pop(OP + "_grad")
    d.lowering = old_lowering
    try:
        old = {mode: _gpt_step(mode) for mode in MODES}
    finally:
        d.lowering = was
        registry.OPS[OP + "_grad"] = grad
    return new, old


@pytest.mark.parametrize("mode", MODES)
def test_a_gpt_step_under_amp_keeps_its_loss_and_every_gradient(gpt_steps,
                                                                mode):
    """The logits are bfloat16 and both lowerings round the gradient to
    bfloat16 once, from float32 values that differ in their last bits: a
    value at a rounding boundary may fall to the other side (2**-8 of
    it) and the bfloat16 backward behind it carries that on, so a whole
    parameter's gradient is held to bfloat16's 2**-7 of its norm (read:
    up to 3.7e-3 eagerly, 2.7e-3 under the mesh, 0 under to_static; a
    dropped one-hot or mask stands at 1)."""
    (loss, grads), (want_loss, want) = gpt_steps[0][mode], gpt_steps[1][mode]
    assert np.isfinite(loss) and abs(loss - want_loss) <= 2e-6 * want_loss
    assert sorted(grads) == sorted(want) and len(grads) > 20
    for name, g in grads.items():
        norm = np.linalg.norm(want[name])
        assert norm > 0, name
        assert np.linalg.norm(g - want[name]) <= 2.0 ** -7 * norm, name


@pytest.mark.parametrize("mode", MODES[1:])
def test_a_compiled_gpt_step_is_the_eager_step(gpt_steps, mode):
    """One program fuses its bfloat16 chain otherwise than op by op: read
    1.3e-2 and 1.8e-2 of a gradient's norm at the most."""
    (loss, grads), (want_loss, want) = gpt_steps[0][mode], gpt_steps[0]["eager"]
    assert abs(loss - want_loss) <= 1e-3 * want_loss
    for name, g in grads.items():
        assert np.linalg.norm(g - want[name]) <= \
            5e-2 * np.linalg.norm(want[name]), name


# -- a static-graph program that records the op ----------------------------

def _static_losses(steps=8):
    from paddle_tpu import layers
    from paddle_tpu.framework import Executor, Program, Scope, program_guard
    from paddle_tpu.optimizer import SGD as StaticSGD
    main, startup = Program(), Program()
    with unique_name.guard(), program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        y = layers.data("y", [1], dtype="int64")
        logits = layers.fc(x, 10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        StaticSGD(learning_rate=0.5).minimize(loss)
    assert OP + "_grad" in [op.type for op in main.global_block().ops]
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(32, 16).astype(np.float32),
            "y": rng.randint(0, 10, (32, 1)).astype(np.int64)}
    scope, exe = Scope(), Executor()
    startup.random_seed = main.random_seed = 11
    exe.run(startup, scope=scope)
    return [float(exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0])
            for _ in range(steps)]


def test_a_static_program_with_the_op_trains_as_before(monkeypatch):
    got = _static_losses()
    assert got[-1] < 0.7 * got[0]
    monkeypatch.setattr(registry.OPS[OP], "lowering", old_lowering)
    monkeypatch.delitem(registry.OPS, OP + "_grad")
    np.testing.assert_allclose(got, _static_losses(), rtol=1e-5)


# -- the reading of a compiled step (tools/train_step_ops.py) ---------------

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,16], p1: bf16[32,16]) -> (f32[8], bf16[8,32]) {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %p1 = bf16[32,16]{1,0} parameter(1)
  %convolution.3 = bf16[8,32]{1,0} convolution(%p0, %p1), dim_labels=bf_oi->bf
  %convert.9 = f32[8,32]{1,0} convert(%convolution.3), metadata={op_name="jit(step)/vocab_loss/convert_element_type"}
  %reduce.2 = f32[8]{0} reduce(%convert.9, %c), dimensions={1}, to_apply=%max
  ROOT %tuple.4 = (f32[8]{0}, bf16[8,32]{1,0}) tuple(%reduce.2, %convolution.3)
}

ENTRY %main.7 (h: bf16[8,16], w: bf16[32,16]) -> f32[8,32] {
  %h = bf16[8,16]{1,0} parameter(0)
  %w = bf16[32,16]{1,0} parameter(1)
  %fusion.5 = (f32[8]{0}, bf16[8,32]{1,0}) fusion(%h, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/dot_general"}
  %gte.1 = bf16[8,32]{1,0} get-tuple-element(%fusion.5), index=1
  ROOT %convert.11 = f32[8,32]{1,0} convert(%gte.1), metadata={op_name="jit(step)/vocab_loss/exp"}
}
"""


def test_the_step_reader_tells_a_written_array_from_a_fused_value():
    """A float32 value of the logits' size inside a fused computation is a
    register's; the same type as an instruction's result outside one is
    1.65 GB written at the cells' shape. ROOT instructions count."""
    from tools import train_step_ops
    table = train_step_ops.instructions(HLO)
    assert table["convert.9"]["fused"] and not table["convert.11"]["fused"]
    assert table["fusion.5"]["convolutions"] == 1
    assert table["fusion.5"]["kind"] == "kOutput"
    assert table["convert.11"]["op_name"].endswith("vocab_loss/exp")
    assert train_step_ops.written_float32(table, 8 * 32, 32) == [
        "convert.11: f32[8,32]"]
    assert train_step_ops.written_float32(table, 8 * 32, 16) == []


def test_the_step_reader_sums_a_trace_by_instruction():
    import json
    import os
    from perfbench import xplane
    from tools import train_step_ops
    path = os.path.join(os.path.dirname(xplane.__file__), "tests", "data",
                        "v5e_trace.json")
    with open(path) as f:
        trace = json.load(f)
    ops = train_step_ops.device_ops(trace, {"fusion.53": {"kind": "kCustom"}},
                                    steps=2)
    events = [e for p in trace["planes"] if p["name"].startswith("/device:TPU")
              for ln in p["lines"] if ln["name"] == xplane.OPS_LINE
              for e in ln["events"]]
    assert abs(sum(o["ms_a_step"] for o in ops) * 2
               - sum(e[2] for e in events) * 1e-6) < 1e-6
    assert ops[0]["ms_a_step"] >= ops[1]["ms_a_step"]
    assert ops[0]["instruction"] == "fusion.53" and ops[0]["kind"] == "kCustom"
