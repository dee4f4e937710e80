"""ZeRO-sharded optimizer plane (distributed/zero.py).

The acceptance contract from the train->serve loop PR: stage 1/2
``zero_train_step`` matches the unsharded step loss-for-loss while the
per-device optimizer bytes drop to ~1/dp, checkpoints of the sharded
state round-trip through ``CheckpointSaver`` layout-free, and the
whole thing stays a single ``tracked_jit`` site (one compile for the
steady train loop).
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import jit, observability as obs
from paddle_tpu.distributed import zero
from paddle_tpu.distributed.sharding import (GPT_TENSOR_PARALLEL_RULES,
                                             ShardingRules,
                                             estimate_zero_opt_bytes,
                                             opt_state_shardings,
                                             zero_partition_spec)
from paddle_tpu.framework import unique_name
from paddle_tpu.incubate.checkpoint import CheckpointSaver
from paddle_tpu.jit import _StateSpec
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.optimizer import AdamW

# every tensor dim divisible by the dp axis sizes used below, so the
# ZeRO layouts shard everything except the (1,)-shaped beta-pow scalars
CFG = dict(vocab_size=128, max_position_embeddings=32, hidden_size=32,
           num_layers=2, num_heads=4, ffn_hidden_size=64)


def _mesh(shape, names=("dp", "mp")):
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


def _build(seed=0):
    """Model+AdamW with deterministic params AND deterministic
    parameter names (unique_name.guard), so optimizer state_dicts keyed
    by param name line up across fresh builds."""
    with unique_name.guard():
        pt.seed(seed)
        model = GPTForCausalLM(GPTConfig(**CFG))
        opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    return model, opt


def _train_fn(model, opt):
    def train_step(ids, labels):
        loss = model(ids, labels=labels)
        model.clear_gradients()
        loss.backward()
        opt.step()
        return loss
    return train_step


def _data(steps=3, batch=4, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        ids = rng.randint(0, CFG["vocab_size"], (batch, seq))
        out.append((ids.astype(np.int32),
                    np.roll(ids, -1, axis=1).astype(np.int32)))
    return out


# -- layout units --------------------------------------------------------


def test_zero_partition_spec_shards_first_free_divisible_dim():
    mesh = _mesh((2, 2))
    assert zero_partition_spec((64, 32), mesh) == P("dp", None)
    # base rule already owns dim 0 -> dp lands on dim 1
    assert zero_partition_spec((64, 32), mesh,
                               base=P("mp")) == P("mp", "dp")
    # base leaves dim 0 free -> dp composes in front of mp
    assert zero_partition_spec((64, 32), mesh,
                               base=P(None, "mp")) == P("dp", "mp")


def test_zero_partition_spec_fallbacks():
    mesh = _mesh((2, 2))
    # indivisible dim: replicated fallback, base preserved
    assert zero_partition_spec((97,), mesh) == P()
    assert zero_partition_spec((97, 3), mesh) == P()
    # beta-pow style scalars replicate (1 < axis size)
    assert zero_partition_spec((1,), mesh) == P()
    # axis of size 1: nothing to shard, base returned untouched
    assert zero_partition_spec((64,), _mesh((1, 2))) == P()


def test_opt_state_shardings_moments_sharded_scalars_replicated():
    model, opt = _build()
    ids, labels = _data(steps=1)[0]
    _train_fn(model, opt)(ids, labels)   # eager step materializes state
    mesh = _mesh((2, 1))
    spec = _StateSpec([model], [opt])
    shardings = opt_state_shardings(spec, mesh, ShardingRules([]),
                                    axis="dp", stage=1)
    assert len(shardings) == 1
    sharded = replicated = 0
    for (pid, key), v in opt._eager_state.items():
        sh = shardings[0][spec.opt_key((pid, key))]
        if tuple(v.shape) == (1,):
            assert sh.spec == P(), f"scalar {key} must replicate"
            replicated += 1
        else:
            assert "dp" in jax.tree_util.tree_leaves(tuple(sh.spec)), \
                f"moment {key} of shape {v.shape} not dp-sharded"
            sharded += 1
    assert sharded and replicated


def test_estimate_zero_opt_bytes_matches_live_state():
    """The static estimator (what tools/lint_sharding.py prints) must
    agree with the bytes the live optimizer actually holds."""
    model, opt = _build()
    ids, labels = _data(steps=1)[0]
    _train_fn(model, opt)(ids, labels)
    mesh = {"dp": 2, "mp": 1}
    est = estimate_zero_opt_bytes(model, mesh, ShardingRules([]),
                                  axis="dp", stage=1)
    live_total = sum(int(np.asarray(v).nbytes)
                     for v in opt._eager_state.values())
    assert est["opt_bytes"] == live_total
    assert est["opt_bytes_per_device"] < est["opt_bytes"]


# -- loss parity + the memory win ----------------------------------------


@pytest.mark.parametrize("stage", [1, 2])
def test_zero_loss_parity_and_opt_bytes_halved(stage):
    """dp=2: stage-1/2 losses match the unsharded step; per-device
    optimizer bytes land at ~1/2 of the total (scalars replicate)."""
    ref_model, ref_opt = _build()
    ref_step = jit.to_static(_train_fn(ref_model, ref_opt),
                             layers=[ref_model], optimizers=[ref_opt])

    z_model, z_opt = _build()
    mesh = _mesh((2, 1))
    z_step = zero.zero_train_step(
        _train_fn(z_model, z_opt), layers=[z_model], optimizers=[z_opt],
        mesh=mesh, stage=stage, arg_specs=(P("dp"), P("dp")))

    for step, (ids, labels) in enumerate(_data()):
        ref_loss = float(np.asarray(ref_step(ids, labels).value))
        z_loss = float(np.asarray(z_step(ids, labels).value))
        assert np.isfinite(z_loss)
        np.testing.assert_allclose(
            z_loss, ref_loss, rtol=2e-3,
            err_msg=f"ZeRO-{stage} loss diverged at step {step}")

    rep = z_step.byte_report()
    ref_rep = zero.byte_report([ref_model], [ref_opt], publish=False)
    assert rep["opt_bytes"] == ref_rep["opt_bytes"]
    # the ZeRO win: moments halve per device; only the (1,) scalars and
    # any indivisible leftovers replicate, so the ratio sits just above
    # 0.5 and far below the replicated 1.0
    ratio = rep["opt_bytes_per_device"] / rep["opt_bytes"]
    assert 0.5 <= ratio < 0.6, f"per-device opt ratio {ratio:.3f}"
    # params stay fully replicated at dp-only sharding
    assert rep["param_bytes_per_device"] == rep["param_bytes"]


def test_zero_composes_with_tensor_parallel_rules():
    """ZeRO over dp x Megatron TP over mp on a 2x2 mesh: parity holds
    and the moments shard over BOTH axes (per-device < 1/2 total)."""
    ref_model, ref_opt = _build()
    ref_step = jit.to_static(_train_fn(ref_model, ref_opt),
                             layers=[ref_model], optimizers=[ref_opt])

    z_model, z_opt = _build()
    mesh = _mesh((2, 2))
    z_step = zero.zero_train_step(
        _train_fn(z_model, z_opt), layers=[z_model], optimizers=[z_opt],
        mesh=mesh, param_rules=GPT_TENSOR_PARALLEL_RULES, stage=1,
        arg_specs=(P("dp"), P("dp")))

    for ids, labels in _data():
        ref_loss = float(np.asarray(ref_step(ids, labels).value))
        z_loss = float(np.asarray(z_step(ids, labels).value))
        np.testing.assert_allclose(z_loss, ref_loss, rtol=2e-3)

    rep = z_step.byte_report()
    assert rep["opt_bytes_per_device"] < 0.5 * rep["opt_bytes"]
    # TP shards the params too — the param bytes also drop per device
    assert rep["param_bytes_per_device"] < rep["param_bytes"]


def test_zero_single_compile_and_gauges():
    """3 steady-state steps = exactly one zero_train_step compile, and
    the byte gauges are published with the stage label."""
    model, opt = _build()
    mesh = _mesh((2, 1))
    step = zero.zero_train_step(
        _train_fn(model, opt), layers=[model], optimizers=[opt],
        mesh=mesh, stage=1, arg_specs=(P("dp"), P("dp")))
    def _site_count():
        return sum(e["count"] for k, e in obs.compiles().items()
                   if k.startswith("zero_train_step"))

    before_n = _site_count()
    for ids, labels in _data():
        step(ids, labels)
    after_n = _site_count()
    # grads are absent on the first call and present after -> the step
    # traces at most twice, and never per-step
    assert 1 <= after_n - before_n <= 2
    gauges = str(obs.snapshot()["gauges"])
    assert "zero_param_bytes_per_device" in gauges
    assert "zero_opt_bytes_per_device" in gauges


# -- stage selection -----------------------------------------------------


def test_resolve_stage_flag_and_validation():
    assert zero.resolve_stage(None) == 0       # flag default
    assert zero.resolve_stage(2) == 2
    with pytest.raises(ValueError):
        zero.resolve_stage(3)
    saved = pt.get_flags(["zero_stage"])
    try:
        pt.set_flags({"zero_stage": 2})
        assert zero.resolve_stage(None) == 2
    finally:
        pt.set_flags(saved)


def test_stage0_delegates_to_plain_to_static():
    ref_model, ref_opt = _build()
    ref_step = jit.to_static(_train_fn(ref_model, ref_opt),
                             layers=[ref_model], optimizers=[ref_opt])
    z_model, z_opt = _build()
    z_step = zero.zero_train_step(
        _train_fn(z_model, z_opt), layers=[z_model], optimizers=[z_opt],
        mesh=None, stage=0)
    for ids, labels in _data(steps=2):
        ref_loss = float(np.asarray(ref_step(ids, labels).value))
        z_loss = float(np.asarray(z_step(ids, labels).value))
        np.testing.assert_allclose(z_loss, ref_loss, rtol=1e-6)
    rep = z_step.byte_report()
    assert rep["stage"] == 0
    assert rep["opt_bytes_per_device"] == rep["opt_bytes"]


def test_stage_requires_mesh():
    model, opt = _build()
    with pytest.raises(ValueError, match="mesh"):
        zero.zero_train_step(_train_fn(model, opt), layers=[model],
                             optimizers=[opt], mesh=None, stage=1)


# -- checkpoint round-trip ----------------------------------------------


def test_sharded_checkpoint_roundtrip(tmp_path):
    """Train 2 ZeRO-1 steps on dp=2, gather-save, restore into a fresh
    replica: params AND optimizer moments match bit-for-bit, and the
    next step computes the same loss."""
    model, opt = _build()
    mesh = _mesh((2, 1))
    step = zero.zero_train_step(
        _train_fn(model, opt), layers=[model], optimizers=[opt],
        mesh=mesh, stage=1, arg_specs=(P("dp"), P("dp")))
    data = _data(steps=3)
    for ids, labels in data[:2]:
        step(ids, labels)

    saver = CheckpointSaver(str(tmp_path), "zero", max_num=2)
    zero.save_train_state(saver, [model], [opt], 0,
                          meta={"zero_stage": 1})

    model2, opt2 = _build(seed=1)   # different init, same names
    meta = zero.load_train_state(saver, [model2], [opt2])
    assert meta is not None and meta["zero_stage"] == 1

    names = dict(model.named_parameters())
    for name, p2 in model2.named_parameters():
        np.testing.assert_array_equal(np.asarray(p2.value),
                                      np.asarray(names[name].value),
                                      err_msg=f"param {name}")
    sd, sd2 = opt.state_dict(), opt2.state_dict()
    assert set(sd) == set(sd2)
    for k in sd:
        np.testing.assert_allclose(np.asarray(sd2[k]), np.asarray(sd[k]),
                                   err_msg=f"opt state {k}")

    # the restored replica continues the run with identical dynamics
    ids, labels = data[2]
    loss_a = float(np.asarray(step(ids, labels).value))
    step2 = zero.zero_train_step(
        _train_fn(model2, opt2), layers=[model2], optimizers=[opt2],
        mesh=mesh, stage=1, arg_specs=(P("dp"), P("dp")))
    loss_b = float(np.asarray(step2(ids, labels).value))
    np.testing.assert_allclose(loss_b, loss_a, rtol=2e-3)


def test_weights_from_checkpoint_is_swap_state(tmp_path):
    model, opt = _build()
    ids, labels = _data(steps=1)[0]
    _train_fn(model, opt)(ids, labels)
    saver = CheckpointSaver(str(tmp_path), "pub")
    zero.save_train_state(saver, [model], [opt], 0)
    state, _meta = saver.load()
    weights = zero.weights_from_checkpoint(state)
    assert set(weights) == {n for n, _ in model.named_parameters()}
    for n, p in model.named_parameters():
        np.testing.assert_array_equal(weights[n], np.asarray(p.value))
