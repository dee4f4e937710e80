"""Jamba-class hybrid decoder (``models/jamba.py``) against its plain
reference (``perfbench/families/jamba.py``) at a small size on the CPU,
seeded random weights: the program's forward (logits and every layer's
output), the three forms of the selective scan against one another with
``last`` inside, at and before a chunk's edge, the convolution with a
carried tail, one token against the carried state, and the served
precision."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu import profiler                                # noqa: E402
from paddle_tpu.observability import compile_tracker           # noqa: E402
from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (JAMBA_CONFIGS, JambaConfig,     # noqa: E402
                               JambaForCausalLM)
from paddle_tpu.ops import ssm_ops                             # noqa: E402
from paddle_tpu.ops.pallas import selective_scan as kernel     # noqa: E402
from perfbench.families import jamba as family                 # noqa: E402

TINY = JAMBA_CONFIGS["jamba-tiny"]


def file_of(mc):
    """The family's view of a program configuration."""
    return {f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)}


def build(mc, seed=3):
    layers.seed(seed)
    model = JambaForCausalLM(mc)
    model.eval()
    return model, {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


def test_the_defaults_are_the_published_model():
    mc = JambaConfig()
    assert mc.num_params() == 3_029_337_472           # the published 3B
    assert mc.layers_of("attention") == (7, 21)
    assert len(mc.layers_of("mamba")) == 26
    assert (mc.d_inner, mc.mamba_d_state, mc.mamba_d_conv,
            mc.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (mc.num_attention_heads, mc.num_key_value_heads,
            mc.head_dim) == (20, 1, 128)
    model, params = build(TINY)
    assert sum(int(np.prod(p.shape)) for p in params.values()) \
        == TINY.num_params()
    assert "lm_head.weight" not in params               # the head is tied
    # the recurrence's leaves are float32 whatever the matrices are
    assert {str(params[f"model.layers.0.mamba.{n}"].dtype)
            for n in ("A_log", "D", "dt_bias", "conv_weight")} == {"float32"}


def test_the_published_file_is_the_program_s_default():
    cfg = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "jamba2-3b.json")))
    mc = family.model_config(cfg)
    want = dataclasses.replace(
        JambaConfig(), max_position_embeddings=8192,
        embed_init_std=cfg["embed_init_std"],
        a_log_init=tuple(cfg["a_log_init"]),
        dt_bias_init=tuple(cfg["dt_bias_init"]),
        final_norm_init=tuple(cfg["final_norm_init"]),
        tokens_a_dispatch=cfg["tokens_a_dispatch"])
    assert mc == want


def test_the_forward_matches_the_reference(tiny):
    model, params = tiny
    ids = np.random.default_rng(0).integers(1, 512, (2, 64))
    got_layers, want_layers = [], []
    got = model(ids, collect=got_layers).value
    want = family.forward(params, jnp.asarray(ids), file_of(TINY),
                          collect=want_layers)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-5
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert len(got_layers) == len(want_layers) == TINY.num_hidden_layers
    for g, w in zip(got_layers, want_layers):
        assert float(jnp.max(jnp.abs(g.value - w))) < 2e-5


def scan_inputs(rows, t, d, n, seed=0):
    r = np.random.default_rng(seed)

    def f(*s):
        return jnp.asarray(r.standard_normal(s), jnp.float32)
    dt = jax.nn.softplus(f(rows, t, d) - 2.0)
    a = -jnp.exp(0.5 * f(n, d) + 0.5)
    return f(rows, t, d), dt, a, f(rows, t, n), f(rows, t, n), f(d), \
        f(rows, t, d)


#: 256 rows of time: the kernel's chunks are 128, the chunked form's 64
LASTS = {"inside": (100, 37), "at_the_edge": (127, 63),
         "first_of_a_chunk": (128, 64), "ends": (255, 0)}


@pytest.mark.parametrize("form", ["chunked", "kernel"])
@pytest.mark.parametrize("where", sorted(LASTS))
def test_a_scan_form_is_the_sequential_one(form, where):
    """Gated y on every row up to ``last`` and the state AT ``last``: the
    rows behind it (a bucket's padding) advance nothing that is kept."""
    args = scan_inputs(2, 256, 128, 8)
    last = jnp.asarray(LASTS[where], jnp.int32)
    y0, s0 = ssm_ops.selective_scan_sequential(*args, last)
    if form == "chunked":
        y1, s1 = ssm_ops.selective_scan_chunked(*args, last)
    else:
        assert kernel.tiles(256, 128)
        y1, s1 = kernel.selective_scan(*args, last, interpret=True)
    assert float(jnp.max(jnp.abs(y1 - y0))) < 2e-5
    assert float(jnp.max(jnp.abs(s1 - s0))) < 2e-5
    assert float(jnp.max(jnp.abs(s0))) > 0.5
    # the state at `last` is not the state at the end
    _, s_end = ssm_ops.selective_scan_sequential(
        *args, jnp.full((2,), 255, jnp.int32))
    if where != "ends":
        assert float(jnp.max(jnp.abs(s_end - s0))) > 0.1


def test_the_kernel_tiles_whole_lanes_only():
    assert kernel.tiles(4096, 5120) and kernel.tiles(512, 5120)
    assert kernel.tiles(32, 128)
    assert not kernel.tiles(256, 96)        # not whole lanes
    assert not kernel.tiles(256, 128 * 12)  # 12 groups: not tiles of 8
    assert not kernel.tiles(4, 128)         # under a sublane tile of rows
    with pytest.raises(ValueError, match="cannot tile"):
        kernel.selective_scan(*scan_inputs(1, 256, 96, 8),
                              jnp.zeros((1,), jnp.int32), interpret=True)


def test_the_convolution_carries_its_tail():
    r = np.random.default_rng(1)
    xp = jnp.asarray(r.standard_normal((2, 24, 16)), jnp.float32)
    w = jnp.asarray(r.standard_normal((16, 4)), jnp.float32)
    b = jnp.asarray(r.standard_normal((16,)), jnp.float32)
    whole = ssm_ops.causal_conv(xp, w, b)
    last = jnp.asarray([9, 1], jnp.int32)
    tail = ssm_ops.conv_tail(xp, last, 4)
    assert tail.shape == (2, 3, 16)
    # the three rows before `last` + 1, zeros before row 0
    np.testing.assert_array_equal(tail[0], xp[0, 7:10])
    np.testing.assert_array_equal(tail[1, 0], np.zeros(16))
    np.testing.assert_array_equal(tail[1, 1:], xp[1, 0:2])
    # the next row, computed from the tail, is the whole convolution's
    for row, at in enumerate((10, 2)):
        nxt = ssm_ops.causal_conv(xp[row:row + 1, at:at + 1], w, b,
                                  tail[row:row + 1])
        np.testing.assert_allclose(nxt[0, 0], whole[row, at], rtol=1e-5,
                                   atol=1e-6)
    # a tail off by one row is another convolution
    off = ssm_ops.conv_tail(xp, last - 1, 4)
    assert float(jnp.max(jnp.abs(ssm_ops.causal_conv(
        xp[:1, 10:11], w, b, off[:1]) - whole[0, 10]))) > 0.1


def test_one_token_against_the_carried_state_continues_the_scan():
    x, dt, a, b, c, d_skip, z = scan_inputs(3, 40, 128, 8, seed=2)
    last = jnp.asarray([38, 20, 0], jnp.int32)
    y, s = ssm_ops.selective_scan_sequential(x, dt, a, b, c, d_skip, z,
                                             last)
    at = last + 1
    pick = lambda v: v[jnp.arange(3), at]                     # noqa: E731
    y1, s1 = ssm_ops.selective_step(pick(x), pick(dt), a, pick(b), pick(c),
                                    d_skip, pick(z), s)
    np.testing.assert_allclose(y1, pick(y), rtol=2e-5, atol=2e-5)
    _, s_next = ssm_ops.selective_scan_sequential(x, dt, a, b, c, d_skip,
                                                  z, at)
    np.testing.assert_allclose(s1, s_next, rtol=2e-5, atol=2e-5)


def test_the_build_and_the_first_trace_have_spans(tmp_path):
    profiler.start_profiler()
    model, _ = build(TINY, seed=5)
    forward = compile_tracker.tracked_jit(
        "test_jamba_forward", lambda ids: model(ids).value)
    forward(np.ones((1, 8), np.int32))
    forward(np.ones((1, 8), np.int32))
    path = str(tmp_path / "spans.json")
    profiler.stop_profiler(profile_path=path)
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert names.count("jamba.build") == 1
    assert not [n for n in names if n.endswith(".first_trace")]
    # the first forward's tracing is the site's account, not a span's
    assert forward.record.count == 1 and forward.record.trace_ms > 0


def test_the_served_precision_is_bfloat16_where_the_configuration_says():
    mc = dataclasses.replace(TINY, dtype="bfloat16", embed_init_std=1.0)
    model, params = build(mc, seed=7)
    dtypes = {n.rsplit(".", 1)[-1]: str(p.dtype) for n, p in params.items()
              if ".layers.0." in n}
    assert dtypes["A_log"] == dtypes["D"] == dtypes["dt_bias"] == "float32"
    assert dtypes["dt_proj"] == "bfloat16"
    spec = model.serving_spec()
    assert spec.kv_dtype == "bf16"
    (state,) = spec.state_kinds
    assert state.arrays == (((3, 128), "bfloat16"), ((8, 128), "float32"))
    ids = np.random.default_rng(0).integers(1, 512, (1, 48))
    got = model(ids).value
    want = family.forward(params, jnp.asarray(ids), file_of(mc))
    assert got.dtype == jnp.float32
    assert 1e-4 < float(jnp.max(jnp.abs(got - want))) < 0.08
