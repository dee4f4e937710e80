"""The serving check takes the reference's logits a block of rows at a time
(PR 37): ``serve.deficits_fn`` multiplies a family's ``hidden`` by its
``head`` ``CHECK_ROWS`` rows at a time and keeps each row's best logit and
the emitted token's. A case a family at toy size on the CPU: the blockwise
deficits are ``max(forward) - forward[next]`` row for row, and the compiled
program holds no ``[rows, vocabulary]`` array. One case compiles the same
function for a described v5e at a vocabulary of 154,880 rows."""

import functools
import json
import os
import re
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402

from perfbench import families, serve           # noqa: E402

BLOCK = 24              # rows a block in these cases
FAMILIES = ("gpt2", "laguna", "mellum", "jamba")
ROWS = 100              # a request is padded to these: no multiple of BLOCK,
#                         and no toy twin's hidden width (a head is [h, V])


@functools.lru_cache(maxsize=None)
def toy(family_name):
    """(family, its toy twin's file, seeded weights by the program's
    parameter names)."""
    import paddle_tpu as pt
    with open(os.path.join(BENCH_DIR, "rehearsal",
                           family_name + "-tiny.json")) as f:
        cfg = json.load(f)
    family = families.load(cfg)
    pt.seed(7)
    if family_name == "laguna":     # trained only: no serving model, no engine
        from paddle_tpu.models import LagunaForCausalLM
        # 200 held rows: the toy's 256 are also its dense MLP's gate and up
        cfg["held"]["vocab_rows"], cfg["vocab_size"] = [16, 216], 200
        cfg.pop("params_held", None)
        model = LagunaForCausalLM(family.model_config(cfg))
    else:
        model = family.serving_model(cfg)
    params = {n: p.value for n, p in model.named_parameters()}
    return family, cfg, params


def request(cfg, length, seed, rows=ROWS):
    """A seeded request of ``length`` tokens padded to ``rows`` as
    ``serve.check`` pads it -> (ids [1, rows], next tokens [rows])."""
    lo, hi = cfg.get("held", {}).get("vocab_rows", (1, int(cfg["vocab_size"])))
    seq = np.random.default_rng(seed).integers(max(lo, 1), hi, size=length)
    ids = np.full((1, rows), lo, np.int32)
    ids[0, :length] = seq
    nxt = np.zeros(rows, np.int32)
    nxt[:length - 1] = seq[1:] - lo * ("held" in cfg)
    return jnp.asarray(ids), jnp.asarray(nxt)


def rows_of_logits(compiled, head):
    """The leading sizes of every float32 ``[n, vocabulary]`` array the
    compiled program names, the head ``[h, vocabulary]`` itself aside."""
    width, vocabulary = head.shape
    assert width != ROWS
    return {int(n) for n in re.findall(r"f32\[(\d+),%d\]" % vocabulary,
                                       compiled.as_text())} - {width}


@pytest.mark.parametrize("family_name", FAMILIES)
def test_blockwise_deficits_are_the_whole_logits_row_for_row(
        family_name, monkeypatch):
    family, cfg, params = toy(family_name)
    rows, length = ROWS, ROWS - 11
    assert rows % BLOCK and length % BLOCK and rows > 2 * BLOCK
    ids, nxt = request(cfg, length, seed=2**31 + 5)
    logits = family.forward(params, ids, cfg)[0]
    head = family.head(params, cfg)
    want = np.asarray(jnp.max(logits, axis=-1)
                      - jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0])
    assert want[:length - 1].max() > 0.0    # some next token is not the best

    monkeypatch.setattr(serve, "CHECK_ROWS", BLOCK)
    blocks = serve.deficits_fn(family, cfg)
    got = np.asarray(blocks(params, ids, nxt))
    assert got.shape == (rows,)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    held = rows_of_logits(blocks.lower(params, ids, nxt).compile(), head)
    assert BLOCK in held and max(held) < rows, held

    # the control: one block as long as the request is the array whole
    whole = serve.deficits_fn(family, cfg, rows_a_block=rows)
    assert rows in rows_of_logits(
        whole.lower(params, ids, nxt).compile(), head)
    np.testing.assert_allclose(np.asarray(whole(params, ids, nxt)), want,
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("family_name", FAMILIES)
def test_forward_is_hidden_times_head(family_name):
    """``forward`` stays, defined as the product of the two halves."""
    family, cfg, params = toy(family_name)
    ids, _ = request(cfg, ROWS - 3, seed=11)
    with jax.default_matmul_precision("highest"):
        product = family.hidden(params, ids, cfg) @ family.head(params, cfg)
    np.testing.assert_array_equal(
        np.asarray(family.forward(params, ids, cfg)), np.asarray(product))
    assert product.dtype == jnp.float32


def test_a_150k_row_head_at_max_len_16384_compiles_under_2_gib_for_v5e():
    """What PR 37 made room for: hidden 2048, a vocabulary of 154,880 rows,
    ``max_len`` 16384. Whole, the logits alone are 10.15 GB; in blocks of
    ``CHECK_ROWS`` the TPU compiler's own analysis of the check's program
    stays under 2 GiB of temporaries. Nothing runs: the chip is described."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    stub = types.SimpleNamespace(
        hidden=lambda params, ids, cfg: params["hidden"],
        head=lambda params, cfg: params["head"].astype(jnp.float32))
    on = SingleDeviceSharding(topo.devices[0])
    rows, width, vocabulary = 16384, 2048, 154_880
    args = ({"hidden": jax.ShapeDtypeStruct((1, rows, width), jnp.float32,
                                            sharding=on),
             "head": jax.ShapeDtypeStruct((width, vocabulary), jnp.bfloat16,
                                          sharding=on)},
            jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=on),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=on))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            compiled = serve.deficits_fn(stub, {}).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert serve.CHECK_ROWS == 1024
    # the float32 head (1.27 GB) and one block of logits (0.63 GB)
    assert rows * vocabulary * 4 > 10e9 and temporaries < 2 * 2**30, \
        temporaries
