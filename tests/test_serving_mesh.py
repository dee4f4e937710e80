"""Mesh-sharded serving: tensor-parallel engine steps on a
("data", "model") mesh (serving/engine.py + distributed/sharding.py).

The correctness contract is absolute: an engine whose params and paged
KV pool are placed with NamedSharding and whose prefill/decode/verify
steps run under pjit must produce token-for-token the ids of the
single-device path — on a degenerate 1x1 mesh (where GSPMD is pure
overhead and any divergence is a sharding bug) across the full
kv_dtype x spec x prefix-cache grid, and on a real (1, 2)
model-parallel mesh with the attention heads actually split across
devices (conftest.py forces 8 virtual CPU devices, so this runs in
CI). The unified step-compile cache must make mesh engines pay exactly
one compile per (step kind, geometry, mesh) — a second engine on an
equal mesh retraces nothing.
"""

import jax
import numpy as np
import pytest
from contextlib import contextmanager

import paddle_tpu as pt
from paddle_tpu.distributed.sharding import (SERVING_TP_RULES,
                                             mesh_cache_key,
                                             parse_serving_mesh,
                                             serving_mesh)
from paddle_tpu.models.generation import (decode_step_paged, greedy_search,
                                          verify_step_paged)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import ServingEngine

CFG = dict(vocab_size=97, max_position_embeddings=64, hidden_size=32,
           num_layers=2, num_heads=4, ffn_hidden_size=64)


def _build_model(seed=7):
    pt.seed(seed)
    m = GPTForCausalLM(GPTConfig(**CFG))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _build_model()


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 97, size=n).tolist() for n in sizes]


@contextmanager
def _serving_flags(**kw):
    pt.set_flags(kw)
    try:
        yield
    finally:
        pt.set_flags({"serving_kv_dtype": "f32",
                      "serving_mesh": ""})


def _run_mesh_engine(model, mesh, prompts, *, mnt=5, spec_tokens=0,
                     prefix_cache=True, kv_dtype=None):
    eng = ServingEngine(model, max_slots=2, max_len=32,
                        buckets=[8, 16], max_queue=16, block_size=4,
                        spec_tokens=spec_tokens,
                        prefix_cache=prefix_cache, kv_dtype=kv_dtype,
                        mesh=mesh)
    reqs = [eng.submit(p, max_new_tokens=mnt) for p in prompts]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return [r.output_ids for r in reqs], eng


# ---------------------------------------------------------------------------
# 1x1 mesh: GSPMD plumbing with zero parallelism — the pure-overhead
# oracle where any token drift is a sharding bug, not a numerics one
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prefix_cache", [True, False])
@pytest.mark.parametrize("spec_tokens", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_mesh_1x1_engine_matches_sequential_greedy(
        model, kv_dtype, spec_tokens, prefix_cache):
    prompts = _prompts((3, 7, 5, 11), seed=1)
    outs, eng = _run_mesh_engine(
        model, serving_mesh(1, 1), prompts, spec_tokens=spec_tokens,
        prefix_cache=prefix_cache, kv_dtype=kv_dtype)
    assert eng.mesh_shape == (1, 1)
    for p, out in zip(prompts, outs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=5,
                            cache_len=32)[0].tolist()
        assert out == ref, (f"{p} diverged on the 1x1 mesh "
                            f"(kv={kv_dtype}, K={spec_tokens}, "
                            f"prefix={prefix_cache})")


@pytest.mark.slow
@pytest.mark.parametrize("spec_tokens", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_mesh_1x1_kernel_read_matches_oracle_and_dense_greedy(
        model, kv_dtype, spec_tokens):
    """The mesh engine's decode / verify steps read through the paged
    kernel (inside its shard_map): token for token the same engine traced
    with the XLA oracle where the kernel stands, and sequential greedy
    over a dense cache."""
    from tests.test_paged_attention import _oracle_read
    prompts = _prompts((4, 9, 6), seed=3)
    mesh = serving_mesh(1, 1)
    with _oracle_read():
        base, _ = _run_mesh_engine(model, mesh, prompts,
                                   spec_tokens=spec_tokens,
                                   kv_dtype=kv_dtype)
    outs, eng = _run_mesh_engine(model, mesh, prompts,
                                 spec_tokens=spec_tokens, kv_dtype=kv_dtype)
    assert outs == base
    st = eng.stats()
    assert 0 < st["kv_blocks_live"] < st["kv_blocks_table"]
    for p, out in zip(prompts, outs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=5,
                            cache_len=32)[0].tolist()
        assert out == ref, f"{p} diverged (kv={kv_dtype})"


def test_mesh_prefix_reuse_stays_exact(model):
    """A resubmitted prompt decodes from shared mesh-sharded blocks and
    must reproduce its first run token-for-token."""
    prompts = _prompts((9, 7), seed=5)
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8, 16],
                        block_size=4, mesh=serving_mesh(1, 1))
    first = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    rep = eng.submit(prompts[0], max_new_tokens=5)
    eng.run_until_idle()
    assert rep.output_ids == first[0].output_ids
    assert eng.stats()["prefix_hit_requests"] >= 1


# ---------------------------------------------------------------------------
# the unified step-compile cache under meshes
# ---------------------------------------------------------------------------


def test_mesh_unified_cache_one_compile_per_site(model):
    """Two engines on equal (recreated) meshes share every compiled
    step: the second engine adds ZERO traces at every site."""
    mesh = serving_mesh(1, 1)
    prompts = _prompts((3, 7), seed=2)
    _run_mesh_engine(model, mesh, prompts)
    decode = decode_step_paged(model, mesh, "f32")["traces"]["count"]
    # a *recreated* Mesh over the same devices must hit the same keys
    outs2, eng2 = _run_mesh_engine(model, serving_mesh(1, 1), prompts)
    assert decode_step_paged(model, mesh, "f32")["traces"]["count"] \
        == decode
    used = {b: e["traces"]["count"] for b, e in eng2._prefill_fns.items()}
    assert all(n == 1 for n in used.values()), used


def test_mesh_and_plain_cache_entries_coexist(model):
    """A mesh engine's steps live under distinct unified-cache keys:
    building one never evicts or retraces the plain-path entries."""
    plain = decode_step_paged(model)
    before = plain["traces"]["count"]
    mesh = serving_mesh(1, 1)
    _run_mesh_engine(model, mesh, _prompts((4,), seed=6), mnt=3)
    assert decode_step_paged(model)["traces"]["count"] == before
    cache = model._step_compile_cache
    mkey = mesh_cache_key(mesh)
    assert ("decode_paged",) in cache
    assert ("decode_paged", mkey, "f32") in cache


def test_mesh_verify_spec_cache_key_includes_k(model):
    mesh = serving_mesh(1, 1)
    _run_mesh_engine(model, mesh, _prompts((5,), seed=7), spec_tokens=2)
    mkey = mesh_cache_key(mesh)
    assert ("verify_paged", 2, mkey, "f32") in model._step_compile_cache
    assert verify_step_paged(model, 2, mesh, "f32")["traces"]["count"] >= 1


# ---------------------------------------------------------------------------
# a real model-parallel split (heads across 2 devices)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices for a (1, 2) mesh")
def test_mesh_1x2_head_sharded_matches_greedy():
    """num_heads=4 over model=2: params and the KV pool genuinely split
    across devices, tokens still bit-identical to 1-device greedy."""
    model = _build_model()           # fresh: placement shards its params
    prompts = _prompts((3, 7, 5, 11), seed=1)
    refs = [greedy_search(model, np.asarray([p]), max_new_tokens=5,
                          cache_len=32)[0].tolist() for p in prompts]
    mesh = serving_mesh(1, 2)
    outs, eng = _run_mesh_engine(model, mesh, prompts)
    assert eng.mesh_shape == (1, 2)
    assert outs == refs
    # the pool is physically head-sharded, not just annotated
    k0 = eng.cache.arrays()[0][0]
    assert len(k0.sharding.device_set) == 2
    assert "model" in str(k0.sharding.spec)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices for a (1, 2) mesh")
def test_mesh_1x2_param_placement_follows_rules():
    model = _build_model(seed=11)
    mesh = serving_mesh(1, 2)
    ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                  mesh=mesh)
    for name, p in model.named_parameters():
        spec = SERVING_TP_RULES.spec_for(name, p.value.shape, mesh)
        assert str(p.value.sharding.spec) == str(spec), name


# ---------------------------------------------------------------------------
# construction-time validation + flag plumbing
# ---------------------------------------------------------------------------


def test_parse_serving_mesh():
    assert parse_serving_mesh("") is None
    assert parse_serving_mesh("1x2") == (1, 2)
    assert parse_serving_mesh("2X4") == (2, 4)
    for bad in ("2", "1x0", "ax2", "1x2x3"):
        with pytest.raises(ValueError):
            parse_serving_mesh(bad)


def test_mesh_engine_from_flag_and_stats(model):
    with _serving_flags(serving_mesh="1x1"):
        eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8])
    assert eng.mesh is not None and eng.mesh_shape == (1, 1)
    st = eng.stats()
    assert st["mesh_shape"] == [1, 1]
    plain = ServingEngine(model, max_slots=1, max_len=32, buckets=[8])
    assert plain.mesh is None
    assert plain.stats()["mesh_shape"] is None


def test_serving_mesh_too_many_devices():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        serving_mesh(n + 1, 1)
