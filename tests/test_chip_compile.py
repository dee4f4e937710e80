"""The main path's Pallas kernels, compiled by the TPU's own compiler.

Every other test runs the kernels under the Pallas interpreter on the
CPU, where they are plain HLO: a block the chip's tiling refuses, a
kernel that wants more VMEM than it may have, or a kernel the
partitioner cannot split all pass there. libtpu is installed, and it
compiles for a chip that is *described* and not attached
(``jax.experimental.topologies``), so these cases hand the real shapes
of ``gpt2-1p1b`` / ``gpt2-1p3b`` (h16 d128), ``gpt2-medium`` (d64) and
the serving pool to Mosaic and assert a ``tpu_custom_call`` came out.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture — never at
import, so every xdist worker collects the same tests and only the
worker that runs this file loads libtpu — and the kernels are steered
to ``interpret=False`` here in the test (``jax.default_backend()`` is
still the CPU), not through an option of the program. All cases live in
this one file: a second file could land on another worker.
"""

import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import chip_smoke
from paddle_tpu.ops.pallas.utils import kernel_sharding

# the package re-exports the functions under the modules' names
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
gm = importlib.import_module("paddle_tpu.ops.pallas.grouped_matmul")
ss = importlib.import_module("paddle_tpu.ops.pallas.selective_scan")
pw = importlib.import_module("paddle_tpu.ops.pallas.pool_write")
ml = importlib.import_module("paddle_tpu.ops.pallas.mla_attention")
gd = importlib.import_module("paddle_tpu.ops.pallas.gated_delta")

KERNEL = chip_smoke.KERNEL      # a Mosaic kernel in a compiled program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip; the persistent compile cache is
    off around these compiles (an entry written for a described chip
    cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the kernels via Mosaic although the backend is the CPU
    (paged_attention also takes ``interpret=False`` itself), and as the
    chip runs them: without the suite's x64 (conftest turns it on, and
    then the literal zeros of the BlockSpec index maps lower as i64,
    which Mosaic refuses)."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(ss, "_interpret", lambda: False)
    monkeypatch.setattr(pw, "_interpret", lambda: False)
    monkeypatch.setattr(ml, "_interpret", lambda: False)
    monkeypatch.setattr(gd, "_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _flash_loss(q, k, v):
    return fa.flash_attention(q, k, v, causal=True).astype(
        jnp.float32).sum()


# [b, h, s, d] of the train step's attention: gpt2-1p1b b8, gpt2-medium
# b8, and the longest sequence the whole-sequence K/V block allows
# (s=16384 is refused for VMEM — see flash_attention's docstring)
FLASH_SHAPES = {"d128_s1024": (8, 16, 1024, 128),
                "d64_s1024": (8, 16, 1024, 64),
                "d128_s8192": (1, 16, 8192, 128)}


# characters of the module ``jax.grad`` of one call lowers to at the parent
# of PR 60 (066054e: one masked tile a grid step, no plan). What a layer
# adds to a step's module is what every start traces, lowers and hashes for
# the persistent cache's key: PR 59's plan read 30.1 k at GPT's shape and
# its forms of four tiles a step in every kernel four times that, which
# `mellum_code_16k`'s set-up paid on every start
PARENT_LOWERED = {"d128_s1024": 23981, "d64_s1024": 23924,
                  "d128_s8192": 23946, "win": 28622, "full": 26610}
#: the most the plan's module may take of the parent's: 1.5, and 1.7 where
#: a window gives every tile two edges (read 1.33-1.37 and 1.55 from a
#: script, 1.41-1.46 and 1.64 here: a kernel's payload holds the Python
#: stack it was traced under, and pytest's is deeper)
LOWERED_MOST = {"win": 1.7}


def _lowered_twice(fn, args, kind):
    """``jax.jit(fn).lower(*args)``, made twice from fresh traces (every
    cache of JAX's dropped, the once-traced kernels' too). What refused PR
    59 was set-up, and what a start pays for a kernel is its tracing, its
    lowering and a cache that finds the program again: the module is the
    same text both times, character for character (the persistent cache
    keys on it), and no longer than the stated multiple of the parent's.
    Both are lowered from ONE line: a kernel's payload holds the lines of
    the Python stack it was called from."""
    lowered = []
    for _ in range(2):
        jax.clear_caches()
        lowered.append(jax.jit(fn).lower(*args))
    text, again = (low.as_text() for low in lowered)
    assert again == text
    most = LOWERED_MOST.get(kind, 1.5) * PARENT_LOWERED[kind]
    assert len(text) <= most, (kind, len(text), most)
    return lowered[0]


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(one_chip, mosaic, shape,
                                          direction):
    q = jax.ShapeDtypeStruct(FLASH_SHAPES[shape], jnp.bfloat16,
                             sharding=one_chip)
    fn = (_flash_loss if direction == "forward"
          else jax.grad(_flash_loss, argnums=(0, 1, 2)))
    lowered = (jax.jit(fn).lower(q, q, q) if direction == "forward"
               else _lowered_twice(fn, (q, q, q), shape))
    text = lowered.compile().as_text()
    # forward: one kernel; backward: forward + dq + dk/dv
    assert text.count(KERNEL) == (1 if direction == "forward" else 3)


# laguna_pretrain_8k's attention on one chip's share: batch 2, one KV head
# of 128 at s 8192 under 8 query heads with window 512 (the window layers)
# or 6 query heads, causal (the full layers)
GROUPED_FLASH = {"win": (8, 512), "full": (6, 0)}


@pytest.mark.parametrize("kind", sorted(GROUPED_FLASH))
def test_windowed_grouped_flash_attention_compiles_for_v5e(one_chip, mosaic,
                                                           kind):
    heads, window = GROUPED_FLASH[kind]

    def s(h):
        return jax.ShapeDtypeStruct((2, h, 8192, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  tag=kind).astype(jnp.float32).sum()
    text = _lowered_twice(jax.grad(loss, argnums=(0, 1, 2)),
                          (s(heads), s(1), s(1)), kind).compile().as_text()
    assert text.count(KERNEL) == 3
    for stem in ("flash_fwd_", "flash_bwd_dq_", "flash_bwd_dkv_"):
        assert stem + kind in text


# what block_plan gives the dq kernel beyond the cells' own shapes: three
# and four query tiles a grid step (s 1536, 2048), and two where four
# would stand at the default VMEM limit (s 9216: 15 MiB by the estimate, the
# most it takes without a limit of its own); under the suite's `highest`
# precision, whose float32 products take the most VMEM; each kernel still
# one call under its name
@pytest.mark.parametrize("window", [0, 512])
@pytest.mark.parametrize("seq,dq_tiles", [(1536, 3), (2048, 4), (9216, 2)])
def test_the_flash_block_plan_s_dq_steps_compile_for_v5e(
        one_chip, mosaic, seq, dq_tiles, window):
    plan = fa.block_plan(seq, seq, 128, True, window)
    assert (plan.tiles, plan.dq_tiles, plan.dq_sub) == (1, dq_tiles, 256)

    def s(h):
        return jax.ShapeDtypeStruct((1, h, seq, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  tag="t").astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        s(8), s(2), s(2)).compile().as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    assert len(calls) == 3
    for stem in ("flash_fwd_t", "flash_bwd_dq_t", "flash_bwd_dkv_t"):
        assert sum(stem in line for line in calls) == 1, stem


# mellum_code_16k's served prompts: one prompt of the longest bucket, 32
# query heads over 4 KV heads of 128, window 1024 or causal, forward only
# (the whole-sequence K/V block holds at 12288 rows; 16384 is refused)
# jamba_reasoning_6k's prompt scan: 5120 channels by 16 states, a dispatch
# of two prompts at the 512-row bucket or of one at the 4096-row bucket
@pytest.mark.parametrize("rows,bucket", [(2, 512), (1, 4096)])
def test_the_selective_scan_compiles_for_v5e(one_chip, mosaic, rows, bucket):
    def s(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    wide = s(rows, bucket, 5120)
    compiled = jax.jit(ss.selective_scan).lower(
        wide, wide, s(16, 5120), s(rows, bucket, 16), s(rows, bucket, 16),
        s(5120), wide, s(rows, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and "selective_scan" in text
    # [T, 5120, 16] never reaches HBM: the program's temporaries are the
    # relayouts of its four [T, 5120] operands, not 16 times that
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 6 * rows * bucket * 5120 * 4


@pytest.mark.parametrize("kind,window", [("win", 1024), ("full", 0)])
def test_served_prompt_flash_attention_compiles_for_v5e(one_chip, mosaic,
                                                        kind, window):
    def s(h):
        return jax.ShapeDtypeStruct((1, h, 12288, 128), jnp.bfloat16,
                                    sharding=one_chip)
    # at the precision the serving process runs at: the suite's `highest`
    # (conftest) makes the kernel's float32 products six passes wide and
    # 12288 rows then pass the kernel's 16 MB of VMEM by 5%
    with jax.default_matmul_precision("default"):
        text = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, tag=kind)).lower(
                s(32), s(4), s(4)).compile().as_text()
    assert text.count(KERNEL) == 1 and "flash_fwd_" + kind in text


# qwen3next_docs_8k's served prompts: one prompt of a bucket, 16 query heads
# over 2 KV heads of 256, forward only. One head's K and V, double-buffered,
# pass the compiler's default scoped limit at 8192 rows (as d=128 does at
# 16384): the forward asks for a limit of its own there
@pytest.mark.parametrize("bucket", [3072, 8192])
def test_served_prompt_flash_attention_at_d256_compiles_for_v5e(
        one_chip, mosaic, bucket):
    def s(h):
        return jax.ShapeDtypeStruct((1, h, bucket, 256), jnp.bfloat16,
                                    sharding=one_chip)
    assert (fa.fwd_vmem_bytes(bucket, 256, jnp.bfloat16, 512, 512)
            > fa._VMEM_DEFAULT) == (bucket == 8192)
    # the cells the benchmark had keep the forward they had: Mellum's
    # longest prompt (12288 rows at d=128) fits the default
    assert fa.fwd_vmem_bytes(12288, 128, jnp.bfloat16, 512, 512) \
        <= fa._VMEM_DEFAULT < fa.fwd_vmem_bytes(6144, 256, jnp.bfloat16,
                                                    512, 512)
    # and the plan gives the forward of so long a sequence one tile a step
    assert fa.block_plan(bucket, bucket, 256, True).tiles == 1
    with jax.default_matmul_precision("default"):
        text = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, tag="full")).lower(
                s(16), s(2), s(2)).compile().as_text()
    assert text.count(KERNEL) == 1 and "flash_fwd_full" in text


@pytest.mark.parametrize("d,rows", [(128, 16384), (256, 6144),
                                    (256, 8192)])
def test_the_flash_forward_past_the_default_limit_is_refused_without_its_own(
        one_chip, mosaic, monkeypatch, d, rows):
    """What the forward's own scoped limit is for: under the compiler's
    default the same shapes are refused, at compile time under jit."""
    monkeypatch.setattr(fa, "_VMEM_DEFAULT", 1 << 40)

    def s(h):
        return jax.ShapeDtypeStruct((1, h, rows, d), jnp.bfloat16,
                                    sharding=one_chip)
    with jax.default_matmul_precision("default"), \
            pytest.raises(Exception, match="vmem"):
        jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, tag="full")).lower(
                s(16), s(2), s(2)).compile()


def test_the_flash_forward_refuses_what_it_may_not_ask_for_before_compile():
    """Past 64 MiB of resident K and V the refusal is a ValueError the
    caller reads before anything is built (``fused_attention_qkv`` then
    takes the composed form), not a RESOURCE_EXHAUSTED under jit."""
    q = jax.ShapeDtypeStruct((1, 16, 65536, 256), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, 65536, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="not streamed by block"):
        jax.eval_shape(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True), q, k, k)
    # the largest it may: d=256 at 32768 rows, d=128 at 65536
    assert fa.fwd_kv_bytes(32768, 256, jnp.bfloat16) == fa._FWD_KV_MOST \
        == fa.fwd_kv_bytes(65536, 128, jnp.bfloat16)


# and its decode read: 64 rows, 16 query heads over 2 KV heads of 256, 36
# table entries of 256-row blocks
def test_the_paged_read_at_d256_compiles_for_v5e(one_chip, mosaic):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = s((2241, 2, 256, 256))
    text = jax.jit(lambda q, kp, vp, t, p: pa.paged_attention(
        q, kp, vp, t, p, scale=1.0 / 16, interpret=False)).lower(
            s((64, 16, 1, 256)), pool, pool, s((64, 36), jnp.int32),
            s((64,), jnp.int32)).compile().as_text()
    assert text.count(KERNEL) == 1 and "paged_decode_attn" in text


# its delta rule: a prompt of a bucket (16 key heads serving 32 value heads
# of 128 x 128), and a decode step's 64 rows against the state array
@pytest.mark.parametrize("bucket", [3072, 8192])
def test_the_chunked_delta_rule_compiles_for_v5e(one_chip, mosaic, bucket):
    def s(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    key, value, gate = (s(1, bucket, 16, 128), s(1, bucket, 32, 128),
                        s(1, bucket, 32))
    compiled = jax.jit(gd.gdn_prefill).lower(
        key, key, value, gate, gate, s(1, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and "gdn_prefill" in text
    # [T, 128, 128] a head never reaches HBM: the temporaries are the
    # relayouts of q, k, v and o, not the states (32 x 64 KiB a row)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 8 * bucket * 4096 * 4


def test_the_delta_rules_decode_step_rewrites_the_state_in_place(one_chip,
                                                                 mosaic):
    def s(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(gd.gdn_decode, donate_argnums=(5,)).lower(
        s(64, 16, 128), s(64, 16, 128), s(64, 32, 128), s(64, 32),
        s(64, 32), s(64, 32, 128, 128), s(64, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and "gdn_decode" in text
    mem = compiled.memory_analysis()
    # the 128 MiB of state go in and come out as ONE buffer, and nothing
    # state-sized is made beside it
    assert mem.alias_size_in_bytes >= 64 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


# and its expert layer at published widths (64 experts of 896 under hidden
# 2304): a decode step's 16 rows x 8 choices in tiles of 16 rows
# (moe_experts_decode: 128 / 16 + 64 tiles), and a prompt's pass of 3072
# rows in tiles of 128; column tiles of 896 (of 1792) and 768 (of 2304)
@pytest.mark.parametrize("name,rows,tm", [("moe_up_dec", 72 * 16, 16),
                                          ("moe_down_dec", 72 * 16, 16),
                                          ("moe_up", 24576 + 64 * 128, 128),
                                          ("moe_down", 24576 + 64 * 128,
                                           128)])
def test_served_grouped_products_compile_for_v5e(one_chip, mosaic, name,
                                                 rows, tm):
    k, n = (2304, 1792) if "up" in name else (896, 2304)
    assert gm._column_tile(n, 1024) == (896 if "up" in name else 768)
    assert gm._column_tile(1024, 1024) == gm._column_tile(2048, 1024) == 1024
    _grouped_product_compiles(one_chip, name, rows, tm, k, n)


# LFM2's expert layer at published widths (64 experts of 1536 under hidden
# 2048): a decode step's 128 rows x 4 choices (512 / 16 + 64 tiles), and
# the longest prefill dispatch's 2048 rows x 4 choices in tiles of 128
@pytest.mark.parametrize("name,rows,tm", [("moe_up_dec", 96 * 16, 16),
                                          ("moe_down_dec", 96 * 16, 16),
                                          ("moe_up", 8192 + 64 * 128, 128),
                                          ("moe_down", 8192 + 64 * 128,
                                           128)])
def test_lfm2_grouped_products_compile_for_v5e(one_chip, mosaic, name, rows,
                                               tm):
    k, n = (2048, 3072) if "up" in name else (1536, 2048)
    assert gm._column_tile(n, 1024) == 1024
    _grouped_product_compiles(one_chip, name, rows, tm, k, n)


def _grouped_product_compiles(one_chip, name, rows, tm, k, n):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(lambda x, w, tg, na: gm.gmm(
        x, w, tg, na, name=name, tm=tm)).lower(
            s((rows, k)), s((64, k, n)), s((rows // tm,), jnp.int32),
            s((1,), jnp.int32)).compile().as_text()
    assert text.count(KERNEL) == 1 and name in text


# the expert layer's grouped products at laguna_pretrain_8k's shapes: 32
# held experts of width 512 under hidden 2048, the fast buffer's 32768 rows
# and a tile an expert; (lhs, rhs or second lhs, transposed, tk, tn)
ROWS, HELD = 32768 + 32 * 128, 32
GROUPED = {
    "moe_up": ((ROWS, 2048), (HELD, 2048, 1024), False),
    "moe_down": ((ROWS, 512), (HELD, 512, 2048), False),
    "moe_up_dx": ((ROWS, 1024), (HELD, 2048, 1024), True),
    "moe_down_dx": ((ROWS, 2048), (HELD, 512, 2048), True),
    "moe_up_dw": ((ROWS, 2048), (ROWS, 1024), (1024, 1024)),
    "moe_down_dw": ((ROWS, 512), (ROWS, 2048), (512, 2048)),
}


@pytest.mark.parametrize("name", sorted(GROUPED))
def test_grouped_products_compile_for_v5e(one_chip, mosaic, name):
    a, b, how = GROUPED[name]

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tables = (s((ROWS // gm.TILE_M,), jnp.int32), s((1,), jnp.int32))
    if isinstance(how, bool):
        def fn(x, w, tg, na):
            return gm.gmm(x, w, tg, na, name=name, transpose_rhs=how)
    else:
        def fn(x, y, tg, na):
            return gm.tgmm(x, y, tg, na, HELD, name=name, tk=how[0],
                           tn=how[1])
    text = jax.jit(fn).lower(s(a), s(b), *tables).compile().as_text()
    assert text.count(KERNEL) == 1 and name in text


def test_the_expert_layer_compiles_for_v5e_with_its_three_arms(one_chip,
                                                             mosaic):
    """``laguna_pretrain_8k``'s expert layer, forward and backward at the
    cell's shapes (16384 tokens, 8 choices of 256 experts, 32 held): the one
    branch point is a conditional of three arms in each direction (the
    chunks, the fast buffer with the k-slot combine, with the prefix form)
    around the six grouped products."""
    from paddle_tpu.ops import decoder_ops as dops
    t, h, f, k, experts = 16384, 2048, 512, 8, 256
    assert dops._plan(t, k, HELD, experts, gm.TILE_M) == (
        ROWS // gm.TILE_M, 4, (16384, 8704, 2176, 384, 128, 128, 128, 128))

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def layer(x, weight, idx, w13, w2, ct):
        def loss(x, weight, w13, w2):
            out, _ = dops.moe_experts(x, weight, idx, w13, w2, 0, experts,
                                      gm.TILE_M)
            return jnp.sum(out.astype(jnp.float32) * ct.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, weight, w13, w2)
    text = jax.jit(layer).lower(
        s((t, h)), s((t, k)), s((t, k), jnp.int32), s((HELD, h, 2 * f)),
        s((HELD, f, h)), s((t, h))).compile().as_text()
    arms = [len(b.split(",")) for b in
            re.findall(r"branch_computations=\{([^}]*)\}", text)]
    assert arms == [3, 3]
    for name in GROUPED:
        assert name in text, name


# the serving pool at gpt2-1p3b width: 32 slots, h16 d128, 16-row
# blocks, 64 table slots (max_len 1024), 2048 blocks
B, H, D, BS, T, NB = 32, 16, 128, 16, 64, 2048
PAGED_CASES = {           # q_len, q dtype, pool dtype, int8 scales
    "decode_f32": (1, jnp.float32, jnp.float32, False),
    "decode_bf16_pool": (1, jnp.float32, jnp.bfloat16, False),
    "decode_int8_pool": (1, jnp.float32, jnp.int8, True),
    "verify_q5": (5, jnp.float32, jnp.float32, False),
    # the GPT serving cells' own engine: 8 slots over 400 blocks
    "decode_f32_cells": (1, jnp.float32, jnp.float32, False,
                         dict(b=8, nb=400)),
    # mellum_code_16k's full layers' read (S2 (b), PR 44): 32 query heads
    # on 4 KV heads, 256-row bfloat16 blocks, 16 slots over 801 blocks
    "decode_gqa_bf16_bs256": (1, jnp.float32, jnp.bfloat16, False,
                              dict(b=16, hq=32, hkv=4, bs=256, nb=801)),
    # lfm2_agents_3k's read: 128 rows, 32 query heads of 64 over 8 KV
    # heads packed two a row of 128 (LagunaConfig.kv_pack: a pool of
    # 64-wide rows is refused, "slice shape ... must be aligned to tiling
    # (128)"), 256-row bfloat16 blocks, 1537 blocks
    "decode_packed_d64_b128": (1, jnp.bfloat16, jnp.bfloat16, False,
                               dict(b=128, hq=32, hkv=4, bs=256, nb=1537)),
    # jamba_reasoning_6k's read: 64 rows, 20 query heads on the one KV
    # head of 128 (20 query rows a product, not a multiple of a tile's
    # 8 or 16 sublanes), 256-row bfloat16 blocks, 1537 blocks
    "decode_gqa_20on1_b64": (1, jnp.bfloat16, jnp.bfloat16, False,
                             dict(b=64, hq=20, hkv=1, bs=256, nb=1537,
                                  t=32)),
}


def _paged_args(q_len, q_dt, pool_dt, quant, geometry=None, *, where):
    g = dict(dict(b=B, hq=H, hkv=H, bs=BS, nb=NB, t=T), **(geometry or {}))

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=where(len(shape)))
    pool = (g["nb"], g["hkv"], g["bs"], D)
    args = [s((g["b"], g["hq"], q_len, D), q_dt), s(pool, pool_dt),
            s(pool, pool_dt), s((g["b"], g["t"]), jnp.int32),
            s((g["b"],), jnp.int32)]
    if quant:
        args += [s(pool[:2], jnp.float32)] * 2
    return args


def _paged(q, k, v, tables, pos, ks=None, vs=None):
    return pa.paged_attention(q, k, v, tables, pos, k_scale=ks,
                              v_scale=vs, interpret=False)


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_attention_compiles_for_v5e(one_chip, mosaic, case):
    """Mosaic takes the walk at the cells' shapes: the pools stay in HBM
    (no operand of the kernel is gathered or copied whole) and the two
    double buffers, eight blocks of K and of V each, fit the scoped VMEM
    (a kernel over its limit is refused here, not on the chip)."""
    args = _paged_args(*PAGED_CASES[case], where=lambda nd: one_chip)
    text = jax.jit(_paged).lower(*args).compile().as_text()
    assert text.count(KERNEL) == 1


def test_the_selected_read_compiles_for_v5e(one_chip, mosaic):
    """keye_longdoc_24k's decode read: 8 rows, 32 query heads on 4 KV
    heads, 256-row bfloat16 blocks, tables of 68 entries over 545 blocks,
    and the chosen set as ``keep`` [8, 68, 256] in VMEM beside the walk's
    double buffers (one row of it a block, a dynamic sublane slice):
    ``ops.attention_ops.sparse_decode_attention`` as the step calls it,
    the sort's cut to the mask and the one kernel."""
    from paddle_tpu.ops.attention_ops import sparse_decode_attention

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((545, 4, 256, 128), jnp.bfloat16)
    text = jax.jit(lambda *a: sparse_decode_attention(*a, 2048)).lower(
        s((8, 32, 1, 128), jnp.bfloat16), pool, pool, s((8, 68), jnp.int32),
        s((8,), jnp.int32), s((8, 68 * 256), jnp.float32)
    ).compile().as_text()
    assert text.count(KERNEL) == 1
    assert "paged_decode_attn" in text


@pytest.mark.parametrize("bucket", [8192, 12288, 16384])
def test_the_prompts_selected_read_compiles_for_v5e(one_chip, bucket):
    """keye_longdoc_24k's prompt read at its three buckets and the
    published widths, the call's own length a traced scalar: one program a
    bucket. A key tile's float32 logits, all heads at once ``[4, 8, 256,
    512]`` (16 MB), are given the chip's fast memory (``S(1)`` in the
    compiled layout) between the product that makes them and the one that
    reads them, which is why a pair costs less here than on the rectangle
    whose ``[8, 256, keys]`` a head went through HBM (PERF.md section 6,
    PR 47); the program's temporaries stay under half a GiB."""
    from paddle_tpu.ops.attention_ops import sparse_prompt_attention

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(lambda *a: sparse_prompt_attention(
        *a[:-1], 2048, live=a[-1])[0]).lower(
        s((1, 32, bucket, 128)), s((1, 4, bucket, 128)),
        s((1, 4, bucket, 128)), s((1, bucket, 16, 64)),
        s((1, bucket, 16), jnp.float32), s((1, bucket, 64)),
        s((), jnp.int32)).compile()
    assert re.search(r"f32\[(1,)?4,8,256,512\]\{[^}]*S\(1\)\}",
                     compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def _kernel_shapes(text):
    """Operand and result shapes of the program's Mosaic kernels."""
    return [s for res, ops in chip_smoke.tpu_custom_calls(text)
            for s in res + ops]


def test_the_absorbed_latent_read_compiles_for_v5e(one_chip, mosaic):
    """dotsvlm_docs_16k's decode read: 32 rows, 128 heads over ONE row of
    512 + 64 values a token, a pool of 2177 blocks of [576, 256] (its
    tokens along the lanes: the 576 pad nothing) under a table of 68
    entries: one ``mla_decode_attn``, and no array of the gathered table's
    shape."""
    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def read(q_lat, q_rope, pool, tables, pos):
        return ml.mla_paged_attention(q_lat, q_rope, pool, tables, pos,
                                      scale=0.135)
    text = jax.jit(read).lower(
        struct((32, 128, 512)), struct((32, 128, 64)),
        struct((2177, 576, 256)), struct((32, 68), jnp.int32),
        struct((32,), jnp.int32)).compile().as_text()
    assert len(re.findall(r"%mla_decode_attn\S* = ", text)) == 1
    assert text.count(KERNEL) == 1
    assert "bf16[32,68,576,256]" not in text


@pytest.mark.parametrize("bucket", [6144, 16384])
def test_the_materialised_latent_read_compiles_for_v5e(one_chip, mosaic,
                                                       bucket):
    """dotsvlm_docs_16k's prompt read at its shortest and longest bucket:
    a pass of 32 heads, a key of 128 + 64 (the 64 ONE array for all
    heads) beside a value of 128, K and V streamed by tile: 16384 rows
    compile (the whole-sequence flash forward is refused there for
    VMEM)."""
    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def read(q_n, q_r, k_n, k_r, v, live):
        return ml.mla_prompt_attention(q_n, q_r, k_n, k_r, v, scale=0.135,
                                       live=live)
    text = jax.jit(read).lower(
        struct((1, 32, bucket, 128)), struct((1, 32, bucket, 64)),
        struct((1, 32, bucket, 128)), struct((1, bucket, 64)),
        struct((1, 32, bucket, 128)), struct((1,), jnp.int32)
    ).compile().as_text()
    assert len(re.findall(r"%mla_prompt_attn\S* = ", text)) == 1


@pytest.mark.parametrize("rows", [1, 16384], ids=["decode", "prompt"])
def test_the_latent_pool_write_compiles_for_v5e_in_place(one_chip, mosaic,
                                                         rows):
    """A prompt's 16384 rows go into the donated latent pool through the
    chunk-write kernel (its tokens along the lanes), a decode step's 32
    rows as in-place columns: neither copies the pool."""
    from paddle_tpu.ops.attention_ops import latent_pool_write
    b = 32 if rows == 1 else 1

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(latent_pool_write, donate_argnums=(0,)).lower(
        struct((2177, 576, 256)), struct((b, rows, 576)),
        struct((b,), jnp.int32), struct((b, 68), jnp.int32)
    ).compile().as_text()
    assert text.count(KERNEL) == (0 if rows == 1 else 1)
    assert not _pool_copies(text, "bf16[2177,576,256]")
    assert "bf16[2177,1,576,256]" not in text or \
        not _pool_copies(text, "bf16[2177,1,576,256]")


def test_flash_attention_runs_on_the_batch_shard_of_each_chip(
        topo, one_chip, mosaic):
    """Inside a program over four chips (ZeRO/data parallel: batch on
    ``dp``) each chip's kernels see batch/4 — and the program compiles
    at all: a bare Mosaic kernel under a mesh is refused."""
    mesh = Mesh(np.array(topo.devices), ("dp",))
    b, h, s, d = FLASH_SHAPES["d64_s1024"]
    sh = NamedSharding(mesh, P("dp"))
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=sh)

    def grads(q, k, v):
        with kernel_sharding(mesh, batch="dp"):
            return jax.grad(_flash_loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads, out_shardings=(sh,) * 3).lower(
        q, q, q).compile().as_text()
    assert text.count(KERNEL) == 3
    assert "all-gather" not in text
    assert {x[0] for x in _kernel_shapes(text) if len(x) == 3} \
        == {b // 4 * h}


def test_paged_attention_runs_on_the_heads_shard_of_each_chip(
        topo, one_chip, mosaic):
    """Tensor-parallel serving (heads on ``model``): each chip's kernel
    sees heads/4 of q and of the pools, with nothing gathered."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    heads = NamedSharding(mesh, P(None, "model"))
    whole = NamedSharding(mesh, P())
    args = _paged_args(*PAGED_CASES["decode_f32"],
                       where=lambda nd: heads if nd == 4 else whole)

    def step(*a):
        with kernel_sharding(mesh, heads="model"):
            return _paged(*a)

    text = jax.jit(step, out_shardings=heads).lower(
        *args).compile().as_text()
    assert text.count(KERNEL) == 1
    assert "all-gather" not in text
    assert {x[1] for x in _kernel_shapes(text) if len(x) == 4} == {H // 4}


def test_a_many_row_pool_write_runs_on_the_heads_shard_of_each_chip(
        topo, one_chip, mosaic):
    """Tensor-parallel serving: a prompt's KV write (8 x 64 rows into the
    GPT cells' k and v pools) is one ``pool_chunk_write`` kernel a pool
    and chip over that chip's heads of the pool and of the rows, the
    pools donated and aliased, with no collective and no copy of a
    pool."""
    from paddle_tpu.ops.attention_ops import block_scatter_write
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    heads = NamedSharding(mesh, P(None, "model"))
    whole = NamedSharding(mesh, P())
    pool = jax.ShapeDtypeStruct((400, H, 16, D), jnp.float32, sharding=heads)
    new = jax.ShapeDtypeStruct((8, H, 64, D), jnp.float32, sharding=heads)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=whole)
    tables = jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=whole)

    def step(kp, vp, k, v, pos, tables):
        with kernel_sharding(mesh, heads="model"):
            return (block_scatter_write(kp, k, pos, tables),
                    block_scatter_write(vp, v, pos, tables))

    text = jax.jit(step, out_shardings=(heads, heads),
                   donate_argnums=(0, 1)).lower(
        pool, pool, new, new, pos, tables).compile().as_text()
    assert text.count(KERNEL) == 2
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    assert not _pool_copies(text, f"f32[400,{H // 4},16,{D}]")
    assert _aliased_outputs(text) == {0, 1}


# the serving cells' decode step (PERF.md section 4): 8 slots, pool
# [400, 16, 16, 128], 64 table slots, at the 1.3B width. Two layers and
# a small vocabulary suffice: every layer writes and reads its pools
# alike, and the logits are not what is asserted.
STEP_SLOTS, STEP_BLOCKS = 8, 400


def _zero_weights(build):
    """``build()`` with every parameter drawn as zeros (drawing a real
    width's parameters on the host is not the test)."""
    from paddle_tpu.dygraph import layers
    real = layers.eager_init
    layers.eager_init = lambda init, shape, dtype, rng: jnp.zeros(
        tuple(int(d) for d in shape), dtype)
    try:
        model = build()
    finally:
        layers.eager_init = real
    model.eval()
    return model


@pytest.fixture(scope="module")
def decode_step_1p3b_width():
    """``(model, lower)``: a 2-layer model of ``gpt2-1p3b``'s width with
    zero weights (drawing 100M parameters on the host is not the test),
    and the compiled text of its paged decode step for one described
    chip at a given pool dtype."""
    import dataclasses
    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
    from paddle_tpu.models.generation import (decode_step_paged,
                                              param_leaves)
    from paddle_tpu.serving.decoding import neutral_samp
    cfg = dataclasses.replace(GPT_CONFIGS["gpt2-1p3b"], num_layers=2,
                              vocab_size=1024)
    model = _zero_weights(lambda: GPTForCausalLM(cfg))

    def lower(one_chip, pool_dtype):
        def s(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        pool = jax.ShapeDtypeStruct(
            (STEP_BLOCKS, cfg.num_heads, BS, cfg.head_dim), pool_dtype,
            sharding=one_chip)
        args = (jax.tree_util.tree_map(s, param_leaves(model)),
                s(jnp.zeros(STEP_SLOTS, jnp.int32)),
                s(jnp.zeros(STEP_SLOTS, jnp.int32)),
                s(jnp.zeros((STEP_SLOTS, T), jnp.int32)),
                [(pool, pool)] * cfg.num_layers,
                jax.tree_util.tree_map(
                    s, neutral_samp(STEP_SLOTS, cfg.vocab_size)))
        fn = decode_step_paged(model)["fn"]
        # the step's KV read is the paged kernel: through Mosaic, as the
        # chip compiles it (the backend here is still the CPU)
        was, pa._interpret = pa._interpret, lambda: False
        try:
            with jax.enable_x64(False):
                return fn.raw.lower(*args).compile().as_text()
        finally:
            pa._interpret = was

    return cfg, lower


def _pool_copies(text, shape):
    """The ``copy`` / ``copy-start`` ops of a compiled program whose
    result holds an array of ``shape`` (``"f32[400,16,16,128]"``): a
    move of a whole pool, to another layout or to another memory."""
    return [ln.strip()[:160] for ln in text.splitlines()
            if re.search(r"\) copy-start\(| copy\(", ln)
            and shape in ln.split(" copy", 1)[0]]


def _aliased_outputs(text):
    """The indices of the compiled program's outputs that alias a
    (donated) input."""
    header = text[:text.index("\n\n")] if "\n\n" in text else text
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                  header, re.S) or re.search(
                      r"input_output_alias=\{(.*)\}", header)
    assert m, "the compiled step aliases nothing"
    return {int(i) for i in re.findall(r"\{(\d+)\}: \(", m.group(1))}


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
def test_decode_step_writes_its_kv_rows_in_place(
        one_chip, decode_step_1p3b_width, pool_dtype):
    """The decode step's KV write copies no pool: no ``copy`` /
    ``copy-start`` gives a pool-shaped result (XLA's layout assignment
    used to move every pool to ``{3,1,2,0}`` for the scatter and back:
    96 copies of 52 MB a step at 24 layers), and every pool leaf's
    output aliases its donated input."""
    cfg, lower = decode_step_1p3b_width
    text = lower(one_chip, jnp.dtype(pool_dtype))
    short = {"float32": "f32", "bfloat16": "bf16"}[pool_dtype]
    shape = f"{short}[{STEP_BLOCKS},{cfg.num_heads},{BS},{cfg.head_dim}]"
    assert not _pool_copies(text, shape)
    # outputs: next tokens, logits, then (k, v) per layer, qerr, keys
    assert set(range(2, 2 + 2 * cfg.num_layers)) <= _aliased_outputs(text)


def _gpt_prefill_program(rows, bucket):
    """A 2-layer model of ``gpt2-1p3b``'s width behind the GPT serving
    cells' engine (8 slots, 64 table entries of 16 rows): the prefill
    entry of ``bucket`` and its arguments' shapes at the cells' pool of
    400 blocks -> (model, fn, args, the pools among them, a pool's shape
    and how the text prints it, the outputs before the pools)."""
    import dataclasses
    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine
    cfg = dataclasses.replace(GPT_CONFIGS["gpt2-1p3b"], num_layers=2,
                              vocab_size=1024)
    model = _zero_weights(lambda: GPTForCausalLM(cfg))
    engine = ServingEngine(model, max_slots=STEP_SLOTS, max_len=1024,
                           buckets=[64], block_size=BS, num_blocks=16,
                           prefix_cache=False)
    assert engine.spec.prefill_rows(bucket, STEP_SLOTS) == rows
    args = (jnp.zeros((rows, bucket), jnp.int32),
            jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32),
            jnp.zeros((rows, 1024 // BS), jnp.int32), engine.cache.arrays())
    pool = (STEP_BLOCKS, cfg.num_heads, BS, cfg.head_dim)
    return (model, engine._prefill_entry(bucket)["fn"], args, args[4], pool,
            f"f32[{','.join(map(str, pool))}]", 1)


def _lfm2_decode_program(rows, _):
    """A 2-layer cut of LFM2-24B-A2B (a convolution layer and an
    attention layer at the published widths, dense MLPs cut to 1024)
    behind ``lfm2_agents_3k``'s engine: the decode step of 128 slots and
    its arguments' shapes at the cell's pool, 1537 blocks of 256 packed
    rows."""
    import dataclasses
    from paddle_tpu.models.lfm2 import LFM2_CONFIGS, Lfm2ForCausalLM
    from paddle_tpu.serving import ServingEngine
    cfg = dataclasses.replace(
        LFM2_CONFIGS["lfm2-24b-a2b"], num_hidden_layers=2,
        layer_types=("conv", "full_attention"), num_dense_layers=2,
        num_attention_heads_per_layer=(), mlp_layer_types=(),
        intermediate_size=1024, vocab_size=1024,
        max_position_embeddings=4096)
    model = _zero_weights(lambda: Lfm2ForCausalLM(cfg))
    engine = ServingEngine(model, max_slots=rows, max_len=4096,
                           buckets=[256], block_size=256, num_blocks=4,
                           prefix_cache=False)
    with engine._step_lock:
        args = engine._step_args(engine._stamps()) + (engine._counted,)
    fn = engine.spec.decode_entry(None, engine.kv_dtype, None)["fn"]
    pool = (1537, cfg.num_key_value_heads // cfg.kv_pack, 256,
            cfg.head_dim * cfg.kv_pack)
    assert pool[1:] == (4, 256, 128)
    return (model, fn, args, args[3], pool,
            f"bf16[{','.join(map(str, pool))}]", 2)


@pytest.mark.parametrize("program,rows,bucket", [
    (_lfm2_decode_program, 128, 1),
    (_gpt_prefill_program, 1, 1024),
    (_gpt_prefill_program, 8, 64),
], ids=["lfm2_decode_128x1", "gpt_prefill_1x1024", "gpt_prefill_8x64"])
def test_a_many_row_step_writes_its_kv_rows_in_place(
        one_chip, monkeypatch, program, rows, bucket):
    """What PR 43 is for: a KV write of more than 64 rows (a decode step
    of 128 requests over ``bf16[1537, 4, 256, 128]`` pools, GPT's
    prompts over ``f32[400, 16, 16, 128]``) compiles, for the described
    chip, to one ``pool_chunk_write`` kernel a pool and no ``copy`` of a
    pool-shaped array (the fused scatter it replaced had two a pool,
    403 MB each in the first; a kernel whose result is not held to HBM
    had one at 8 x 64, where the compiler moved a whole pool into the
    chip's alternate memory and back around it: no array of a pool's
    shape lives anywhere but in HBM), every pool leaf's output aliased
    to its donated input."""
    for kernels in (pa, fa, pw):
        monkeypatch.setattr(kernels, "_interpret", lambda: False)
    from paddle_tpu.models.generation import param_leaves
    model, fn, args, pools, pool, shape, before = program(rows, bucket)

    def struct(x):
        dims = pool if x.shape[1:] == pool[1:] else x.shape
        return jax.ShapeDtypeStruct(dims, x.dtype, sharding=one_chip)
    leaves = jax.tree_util.tree_map(struct, (param_leaves(model), *args))
    with jax.enable_x64(False):
        text = fn.raw.lower(*leaves).compile().as_text()
    flat = jax.tree_util.tree_leaves(pools)
    at = {before + i for i, x in enumerate(flat)
          if x.shape[1:] == pool[1:]}
    assert len(re.findall(r"%pool_chunk_write\S* = ", text)) == len(at) > 0
    assert not _pool_copies(text, shape)
    assert not re.findall(re.escape(shape) + r"\{[^}]*S\(\d\)", text)
    assert at <= _aliased_outputs(text)


def _mellum_decode_cut():
    """A 2-layer cut of Mellum2-12B-A2.5B at the published attention
    widths (a window layer and a full layer, 32 query heads on 4 KV heads
    of 128; dense MLPs of 1024 where the experts stand, which this read
    does not touch) behind ``mellum_code_16k``'s engine: 16 slots, tables
    of 64 entries of 256 rows, the full layers' pool of 801 blocks."""
    import dataclasses
    from paddle_tpu.models import MELLUM_CONFIGS, MellumForCausalLM
    cfg = dataclasses.replace(
        MELLUM_CONFIGS["mellum2-12b-a2p5b"], num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        mlp_layer_types=("dense", "dense"), intermediate_size=1024,
        vocab_size=1024, max_position_embeddings=16384)
    return MellumForCausalLM, cfg, 16, 16384, 801


def _jamba_decode_cut():
    """A Mamba layer and an attention layer of AI21-Jamba2-3B at the
    published widths (20 query heads on 1 KV head of 128; the MLPs cut to
    1024) behind ``jamba_reasoning_6k``'s engine: 64 slots, tables of 32
    entries of 256 rows, a pool of 1537 blocks."""
    import dataclasses
    from paddle_tpu.models import JAMBA_CONFIGS, JambaForCausalLM
    cfg = dataclasses.replace(
        JAMBA_CONFIGS["jamba2-3b"], num_hidden_layers=2,
        attn_layer_period=2, attn_layer_offset=1, intermediate_size=1024,
        vocab_size=1024, max_position_embeddings=8192)
    return JambaForCausalLM, cfg, 64, 8192, 1537


@pytest.mark.parametrize("cut", [_mellum_decode_cut, _jamba_decode_cut],
                         ids=["mellum_16x64", "jamba_64x32"])
def test_a_grouped_decode_step_reads_its_full_layers_through_the_kernel(
        one_chip, monkeypatch, cut):
    """What PR 44 is for: the decode step of ``mellum_code_16k`` and of
    ``jamba_reasoning_6k`` compiles, for the described chip, with one
    ``paged_decode_attn`` a layer without a window over pools that stay
    where they are (every pool leaf's output aliased to its donated
    input) and no array of the gathered table's shape
    (``bf16[16,64,4,256,128]``, 268 MB, K and V a full layer;
    ``bf16[64,32,1,256,128]``, 134 MB); Mellum's window layer keeps its
    gather of the five entries its window of 1024 covers."""
    from paddle_tpu.models.generation import param_leaves
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.serving import ServingEngine
    for kernels in (pa, fa, pw, gm, ss):
        monkeypatch.setattr(kernels, "_interpret", lambda: False)
    monkeypatch.setattr(ssm_ops, "interpret_mode", lambda: False)
    build, cfg, rows, max_len, blocks = cut()
    model = _zero_weights(lambda: build(cfg))
    engine = ServingEngine(model, max_slots=rows, max_len=max_len,
                           buckets=[256], block_size=256, num_blocks=4,
                           prefix_cache=False)
    with engine._step_lock:
        args = engine._step_args(engine._stamps())
    if engine.spec.counters:
        args += (engine._counted,)
    fn = engine.spec.decode_entry(None, engine.kv_dtype, None)["fn"]
    small = (4, cfg.num_key_value_heads, 256, cfg.head_dim)
    pool = (blocks,) + small[1:]

    def struct(x):
        return jax.ShapeDtypeStruct(pool if x.shape == small else x.shape,
                                    x.dtype, sharding=one_chip)
    leaves = jax.tree_util.tree_map(struct, (param_leaves(model), *args))
    with jax.enable_x64(False):
        text = fn.raw.lower(*leaves).compile().as_text()
    flat = jax.tree_util.tree_leaves(args[3])
    full = [i for i, x in enumerate(flat) if x.shape == small]
    assert len(full) == 2                   # K and V of the one full layer
    assert len(re.findall(r"%paged_decode_attn\S* = ", text)) == 1
    table = max_len // 256
    assert f"bf16[{rows},{table},{','.join(map(str, small[1:]))}]" \
        not in text
    assert not _pool_copies(text, f"bf16[{','.join(map(str, pool))}]")
    # outputs: next tokens, logits, then the cache's arrays
    assert {2 + i for i in range(len(flat))} <= _aliased_outputs(text)
    windows = [x for x in flat if x.ndim == 4 and x.shape[1:] == small[1:]
               and x.shape != small]
    if windows:
        assert len(windows) == 2 and f"bf16[{rows},5,4,256,128]" in text


def test_decode_step_keeps_the_sampler_under_a_conditional(
        one_chip, decode_step_1p3b_width):
    """The chip's compiler keeps ``decoding._where_any_sampled``'s
    ``cond`` a conditional (it does not run both branches and select):
    the step's entry computation has one ``conditional`` and no sort;
    the top-k / top-p sorts live in the branch it calls."""
    import re
    _, lower = decode_step_1p3b_width
    text = lower(one_chip, jnp.dtype("float32"))
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert len(re.findall(r" conditional\(", entry)) == 1
    assert not re.findall(r" sort\(", entry)
    assert re.findall(r" sort\(", text)


@pytest.fixture(scope="module")
def gpt_cut_texts(one_chip):
    """``pretrain_1chip``'s step (batch 8 x 1024, AMP O2, bf16 moments,
    grads inside) over a 2-block cut of ``gpt2-1p1b`` with its whole
    vocabulary, compiled for the described chip -> the optimised HLO of
    the train step (``backward``) and of the loss alone (``forward``)."""
    import dataclasses
    from paddle_tpu import amp, jit
    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
    from paddle_tpu.optimizer import AdamW
    cfg = dataclasses.replace(GPT_CONFIGS["gpt2-1p1b"], num_layers=2,
                              recompute=True)
    model = _zero_weights(lambda: GPTForCausalLM(cfg))
    model.train()
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                moment_dtype="bfloat16")

    def loss_of(ids, labels):
        with amp.auto_cast(level="O2"):
            return model(ids, labels=labels)

    def train_step(ids, labels):
        loss = loss_of(ids, labels)
        model.clear_gradients()
        loss.backward()
        opt.step()
        return loss
    steps = {"forward": jit.to_static(loss_of, layers=[model]),
             "backward": jit.to_static(train_step, layers=[model],
                                       optimizers=[opt], retain_grads=False)}
    ids = jnp.zeros((8, 1024), jnp.int32)
    texts = {}
    with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
        patch.setattr(fa, "_interpret", lambda: False)
        for name, step in steps.items():
            lowered = step.lower(
                ids, ids, place=lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=one_chip))
            texts[name] = lowered.compile().as_text()
            texts[name + "_lowered"] = lowered.as_text()
    return texts


def test_a_recomputed_train_step_runs_the_flash_forward_once_a_block(
        gpt_cut_texts):
    """Each block of the step holds one ``flash_fwd`` and one of each
    backward kernel (PR 53; under a bare checkpoint, or with the vjp taken
    in the backward op, the forward kernel is there twice a block:
    ``tests/test_recompute.py`` counts both in the jaxpr). Every kernel is
    an instruction of the name the program gave it, the differentiated
    forward too: the names are read as the benchmark's trace reader
    reads them (``perfbench/xplane.py``, ``kernel_stem``), which finds a
    kernel's seconds by that name."""
    from perfbench import xplane
    names = [xplane.kernel_stem(ln.strip())
             for ln in gpt_cut_texts["backward"].splitlines()
             if xplane.is_mosaic(ln)]
    assert sorted(names) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * 2)


def test_a_step_of_many_blocks_holds_each_flash_kernel_s_payload_once(
        gpt_cut_texts):
    """The kernels are traced once a shape (``_traced_once``): the module
    the step lowers to, which every start builds again and hashes for the
    persistent cache's key, holds ONE custom call a kernel whatever the
    depth, each block calling the function around it, while the compiled
    step has its call a kernel a block (the test above). The 2-block cut's
    module is shorter than the parent's (066054e: 295,050 characters, a
    payload a kernel a block; PR 59's plan 305,610), though a kernel's own
    text is a third longer."""
    lowered = gpt_cut_texts["backward_lowered"]
    assert lowered.count("@tpu_custom_call") == 3
    assert len(lowered) < 295050


@pytest.mark.parametrize("program", ["forward", "backward"])
def test_a_gpt_step_writes_no_float32_array_of_its_logits(gpt_cut_texts,
                                                          program):
    """The loss reads the bfloat16 logits ``[8, 1024, 50304]`` through a
    fused convert (the AMP black list's cast is an elementwise producer of
    reductions and of the two gradient products' inputs): the step writes
    no float32 array of their size, forward or backward (PR 56; the
    lowerings to PR 55 wrote three, 1.65 GB each). Instructions inside a
    fused computation keep their values in registers and do not count."""
    from tools import train_step_ops
    table = train_step_ops.instructions(gpt_cut_texts[program])
    assert train_step_ops.written_float32(table, 8 * 1024 * 50304,
                                          50304) == []
    logits = [name for name, info in table.items() if not info["fused"]
              and "bf16[8,1024,50304]" in info["results"]]
    assert logits, "the head's product is not in the program"


def test_head_and_loss_compile_to_three_products_and_one_pass(one_chip):
    """The tied head and the loss, forward and backward, as the tape
    records them (``matmul_v2``, the cast, ``softmax_with_cross_entropy``,
    its grad, the cast's, ``matmul_v2_grad``) at the GPT cells' shape:
    three products, no float32 array of the logits' size written, under
    1 GiB of temporaries (0.77; the lowerings to PR 55: 3.07 GiB here,
    3.84 with float32 master weights' casts; ``tools/head_loss_forms.py``
    times both on the chip)."""
    from tools import head_loss_forms, train_step_ops

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with jax.enable_x64(False):
        compiled = jax.jit(
            head_loss_forms.op_chain("softmax_with_cross_entropy")).lower(
                s((8, 1024, 2048)), s((50304, 2048)),
                s((8, 1024), jnp.int32)).compile()
    table = train_step_ops.instructions(compiled.as_text())
    assert sum(info["convolutions"] for info in table.values()
               if not info["fused"]) == 3
    assert train_step_ops.written_float32(table, 8192 * 50304, 50304) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
