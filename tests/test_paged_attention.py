"""Fused paged decode attention + int8 KV cache (serving hot path).

Three layers of contract, bottom-up:

- kernel vs oracle: ``ops.pallas.paged_attention`` (interpret mode on
  CPU) against the XLA-composed ``paged_attention_reference`` across
  block-boundary, ragged-length, dead-row, trash-block-padded and
  verify-width (spec-decode rollback) cases, grouped KV heads, 16- and
  256-row blocks, chunked walks that end on a short chunk, f32 / bf16 /
  int8;
- the quantizing scatter ``block_scatter_write_quant``: parity with the
  float write, requantization idempotence (committed codes never drift
  when quieter rows land later), window locality, overflow routing;
- the engine: its decode and verify steps read through the kernel (the
  query block's shape decides, ``gpt.PAGED_KERNEL_MAX_ROWS``; no flag),
  float32 and ``FLAGS_serving_kv_dtype=int8``, and stay token-identical
  to the same engine traced with the XLA oracle where the kernel stands
  AND to sequential ``greedy_search`` over a dense cache — including
  speculative verify (K>0, rollback) and prefix-cache on/off.

Plus the lane-width regression: head dims that are not a multiple of
the 128-lane register width (e.g. 20) are padded inside the kernels via
``pad_lane_dim`` instead of failing block selection.
"""

from contextlib import contextmanager

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.generation import greedy_search
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.attention_ops import (block_scatter_write,
                                          block_scatter_write_quant,
                                          paged_attention_reference)
from paddle_tpu.ops.pallas import pool_write
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.paged_attention import paged_attention
from paddle_tpu.ops.pallas.utils import pad_lane_dim, pick_block
from paddle_tpu.ops.quant_ops import dequantize_int8
from paddle_tpu.serving import ServingEngine

# the package re-exports the function under the module's name
pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")


@contextmanager
def _serving_flags(**kw):
    pt.set_flags(kw)
    try:
        yield
    finally:
        pt.set_flags({"serving_kv_dtype": "f32"})


@contextmanager
def _oracle_read():
    """The engine's steps traced with the XLA oracle where the kernel
    stands (``gpt.py`` looks the function up when it traces; a flags
    bump drops the steps compiled before, on the way in and out)."""
    def oracle(q, k_pool, v_pool, tables, pos, k_scale=None, v_scale=None):
        return paged_attention_reference(q, k_pool, v_pool, tables, pos,
                                         k_scale=k_scale, v_scale=v_scale)
    def drop_compiled_steps():       # any set_flags moves the version
        pt.set_flags(pt.get_flags("serving_kv_dtype"))
    real, pa.paged_attention = pa.paged_attention, oracle
    drop_compiled_steps()
    try:
        yield
    finally:
        pa.paged_attention = real
        drop_compiled_steps()


# ---------------------------------------------------------------------------
# kernel vs XLA reference
# ---------------------------------------------------------------------------


def _tables_for(pos, s, bs, T):
    """Block tables with each request's live logical blocks mapped to
    distinct physical blocks and every entry past the reservation left
    pointing at the trash block (0) — the allocator's padding shape."""
    tables = np.zeros((len(pos), T), np.int32)
    nxt = 1
    for i, p in enumerate(pos):
        for j in range((p + s - 1) // bs + 1):
            tables[i, j] = nxt
            nxt += 1
    return jnp.asarray(tables), nxt


def _written_by_hand(pool, new, pos, tables, bs):
    """``block_scatter_write``'s contract as a plain loop over numpy
    arrays: row ``j`` of request ``i`` lands at position ``pos[i] + j``
    of the request's table, on the trash block (0) past the table."""
    pool = np.array(pool)
    T = tables.shape[1]
    for i in range(new.shape[0]):
        for j in range(new.shape[2]):
            at = pos[i] + j
            phys = tables[i, at // bs] if at // bs < T else 0
            pool[phys, :, at % bs] = new[i, :, j]
    return pool


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,s,bs,T", [
    (8, 1, 4, 6), (4, 5, 4, 6), (3, 2, 4, 6),   # 64 rows and under
    (128, 1, 32, 3),    # a decode step of 128 requests: a piece of a block
    (32, 4, 32, 3),     # a verify step of 32 slots x K+1 = 4 rows
    (2, 40, 16, 4),     # prompts that straddle blocks
    (1, 96, 16, 5),     # a prompt that overflows its table
])
def test_inplace_row_write_equals_the_scatter(monkeypatch, b, s, bs, T,
                                              dtype):
    """Every form of ``block_scatter_write`` (unrolled in-place row
    updates for a few rows, one kernel over the touched chunks of
    blocks for many; the fused scatter they replaced is gone) leaves the
    pool a plain loop over its contract leaves, bit for bit off the
    trash block, over random tables: rows that straddle blocks at
    unaligned positions, rows past the table routed to the trash block,
    and several requests whose overflow rows collide there (where a
    form may keep any one of the colliding rows, or the old one)."""
    rng = np.random.RandomState(100 * b + s)
    h, d = 2, 8
    few = b * s <= attention_ops.INPLACE_WRITE_MAX_ROWS
    for trial in range(8 if few else 3):
        nb = b * T + 1
        perm = rng.permutation(np.arange(1, nb))
        # each request reserves a random number of blocks; the rest of
        # its row stays on the trash block, as the allocator leaves it
        reserved = rng.randint(1, T + 1, size=b)
        tables = np.zeros((b, T), np.int32)
        for i in range(b):
            tables[i, :reserved[i]] = perm[i * T:i * T + reserved[i]]
        # positions up to the table's end: with s > 1 the last rows of
        # a request at the end overflow the table; two requests are
        # pinned there so that their overflow rows collide
        pos = rng.randint(0, T * bs, size=b)
        if b > 2:
            pos[:2] = T * bs - 1
            pos[-1] = 0       # and one surely writes a block it owns
        else:
            # prompts: one from an unaligned position near the table's
            # end, the last from row 3 of a table it owns whole
            pos[0], pos[-1] = T * bs - 7, 3
            tables[-1] = perm[-T:]
        pool = jnp.asarray(rng.randn(nb, h, bs, d), dtype)
        new = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
        args = (pool, new, jnp.asarray(pos, jnp.int32),
                jnp.asarray(tables))
        old = np.asarray(pool, np.float32)
        cand = np.asarray(jnp.asarray(new, dtype), np.float32)
        want = _written_by_hand(old, cand, pos, tables, bs)
        assert not np.array_equal(want[1:], old[1:])
        kernels = []    # the calls that took the many-row form
        real = pool_write.pool_chunk_write
        monkeypatch.setattr(
            pool_write, "pool_chunk_write",
            lambda *a: kernels.append(len(a[2])) or real(*a))
        got = [np.asarray(block_scatter_write(*args), np.float32)]
        assert len(kernels) == (0 if few else 1)
        if few:     # the many-row form at a few rows too
            monkeypatch.setattr(attention_ops,
                                "INPLACE_WRITE_MAX_ROWS", 0)
            got.append(np.asarray(block_scatter_write(*args),
                                  np.float32))
            assert len(kernels) == 1
        monkeypatch.undo()
        for pool_after in got:
            np.testing.assert_array_equal(pool_after[1:], want[1:])
            # the trash block: every row is the old row or one of the
            # rows routed there
            for off in range(bs):
                row = pool_after[0][:, off]
                ok = np.array_equal(row, old[0, :, off]) or any(
                    np.array_equal(row, cand[i, :, j])
                    for i in range(b) for j in range(s))
                assert ok, (trial, off)


@pytest.mark.parametrize("s,pos", [
    (1, [3, 15, 4]),     # decode width; pos=15 ends exactly on a block
    (3, [3, 13, 0]),     # verify width (spec K=2): rows straddle blocks
    (1, [0, 7, 8]),      # first token; boundary-1 / boundary
])
def test_kernel_matches_reference_f32(s, pos):
    rng = np.random.RandomState(3)
    bs, T, h, d = 4, 5, 2, 32
    tables, nb = _tables_for(pos, s, bs, T)
    k_pool = jnp.asarray(rng.randn(nb, h, bs, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, h, bs, d), jnp.float32)
    # poison the trash block: if either side fails to mask table
    # padding, the 100x rows blow the comparison wide open
    k_pool = k_pool.at[0].set(100.0)
    v_pool = v_pool.at[0].set(100.0)
    q = jnp.asarray(rng.randn(len(pos), h, s, d), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, posv)
    ref = paged_attention_reference(q, k_pool, v_pool, tables, posv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s,group", [(1, 1), (1, 4), (3, 2)])
def test_kernel_reads_only_the_kept_keys(pool_dtype, s, group):
    """``keep``: a request's rows read the keys that are kept AND at or
    before the row, and no others: grouped heads or not, whatever form
    the shape alone would pick, over a row whose first block keeps
    nothing (its state is wiped by the first kept key) and a row that
    keeps one key."""
    rng = np.random.RandomState(11)
    bs, T, h, d = 4, 5, 2, 32
    pos = [9, 15, 4]
    tables, nb = _tables_for(pos, s, bs, T)
    dtype = jnp.dtype(pool_dtype)
    k_pool = jnp.asarray(rng.randn(nb, h, bs, d), dtype).at[0].set(100.0)
    v_pool = jnp.asarray(rng.randn(nb, h, bs, d), dtype).at[0].set(100.0)
    q = jnp.asarray(rng.randn(len(pos), h * group, s, d), jnp.float32)
    keep = rng.rand(len(pos), T * bs) < 0.5
    keep[0, :bs] = False                # nothing of the first block
    keep[0, bs + 1] = True
    keep[2] = False                     # one key in all
    keep[2, 2] = True
    posv = jnp.asarray(pos, jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, posv,
                          keep=jnp.asarray(keep).reshape(len(pos), T, bs))
    kg = np.asarray(k_pool[tables], np.float32).transpose(0, 2, 1, 3, 4) \
        .reshape(len(pos), h, T * bs, d)
    vg = np.asarray(v_pool[tables], np.float32).transpose(0, 2, 1, 3, 4) \
        .reshape(len(pos), h, T * bs, d)
    for r, p0 in enumerate(pos):
        for j in range(h * group):
            for i in range(s):
                seen = keep[r] & (np.arange(T * bs) <= p0 + i)
                lg = kg[r, j // group, seen] @ np.asarray(q[r, j, i]) \
                    / np.sqrt(d)
                w = np.exp(lg - lg.max())
                want = (w / w.sum()) @ vg[r, j // group, seen]
                np.testing.assert_allclose(
                    np.asarray(out[r, j, i]), want,
                    **(dict(rtol=2e-5, atol=2e-5) if pool_dtype == "float32"
                       else dict(rtol=2e-2, atol=2e-2)))


def test_keep_is_a_value_a_table_position_of_a_float_pool():
    pool = jnp.zeros((6, 2, 4, 32))
    q, tables = jnp.zeros((1, 2, 1, 32)), jnp.ones((1, 5), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="block_size"):
        paged_attention(q, pool, pool, tables, pos,
                        keep=jnp.ones((1, 20), bool))
    with pytest.raises(ValueError, match="float pools"):
        paged_attention(q, pool.astype(jnp.int8), pool.astype(jnp.int8),
                        tables, pos, k_scale=jnp.ones((6, 2)),
                        v_scale=jnp.ones((6, 2)),
                        keep=jnp.ones((1, 5, 4), bool))


def _written_int8_pools(rng, tables, bs, T, h, d, widths):
    """Build int8 + mirror f32 pools through the real write path: the
    incremental decode/verify write sequence ``widths`` (mixed decode
    and verify step widths), starting from empty pools."""
    b = tables.shape[0]
    nb = int(jnp.max(tables)) + 1
    kq = jnp.zeros((nb, h, bs, d), jnp.int8)
    vq = jnp.zeros((nb, h, bs, d), jnp.int8)
    ksc = jnp.zeros((nb, h), jnp.float32)
    vsc = jnp.zeros((nb, h), jnp.float32)
    kf = jnp.zeros((nb, h, bs, d), jnp.float32)
    vf = jnp.zeros((nb, h, bs, d), jnp.float32)
    pos = 0
    for w in widths:
        newk = jnp.asarray(rng.randn(b, h, w, d), jnp.float32)
        newv = jnp.asarray(rng.randn(b, h, w, d), jnp.float32)
        posv = jnp.full((b,), pos, jnp.int32)
        kq, ksc, kerr = block_scatter_write_quant(kq, ksc, newk, posv,
                                                  tables)
        vq, vsc, verr = block_scatter_write_quant(vq, vsc, newv, posv,
                                                  tables)
        assert float(kerr) < 0.05 and float(verr) < 0.05
        kf = block_scatter_write(kf, newk, posv, tables)
        vf = block_scatter_write(vf, newv, posv, tables)
        pos += w
    return kq, vq, ksc, vsc, kf, vf, pos


@pytest.mark.parametrize("s", [1, 3])
def test_kernel_matches_reference_int8(s):
    rng = np.random.RandomState(5)
    bs, T, h, d = 4, 5, 2, 32
    b = 2
    widths = [3, 1, 4, 1, 2]  # mixed decode/verify writes, 11 rows
    end = sum(widths)
    tables, _ = _tables_for([end - 1] * b, 1, bs, T)
    kq, vq, ksc, vsc, kf, vf, end2 = _written_int8_pools(
        rng, tables, bs, T, h, d, widths)
    assert end2 == end
    pos = jnp.full((b,), end - s, jnp.int32)  # rows pos..end-1 written
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)

    out = paged_attention(q, kq, vq, tables, pos,
                          k_scale=ksc, v_scale=vsc)
    ref = paged_attention_reference(q, kq, vq, tables, pos,
                                    k_scale=ksc, v_scale=vsc)
    # same dequant math on both sides -> only softmax accumulation
    # order differs
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # and the quantized pools stay close to the exact f32 ones
    ref_f32 = paged_attention_reference(q, kf, vf, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_f32),
                               rtol=0.12, atol=0.12)


def _pools(rng, nb, h_kv, bs, d, pool_dtype):
    """Random K / V pools (trash block poisoned) and, for int8, scales."""
    if pool_dtype == "int8":
        k_pool, v_pool = (jnp.asarray(rng.randint(-127, 128,
                                                  (nb, h_kv, bs, d)),
                                      jnp.int8) for _ in range(2))
        scales = dict(
            k_scale=jnp.asarray(rng.uniform(0.5, 2.0, (nb, h_kv)),
                                jnp.float32),
            v_scale=jnp.asarray(rng.uniform(0.5, 2.0, (nb, h_kv)),
                                jnp.float32))
        return k_pool.at[0].set(127), v_pool.at[0].set(127), scales
    k_pool, v_pool = (jnp.asarray(rng.randn(nb, h_kv, bs, d), pool_dtype)
                      for _ in range(2))
    return k_pool.at[0].set(100.0), v_pool.at[0].set(100.0), {}


# (block size, query heads a KV head, query rows): 16-row blocks on the
# vector unit (GPT's decode and verify, grouped heads), 256-row blocks on
# the vector unit (one row) and on the matrix unit (16 rows a KV head)
WALKS = [(16, 1, 1), (16, 1, 4), (16, 4, 1), (16, 4, 4),
         (256, 1, 1), (256, 16, 1), (256, 4, 4)]


def test_the_shape_picks_the_arithmetic():
    """(query rows a KV head, block rows, keys a slice) of the cells:
    GPT's decode and verify over 16-row float32 blocks multiply and
    reduce on the vector unit, Mellum's 8 query heads a KV head over
    256-row bfloat16 blocks go through the matrix unit, like the two
    grouped walks over 256-row blocks below in every pool type."""
    assert pa._rows_form(1, 16, 8) and pa._rows_form(5, 16, 8)
    assert pa._rows_form(1, 256, 8)
    assert not pa._rows_form(8, 256, 16)
    for sub in (8, 16, 32):
        assert not pa._rows_form(16, 256, sub)


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("bs,group,s", WALKS)
def test_kernel_walks_the_live_blocks(monkeypatch, bs, group, s,
                                      pool_dtype):
    """Two blocks a compute step over a table of five (not a multiple of
    two): a dead row (``pos`` 0, one block, the trash block's), a row
    whose last query row ends exactly on a block's edge (two live blocks,
    one whole chunk), one a row further (a last chunk with one live block
    of two), and one that fills the table. The trash block is poisoned."""
    monkeypatch.setattr(pa, "BLOCKS_A_STEP", 2)
    rng = np.random.RandomState(bs + 7 * group + s)
    T, h_kv, d = 5, 2, 32
    pos = [0, 2 * bs - s, 2 * bs - s + 1, T * bs - s]
    tables, nb = _tables_for(pos, s, bs, T)
    tables = tables.at[0].set(0)          # a released row's table
    k_pool, v_pool, scales = _pools(rng, nb, h_kv, bs, d, pool_dtype)
    q = jnp.asarray(rng.randn(len(pos), h_kv * group, s, d), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, posv, **scales)
    if group > 1:       # the oracle reads one KV head a query head
        k_pool, v_pool = (jnp.repeat(p, group, axis=1)
                          for p in (k_pool, v_pool))
        scales = {n: jnp.repeat(x, group, axis=1)
                  for n, x in scales.items()}
    ref = paged_attention_reference(q, k_pool, v_pool, tables, posv,
                                    **scales)
    # a bfloat16 pool's products on the matrix unit take q and the
    # probabilities in bfloat16 (block_attention_gqa's contract)
    tol = 2e-2 if pool_dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_kernel_default_chunk_over_a_table_it_does_not_divide():
    """The default eight blocks a step over a table of 20: rows of 1, 8,
    11 and 20 live blocks (a whole chunk, a chunk and three, two chunks
    and four)."""
    rng = np.random.RandomState(23)
    bs, T, h, d = 4, 20, 2, 32
    pos = [0, 8 * bs - 1, 11 * bs - 2, T * bs - 1]
    tables, nb = _tables_for(pos, 1, bs, T)
    k_pool, v_pool, _ = _pools(rng, nb, h, bs, d, "float32")
    q = jnp.asarray(rng.randn(len(pos), h, 1, d), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, posv)
    ref = paged_attention_reference(q, k_pool, v_pool, tables, posv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gpt_step_takes_the_kernel_by_the_query_blocks_shape():
    """The paged branch of ``models/gpt.py`` at a decode shape (one row a
    request), a verify shape and a prefill bucket: the first two hold the
    kernel and gather nothing table-sized, the bucket composes the read
    over the gathered ``[b, T, h, bs, d]`` table and holds no kernel. A
    lone bucket-64 prompt is a prefill, whatever its rows."""
    from paddle_tpu.dygraph.tape import no_grad
    from paddle_tpu.dygraph.tensor import Tensor
    pt.seed(3)
    cfg = GPTConfig(vocab_size=97, max_position_embeddings=128,
                    hidden_size=32, num_layers=1, num_heads=4,
                    ffn_hidden_size=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    bs, T, nb = 4, 32, 40
    pool = jnp.zeros((nb, cfg.num_heads, bs, cfg.head_dim), jnp.float32)

    def text(b, s):
        def step(ids, pos, tables, k_pool, v_pool):
            with no_grad():
                logits, _ = model(
                    Tensor(ids, stop_gradient=True),
                    cache=[(Tensor(k_pool, stop_gradient=True),
                            Tensor(v_pool, stop_gradient=True))],
                    cache_pos=pos, block_tables=tables)
            return logits.value
        return str(jax.make_jaxpr(step)(
            jnp.zeros((b, s), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, T), jnp.int32), pool, pool))

    def gathered(b):
        return f"f32[{b},{T},{cfg.num_heads},{bs},{cfg.head_dim}]"

    assert gpt_mod.PAGED_KERNEL_MAX_ROWS == 8
    for b, s in ((8, 1), (8, 5), (2, gpt_mod.PAGED_KERNEL_MAX_ROWS)):
        t = text(b, s)
        assert "paged_decode_attn" in t and gathered(b) not in t, (b, s)
    for b, s in ((1, 64), (8, 16)):
        t = text(b, s)
        assert "paged_decode_attn" not in t and gathered(b) in t, (b, s)


@pytest.mark.parametrize("pool_dtype", ["bfloat16", "float32"])
def test_kernel_reads_twenty_query_heads_on_one_kv_head(pool_dtype):
    """Jamba's read at a toy size: 20 query heads on the one KV head (20
    rows a product: the matrix-unit form, and no multiple of a tile's
    sublanes) over 256-row blocks, the default blocks a step over a table
    they do not divide, a dead row among the rows. The kernel, the
    reference and the composed read it replaced in the served step
    (``block_attention_gqa`` without a window) agree."""
    rng = np.random.RandomState(44)
    bs, T, d, hq = 256, 9, 32, 20
    assert not pa._rows_form(hq, bs, 16)
    pos = [0, 3 * bs - 1, 3 * bs, T * bs - 1, 700]
    tables, nb = _tables_for(pos, 1, bs, T)
    tables = tables.at[0].set(0)
    k_pool, v_pool, _ = _pools(rng, nb, 1, bs, d, pool_dtype)
    q = jnp.asarray(rng.randn(len(pos), hq, 1, d), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, posv)
    ref = paged_attention_reference(
        q, *(jnp.repeat(p, hq, axis=1) for p in (k_pool, v_pool)), tables,
        posv)
    composed = attention_ops.block_attention_gqa(q, k_pool, v_pool, tables,
                                                 posv)
    assert out.shape == ref.shape == composed.shape == (len(pos), hq, 1, d)
    tol = 2e-2 if pool_dtype == "bfloat16" else 1e-4
    for other in (ref, composed):
        np.testing.assert_allclose(np.asarray(out), np.asarray(other),
                                   rtol=tol, atol=tol)


def _program_facts(jaxpr):
    """(calls of ``paged_decode_attn``, the shape of every value a
    primitive of the program gives, by primitive), nested programs
    included: an inner ``jit`` (the kernel's walk is one), a loop's body."""
    kernels, shapes = 0, []

    def walk(jp):
        nonlocal kernels
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels += "paged_decode_attn" in str(
                    eqn.params.get("name_and_src_info", eqn.params))
                continue            # the kernel's body is not the program's
            shapes.extend((eqn.primitive.name, tuple(v.aval.shape))
                          for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return kernels, shapes


def _mellum_toy():
    """-> (model, its layers without a window, its window layers)."""
    import dataclasses
    from paddle_tpu.models import MELLUM_CONFIGS, MellumForCausalLM
    kinds = ("sliding_attention", "full_attention") * 2
    return MellumForCausalLM(dataclasses.replace(
        MELLUM_CONFIGS["mellum-tiny"], layer_types=kinds)), 2, 2


def _jamba_toy():
    from paddle_tpu.models import JAMBA_CONFIGS, JambaForCausalLM
    return JambaForCausalLM(JAMBA_CONFIGS["jamba-tiny"]), 1, 0


@pytest.mark.parametrize("rows", [1, 32], ids=["decode", "prompt"])
@pytest.mark.parametrize("toy", [_mellum_toy, _jamba_toy],
                         ids=["mellum", "jamba"])
def test_a_full_layers_decode_row_reads_through_the_kernel(toy, rows):
    """What a layer sees of itself picks its decode read, and no option: a
    decode step of Mellum (two window layers of 16 rows, two full layers)
    and of Jamba (one attention layer) holds ``paged_decode_attn`` once a
    layer without a window and no array of the gathered table's shape
    ``[b, T, hkv, bs, d]``; Mellum's window layers gather the ``ceil(window
    / bs) + 1`` entries their window covers, K and V, and hold no kernel; a
    prompt holds no kernel at all and gathers nothing."""
    from paddle_tpu.dygraph.tape import no_grad
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.models.generation import _wrap_pools
    pt.seed(3)
    model, full, window = toy()
    model.eval()
    b, bs, max_len = 2, 8, 128
    engine = ServingEngine(model, max_slots=b, max_len=max_len,
                           buckets=[32], block_size=bs, num_blocks=0,
                           prefix_cache=False, eos_token_id=None)

    def call(ids, pos, tables, pools):
        with no_grad():
            logits, _ = model(
                Tensor(ids, stop_gradient=True), cache=_wrap_pools(pools),
                cache_pos=pos, block_tables=tables,
                last=None if rows == 1 else jnp.zeros((b,), jnp.int32))
        return logits.value
    kernels, shapes = _program_facts(jax.make_jaxpr(call)(
        jnp.zeros((b, rows), jnp.int32), jnp.ones((b,), jnp.int32),
        jax.tree_util.tree_map(jnp.asarray, engine.cache.tables_arg()),
        engine.cache.arrays()))
    cfg = model.cfg
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    gathered = [s for name, s in shapes if name == "gather"
                and len(s) == 5 and s[2:] == (hkv, bs, d)]
    assert (b, max_len // bs, hkv, bs, d) not in [s for _, s in shapes]
    if rows > 1:
        assert kernels == 0 and not gathered
        return
    assert kernels == full
    slots = -(-cfg.sliding_window // bs) + 1 if window else 0
    assert gathered == [(b, slots, hkv, bs, d)] * (2 * window)


# ---------------------------------------------------------------------------
# quantizing scatter: parity, idempotence, locality, overflow
# ---------------------------------------------------------------------------


def test_quant_write_matches_float_write():
    rng = np.random.RandomState(7)
    bs, T, h, d = 4, 4, 2, 8
    tables, nb = _tables_for([10, 6], 1, bs, T)
    kq, vq, ksc, vsc, kf, vf, _ = _written_int8_pools(
        rng, tables, bs, T, h, d, [2, 4, 1, 3, 1])
    live = np.unique(np.asarray(tables))
    live = live[live != 0]
    deq = dequantize_int8(kq, ksc[..., None, None])
    np.testing.assert_allclose(np.asarray(deq[live]),
                               np.asarray(kf[live]), atol=0.05)


def test_quant_write_quieter_rows_never_drift_committed_codes():
    """Monotone scales: a later, quieter write into the same block must
    leave the already-committed codes AND scale bit-identical (the
    dequantize->requantize round trip is exact at an unchanged scale)."""
    rng = np.random.RandomState(9)
    bs, h, d = 4, 2, 8
    tables = jnp.asarray([[1, 2]], jnp.int32)
    pool = jnp.zeros((3, h, bs, d), jnp.int8)
    sc = jnp.zeros((3, h), jnp.float32)
    loud = jnp.asarray(rng.randn(1, h, 2, d) * 4.0, jnp.float32)
    pool, sc, _ = block_scatter_write_quant(
        pool, sc, loud, jnp.asarray([0], jnp.int32), tables)
    before_codes = np.asarray(pool[1])[:, :2]
    before_sc = np.asarray(sc[1])
    quiet = jnp.asarray(rng.randn(1, h, 1, d) * 0.1, jnp.float32)
    pool, sc, _ = block_scatter_write_quant(
        pool, sc, quiet, jnp.asarray([2], jnp.int32), tables)
    np.testing.assert_array_equal(np.asarray(sc[1]), before_sc)
    np.testing.assert_array_equal(np.asarray(pool[1])[:, :2],
                                  before_codes)


def test_quant_write_only_touches_window_blocks():
    rng = np.random.RandomState(11)
    bs, h, d = 4, 2, 8
    tables = jnp.asarray([[1, 2, 3]], jnp.int32)
    pool = jnp.zeros((4, h, bs, d), jnp.int8)
    sc = jnp.zeros((4, h), jnp.float32)
    first = jnp.asarray(rng.randn(1, h, 3, d), jnp.float32)
    pool, sc, _ = block_scatter_write_quant(
        pool, sc, first, jnp.asarray([0], jnp.int32), tables)
    blk1_codes, blk1_sc = np.asarray(pool[1]), np.asarray(sc[1])
    # write entirely within logical block 1 (pos 4..5): physical block
    # 1 is outside the affected window and must be untouched
    nxt = jnp.asarray(rng.randn(1, h, 2, d), jnp.float32)
    pool, sc, _ = block_scatter_write_quant(
        pool, sc, nxt, jnp.asarray([4], jnp.int32), tables)
    np.testing.assert_array_equal(np.asarray(pool[1]), blk1_codes)
    np.testing.assert_array_equal(np.asarray(sc[1]), blk1_sc)


def test_quant_write_overflow_rows_route_to_trash():
    """Rows past the table (bucketed prefill suffix padding) land in
    the trash block; live blocks keep exact codes and the error stat
    only covers live rows."""
    rng = np.random.RandomState(13)
    bs, T, h, d = 4, 2, 2, 8
    tables = jnp.asarray([[1, 2]], jnp.int32)
    pool = jnp.zeros((3, h, bs, d), jnp.int8)
    sc = jnp.zeros((3, h), jnp.float32)
    new = jnp.asarray(rng.randn(1, h, 3, d), jnp.float32)
    # pos = T*bs - 1: row 7 is the last live row, rows 8/9 overflow
    pool, sc, err = block_scatter_write_quant(
        pool, sc, new, jnp.asarray([T * bs - 1], jnp.int32), tables)
    assert np.isfinite(float(err)) and float(err) < 0.05
    deq = dequantize_int8(pool[2], sc[2][:, None, None])
    np.testing.assert_allclose(np.asarray(deq[:, bs - 1]),
                               np.asarray(new[0, :, 0]), atol=0.05)
    # overflow rows went somewhere harmless: the trash block
    assert np.abs(np.asarray(pool[0])).sum() > 0
    assert np.abs(np.asarray(pool[1])).sum() == 0  # untouched live block


# ---------------------------------------------------------------------------
# lane-width regression: head_dim not a multiple of 128
# ---------------------------------------------------------------------------


def test_pad_lane_dim_policy():
    assert pad_lane_dim(20) == 24      # sub-lane widths round to 8s
    assert pad_lane_dim(1) == 8
    assert pad_lane_dim(32) == 32      # standard head dims unchanged
    assert pad_lane_dim(64) == 64
    assert pad_lane_dim(128) == 128
    assert pad_lane_dim(150) == 256    # >= LANE rounds to whole lanes
    with pytest.raises(ValueError):
        pad_lane_dim(0)
    # and the sequence-axis helper is NOT the tool for head dims:
    # 20 has no power-of-two divisor >= 8
    assert pick_block(20, 64) == 0


def test_paged_kernel_odd_head_dim():
    rng = np.random.RandomState(17)
    bs, T, h, d = 4, 4, 2, 20
    pos = [5, 9]
    tables, nb = _tables_for(pos, 1, bs, T)
    k_pool = jnp.asarray(rng.randn(nb, h, bs, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, h, bs, d), jnp.float32)
    q = jnp.asarray(rng.randn(2, h, 1, d), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, posv)
    ref = paged_attention_reference(q, k_pool, v_pool, tables, posv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_odd_head_dim():
    rng = np.random.RandomState(19)
    b, h, s, d = 1, 2, 64, 20
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    from tests.test_pallas_kernels import composed_attention
    ref = composed_attention(q, k, v, True, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine: the kernel's read / int8 token parity with the oracle's read and
# with sequential greedy over a dense cache
#
# These retrace prefill+decode per combination under the Pallas
# interpreter, which is heavy inside the full tier-1 run — they carry
# the `slow` marker and run in the ci.sh serving gate (step 6, which
# invokes this file without the tier-1 `-m 'not slow'` filter). The
# kernel-vs-oracle and
# quantizing-scatter tests above stay in tier-1.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = GPTConfig(vocab_size=97, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 97, size=n).tolist() for n in sizes]


def _run(model, prompts, mnt=5, **eng_kw):
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8, 16],
                        max_queue=16, block_size=4, **eng_kw)
    reqs = [eng.submit(p, max_new_tokens=mnt) for p in prompts]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return [r.output_ids for r in reqs], eng


def _dense_greedy(model, prompt, mnt=5):
    return greedy_search(model, np.asarray([prompt]), max_new_tokens=mnt,
                         cache_len=32)[0].tolist()


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_engine_kernel_read_matches_oracle_and_dense_greedy(model,
                                                            kv_dtype):
    """The kernel (and the int8 pool under it) must not move a single
    sampled token: the engine == the engine traced with the oracle's
    read == sequential f32 greedy_search over a dense cache, prompts
    spanning slot reuse and both buckets."""
    prompts = _prompts((3, 7, 5, 11))
    with _serving_flags(serving_kv_dtype=kv_dtype):
        with _oracle_read():
            base, _ = _run(model, prompts)
        fused, eng = _run(model, prompts)
    assert fused == base
    assert eng.kv_dtype == kv_dtype
    st = eng.stats()
    assert 0 < st["kv_blocks_live"] < st["kv_blocks_table"]
    for p, out in zip(prompts, fused):
        assert out == _dense_greedy(model, p), \
            f"{p} diverged from f32 greedy"


@pytest.mark.slow
def test_engine_kernel_int8_spec_decode_parity(model):
    """Speculative verify (K=2): the widened verify query and its
    rollback re-writes ride the same kernel/quantized pool and must
    stay token-identical to plain greedy over a dense cache."""
    prompts = _prompts((4, 9, 6), seed=3)
    with _serving_flags(serving_kv_dtype="int8"):
        outs, eng = _run(model, prompts, spec_tokens=2)
    assert eng.spec_tokens == 2
    for p, out in zip(prompts, outs):
        assert out == _dense_greedy(model, p), \
            f"{p} diverged under spec decode"


@pytest.mark.slow
@pytest.mark.parametrize("prefix_cache", [True, False])
def test_engine_kernel_int8_prefix_cache_parity(model, prefix_cache):
    prompts = _prompts((7, 9), seed=5)
    with _serving_flags(serving_kv_dtype="int8"):
        eng = ServingEngine(model, max_slots=2, max_len=32,
                            buckets=[8, 16], block_size=4,
                            prefix_cache=prefix_cache)
        first = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        # resubmit: with the prefix cache on, the repeat decodes from
        # shared quantized blocks; either way tokens must match
        rep = eng.submit(prompts[0], max_new_tokens=5)
        eng.run_until_idle()
    assert rep.state == "done"
    assert rep.output_ids == first[0].output_ids
    assert rep.output_ids == _dense_greedy(model, prompts[0])
    st = eng.stats()
    assert st["kv_dtype"] == "int8"
    assert st["kv_quant_max_abs_err"] > 0.0


@pytest.mark.slow
def test_engine_int8_reports_quant_error(model):
    with _serving_flags(serving_kv_dtype="int8"):
        outs, eng = _run(model, _prompts((5,), seed=8), mnt=4)
    st = eng.stats()
    assert 0.0 < st["kv_quant_max_abs_err"] < 0.5


@pytest.mark.parametrize("s,pos", [(1, [3, 15, 4]), (3, [3, 13, 0]),
                                   (8, [0, 5, 8])])
def test_block_attention_equals_the_gathered_reference(s, pos):
    """The engine's prefill read contracts over the blocks as gathered
    ([b, T, h, bs, d]); it is the reference's attention over the
    [b, h, T*bs, d] view, trash-block padding masked alike."""
    rng = np.random.RandomState(11)
    bs, T, h, d = 4, 5, 2, 32
    tables, nb = _tables_for(pos, s, bs, T)
    k_pool = jnp.asarray(rng.randn(nb, h, bs, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, h, bs, d), jnp.float32)
    k_pool = k_pool.at[0].set(100.0)
    v_pool = v_pool.at[0].set(100.0)
    q = jnp.asarray(rng.randn(len(pos), h, s, d), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = attention_ops.block_attention(q, k_pool, v_pool, tables, posv)
    ref = paged_attention_reference(q, k_pool, v_pool, tables, posv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- the latent pool's two reads
# (ops/pallas/mla_attention.py: one vector a token for all the heads, a
# token a lane, no head axis)

MLA_DECODE = {
    # (block size, table entries, positions, heads, rank, rope, dtype)
    "ragged_b8": (8, 6, [0, 13, 47, 7], 4, 32, 8, jnp.float32),
    "one_block_b16": (16, 2, [3, 15, 16], 2, 16, 8, jnp.float32),
    # five live blocks walk two chunks of four, the last one short
    "two_chunks_b8": (8, 9, [39, 64, 1], 3, 24, 8, jnp.float32),
    "bfloat16_b8": (8, 6, [5, 30, 47], 4, 32, 16, jnp.bfloat16),
    "wide_b256": (256, 3, [700, 255, 256], 8, 128, 64, jnp.float32),
}


def latent_decode_reference(q_lat, q_rope, pool, tables, pos, *, scale):
    """What ``ops.pallas.mla_attention.mla_paged_attention`` computes, by a
    gather of the tables' blocks: ``q_lat`` [b, H, r], ``q_rope`` [b, H,
    dr], ``pool`` [blocks, r + dr, bs] -> float32 [b, H, r], each head's
    softmax over the keys ``0 .. pos`` of ``(q_lat . c + q_rope . k_r)
    scale`` times the latents ``c``."""
    r = q_lat.shape[-1]
    g = pool[jnp.asarray(tables, jnp.int32)].astype(jnp.float32)
    b, T, w, bs = g.shape
    g = g.transpose(0, 1, 3, 2).reshape(b, T * bs, w)      # [b, keys, r + dr]
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    lg = jnp.einsum("bhw,bkw->bhk", q, g,
                    precision=jax.lax.Precision.HIGHEST) * scale
    seen = jnp.arange(T * bs)[None, None, :] <= jnp.asarray(pos)[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p, g[..., :r],
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("case", sorted(MLA_DECODE))
def test_the_absorbed_latent_read_equals_the_gathered_reference(case):
    """``mla_decode_attn`` under the interpreter against a gather of the
    tables' blocks: ragged lengths, a dead slot (position 0), a walk of
    several chunks, tables whose blocks are scattered, the trash block
    (poisoned) behind every live one."""
    from paddle_tpu.ops.pallas.mla_attention import mla_paged_attention
    bs, T, pos, heads, r, dr, dtype = MLA_DECODE[case]
    rng = np.random.RandomState(len(case))
    tables, nb = _tables_for(pos, 1, bs, T)
    pool = jnp.asarray(rng.randn(nb, r + dr, bs), jnp.float32)
    pool = pool.at[0].set(100.0).astype(dtype)
    q_lat = jnp.asarray(rng.randn(len(pos), heads, r), jnp.float32)
    q_rope = jnp.asarray(rng.randn(len(pos), heads, dr), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    out = mla_paged_attention(q_lat, q_rope, pool, tables, posv, scale=0.21)
    # the kernel multiplies the scaled query in the pool's type
    ref = latent_decode_reference(
        (q_lat * 0.21).astype(dtype), (q_rope * 0.21).astype(dtype), pool,
        tables, posv, scale=1.0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("s,live,tiles", [(64, [64, 21], (16, 8)),
                                          (48, [1, 48], (16, 16)),
                                          (32, [32, 9], (32, 8))])
def test_the_materialised_latent_read_equals_a_masked_softmax(
        monkeypatch, s, live, tiles):
    """``mla_prompt_attn`` under the interpreter: a key of two parts (a
    head's own and the ONE rotated key of all heads) beside a value of
    another width, causal by tiles; rows of query blocks past a prompt's
    live rows come out zero and its live rows are untouched by the
    padding."""
    from paddle_tpu.ops.pallas import mla_attention as M
    monkeypatch.setattr(M, "PROMPT_BLOCK_Q", tiles[0])
    monkeypatch.setattr(M, "PROMPT_BLOCK_K", tiles[1])
    rng = np.random.RandomState(s)
    b, heads, dn, dr, dv = 2, 3, 16, 8, 24
    q_n, k_n = (jnp.asarray(rng.randn(b, heads, s, dn), jnp.float32)
                for _ in range(2))
    q_r = jnp.asarray(rng.randn(b, heads, s, dr), jnp.float32)
    k_r = jnp.asarray(rng.randn(b, s, dr), jnp.float32)
    v = jnp.asarray(rng.randn(b, heads, s, dv), jnp.float32)
    out = np.asarray(M.mla_prompt_attention(
        q_n, q_r, k_n, k_r, v, scale=0.3, live=jnp.asarray(live, jnp.int32)))
    lg = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n)
          + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r)) * 0.3
    seen = jnp.tril(jnp.ones((s, s), bool))
    ref = np.asarray(jnp.einsum(
        "bhqk,bhkd->bhqd",
        jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), -1), v))
    for i, n in enumerate(live):
        np.testing.assert_allclose(out[i, :, :n], ref[i, :, :n], rtol=1e-5,
                                   atol=1e-5)
        skipped = -(-n // tiles[0]) * tiles[0]
        assert not out[i, :, skipped:].any()
    # counted from the live triangle, never the tiles the kernel ran
    assert M.prompt_pairs(1, s, live[0]) == live[0] * (live[0] + 1) // 2
    assert M.prompt_pairs(2, s, s + 5) == s * (s + 1)
