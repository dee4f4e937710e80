#!/usr/bin/env python3
"""A KV write of many rows in five forms, timed alone over donated pools at
the cells' shapes (PR 43).

    python3 perfbench/study/pool_write_forms.py --seed 4300000001 \\
        --out chiprun_out/p43/forms.jsonl

Each shape is ``pools`` pools of one layer kind, ``b`` requests of ``s`` rows
at unaligned positions, written by one jitted call that owns the pools (as
a step entry does: ``generation.POOLS_DONATED``); ms a call of

  ``scatter``   ``pool.at[phys, :, offset].set(rows)``: the program's form
                above 64 rows to PR 42, kept HERE only (XLA's TPU layout
                assignment moves the whole pool to ``[block, row, head, d]``
                and back for it);
  ``unrolled``  one ``dynamic_update_slice`` a row, unrolled: the program's
                form at 64 rows and under, forced on by lifting
                ``INPLACE_WRITE_MAX_ROWS`` HERE (shapes of at most 256 rows:
                the rest would be minutes of compile);
  ``rolled``    the same row updates inside a ``fori_loop`` (PR 25's
                ``microbench_write.py``, gone with its PR);
  ``loop``      a ``fori_loop`` over (request, touched chunk): the chunk
                (``attention_ops._chunk_rows`` picks it by shape) sliced out
                of the pool, merged with the request's rows under a mask
                and written back, one in-place fusion an iteration (HERE
                only: the program's first form in this PR, ~4 us an
                iteration whatever the chunk);
  ``chunks``    the program's form above 64 rows since PR 43: the same work
                items as the grid of one kernel with the pool aliased in and
                out (``ops/pallas/pool_write.py``);
  ``blocks``    the same kernel with the chunk forced to a whole block, where
                ``_chunk_rows`` picks less (a write within one block);

each checked against ``scatter`` off the trash block, bit for bit, on one
pool. One record a shape is appended to ``--out``. ``--rehearsal`` runs toy
shapes on the CPU for the code path only; its times are never a result.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: name -> (pool shape, dtype, pools, b, s, table entries): a cell's layer
#: kind at the batch its program has
SHAPES = {
    "lfm2_decode_128x1": ((1537, 4, 256, 128), "bfloat16", 4, 128, 1, 16),
    "lfm2_prompt_2x256": ((1537, 4, 256, 128), "bfloat16", 4, 2, 256, 16),
    "lfm2_prompt_1x512": ((1537, 4, 256, 128), "bfloat16", 4, 1, 512, 16),
    "lfm2_prompt_1x2048": ((1537, 4, 256, 128), "bfloat16", 4, 1, 2048, 16),
    "gpt_prompt_1x1024": ((400, 16, 16, 128), "float32", 48, 1, 1024, 65),
    "gpt_prompt_8x64": ((400, 16, 16, 128), "float32", 48, 8, 64, 64),
    # Mellum's largest bucket: 2 full layers write the prompt, 6 window
    # layers the 1280 rows their window keeps
    "mellum_prompt_full_1x12288": ((801, 4, 256, 128), "bfloat16", 4, 1,
                                   12288, 64),
    "mellum_prompt_window_1x1280": ((801, 4, 256, 128), "bfloat16", 12, 1,
                                    1280, 64),
    # for the next issue only: the decode writes at 64 rows and under
    "mellum_decode_16x1": ((801, 4, 256, 128), "bfloat16", 16, 16, 1, 64),
    "jamba_decode_64x1": ((1537, 1, 256, 128), "bfloat16", 4, 64, 1, 32),
    "gpt_decode_8x1": ((400, 16, 16, 128), "float32", 48, 8, 1, 64),
    # Jamba's prompts: one KV head, two attention layers
    "jamba_prompt_2x512": ((1537, 1, 256, 128), "bfloat16", 4, 2, 512, 32),
    "jamba_prompt_1x2048": ((1537, 1, 256, 128), "bfloat16", 4, 1, 2048, 32),
}
TOY = {
    "toy_decode_72x1": ((160, 2, 16, 128), "bfloat16", 2, 72, 1, 4),
    "toy_prompt_2x40": ((40, 2, 16, 128), "float32", 2, 2, 40, 4),
}


def timed(fn, pools, rest, calls):
    """ms a call of a jitted ``fn`` that owns ``pools`` (compiled and run
    once before), and the pools it left."""
    import jax
    pools = jax.block_until_ready(fn(pools, *rest))
    t = time.perf_counter()
    for _ in range(calls):
        pools = fn(pools, *rest)
    jax.block_until_ready(pools)
    return 1e3 * (time.perf_counter() - t) / calls, pools


def scatter(pool, new, pos, tables):
    """The fused scatter ``block_scatter_write`` took above 64 rows to
    PR 42."""
    import jax.numpy as jnp
    b, h, s, d = new.shape
    bs, T = pool.shape[2], tables.shape[1]
    rowpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    logical = rowpos // bs
    phys = jnp.take_along_axis(tables, jnp.minimum(logical, T - 1), axis=1)
    phys = jnp.where(logical < T, phys, 0)
    rows = jnp.swapaxes(new.astype(pool.dtype), 1, 2).reshape(b * s, h, d)
    return pool.at[phys.reshape(-1), :, (rowpos % bs).reshape(-1)].set(rows)


def rolled(pool, new, pos, tables):
    """One-row ``dynamic_update_slice``s inside a ``fori_loop``."""
    import jax
    import jax.numpy as jnp
    b, h, s, d = new.shape
    bs, T = pool.shape[2], tables.shape[1]
    rowpos = (pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]).reshape(-1)
    new = new.astype(pool.dtype)
    z = jnp.zeros((), jnp.int32)

    def one(k, pool):
        k = jnp.asarray(k, jnp.int32)
        logical = rowpos[k] // bs
        phys = jnp.where(logical < T,
                         tables[k // s, jnp.minimum(logical, T - 1)], 0)
        row = jax.lax.dynamic_slice(new, (k // s, z, k % s, z), (1, h, 1, d))
        return jax.lax.dynamic_update_slice(pool, row,
                                            (phys, z, rowpos[k] % bs, z))

    return jax.lax.fori_loop(0, b * s, one, pool)


def loop(pool, new, pos, tables):
    """The touched chunks, read, merged and written back one an iteration
    of a ``fori_loop``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import _chunk_rows
    b, h, s, d = new.shape
    bs, T = pool.shape[2], tables.shape[1]
    c = _chunk_rows(s, bs, pool.dtype)
    n = (s + c - 2) // c + 1
    start = pos[:, None] // c * c + c * jnp.arange(n, dtype=jnp.int32)[None]
    logical = start // bs
    phys = jnp.take_along_axis(tables, jnp.minimum(logical, T - 1), axis=1)
    phys = jnp.where(logical < T, phys, 0).reshape(-1)
    offset, first = (start % bs).reshape(-1), (start - pos[:, None]) \
        .reshape(-1)
    padded = jnp.pad(new.astype(pool.dtype), ((0, 0), (0, 0), (c, c), (0, 0)))
    z = jnp.zeros((), jnp.int32)

    def merge(k, pool):
        k = jnp.asarray(k, jnp.int32)
        at = (phys[k], z, offset[k], z)
        t = first[k] + jnp.arange(c, dtype=jnp.int32)
        lands = jnp.logical_and(t >= 0, t < s)[None, None, :, None]
        mine = jax.lax.dynamic_slice(padded, (k // n, z, first[k] + c, z),
                                     (1, h, c, d))
        held = jax.lax.dynamic_slice(pool, at, (1, h, c, d))
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(lands, mine, held), at)

    return jax.lax.fori_loop(0, b * n, merge, pool)


def measure(name, spec, args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import attention_ops as ao
    shape, dtype, pools, b, s, T = spec
    nb, h, bs, d = shape
    rng = np.random.RandomState(args.seed % (2 ** 31))
    # every request owns the blocks its rows reach, at an unaligned
    # position; the rest of its table is the trash block
    reach = -(-(bs - 1 + s) // bs)
    assert b * reach < nb and reach <= T, name
    tables = np.zeros((b, T), np.int32)
    pos = rng.randint(0, min(bs, T * bs - s + 1), size=b).astype(np.int32)
    tables[:, :reach] = 1 + rng.permutation(nb - 1)[:b * reach].reshape(
        b, reach)
    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    news = [jax.random.normal(k, (b, h, s, d), jnp.float32).astype(dtype)
            for k in jax.random.split(key, pools)]
    rest = (news, jnp.asarray(pos), jnp.asarray(tables))

    def over_pools(write):
        return jax.jit(lambda ps, ns, p, t: [write(a, n, p, t)
                                             for a, n in zip(ps, ns)],
                       donate_argnums=(0,))

    def program(max_rows, chunk=None):
        """``block_scatter_write`` with the row form's limit (and the
        chunk) set HERE for the trace; the program has no such option."""
        def write(pool, new, pos, tables):
            was = ao.INPLACE_WRITE_MAX_ROWS, ao._chunk_rows
            ao.INPLACE_WRITE_MAX_ROWS = max_rows
            if chunk is not None:
                ao._chunk_rows = lambda s, bs, dtype: chunk
            try:
                return ao.block_scatter_write(pool, new, pos, tables)
            finally:
                ao.INPLACE_WRITE_MAX_ROWS, ao._chunk_rows = was
        return write

    c = ao._chunk_rows(s, bs, dtype)
    forms = {"scatter": scatter, "rolled": rolled, "loop": loop,
             "chunks": program(0)}
    if b * s <= 256:
        forms["unrolled"] = program(b * s)
    if c != bs:
        forms["blocks"] = program(0, bs)
    rec = {"tag": "pool_write_forms", "shape": name, "pool": list(shape),
           "dtype": dtype, "pools": pools, "b": b, "s": s,
           "chunk_rows": c, "chunks_a_request": (s + c - 2) // c + 1,
           "calls": args.calls, "ms": {}, "compile_s": {},
           "equals_scatter_off_trash": {}}
    one = jnp.zeros(shape, dtype) + jnp.arange(nb, dtype=jnp.float32) \
        .astype(dtype)[:, None, None, None]
    want = jax.jit(scatter)(one, news[0], *rest[1:])[1:]
    for form, write in forms.items():
        got = jax.jit(write)(one, news[0], *rest[1:])[1:]
        rec["equals_scatter_off_trash"][form] = bool(
            jnp.array_equal(got, want))
        del got
        held = [jnp.zeros(shape, dtype) for _ in range(pools)]
        t = time.perf_counter()
        fn = over_pools(write)
        held = jax.block_until_ready(fn(held, *rest))
        rec["compile_s"][form] = time.perf_counter() - t
        rec["ms"][form], held = timed(fn, held, rest, args.calls)
        del held
        print(name, form, rec["ms"][form], file=sys.stderr, flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--shapes", default="",
                    help="comma-separated names (default: all)")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    table = TOY if args.rehearsal else SHAPES
    if not args.rehearsal and dev.platform != "tpu":
        raise SystemExit("pool_write_forms.py times a TPU; use --rehearsal "
                         "for the code path")
    names = [n for n in args.shapes.split(",") if n] or list(table)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for name in names:
        rec = measure(name, table[name], args)
        rec.update(seed=args.seed, rehearsal=args.rehearsal,
                   device={"platform": dev.platform,
                           "device_kind": dev.device_kind})
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
