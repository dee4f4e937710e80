#!/usr/bin/env python3
"""A decode row's selected read, two forms, on the chip (PR 46).

    python3 perfbench/study/decode_read_forms_keye.py [--out runs.jsonl]

``ops.attention_ops.sparse_decode_attention`` walks a row's live blocks
through ``paged_decode_attn`` under the chosen set's mask. Until this PR's
second round it gathered the chosen rows of K and V through the block
table (``gathered`` below keeps that form, as the oracle and as the other
side of the timing). At ``keye_longdoc_24k``'s shapes (8 rows, 32 query
heads on 4 KV heads of 128, 256-row bfloat16 blocks, tables of 68 and of
100 entries, ``topk`` 2048, scores rounded so that ties at the cut are
common) this prints one JSON line a case: the largest difference of the two
outputs, whether both counted the same eligible and chosen keys, and each
form's ms a call (the whole op: the sort, the mask or the indices, the
read). Contexts under ``topk`` are a case too: both keep every key. Random
values from fixed keys; a chip run of a minute.
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from paddle_tpu.ops import attention_ops as A                  # noqa: E402

#: (table entries a row, the rows' positions drawn from)
CASES = ((68, (4300, 16500)), (100, (4300, 24500)), (68, (100, 3000)))
B, HQ, HKV, D, BS, TOPK = 8, 32, 4, 128, 256, 2048


def gathered(q, k_pool, v_pool, tables, pos, scores, topk):
    """The form the walk replaced: ``lax.top_k``'s indices, the chosen rows
    of K and V gathered through the table, a softmax over them."""
    b, hq, _, d = q.shape
    blocks, hkv, bs, _ = k_pool.shape
    n = scores.shape[1]
    eligible = jnp.arange(n, dtype=jnp.int32)[None] <= pos[:, None]
    masked = jnp.where(eligible, jnp.where(scores == 0, 0.0, scores),
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(int(topk), n))
    idx = idx.astype(jnp.int32)
    chosen = idx <= pos[:, None]
    phys = jnp.take_along_axis(tables, idx // bs, axis=1)
    flat = (phys[:, None, :] * hkv
            + jnp.arange(hkv, dtype=jnp.int32)[None, :, None]) * bs \
        + (idx % bs)[:, None, :]
    kg = jnp.take(k_pool.reshape(blocks * hkv * bs, d), flat, axis=0)
    vg = jnp.take(v_pool.reshape(blocks * hkv * bs, d), flat, axis=0)
    qg = q.reshape(b, hkv, hq // hkv, d).astype(kg.dtype)
    logits = jnp.einsum("bhgd,bhkd->bhgk", qg, kg,
                        preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(d))
    logits = jnp.where(chosen[:, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, d), jnp.stack(
        [jnp.sum(eligible, 1, dtype=jnp.int32),
         jnp.sum(chosen, 1, dtype=jnp.int32)], 1)


def case(T, ctx):
    nb = B * T + 1
    ks = jax.random.split(jax.random.PRNGKey(T + ctx[0]), 6)
    q = jax.random.normal(ks[0], (B, HQ, 1, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (nb, HKV, BS, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (nb, HKV, BS, D), jnp.bfloat16)
    tables = jax.random.permutation(ks[3], nb - 1)[:B * T].reshape(
        B, T).astype(jnp.int32) + 1
    pos = jax.random.randint(ks[4], (B,), *ctx).astype(jnp.int32)
    scores = jnp.round(
        jax.random.normal(ks[5], (B, T * BS), jnp.float32) * 40) / 40
    args = (q, kp, vp, tables, pos, scores)
    walk = jax.jit(lambda *a: A.sparse_decode_attention(*a, TOPK))
    gather = jax.jit(lambda *a: gathered(*a, TOPK))
    (o1, c1), (o0, c0) = walk(*args), gather(*args)
    rec = {"table_entries": T, "positions": np.asarray(pos).tolist(),
           "max_abs_diff": float(jnp.max(jnp.abs(o1 - o0))),
           "max_abs": float(jnp.max(jnp.abs(o0))),
           "counts_equal": bool(jnp.all(c1 == c0)),
           "chosen": np.asarray(c1)[:, 1].tolist()}
    for name, f in (("walk_ms", walk), ("gather_ms", gather)):
        f(*args)[0].block_until_ready()
        t = time.perf_counter()
        for _ in range(30):
            r = f(*args)
        r[0].block_until_ready()
        rec[name] = (time.perf_counter() - t) / 30 * 1e3
    rec["device"] = jax.devices()[0].device_kind
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="append the lines to this file too")
    args = ap.parse_args(argv)
    for T, ctx in CASES:
        line = json.dumps(dict(tool="decode_read_forms_keye",
                               **case(T, ctx)))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
