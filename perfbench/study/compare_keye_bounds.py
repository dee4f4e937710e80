#!/usr/bin/env python3
"""``compare_keye.py`` with the one planted fault the bounded prompt read
needs beside ``no_causal_mask`` (PR 47).

    python3 perfbench/study/compare_keye_bounds.py --seed 4600000301 \\
        --out chiprun_out/p47c3/compare_bounds.jsonl

Since PR 47 a prompt's read is causal TWICE: by the chosen set's mask (a
row chooses among keys ``<= t``) and by the extents of its loops (a chunk
of queries multiplies no key tile past its own last live row:
``ops.attention_ops.sparse_prompt_tiles``). ``compare_keye.py``'s
``no_causal_mask`` takes the first away and the second still holds: a
query then reaches at most the rest of its own tile of 512 keys, where on
the rectangle of PR 46 it reached every later row of the bucket and its
padding. The comparison still sees it (sets, set sizes, both increments)
but the logit deficit the benchmark's ``correct`` reads does not (0.008
against 0.77 on the rectangle: my chip run, PR 47, call 2). This script
plants the fault that is to the bounded read what ``no_causal_mask`` was
to the rectangle, ``no_causal_bound``: the mask ignored AND every chunk
given every tile of the bucket, which must read over ``deficit_max`` as
that did. Everything else (the clean run, the reference, the limits, the
record's form) is ``compare_keye.py``'s own, unchanged.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare_keye as C                                       # noqa: E402

FAULT = "no_causal_bound"


def inject(model, fault, planted=C.inject):
    if fault != FAULT:
        return planted(model, fault)
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import attention_ops as A
    mask, tiles = A.topk_mask, A.sparse_prompt_tiles

    def every_tile(s, live=None):
        c, kt, _ = A.sparse_prompt_tiling(s)
        return np.full((s // c,), s // kt, np.int32)

    def repair():
        A.topk_mask, A.sparse_prompt_tiles = mask, tiles
    A.sparse_prompt_tiles = every_tile
    A.topk_mask = lambda s, valid, k: mask(s, jnp.ones_like(valid), k)
    return repair


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--faults" not in argv:
        argv += ["--faults", FAULT]
    C.inject = inject
    return C.main(argv)


if __name__ == "__main__":
    sys.exit(main())
