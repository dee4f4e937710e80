#!/usr/bin/env python
"""The tied head and the loss over the vocabulary, forward and backward,
in three forms at the GPT training cells' shape (``[8, 1024, 2048]``
bfloat16 x ``[50304, 2048]``), timed alone on the chip (PR 56):

(a) ``old``: the lowerings to PR 55: ``matmul_v2``, the AMP black list's
    cast to float32, ``log_softmax`` + ``take_along_axis`` (kept here,
    registered as ``softmax_with_cross_entropy_old``), its backward the
    registry's generic ``jax.vjp`` inside the backward op, the cast's
    grad, ``matmul_v2_grad``;
(b) ``one_pass``: the same chain over the op as it stands
    (``ops/nn_ops.py``: one read of the logits each way);
(c) ``chunked_<c>``: the product inside a ``lax.scan`` over ``c``
    positions of the sequence, logits never whole, recomputed in the
    backward; dW is the scan's carry.

Each form is one jitted function ``(h, w, labels) -> (loss, dh, dW)``
with the mean's cotangent inside. One JSON line a form: ms a call (mean
and least of ``--calls`` calls behind two warm ones),
``memory_analysis()``'s temporaries, the loss, and the largest distance
of ``dh`` / ``dW`` from form (a)'s.

    python3 tools/head_loss_forms.py --out chiprun_out/<call>/forms.jsonl
    JAX_PLATFORMS=cpu python3 tools/head_loss_forms.py --batch 2 --seq 64 \
        --hidden 32 --vocab 96 --chunks 16,32    # a rehearsal, no timing
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from paddle_tpu.ops import registry  # noqa: E402
from paddle_tpu.ops.registry import LoweringContext, execute  # noqa: E402

OLD = "softmax_with_cross_entropy_old"


def _old_lowering(ctx, ins, attrs):
    """The hard-label lowering to PR 55, word for word."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1)
    ignore_index = attrs.get("ignore_index", -100)
    logp = jax.nn.log_softmax(logits, axis=axis)
    softmax = jnp.exp(logp)
    lbl = label
    if lbl.ndim == logits.ndim and lbl.shape[axis] == 1:
        lbl = jnp.squeeze(lbl, axis)
    valid = lbl != ignore_index
    n_class = logits.shape[axis]
    safe_lbl = jnp.clip(jnp.where(valid, lbl, 0), 0, n_class - 1)
    picked = jnp.take_along_axis(
        logp, jnp.expand_dims(safe_lbl, axis).astype(jnp.int32), axis=axis)
    loss = jnp.where(jnp.expand_dims(valid, axis), -picked, 0.0)
    return {"Softmax": [softmax], "Loss": [loss]}


def op_chain(loss_op: str):
    """Head and loss as the train step's tape records them, op by op."""
    ctx = LoweringContext()
    attrs = {"soft_label": False, "ignore_index": -100, "axis": -1}

    def step(h, w, labels):
        b, s, _ = h.shape
        mm = {"X": [h], "Y": [w]}
        logits = execute(ctx, "matmul_v2", mm, {"trans_y": True})["Out"][0]
        flat = logits.reshape(b * s, -1)
        cast = {"X": [flat]}
        z = execute(ctx, "cast", cast, {"out_dtype": "float32"})["Out"][0]
        lab = labels.reshape(b * s, 1)
        loss = execute(ctx, loss_op, {"Logits": [z], "Label": [lab]},
                       attrs)["Loss"][0]
        g = jnp.full(loss.shape, 1.0 / loss.size, loss.dtype)
        dz = execute(ctx, loss_op + "_grad",
                     {"Logits": [z], "Label": [lab], "Loss@GRAD": [g]},
                     attrs)["Logits@GRAD"][0]
        dflat = execute(ctx, "cast_grad", {**cast, "Out@GRAD": [dz]},
                        {"out_dtype": "float32"})["X@GRAD"][0]
        grads = execute(ctx, "matmul_v2_grad",
                        {**mm, "Out@GRAD": [dflat.reshape(logits.shape)]},
                        {"trans_y": True})
        return loss.sum() / loss.size, grads["X@GRAD"][0], grads["Y@GRAD"][0]
    return step


def chunked(c: int):
    """The product inside the scan: a chunk's logits live only in its
    iteration, forward and backward."""
    def stats(hc, w, lc):
        z = jnp.einsum("bch,vh->bcv", hc, w,
                       preferred_element_type=jnp.float32)
        m = jnp.max(z, axis=-1, keepdims=True)
        lse = m + jnp.log(jnp.sum(jnp.exp(z - m), axis=-1, keepdims=True))
        col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 2)
        onehot = col == lc[..., None]
        return z, lse, onehot

    def step(h, w, labels):
        b, s, hid = h.shape
        n = s // c
        hs = h.reshape(b, n, c, hid).swapaxes(0, 1)
        ls = labels.reshape(b, n, c).swapaxes(0, 1)

        def fwd(total, xs):
            z, lse, onehot = stats(xs[0], w, xs[1])
            picked = jnp.sum(jnp.where(onehot, z, 0.0), axis=-1)
            return total + jnp.sum(lse[..., 0] - picked), None
        total, _ = jax.lax.scan(fwd, jnp.float32(0.0), (hs, ls))

        def bwd(dw, xs):
            z, lse, onehot = stats(xs[0], w, xs[1])
            d = ((jnp.exp(z - lse) - onehot) / (b * s)).astype(h.dtype)
            dh = jnp.einsum("bcv,vh->bch", d, w)
            dw = dw + jnp.einsum("bcv,bch->vh", d, xs[0],
                                 preferred_element_type=jnp.float32)
            return dw, dh
        dw, dhs = jax.lax.scan(bwd, jnp.zeros(w.shape, jnp.float32),
                               (hs, ls))
        return (total / (b * s), dhs.swapaxes(0, 1).reshape(h.shape),
                dw.astype(w.dtype))
    return step


def forms(chunks):
    if OLD not in registry.OPS:     # here and not at import: tests import
        registry.register(OLD, no_grad_slots=("Label",),    # op_chain
                          nondiff_outputs=("Softmax",))(_old_lowering)
    out = {"old": op_chain(OLD),
           "one_pass": op_chain("softmax_with_cross_entropy")}
    for c in chunks:
        out[f"chunked_{c}"] = chunked(c)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--chunks", default="128,256,512")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=56)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    kh, kw, kl = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    h = jax.random.normal(kh, (args.batch, args.seq, args.hidden),
                          jnp.bfloat16)
    w = (0.02 * jax.random.normal(kw, (args.vocab, args.hidden))
         ).astype(jnp.bfloat16)
    labels = jax.random.randint(kl, (args.batch, args.seq), 0, args.vocab,
                                jnp.int32)
    dev = jax.devices()[0]
    first = None
    for name, fn in forms([int(c) for c in args.chunks.split(",") if c]
                          ).items():
        compiled = jax.jit(fn).lower(h, w, labels).compile()
        mem = compiled.memory_analysis()
        for _ in range(2):
            got = jax.block_until_ready(compiled(h, w, labels))
        ms = []
        for _ in range(args.calls):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(h, w, labels))
            ms.append(1e3 * (time.perf_counter() - t0))
        got = [np.asarray(x, np.float32) for x in got]
        first = first or got
        line = {"pr": 56, "tool": "tools/head_loss_forms.py", "form": name,
                "shape": [args.batch, args.seq, args.hidden, args.vocab],
                "platform": dev.platform, "kind": dev.device_kind,
                "ms_a_call_mean": float(np.mean(ms)),
                "ms_a_call_min": float(np.min(ms)), "ms_calls": ms,
                "temp_bytes": None if mem is None
                else int(mem.temp_size_in_bytes),
                "loss": float(got[0]),
                "dh_max_abs_from_old": float(np.abs(got[1] - first[1]).max()),
                "dw_max_abs_from_old": float(np.abs(got[2] - first[2]).max()),
                "dh_max_abs": float(np.abs(first[1]).max()),
                "dw_max_abs": float(np.abs(first[2]).max())}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
