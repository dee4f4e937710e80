#!/usr/bin/env python3
"""What each form of a Mamba layer's scan costs on the chip, one layer at
the published widths (5120 channels, 16 states), float32 operands:

    python3 perfbench/study/scan_forms.py --out chiprun_out/p33/scan_forms.jsonl

- a prompt's scan at the cell's dispatch shapes (``rows x bucket``: 2 x 512,
  1 x 1024, 1 x 2048, 1 x 4096): the Pallas kernel
  (``ops/pallas/selective_scan.py``) at chunks of 64 / 128 / 256 rows of
  time, the chunked ``jax.numpy`` form (an associative scan inside chunks
  of 64: ``[64, 16, 5120]`` a row materialised a chunk) and the sequential
  ``lax.scan`` (one step a row of time);
- the decode step's one-token update at 64 rows (``ssm_ops.selective_step``,
  plain XLA: one pass over the 21 MB of a layer's states).

A line a (form, shape): median ms of ``--reps`` calls after the compiling
one, the floor ``bytes / HBM bandwidth`` the family counts for it, and the
form's distance from the sequential one (``y`` up to ``last`` and the state
at ``last``, relative, Frobenius).
"""

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

D, N = 5120, 16
SHAPES = ((2, 512), (1, 1024), (1, 2048), (1, 4096))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.ops.pallas import selective_scan as kernel
    from perfbench import flops
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("scan_forms needs the TPU (or --allow-cpu)")
    bw = flops.peaks(dev.device_kind)["hbm_bytes_per_s"] \
        if dev.platform == "tpu" else float("nan")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    d, n = (D, N) if dev.platform == "tpu" else (128, 4)
    shapes = SHAPES if dev.platform == "tpu" else ((2, 32),)

    def note(rec):
        rec.update(device=dev.device_kind, channels=d, states=n)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    def timed(fn, operands):
        try:
            jax.block_until_ready(fn(*operands))
        except Exception as e:      # a chunk the kernel's VMEM refuses
            return None, f"{type(e).__name__}: {str(e)[:200]}"
        ms = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            ms.append(1e3 * (time.perf_counter() - t))
        return float(np.median(ms)), None

    key = jax.random.PRNGKey(0)
    for rows, t in shapes:
        ks = jax.random.split(key, 6)
        x, z = (jax.random.normal(k, (rows, t, d)) for k in ks[:2])
        dt = jax.nn.softplus(jax.random.normal(ks[2], (rows, t, d)) - 4.0)
        a = -jnp.exp(jax.random.normal(ks[3], (n, d)) * 0.8 + 1.5)
        b, c = (jax.random.normal(k, (rows, t, n)) for k in ks[4:])
        last = jnp.full((rows,), t - 3, jnp.int32)
        operands = (x, dt, a, b, c, jnp.ones((d,)), z, last)
        floor_ms = 1e3 * 4.0 * (4 * rows * t * d + 2 * rows * t * n
                                + n * d + d + rows * n * d) / bw
        forms = {}
        for chunk in (64, 128, 256):
            def with_chunk(*ops, chunk=chunk):
                kernel.CHUNK = chunk
                try:
                    return kernel.selective_scan.__wrapped__(
                        *ops, interpret=dev.platform != "tpu")
                finally:
                    kernel.CHUNK = 128
            forms[f"kernel_chunk{chunk}"] = jax.jit(with_chunk)
        forms["xla_chunked64"] = jax.jit(ssm_ops.selective_scan_chunked)
        forms["xla_sequential"] = jax.jit(
            ssm_ops.selective_scan_sequential)
        want_y, want_s = forms["xla_sequential"](*operands)
        for name, fn in forms.items():
            ms, err = timed(fn, operands)
            rec = {"form": name, "rows": rows, "bucket": t, "ms": ms,
                   "floor_ms": floor_ms, "error": err,
                   "us_a_position": None if ms is None
                   else 1e3 * ms / (rows * t)}
            if ms is not None:
                # against the sequential form: y up to `last`, the state
                got_y, got_s = fn(*operands)
                upto = int(last[0]) + 1
                rec["y_rel_err"] = float(
                    jnp.linalg.norm(got_y[:, :upto] - want_y[:, :upto])
                    / jnp.linalg.norm(want_y[:, :upto]))
                rec["state_rel_err"] = float(
                    jnp.linalg.norm(got_s - want_s)
                    / jnp.linalg.norm(want_s))
            note(rec)
    # the decode step's update, 64 rows of one layer
    slots = 64 if dev.platform == "tpu" else 4
    ks = jax.random.split(key, 6)
    x, z = (jax.random.normal(k, (slots, d)) for k in ks[:2])
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, d)) - 4.0)
    a = -jnp.exp(jax.random.normal(ks[3], (n, d)) * 0.8 + 1.5)
    b, c = (jax.random.normal(k, (slots, n)) for k in ks[4:])
    state = jax.random.normal(key, (slots, n, d))
    step = jax.jit(ssm_ops.selective_step, donate_argnums=(7,))

    def one(state):
        return step(x, dt, a, b, c, jnp.ones((d,)), z, state)
    _, state = one(state)
    ms = []
    for _ in range(4 * args.reps):
        t0 = time.perf_counter()
        y, state = one(state)
        jax.block_until_ready(state)
        ms.append(1e3 * (time.perf_counter() - t0))
    note({"form": "xla_step", "rows": slots, "bucket": 1,
          "ms": float(np.median(ms)),
          "floor_ms": 1e3 * 2.0 * slots * n * d * 4 / bw, "error": None})
    return 0


if __name__ == "__main__":
    sys.exit(main())
