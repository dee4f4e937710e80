#!/usr/bin/env python3
"""A numpy model of what the harness's check can see of dots.vlm1's latent
attention at random weights, by the initial scales: NOT a device number and
not the program, but the arithmetic that chose ``embed_init_std`` /
``attn_q_init_std`` / ``attn_out_init_std`` in
``configs/dots-vlm1-share32-d6.json`` before the one chip run that could
be afforded (PERF.md section 6, PR 49).

    python3 perfbench/study/latent_share_model.py --embed 24 --w-o 0.075 \
        --w-qb 0.066 --positions 2048 --seed 1 --out /root/scratch/m.jsonl

Six layers of latent attention ONLY at the published widths (hidden 7168,
latents 1536 / 512, a head 128 + 64 / 128, YaRN as published), ``--heads``
heads standing for the 128 (W_o's std scaled so that their sum has the 128's
size), the MLPs left out (at an embedding of 24 a dense MLP is 6% of the
stream, a routed expert's term 0.6%), an untied head of 16160. Four readings
of the last 512 positions' logits: float32; every matmul's input rounded to
bfloat16 where the program rounds it (``bf16``: the sound program); that
with the cache's rows rounded to float8's 3 bits of mantissa (``fp8``: the
control of precision); and with the rotated key dropped (``rot``). For each
against float32, ``serve.check``'s deficit of the emitted token: its
maximum and the positions over 0.05.

What it showed (5 seeds at 2048 and 8192 positions; ``runs_pr49.jsonl``):
at ``--w-o 1.9`` under the flat softmax of 0.02 weights (score std 1.5,
~250 effective keys) each layer adds the mean of the keys' values, one
vector for every position, multiplied 4x a layer: 98.7% of the stream after
six, 9 distinct top tokens in 512 positions, which is what call 9 read on
the chip (stream 183 here, 180 there; the clean program's median largest
logit error 0.0308 both). A PEAKED softmax (``--w-qb`` 0.066: score std 5,
4.5-6.5 effective keys at 2k-8k positions) carries a quarter of the stream
at 5% common part, and float8 rows then err 7.4x the clean program whatever
the scale.
"""

import argparse
import json
import math
import time

import numpy as np

H, RQ, R, DR, DN, DV, HEADS, LAYERS, VOCAB = \
    7168, 1536, 512, 64, 128, 128, 128, 6, 16160
ANSWER = 512
STD = 0.02
FIRST = 4000        # the first position: contexts of thousands


def bf16(x):
    """Round to nearest even to bfloat16, kept in float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    return ((u + (((u >> 16) & 1) + 0x7fff)) & 0xffff0000).view(np.float32)


def fp8_mantissa(x):
    m, e = np.frexp(np.asarray(x, np.float32))
    return np.ldexp(np.round(m * 16) / 16, e).astype(np.float32)


def rms(x):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)


def yarn_inv_freq():
    theta, factor, orig, fast, slow = 10000.0, 40.0, 4096, 32.0, 1.0
    pos = theta ** (np.arange(0, DR, 2) / DR)

    def corr(n):
        return DR * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), DR - 1)
    keep = 1 - np.clip((np.arange(DR // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (1 / (factor * pos)) * (1 - keep) + (1 / pos) * keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--embed", type=float, required=True)
    ap.add_argument("--w-o", type=float, required=True)
    ap.add_argument("--w-qb", type=float, default=STD)
    ap.add_argument("--positions", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--modes", default="bf16,fp8,rot")
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    S, hs = a.positions, a.heads
    rng = np.random.default_rng(a.seed)

    def drawn(shape, std):
        return bf16(rng.standard_normal(shape, dtype=np.float32) * std)
    layers = [dict(qa=drawn((H, RQ), STD), kva=drawn((H, R + DR), STD),
                   qb=drawn((RQ, hs, DN + DR), a.w_qb),
                   kvb=drawn((R, hs, DN + DV), STD),
                   o=drawn((hs * DV, H), a.w_o * math.sqrt(HEADS / hs)))
              for _ in range(LAYERS)]
    head, emb = drawn((H, VOCAB), STD), drawn((S, H), a.embed)
    ang = (np.arange(S, dtype=np.float64) + FIRST)[:, None] * yarn_inv_freq()
    cos, sin = np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)
    scale = (0.1 * math.log(40) + 1) ** 2 / math.sqrt(DN + DR)
    causal = np.tril(np.ones((S, S), bool))

    def rot(x):
        c, s = (cos, sin) if x.ndim == 2 else (cos[:, None], sin[:, None])
        x1, x2 = x[..., :DR // 2], x[..., DR // 2:]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def run(mode):
        act = (lambda x: x) if mode == "f32" else bf16
        x, read = emb.copy(), []
        for w in layers:
            u = act(rms(x))
            kv = u @ w["kva"]
            k_r = np.zeros((S, DR), np.float32) if mode == "rot" \
                else rot(kv[:, R:])
            kept = act(np.concatenate([rms(kv[:, :R]), k_r], -1))
            if mode == "fp8":
                kept = fp8_mantissa(kept)
            q = np.einsum("sq,qhd->shd", act(rms(u @ w["qa"]) * scale),
                          w["qb"])
            q_n, q_r = act(q[..., :DN]), act(rot(q[..., DN:]))
            k_n, v = (act(np.einsum("sr,rhd->shd", kept[:, :R], part))
                      for part in (w["kvb"][..., :DN], w["kvb"][..., DN:]))
            o = np.empty((S, hs, DV), np.float32)
            for h in range(hs):
                for lo in range(0, S, 1024):
                    hi = min(lo + 1024, S)
                    sc = q_n[lo:hi, h] @ k_n[:hi, h].T \
                        + q_r[lo:hi, h] @ kept[:hi, R:].T
                    sc = np.where(causal[lo:hi, :hi], sc, -np.inf)
                    p = np.exp(sc - sc.max(-1, keepdims=True))
                    p /= p.sum(-1, keepdims=True)
                    if h == 0 and hi == S:
                        keys = float((1 / (p[-ANSWER:] ** 2).sum(-1)).mean())
                    o[lo:hi, h] = act(act(p) @ v[:hi, h])
            y = act(o.reshape(S, -1) @ w["o"])
            read.append((round(float(np.sqrt((y[-ANSWER:] ** 2).mean())), 3),
                         round(keys, 1)))
            x = x + y
        return act(rms(x[-ANSWER:])) @ head, x[-ANSWER:], read

    t = time.time()
    ref, x, read = run("f32")
    top = np.sort(ref, -1)
    rec = {"tool": "latent_share_model", "device": "numpy on the CPU: a "
           "model, not a device number", "embed": a.embed, "w_o": a.w_o,
           "w_qb": a.w_qb, "positions": S, "heads": hs, "seed": a.seed,
           "attention_rms_and_effective_keys_a_layer": read,
           "stream_rms": float(np.sqrt((x ** 2).mean())),
           "common_share": float(np.sqrt((x.mean(0) ** 2).mean())
                                 / np.sqrt((x ** 2).mean())),
           "top_lead_median": float(np.median(top[:, -1] - top[:, -2])),
           "distinct_top_tokens": len(set(ref.argmax(-1).tolist()))}
    for mode in [m for m in a.modes.split(",") if m]:
        got = run(mode)[0]
        d = ref.max(-1) - ref[np.arange(ANSWER), got.argmax(-1)]
        rec[mode] = {"deficit_max": float(d.max()),
                     "positions_over_0.05": int((d > 0.05).sum()),
                     "logit_error_std": float(np.sqrt(((got - ref) ** 2)
                                                      .mean())),
                     "largest_logit_error_p50":
                         float(np.median(np.abs(got - ref).max(-1)))}
    rec["seconds"] = round(time.time() - t, 1)
    print(json.dumps(rec), flush=True)
    with open(a.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
