#!/usr/bin/env python
"""Measure Hogwild device workers (train_from_dataset thread_num>1).

The workers prove parity in the tests; this measures throughput in the
dispatch-bound regime they exist for: a small dense step (fc tower,
batch 64) where per-step latency is dominated by host-side dispatch +
fetch, not device compute. N workers overlap those blocking
round-trips against one shared compiled step — the hogwild_worker.cc
throughput story with XLA replacing the per-thread op execution.

    python tools/hogwild_bench.py      # prints one JSON line
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.framework import (Executor, Program, Scope,  # noqa: E402
                                  program_guard, unique_name)
from paddle_tpu.optimizer import SGDOptimizer  # noqa: E402


class _FeedStream:
    """Minimal Dataset facade: batch_iterator() over prebuilt feeds."""

    def __init__(self, feeds):
        self._feeds = feeds

    def batch_iterator(self, drop_last=False):
        return iter(self._feeds)


def build(seed=3):
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = seed
    with program_guard(main, startup), unique_name.guard():
        x = layers.data("x", [32])
        y = layers.data("y", [1])
        h = layers.fc(x, 64, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        SGDOptimizer(0.01).minimize(loss)
    return main, startup, loss


def run_mode(thread_num, n_batches=60, batch=64):
    main, startup, loss = build()
    scope = Scope()
    # hogwild needs a non-donating executor (shared scope buffers)
    exe = Executor(donate_state=False)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.randn(batch, 32).astype(np.float32),
              "y": rng.randn(batch, 1).astype(np.float32)}
             for _ in range(n_batches)]
    from paddle_tpu.trainer_desc import MultiTrainer
    desc = MultiTrainer()
    desc.set_thread(thread_num)
    # warmup/compile outside the timed window
    exe.train_from_dataset(main, _FeedStream(feeds[:2]), scope=scope,
                           fetch_list=[loss.name], trainer_desc=desc)
    t0 = time.perf_counter()
    exe.train_from_dataset(main, _FeedStream(feeds), scope=scope,
                           fetch_list=[loss.name], trainer_desc=desc)
    dt = time.perf_counter() - t0
    return n_batches * batch / dt, dt / n_batches


def main():
    import jax
    results = {}
    for n in (1, 2, 4):
        ex_s, step_s = run_mode(n)
        results[n] = (round(ex_s, 1), round(step_s * 1e3, 2))
    base = results[1][0]
    best_n = max(results, key=lambda n: results[n][0])
    print(json.dumps({
        "metric": "hogwild_speedup_best",
        "value": round(results[best_n][0] / base, 3), "unit": "x",
        "best_thread_num": best_n,
        "examples_per_sec": {str(n): results[n][0] for n in results},
        "step_ms": {str(n): results[n][1] for n in results},
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }))


if __name__ == "__main__":
    main()
