#!/usr/bin/env python
"""Driver benchmark: one train step (default ``gpt2-1p1b``) on the chip.

One process for each chip: this parent imports only numpy and the
standard library — never jax, never paddle_tpu — and runs every attempt
as a ``--child`` process, because a process that has touched JAX holds
the chip and a child that needs it then fails or hangs. Keep it so.

Prints ONE JSON line:
  {"metric": "gpt2_345m_mfu", "value": <achieved MFU %>, "unit": "%",
   "vs_baseline": <MFU / 40% north-star>, ...extras}

The train step is the flagship path: paddle_tpu.models GPT ->
dygraph-to-static (one XLA computation: forward, program-level backward,
AdamW update, all state donated) with AMP O2 bf16 so matmuls hit the MXU.
Model FLOPs are counted analytically (fwd matmul FLOPs x3 for fwd+bwd),
the standard MFU accounting; peak is the chip's bf16 rating from the
one table keyed by device_kind (paddle_tpu.utils.chip.TPU_PEAKS; v5e:
197 TFLOP/s; override with BENCH_PEAK_FLOPS).

Measurement discipline (each item burned a previous round):
- the timed call uses the SAME (steps, batch, seq) shapes as the warmup
  call, so zero recompiles land inside the timed window;
- synchronization is a value fetch (np.asarray of the per-step losses)
  inside the window: it waits for the device exactly as
  ``block_until_ready`` would, and hands back the numbers the result
  line reports, so a window that did not run cannot be timed;
- a computed MFU > 100% is physically impossible and aborts the run
  instead of being printed;
- each OOM retry runs in a FRESH subprocess (an in-process retry keeps
  the failed attempt's device buffers), and the result line carries
  ``batch_asked`` beside the ``batch`` that ran.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

OOM_RC = 42  # child exit code meaning "out of device memory"

#: configs that need per-block recompute and the grads-internal contract
#: to train on one 16 GB chip; both become defaults for them
BILLION_CLASS = ("gpt2-1p1b", "gpt2-1p3b")


def billion_class_defaults(model_name: str):
    """Set BENCH_RECOMPUTE / BENCH_NO_RETAIN_GRADS for the configs that
    need them (an explicit setting of either stands)."""
    if model_name in BILLION_CLASS:
        os.environ.setdefault("BENCH_RECOMPUTE", "1")
        os.environ.setdefault("BENCH_NO_RETAIN_GRADS", "1")


def is_oom(e: Exception) -> bool:
    """Does this exception say the device ran out of memory?"""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def detect_peak_flops(device) -> float:
    """Peak bf16 FLOP/s the MFU divides by. On a TPU: the device_kind's
    row of the one peak table, ``paddle_tpu.utils.chip.TPU_PEAKS`` (an
    unlisted kind raises — no default).
    Off the TPU there is no chip to rate, and the rehearsal's "MFU"
    keeps the v5e figure it always used (ROADMAP D5's remainder)."""
    if "BENCH_PEAK_FLOPS" in os.environ:
        return float(os.environ["BENCH_PEAK_FLOPS"])
    if device.platform == "tpu":
        from paddle_tpu.utils.chip import tpu_peaks
        return tpu_peaks(device.device_kind)[0]
    return 197e12


def model_flops_per_token(cfg, seq: int) -> float:
    """Forward matmul FLOPs per token x3 (backward = 2x forward)."""
    h, f, L, V = (cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_layers,
                  cfg.vocab_size)
    per_layer = 8 * h * h + 4 * h * f + 4 * seq * h  # qkv+out, ffn, attn
    fwd = L * per_layer + 2 * h * V                  # + tied LM head
    return 3.0 * fwd


def build_train(model_name: str, seq: int = 1024):
    """The flagship GPT train job, before compilation: ``(cfg, model,
    opt, train_step, retain_grads)``. ``build_steps`` compiles it with
    ``jit.to_static``; ``chip_smoke.py`` hands the same pieces to
    ``zero_train_step`` for the path across chips."""
    from paddle_tpu import amp
    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
    from paddle_tpu.optimizer import AdamW

    cfg = GPT_CONFIGS[model_name]
    import dataclasses
    if os.environ.get("BENCH_RECOMPUTE") == "1":
        cfg = dataclasses.replace(cfg, recompute=True)
    if os.environ.get("BENCH_GPT_LAYERS"):
        # capacity-search override (PERF.md ≥1B analysis)
        cfg = dataclasses.replace(
            cfg, num_layers=int(os.environ["BENCH_GPT_LAYERS"]))
    if seq > cfg.max_position_embeddings:
        # long-seq configs need position rows to exist (the model raises
        # on out-of-range positions rather than NaN-ing)
        cfg = dataclasses.replace(cfg, max_position_embeddings=seq)
    model = GPTForCausalLM(cfg)
    # bf16 m/v is the recommended TPU config (halves optimizer-state HBM;
    # measured +1.1pt MFU on the 345M flagship) — opt out with =0
    moment_dtype = (None if os.environ.get("BENCH_BF16_MOMENTS") == "0"
                    else "bfloat16")
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                moment_dtype=moment_dtype)
    from paddle_tpu import flags as _flags
    _flags.set_flags({
        "pallas_flash_block_q": int(os.environ.get("BENCH_FLASH_BQ", 512)),
        "pallas_flash_block_k": int(os.environ.get("BENCH_FLASH_BK", 512)),
        "use_pallas_layer_norm": os.environ.get("BENCH_PALLAS_LN",
                                                "0") == "1"})

    def train_step(ids, labels):
        with amp.auto_cast(level="O2"):
            loss = model(ids, labels=labels)
        model.clear_gradients()
        loss.backward()
        opt.step()
        return loss

    # BENCH_NO_RETAIN_GRADS=1: grads stay internal to the compiled step
    # (set_to_none contract) — the ≥1B capacity lever
    retain = os.environ.get("BENCH_NO_RETAIN_GRADS") != "1"
    return cfg, model, opt, train_step, retain


def build_steps(model_name: str, seq: int = 1024):
    from paddle_tpu import jit

    cfg, model, opt, train_step, retain = build_train(model_name, seq)
    step = jit.to_static(train_step, layers=[model], optimizers=[opt],
                         retain_grads=retain)
    multi = jit.to_static_multi_step(train_step, layers=[model],
                                     optimizers=[opt],
                                     retain_grads=retain)
    return cfg, step, multi


def child_main_ernie(batch: int, seq: int, steps: int) -> int:
    """BENCH_MODEL=ernie: ERNIE-base MLM+SOP pretraining step (BASELINE
    configs[3]'s model family, single-chip perf point; the sharded
    multi-chip regime is exercised by the dryrun's ZeRO+TP leg)."""
    import dataclasses

    import jax

    from paddle_tpu import amp, jit
    from paddle_tpu.models import ERNIE_CONFIGS, ErnieForPretraining
    from paddle_tpu.optimizer import AdamW

    dev = jax.devices()[0]
    peak = detect_peak_flops(dev)
    cfg = dataclasses.replace(ERNIE_CONFIGS["ernie-base"],
                              hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0)
    try:
        model = ErnieForPretraining(cfg)
        moment_dtype = (None if os.environ.get("BENCH_BF16_MOMENTS")
                        == "0" else "bfloat16")
        opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                    moment_dtype=moment_dtype)

        def train_step(ids, mlm_labels, ns_labels):
            with amp.auto_cast(level="O2"):
                loss = model(ids, masked_lm_labels=mlm_labels,
                             next_sentence_label=ns_labels)
            model.clear_gradients()
            loss.backward()
            opt.step()
            return loss

        step = jit.to_static(train_step, layers=[model],
                             optimizers=[opt])
        multi = jit.to_static_multi_step(train_step, layers=[model],
                                         optimizers=[opt])
        rng = np.random.RandomState(0)
        ids1 = rng.randint(3, cfg.vocab_size,
                           (batch, seq)).astype(np.int32)
        ns1 = rng.randint(0, 2, (batch,)).astype(np.int32)
        for _ in range(2):
            np.asarray(step(ids1, ids1, ns1).value)
        ids = rng.randint(3, cfg.vocab_size,
                          (steps, batch, seq)).astype(np.int32)
        ns = rng.randint(0, 2, (steps, batch)).astype(np.int32)
        np.asarray(multi(ids, ids, ns).value)
        t0 = time.perf_counter()
        losses = np.asarray(multi(ids, ids, ns).value)
        dt = (time.perf_counter() - t0) / steps
    except Exception as e:
        if is_oom(e):
            sys.stderr.write("OOM: " + str(e)[:300] + "\n")
            return OOM_RC
        raise

    h, f, L, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_hidden_layers, cfg.vocab_size)
    fwd_per_tok = L * (8 * h * h + 4 * h * f + 4 * seq * h) + 2 * h * V
    tokens_per_sec = batch * seq / dt
    mfu = 3.0 * fwd_per_tok * tokens_per_sec / peak
    if mfu > 1.0:
        sys.stderr.write(f"implausible MFU {mfu*100:.1f}% — refusing\n")
        return 3
    print(json.dumps({
        "metric": "ernie_base_mfu", "value": round(mfu * 100, 2),
        "unit": "%", "vs_baseline": round(mfu / 0.40, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_ms": round(dt * 1000, 2), "batch": batch,
        "seq": seq, "loss": round(float(losses[-1]), 4),
        "device": getattr(dev, "device_kind", str(dev)),
        "peak_flops": peak,
    }))
    return 0


def child_main_widedeep(batch: int, steps: int) -> int:
    """BENCH_MODEL=widedeep: Wide&Deep parameter-server CTR
    (BASELINE configs[4]) with the HOST-PACED sparse transport —
    pull -> compute -> push around a host-call-free compiled step
    (nothing in-graph calls back to the host). Criteo geometry: 26 slots, embed 16, 400x400x400 tower,
    1M-id space, PullPrefetcher overlap."""
    import jax

    from paddle_tpu.distributed.ps import sparse_table as st
    from paddle_tpu.distributed.ps.host_paced import (SparseFeed,
                                                      run_host_paced)
    from paddle_tpu.framework import Executor, Scope
    from paddle_tpu.models.ctr import build_wide_deep_program

    SLOTS, DIM = 26, 16
    dev = jax.devices()[0]
    st.REGISTRY.clear()
    main, startup, loss, _ = build_wide_deep_program(
        num_slots=SLOTS, embed_dim=DIM, hidden_sizes=(400, 400, 400),
        table_name="bench_emb", sparse_lr=0.05, dense_lr=0.01,
        host_paced=True)
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    feeds = [SparseFeed("ctr_emb", "bench_emb", DIM, lr=0.05),
             SparseFeed("ctr_wide", "bench_emb_wide", 1, lr=0.05)]

    rng = np.random.RandomState(0)

    def batches(n):
        for _ in range(n):
            ids = rng.randint(1, 1_000_000,
                              (batch, SLOTS)).astype(np.int64)
            y = (ids[:, 0] % 2 == 0).astype(np.float32)[:, None]
            yield {"ids": ids, "label": y}

    try:
        # warmup: compile + materialize tables
        run_host_paced(exe, main, scope, batches(3), feeds,
                       fetch_list=[loss.name])
        t0 = time.perf_counter()
        outs = run_host_paced(exe, main, scope, batches(steps), feeds,
                              fetch_list=[loss.name])
        dt = (time.perf_counter() - t0) / steps
    except Exception as e:
        if is_oom(e):
            sys.stderr.write("OOM: " + str(e)[:300] + "\n")
            return OOM_RC
        raise

    ex_per_sec = batch / dt
    print(json.dumps({
        "metric": "widedeep_host_paced_examples_per_sec",
        "value": round(ex_per_sec, 1), "unit": "examples/s",
        "vs_baseline": round(ex_per_sec / 4095.0, 4),
        "step_time_ms": round(dt * 1000, 2), "batch": batch,
        "slots": SLOTS, "embed_dim": DIM,
        "loss": round(float(outs[-1][0]), 4),
        "rows_live": st.REGISTRY.get("bench_emb").size(),
        "device": getattr(dev, "device_kind", str(dev)),
    }))
    return 0


# ResNet-50 fwd FLOPs per image at 224x224 (the standard 4.1 GFLOP
# figure, He et al. accounting); scales with spatial area.
RESNET50_FWD_FLOPS_224 = 4.089e9


def child_main_resnet(batch: int, img: int, steps: int) -> int:
    """BENCH_MODEL=resnet50: image-classification train-step config
    (BASELINE.md's ResNet-50 DP row, single chip)."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu import amp, jit
    from paddle_tpu.vision import resnet50

    dev = jax.devices()[0]
    peak = detect_peak_flops(dev)
    try:
        model = resnet50(num_classes=1000)
        opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
        ce = pt.nn.CrossEntropyLoss()

        def train_step(img_b, lab_b):
            with amp.auto_cast(level="O2"):
                logits = model(pt.dygraph.to_tensor(img_b))
                loss = ce(logits, pt.dygraph.to_tensor(lab_b))
            model.clear_gradients()
            loss.backward()
            opt.step()
            return loss

        step = jit.to_static(train_step, layers=[model], optimizers=[opt])
        multi = jit.to_static_multi_step(train_step, layers=[model],
                                         optimizers=[opt])
        rng = np.random.RandomState(0)
        x1 = rng.randn(batch, 3, img, img).astype(np.float32)
        l1 = rng.randint(0, 1000, (batch,)).astype(np.int64)
        for _ in range(2):
            np.asarray(step(x1, l1).value)
        # images are ~385 MB/step-window: push them to HBM BEFORE the
        # timed region, else the host->device transfer (not compute)
        # dominates the measurement. Real input pipelines overlap this
        # via the DeviceLoader double-buffer.
        xs = jax.device_put(
            rng.randn(steps, batch, 3, img, img).astype(np.float32))
        ls = jax.device_put(
            rng.randint(0, 1000, (steps, batch)).astype(np.int64))
        xs.block_until_ready()
        np.asarray(multi(xs, ls).value)
        t0 = time.perf_counter()
        losses = np.asarray(multi(xs, ls).value)
        dt = (time.perf_counter() - t0) / steps
    except Exception as e:
        if is_oom(e):
            sys.stderr.write("OOM: " + str(e)[:300] + "\n")
            return OOM_RC
        raise

    imgs_per_sec = batch / dt
    fwd = RESNET50_FWD_FLOPS_224 * (img / 224.0) ** 2
    mfu = 3.0 * fwd * imgs_per_sec / peak
    if mfu > 1.0:
        sys.stderr.write(f"implausible MFU {mfu*100:.1f}% — refusing\n")
        return 3
    print(json.dumps({
        "metric": "resnet50_mfu", "value": round(mfu * 100, 2),
        "unit": "%", "vs_baseline": round(mfu / 0.40, 4),
        "images_per_sec_per_chip": round(imgs_per_sec, 1),
        "step_time_ms": round(dt * 1000, 2), "batch": batch, "img": img,
        "loss": round(float(losses[-1]), 4),
        "device": getattr(dev, "device_kind", str(dev)),
        "peak_flops": peak,
    }))
    return 0


def child_main(model_name: str, batch: int, seq: int, steps: int) -> int:
    """Measure one (model, batch, seq, steps) config; print the JSON line.

    Exit codes: 0 ok; OOM_RC device OOM; 3 implausible measurement.
    """
    import jax

    dev = jax.devices()[0]
    peak = detect_peak_flops(dev)

    try:
        cfg, step, multi = build_steps(model_name, seq)
        rng = np.random.RandomState(0)
        ids1 = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        lab1 = np.roll(ids1, -1, axis=1).astype(np.int32)
        # warmup single steps: materialize grads + optimizer accumulators
        # so the scanned state structure is stable
        for _ in range(2):
            np.asarray(step(ids1, lab1).value)
        ids = rng.randint(0, cfg.vocab_size,
                          (steps, batch, seq)).astype(np.int32)
        labels = np.roll(ids, -1, axis=2).astype(np.int32)
        # compile + warm the scan at the EXACT shape we will time
        np.asarray(multi(ids, labels).value)
        # timed: same shapes => no recompile; the fetch of the losses
        # inside the window is the sync (see the module docstring)
        t0 = time.perf_counter()
        losses = np.asarray(multi(ids, labels).value)
        dt = (time.perf_counter() - t0) / steps
    except Exception as e:
        if is_oom(e):
            sys.stderr.write("OOM: " + str(e)[:300] + "\n")
            return OOM_RC
        raise

    loss = float(losses[-1])
    tokens_per_sec = batch * seq / dt
    fpt = model_flops_per_token(cfg, seq)
    mfu = fpt * tokens_per_sec / peak
    if mfu > 1.0:
        sys.stderr.write(
            f"implausible MFU {mfu * 100:.1f}% (step {dt * 1000:.3f} ms) — "
            "timing did not synchronize; refusing to report\n")
        return 3
    from paddle_tpu import observability
    print(json.dumps({
        "metric": "gpt2_345m_mfu" if model_name == "gpt2-medium"
        else f"{model_name}_mfu",
        "value": round(mfu * 100, 2),
        "unit": "%",
        "vs_baseline": round(mfu / 0.40, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_ms": round(dt * 1000, 2),
        "batch": batch,
        "seq": seq,
        "n_params": cfg.num_params(),
        "loss": round(loss, 4),
        "device": getattr(dev, "device_kind", str(dev)),
        "peak_flops": peak,
        # compile accounting for the timed step (count should stay at
        # the warmup's 1 — a recompile inside the window is a bug)
        "observability": {"compiles": observability.snapshot()["compiles"]},
    }))
    return 0


def main() -> int:
    # default flagship: the 1.112B d=128 config — the largest geometry
    # that trains at batch 8 on one v5e chip (measured capacity curve in
    # PERF.md); it needs the grads-internal contract + per-block
    # recompute, which become defaults for it (override any of these
    # with the usual env knobs)
    model_name = os.environ.get("BENCH_MODEL", "gpt2-1p1b")
    billion_class_defaults(model_name)
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    default_batch = {"resnet50": "128", "widedeep": "512",
                     "ernie": "16"}.get(model_name, "8")
    batch = int(os.environ.get("BENCH_BATCH", default_batch))
    if model_name == "resnet50":
        seq = int(os.environ.get("BENCH_IMG", "224"))
    if model_name == "ernie":
        seq = int(os.environ.get("BENCH_SEQ", "512"))

    here = os.path.abspath(__file__)
    last_err = ""
    batch_asked = batch
    while batch >= 1:
        proc = subprocess.run(
            [sys.executable, here, "--child", model_name, str(batch),
             str(seq), str(steps)],
            cwd=os.path.dirname(here), capture_output=True, text=True,
            timeout=3600)
        if proc.returncode == 0:
            # relay the child's single JSON line, with the batch that
            # was asked for beside the batch that ran
            out = json.loads([ln for ln in proc.stdout.splitlines()
                              if ln.startswith("{")][-1])
            out["batch_asked"] = batch_asked
            print(json.dumps(out))
            return 0
        if proc.returncode == OOM_RC:
            last_err = proc.stderr.strip().splitlines()[-1] if proc.stderr \
                else "OOM"
            sys.stderr.write(f"bench: batch {batch} out of device memory "
                             f"({last_err[:200]}); retrying at "
                             f"{batch // 2}\n")
            batch //= 2   # fresh subprocess => device memory actually freed
            continue
        sys.stderr.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"bench child failed (rc={proc.returncode})")
    raise RuntimeError(f"OOM even at batch 1: {last_err}")


if __name__ == "__main__":
    if "--child" in sys.argv:
        from paddle_tpu.utils.chip import enable_compile_cache
        enable_compile_cache()
        i = sys.argv.index("--child")
        name = sys.argv[i + 1]
        if name == "resnet50":
            sys.exit(child_main_resnet(int(sys.argv[i + 2]),
                                       int(sys.argv[i + 3]),
                                       int(sys.argv[i + 4])))
        if name == "widedeep":
            sys.exit(child_main_widedeep(int(sys.argv[i + 2]),
                                         int(sys.argv[i + 4])))
        if name == "ernie":
            sys.exit(child_main_ernie(int(sys.argv[i + 2]),
                                      int(sys.argv[i + 3]),
                                      int(sys.argv[i + 4])))
        sys.exit(child_main(name, int(sys.argv[i + 2]),
                            int(sys.argv[i + 3]), int(sys.argv[i + 4])))
    sys.exit(main())
