#!/usr/bin/env python
"""Smoke test on the chip: the two hot paths, end to end, through the
entry points a user calls, at the full width of a model the zoo holds.

    python chip_smoke.py                  # one chip: train, then serve
    python chip_smoke.py --phase serve    # one of them
    python chip_smoke.py --chips 4        # the path across chips, only

One chip (what the driver runs):

- ``train``: ``gpt2-1p1b`` built as ``bench.py`` builds it
  (``jit.to_static``, AMP O2 bf16, bf16 AdamW moments, per-block
  recompute, grads internal), batch 8, seq 1024, a few steps on one
  fixed batch made from ``--seed``. Loss finite and falling; the
  compiled step holds the flash kernel (``tpu_custom_call``) and no
  attention gave way to the XLA-composed form. A batch that does not fit
  is reported with the compiler's words and halved, never in silence.
- ``serve``: ``gpt2-1p3b`` in a ``ServingEngine`` (paged cache) behind
  ``serving/http.py``, a handful of requests over two prefill buckets;
  they agree token for token with ``generation.greedy_search`` on the
  same weights under float32 ``highest`` matmul precision (stated, not
  loosened: on the TPU float32 matmuls default to bf16 passes), zero
  leaked blocks, zero exceptions, and the decode step holds the paged
  kernel.

Four chips (``--chips 4``, run by the builder; ``gpt2-medium`` so both
sides of each comparison fit): ``zero`` — ``zero_train_step(stage=2)``
on a ``dp=4`` mesh against the one-chip ``jit.to_static`` step; ``tp``
— one ``ServingEngine`` on ``serving_mesh(1, 4)`` against a one-device
engine. Shards sit on four devices and every
kernel takes its chip's own shard.

Each phase is a child process and this parent imports neither jax nor
paddle_tpu: a chip belongs to one process at a time, and the 13 GB of
train state must be gone before the engine is built. The LAST stdout
line is one JSON object; ``"ok"`` is true only on a TPU with every phase
passed, and the exit code is 0 only then. On any other backend the
phases still run (the CPU rehearsal: ``JAX_PLATFORMS=cpu python
chip_smoke.py --train-model gpt2-tiny --serve-model gpt2-tiny
--train-seq 128 --train-batch 2 --max-len 128 --buckets 16,32``) but
the script never reports success. Needs no network and no file outside
the checkout.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OOM_RC = 42           # a child's "the batch does not fit the device"
TIME_LIMIT_S = 1150   # all phases together; the driver allows 1200
PHASES = {1: ("train", "serve"), 4: ("zero", "tp")}
KERNEL = 'custom_call_target="tpu_custom_call"'   # a Mosaic kernel, in HLO


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--phase", default="all",
                    choices=("all", "train", "serve", "zero", "tp"))
    ap.add_argument("--train-model", default="gpt2-1p1b")
    ap.add_argument("--train-batch", type=int, default=8)
    ap.add_argument("--train-seq", type=int, default=1024)
    ap.add_argument("--train-steps", type=int, default=4)
    ap.add_argument("--serve-model", default="gpt2-1p3b")
    ap.add_argument("--mesh-model", default="gpt2-medium",
                    help="model of both --chips 4 phases")
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--buckets", default="64,128")
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase != "all" and args.phase not in PHASES[args.chips]:
        chips = next(c for c in PHASES if args.phase in PHASES[c])
        ap.error(f"--phase {args.phase} belongs to --chips {chips}")
    return args


# ----------------------------------------------------------------------
# parent: runs the phases as children, never touches jax
# ----------------------------------------------------------------------

def run_child(phase, argv, timeout):
    """Run one phase; relay its lines; return (rc, last stdout line)."""
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.join(HERE, "chip_smoke.py"),
         "--child", phase] + argv,
        cwd=HERE, stdout=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        signal.signal(signal.SIGALRM, lambda *_: os.killpg(
            proc.pid, signal.SIGKILL))
        signal.alarm(max(1, int(timeout)))
        last = ""
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                last = line
                print(line, flush=True)
        return proc.wait(), last
    finally:
        signal.alarm(0)
        if proc.poll() is None:     # we are leaving early: take it along
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()


def parent(args, argv):
    t_start = time.time()
    phases = ([args.phase] if args.phase != "all"
              else list(PHASES[args.chips]))
    results, device = {}, None
    for phase in phases:
        extra, batch = [], args.train_batch
        while True:
            left = TIME_LIMIT_S - (time.time() - t_start)
            rc, last = run_child(phase, argv + extra, left)
            if phase == "train" and rc == OOM_RC and batch > 1:
                # said on its own line, before the result: never halve
                # in silence
                print(f"[train] batch {batch} does not fit the device "
                      f"(see the compiler's words above); retrying at "
                      f"batch {batch // 2}", flush=True)
                batch //= 2
                extra = ["--train-batch", str(batch)]
                continue
            break
        try:
            res = json.loads(last)
        except ValueError:
            res = {}
        results[phase] = rc == 0 and res.get("ok") is True
        device = res.get("device", device)
        if phase == "train":
            results["train_batch"] = [args.train_batch, batch]
        if not results[phase]:
            print(f"[{phase}] FAILED (exit code {rc})", flush=True)
    passed = all(results[p] for p in phases)
    on_tpu = bool(device) and device.get("platform") == "tpu"
    if passed and not on_tpu:
        print(f"every phase passed, but on {device}: this is a "
              f"rehearsal, not a chip run", flush=True)
    print(f"phases: {json.dumps(results)}  wall "
          f"{time.time() - t_start:.0f}s", flush=True)
    ok = passed and on_tpu and device.get("count") == args.chips
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


# ----------------------------------------------------------------------
# children: one phase each, one process on the chip
# ----------------------------------------------------------------------

class Phase:
    """Shared set-up and reporting of one child."""

    def __init__(self, name, args):
        import jax
        from paddle_tpu.utils import chip
        self.name, self.args, self.t0 = name, args, time.time()
        self.cache_dir = chip.enable_compile_cache()
        self.cache_before = chip.cache_entries()
        self.device = chip.device_info()
        self.on_tpu = self.device["platform"] == "tpu"
        import jaxlib
        try:
            from importlib.metadata import version
            libtpu = version("libtpu")
        except Exception:
            libtpu = "not installed"
        self.say(f"device {self.device}  jax {jax.__version__} jaxlib "
                 f"{jaxlib.__version__} libtpu {libtpu}")
        self.say(f"compile cache {self.cache_dir}: "
                 f"{self.cache_before} entries before")
        if args.chips > 1 and self.device["count"] != args.chips:
            raise SystemExit(
                f"--chips {args.chips} needs {args.chips} devices, JAX "
                f"reports {self.device['count']}")

    def say(self, msg):
        print(f"[{self.name}] {msg}", flush=True)

    def check(self, cond, what):
        """A failed check fails the phase (no exception is swallowed:
        this raises, the child exits non-zero)."""
        self.say(("pass: " if cond else "FAIL: ") + what)
        if not cond:
            raise SystemExit(f"[{self.name}] check failed: {what}")

    def check_kernel(self, text, what):
        """On the TPU the compiled program must hold a Mosaic kernel."""
        n = text.count(KERNEL)
        if self.on_tpu:
            self.check(n > 0, f"{what} holds {n} tpu_custom_call")
        else:
            self.say(f"{what}: tpu_custom_call not expected off the TPU "
                     f"(Pallas interpreter), found {n}")
        return n

    def finish(self, **extra):
        import jax
        from paddle_tpu import native
        from paddle_tpu.utils import chip
        stats = [d.memory_stats() or {} for d in jax.devices()]
        peaks = [st.get("peak_bytes_in_use") for st in stats]
        limit = stats[0].get("bytes_limit")
        self.say("peak HBM per device: " + (
            ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks)
            + f" of {limit / 2**30:.2f} GiB" if peaks[0] is not None
            else "not reported by this backend"))
        after = chip.cache_entries()
        self.say(f"compile cache: {after} entries after "
                 f"({after - self.cache_before:+d}; a directory with a "
                 f"size cap evicts its oldest entries)")
        self.say(f"native libraries built/loaded: "
                 f"{sorted(k[0] for k, v in native._libs.items() if v)}"
                 f" (the GPT paths need none)")
        self.say(f"phase wall {time.time() - self.t0:.1f}s")
        print(json.dumps(dict(
            phase=self.name, ok=True, device=self.device,
            peak_hbm_bytes=peaks, cache_entries_new=after
            - self.cache_before, **extra)))


def tpu_custom_calls(text):
    """[(result shapes, operand shapes)] of every Mosaic kernel in a
    compiled program's text; shapes as tuples of ints."""
    import re
    shape = re.compile(r"\b(?:bf16|f32|s32|s8|u32|f16)\[([0-9,]*)\]")

    def dims(s):
        return [tuple(int(x) for x in m.split(",") if x)
                for m in shape.findall(s)]
    out = []
    for ln in text.splitlines():
        if KERNEL not in ln:
            continue
        res = ln.split("custom-call(")[0].split("=", 1)[1]
        ops = re.search(r"operand_layout_constraints=\{(.*?)\}\}", ln)
        out.append((dims(res), dims(ops.group(1)) if ops else []))
    return out


def fixed_batch(cfg, batch, seq, seed):
    import numpy as np
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1).astype(np.int32)


def build_train(ph, model_name, seq):
    """The train job as bench.py builds it (its env knobs set the way
    bench.main() sets them for the billion-class configs)."""
    import paddle_tpu as pt
    import bench
    bench.billion_class_defaults(model_name)
    pt.seed(ph.args.seed)
    t = time.time()
    parts = bench.build_train(model_name, seq)
    ph.say(f"{model_name} and its optimizer built in {time.time() - t:.1f}s")
    # the flash kernel takes over from seq 1024 up (FLAGS_pallas_min_seq);
    # a rehearsal at a shorter seq still has to drive it
    pt.set_flags({"pallas_min_seq": min(1024, seq)})
    return parts


def run_steps(ph, step, ids, labels, n):
    """n steps on one batch -> (losses, seconds per step)."""
    import numpy as np
    losses, secs = [], []
    for _ in range(n):
        t = time.time()
        losses.append(float(np.asarray(step(ids, labels).value)))
        secs.append(time.time() - t)
    ph.say("loss " + " ".join(f"{x:.4f}" for x in losses))
    ph.say("step seconds " + " ".join(f"{x:.2f}" for x in secs)
           + "  (steps 1 and 2 compile: the optimizer state appears "
           "after step 1)")
    ph.check(all(np.isfinite(losses)), "loss finite")
    ph.check(losses[-1] < losses[0],
             f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses, secs


def phase_train(ph):
    import bench
    from paddle_tpu import jit
    from paddle_tpu.ops import attention_ops
    a = ph.args
    cfg, model, opt, train_step, retain = build_train(
        ph, a.train_model, a.train_seq)
    step = jit.to_static(train_step, layers=[model], optimizers=[opt],
                         retain_grads=retain)
    ph.say(f"{a.train_model}: {cfg.num_params() / 1e9:.3f}B params, "
           f"{cfg.num_layers} layers h{cfg.hidden_size} "
           f"d{cfg.head_dim}, batch {a.train_batch} seq {a.train_seq}, "
           f"recompute={cfg.recompute} retain_grads={retain}")
    ids, labels = fixed_batch(cfg, a.train_batch, a.train_seq, a.seed)
    try:
        losses, secs = run_steps(ph, step, ids, labels, a.train_steps)
    except Exception as e:
        if not bench.is_oom(e):
            raise
        ph.say(f"FINDING: batch {a.train_batch} does not fit: "
               + " ".join(str(e).split())[:1500])
        sys.exit(OOM_RC)
    compiled = step.lower(ids, labels).compile()
    ph.say(f"memory_analysis: {compiled.memory_analysis()}")
    n_kernels = ph.check_kernel(compiled.as_text(), "train step")
    ph.check(not attention_ops.flash_fallback_shapes,
             "no attention fell back to the XLA-composed form "
             f"({sorted(attention_ops.flash_fallback_shapes)})")
    steady = sorted(secs[2:])[len(secs[2:]) // 2] if len(secs) > 2 else None
    ph.finish(model=a.train_model, batch=a.train_batch,
              seq=a.train_seq, losses=losses, step_seconds=secs,
              compile_seconds_estimate=(
                  None if steady is None
                  else secs[0] + secs[1] - 2 * steady),
              tpu_custom_calls=n_kernels)


# ------------------------------------------------------------- serving

def make_prompts(cfg, buckets, n, seed):
    """n prompts of different lengths, spread over the buckets (the
    first bucket is reached from below, the last one filled)."""
    import numpy as np
    rng = np.random.RandomState(seed + 1)
    lo, hi = 3, buckets[-1]
    lens = sorted({int(x) for x in np.linspace(lo, hi, n)})
    return [rng.randint(1, cfg.vocab_size, size=L).tolist() for L in lens]


def serve_http(ph, model, prompts, new_tokens, **engine_kw):
    """Serve the prompts through serving/http.py, all in flight at once.
    Returns (engine, [output_ids], seconds)."""
    import threading
    import urllib.request
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.http import ServingHTTPServer
    a = ph.args
    eng = ServingEngine(model, max_slots=a.slots, max_len=a.max_len,
                        buckets=[int(b) for b in a.buckets.split(",")],
                        max_queue=len(prompts) + a.slots, **engine_kw)
    srv = ServingHTTPServer(eng, port=0, request_timeout=TIME_LIMIT_S)
    srv.start()
    outs, errors = [None] * len(prompts), []

    def post(i):
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/generate",
                data=json.dumps({"ids": prompts[i],
                                 "max_new_tokens": new_tokens}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=TIME_LIMIT_S) as r:
                body = json.loads(r.read())
            assert body["state"] == "done", body
            outs[i] = body["output_ids"]
        except Exception as e:      # counted, reported, fails the phase
            errors.append(f"request {i}: {e!r}")
    t = time.time()
    threads = [threading.Thread(target=post, args=(i,))
               for i in range(len(prompts))]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        srv.stop()
    secs = time.time() - t
    ph.check(not errors, f"exceptions == 0 {errors}")
    eng.cache.flush_prefix_cache()
    leaked = max(0, eng.cache.allocator.leaked() - 1)   # - trash block
    ph.check(leaked == 0, f"leaked_kv_blocks == {leaked}")
    return eng, outs, secs


def compare_tokens(ph, what, got, want):
    bad = [(i, next(j for j, (x, y) in enumerate(zip(g, w)) if x != y))
           for i, (g, w) in enumerate(zip(got, want)) if g != w]
    ph.check(not bad, f"{what}: {len(got) - len(bad)}/{len(got)} "
             f"requests token-identical"
             + (f"; first differing (request, position): {bad}"
                if bad else ""))


def decode_step_text(eng):
    """as_text() of the compiled paged decode step the engine ran."""
    import jax.numpy as jnp
    from paddle_tpu.models.generation import (decode_step_paged,
                                              param_leaves)
    from paddle_tpu.serving.decoding import neutral_samp
    fn = decode_step_paged(eng.model, eng.mesh, eng.kv_dtype, None)["fn"]
    args = (jnp.zeros(eng.max_slots, jnp.int32),
            jnp.asarray(eng.cache.lengths), jnp.asarray(eng.cache.tables),
            eng.cache.arrays(),
            neutral_samp(eng.max_slots, eng.model.gpt.cfg.vocab_size))
    return fn.raw.lower(param_leaves(eng.model), *args).compile().as_text()


def build_server_model(ph, name):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
    # one stated precision for every comparison of this phase: float32
    # matmuls at full precision. The TPU's default (bf16 passes) is what
    # serving runs at, and there token identity between two correct
    # attention orders is not to be expected.
    jax.config.update("jax_default_matmul_precision", "highest")
    pt.seed(ph.args.seed)
    cfg = GPT_CONFIGS[name]
    t = time.time()
    model = GPTForCausalLM(cfg)
    model.eval()
    ph.say(f"{name} built in {time.time() - t:.1f}s")
    ph.say(f"{name}: {cfg.num_params() / 1e9:.3f}B params float32, "
           f"{cfg.num_layers} layers h{cfg.hidden_size} d{cfg.head_dim}; "
           f"max_len {ph.args.max_len}, {ph.args.slots} slots, buckets "
           f"{ph.args.buckets}; matmul precision: highest")
    return cfg, model


def phase_serve(ph):
    import numpy as np
    from paddle_tpu.models.generation import greedy_search
    a = ph.args
    cfg, model = build_server_model(ph, a.serve_model)
    buckets = [int(b) for b in a.buckets.split(",")]
    prompts = make_prompts(cfg, buckets, a.requests, a.seed)
    ph.say(f"{len(prompts)} prompts of lengths "
           f"{[len(p) for p in prompts]}, {a.new_tokens} new tokens each")
    t = time.time()
    want = [greedy_search(model, np.asarray([p]),
                          max_new_tokens=a.new_tokens,
                          cache_len=a.max_len)[0].tolist()
            for p in prompts]
    ph.say(f"oracle (generation.greedy_search, one request at a time): "
           f"{time.time() - t:.1f}s")
    eng, got, seconds = serve_http(ph, model, prompts, a.new_tokens)
    toks = sum(len(g) - len(p) for g, p in zip(got, prompts))
    ph.say(f"{toks} tokens generated over HTTP in {seconds:.1f}s "
           f"(compiles included)")
    compare_tokens(ph, "engine vs greedy_search", got, want)
    kernels = ph.check_kernel(decode_step_text(eng), "decode step")
    del eng
    ph.finish(model=a.serve_model, requests=len(prompts),
              new_tokens=a.new_tokens, serve_seconds=seconds,
              tpu_custom_calls=kernels, transport="http")


# ---------------------------------------------------- across four chips

def on_all_devices(ph, what, arrays):
    """Every array has a shard on each of the chips."""
    import jax
    want = set(jax.devices())
    bad = [i for i, x in enumerate(arrays)
           if {s.device for s in x.addressable_shards} != want]
    ph.check(not bad, f"{what}: {len(arrays) - len(bad)}/{len(arrays)} "
             f"arrays have shards on all {len(want)} devices")


def check_local_kernels(ph, text, what, local):
    """Every Mosaic kernel of a sharded program takes its chip's own
    shard. The flash kernels see ``[batch*heads, seq, d]`` (3-D: extent
    0 must be the local batch*heads), the paged kernel ``[batch | blocks,
    heads, rows, d]`` (4-D: extent 1 must be the local heads). A gathered
    operand would show the global extent instead."""
    calls = tpu_custom_calls(text)
    if not ph.on_tpu:
        ph.say(f"{what}: local-shard check needs the TPU's compiler")
        return
    ph.check(bool(calls), f"{what} holds {len(calls)} tpu_custom_call")
    wide = [(res, ops) for res, ops in calls
            if any((len(s) == 3 and s[0] != local)
                   or (len(s) == 4 and s[1] != local) for s in res + ops)]
    ph.check(not wide, f"{what}: every kernel operand and result is the "
             f"local shard (extent {local})"
             + (f"; offenders {wide[:2]}" if wide else ""))


def phase_zero(ph):
    import gc
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import jit
    from paddle_tpu.distributed import zero
    from paddle_tpu.distributed.env import build_mesh
    a = ph.args
    dp = a.chips
    # one chip, device 0: the plain compiled step
    cfg, model, opt, fn, retain = build_train(ph, a.mesh_model,
                                              a.train_seq)
    ids, labels = fixed_batch(cfg, a.train_batch, a.train_seq, a.seed)
    ph.say(f"{a.mesh_model}: {cfg.num_params() / 1e9:.3f}B params, global "
           f"batch {a.train_batch} seq {a.train_seq}; one-chip "
           f"jit.to_static on {jax.devices()[0]}")
    step = jit.to_static(fn, layers=[model], optimizers=[opt],
                         retain_grads=retain)
    ref, _ = run_steps(ph, step, ids, labels, a.train_steps)
    del step, model, opt, fn
    gc.collect()
    # four chips: ZeRO-2 over dp
    mesh = build_mesh(("dp",), (dp,))
    cfg, model, opt, fn, retain = build_train(ph, a.mesh_model,
                                              a.train_seq)
    zstep = zero.zero_train_step(
        fn, layers=[model], optimizers=[opt], mesh=mesh, stage=2,
        arg_specs=(P("dp"), P("dp")), retain_grads=retain)
    ph.say(f"zero_train_step(stage=2) on mesh {dict(mesh.shape)}")
    got, _ = run_steps(ph, zstep, ids, labels, a.train_steps)
    rel = [abs(x - y) / abs(y) for x, y in zip(got, ref)]
    ph.check(max(rel) <= 2e-2, "ZeRO-2 dp=4 losses agree with the one-chip "
             "step to bf16 tolerance (rel diff "
             + " ".join(f"{r:.1e}" for r in rel) + " <= 2e-2)")
    rep = zstep.byte_report()
    ratio = rep["opt_bytes_per_device"] / rep["opt_bytes"]
    ph.check(ratio <= 1.0 / dp + 0.05,
             f"optimizer state per device {rep['opt_bytes_per_device']} "
             f"of {rep['opt_bytes']} bytes = {ratio:.3f} (~1/{dp})")
    on_all_devices(ph, "parameters",
                   [p.value for p in model.parameters()])
    on_all_devices(ph, "optimizer state",
                   [v for v in opt._eager_state.values()
                    if hasattr(v, "addressable_shards")])
    if ph.on_tpu:
        used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        ph.check(min(used) > 0, f"bytes_in_use on every device: {used}")
    text = zstep.lower(ids, labels).compile().as_text()
    colls = sorted({c for c in ("reduce-scatter", "all-gather",
                                "all-reduce", "all-to-all")
                    if c + "(" in text or c + "-start(" in text})
    ph.check("reduce-scatter" in colls or "all-gather" in colls,
             f"ZeRO-2 step's collectives: {colls}")
    check_local_kernels(ph, text, "ZeRO-2 step",
                        a.train_batch // dp * cfg.num_heads)
    ph.finish(model=a.mesh_model, losses=got, reference_losses=ref,
              opt_bytes_ratio=ratio, collectives=colls)


def phase_tp(ph):
    from paddle_tpu.distributed.sharding import serving_mesh
    a = ph.args
    cfg, model = build_server_model(ph, a.mesh_model)
    buckets = [int(b) for b in a.buckets.split(",")]
    prompts = make_prompts(cfg, buckets, a.requests, a.seed)
    eng, want, secs = serve_http(ph, model, prompts, a.new_tokens)
    ph.say(f"one-device engine: {secs:.1f}s")
    del eng
    eng, got, seconds = serve_http(ph, model, prompts, a.new_tokens,
                                   mesh=serving_mesh(1, a.chips))
    ph.say(f"TP={a.chips} engine: {seconds:.1f}s")
    compare_tokens(ph, f"TP={a.chips} engine vs the one-device engine",
                   got, want)
    on_all_devices(ph, "KV pools",
                   [x for layer in eng.cache.arrays() for x in layer])
    check_local_kernels(ph, decode_step_text(eng), "TP decode step",
                        cfg.num_heads // a.chips)
    del eng
    split = [p.value for n, p in model.named_parameters()
             if n.endswith(("qkv_proj.weight", "fc1.weight",
                            "fc2.weight", "out_proj.weight"))]
    on_all_devices(ph, "tensor-parallel weights", split)
    ph.check(all(x.addressable_shards[0].data.size * a.chips == x.size
                 for x in split),
             f"each chip holds 1/{a.chips} of every tensor-parallel "
             "weight")
    ph.finish(model=a.mesh_model, requests=len(prompts),
              serve_seconds=seconds)


def child(args):
    sys.path.insert(0, HERE)
    ph = Phase(args.child, args)
    {"train": phase_train, "serve": phase_serve, "zero": phase_zero,
     "tp": phase_tp}[args.child](ph)
    return 0


if __name__ == "__main__":
    _args = parse_args()
    if _args.child:
        sys.exit(child(_args))
    sys.exit(parent(_args, sys.argv[1:]))
