"""One training cell, once: the family's train job for the configuration
(model, optimizer, train function) compiled as the job file says
(``jit.to_static`` or ``zero_train_step``), a new seeded batch every step
made on the device before the step that uses it, every step's loss fetched.

The window opens after ``warm_steps`` (two of them compile: the optimizer's
state appears after step 1), each ended by a fetch of its loss, so nothing
is in flight when it opens. Inside it the steps are dispatched ahead of the
one whose loss is waited for, ``AHEAD_S`` seconds of them by the last warm
step's time: the chip stays fed while the host stands still for less than
that, and a loss is read that many steps late. When ``--seconds`` are up
nothing more is sent, every step that was sent is waited for, and the clock
is read after that wait: every step is whole, and the rate is all of those
steps' tokens over all of that time.
"""

from __future__ import annotations

import collections
import time

import numpy as np

TRACE_STEPS = 4     # the device trace covers TRACE_S seconds of steps, and
TRACE_S = 4.0       # at least TRACE_STEPS of them (no more than are ahead)
#: seconds of steps in flight ahead of the loss that is waited for: a host
#: that stands still for less leaves the chip fed, and the last wait is no
#: longer than this (PERF.md section 6, PR 57: stalls of 0.9 and 2.1 s seen)
AHEAD_S = 5.0
#: |program's loss - reference's loss| on the check batch. The program
#: computes in bf16 (AMP O2) and hands back its loss as a bfloat16, whose
#: step at ~10.9 is 0.0625: rounding alone moves it by up to 0.031. The
#: reference is float32 at "highest". The proving runs differed by at most
#: 0.029. On random tokens near the initial weights this check sees a loss
#: that is not this model's on these weights (wrong or stale weights, a
#: broken scale, a diverged step); it does not see a dropped layer or a
#: dropped mask (study/correctness.md has the readings).
LOSS_TOLERANCE = 0.05


def build(cfg: dict, traffic: dict, seed: int):
    """-> (model, make_step, mesh or None); ``make_step()`` wraps
    the train function as the job says (``to_static`` / ``zero``)."""
    import paddle_tpu as pt
    from paddle_tpu import jit
    from . import families, weights
    family = families.load(cfg)
    seq = int(traffic["seq"])
    mesh = replicated = None
    if traffic["step"] == "zero":
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed.env import build_mesh
        (axis, n), = traffic["mesh"].items()
        mesh = build_mesh((axis,), (int(n),))
        replicated = NamedSharding(mesh, P())
    with weights.recording(replicated) as specs:
        model, opt, fn, retain = family.train_job(cfg, traffic)
    weights.fill(model, specs, seed, sharding=replicated)
    # the flash kernel takes over from seq 1024 up (FLAGS_pallas_min_seq);
    # a rehearsal at a shorter seq still has to drive it
    pt.set_flags({"pallas_min_seq": min(1024, seq)})

    def make_step():
        if traffic["step"] == "to_static":
            return jit.to_static(fn, layers=[model], optimizers=[opt],
                                 retain_grads=retain)
        if traffic["step"] == "zero":
            from paddle_tpu.distributed import zero
            return zero.zero_train_step(
                fn, layers=[model], optimizers=[opt], mesh=mesh,
                stage=int(traffic["zero_stage"]),
                arg_specs=(P(axis), P(axis)), retain_grads=retain)
        raise SystemExit(f"unknown train step {traffic['step']!r}")
    return model, make_step, mesh


def batch_maker(seed: int, batch: int, seq: int, vocab: int, mesh):
    """``make(i)`` -> (ids, labels) of step ``i`` on the device(s), split
    over the mesh's axis where there is one; ``tiled(i)`` -> the same with
    every row equal to row 0 (the check batch)."""
    import jax
    import jax.numpy as jnp
    from . import weights
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    key = weights.seed_key(seed)

    def draw(i, tile):
        rows = 1 if tile else batch
        ids = jax.random.randint(jax.random.fold_in(key, i), (rows, seq),
                                 1, vocab, jnp.int32)
        ids = jnp.broadcast_to(ids, (batch, seq))
        return ids, jnp.roll(ids, -1, axis=1)

    jitted = jax.jit(draw, static_argnums=1,
                     out_shardings=None if sharding is None
                     else (sharding, sharding))
    return (lambda i: jitted(np.int32(i), False),
            lambda i: jitted(np.int32(i), True))


def fetch(loss) -> float:
    return float(np.asarray(loss.value))


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, out_dir: str, t_start: float):
    import jax
    from . import families, flops, serve
    family = families.load(cfg)
    chips = int(cell["chips"])
    if len(jax.devices()) < chips:
        raise SystemExit(f"{cell['name']} needs {chips} chips")
    model, make_step, mesh = build(cfg, traffic, seed)
    step = make_step()
    batch = int(traffic["batch_per_chip"]) * chips
    seq = int(traffic["seq"])
    make, tiled = batch_maker(seed, batch, seq, int(cfg["vocab_size"]), mesh)

    losses = []
    nxt = make(0)
    n = 0
    clock = time.perf_counter
    for _ in range(int(traffic["warm_steps"])):
        cur, nxt = nxt, make(n + 1)
        warm_t = clock()
        losses.append(fetch(step(*cur)))
        warm_s = clock() - warm_t
        n += 1
        if n == 1 and traffic.get("rewrap_after_first_step"):
            # a program fault worked around (the traffic file's rewrap_why);
            # the key and this line go when zero_train_step is repaired
            step = make_step()
    tiled(0)                      # compiled before the window, used after it

    before = serve.compile_count()
    ahead = max(1, int(AHEAD_S / warm_s))
    step_s = []         # between one loss's arrival and the next one's
    sent = collections.deque()
    # the device trace covers the window's last TRACE_S seconds of steps:
    # it starts during the last wait, when that many are still in flight
    # (the host has nothing more to send, so starting it delays no step),
    # and stops after the clock is read
    tracing = serve.Tracing(trace, out_dir, seconds)
    traced_steps = min(ahead, max(TRACE_STEPS, int(TRACE_S / warm_s)))
    t0 = clock()
    te = t0

    def arrive():
        nonlocal te
        with serve.span("bench.step"):
            losses.append(fetch(sent.popleft()))
        now = clock()
        step_s.append(now - te)
        te = now

    while clock() - t0 < seconds:       # time up: nothing more is sent
        cur, nxt = nxt, make(n + 1)
        sent.append(step(*cur))
        n += 1
        if len(sent) > ahead:
            arrive()
    while sent:
        if trace and not tracing.device_on and len(sent) <= traced_steps:
            tracing.start_device()
        arrive()                # te: the clock after the last wait
    tracing.stop_device()
    window_s = te - t0
    compiles = serve.compile_count() - before
    tokens = len(step_s) * batch * seq
    rate = tokens / window_s / chips
    kind = jax.devices()[0].device_kind
    per_token = family.train_flops_per_token(cfg, seq)

    # correctness, outside the window: one more step on a batch whose rows
    # are all the same row, so the program's mean loss is that row's loss,
    # against the reference's loss on the row under the same weights
    ids, labels = tiled(n + 7)
    row = (np.asarray(ids[:1]), np.asarray(labels[:1]))
    params = {}
    for name, p in model.named_parameters():
        v = p.value
        params[name] = v.addressable_shards[0].data if mesh is not None else v
    ref = jax.jit(lambda p, i, l: family.loss(p, i, l, cfg))
    want = float(np.asarray(ref(params, *row)))
    del params
    got = fetch(step(ids, labels))
    diff = abs(got - want)
    ok = (all(np.isfinite(losses)) and losses[-1] < losses[0]
          and np.isfinite(diff) and diff <= LOSS_TOLERANCE and compiles == 0)
    counts = {"attempted": len(step_s), "failed": 0, "correct": bool(ok),
              "loss_first": losses[0], "loss_last": losses[-1],
              "check_loss_program": got, "check_loss_reference": want,
              "check_loss_diff": diff, "steps_in_window": len(step_s),
              "steps_ahead": ahead, "step_gap_max_s": max(step_s)}
    e2e = {"train_tok_s_chip": rate, "setup_s": t0 - t_start}
    mfu = None
    if jax.devices()[0].platform == "tpu":
        mfu = 100.0 * per_token * rate / flops.peaks(kind)["bf16_flops"]
    red = tracing.reduce()
    obs = {"spans": {}, "samples": {"train_step_s": step_s},
           "counters": {"compiles_in_window": compiles, "window_s": window_s,
                        **({} if mfu is None else {"mfu_pct": mfu})},
           "trace": red or {}}
    return e2e, obs, counts
