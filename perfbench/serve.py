"""One serving cell, once: build, warm, pre-roll, measure, check.

The harness drives ``ServingEngine.submit`` / ``step`` from this one thread
(the release loop of ``tools/loadgen.py``, copied: open loop on the wall
clock, in-process) and stamps every token itself when ``step()`` returns;
the first token of a request takes the engine's own stamp
(``Request.first_token_at``, same clock), taken when the prefill landed.

What the window counts is in PERF.md section 2; in short:

- open loop: every gap between consecutive output tokens of one stream that
  ends inside the window -> ``itl_mean_ms``, ``itl_worst5pct_mean_ms``;
- closed loop: tokens credited per step (a prompt's tokens when its prefill
  lands, output tokens as they are committed), counted from the first to
  the last request completion inside the window -> ``serve_tok_s``.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from . import traffic as T

TRACE_S = 6.0           # the device trace covers the last seconds of the window
TERMINAL = ("done", "shed", "canceled")
SAMPLE_REQUESTS = 8     # completed requests checked against the reference
#: A token the engine emitted must score within this many logit units of
#: the reference's best logit at its position. The engine multiplies
#: float32 weights at the TPU's default precision, the reference at
#: "highest"; with random weights the engine's choice is the reference's
#: own best or a near tie: the largest deficit over the proving runs was
#: 0.015. What the tolerance can and cannot tell apart, with the runs
#: behind each statement (study/check_power.py): study/correctness.md.
LOGIT_TOLERANCE = 0.05
#: Rows of logits the check holds at once: it takes the reference's hidden
#: state times its head this many rows at a time and keeps each row's best
#: logit and the emitted token's, so its memory is CHECK_ROWS x vocabulary
#: x 4 bytes whatever ``max_len`` is (0.63 GB at 154,880 rows of
#: vocabulary, where ``max_len`` 16384 at once would be 10.15 GB).
CHECK_ROWS = 1024


class Stream:
    """One request as the harness follows it."""

    __slots__ = ("req", "arrival", "seen", "last", "in_window", "first",
                 "finished")

    def __init__(self, req, arrival, in_window):
        self.req, self.arrival, self.in_window = req, arrival, in_window
        self.seen, self.last = 0, None
        self.first = self.finished = None


class Follower:
    """Stamps tokens and credits work at step boundaries."""

    def __init__(self):
        self.live: List[Stream] = []
        self.done: List[Stream] = []
        self.gaps: List[tuple] = []          # (end stamp, gap seconds)
        self.completions: List[float] = []   # step-end stamp of each
        self.admitted = 0                    # prefills landed in the last step

    def queued(self) -> int:
        return sum(1 for s in self.live if s.req.state == "queued")

    def after_step(self, t: float) -> int:
        """Stamp what the step committed; returns the tokens it is credited
        with (prompt tokens of prefills that landed + output tokens)."""
        credit, still = 0, []
        self.admitted = 0
        for s in self.live:
            n = len(s.req.tokens)
            if n > s.seen:
                if s.seen == 0:
                    credit += len(s.arrival.prompt)
                    self.admitted += 1
                for k in range(s.seen, n):
                    stamp = t
                    if k == 0:
                        stamp = s.first = (s.req.first_token_at
                                           if s.req.first_token_at is not None
                                           else t)
                    else:
                        self.gaps.append((stamp, stamp - s.last))
                    s.last = stamp
                credit += n - s.seen
                s.seen = n
            if s.req.state in TERMINAL:
                s.finished = t
                self.done.append(s)
                if s.req.state == "done":
                    self.completions.append(t)
            else:
                still.append(s)
        self.live = still
        return credit


# ------------------------------------------------------------------- build

def build_engine(cfg: dict, seed: int):
    from paddle_tpu.serving import ServingEngine
    from . import families, weights
    family = families.load(cfg)
    with weights.recording() as specs:
        model = family.serving_model(cfg)
    weights.fill(model, specs, seed)
    model.eval()
    e = cfg["engine"]
    engine = ServingEngine(
        model, max_slots=e["max_slots"], max_len=e["max_len"],
        buckets=e["buckets"], block_size=e["block_size"],
        num_blocks=e["num_blocks"], prefix_cache=e["prefix_cache"],
        max_queue=e["max_queue"], eos_token_id=None)
    return model, engine


def warm(engine, traffic: dict, token_limit: int):
    """Compile this cell's own shapes: one prefill per bucket its traffic
    reaches (with the longest prompt the traffic sends there), and the
    decode step. Leaves the engine empty."""
    rng = np.random.default_rng(0)
    longest = {}
    for plen, _ in T.multiset(traffic):
        b = T.bucket_for(plen, engine.buckets)
        longest[b] = max(longest.get(b, 0), plen)
    for bucket in sorted(longest):
        # two at once: the batched admit and the decode step over two rows
        for _ in range(2):
            engine.submit(
                rng.integers(1, token_limit, size=longest[bucket]).tolist(),
                max_new_tokens=3)
        engine.run_until_idle()
    engine.cache.flush_prefix_cache()


# ------------------------------------------------------------------ tracing

class Tracing:
    """What is read at the window's edges: the program's counters at its
    open and close (``counters``, a callable, in every run), and the traced
    run's extras: the program's host spans over the whole window, the
    device trace over its last ``last_s`` seconds."""

    def __init__(self, on: bool, out_dir: str, seconds: float,
                 last_s: float = TRACE_S, counters=None):
        self.on, self.dir = on, os.path.join(out_dir, "trace")
        self.start_at = max(0.0, seconds - last_s)
        self.device_on = False
        self._ann = None
        self.spans_path = os.path.join(out_dir, "host_spans.json")
        self._counters, self._at_open = counters, {}
        self.window_counters: Dict[str, float] = {}
        self.span_args: Dict[str, List[float]] = {}

    def window_open(self):
        if self._counters is not None:
            self._at_open = self._counters()
        if self.on:
            from paddle_tpu import profiler
            profiler.start_profiler()

    def tick(self, now_s: float):
        if self.on and not self.device_on and now_s >= self.start_at:
            import shutil
            import jax.profiler
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # TraceAnnotation spans suffice
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.device_on = True

    def stop_device(self):
        if self.on and self.device_on and self._ann is not None:
            import jax.profiler
            self._ann.__exit__(None, None, None)
            self._ann = None
            jax.profiler.stop_trace()

    def window_close(self) -> Dict[str, List[float]]:
        """Stops both; returns the program's spans as name -> seconds
        (their numeric ``args`` go to ``span_args`` as ``<span>.<arg>`` ->
        values). A counter of the program's is kept twice: under its name
        the window's difference (what a cumulative count wants), under
        ``<name>.close`` its closing value (what a gauge wants)."""
        if self._counters is not None:
            for name, value in self._counters().items():
                self.window_counters[name] = value - self._at_open.get(name, 0)
                self.window_counters[name + ".close"] = value
        if not self.on:
            return {}
        from paddle_tpu import profiler
        self.stop_device()
        with contextlib.redirect_stdout(io.StringIO()):
            profiler.stop_profiler(profile_path=self.spans_path)
        with open(self.spans_path) as f:
            spans, self.span_args = spans_of(json.load(f)["traceEvents"])
        return spans

    def reduce(self) -> Optional[dict]:
        if not (self.on and self.device_on):
            return None
        import glob
        import jax
        from . import xplane
        if jax.devices()[0].platform != "tpu":
            return None     # a CPU rehearsal has no device plane to read
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise SystemExit("the profiler wrote no trace")
        red = xplane.reduce(xplane.load(paths[0]))
        red["idle_s"] = red["window_s"] - red["busy_s"]
        return red


def _numeric(value) -> bool:
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def spans_of(events: List[dict]):
    """The profiler's events -> (name -> durations in seconds,
    ``<span>.<arg>`` -> the values of each numeric argument)."""
    spans: Dict[str, List[float]] = {}
    span_args: Dict[str, List[float]] = {}
    for ev in events:
        spans.setdefault(ev["name"], []).append(ev["dur"] / 1e6)
        for arg, value in (ev.get("args") or {}).items():
            if _numeric(value):
                span_args.setdefault(f"{ev['name']}.{arg}", []).append(value)
    return spans, span_args


def program_counters(engine) -> Dict[str, float]:
    """Every numeric entry of ``engine.stats()`` (as ``engine.<key>``) and
    of the monitor's ``STAT_serving_*`` counters (under their own names),
    as they stand now."""
    from paddle_tpu import monitor
    out = {f"engine.{k}": v for k, v in engine.stats().items()
           if _numeric(v)}
    out.update(monitor.stats_with_prefix("STAT_serving"))
    return out


def compile_count() -> int:
    """Compiles the program's ``tracked_jit`` sites have made so far."""
    from paddle_tpu import observability
    return sum(v["count"] for v in observability.compiles().values())


def span(name: str):
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------- the loops

def step_samples(steps) -> dict:
    """``steps`` is (seconds, prefills landed) per ``engine.step()`` of the
    window, by the harness's clock. A step that admitted nothing is one
    decode step; what an admitting step takes beyond the median decode
    step is prefill (the program's own ``serving.prefill`` /
    ``serving.decode`` spans close when the work is dispatched, before the
    device has done it: they measure the host's launch, not the step)."""
    decode = [d for d, admitted in steps if not admitted]
    out = {"decode_only_step_s": decode,
           "admitted_per_admitting_step": [a for _, a in steps if a]}
    if decode:
        base = float(np.median(decode))
        out["prefill_excess_s"] = [max(0.0, d - base)
                                   for d, admitted in steps if admitted]
    return out


def _submit(engine, fol: Follower, arrival, in_window, failures):
    from paddle_tpu.serving import QueueFullError
    try:
        with span("bench.submit"):
            req = engine.submit(list(arrival.prompt),
                                max_new_tokens=arrival.max_new_tokens)
    except (QueueFullError, ValueError) as e:
        if in_window:
            failures.append(f"{type(e).__name__}: {e}")
        return
    fol.live.append(Stream(req, arrival, in_window))


def run_open(engine, traffic, seed, seconds, vocab, tracing: Tracing,
             rate_override=None):
    """Pre-roll then window of a paced open loop. Returns (end_to_end,
    samples, counts, program spans, the follower, the window's (lo, hi))."""
    if rate_override is not None:
        traffic = dict(traffic, rate_per_s=float(rate_override))
    arrivals = T.open_loop_schedule(traffic, seed, seconds, vocab)
    preroll = float(traffic["preroll_s"])
    fol, failures = Follower(), []
    lags, found_busy, occupancy, steps = [], [], [], []
    clock = time.perf_counter
    t0 = clock() + preroll        # the window opens here; due times are
    i, opened = 0, False          # relative to it
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if not opened and now >= 0.0:
            opened = True
            tracing.window_open()
        if opened:
            tracing.tick(now)
        while i < len(arrivals) and arrivals[i].due_s <= now:
            a = arrivals[i]
            if a.in_window:
                lags.append(now - a.due_s)
                found_busy.append(engine.cache.num_free == 0)
            _submit(engine, fol, a, a.in_window, failures)
            i += 1
            now = clock() - t0
        if engine.idle:
            nxt = arrivals[i].due_s if i < len(arrivals) else seconds
            time.sleep(min(max(nxt - now, 0.0), 0.0005))
            continue
        ts = clock()
        with span("bench.step"):
            engine.step()
        te = clock()
        fol.after_step(te)
        if te - t0 >= 0.0:
            steps.append((te - ts, fol.admitted))
            occupancy.append(engine.cache.num_used / engine.max_slots)
    spans = tracing.window_close()
    lo, hi = t0, t0 + seconds
    gaps = sorted(g for end, g in fol.gaps if lo <= end <= hi)
    if len(gaps) < 20:
        raise SystemExit(f"only {len(gaps)} token gaps in the window")
    worst = gaps[-max(1, round(0.05 * len(gaps))):]
    streams = fol.done + fol.live
    due_in = [s for s in streams if s.in_window]
    e2e = {"itl_mean_ms": 1e3 * float(np.mean(gaps))}
    samples = {"gen_lag_s": lags, "live_slot_share": occupancy,
               "ttft_s": [s.first - (t0 + s.arrival.due_s) for s in due_in
                          if s.first is not None and s.first <= hi],
               **step_samples(steps)}
    shed = sum(1 for s in due_in if s.req.state in ("shed", "canceled"))
    counts = {"attempted": sum(1 for a in arrivals if a.in_window),
              "failed": len(failures) + shed, "failures": failures[:5],
              "found_all_slots_busy_share":
                  float(np.mean(found_busy)) if found_busy else 0.0,
              "token_gaps": len(gaps),
              "itl_worst5pct_mean_ms": 1e3 * float(np.mean(worst)),
              "completed_in_window": sum(
                  1 for s in fol.done if s.finished >= lo
                  and s.req.state == "done")}
    return e2e, samples, counts, spans, fol, (lo, hi)


def run_closed(engine, traffic, seed, seconds, vocab, tracing: Tracing):
    """Closed loop: the queue is kept ``queue_depth_slots * max_slots``
    deep; the window opens at the ``preroll_completions``-th completion.
    Returns what :func:`run_open` returns."""
    stream = T.closed_loop_stream(traffic, seed, vocab)
    depth = int(traffic["queue_depth_slots"]) * engine.max_slots
    need = int(traffic["preroll_completions"])
    fol, failures = Follower(), []
    occupancy, steps, credits = [], [], []     # credits: (step end, tokens)
    clock = time.perf_counter
    t0 = None
    submitted_in_window = 0
    while True:
        now = clock()
        if t0 is not None:
            if now - t0 >= seconds:
                break
            tracing.tick(now - t0)
        short = depth - fol.queued()
        for _ in range(max(0, short)):
            _submit(engine, fol, next(stream), t0 is not None, failures)
            submitted_in_window += t0 is not None
        ts = clock()
        with span("bench.step"):
            engine.step()
        te = clock()
        credit = fol.after_step(te)
        if t0 is None:
            if len(fol.completions) >= need:
                t0 = te
                tracing.window_open()
            continue
        credits.append((te, credit))
        steps.append((te - ts, fol.admitted))
        occupancy.append(engine.cache.num_used / engine.max_slots)
    spans = tracing.window_close()
    lo, hi = t0, t0 + seconds
    comps = [c for c in fol.completions if lo < c <= hi]
    if len(comps) < 3:
        raise SystemExit(f"only {len(comps)} completions in the window")
    first, last = comps[0], comps[-1]
    tokens = sum(n for te, n in credits if first < te <= last)
    e2e = {"serve_tok_s": tokens / (last - first)}
    samples = {"live_slot_share": occupancy, **step_samples(steps)}
    in_win = [s for s in fol.done + fol.live if s.in_window]
    shed = sum(1 for s in in_win if s.req.state in ("shed", "canceled"))
    counts = {"attempted": submitted_in_window,
              "failed": len(failures) + shed, "failures": failures[:5],
              "completed_in_window": len(comps),
              "tokens_counted": tokens, "counted_s": last - first}
    return e2e, samples, counts, spans, fol, (lo, hi)


# --------------------------------------------------------------- correctness

def queue_waits(fol: Follower, lo: float, hi: float) -> List[float]:
    """tracing.blame()'s queue component of the requests that were
    submitted and finished inside the window."""
    from paddle_tpu.observability import tracing
    mine = {s.req.id for s in fol.done
            if s.in_window and s.finished is not None and s.finished <= hi}
    out = []
    for tr in tracing.store().finished():
        if tr.rid in mine and tr.outcome == "done":
            q = tracing.blame(tr)["components"].get("queue")
            if q is not None:
                out.append(q)
    return out


def block_deficits(x, w, nxt, rows_a_block: int):
    """``x`` [rows, h] times ``w`` [h, vocabulary], ``rows_a_block`` rows at
    a time (the last block padded up) -> float32 [rows]: each row's best
    logit less its logit of ``nxt``. The logits are never held whole."""
    import jax
    import jax.numpy as jnp
    rows, width = x.shape
    block = min(rows_a_block, rows)
    fill = -rows % block
    xs = jnp.pad(x, ((0, fill), (0, 0))).reshape(-1, block, width)
    ns = jnp.pad(nxt, (0, fill)).reshape(-1, block)

    def one(block_of):
        xb, nb = block_of
        with jax.default_matmul_precision("highest"):
            logits = xb @ w
        got = jnp.take_along_axis(logits, nb[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got
    return jax.lax.map(one, (xs, ns)).reshape(-1)[:rows]


def deficits_fn(family, cfg: dict, rows_a_block: Optional[int] = None):
    """The check's one program: jitted ``(params, ids [1, rows], nxt
    [rows]) -> float32 [rows]``, by how much the reference's logit of
    ``nxt[i]`` at row ``i`` lies below that row's best: the family's
    ``hidden`` times its ``head``, ``rows_a_block`` (``CHECK_ROWS``) rows at
    a time. A row's logits do not depend on the rows beside it."""
    import jax
    rows_a_block = rows_a_block or CHECK_ROWS

    @jax.jit
    def deficits(params, ids, nxt):
        return block_deficits(family.hidden(params, ids, cfg)[0],
                              family.head(params, cfg), nxt, rows_a_block)
    return deficits


def check(model, engine, cfg, fol: Follower, seed: int, lo: float,
          hi: float) -> dict:
    """Outside the window: a seeded sample of completed requests against
    the plain reference, teacher-forced on the engine's own tokens; every
    request the schedule fixed in length has that length; no block leaks."""
    import jax.numpy as jnp
    from . import families
    deficits = deficits_fn(families.load(cfg), cfg)
    done = [s for s in fol.done if s.req.state == "done"
            and s.finished is not None and lo <= s.finished <= hi]
    notes = {}
    ok = bool(done)
    short = [s.req.id for s in done
             if len(s.req.tokens) != s.arrival.max_new_tokens]
    if short:
        ok = False
        notes["wrong_length_requests"] = short[:5]
    pad = int(cfg["engine"]["max_len"])
    params = {n: p.value for n, p in model.named_parameters()}
    rng = np.random.default_rng([int(seed), 9])
    picks = rng.choice(len(done), size=min(SAMPLE_REQUESTS, len(done)),
                       replace=False) if done else []
    worst = 0.0
    for j in picks:
        s = done[int(j)]
        seq = list(s.arrival.prompt) + list(s.req.tokens)
        p, n = len(s.arrival.prompt), len(s.req.tokens)
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        nxt = np.zeros(pad, np.int32)
        nxt[:len(seq) - 1] = seq[1:]
        d = np.asarray(deficits(params, jnp.asarray(ids), jnp.asarray(nxt)))
        worst = max(worst, float(d[p - 1:p + n - 1].max()))
    notes["max_logit_deficit"] = worst
    notes["checked_requests"] = len(picks)
    if not np.isfinite(worst) or worst > LOGIT_TOLERANCE:
        ok = False
    # leaks: everything still in flight is cancelled first
    for s in list(fol.live):
        engine.cancel(s.req.id)
    engine.cache.flush_prefix_cache()
    leaked = max(0, engine.cache.allocator.leaked() - 1)   # - trash block
    notes["leaked_kv_blocks"] = leaked
    if leaked:
        ok = False
    return {"correct": ok, **notes}


# ---------------------------------------------------------------------- run

def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, out_dir: str, t_start: float, rate_override=None):
    """One run of one serving cell -> (end_to_end, observations, counts)."""
    model, engine = build_engine(cfg, seed)
    token_limit = int(cfg["vocab_size"])
    warm(engine, traffic, token_limit)
    tracing = Tracing(trace, out_dir, seconds,
                      counters=lambda: program_counters(engine))
    gc.collect()
    gc.freeze()

    before = compile_count()
    if traffic["kind"] == "open_loop":
        e2e, samples, counts, spans, fol, (lo, hi) = run_open(
            engine, traffic, seed, seconds, token_limit, tracing,
            rate_override)
    elif traffic["kind"] == "closed_loop":
        e2e, samples, counts, spans, fol, (lo, hi) = run_closed(
            engine, traffic, seed, seconds, token_limit, tracing)
    else:
        raise SystemExit(f"serve.py cannot run traffic kind {traffic['kind']}")
    # process start to window open: the pre-roll is set-up
    e2e["setup_s"] = lo - t_start
    compiles = compile_count() - before
    samples["queue_wait_s"] = queue_waits(fol, lo, hi)
    counts.update(check(model, engine, cfg, fol, seed, lo, hi))
    if compiles:
        counts["correct"] = False
        counts["compiled_in_window"] = compiles
    counters = {**tracing.window_counters,
                "compiles_in_window": compiles, "window_s": hi - lo}
    if "itl_worst5pct_mean_ms" in counts:
        counters["itl_worst5pct_mean_ms"] = counts["itl_worst5pct_mean_ms"]
    obs = {"spans": spans, "span_args": tracing.span_args,
           "samples": samples, "counters": counters,
           "trace": tracing.reduce() or {}}
    return e2e, obs, counts
