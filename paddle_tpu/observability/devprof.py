"""Device-cost observatory: XLA cost capture + sampled device timing.

PR 15's tracing measures host-side latency only, and after PR 19's
megasteps the ``decode`` blame component is an opaque device blob.
This module is the TPU-native analog of the reference framework's
CUPTI profiler tier: instead of driver event hooks it reads what XLA
already knows — the lowered computation's ``cost_analysis()`` — and
samples real device time with a ``block_until_ready`` timer, deriving
roofline attribution from the two.

Three planes, all off by default (``FLAGS_serving_devprof``):

- **static cost capture** — :func:`note_compile` is called by
  ``tracked_jit`` whenever a tracked site compiles. It lowers the RAW
  python function out-of-band (never the tracked wrapper, so the
  per-instance retrace counters and ``xla_compiles`` never move — the
  zero-compile contract ``predict_serving_compiles(devprof=True)``
  validates) and records flops / HBM bytes / output bytes per
  site+signature into :func:`cost_table` and the
  ``xla_cost{fn,metric}`` gauges.

- **sampled device timing** — the serving engine owns a
  :class:`DevProfiler`; a deterministic hash of its dispatch counter
  (``FLAGS_serving_devprof_sample``, same Knuth-hash scheme as trace
  sampling — no RNG stream consumed) picks which dispatches get a
  ``block_until_ready`` timer. Timestamps come off the *engine clock*,
  so a seeded virtual-clock run stays deterministic (and its timings
  collapse to the virtual step cost — wall time never leaks into
  byte-identity surfaces). Each sample feeds the per-entry
  ``serving_device_step_ms{fn=...}`` histogram and the live
  ``serving_mfu`` / ``serving_hbm_util`` /
  ``serving_host_overhead_share`` gauges; joining a sample against the
  entry's captured cost yields the roofline verdict — compute-bound vs
  HBM-bound vs host-bound (the host-overhead share is exactly the
  number PR 19's megasteps claim to shrink, now continuously
  measured).

- **blame split** — :meth:`DevProfiler.device_frac` is the sampled
  device share of decode step time; the engine annotates it onto each
  finished trace and ``tracing.blame()`` splits ``decode`` into
  ``decode_device`` + ``decode_host`` with the exact-reconciliation
  identity preserved (see observability/tracing.py).

``tools/perf_ledger.py`` / ``tools/perf_regress.py`` persist the
resulting numbers (plus a cost-table digest) as an enforced
perf-regression trajectory.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from typing import Any, Dict, List, Optional

from .. import flags as _flags

#: Per-chip peaks of the TPUs this repository may be measured on,
#: keyed by ``jax.devices()[0].device_kind``: (dense bf16 FLOP/s, HBM
#: bytes/s). Source: Google Cloud TPU documentation, the "System
#: architecture" page of each generation (v5e: 197 TFLOP/s bf16,
#: 819 GB/s; v6e: 918 TFLOP/s, 1640 GB/s; v5p: 459 TFLOP/s, 2765 GB/s;
#: v4: 275 TFLOP/s, 1200 GB/s). The ONE table: ``bench.py`` reads it
#: too. A TPU that is not listed is an error (:func:`tpu_peaks`), never
#: a default — a wrong peak makes every utilisation wrong silently.
TPU_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v4": (275e12, 1200e9),
}

#: nominal peaks for the non-TPU platforms (CPU tests, GPU), used when
#: the devprof_peak_* flags are 0
_PEAK_FLOPS = {"gpu": 312e12, "cpu": 1e11}
_PEAK_HBM_GBPS = {"gpu": 2000.0, "cpu": 50.0}

_lock = threading.Lock()
#: qualified tracked_jit name -> {"signature", "flops", "hbm_bytes",
#: "out_bytes", "captures", "supported"} (latest signature wins; the
#: capture count keeps recompile churn visible)
_COSTS: Dict[str, Dict[str, Any]] = {}
#: live DevProfiler instances with >= 1 sample feed the export embeds
_PROFILERS: List["DevProfiler"] = []


def enabled() -> bool:
    """The master switch: FLAGS_serving_devprof."""
    return bool(_flags.get_flag("serving_devprof"))


def tpu_peaks(device_kind: str):
    """``(peak bf16 FLOP/s, peak HBM bytes/s)`` of one TPU chip by its
    ``device_kind``; raises for a kind outside :data:`TPU_PEAKS`."""
    try:
        return TPU_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s / HBM bandwidth known for TPU device_kind "
            f"{device_kind!r}; add it (with its source) to "
            f"observability.devprof.TPU_PEAKS — known: "
            f"{sorted(TPU_PEAKS)}") from None


def _normalize_cost(cost) -> Dict[str, Optional[float]]:
    """Pick the three numbers the roofline needs out of
    ``Lowered.cost_analysis()``'s dict (None when the backend reports
    nothing). Unknown keys are ignored; missing keys stay None."""
    if not isinstance(cost, dict):
        return {"flops": None, "hbm_bytes": None, "out_bytes": None}

    def pick(key):
        v = cost.get(key)
        return float(v) if isinstance(v, (int, float)) else None

    return {"flops": pick("flops"),
            "hbm_bytes": pick("bytes accessed"),
            "out_bytes": pick("bytes accessedout{}")}


def _qualname(name: str, labels: Dict[str, str]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def note_compile(name: str, labels: Dict[str, str], fn, jit_kwargs,
                 args, kwargs) -> Optional[dict]:
    """Called by ``tracked_jit`` right after it accounts a compile:
    lower the RAW ``fn`` with the compiling call's concrete args and
    record its cost analysis. The raw lowering never touches the
    tracked wrapper, so retrace counters and ``xla_compiles`` stay
    exactly where the predictor expects them; the shared-model trace
    lock (PR 19) makes the re-trace thread-safe. No-op unless
    FLAGS_serving_devprof. Returns the recorded entry (tests)."""
    if not enabled():
        return None
    qual = _qualname(name, dict(labels or {}))
    entry = {"flops": None, "hbm_bytes": None, "out_bytes": None,
             "signature": None, "supported": True}
    try:
        import jax
        lowered = jax.jit(fn, **jit_kwargs).lower(*args, **kwargs)
        entry.update(_normalize_cost(lowered.cost_analysis()))
    except Exception:
        # a site whose lowering needs device context we don't have
        # (exotic shardings, backend quirks) records None fields —
        # the observatory must never break the serving path
        entry["supported"] = False
    from .compile_tracker import abstract_signature
    entry["signature"] = abstract_signature(args, kwargs)
    with _lock:
        rec = _COSTS.setdefault(qual, {"captures": 0})
        rec.update(entry)
        rec["captures"] += 1
    from . import metrics as _metrics
    g = _metrics.DEFAULT.gauge(
        "xla_cost",
        "XLA cost_analysis() of the latest compile per tracked site "
        "(metric: flops | hbm_bytes | out_bytes)")
    for metric in ("flops", "hbm_bytes", "out_bytes"):
        v = entry[metric]
        if v is not None:
            g.labels(fn=qual, metric=metric).set(v)
    from . import runlog as _runlog
    if _runlog.enabled():
        _runlog.log_event("devprof_cost", fn=qual,
                          flops=entry["flops"],
                          hbm_bytes=entry["hbm_bytes"],
                          out_bytes=entry["out_bytes"])
    with _lock:
        return dict(_COSTS[qual])


def cost_table() -> Dict[str, Dict[str, Any]]:
    """Snapshot of captured costs, keyed by qualified tracked_jit
    name (``decode_step_paged``, ``decode_megastep_paged{n=4}``,
    ``serving_prefill_paged{bucket=16}``, ...)."""
    with _lock:
        return {k: dict(v) for k, v in sorted(_COSTS.items())}


def cost_digest() -> Optional[str]:
    """Stable short digest of the cost table (flops/bytes per site,
    signatures excluded — they carry process-unique leaf counts only
    in pathological cases but churn on geometry). The perf ledger
    stores it so a cost change shows up as a digest change even when
    wall-clock metrics hide it."""
    with _lock:
        if not _COSTS:
            return None
        doc = {k: [v.get("flops"), v.get("hbm_bytes"),
                   v.get("out_bytes")]
               for k, v in sorted(_COSTS.items())}
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()[:16]


def _peaks() -> Dict[str, float]:
    """Resolve the roofline peaks: flags when pinned; else on a TPU the
    :data:`TPU_PEAKS` row of the device (an unlisted kind raises), on
    other platforms the nominals."""
    g = _flags.get_flags(["devprof_peak_flops", "devprof_peak_hbm_gbps"])
    flops = float(g["devprof_peak_flops"])
    bps = float(g["devprof_peak_hbm_gbps"]) * 1e9
    if flops <= 0 or bps <= 0:
        import jax
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            tab_flops, tab_bps = tpu_peaks(dev.device_kind)
        else:
            tab_flops = _PEAK_FLOPS.get(dev.platform, _PEAK_FLOPS["cpu"])
            tab_bps = 1e9 * _PEAK_HBM_GBPS.get(dev.platform,
                                               _PEAK_HBM_GBPS["cpu"])
        if flops <= 0:
            flops = tab_flops
        if bps <= 0:
            bps = tab_bps
    return {"peak_flops": flops, "peak_bytes_per_s": bps}


class DevProfiler:
    """One engine's sampled device timer + roofline aggregator.

    The engine calls :meth:`tick` once per step dispatch (under its
    step lock); a True return means *this* dispatch should be timed —
    the engine blocks on the dispatch's outputs and reports the
    measured split via :meth:`note_step`. A False return costs one
    integer hash and leaves the async/dispatch-ahead path untouched.
    Sampling decisions hash the dispatch counter (deterministic per
    step index — seeded replays sample the same steps); timestamps
    are the *caller's* clock, so virtual-clock runs stay wall-free.
    """

    def __init__(self, sample: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 peak_bytes_per_s: Optional[float] = None,
                 gauge_labels: Optional[Dict[str, str]] = None):
        if sample is None:
            sample = float(_flags.get_flag("serving_devprof_sample"))
        if not (0.0 <= sample <= 1.0):
            raise ValueError(
                f"devprof sample must be in [0, 1], got {sample}")
        peaks = _peaks()
        self.sample = float(sample)
        self.peak_flops = float(peak_flops if peak_flops is not None
                                else peaks["peak_flops"])
        self.peak_bytes_per_s = float(
            peak_bytes_per_s if peak_bytes_per_s is not None
            else peaks["peak_bytes_per_s"])
        self._labels = dict(gauge_labels or {})
        self._lock = threading.Lock()
        self._dispatches = 0
        self._samples = 0
        self._device_s = 0.0
        self._host_s = 0.0
        #: per-entry aggregates: name -> [samples, device_s, host_s]
        self._entries: Dict[str, List[float]] = {}
        self._registered = False
        self._gauges = None

    # ------------------------------------------------------- sampling
    def tick(self) -> bool:
        """Advance the dispatch counter; True = time this dispatch.
        The Knuth-hash decision is a pure function of the counter, so
        two same-seed runs sample the same step indices."""
        with self._lock:
            i = self._dispatches
            self._dispatches += 1
        if self.sample <= 0.0:
            return False
        if self.sample >= 1.0:
            return True
        return ((i * 2654435761) % (2 ** 32)) / (2 ** 32) < self.sample

    # ------------------------------------------------------ recording
    def _gauge_handles(self):
        if self._gauges is None:
            from . import metrics as _metrics
            reg = _metrics.DEFAULT
            self._gauges = {
                "mfu": reg.gauge(
                    "serving_mfu",
                    "model FLOPs utilization of sampled step "
                    "dispatches: captured cost_analysis flops / "
                    "(sampled device seconds * peak FLOP/s)"
                    ).labels(**self._labels),
                "hbm": reg.gauge(
                    "serving_hbm_util",
                    "HBM bandwidth utilization of sampled step "
                    "dispatches: cost_analysis bytes accessed / "
                    "(sampled device seconds * peak bytes/s)"
                    ).labels(**self._labels),
                "host": reg.gauge(
                    "serving_host_overhead_share",
                    "host share of sampled step wall time: host_s / "
                    "(host_s + device_s) — the number decode "
                    "megasteps exist to shrink"
                    ).labels(**self._labels),
                "hist": reg.histogram(
                    "serving_device_step_ms",
                    "sampled device ms per step dispatch, per "
                    "compiled entry"),
            }
        return self._gauges

    def note_step(self, entry: str, device_s: float, host_s: float):
        """Record one sampled dispatch: ``device_s`` is dispatch ->
        block_until_ready on the caller's clock, ``host_s`` the
        commit/bookkeeping remainder of the step. Feeds the per-entry
        histogram and the live roofline gauges."""
        device_s = max(0.0, float(device_s))
        host_s = max(0.0, float(host_s))
        with self._lock:
            self._samples += 1
            self._device_s += device_s
            self._host_s += host_s
            agg = self._entries.setdefault(entry, [0, 0.0, 0.0])
            agg[0] += 1
            agg[1] += device_s
            agg[2] += host_s
        with _lock:
            if not self._registered:
                self._registered = True
                _PROFILERS.append(self)
        g = self._gauge_handles()
        g["hist"].labels(fn=entry).observe(device_s * 1e3)
        g["host"].set(self.host_share() or 0.0)
        roof = self.roofline(entry)
        if roof["mfu"] is not None:
            g["mfu"].set(roof["mfu"])
        if roof["hbm_util"] is not None:
            g["hbm"].set(roof["hbm_util"])

    # -------------------------------------------------------- queries
    def device_frac(self) -> Optional[float]:
        """Sampled device share of step time — the decode blame-split
        fraction. None until a sample with nonzero time exists (a
        virtual-clock run whose samples are all zero stays
        unannotated, preserving byte-identical exports)."""
        with self._lock:
            tot = self._device_s + self._host_s
            if self._samples == 0 or tot <= 0.0:
                return None
            return self._device_s / tot

    def host_share(self) -> Optional[float]:
        f = self.device_frac()
        return None if f is None else 1.0 - f

    def roofline(self, entry: str) -> Dict[str, Any]:
        """One entry's roofline verdict from its sampled device time
        joined against its captured cost: ``compute-bound`` vs
        ``hbm-bound`` by which utilization dominates, ``host-bound``
        when the sampled host share exceeds the device share,
        ``unattributed`` without a cost capture."""
        with self._lock:
            agg = self._entries.get(entry)
            samples, dev_s, host_s = (agg if agg else (0, 0.0, 0.0))
        cost = cost_table().get(entry, {})
        flops, hbm = cost.get("flops"), cost.get("hbm_bytes")
        mfu = hbm_util = None
        if samples and dev_s > 0:
            per_dispatch = dev_s / samples
            if flops:
                mfu = flops / (per_dispatch * self.peak_flops)
            if hbm:
                hbm_util = hbm / (per_dispatch *
                                  self.peak_bytes_per_s)
        if samples and host_s > dev_s:
            verdict = "host-bound"
        elif mfu is None and hbm_util is None:
            verdict = "unattributed"
        elif (mfu or 0.0) >= (hbm_util or 0.0):
            verdict = "compute-bound"
        else:
            verdict = "hbm-bound"
        return {
            "entry": entry,
            "samples": samples,
            "device_ms_mean": (round(dev_s / samples * 1e3, 6)
                               if samples else None),
            "host_ms_mean": (round(host_s / samples * 1e3, 6)
                             if samples else None),
            "flops": flops,
            "hbm_bytes": hbm,
            "mfu": None if mfu is None else round(mfu, 6),
            "hbm_util": (None if hbm_util is None
                         else round(hbm_util, 6)),
            "verdict": verdict,
        }

    def entries(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)

    def stats(self) -> dict:
        """The ``/v1/stats`` devprof section."""
        with self._lock:
            dispatches, samples = self._dispatches, self._samples
        frac = self.device_frac()
        return {
            "sample": self.sample,
            "dispatches": dispatches,
            "samples": samples,
            "device_frac": (None if frac is None
                            else round(frac, 6)),
            "host_overhead_share": (None if frac is None
                                    else round(1.0 - frac, 6)),
            "mfu": self.mfu(),
            "entries": [self.roofline(e) for e in self.entries()],
        }

    def mfu(self) -> Optional[float]:
        """Aggregate MFU over every sampled entry with a cost: total
        sampled flops / (total sampled device seconds * peak)."""
        costs = cost_table()
        flops_total = dev_total = 0.0
        with self._lock:
            items = [(e, list(a)) for e, a in self._entries.items()]
        for entry, (samples, dev_s, _h) in items:
            f = costs.get(entry, {}).get("flops")
            if f and dev_s > 0:
                flops_total += f * samples
                dev_total += dev_s
        if dev_total <= 0.0:
            return None
        return round(flops_total / (dev_total * self.peak_flops), 6)


def roofline_entries() -> List[dict]:
    """Every registered profiler's per-entry roofline rows — the
    trace-export embed (chrome ``devprof`` metadata events / JSONL
    ``{"devprof": ...}`` lines). Empty when nothing sampled, so
    devprof-off exports are byte-identical to before."""
    with _lock:
        profs = list(_PROFILERS)
    out = []
    for p in profs:
        out.extend(p.roofline(e) for e in p.entries())
    return out


def snapshot() -> dict:
    """The observability.snapshot() / profiler summary section."""
    return {"costs": cost_table(),
            "cost_digest": cost_digest(),
            "rooflines": roofline_entries()}


def reset():
    """Drop captured costs and registered profilers (tests)."""
    global _SUPPORTED
    with _lock:
        _COSTS.clear()
        _PROFILERS.clear()


class StepTimer:
    """Tiny helper the engine wraps around one sampled dispatch:

        timer = profiler.timer(entry, clock)   # tick() already True
        ... dispatch ...
        timer.device_done(out)   # block_until_ready + stamp
        ... host commit work ...
        timer.finish()           # records the split

    ``device_done`` is a no-op pass-through for None timers, so call
    sites stay branch-light."""

    def __init__(self, profiler: DevProfiler, entry: str, clock):
        self._p = profiler
        self._entry = entry
        self._clock = clock
        self._t0 = clock()
        self._t_dev: Optional[float] = None

    def device_done(self, out):
        import jax
        try:
            jax.block_until_ready(out)
        except Exception:
            pass
        self._t_dev = self._clock()
        return out

    def finish(self):
        t_end = self._clock()
        t_dev = self._t_dev if self._t_dev is not None else t_end
        self._p.note_step(self._entry,
                          device_s=t_dev - self._t0,
                          host_s=t_end - t_dev)


def timer(profiler: Optional[DevProfiler], entry: str,
          clock=time.perf_counter) -> Optional[StepTimer]:
    """A StepTimer when this dispatch sampled in, else None — the
    engine's one-line call site: ``t = devprof.timer(p, entry, clock)
    if p and p.tick() else None``."""
    if profiler is None:
        return None
    return StepTimer(profiler, entry, clock)
