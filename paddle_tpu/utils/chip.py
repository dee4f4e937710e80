"""What an entry point settles before it touches the chip: where JAX's
persistent compilation cache lives, what the device calls itself, and
which peaks a device of that kind is rated at.

Every call to the chip starts a fresh machine, and compiling a
billion-parameter step takes longer than running it. The entry points
that touch the chip (``chip_smoke.py``, ``bench.py --child``,
``tools/loadgen.py``, ``tools/soak.py``) call
:func:`enable_compile_cache` once, before their first compile. It is
deliberately NOT called at ``import paddle_tpu``: a library import must
not start writing files.

The directory is part of the cache key's environment, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when the caller set it (JAX reads
that variable itself — nothing is overridden here), otherwise one fixed
path inside the checkout. Never a temp dir, pid or timestamp.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_cache (git-ignored) — used when the variable is unset
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """Where the persistent cache lives for this process."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`cache_dir`; returns it."""
    import jax

    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # the default 1 s floor skips small step programs from run to run
    # depending on how long the compiler happened to take; 0 writes
    # every program, so "same shapes -> no new entries" is exact
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entries(path: str = None) -> int:
    """Number of executables in the cache directory (0 if absent)."""
    path = path or cache_dir()
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except FileNotFoundError:
        return 0


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the devices this process
    runs on, as JAX reports them — every published result carries it,
    so a CPU rehearsal can never pass for a chip run."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


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


def tpu_peaks(device_kind: str):
    """``(peak bf16 FLOP/s, peak HBM bytes/s)`` of one TPU chip by its
    ``device_kind``; raises for a kind outside :data:`TPU_PEAKS`."""
    try:
        return TPU_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s / HBM bandwidth known for TPU device_kind "
            f"{device_kind!r}; add it (with its source) to "
            f"utils.chip.TPU_PEAKS — known: "
            f"{sorted(TPU_PEAKS)}") from None
