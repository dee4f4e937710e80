"""A configuration names its family, and the family is found by name.

``configs/<name>.json`` may carry ``"family": "<family>"`` (absent means
``gpt2``); ``perfbench/families/<family>`` (a module or a package) holds
everything that is the architecture's and not the harness's. A family
exports:

- ``model_config(cfg)``: the program's model configuration for the file,
  checked against the file's own numbers (the file is the truth);
- ``serving_model(cfg)``: the program's model as the engine serves it,
  built where the harness calls it (inside ``weights.recording``);
- ``train_job(cfg, job)`` -> ``(model, optimizer, train function,
  retain_grads)`` for a configuration and a training job file;
- ``hidden(params, ids, cfg)`` -> the final normed hidden state
  ``[b, s, h]`` and ``head(params, cfg)`` -> the output matrix
  ``[h, vocabulary]`` (the tied embedding transposed where the head is
  tied): the plain reference in its two halves, float32 at ``highest``,
  weights keyed by the program's parameter names. The serving check
  multiplies them a block of rows at a time (``serve.deficits_fn``), so its
  memory does not grow with ``max_len x vocabulary``;
- ``forward(params, ids, cfg)`` -> logits, their product whole (tests and
  ``study/`` compare whole logits at sizes that hold them), and
  ``loss(params, ids, labels, cfg)``;
- ``train_flops_per_token(cfg, seq)``: model FLOPs of one trained token
  (for a model with experts, of the experts a token is routed to);
- ``kernel_counts(name, cfg, job)`` -> ``(flops, bytes)`` of ONE call of
  the named kernel at the cell's shapes (a training job's or a serving
  job's), or ``None`` for a kernel the family has no count for.

The runners reach the architecture only through these, so a second
architecture is ``families/<name>``, ``configs/<name>.json``, its twin
``rehearsal/<family>-tiny.json`` and entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib
import pkgutil

DEFAULT = "gpt2"
EXPORTS = ("model_config", "serving_model", "train_job", "hidden", "head",
           "forward", "loss", "train_flops_per_token", "kernel_counts")


def known() -> list:
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def name_of(cfg: dict) -> str:
    return cfg.get("family", DEFAULT)


def load(cfg: dict):
    """The family module a configuration file names."""
    name = name_of(cfg)
    full = f"{__name__}.{name}"
    try:
        family = importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise SystemExit(
            f"configuration {cfg.get('name')!r} names the family {name!r}; "
            f"perfbench/families has {known()}") from None
    missing = [x for x in EXPORTS if not hasattr(family, x)]
    if missing:
        raise SystemExit(f"family {name!r} does not export {missing}")
    return family
