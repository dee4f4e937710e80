"""The GPT-2 family: learned positions, pre-LayerNorm blocks, full
multi-head causal attention, GELU MLP, output head tied to the embedding
(``paddle_tpu/models/gpt.py``; the plain reference is ``reference.py``).

A configuration of this family gives ``n_embd``, ``n_layer``, ``n_head``,
``n_inner``, ``n_positions``, ``vocab_size`` and a ``program`` group: the
program's ``preset``, the rows its embedding holds (``vocab_rows``), and
optionally ``num_layers`` for a depth the preset does not have.
"""

from __future__ import annotations

import os

from .. import flops, reference


def model_config(cfg: dict):
    """The program's GPTConfig for a configuration file, checked against
    the file's own numbers (the file is the truth, the preset the means)."""
    import dataclasses
    from paddle_tpu.models import GPT_CONFIGS
    prog = cfg["program"]
    gc_ = GPT_CONFIGS[prog["preset"]]
    if "num_layers" in prog:
        gc_ = dataclasses.replace(gc_, num_layers=int(prog["num_layers"]))
    want = {"hidden_size": cfg["n_embd"], "num_layers": cfg["n_layer"],
            "num_heads": cfg["n_head"], "ffn_hidden_size": cfg["n_inner"],
            "max_position_embeddings": cfg["n_positions"],
            "vocab_size": prog["vocab_rows"]}
    got = {k: getattr(gc_, k) for k in want}
    if got != want:
        raise SystemExit(f"configuration {cfg['name']}: the program's preset "
                         f"{prog['preset']} has {got}, the file says {want}")
    return gc_


def serving_model(cfg: dict):
    from paddle_tpu.models import GPTForCausalLM
    return GPTForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    """The program's own train job (``bench.build_train``: AdamW, AMP O2)
    -> (model, optimizer, train function, retain_grads)."""
    import bench
    gcfg = model_config(cfg)
    tr = cfg["trainer"]
    # bench.build_train reads its levers from the environment
    os.environ["BENCH_RECOMPUTE"] = "1" if tr["recompute"] else "0"
    os.environ["BENCH_NO_RETAIN_GRADS"] = "0" if tr["retain_grads"] else "1"
    os.environ["BENCH_BF16_MOMENTS"] = \
        "1" if tr["moment_dtype"] == "bfloat16" else "0"
    os.environ.pop("BENCH_GPT_LAYERS", None)
    _, model, opt, fn, retain = bench.build_train(
        cfg["program"]["preset"], int(job["seq"]))
    if model.cfg.num_layers != gcfg.num_layers:
        raise SystemExit("bench.build_train built another depth than the "
                         "configuration file states")
    return model, opt, fn, retain


def _shape(cfg: dict) -> dict:
    return dict(num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                vocab_size=cfg["program"]["vocab_rows"])


def hidden(params: dict, ids, cfg: dict):
    return reference.hidden(params, ids, num_layers=cfg["n_layer"],
                            num_heads=cfg["n_head"])


def head(params: dict, cfg: dict):
    return reference.head(params, vocab_size=cfg["program"]["vocab_rows"])


def forward(params: dict, ids, cfg: dict):
    return reference.forward(params, ids, **_shape(cfg))


def loss(params: dict, ids, labels, cfg: dict):
    return reference.loss(params, ids, labels, **_shape(cfg))


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Dense: every token passes every weight (``flops.py`` has the count)."""
    return flops.train_flops_per_token(
        hidden=cfg["n_embd"], ffn=cfg["n_inner"], layers=cfg["n_layer"],
        vocab_rows=cfg["program"]["vocab_rows"], seq=seq)


#: matmuls of one flash kernel call, each ``[s, d] x [d, s]`` or its like:
#: forward QK^T and PV; dq recomputes QK^T, then dO V^T and dS K; dkv
#: recomputes QK^T, then P^T dO, dO V^T and dS^T Q. And the ``[bh, s, d]``
#: arrays a call reads and writes (q k v | o; q k v do | dq; q k v do | dk
#: dv) and the float32 ``[bh, s]`` rows beside them (lse; lse delta).
_FLASH = {"flash_fwd": (2, 4, 1), "flash_bwd_dq": (3, 5, 2),
          "flash_bwd_dkv": (4, 6, 2)}
_AMP_O2_BYTES = 2       # the train step hands the kernels bfloat16


def kernel_counts(name: str, cfg: dict, job: dict):
    """(flops, bytes) of one call of a flash kernel in a training job: one
    chip's batch, all heads, causal, so half of the ``s x s`` products (as
    ``flops.train_flops_per_token`` counts the model); HBM bytes are each
    operand and result once. Fixed shapes only: a kernel whose work depends
    on data has no count here."""
    if name not in _FLASH or job.get("kind") != "train":
        return None
    matmuls, arrays, rows = _FLASH[name]
    bh = int(job["batch_per_chip"]) * cfg["n_head"]
    s, d = int(job["seq"]), cfg["n_embd"] // cfg["n_head"]
    return (matmuls * 2.0 * bh * s * s * d / 2.0,
            arrays * bh * s * d * _AMP_O2_BYTES + rows * bh * s * 4.0)
