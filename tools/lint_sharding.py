#!/usr/bin/env python
"""Lint GSPMD sharding-rule tables against a model and a mesh.

Static pre-flight for ``to_static(mesh=..., param_rules=...)`` — runs
``distributed.sharding.lint_sharding_rules`` over a preset rule table
and the GPT benchmark model, with the mesh given as plain axis sizes
(no TPU devices needed):

    python tools/lint_sharding.py --preset gpt_tp --mesh dp=2,mp=2
    python tools/lint_sharding.py --preset gpt_tp+fully_sharded \\
        --mesh dp=4,mp=2 --strict --json

Findings (structured Diagnostics, same records as lint_program.py):
dead rules, earlier regexes shadowing later ones, silent
replicated-fallback on non-divisible dims, unknown mesh axes (ERROR),
oversized fully-replicated tensors — plus the per-device parameter
memory estimate under the fitted specs.

Exit status 1 on ERROR findings; --strict also fails on warnings.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the tiny-but-structurally-faithful GPT used across CI gates
# (the serving tests): every TP rule family
# (qkv/out_proj/fc1/fc2/wte) has a live target. vocab_pad_to=2 pads the
# deliberately-awkward 97-row vocab to 98 so the vocab-parallel wte
# rule divides cleanly — `--preset gpt_tp --strict` runs warning-free
# (the old vocab-97 replicated fallback was the one expected finding).
GPT_CFG = dict(vocab_size=97, max_position_embeddings=64, hidden_size=32,
               num_layers=2, num_heads=4, ffn_hidden_size=64,
               vocab_pad_to=2)


def build_model():
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    pt.seed(0)
    return GPTForCausalLM(GPTConfig(**GPT_CFG))


def resolve_rules(preset: str):
    from paddle_tpu.distributed import sharding as sh
    presets = {
        "gpt_tp": sh.GPT_TENSOR_PARALLEL_RULES,
        "encoder_tp": sh.ENCODER_TENSOR_PARALLEL_RULES,
        "serving_tp": sh.SERVING_TP_RULES,
        "fully_sharded": sh.FULLY_SHARDED_RULES,
    }
    parts = [p.strip() for p in preset.split("+") if p.strip()]
    unknown = [p for p in parts if p not in presets]
    if unknown:
        raise SystemExit(
            f"unknown preset(s) {unknown}; available: "
            f"{sorted(presets)} (combine with '+', first wins)")
    rules = presets[parts[0]]
    for p in parts[1:]:
        rules = rules.merge(presets[p])
    return rules


def parse_mesh(text: str) -> dict:
    mesh = {}
    for tok in text.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "=" not in tok:
            raise SystemExit(
                f"bad --mesh entry {tok!r}: expected axis=size "
                f"(e.g. dp=2,mp=2)")
        axis, size = tok.split("=", 1)
        mesh[axis.strip()] = int(size)
    if not mesh:
        raise SystemExit("--mesh needs at least one axis=size entry")
    return mesh


def main(argv=None):
    ap = argparse.ArgumentParser(
        "lint_sharding",
        description="Static checks over sharding-rule tables.")
    ap.add_argument("--preset", default="gpt_tp",
                    help="rule table: gpt_tp | encoder_tp | serving_tp "
                         "| fully_sharded, or 'a+b' to merge (a wins) "
                         "[gpt_tp]")
    ap.add_argument("--mesh", default="dp=2,mp=2",
                    help="mesh axis sizes, axis=size,... [dp=2,mp=2]")
    ap.add_argument("--dtype-bytes", type=int, default=4,
                    help="bytes per parameter element [4]")
    ap.add_argument("--replicated-warn-mb", type=float, default=64.0,
                    help="warn on fully-replicated params above this "
                         "size [64]")
    ap.add_argument("--strict", action="store_true",
                    help="treat warnings as fatal too")
    ap.add_argument("--zero-stage", type=int, default=-1,
                    help="also estimate per-device optimizer-state "
                         "bytes under this ZeRO stage (0|1|2; -1 = "
                         "skip) [-1]")
    ap.add_argument("--zero-axis", default="dp",
                    help="mesh axis ZeRO shards optimizer state over "
                         "[dp]")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one JSON report on stdout instead of text")
    args = ap.parse_args(argv)

    from paddle_tpu.distributed.sharding import (estimate_zero_opt_bytes,
                                                 lint_sharding_rules)

    mesh = parse_mesh(args.mesh)
    rules = resolve_rules(args.preset)
    model = build_model()
    result = lint_sharding_rules(
        rules, model, mesh, dtype_bytes=args.dtype_bytes,
        replicated_warn_mb=args.replicated_warn_mb)
    zero = None
    if args.zero_stage >= 0:
        if args.zero_axis not in mesh:
            raise SystemExit(
                f"--zero-axis {args.zero_axis!r} not in --mesh "
                f"{sorted(mesh)}")
        zero = estimate_zero_opt_bytes(
            model, mesh, rules, axis=args.zero_axis,
            stage=args.zero_stage, dtype_bytes=args.dtype_bytes)
    failed = bool(result.errors) or (args.strict
                                     and bool(result.warnings))

    if args.as_json:
        print(json.dumps({
            "ok": not failed,
            "preset": args.preset,
            "mesh": mesh,
            "errors": len(result.errors),
            "warnings": len(result.warnings),
            "diagnostics": [dataclasses.asdict(d)
                            for d in result.diagnostics],
            "rules": [dataclasses.asdict(r) if r.pattern is not None
                      else {**dataclasses.asdict(r), "pattern": None}
                      for r in _plain_rules(result.rules)],
            "params": [{"name": n, "shape": list(s), "spec": str(p)}
                       for n, s, p in result.params],
            "total_bytes": result.total_bytes,
            "per_device_bytes": result.per_device_bytes,
            "replicated_bytes": result.replicated_bytes,
            **({"zero": {"stage": args.zero_stage,
                         "axis": args.zero_axis, **zero}}
               if zero is not None else {}),
        }, indent=2))
        return 1 if failed else 0

    print(f"sharding lint: preset={args.preset} mesh={mesh} "
          f"({len(result.params)} params)")
    for i, r in enumerate(result.rules):
        label = (f"#{i} {r.pattern!r}" if r.pattern is not None
                 else "<default>")
        print(f"  {label}: spec={r.spec} matches={r.matches} "
              f"wins={r.wins}")
    for d in result.diagnostics:
        print(f"  {d}")
    mib = 1024 * 1024
    print(f"  parameter bytes: total={result.total_bytes} "
          f"({result.total_bytes / mib:.2f} MiB), "
          f"per-device={result.per_device_bytes} "
          f"({result.per_device_bytes / mib:.2f} MiB), "
          f"replicated={result.replicated_bytes}")
    if zero is not None:
        print(f"  ZeRO-{args.zero_stage} optimizer bytes (axis "
              f"{args.zero_axis!r}): total={zero['opt_bytes']}, "
              f"per-device={zero['opt_bytes_per_device']}")
    print(f"{'FAIL' if failed else 'ok'}: {len(result.errors)} error(s), "
          f"{len(result.warnings)} warning(s)")
    return 1 if failed else 0


def _plain_rules(reports):
    """dataclasses.asdict chokes on PartitionSpec fields — stringify."""
    out = []
    for r in reports:
        out.append(type(r)(pattern=r.pattern, spec=str(r.spec),
                           matches=r.matches, wins=r.wins))
    return out


if __name__ == "__main__":
    sys.exit(main())
