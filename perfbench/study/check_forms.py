#!/usr/bin/env python3
"""The serving check's two forms on the same requests of one run (PR 37):
in blocks of ``CHECK_ROWS`` rows, as the check runs it, and whole (one block
as long as ``max_len``: the form it had to PR 36).

    python3 perfbench/study/check_forms.py --workload mellum_code_16k \
        --seed 0 --seconds 20 --out chiprun_out/p37/forms.jsonl

The cell runs through the harness as ``run.py`` runs it; ``serve.check``
gets a ``deficits_fn`` that computes both forms for every request it
samples and hands it the blocks' result. A parent's and a change's run of
one seed sample different requests (which requests end inside the window
follows the clock), so this, not a pair of runs, says whether a row's
deficit depends on its block. The two forms are two compiled programs, and
a reference with a router can choose another expert at a near tie from one
compilation of the same equations to the next; so the head is also taken
in both forms over ONE hidden state (``family.hidden`` jitted alone), where
only the block differs. One JSON record: the largest difference between the
two programs over every row of every sampled request (and over the
request's own rows, and how many rows differ by over 1e-4), the largest
over one hidden state, the seconds of each call (a form's first call
compiles), and the run's line.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the cell's toy twin on the CPU, as rehearse.py")
    args = ap.parse_args(argv)
    args.trace = 0
    import numpy as np
    from perfbench import run as harness, serve
    one_form = serve.deficits_fn
    seen = {"requests": 0, "max_abs_diff": 0.0,
            "max_abs_diff_request_rows": 0.0, "rows": 0,
            "rows_over_1e-4": 0, "max_abs_diff_one_hidden": 0.0,
            "seconds_blocks": [], "seconds_whole": []}

    def both_forms(family, cfg):
        import functools
        import jax
        rows = int(cfg["engine"]["max_len"])
        forms = {"blocks": one_form(family, cfg),
                 "whole": one_form(family, cfg, rows)}
        hidden = jax.jit(lambda p, i: family.hidden(p, i, cfg)[0])
        head = jax.jit(lambda p: family.head(p, cfg))
        tails = [jax.jit(functools.partial(serve.block_deficits,
                                           rows_a_block=b))
                 for b in (serve.CHECK_ROWS, rows)]

        def deficits(params, ids, nxt):
            got = {}
            for name, fn in forms.items():
                t = time.perf_counter()
                got[name] = np.asarray(fn(params, ids, nxt))
                seen["seconds_" + name].append(time.perf_counter() - t)
            diff = np.abs(got["blocks"] - got["whole"])
            mine = np.asarray(nxt) != 0         # the request's own rows
            x, w = hidden(params, ids), head(params)
            one_hidden = [np.asarray(tail(x, w, nxt)) for tail in tails]
            seen["requests"] += 1
            seen["rows"] += int(diff.size)
            seen["rows_over_1e-4"] += int(np.sum(diff > 1e-4))
            for key, value in (
                    ("max_abs_diff", diff.max()),
                    ("max_abs_diff_request_rows", diff[mine].max()),
                    ("max_abs_diff_one_hidden",
                     np.abs(one_hidden[0] - one_hidden[1]).max())):
                seen[key] = max(seen[key], float(value))
            return got["blocks"]
        return deficits

    serve.deficits_fn = both_forms
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    if args.rehearsal:
        from perfbench import rehearse
        line = rehearse.run_twin(
            bench, harness.find_cell(bench, args.workload), args)
    else:
        line = harness.run_cell(bench, args)
    rec = {"tool": "check_forms.py", "workload": args.workload,
           "seed": args.seed, "seconds": args.seconds,
           "rows_a_block": serve.CHECK_ROWS, **seen, "line": line}
    print(json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
