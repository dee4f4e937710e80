#!/usr/bin/env python3
"""The same values are stored: a cell's own engine (`lfm2_agents_3k`'s by
default) fed the same requests on two checkouts emits the same tokens
(PR 43).

    python3 perfbench/study/same_tokens.py --root _chip_scratch/parent \\
        --side parent --out chiprun_out/p43/same_tokens.jsonl
    python3 perfbench/study/same_tokens.py --side change \\
        --out chiprun_out/p43/same_tokens.jsonl

A cell's `max_logit_deficit` is read on the requests a run happens to
sample, so two sides that complete different numbers of requests in the
window sample different ones and their deficits differ with nothing wrong.
This asks the question directly: the cell's configuration and engine
(`perfbench/configs/lfm2-24b-a2b-d9.json`, weights from one fixed seed), 192
requests of 130-1900 prompt tokens and 24-64 new tokens from one fixed
stream, all submitted at once and run until idle (greedy: what is emitted
depends on nothing but the weights, the prompts and what the pools hold);
one sha256 over every emitted token, in the order of submission (and one a
request, `each`, to say which requests differ where the sides do). One
process a side (each holds the chip and 10.4 GB of it); equal hashes on the
two records say that every KV row a later step read was the row the other
side stored. `--root` is the checkout whose program is run (default: this
one); `--config` another serving configuration of it (the same requests,
drawn below its vocabulary); `--rehearsal` takes the toy twin and 12 short
requests, for the code path on the CPU.
"""

import argparse
import hashlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(HERE)))
    ap.add_argument("--side", required=True, help="a name for the record")
    ap.add_argument("--out", required=True)
    ap.add_argument("--config", default="",
                    help="under --root (default: lfm2_agents_3k's)")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    root, out = os.path.abspath(args.root), os.path.abspath(args.out)
    sys.path.insert(0, root)
    os.chdir(root)
    import numpy as np
    from paddle_tpu.utils.chip import enable_compile_cache
    enable_compile_cache()
    from perfbench import serve
    config = args.config or (
        "perfbench/rehearsal/lfm2-tiny.json" if args.rehearsal
        else "perfbench/configs/lfm2-24b-a2b-d9.json")
    with open(os.path.join(root, config)) as f:
        cfg = json.load(f)
    t0 = time.time()
    _, engine = serve.build_engine(cfg, 4300000301)
    rng = np.random.RandomState(43)
    count, low, high = (12, 5, 60) if args.rehearsal else (192, 130, 1900)
    vocab = int(cfg["vocab_size"])
    lens = np.exp(rng.uniform(np.log(low), np.log(high), count))
    news = rng.randint(24, 65, count)
    reqs = [engine.submit(rng.randint(1, vocab, int(n)).tolist(),
                          max_new_tokens=int(k))
            for n, k in zip(lens, news)]
    engine.run_until_idle()
    digest, total, each = hashlib.sha256(), 0, []
    for r in reqs:
        assert r.state == "done", r.state
        tokens = np.asarray(r.tokens, np.int64).tobytes()
        digest.update(tokens)
        each.append(hashlib.sha256(tokens).hexdigest()[:8])
        total += len(r.tokens)
    st = engine.stats()
    rec = {"tag": "same_tokens", "side": args.side, "root": root,
           "config": config, "sha256": digest.hexdigest(),
           "requests": len(reqs), "tokens": total,
           "first": [int(t) for t in reqs[0].tokens[:6]],
           "last": [int(t) for t in reqs[-1].tokens[-6:]],
           "pool_dispatches": st["pool_dispatches"],
           # where two sides differ: which requests, of what prompt length
           "prompt_lens": [int(n) for n in lens], "each": each,
           "seconds": time.time() - t0}
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
