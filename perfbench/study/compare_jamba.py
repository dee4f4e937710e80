#!/usr/bin/env python3
"""The Jamba program against its plain reference at the published widths
and the cell's lengths, and the planted faults the comparison must see.

    python3 perfbench/study/compare_jamba.py --seed 2147484201 \
        --out chiprun_out/p33/compare.jsonl

Prefill then decode through the serving cache (``BlockKVCache.for_model``:
the attention layers' blocks and the Mamba layers' rows of state), jitted as
the engine's entries jit it, against the reference's full forward
(``families/jamba.forward``, float32 at ``highest``) on the same tokens:

- ``short``: two prompts of unequal length (``--short``, default 301 and
  498 rows) in ONE dispatch of the 512-row bucket, so each one's state is
  taken at its own last token, then ``--steps`` tokens decoded greedily by
  both rows together;
- ``long``: one prompt of ``--long`` rows (default 3003) in the 4096-row
  bucket, then ``--steps`` tokens.

A comparison reads, worst over its rows:

- ``deficit_max``: the harness's own measure (the reference's best logit
  minus its logit of the emitted token) over the decoded positions;
- ``logit_max``: the largest |program - reference| logit there;
- ``inc_median``: per layer and decoded row, |program's increment to the
  stream - reference's| / |reference's increment|, the median over layers
  and rows (a layer's increment is its output minus its input);
- ``inc_worst_layer``: the largest over layers of that ratio's median over
  the decoded rows (one layer that is wrong shows here, not in the median
  over 28);
- ``state_last`` / ``state_end``: |carried s - reference's s| / |reference's
  s| of the watched Mamba layer (the first one), at the prompt's last row (what the prefill handed over) and after
  the last decoded token (Frobenius norms; the worse of the rows);
- ``state_replay``: the carried s after the last decoded token against the
  s the PROGRAM's own prefill of the same tokens (prompt and answer, as one
  prompt) leaves at that row: both sides multiply bfloat16 inputs alike, so
  what is left is how the state was carried (the reference stands too far
  off, by the products' rounding, to see a state kept in bfloat16).

Faults, each replaying the clean run's tokens (``--faults``): ``zeroed``
(the scan state zeroed at the first decode step), ``pads`` (the state taken
at the bucket's end: the padding advanced it), ``tail`` (the convolution's
tail one row late), ``bf16_state`` (the scan state rounded to bfloat16 after
the prefill and after every step), ``skip`` (one Mamba layer's ``D x`` term
dropped). Exit code 0 when the clean program passes and every fault fails.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: limits of one comparison; a reading above any of them fails it. Each
#: stands between what the clean program read and what the fault it is
#: there to see read (my chip runs, PR 33, calls 5 and 6, seed 2147484201,
#: the final norm's gain at 0.01; clean = the worse of the two cases;
#: ``perfbench/study/runs_pr33.jsonl`` has every line):
#: - ``deficit_max`` 0.05 is the harness's own limit (``serve.check``): clean
#:   0.0025; state zeroed 0.22, taken at the bucket's end 0.62, tail a row
#:   late 0.23, one layer's ``D x`` dropped 0.15; a bfloat16 state 0.0 (not
#:   seen there: ``state_replay`` is for it);
#: - ``logit_max`` 0.05: clean 0.0114 (bfloat16 rounding of every matmul's
#:   input, the logits' scale ~1); the four faults above 0.26-1.2;
#: - ``inc_median`` 0.012 / ``inc_worst_layer`` 0.015: clean 0.0056 / 0.0064;
#:   the tail a row late 0.032 / 0.040, the others 0.13-0.24 / 0.23-0.42
#:   (``D x`` dropped in ONE layer: 0.125 / 0.38);
#: - ``state_last`` 0.015: clean 0.0047; zeroed 1.0, the bucket's end 3.3;
#: - ``state_end`` 0.02: clean 0.0062; the tail 0.048, zeroed 0.25;
#: - ``state_replay`` 0.002: clean 0.0 (at the first layer the prefill and
#:   the decode step agree to the bit); a bfloat16 state 0.0073, the tail
#:   0.048, zeroed 0.25, the bucket's end 0.98.
TOLERANCE = {"deficit_max": 0.05, "logit_max": 0.05, "inc_median": 0.012,
             "inc_worst_layer": 0.015, "state_last": 0.015,
             "state_end": 0.02, "state_replay": 0.002}
FAULTS = ("zeroed", "pads", "tail", "bf16_state", "skip")
BLOCK = 256


class Runner:
    """The model's serving forward over a ``BlockKVCache``, jitted as the
    engine's entries jit it, with every layer's output carried out."""

    def __init__(self, model, cfg, slots=2):
        from paddle_tpu.serving.kv_cache import BlockKVCache
        from paddle_tpu.serving.seam import served
        self.model, self.cfg, self.slots = model, cfg, slots
        self.spec = served(model)
        e = cfg["engine"]
        self.max_len = e["max_len"]
        self.cache = BlockKVCache.for_model(
            self.spec, slots, self.max_len, block_size=e["block_size"],
            num_blocks=0, prefix_cache=False, kv_dtype=self.spec.kv_dtype)
        self._fns = {}
        self.round_state = False    # the bf16_state fault
        self.zero_state = False     # the zeroed fault

    def _fn(self, key):
        """A jitted serving forward; traced anew after a fault that
        changes code (``self._fns`` is cleared)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.dygraph.tensor import Tensor
        from paddle_tpu.models.generation import (_borrowed_params,
                                                  _unwrap_pools,
                                                  _wrap_pools)
        if key in self._fns:
            return self._fns[key]
        model, prompt = self.model, key[0] == "prefill"

        def fn(params, ids, pos, last, tables, pools):
            from paddle_tpu.dygraph.tape import no_grad
            states = []
            with no_grad(), _borrowed_params(model, params):
                logits, newp = model(
                    Tensor(ids, stop_gradient=True),
                    cache=_wrap_pools(pools), cache_pos=pos,
                    block_tables=tables, last=last if prompt else None,
                    collect=states)
                emb = model.model._embed(Tensor(ids, stop_gradient=True))
            at = last[:, None, None] if prompt \
                else jnp.zeros((ids.shape[0], 1, 1), jnp.int32)
            # each layer's output at the row that matters, and the embedded
            # input there: [rows, layers + 1, h]
            rows = [jnp.take_along_axis(t.value, at, axis=1)[:, 0]
                    for t in [emb] + states]
            return (logits.value[:, 0], _unwrap_pools(newp)[0],
                    jnp.stack(rows, axis=1))
        self._fns[key] = jax.jit(fn, donate_argnums=(5,))
        return self._fns[key]

    def _params(self):
        return [p.value for _, p in self.model.named_parameters()]

    def _state_of(self, layer):
        """The scan state [slots, N, d] of model layer ``layer``."""
        import numpy as np
        return np.asarray(self.cache.arrays()[layer][1], np.float32)

    def _touch_state(self, f):
        mamba = set(self.spec.state_kinds[0].layers)
        self.cache.set_arrays([
            (layer[0], f(layer[1])) if i in mamba else layer
            for i, layer in enumerate(self.cache.arrays())])

    def _prefill(self, seqs, lengths, bucket, room):
        """``seqs[i][:lengths[i]]`` as the prompts of ONE dispatch of
        ``bucket`` into fresh rows (with ``room`` more rows reserved) ->
        (each prompt's last row's logits, the cache rows)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        c, n = self.cache, len(seqs)
        rows = [c.acquire(list(s[:p]), p + room)[0]
                for s, p in zip(seqs, lengths)]
        ids = np.zeros((n, bucket), np.int32)
        for i, (s, p) in enumerate(zip(seqs, lengths)):
            ids[i, :p] = s[:p]
        lg, pools, _ = self._fn(("prefill", bucket, n))(
            self._params(), jnp.asarray(ids), jnp.zeros(n, jnp.int32),
            jnp.asarray(lengths, jnp.int32) - 1,
            jax.tree_util.tree_map(jnp.asarray, c.table_rows(rows, n)),
            c.arrays())
        c.set_arrays(pools)
        return lg, rows

    def run(self, seqs, n_prompt, bucket, steps, watch, greedy=True):
        """Prefill ``seqs[i][:n_prompt[i]]`` together in one dispatch of
        ``bucket``, then ``steps`` tokens (greedy, or ``seqs``' own) ->
        per request {tokens, logits [steps, vocab], layers [steps,
        layers + 1, h], state_last, state_end (layer ``watch``)}."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        c, n = self.cache, len(seqs)
        lg, rows = self._prefill(seqs, n_prompt, bucket, steps + 1)
        for r, p in zip(rows, n_prompt):
            c.commit_prefill(r, p)
        if self.zero_state:
            self._touch_state(jnp.zeros_like)
        if self.round_state:
            self._touch_state(lambda s: s.astype(jnp.bfloat16)
                              .astype(jnp.float32))
        state_last = self._state_of(watch)[rows]
        out = [{"tokens": list(s[:p]), "logits": [], "layers": []}
               for s, p in zip(seqs, n_prompt)]
        first = np.asarray(jnp.argmax(lg, axis=-1))
        for i in range(n):
            out[i]["logits"].append(np.asarray(lg[i]))
        step = self._fn(("decode", self.slots))
        for k in range(steps):
            tok = np.zeros(self.slots, np.int32)
            for i, r in enumerate(rows):
                if greedy:
                    nxt = int(first[i]) if k == 0 else \
                        int(np.argmax(out[i]["logits"][-1]))
                else:
                    nxt = int(seqs[i][n_prompt[i] + k])
                out[i]["tokens"].append(nxt)
                tok[r] = nxt
            lg, pools, layers = step(
                self._params(), jnp.asarray(tok[:, None]),
                jnp.asarray(c.lengths.copy()), None,
                jax.tree_util.tree_map(jnp.asarray, c.tables_arg()),
                c.arrays())
            c.set_arrays(pools)
            if self.round_state:
                self._touch_state(lambda s: s.astype(jnp.bfloat16)
                                  .astype(jnp.float32))
            lg, layers = np.asarray(lg), np.asarray(layers)
            for i, r in enumerate(rows):
                c.advance(r, 1)
                out[i]["logits"].append(lg[r])
                out[i]["layers"].append(layers[r])
        state_end = self._state_of(watch)[rows]
        for r in rows:
            c.release_row(r)
        # the same tokens as ONE prompt each, through the program's prefill
        whole = [p + steps for p in n_prompt]
        again = min(b for b in self.cfg["engine"]["buckets"]
                    if b >= max(whole))
        _, rows = self._prefill([o["tokens"] for o in out], whole, again, 1)
        replayed = self._state_of(watch)[rows]
        for i, r in enumerate(rows):
            out[i]["state_replayed"] = replayed[i]
            out[i]["state_last"], out[i]["state_end"] = \
                state_last[i], state_end[i]
            # logits[k] scores position p - 1 + k; the last one scores a
            # token that was never fed
            out[i]["logits"] = np.stack(out[i]["logits"][:-1]) \
                if steps else np.zeros((0, lg.shape[-1]))
            out[i]["layers"] = np.stack(out[i]["layers"])
            c.release_row(r)
        return out


def reference_of(family, params, cfg, seq, p, steps, watch):
    """The reference on one sequence -> (logits of positions p - 1 .. p +
    steps - 2, every layer's output and the embedded input at positions
    p .. p + steps - 1 [steps, layers + 1, h], the watched layer's state at
    rows p - 1 and p + steps - 1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pad = -(-len(seq) // BLOCK) * BLOCK
    ids = np.zeros((1, pad), np.int32)
    ids[0, :len(seq)] = seq
    pre = f"model.layers.{watch}.mamba."

    @jax.jit
    def run(params, ids):
        out = []
        for at in (p - 1, p + steps - 1):
            states, keep = [], {"at": at}
            logits = family.forward(params, ids, cfg, collect=states,
                                    keep=keep)[0]
            out.append(keep[pre])
        emb = jnp.asarray(params["model.embed.weight"][ids[0]], jnp.float32)
        layers = jnp.stack([emb] + [s[0] for s in states], axis=1)
        return (jax.lax.dynamic_slice_in_dim(logits, p - 1, steps, 0),
                jax.lax.dynamic_slice_in_dim(layers, p, steps, 0),
                out[0], out[1])
    lg, layers, s_last, s_end = run(params, jnp.asarray(ids))
    # the reference's state is [d, N]; the program's [N, d]
    return (np.asarray(lg), np.asarray(layers), np.asarray(s_last).T,
            np.asarray(s_end).T)


def compare(got, refs):
    """``got`` a run's requests, ``refs`` the reference's of each -> the
    readings (the worst over the requests)."""
    import numpy as np
    out = {k: 0.0 for k in TOLERANCE}
    ratios = []
    for g, (lg, layers, s_last, s_end) in zip(got, refs):
        n = len(g["logits"])
        emitted = np.argmax(g["logits"], axis=-1)
        d = lg[:n].max(-1) - lg[np.arange(n), emitted]
        out["deficit_max"] = max(out["deficit_max"], float(d.max()))
        out["logit_max"] = max(out["logit_max"],
                               float(np.abs(g["logits"] - lg[:n]).max()))
        inc_p = g["layers"][:, 1:] - g["layers"][:, :-1]
        inc_r = layers[:, 1:] - layers[:, :-1]
        ratios.append(np.linalg.norm(inc_p - inc_r, axis=-1)
                      / np.linalg.norm(inc_r, axis=-1))    # [steps, layers]
        for key, mine, ref in (("state_last", g["state_last"], s_last),
                               ("state_end", g["state_end"], s_end),
                               ("state_replay", g["state_end"],
                                g["state_replayed"])):
            out[key] = max(out[key], float(
                np.linalg.norm(mine - ref) / np.linalg.norm(ref)))
    ratio = np.concatenate(ratios)
    out["inc_median"] = float(np.median(ratio))
    out["inc_worst_layer"] = float(np.median(ratio, axis=0).max())
    return out


def inject(runner, fault, layer):
    """Break the program in one place; -> a function that repairs it."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    if fault == "zeroed":
        runner.zero_state = True
        return lambda: setattr(runner, "zero_state", False)
    if fault == "bf16_state":
        runner.round_state = True
        return lambda: setattr(runner, "round_state", False)
    if fault == "skip":
        p = runner.model.model.layers[layer].mamba.D
        was = p.value
        p.value = jnp.zeros_like(was)
        return lambda: setattr(p, "value", was)
    if fault == "pads":
        real = ssm_ops.selective_scan
        ssm_ops.selective_scan = lambda x, dt, a, b, c, d, z, last: real(
            x, dt, a, b, c, d, z, jnp.full_like(last, x.shape[1] - 1))
        runner._fns.clear()

        def repair():
            ssm_ops.selective_scan = real
            runner._fns.clear()
        return repair
    if fault == "tail":
        real = ssm_ops.conv_tail
        ssm_ops.conv_tail = lambda xp, last, k: real(xp, last - 1, k)
        runner._fns.clear()

        def repair():
            ssm_ops.conv_tail = real
            runner._fns.clear()
        return repair
    raise ValueError(fault)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="jamba2-3b")
    ap.add_argument("--short", default="301,498")
    ap.add_argument("--short-bucket", type=int, default=512)
    ap.add_argument("--long", type=int, default=3003)
    ap.add_argument("--long-bucket", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seed", type=int, default=2147484201)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, weights
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("compare_jamba needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family = families.load(cfg)
    with weights.recording() as specs:
        model = family.serving_model(cfg)
    weights.fill(model, specs, args.seed)
    model.eval()
    runner = Runner(model, cfg)
    # the first Mamba layer: its input is the embedding alone, which the
    # prefill and the decode step round alike, so `state_replay` reads how
    # the state was carried and nothing of the stream's bfloat16 noise (at
    # layer 6 the two paths' streams already differ by the MLPs' rounding
    # and a sound program read 0.0054-0.0059 there, a bfloat16 state 0.0089)
    watch = runner.spec.state_kinds[0].layers[0]
    rng = np.random.default_rng([args.seed, 7])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def note(rec):
        rec.update(config=cfg["name"], seed=args.seed, steps=args.steps,
                   watched_layer=watch, device=dev.device_kind,
                   tolerance=TOLERANCE)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    def params_now():
        return {n: p.value for n, p in model.named_parameters()}

    cases = {"short": ([int(x) for x in args.short.split(",")],
                       args.short_bucket)}
    if args.long:
        cases["long"] = ([args.long], args.long_bucket)
    ok, kept = True, {}
    for name, (n_prompt, bucket) in cases.items():
        t = time.time()
        prompts = [rng.integers(1, cfg["vocab_size"], size=p).tolist()
                   for p in n_prompt]
        clean = runner.run([p + [0] * args.steps for p in prompts],
                           n_prompt, bucket, args.steps, watch)
        seqs = [g["tokens"] for g in clean]
        refs = [reference_of(family, params_now(), cfg, s, p, args.steps,
                             watch) for s, p in zip(seqs, n_prompt)]
        kept[name] = (seqs, refs, n_prompt, bucket)
        rec = compare(clean, refs)
        rec["pass"] = all(rec[k] <= TOLERANCE[k] for k in TOLERANCE)
        ok = ok and rec["pass"]
        note(dict(rec, run="clean", case=name, prompts=n_prompt,
                  bucket=bucket, seconds=round(time.time() - t, 1)))
    seqs, refs, n_prompt, bucket = kept["short"]
    for fault in [f for f in args.faults.split(",") if f]:
        t = time.time()
        repair = inject(runner, fault, watch)
        try:
            got = runner.run(seqs, n_prompt, bucket, args.steps, watch,
                             greedy=False)
        finally:
            repair()
        rec = compare(got, refs)
        rec["pass"] = all(rec[k] <= TOLERANCE[k] for k in TOLERANCE)
        rec["caught_by"] = [k for k in TOLERANCE if rec[k] > TOLERANCE[k]]
        ok = ok and not rec["pass"]
        note(dict(rec, run=fault, case="short", prompts=n_prompt,
                  bucket=bucket, seconds=round(time.time() - t, 1)))
    print("compare_jamba:", "as expected" if ok else "NOT as expected",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
