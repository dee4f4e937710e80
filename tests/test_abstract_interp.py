"""Static shape/dtype inference + recompile prediction
(paddle_tpu/analysis/).

Three legs:

- the abstract interpreter: exact shapes for the book programs and the
  recorded GPT benchmark graph (zero unknown-op fallbacks — the
  eval_shape-over-lowering fallback plus the explicit control-flow /
  collective / PS rules must cover everything those graphs use), the
  mis-shaped-program negative fixture (a structured pre-trace ERROR
  naming the op and the mismatched dims), grad mirroring, dynamic-batch
  probing, and the loop-carry / branch-mismatch contracts;
- verifier integration: `shapes.infer` is a registered check, gated
  behind FLAGS_check_shapes unless explicitly selected;
- the recompile predictor: executor cache-key mirror and the serving
  bucket/prefix model, cross-checked against the live compile tracker.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import (AbstractVar, ExecutorCompilePredictor,
                                 interpret_program,
                                 predict_serving_compiles)
from paddle_tpu.framework import (Executor, Program, Scope, program_guard,
                                  unique_name)


def _errors(r):
    return [d for d in r.diagnostics if d.severity == "error"]


def _build(fn):
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        out = fn()
    return main, startup, out


# ---------------------------------------------------------------------
# coverage: the acceptance graphs infer with zero unknown ops
# ---------------------------------------------------------------------


def test_book_programs_infer_all_ops():
    from tools.book_programs import build_all
    names = []
    for name, main, startup, fetches in build_all():
        names.append(name)
        r = interpret_program(main)
        assert not r.unknown_ops, f"{name}: {r.unknown_ops}"
        assert not _errors(r), (
            f"{name}: " + "\n".join(str(d) for d in _errors(r)))
        n_ops = sum(len(b.ops) for b in main.blocks)
        assert r.ops_inferred == n_ops, (name, r.ops_inferred, n_ops)
    assert len(names) == 8


def test_gpt_recorded_graph_infers_all_ops():
    from paddle_tpu.dygraph.tape import record_program
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    pt.seed(0)
    cfg = GPTConfig(vocab_size=97, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    prog = Program()
    with record_program(prog):
        out = m(pt.to_tensor(np.ones((2, 8), dtype=np.int64)))
    r = interpret_program(prog)
    assert not r.unknown_ops and not _errors(r)
    n_ops = sum(len(b.ops) for b in prog.blocks)
    assert r.ops_inferred == n_ops
    av = r.shape_of(out.name)
    assert (av.shape, av.dtype) == ((2, 8, 97), "float32")


# ---------------------------------------------------------------------
# the negative fixture: mis-shaped program -> located pre-trace ERROR
# ---------------------------------------------------------------------


def test_mis_shaped_matmul_reports_op_and_dims():
    def build():
        a = layers.data("a", [4])          # [-1, 4]
        w = layers.create_parameter([8, 5], "float32")
        return layers.matmul(a, w)         # 4 vs 8: contract violation

    main, _, _ = _build(build)
    r = interpret_program(main)
    errs = _errors(r)
    assert len(errs) == 1
    d = errs[0]
    assert d.check == "shapes.infer"
    assert d.severity == "error"
    assert (d.block_idx, d.op_idx) == (0, 0)
    # names the op and both mismatched operand shapes
    assert "matmul" in d.message
    assert "4" in d.message and "8,5" in d.message.replace(" ", "")


def test_elementwise_shape_mismatch_caught():
    def build():
        a = layers.data("a", [4])
        b = layers.data("b", [6])
        return layers.elementwise_add(a, b)

    main, _, _ = _build(build)
    errs = _errors(interpret_program(main))
    assert len(errs) == 1 and "elementwise_add" in errs[0].message


# ---------------------------------------------------------------------
# transfer-function details
# ---------------------------------------------------------------------


def test_grad_ops_mirror_forward_shapes():
    from paddle_tpu.optimizer import SGDOptimizer

    def build():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        SGDOptimizer(0.1).minimize(loss)
        return loss

    main, _, loss = _build(build)
    r = interpret_program(main)
    assert not r.unknown_ops and not _errors(r)
    # every @GRAD var matches its forward var's inferred shape
    checked = 0
    for (bidx, name), av in r.var_shapes.items():
        if "@GRAD" not in name or not av.known:
            continue
        base = name.split("@GRAD", 1)[0]
        fwd = r.var_shapes.get((bidx, base))
        if fwd is not None and fwd.known:
            assert av.shape == fwd.shape, (name, av, fwd)
            checked += 1
    assert checked >= 3


def test_dynamic_batch_dim_reported_as_minus_one():
    def build():
        x = layers.data("x", [4])          # [-1, 4]
        return layers.fc(x, 3)

    main, _, out = _build(build)
    r = interpret_program(main)
    av = r.shape_of(out.name)
    assert av.shape == (-1, 3), av         # batch joins to dynamic
    assert av.dtype == "float32"


def test_feed_shapes_override_declared_batch():
    def build():
        x = layers.data("x", [4])
        return layers.fc(x, 3)

    main, _, out = _build(build)
    r = interpret_program(main, feeds={"x": ((16, 4), "float32")})
    assert r.shape_of(out.name).shape == (16, 3)


def test_while_loop_infers_and_flags_carry_drift():
    def build():
        i = layers.fill_constant([1], "int32", 0)
        ten = layers.fill_constant([1], "int32", 10)
        out = layers.while_loop(
            lambda i: layers.less_than(i, ten),
            lambda i: [layers.elementwise_add(
                i, layers.fill_constant([1], "int32", 1))],
            [i])
        return out[0] if isinstance(out, (list, tuple)) else out

    main, _, out = _build(build)
    r = interpret_program(main)
    assert not _errors(r) and not r.unknown_ops
    av = r.shape_of(out.name)
    assert (av.shape, av.dtype) == ((1,), "int32")

    # corrupt the body: the carry doubles in size every iteration
    wop = next(op for b in main.blocks for op in b.ops
               if op.type == "while")
    sub = main.blocks[int(wop.attrs["sub_block"])]
    cname = wop.attrs["carry_names"][0]
    sub.append_op("concat", {"X": [cname, cname]}, {"Out": cname},
                  {"axis": 0})
    r2 = interpret_program(main)
    bad = [d for d in r2.diagnostics if d.check == "shapes.loop-carry"]
    assert len(bad) == 1
    assert bad[0].severity == "error" and cname in bad[0].message
    assert "int32[1]" in bad[0].message and "int32[2]" in bad[0].message


def test_cond_branch_mismatch_flagged():
    def build():
        x = layers.data("x", [4])
        pred = layers.less_than(
            layers.mean(x), layers.fill_constant([1], "float32", 0.0))
        return layers.cond(pred,
                           lambda: layers.elementwise_add(x, x),
                           lambda: layers.elementwise_mul(x, x))

    main, _, out = _build(build)
    r = interpret_program(main)
    assert not _errors(r)
    assert r.shape_of(out.name).shape == (-1, 4)

    # corrupt the false branch: its output gains a dim-0 concat
    cop = next(op for b in main.blocks for op in b.ops
               if op.type == "cond")
    sub_f = main.blocks[int(cop.attrs["sub_block_f"])]
    oname = cop.attrs["out_names"][0]
    sub_f.append_op("concat", {"X": [oname, oname]}, {"Out": oname},
                    {"axis": 0})
    r2 = interpret_program(main)
    bad = [d for d in r2.diagnostics
           if d.check == "shapes.branch-mismatch"]
    assert len(bad) == 1 and oname in bad[0].message


def test_collective_rules_scale_by_nranks():
    prog = Program()
    blk = prog.global_block()
    blk.create_var("x", is_data=True, shape=[8, 3], dtype="float32")
    for name, op_type, nranks in [("g", "c_allgather", 4),
                                  ("s", "c_reducescatter", 4),
                                  ("r", "c_allreduce_sum", 4)]:
        blk.create_var(name)
        blk.append_op(op_type, {"X": "x"}, {"Out": name},
                      {"nranks": nranks})
    r = interpret_program(prog)
    assert not _errors(r)
    assert r.shape_of("g").shape == (32, 3)   # gather: dim0 * nranks
    assert r.shape_of("s").shape == (2, 3)    # scatter: dim0 / nranks
    assert r.shape_of("r").shape == (8, 3)    # allreduce: identity

    blk.create_var("bad")
    blk.append_op("c_reducescatter", {"X": "x"}, {"Out": "bad"},
                  {"nranks": 3})              # 8 % 3 != 0
    r2 = interpret_program(prog)
    errs = _errors(r2)
    assert len(errs) == 1 and "divisible" in errs[0].message


def test_ps_rules_never_touch_host_state():
    from paddle_tpu.distributed.ps.sparse_table import REGISTRY
    prog = Program()
    blk = prog.global_block()
    blk.create_var("ids", is_data=True, shape=[4, 1], dtype="int64")
    blk.create_var("emb")
    blk.append_op("distributed_lookup_table", {"Ids": "ids"},
                  {"Out": "emb"},
                  {"table_name": "interp_test_table", "value_dim": 16})
    blk.create_var("rx")
    blk.append_op("recv", {}, {"Out": "rx"},
                  {"recv_varnames": ["v"], "shape": [3, 5]})
    r = interpret_program(prog)
    assert not _errors(r) and not r.unknown_ops
    assert r.shape_of("emb").shape == (4, 1, 16)
    assert r.shape_of("rx") == AbstractVar((3, 5), "float32")
    # the real lowering creates the table at trace time; the static
    # rule must not (that is why PS ops are never eval_shape'd)
    assert REGISTRY.get("interp_test_table") is None


def test_unknown_op_is_warning_not_error():
    prog = Program()
    blk = prog.global_block()
    blk.create_var("x", is_data=True, shape=[2], dtype="float32")
    blk.create_var("y")
    blk.append_op("totally_unregistered_op", {"X": "x"}, {"Out": "y"})
    r = interpret_program(prog)
    assert [u[0] for u in r.unknown_ops] == ["totally_unregistered_op"]
    assert not _errors(r)
    assert r.shape_of("y") == AbstractVar()   # unknown propagates


# ---------------------------------------------------------------------
# verifier / flag integration
# ---------------------------------------------------------------------


def test_shapes_check_gated_behind_flag():
    def build():
        a = layers.data("a", [4])
        w = layers.create_parameter([8, 5], "float32")
        return layers.matmul(a, w)

    main, _, _ = _build(build)
    # default: registered but inert
    assert "shapes.infer" in __import__(
        "paddle_tpu.framework.analysis", fromlist=["ANALYSIS_CHECKS"]
    ).ANALYSIS_CHECKS
    assert main.verify().ok()
    # explicit selection runs it without the flag
    r = main.verify(checks=["shapes.infer"])
    assert not r.ok() and r.errors[0].check == "shapes.infer"
    # flag turns it on inside the default suite
    pt.set_flags({"check_shapes": True})
    try:
        assert not main.verify().ok()
    finally:
        pt.set_flags({"check_shapes": False})


def test_executor_first_compile_catches_mis_shape_under_flag():
    def build():
        a = layers.data("a", [4])
        w = layers.create_parameter([8, 5], "float32")
        return layers.matmul(a, w)

    main, startup, out = _build(build)
    from paddle_tpu.framework import ProgramVerifyError
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    pt.set_flags({"check_shapes": True})
    try:
        with pytest.raises(ProgramVerifyError) as ei:
            exe.run(main,
                    feed={"a": np.zeros((2, 4), np.float32)},
                    fetch_list=[out.name], scope=scope)
    finally:
        pt.set_flags({"check_shapes": False})
    assert "shapes.infer" in str(ei.value)


# ---------------------------------------------------------------------
# recompile prediction
# ---------------------------------------------------------------------


def test_executor_predictor_matches_observed_compiles():
    from paddle_tpu import observability

    def build():
        x = layers.data("x", [4])
        return layers.fc(x, 2)

    main, startup, out = _build(build)
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)

    def count():
        return observability.compiles().get(
            "executor_step", {}).get("count", 0)

    pred = ExecutorCompilePredictor()
    feeds = [np.zeros((2, 4), np.float32),
             np.zeros((2, 4), np.float32),   # same signature: cached
             np.zeros((6, 4), np.float32)]   # new batch: retrace
    for arr in feeds:
        before = count()
        predicted = pred.would_compile(main, {"x": arr}, [out.name],
                                       scope)
        exe.run(main, feed={"x": arr}, fetch_list=[out.name],
                scope=scope)
        assert (count() - before == 1) == predicted, arr.shape
    assert pred.predicted_counts() == {"executor_step": 2}


def test_serving_predictor_buckets_and_decode():
    # two prompts in one round, different buckets; one-token request
    # (max_new_tokens=1) alone must not predict a decode compile
    p = predict_serving_compiles(
        [[(list(range(1, 6)), 1), (list(range(1, 13)), 1)]],
        buckets=[8, 16], max_len=32)
    assert p == {"serving_prefill_paged{bucket=8}": 1,
                 "serving_prefill_paged{bucket=16}": 1}
    p2 = predict_serving_compiles(
        [[(list(range(1, 6)), 4)]], buckets=[8], max_len=32)
    assert p2 == {"serving_prefill_paged{bucket=8}": 1,
                  "decode_step_paged": 1}


def test_serving_predictor_has_one_kv_path():
    # the engine has one KV manager, so the predictor has no switch:
    # the site names are the paged ones whatever else is asked for
    with pytest.raises(TypeError):
        predict_serving_compiles(
            [[(list(range(1, 6)), 4)]], buckets=[8], max_len=32,
            **{"paged": False})
    p = predict_serving_compiles(
        [[(list(range(1, 6)), 4)]], buckets=[8], max_len=32,
        prefix_cache=False, spec_tokens=2)
    assert p == {"serving_prefill_paged{bucket=8}": 1,
                 "verify_step_paged{k=2}": 1}


def test_serving_predictor_prefix_rounds():
    prompt = list(range(1, 12))  # 11 tokens, block_size 4 -> 2 blocks
    # same round: nothing published yet -> both hit the len-11 bucket
    one_round = predict_serving_compiles(
        [[(prompt, 4), (prompt, 4)]],
        buckets=[4, 16], max_len=32, block_size=4)
    assert one_round == {"serving_prefill_paged{bucket=16}": 1,
                         "decode_step_paged": 1}
    # across rounds: 8 shared tokens -> suffix 3 -> the small bucket
    two_rounds = predict_serving_compiles(
        [[(prompt, 4)], [(prompt, 4)]],
        buckets=[4, 16], max_len=32, block_size=4)
    assert two_rounds == {"serving_prefill_paged{bucket=16}": 1,
                          "serving_prefill_paged{bucket=4}": 1,
                          "decode_step_paged": 1}
    # prefix cache off: round structure stops mattering
    no_cache = predict_serving_compiles(
        [[(prompt, 4)], [(prompt, 4)]],
        buckets=[4, 16], max_len=32, block_size=4, prefix_cache=False)
    assert no_cache == {"serving_prefill_paged{bucket=16}": 1,
                        "decode_step_paged": 1}


def test_serving_predictor_whole_prompt_shared_recomputes_last_token():
    prompt = list(range(1, 9))   # exactly 2 full blocks of 4
    p = predict_serving_compiles(
        [[(prompt, 4)], [(prompt, 4)]],
        buckets=[1, 8], max_len=32, block_size=4)
    # shared = min(8, len-1) = 7 -> suffix 1: the engine always
    # recomputes the last prompt token to emit the first output
    assert p == {"serving_prefill_paged{bucket=8}": 1,
                 "serving_prefill_paged{bucket=1}": 1,
                 "decode_step_paged": 1}


def test_serving_predictor_spec_tokens_take_verify_path():
    p = predict_serving_compiles(
        [[(list(range(1, 6)), 4)]], buckets=[8], max_len=32,
        block_size=4, spec_tokens=3)
    assert p == {"serving_prefill_paged{bucket=8}": 1,
                 "verify_step_paged{k=3}": 1}


def test_serving_predictor_matches_live_engine():
    """In-process predicted == observed."""
    from paddle_tpu import observability
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine
    pt.seed(11)
    cfg = GPTConfig(vocab_size=53, max_position_embeddings=64,
                    hidden_size=16, num_layers=1, num_heads=2,
                    ffn_hidden_size=32)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, max_slots=2, max_len=24, buckets=[8],
                        block_size=4, spec_tokens=0)
    before = {s: c["count"] for s, c in observability.compiles().items()}
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 53, size=n).tolist() for n in (3, 6)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    after = {s: c["count"] for s, c in observability.compiles().items()}
    observed = {}
    for site, n in after.items():
        if not site.startswith(("serving_", "decode_", "verify_")):
            continue
        delta = n - before.get(site, 0)
        if delta:
            observed[site] = delta
    predicted = predict_serving_compiles(
        [[(p, 3) for p in prompts]], buckets=[8], max_len=24,
        block_size=4)
    assert predicted == observed, (predicted, observed)
