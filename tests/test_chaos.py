"""Chaos suite: deterministic fault specs driving real recovery paths.

The acceptance demo lives here: a small training run under a fixed
fault spec (PS connection drops + injected-NaN batches + a corrupted
checkpoint) that completes via retry/skip/rollback and lands within
tolerance of the fault-free run — with every injection and every
recovery asserted through its monitor counter, so CI proves the
resilience plane observes what it survives.

All specs are seeded; a failure here replays exactly with
``FLAGS_fault_spec=<spec> FLAGS_fault_seed=<seed>``.
"""

import os
import socket
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, monitor
from paddle_tpu.framework import (Executor, Program, Scope,
                                  program_guard, unique_name)
from paddle_tpu.incubate.checkpoint import (CheckpointSaver,
                                            train_epoch_range)
from paddle_tpu.optimizer import SGDOptimizer
from paddle_tpu.resilience import (TrainGuardian, fault_scope,
                                   fault_point)
from paddle_tpu.resilience import injector as injector_mod

pytestmark = pytest.mark.chaos

_RESTORE_FLAGS = ("fault_spec", "fault_seed", "retry_max_attempts",
                  "retry_base_delay", "retry_max_delay",
                  "retry_deadline", "guardian_max_skip")


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    saved = pt.get_flags(list(_RESTORE_FLAGS))
    monitor.reset()
    injector_mod.reset()
    pt.set_flags({"retry_base_delay": 0.005, "retry_max_delay": 0.05,
                  "retry_max_attempts": 8})
    yield
    pt.set_flags(saved)
    injector_mod.reset()
    monitor.reset()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- model plumbing shared by the demo ----------------------------------

def _build_train():
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 5
    with program_guard(main, startup), unique_name.guard():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


def _build_eval():
    """Same graph minus the optimizer, SAME parameter names (fresh
    unique_name.guard), so it reads the training scope's params
    without mutating them."""
    evalp = Program()
    evalp.random_seed = 5
    with program_guard(evalp, Program()), unique_name.guard():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
    return evalp, loss


_W_TRUE = np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32)


def _batch(i):
    rng = np.random.RandomState(i)
    x = rng.randn(16, 4).astype(np.float32)
    return {"x": x, "y": (x @ _W_TRUE).astype(np.float32)}


def _eval_loss(scope):
    evalp, eloss = _build_eval()
    out = Executor().run(evalp, feed=_batch(1000),
                         fetch_list=[eloss], scope=scope)
    return float(out[0])


STEPS = 60

# the acceptance spec: PS drops throughout, a lone NaN batch at step
# 25, a NaN burst at 30/31 that trips the rollback, and a corrupted
# third checkpoint (the latest one at rollback time, forcing the
# validated load to fall back a generation)
DEMO_SPEC = ("ps.rpc.call:drop@0.12;"
             "exec.step:nan@25;exec.step:nan@30;exec.step:nan@31;"
             "ckpt.save:corrupt@2")
DEMO_SEED = 11


def _run_training(chaos: bool, tmp_path, endpoints=None):
    main, startup, loss = _build_train()
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    saver = CheckpointSaver(
        str(tmp_path), "chaos" if chaos else "plain", max_num=3)
    guard = TrainGuardian(exe, main, scope, saver=saver, max_skip=1,
                          checkpoint_every=8)

    client = None
    if endpoints is not None:
        from paddle_tpu.distributed.ps.rpc import PSClient
        client = PSClient(endpoints)
        client.create_table("emb", 4, init="zeros")

    def loop():
        for i in range(STEPS):
            if client is not None:
                # the PS leg of a step: liveness + a pull, both riding
                # the retry layer (drops must be invisible here)
                client.heartbeat(0)
                r = client.pull("emb", np.arange(8) + i, value_dim=4)
                assert r.shape == (8, 4)
            guard.step(_batch(i), fetch_list=[loss])

    if chaos:
        with fault_scope(DEMO_SPEC, seed=DEMO_SEED):
            loop()
    else:
        loop()
    if client is not None:
        client.close()
    return guard, _eval_loss(scope)


def test_chaos_demo_end_to_end(tmp_path):
    """The ISSUE acceptance run: drops + NaNs + a corrupt checkpoint,
    survived via retry + skip + rollback, loss parity with fault-free."""
    port = _free_port()
    from paddle_tpu.distributed.ps.rpc import PSServer
    srv = PSServer(f"127.0.0.1:{port}").start()
    try:
        _, clean_loss = _run_training(False, tmp_path,
                                      [f"127.0.0.1:{port}"])
        monitor.reset()
        guard, chaos_loss = _run_training(True, tmp_path,
                                          [f"127.0.0.1:{port}"])
    finally:
        srv.stop()

    # survival: the run completed, skipping 3 batches, one rollback
    assert guard.skipped == 3
    assert guard.rollbacks == 1

    # ...and recovery, not luck: every site fired and every recovery
    # path left its counter
    stats = monitor.stats()
    assert stats.get("STAT_fault_ps.rpc.call", 0) > 0
    assert stats.get("STAT_retry_ps.rpc.call", 0) > 0
    assert stats.get("STAT_fault_exec.step", 0) == 3
    assert stats.get("STAT_guardian_skipped", 0) == 3
    assert stats.get("STAT_guardian_rollbacks", 0) == 1
    assert stats.get("STAT_fault_ckpt.save", 0) == 1
    assert stats.get("STAT_ckpt_load_fallback", 0) >= 1, \
        "rollback must have walked past the corrupted checkpoint"
    assert stats.get("STAT_guardian_checkpoints", 0) >= 3

    # loss parity: the chaos run converges to the same place
    assert clean_loss < 0.05
    assert chaos_loss < 0.05
    assert abs(chaos_loss - clean_loss) < 0.05


def test_ps_ops_survive_connection_drops():
    from paddle_tpu.distributed.ps.rpc import PSClient, PSServer
    port = _free_port()
    srv = PSServer(f"127.0.0.1:{port}").start()
    c = PSClient([f"127.0.0.1:{port}"])
    try:
        c.create_table("emb", 4, init="zeros")
        with fault_scope("ps.rpc.call:drop@0.15", seed=3):
            for i in range(15):
                r = c.pull("emb", np.arange(10), value_dim=4)
                assert r.shape == (10, 4)
                c.heartbeat(0)
                assert c.barrier(expected=1)
            assert c.size("emb") == 10
        assert monitor.stat_get("STAT_fault_ps.rpc.call") > 0
        assert monitor.stat_get("STAT_retry_ps.rpc.call") > 0
        status = c.worker_status()
        assert status["0"]["alive"]
    finally:
        c.shutdown_servers()


def test_guardian_detects_dead_ps_worker():
    from paddle_tpu.distributed.ps.rpc import PSClient, PSServer
    port = _free_port()
    srv = PSServer(f"127.0.0.1:{port}").start()
    c = PSClient([f"127.0.0.1:{port}"])
    try:
        c.create_table("emb", 4)
        c.heartbeat(0)
        guard = TrainGuardian(Executor(), None, Scope(), ps_client=c,
                              expected_workers=[0, 1])
        # worker 1 never heartbeats; worker 0 goes stale against a
        # tiny liveness window
        time.sleep(0.05)
        dead = guard.dead_workers(timeout=0.01)
        assert set(dead) == {0, 1}
        assert monitor.stat_get("STAT_guardian_dead_workers") == 2
        # generous window: only the silent worker is dead
        monitor.reset()
        dead = guard.dead_workers(timeout=30.0)
        assert set(dead) == {1}
    finally:
        c.shutdown_servers()


def test_allreduce_injected_drop_retried():
    from paddle_tpu.distributed.collective import all_reduce
    t = pt.to_tensor(np.ones(4, np.float32))
    with fault_scope("collective.allreduce:drop@0"):
        out = all_reduce(t)
    np.testing.assert_allclose(np.asarray(out.value), 1.0)
    assert monitor.stat_get("STAT_fault_collective.allreduce") == 1
    assert monitor.stat_get("STAT_retry_collective.allreduce") == 1


def test_train_epoch_range_resumes_after_injected_preemption(tmp_path):
    """In-process preemption: `preempt` unwinds like SIGTERM-SystemExit
    mid-epoch; the restarted range skips completed epochs, restores
    state, and finishes with the uninterrupted result."""
    from paddle_tpu.distributed.fleet.elastic import resume_epoch

    def run(spec):
        scope = Scope()
        scope.set_var("acc", np.float64(0.0))
        done = []

        def epochs():
            for epoch in train_epoch_range(5, scope, name="job",
                                           root=str(tmp_path)):
                fault_point("train.epoch")  # injector-driven kill site
                scope.set_var(
                    "acc",
                    np.float64(np.asarray(scope.find_var("acc"))
                               + epoch))
                done.append(epoch)

        if spec:
            with fault_scope(spec):
                epochs()
        else:
            epochs()
        return done, float(np.asarray(scope.find_var("acc")))

    with pytest.raises(SystemExit):
        run("train.epoch:preempt@2")
    assert resume_epoch(str(tmp_path), name="job") == 2
    done, acc = run("")
    assert done == [2, 3, 4], "completed epochs must be skipped"
    assert acc == 0.0 + 1.0 + 2.0 + 3.0 + 4.0


# -- elastic pod restart through the injector ---------------------------

def _elastic_chaos_worker(ckpt_root, total_epochs):
    """Counter-training worker; generation 0's rank 0 is hard-killed by
    the injector (`kill` == os._exit, no unwinding — a real preemption)
    mid-epoch-2, before that epoch's checkpoint lands."""
    import os

    import numpy as np

    from paddle_tpu import set_flags
    from paddle_tpu.distributed.fleet.elastic import resume_epoch
    from paddle_tpu.incubate.checkpoint import CheckpointSaver
    from paddle_tpu.resilience.injector import fault_point

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    gen = int(os.environ["PADDLE_ELASTIC_GENERATION"])
    if gen == 0 and rank == 0:
        set_flags({"fault_spec": "elastic.epoch:kill@2"})
    saver = CheckpointSaver(ckpt_root, name="elastic_ckpt")
    start = resume_epoch(ckpt_root, name="elastic_ckpt")
    state, _ = saver.load()
    acc = float(state["acc"]) if state is not None else 0.0
    for epoch in range(start, int(total_epochs)):
        acc += epoch
        fault_point("elastic.epoch")   # gen0/rank0 dies here at epoch 2
        if rank == 0:
            saver.save({"acc": np.float64(acc)}, epoch,
                       meta={"epoch": epoch, "generation": gen})
            with open(os.path.join(ckpt_root, "progress.log"), "a") as f:
                f.write(f"gen{gen} epoch{epoch} acc{acc}\n")


def test_elastic_restart_after_injector_kill(tmp_path):
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)
    em = ElasticManager(_elastic_chaos_worker, args=(str(tmp_path), 5),
                        nprocs=2, max_restarts=2, started_port=6390,
                        monitor_interval=0.1)
    status = em.run()
    assert status == ElasticStatus.COMPLETED
    assert em.restarts == 1 and em.generation == 1
    assert monitor.stat_get("STAT_elastic_restarts") == 1
    log = (tmp_path / "progress.log").read_text().splitlines()
    gens = [line.split()[0] for line in log]
    epochs = [int(line.split()[1][5:]) for line in log]
    # gen 0 landed epochs 0,1 then was killed mid-2; gen 1 resumed AT 2
    assert gens == ["gen0", "gen0", "gen1", "gen1", "gen1"]
    assert epochs == [0, 1, 2, 3, 4]
    assert log[-1].endswith("acc10.0"), \
        "state must carry across the restart (0+1+2+3+4)"


# -- serving plane under injected faults --------------------------------

@pytest.fixture(scope="module")
def _serving_model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    pt.seed(11)
    cfg = GPTConfig(vocab_size=61, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _serving_engine(model, **kw):
    from paddle_tpu.serving import ServingEngine
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("buckets", [8])
    return ServingEngine(model, **kw)


def test_serving_step_drop_is_retried(_serving_model):
    """A transient drop inside a prefill/decode attempt retries through
    RetryPolicy; every request still completes with the exact fault-free
    tokens, and both the injection and the recovery are counted."""
    from paddle_tpu.models.generation import greedy_search
    prompts = [[1, 2, 3], [4, 5, 6, 7]]
    with fault_scope("serving.step:drop@1"):
        eng = _serving_engine(_serving_model)
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        assert [r.state for r in reqs] == ["done", "done"]
        assert monitor.stat_get("STAT_fault_serving.step") == 1
        assert monitor.stat_get("STAT_retry_serving.step") >= 1
        for p, r in zip(prompts, reqs):
            ref = greedy_search(_serving_model, np.asarray([p]),
                                max_new_tokens=4,
                                cache_len=eng.max_len)[0].tolist()
            assert r.output_ids == ref


def test_serving_step_skip_sheds_one_prefill(_serving_model):
    """`skip` during a prefill sheds exactly the request being admitted;
    everything behind it completes untouched."""
    with fault_scope("serving.step:skip@0"):
        eng = _serving_engine(_serving_model)
        reqs = [eng.submit([1, 2, 3], max_new_tokens=3),
                eng.submit([4, 5], max_new_tokens=3)]
        eng.run_until_idle()
        assert reqs[0].state == "shed" and reqs[0].error is not None
        assert reqs[1].state == "done" and len(reqs[1].tokens) == 3
        assert monitor.stat_get("STAT_fault_serving.step") == 1
        assert monitor.stat_get("STAT_serving_shed") == 1
        assert monitor.stat_get("STAT_serving_completed") == 1
        assert eng.cache.num_free == eng.max_slots  # no leaked slot


def test_serving_step_skip_decode_iteration(_serving_model):
    """`skip` during decode drops one iteration, not the requests: the
    next step redoes the decode and the output is still exact."""
    from paddle_tpu.models.generation import greedy_search
    with fault_scope("serving.step:skip@1"):  # call 0 = prefill
        eng = _serving_engine(_serving_model)
        req = eng.submit([7, 8, 9], max_new_tokens=4)
        eng.run_until_idle()
        assert req.state == "done"
        assert monitor.stat_get("STAT_fault_serving.step") == 1
        ref = greedy_search(_serving_model, np.asarray([[7, 8, 9]]),
                            max_new_tokens=4,
                            cache_len=eng.max_len)[0].tolist()
        assert req.output_ids == ref


def test_serving_retry_exhaustion_sheds_not_kills(_serving_model):
    """A persistent step fault sheds the affected requests but leaves
    the engine serving: the next fault-free submission completes."""
    pt.set_flags({"retry_max_attempts": 2})
    eng = _serving_engine(_serving_model)
    with fault_scope("serving.step:drop"):
        reqs = [eng.submit([1, 2], max_new_tokens=3),
                eng.submit([3, 4], max_new_tokens=3)]
        eng.run_until_idle()
        assert [r.state for r in reqs] == ["shed", "shed"]
        assert monitor.stat_get("STAT_serving_shed") == 2
        assert eng.cache.num_free == eng.max_slots
    req = eng.submit([5, 6], max_new_tokens=3)
    eng.run_until_idle()
    assert req.state == "done" and len(req.tokens) == 3


def test_serving_submit_fault_rejects_before_queue(_serving_model):
    """serving.submit faults reject at admission (backpressure), leaving
    queued and in-flight work untouched."""
    from paddle_tpu.resilience.injector import InjectedIOError
    eng = _serving_engine(_serving_model)
    ok = eng.submit([1, 2, 3], max_new_tokens=2)
    with fault_scope("serving.submit:error@0"):
        with pytest.raises(InjectedIOError):
            eng.submit([4, 5], max_new_tokens=2)
        assert monitor.stat_get("STAT_fault_serving.submit") == 1
        later = eng.submit([6, 7], max_new_tokens=2)  # call 1: clean
        eng.run_until_idle()
    assert ok.state == "done" and later.state == "done"


# -- paged KV allocator under injected faults ---------------------------

def test_serving_alloc_skip_sheds_request_not_engine(_serving_model):
    """An injected allocator failure (`skip`) sheds exactly the request
    whose acquisition failed; the one behind it completes, and no block
    leaks — after drain + prefix flush only the trash block holds a
    ref."""
    from paddle_tpu.models.generation import greedy_search
    with fault_scope("serving.alloc:skip@0"):
        eng = _serving_engine(_serving_model)
        reqs = [eng.submit([1, 2, 3], max_new_tokens=3),
                eng.submit([4, 5], max_new_tokens=3)]
        eng.run_until_idle()
        assert reqs[0].state == "shed" and reqs[0].error is not None
        assert reqs[1].state == "done" and len(reqs[1].tokens) == 3
        assert monitor.stat_get("STAT_fault_serving.alloc") == 1
        assert monitor.stat_get("STAT_serving_shed") == 1
        ref = greedy_search(_serving_model, np.asarray([[4, 5]]),
                            max_new_tokens=3,
                            cache_len=eng.max_len)[0].tolist()
        assert reqs[1].output_ids == ref
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1  # the trash block


def test_serving_alloc_drop_is_retried(_serving_model):
    """A transient allocator drop retries through RetryPolicy and the
    request still completes with the exact fault-free tokens."""
    from paddle_tpu.models.generation import greedy_search
    with fault_scope("serving.alloc:drop@0"):
        eng = _serving_engine(_serving_model)
        req = eng.submit([1, 2, 3, 4], max_new_tokens=4)
        eng.run_until_idle()
        assert req.state == "done"
        assert monitor.stat_get("STAT_fault_serving.alloc") == 1
        assert monitor.stat_get("STAT_retry_serving.alloc") >= 1
        ref = greedy_search(_serving_model, np.asarray([[1, 2, 3, 4]]),
                            max_new_tokens=4,
                            cache_len=eng.max_len)[0].tolist()
        assert req.output_ids == ref
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1


def test_serving_alloc_persistent_fault_no_block_leak(_serving_model):
    """Retry exhaustion on the allocator sheds the requests but leaves
    the pool intact: zero leaked blocks, and the next fault-free
    submission completes."""
    pt.set_flags({"retry_max_attempts": 2})
    eng = _serving_engine(_serving_model)
    with fault_scope("serving.alloc:drop"):
        reqs = [eng.submit([1, 2], max_new_tokens=3),
                eng.submit([3, 4], max_new_tokens=3)]
        eng.run_until_idle()
        assert [r.state for r in reqs] == ["shed", "shed"]
        assert eng.cache.blocks_used == 1  # trash only: nothing leaked
    req = eng.submit([5, 6], max_new_tokens=3)
    eng.run_until_idle()
    assert req.state == "done" and len(req.tokens) == 3
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1


def test_serving_alloc_shed_no_block_leak_int8(_serving_model):
    """The all-or-nothing acquire unwind must hold for int8 pools too:
    the 4-wide (codes + scales) layers ride the same allocator, and a
    shed admission — injected allocator failure mid-workload — must
    leak zero blocks. After drain + prefix flush only the trash block
    holds a ref, and the surviving requests' outputs are exact."""
    from paddle_tpu.models.generation import greedy_search
    pt.set_flags({"serving_kv_dtype": "int8"})
    try:
        with fault_scope("serving.alloc:skip@1"):
            eng = _serving_engine(_serving_model)
            assert eng.cache.kv_dtype == "int8"
            assert len(eng.cache.layers[0]) == 4
            reqs = [eng.submit([1, 2, 3], max_new_tokens=3),
                    eng.submit([4, 5], max_new_tokens=3),
                    eng.submit([6, 7, 8], max_new_tokens=3)]
            eng.run_until_idle()
            states = [r.state for r in reqs]
            assert states.count("shed") == 1, states
            assert states.count("done") == 2, states
            for r in reqs:
                if r.state != "done":
                    continue
                ref = greedy_search(
                    _serving_model, np.asarray([r.prompt]),
                    max_new_tokens=3,
                    cache_len=eng.max_len)[0].tolist()
                assert r.output_ids == ref
        eng.cache.flush_prefix_cache()
        assert eng.cache.allocator.leaked() == 1  # the trash block only
    finally:
        pt.set_flags({"serving_kv_dtype": "f32"})
