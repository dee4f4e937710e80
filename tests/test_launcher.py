"""Launcher end-to-end on localhost: PS mode spawns real server+worker
processes that train a sparse table over the RPC wire; collective mode
wires the PADDLE_* env plane. Was never exercised in rounds 1-2.

Parity: python -m paddle.distributed.launch (fleet/launch.py:188,227,
launch_utils.py:407-411), TestDistBase subprocess pattern.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PS_SCRIPT = """
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from paddle_tpu.distributed.fleet.fleet_base import Fleet
from paddle_tpu.distributed.fleet.distributed_strategy import \\
    DistributedStrategy

fleet = Fleet()
strategy = DistributedStrategy()
strategy.a_sync = True
fleet.init(is_collective=False, strategy=strategy)

if fleet.is_server():
    fleet.init_server()
    fleet.run_server()          # returns after a client shutdown
elif fleet.is_worker():
    fleet.init_worker()
    from paddle_tpu.distributed.ps.sparse_table import REGISTRY
    t = REGISTRY.get_or_create("emb", 4, lr=1.0, init="zeros")
    tid = fleet.worker_index()
    ids = np.arange(8, dtype=np.int64)
    t.pull(ids)
    for _ in range(10):
        t.push(ids, np.full((8, 4), 0.1, np.float32))
    # rendezvous both workers, then worker 0 stops the servers
    from paddle_tpu.distributed.ps import runtime
    client = runtime._remote_client
    client.barrier(expected=2, server=0)
    rows = t.pull(ids)
    out = os.environ["TEST_OUT_DIR"] + f"/worker{{tid}}.npy"
    np.save(out, rows)
    if tid == 0:
        client.barrier(expected=2, server=1)
        time.sleep(0.5)
        client.shutdown_servers()
    else:
        client.barrier(expected=2, server=1)
    fleet.stop_worker()
"""

COLLECTIVE_SCRIPT = """
import os, sys
sys.path.insert(0, {repo!r})
assert os.environ["PADDLE_TRAINER_ID"] == "0"
assert os.environ["PADDLE_TRAINERS_NUM"] == "1"
assert "PADDLE_CURRENT_ENDPOINT" in os.environ
with open(os.environ["TEST_OUT_DIR"] + "/collective_ok", "w") as f:
    f.write("ok")
"""


def _run_launch(tmp_path, script_body, extra_args):
    script = tmp_path / "train.py"
    script.write_text(script_body.format(repo=REPO))
    env = dict(os.environ, TEST_OUT_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         *extra_args, str(script)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180)


def test_launch_ps_two_servers_two_workers(tmp_path):
    proc = _run_launch(tmp_path, PS_SCRIPT,
                       ["--server_num", "2", "--worker_num", "2"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    r0 = np.load(tmp_path / "worker0.npy")
    r1 = np.load(tmp_path / "worker1.npy")
    # both workers see the SAME jointly-updated rows, and the updates
    # actually landed: zeros init - 2 workers x 10 pushes x 0.1 x lr 1.0
    np.testing.assert_allclose(r0, r1, atol=1e-5)
    np.testing.assert_allclose(r0, np.full((8, 4), -2.0), atol=1e-5)


def test_launch_collective_env_plane(tmp_path):
    proc = _run_launch(tmp_path, COLLECTIVE_SCRIPT, [])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert (tmp_path / "collective_ok").exists()


def test_http_kv_rendezvous():
    """KVServer/KVClient (fleet/utils/http_server.py parity): scoped
    put/get/keys/delete plus a multi-threaded all-gather rendezvous of
    role endpoints (the gloo HTTP-rendezvous analog)."""
    import threading

    from paddle_tpu.distributed.fleet.utils.http_server import (KVClient,
                                                                KVServer)

    srv = KVServer(0, size={"job": 3})
    srv.start()
    try:
        ep = f"127.0.0.1:{srv.port}"
        c = KVClient(ep)
        assert c.kv_put("s", "a", "hello")
        assert c.kv_get("s", "a") == b"hello"
        assert c.kv_get("s", "missing") is None
        c.kv_put("s", "b", "world")
        assert sorted(c.kv_keys("s")) == ["a", "b"]

        results = {}

        def role(rank):
            cl = KVClient(ep)
            results[rank] = cl.rendezvous(
                "job", rank, f"10.0.0.{rank}:600{rank}", world=3)

        ts = [threading.Thread(target=role, args=(r,)) for r in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        for r in range(3):
            assert results[r] == {0: "10.0.0.0:6000", 1: "10.0.0.1:6001",
                                  2: "10.0.0.2:6002"}

        # teardown tracking: deletes drive should_stop
        assert not srv.should_stop()
        for r in range(3):
            c.kv_delete("job", str(r))
        assert srv.should_stop()
    finally:
        srv.stop()


def test_multiproc_refused_off_the_cpu_platform_plane(monkeypatch):
    """--nproc_per_node > 1 starts N children that each open every local
    chip; on an accelerator host that fails or hangs, so the launcher
    refuses from its arguments alone (no jax call in the parent) unless
    the platform plane says CPU."""
    from paddle_tpu.distributed.fleet import launch
    monkeypatch.delenv("PADDLE_DIST_PLATFORM", raising=False)
    monkeypatch.setattr(launch.subprocess, "Popen", lambda *a, **k: (
        pytest.fail("launcher started a child it had to refuse")))
    with pytest.raises(SystemExit) as exc:
        launch.main(["--nproc_per_node", "2", "train.py"])
    assert "--dist_platform cpu" in str(exc.value)
    # either spelling of the CPU plane gets past the refusal
    started = []
    monkeypatch.setattr(launch.subprocess, "Popen",
                        lambda *a, **k: started.append(k["env"]))
    monkeypatch.setattr(launch, "_watch_pod", lambda procs: None)
    launch.main(["--nproc_per_node", "2", "--dist_platform", "cpu",
                 "train.py"])
    monkeypatch.setenv("PADDLE_DIST_PLATFORM", "cpu")
    launch.main(["--nproc_per_node", "2", "train.py"])
    assert len(started) == 4
    assert all(e["PADDLE_DIST_PLATFORM"] == "cpu" for e in started)
