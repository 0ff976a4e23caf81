"""Multi-process integration test (the reference's `@distributed_test`
forked-worker fixture, `tests/unit/common.py:16-100`): two REAL
processes join a gloo-backed CPU cluster (2 local devices each, 4
global), run `jax.distributed` init → `deeperspeed_tpu.initialize` over
the global mesh → ZeRO-2 train_batch → rank-0-gated save_checkpoint →
cross-process restore → trajectory parity. Exercises exactly the
surfaces the single-process suite cannot: coordinator bring-up,
non-fully-addressable arrays in checkpoint IO, process-0 write gating,
and the save barrier."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

# real multi-process workers: `slow` by tier-1's rule (`pyproject.toml`)
pytestmark = pytest.mark.slow


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_train_checkpoint_restore(tmp_path):
    port = _free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=os.pathsep.join(
            [os.getcwd()] + os.environ.get("PYTHONPATH", "").split(
                os.pathsep)),
    )
    worker = os.path.join(os.path.dirname(__file__), "dist_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(port), str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(2)]
    results = {}
    for p in procs:
        out, _ = p.communicate(timeout=280)
        text = out.decode()
        assert p.returncode == 0, text[-3000:]
        for line in text.splitlines():
            if line.startswith("WORKER_RESULT "):
                r = json.loads(line[len("WORKER_RESULT "):])
                results[r["pid"]] = r
    assert set(results) == {0, 1}, results
    # both processes observe identical (replicated) losses
    np.testing.assert_allclose(results[0]["losses"], results[1]["losses"],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(results[0]["got"], results[1]["got"],
                               rtol=1e-6, atol=1e-6)
    # only process 0 wrote the files; they exist exactly once
    assert (tmp_path / "latest").is_file()


def test_launcher_driven_two_process_bringup(tmp_path):
    """The real launcher chain (reference `launch.py:69`): spawn
    `deeperspeed_tpu.launcher.launch` per node; IT spawns the user
    script with the RANK/MASTER_* env handoff; the workers form the
    cluster from env alone and train in lockstep."""
    from deeperspeed_tpu.launcher.runner import encode_world_info
    port = _free_port()
    world_info = encode_world_info({"node0": 2, "node1": 2})
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PYTHONPATH=os.pathsep.join(
            [os.getcwd()] + os.environ.get("PYTHONPATH", "").split(
                os.pathsep)),
    )
    worker = os.path.join(os.path.dirname(__file__), "launch_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, "-m", "deeperspeed_tpu.launcher.launch",
         "--node_rank", str(i), "--master_addr", "127.0.0.1",
         "--master_port", str(port), "--world_info", world_info, worker],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(2)]
    results = {}
    for p in procs:
        out, _ = p.communicate(timeout=280)
        text = out.decode()
        assert p.returncode == 0, text[-3000:]
        for line in text.splitlines():
            if line.startswith("WORKER_RESULT "):
                r = json.loads(line[len("WORKER_RESULT "):])
                results[r["rank"]] = r
    assert set(results) == {0, 1}, results
    for r in results.values():
        assert r["world"] == 2
        assert r["slots"] == "2"          # DS_SLOTS from the hostfile
        assert r["dp_world"] == 2
    np.testing.assert_allclose(results[0]["losses"], results[1]["losses"],
                               rtol=1e-6, atol=1e-6)


def test_launcher_signal_kills_child(tmp_path):
    """SIGTERM on the launcher terminates its child process group — the
    reference launch.py's signal-handling contract."""
    import signal
    import time
    pidfile = tmp_path / "child.pid"
    script = tmp_path / "sleeper.py"
    script.write_text(
        "import os, time, sys\n"
        f"open({str(pidfile)!r}, 'w').write(str(os.getpid()))\n"
        "sys.stdout.flush()\n"
        "time.sleep(120)\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.getcwd()] + os.environ.get("PYTHONPATH", "").split(
            os.pathsep)))
    p = subprocess.Popen(
        [sys.executable, "-m", "deeperspeed_tpu.launcher.launch",
         str(script)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    for _ in range(100):
        if pidfile.is_file() and pidfile.read_text():
            break
        time.sleep(0.1)
    child_pid = int(pidfile.read_text())
    p.send_signal(signal.SIGTERM)
    p.wait(timeout=30)
    assert p.returncode != 0
    for _ in range(100):
        try:
            os.kill(child_pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        os.kill(child_pid, signal.SIGKILL)
        raise AssertionError("launcher left its child running")


def test_two_process_streamed_nvme_checkpoint(tmp_path):
    """Multi-process save/restore on the NVMe store-of-record tier
    (VERDICT r4 missing #6): each process writes its zero_pp_rank_*
    shard dir, process 0 the union manifest; a fresh 2-process engine
    restores and continues the trajectory exactly."""
    port = _free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PYTHONPATH=os.pathsep.join(
            [os.getcwd()] + os.environ.get("PYTHONPATH", "").split(
                os.pathsep)),
    )
    worker = os.path.join(os.path.dirname(__file__), "streamed_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(port), str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(2)]
    results = {}
    for p in procs:
        out, _ = p.communicate(timeout=280)
        text = out.decode()
        assert p.returncode == 0, text[-3000:]
        for line in text.splitlines():
            if line.startswith("WORKER_RESULT "):
                r = json.loads(line[len("WORKER_RESULT "):])
                results[r["pid"]] = r
    assert set(results) == {0, 1}, results
    for r in results.values():
        # restore-then-step == save-then-step (trajectory parity)
        np.testing.assert_allclose(r["resumed"], r["cont"],
                                   rtol=2e-5, atol=2e-5)
    # processes agree (replicated state)
    np.testing.assert_allclose(results[0]["losses"], results[1]["losses"],
                               rtol=1e-6, atol=1e-6)
    # layout: per-process shard dirs + union manifest + latest
    ckpt = tmp_path / "ckpt" / "step2"
    assert (ckpt / "zero_pp_rank_0_mp_rank_00" / "streamed_states.pt")\
        .is_file()
    assert (ckpt / "zero_pp_rank_1_mp_rank_00" / "streamed_states.pt")\
        .is_file()
    assert (ckpt / "mp_rank_00_model_states.pt").is_file()
    assert (tmp_path / "ckpt" / "latest").read_text().strip() == "step2"
