"""Multi-process DCN tests (ref: tests/nightly/dist_sync_kvstore.py run via
tools/launch.py --launcher local).

Spawns real worker processes on the CPU backend; jax.distributed's
coordination service plays the scheduler role and gloo carries the
cross-process collectives (the DCN stand-in on one host)."""
import os
import socket
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "dist_worker.py")
_LAUNCH = os.path.join(_REPO, "tools", "launch.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env():
    env = dict(os.environ)
    # N workers cannot share one chip: the CPU backend is the
    # multi-process test substrate
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def _spawn_workers(mode, n):
    port = str(_free_port())
    procs = []
    for i in range(n):
        env = _worker_env()
        env.update({"DMLC_ROLE": "worker", "DMLC_PS_ROOT_URI": "127.0.0.1",
                    "DMLC_PS_ROOT_PORT": port, "DMLC_NUM_WORKER": str(n),
                    "DMLC_WORKER_ID": str(i)})
        procs.append(subprocess.Popen(
            [sys.executable, _WORKER, mode], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out))
    return outs


@pytest.mark.parametrize("n", [2, 3])
def test_dist_sync_kvstore_multiprocess(n):
    outs = _spawn_workers("kvstore", n)
    for rc, out in outs:
        assert rc == 0, out[-2000:]
        assert "DIST_OK" in out, out[-2000:]


def test_dist_sync_training_two_process():
    outs = _spawn_workers("train", 2)
    for rc, out in outs:
        assert rc == 0, out[-2000:]
        assert "DIST_OK" in out, out[-2000:]


def test_hybrid_dcn_ici_grads_match_single_process():
    """The real pod topology in miniature (round-4 verdict item #6):
    2 processes (DCN stand-in: gloo dist_sync KVStore) x 4 virtual
    devices each (ICI stand-in: in-graph GSPMD psum over a dp=4 mesh).
    The combined gradient must equal the single-process 8-device run —
    this pytest process IS that oracle (conftest pins cpu x8)."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel
    from tests.dist_worker import hybrid_loss_and_data

    outs = _spawn_workers("hybrid", 2)
    grads_line = None
    for rc, out in outs:
        assert rc == 0, out[-2000:]
        assert "DIST_OK" in out, out[-2000:]
        for ln in out.splitlines():
            if ln.startswith("HYBRID_GRADS "):
                grads_line = ln[len("HYBRID_GRADS "):]
    assert grads_line, outs
    worker_grads = {k: np.asarray(v, np.float32)
                    for k, v in json.loads(grads_line).items()}

    # single-process oracle: same loss/params/data, all 8 devices in one
    # dp mesh, one in-graph psum — no DCN hop
    params, X, y, loss = hybrid_loss_and_data()
    with parallel.make_mesh(dp=8) as mesh:
        xd = jax.device_put(jnp.asarray(X), NamedSharding(mesh.mesh,
                                                          P("dp")))
        yd = jax.device_put(jnp.asarray(y), NamedSharding(mesh.mesh,
                                                          P("dp")))
        oracle = jax.jit(jax.grad(loss))(params, xd, yd)

    assert sorted(worker_grads) == sorted(oracle)
    for name in oracle:
        np.testing.assert_allclose(
            worker_grads[name], np.asarray(oracle[name]),
            rtol=1e-5, atol=1e-6, err_msg=f"grad {name}")


def test_peer_loss_aborts_not_hangs():
    """Failure detection (SURVEY.md §5): worker 1 dies before the barrier;
    worker 0 must raise MXNetError within its watchdog timeout instead of
    deadlocking on the dead peer."""
    outs = _spawn_workers("peerloss", 2)
    for rc, out in outs:
        assert rc == 0, out[-2000:]
    assert any("peer-loss detected" in out for _, out in outs), outs


def test_launch_py_local():
    """The reference-style launcher end to end."""
    env = _worker_env()
    p = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "2", "-s", "1",
         sys.executable, _WORKER, "kvstore"],
        env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert p.stdout.count("DIST_OK") == 2, p.stdout


def test_launch_dry_run_launchers(tmp_path):
    """The ssh/mpi/slurm launchers emit correct per-worker commands with
    the DMLC_* contract (--dry-run; execution needs real hosts)."""
    import subprocess
    import sys

    tool = _LAUNCH
    hostfile = tmp_path / "hosts"
    hostfile.write_text("nodeA\nnodeB  # trailing comment\n")

    def run(*extra):
        r = subprocess.run(
            [sys.executable, tool, "-n", "4", "--dry-run", *extra,
             "python", "train.py", "--kv-store", "dist_sync"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        return r.stdout.strip().splitlines()

    local = run()
    assert len(local) == 4
    assert "DMLC_WORKER_ID=3" in local[3]
    assert "DMLC_NUM_WORKER=4" in local[0]

    ssh = run("--launcher", "ssh", "-H", str(hostfile))
    assert len(ssh) == 4
    assert ssh[0].startswith("ssh ")
    assert "nodeA" in ssh[0] and "nodeB" in ssh[1]
    assert "nodeA" in ssh[2]  # round-robin wraps
    assert "DMLC_PS_ROOT_URI=nodeA" in ssh[1]  # worker 0's host is root

    mpi = run("--launcher", "mpi")
    assert len(mpi) == 1
    assert mpi[0].startswith("mpirun -n 4 env ")  # portable env prefix
    assert "DMLC_NUM_WORKER=4" in mpi[0]
    assert "DMLC_WORKER_ID" not in mpi[0]   # rank comes from MPI
    # coordinator resolves at runtime on rank 0's node, NOT the launch
    # host (which may be a login node)
    assert "DMLC_PS_ROOT_URI" not in mpi[0]

    slurm = run("--launcher", "slurm")
    assert len(slurm) == 1
    assert "srun --ntasks=4 env " in slurm[0]
    assert "DMLC_PS_ROOT_URI" not in slurm[0]


@pytest.mark.slow  # 20s multi-process spawn; scheduler-role parking is
# infra-level coverage redundant with the other tier-1 dist spawns —
# runs nightly (heavy-integration stage)
def test_server_role_parks_not_trains():
    """A DMLC_ROLE=server process importing the package must PARK (the
    reference kvstore_server semantics), not run the script body as a
    rogue extra worker; the tracker terminates it."""
    env = _worker_env()
    env["DMLC_ROLE"] = "server"
    p = subprocess.Popen(
        [sys.executable, "-c",
         "import mxnet_tpu; print('FELL_THROUGH', flush=True)"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        out, _ = p.communicate(timeout=20)
        raise AssertionError(f"server did not park: {out[-500:]}")
    except subprocess.TimeoutExpired:
        pass  # parked, as it should
    finally:
        p.kill()
        out, _ = p.communicate()
    assert "FELL_THROUGH" not in out
