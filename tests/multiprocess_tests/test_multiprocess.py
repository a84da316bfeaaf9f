"""True multi-process tests: the DCN/object-comm path under real
``jax.distributed`` processes (SURVEY.md S4 test-contract item (b) — the
analog of the reference's ``mpiexec -n 2 pytest`` runs).

Spawns N fresh Python processes (the in-process conftest already owns the
jax runtime, so workers must be subprocesses), joins them through a local
coordinator, and runs ``worker.py``'s scenario suite over the
coordination-service KV store.
"""

import os
import socket
import subprocess
import sys

import jax
import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_world(size: int, tmpdir: str, timeout: float = 240.0,
                  transport: str = "kv", worker: str = None,
                  extra_env: dict = None):
    worker = worker or _WORKER
    port = _free_port()
    env_base = {
        k: v for k, v in os.environ.items()
        # XLA_FLAGS: the conftest's forced 8-device flag is for THIS process;
        # workers stay at 1 CPU device each so the geometry is process-shaped.
        # CHAINERMN_TPU_OBJSTORE: the transport param controls it below — an
        # ambient native-sidecar address must not redirect the KV runs.
        if k not in ("XLA_FLAGS", "CHAINERMN_TPU_OBJSTORE")
    }
    server = None
    if transport == "native":
        # The test process hosts the C++ sidecar (the "process 0's launcher
        # runs serve()" deployment contract); workers connect over TCP.
        from chainermn_tpu.native import objstore

        server = objstore.ObjStoreServer()
        env_base["CHAINERMN_TPU_OBJSTORE"] = f"127.0.0.1:{server.port}"
    procs = []
    try:
        for r in range(size):
            env = dict(
                env_base,
                MP_TEST_RANK=str(r),
                MP_TEST_SIZE=str(size),
                MP_TEST_PORT=str(port),
                MP_TEST_TMPDIR=tmpdir,
                MP_TEST_TRANSPORT=transport,
                PYTHONPATH=_REPO + os.pathsep + env_base.get("PYTHONPATH", ""),
            )
            env.update(extra_env or {})
            procs.append(subprocess.Popen(
                [sys.executable, worker],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            ))
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=timeout)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    finally:
        if server is not None:
            server.stop()
    return procs, outs


@pytest.mark.parametrize("size", [2, 4])
def test_multiprocess_suite(size, tmp_path):
    procs, outs = _launch_world(size, str(tmp_path))
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"rank {r} failed (rc={p.returncode}):\n{out[-4000:]}"
        )
        assert f"WORKER_OK {r}" in out, f"rank {r} did not finish:\n{out[-4000:]}"


def test_multiprocess_suite_native_transport(tmp_path):
    """The FULL worker scenario suite again, but over the C++ objstore
    sidecar instead of the KV store — NativeObjectComm under a real
    multi-process launch (VERDICT r2 #6)."""
    pytest.importorskip("chainermn_tpu.native.objstore")
    from chainermn_tpu.native import objstore

    if not objstore_builds():
        pytest.skip("objstore sidecar cannot build here")
    procs, outs = _launch_world(2, str(tmp_path), transport="native")
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"rank {r} failed (rc={p.returncode}):\n{out[-4000:]}"
        )
        assert f"WORKER_OK {r}" in out, f"rank {r} did not finish:\n{out[-4000:]}"


def objstore_builds() -> bool:
    from chainermn_tpu.native import objstore

    try:
        objstore._load()
        return True
    except Exception:
        return False
