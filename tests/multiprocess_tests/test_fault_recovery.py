"""Crash/resume fault injection under real processes (SURVEY.md S5
"failure detection / elastic recovery": fail-fast + fail-and-restart).

Launch 1 trains with per-step snapshots and rank 1 dies mid-run with
``os._exit(1)`` — no cleanup, no distributed shutdown. Launch 2 is a fresh
world (new coordinator) over the same snapshot directory: the multi-node
checkpointer must agree on the newest COMMON iteration (discarding the
orphan snapshot rank 0 wrote after the crash), resume, and reach exactly
the state of an uninterrupted run. The reference exercises recovery by
deleting a snapshot file in-process; this drives the real thing — an
abrupt process death and a cross-launch resume."""

import os


from .test_multiprocess import _launch_world

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "worker_resume.py")


def _launch(phase: str, tmpdir: str, size: int = 2, timeout: float = 240.0):
    return _launch_world(size, tmpdir, timeout=timeout, worker=_WORKER,
                         extra_env={"MP_TEST_PHASE": phase})


def test_crash_then_resume(tmp_path):
    tmpdir = str(tmp_path)

    procs, outs = _launch("crash", tmpdir)
    assert procs[0].returncode == 0, f"rank 0:\n{outs[0][-4000:]}"
    assert "WORKER_CRASH_PHASE_OK 0" in outs[0], outs[0][-4000:]
    # the injected fault: rank 1 must have died abruptly with rc=1
    assert procs[1].returncode == 1, (
        f"rank 1 should have crashed (rc={procs[1].returncode}):\n"
        f"{outs[1][-4000:]}")

    procs, outs = _launch("resume", tmpdir)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"resume rank {r} failed (rc={p.returncode}):\n{out[-4000:]}")
        assert f"WORKER_OK {r}" in out, f"resume rank {r}:\n{out[-4000:]}"
