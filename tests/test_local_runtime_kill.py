"""LocalRuntime._kill: a pod that outlasts its first SIGKILL wait (an
engine that holds a chip, after a profiler capture) is killed again and
waited for again; the caller's thread never dies of TimeoutExpired."""

import subprocess

import pytest

from kubeai_tpu.runtime.local import LocalProcess, LocalRuntime


class _Proc:
    """A Popen whose process is gone only after *stubborn* waits."""

    pid = 2**22 + 12345  # no such process: killpg finds nothing

    def __init__(self, stubborn: int):
        self.stubborn, self.waits, self.kills = stubborn, [], 0

    def wait(self, timeout=None):
        self.waits.append(timeout)
        if len(self.waits) <= self.stubborn:
            raise subprocess.TimeoutExpired("engine", timeout)
        return -9

    def kill(self):
        self.kills += 1


@pytest.mark.parametrize("stubborn,waits,kills", [(0, 1, 0), (1, 2, 1), (2, 2, 1)])
def test_kill_waits_again_and_never_raises(stubborn, waits, kills):
    rt = LocalRuntime.__new__(LocalRuntime)  # _kill reads two class constants, no state
    proc = _Proc(stubborn)
    rt._kill(LocalProcess("pod-a", proc, 1234))
    assert len(proc.waits) == waits and proc.kills == kills
    assert proc.waits[0] == LocalRuntime.KILL_WAIT_S
    if waits == 2:
        assert proc.waits[1] == LocalRuntime.KILL_WAIT_AGAIN_S > LocalRuntime.KILL_WAIT_S
