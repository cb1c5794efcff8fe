"""Shared helper for the driver entry point (__graft_entry__.py).

Children run in their own session with a process-group kill on the
deadline — ``subprocess.run(timeout=...)`` only kills the direct child
and then waits on inherited pipes, which converts a wedged grandchild
into a hang of the parent.
"""
import os
import signal
import subprocess
import sys
import threading
import time


def run_bounded(cmd, env, timeout, cwd=None, echo=False):
    """Run cmd in its own session; SIGKILL the whole group on deadline.

    Returns ``(rc, output)`` where rc is None when the deadline killed
    the group; the output collected so far is for diagnostics.
    """
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env,
                            cwd=cwd, start_new_session=True)
    chunks = []

    def _reader():
        for line in proc.stdout:
            chunks.append(line)
            if echo:
                sys.stdout.write(line)

    t = threading.Thread(target=_reader, daemon=True)
    t.start()
    deadline = time.time() + timeout
    rc = None
    while time.time() < deadline:
        rc = proc.poll()
        if rc is not None:
            break
        time.sleep(0.25)
    else:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        rc = None
    t.join(timeout=10)
    return rc, "".join(chunks)
