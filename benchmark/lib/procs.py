"""Children of a run: each in a process group of its own, every one
stopped and waited for by the run that started it."""

from __future__ import annotations

import os
import signal
import subprocess
import sys


class Children:
    def __init__(self) -> None:
        self._procs: list[subprocess.Popen] = []

    def spawn(self, argv, *, env, cwd, log_path, **kw) -> subprocess.Popen:
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(argv, env=env, cwd=cwd, stderr=log,
                                    start_new_session=True, **kw)
        finally:
            log.close()
        self._procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for proc in self._procs:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def __enter__(self) -> "Children":
        return self

    def __exit__(self, *exc) -> None:
        self.stop_all()


def fail(msg: str, *logs: str):
    """No result line: the tail of the children's logs, then a non-zero
    exit."""
    for path in logs:
        if os.path.exists(path):
            with open(path, errors="replace") as f:
                sys.stderr.write(f"---- {path}\n{f.read()[-6000:]}\n")
    raise SystemExit(f"benchmark: FAILED — {msg}")
