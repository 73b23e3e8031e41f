"""The supervisor of run.py (`run_swept`) leaves no process behind, on any way
out: the command's own end, a signal that tells the supervisor to end, or the
supervisor's own death. The stand-in for a cell is a process that starts a
grandchild deaf to SIGTERM with a thread of its own, as a worker that holds a
chip is.

    python3 -m pytest benchmarks/tests -q        (CPU, under a minute)
"""
import builtins
import os
import signal
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402

WORKER = """
import signal, sys, threading, time
signal.signal(signal.SIGTERM, signal.SIG_IGN)
threading.Thread(target=time.sleep, args=(600,), daemon=True).start()
time.sleep(600)
"""
# The cell's stand-in: takes the lifeline the way run.py's child does, starts
# the worker without waiting for it (the program's daemon kills its workers and
# waits for none), says both process ids, then ends at once or stays.
CELL = """
import os, subprocess, sys, time
sys.path.insert(0, {bench!r})
import run
run._lifeline(int(sys.argv[sys.argv.index("--lifeline") + 1]))
w = subprocess.Popen([sys.executable, "-c", {worker!r}])
print("pids", os.getpid(), w.pid, flush=True)
time.sleep({stay})
"""
# A supervisor in a process of its own, so that a test can signal it.
SUPERVISOR = """
import sys
sys.path.insert(0, {bench!r})
import run
end = run.run_swept([sys.executable, "-c", {cell!r}], dict(__import__("os").environ), 300,
                    lambda line: print(line, end="", flush=True))
print("swept", end["signal"], end["left"], end["reaped_all"], flush=True)
"""


def cell(stay: float) -> str:
    return CELL.format(bench=BENCH_DIR, worker=WORKER, stay=stay)


def gone(pid: int) -> bool:
    """No entry at all: not running, and no zombie waiting for a parent."""
    return not os.path.exists(f"/proc/{pid}")


def wait_gone(pids, seconds: float) -> list:
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds and not all(gone(p) for p in pids):
        time.sleep(0.05)
    return [p for p in pids if not gone(p)]


def test_a_worker_the_cell_left_is_killed_and_reaped():
    sup = subprocess.run([sys.executable, "-c", SUPERVISOR.format(bench=BENCH_DIR, cell=cell(0))],
                         stdout=subprocess.PIPE, text=True, timeout=120)
    lines = sup.stdout.splitlines()
    pids = [int(x) for x in lines[0].split()[1:]]
    assert len(pids) == 2 and sup.returncode == 0
    assert lines[-1] == "swept None [] True"
    assert all(gone(p) for p in pids), "a process or its zombie outlived the sweep"


@pytest.mark.parametrize("signum", [signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGKILL])
def test_nothing_outlives_a_supervisor_that_is_ended(signum):
    """SIGTERM, SIGINT, SIGHUP: the supervisor sweeps before it ends. SIGKILL:
    it cannot, and the cell's lifeline ends the session."""
    sup = subprocess.Popen([sys.executable, "-c", SUPERVISOR.format(bench=BENCH_DIR, cell=cell(600))],
                           stdout=subprocess.PIPE, text=True)
    try:
        pids = [int(x) for x in sup.stdout.readline().split()[1:]]
        assert len(pids) == 2 and not any(gone(p) for p in pids)
        sup.send_signal(signum)
        rest = sup.stdout.read()
        sup.wait(timeout=60)
        if signum != signal.SIGKILL:
            assert f"swept {int(signum)} [] True" in rest
            assert all(gone(p) for p in pids)
        else:
            # The processes are init's to reap here; what has to end is their running.
            assert wait_gone(pids, 20) == [] or all(
                open(f"/proc/{p}/stat").read().rsplit(")", 1)[1].split()[0] == "Z"
                for p in pids if not gone(p))
    finally:
        if sup.poll() is None:
            sup.kill()
        for p in locals().get("pids", []):
            try:
                os.kill(p, signal.SIGKILL)
            except ProcessLookupError:
                pass


def test_a_thread_that_ends_under_the_look_does_not_hide_its_process(monkeypatch):
    """The fault this file was written after: one thread's stat file gone
    between the listing and the read made the whole process count as gone,
    while its other threads still held the chip."""
    w = subprocess.Popen([sys.executable, "-c", WORKER], start_new_session=True)
    try:
        t0 = time.monotonic()
        while len(os.listdir(f"/proc/{w.pid}/task")) < 2 and time.monotonic() - t0 < 10:
            time.sleep(0.05)
        first = os.listdir(f"/proc/{w.pid}/task")[0]
        real = builtins.open

        def racing(path, *a, **k):
            if path == f"/proc/{w.pid}/task/{first}/stat":
                raise FileNotFoundError(path)
            return real(path, *a, **k)

        monkeypatch.setattr(builtins, "open", racing)
        assert run._session_pids(w.pid) == [w.pid]
    finally:
        w.kill()
        w.wait()
