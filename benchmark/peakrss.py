"""What a process of a run peaked at in memory, read once when it ends:
no thread, no poll, nothing inside the window.

Three readings, because the kernel's high-water mark has a floor that
is not the child's: a process keeps the mark of the address space it
was started from across its ``exec`` (Linux, ``exec_mmap``; gVisor the
same), so a child's ``ru_maxrss`` is never under what its parent held
when it started it.  Measured: in the sandbox a child that imports
nothing, started by a parent holding 1.05 GB, reads 1.05 GB both from
``wait4`` and from its own ``RUSAGE_SELF``; on the chip's machine
(gVisor: ``/proc/self/status`` has no ``VmHWM`` line) all 18 children of
a collector at 16.4 GB read 16.425 GB (PR 33, call 40), and a child
that imports nothing reads its parent's 15.5 GB while its ``statm``
says 0.02 GB (call 45).  So:

``own()``      this process's high-water mark (``VmHWM``, else
               ``ru_maxrss``): what the collector, which a small shell
               starts, says of itself.
``wait()``     ``Popen.wait`` that keeps the kernel's ``ru_maxrss`` of
               that one child: for the generator and the sink reader,
               which are started before anything large is imported and
               which report nothing themselves.
``Fullest``    what a process holds *now* (``statm``), looked at where
               it holds most, the most of those kept: what a child of
               the comparison, started by a collector that holds
               gigabytes, says of itself in the file it writes.
"""

from __future__ import annotations

import os
import resource
import subprocess
import time


def own():
    """Peak resident bytes of this process: its own address space's
    mark where the kernel keeps one, else the kernel's ``ru_maxrss``
    (never under what the parent held when it started this process)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def resident():
    """Resident bytes of this process now."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except OSError:
        return own()


class Fullest:
    """The most resident bytes seen at the instants ``look()`` was
    called; ``look()`` returns it."""

    def __init__(self):
        self.bytes = 0

    def look(self):
        self.bytes = max(self.bytes, resident())
        return self.bytes


def wait(proc, timeout=None):
    """``proc.wait(timeout)``; returns (exit code, peak resident bytes
    as the kernel counted them for that child, or None where somebody
    had waited for it before).  Raises ``subprocess.TimeoutExpired`` as
    ``Popen.wait`` does."""
    if proc.returncode is not None:
        return proc.returncode, None
    deadline = None if timeout is None else time.monotonic() + timeout
    delay = 0.0005
    while True:
        pid, status, usage = os.wait4(
            proc.pid, 0 if deadline is None else os.WNOHANG)
        if pid:
            break
        if time.monotonic() >= deadline:
            raise subprocess.TimeoutExpired(proc.args, timeout)
        delay = min(delay * 2, 0.05)
        time.sleep(delay)
    proc.returncode = os.waitstatus_to_exitcode(status)
    return proc.returncode, usage.ru_maxrss * 1024


def gb(n):
    return "not read" if n is None else f"{n / 1e9:.3f} GB"
