"""Process memory accounting helpers."""

from __future__ import annotations


def current_rss_mb() -> float:
    """This process's CURRENT resident set, in MiB (``/proc/self/statm``).

    Unlike the high-water counters (``ru_maxrss``, ``VmHWM``), the current
    RSS can never leak a forked parent's footprint through ``execve``:
    ``ru_maxrss`` lives on the signal struct, which survives exec, and
    sandboxed kernels that emulate /proc (gVisor) serve ``VmHWM`` from the
    same counter, so a child forked from a large parent reports the
    parent's peak.  A subprocess that samples this at its own cadence
    (e.g. once per consumed batch, tests/test_stream_ceiling.py) gets a
    peak that is genuinely ITS OWN on every kernel, emulated or not.
    Returns 0.0 where /proc is absent."""
    import os

    try:
        with open("/proc/self/statm") as f:
            resident_pages = int(f.read().split()[1])
        return resident_pages * os.sysconf("SC_PAGE_SIZE") / (1 << 20)
    except (OSError, ValueError, IndexError):
        return 0.0
