"""Helpers for tests that run bus consumers in separate processes.

:func:`spawn_worker` starts ``tests/workers/transport_worker.py`` against a
served bus; each worker writes one ``k,i`` line per message it consumed.
The other helpers read those files back and check them.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import time

_REPO = pathlib.Path(__file__).resolve().parent.parent
WORKER = _REPO / "tests" / "workers" / "transport_worker.py"
WAIT_S = 60.0


def spawn_worker(addr: tuple[str, int], subject: str, group: str, name: str,
                 outfile: str, *, key: str | None = None,
                 kill_after: int | None = None) -> subprocess.Popen:
    """Start one consumer process (see transport_worker.py) against a served
    bus."""
    cmd = [sys.executable, str(WORKER), "--addr", f"{addr[0]}:{addr[1]}",
           "--subject", subject, "--group", group, "--name", name,
           "--outfile", outfile]
    if key:
        cmd += ["--key", key]
    if kill_after is not None:
        cmd += ["--kill-after", str(kill_after)]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.Popen(cmd, env=env, cwd=str(_REPO))


def read_records(*outfiles: str) -> list[tuple[str, int]]:
    """Every ``(key, i)`` record the workers wrote (order preserved per
    file, files concatenated)."""
    records = []
    for path in outfiles:
        try:
            with open(path) as f:
                for line in f:
                    k, _, i = line.strip().partition(",")
                    if i:
                        records.append((k, int(i)))
        except FileNotFoundError:
            pass
    return records


def wait_for(published: set, outfiles: list[str],
             timeout: float = WAIT_S) -> list[tuple[str, int]]:
    """Poll worker outfiles until every published record appears (or
    timeout); returns the full record list (duplicates included)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        records = read_records(*outfiles)
        if set(records) >= published:
            return records
        time.sleep(0.05)
    return read_records(*outfiles)


def ordering_violations(outfiles: list[str]) -> int:
    """Per-key order regressions within each worker's own record stream.
    Keyed delivery pins a key to one member at a time and re-homes whole
    partitions in order, so each file must see every key's ``i`` strictly
    increasing."""
    bad = 0
    for path in outfiles:
        last: dict[str, int] = {}
        for k, i in read_records(path):
            if i <= last.get(k, -1):
                bad += 1
            last[k] = i
    return bad


def await_members(bus, subject: str, group: str, n: int,
                  timeout: float = WAIT_S) -> None:
    """Block until ``n`` members joined the group: the bus is
    fire-and-forget for subscriber-less subjects, so the driver must not
    start publishing before the remote members' subscriptions land (worker
    startup pays a multi-second interpreter+import cost)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        info = bus.group_info(subject, group)
        if info is not None and len(info["members"]) >= n:
            return
        time.sleep(0.05)
    raise TimeoutError(
        f"{n} remote members did not join {subject}/{group} in {timeout}s")
