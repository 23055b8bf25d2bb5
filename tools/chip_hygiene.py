"""Chip hygiene: detect lingering accelerator-holding processes.

A chip belongs to one process at a time, and a backend init that finds
it held fails at once (``utils/platform.py:check_backend``, no
retry). The cause — a previous run's process still holding the TPU — is
invisible after the fact. This tool makes it a reported condition
BEFORE it costs a run: it scans ``/proc`` for
processes holding accelerator device nodes (``/dev/accel*``,
``/dev/vfio/*``) or the libtpu lockfile, and prints ONE JSON line a
driver or operator can parse. ``ci.sh`` runs it as an informational
step.

Report only — nothing is killed. ``--fail-on-holders`` turns holders
(other than this process tree) into exit code 1 for gating scripts.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List

# device nodes + lockfiles whose open fds mark a process as chip-holding
_TARGET_GLOBS = (
    "/dev/accel*",
    "/dev/apex_*",
    "/dev/vfio/*",
    "/tmp/libtpu_lockfile*",
)


def _target_paths() -> List[str]:
    out: List[str] = []
    for pat in _TARGET_GLOBS:
        out.extend(glob.glob(pat))
    return sorted(set(out))


def _cmdline(pid: int) -> str:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode("utf-8", "replace").strip()
    except OSError:
        return ""


def _age_s(pid: int) -> float | None:
    try:
        import time

        return round(time.time() - os.stat(f"/proc/{pid}").st_mtime, 1)
    except OSError:
        return None


def _ancestors(pid: int) -> List[int]:
    """pid + its ancestor chain — a report must not flag the reporting
    shell/CI pipeline itself as a lingering holder."""
    chain = [pid]
    for _ in range(64):
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().split(")")[-1].split()[1])
        except (OSError, ValueError, IndexError):
            break
        if ppid <= 1:
            break
        chain.append(ppid)
        pid = ppid
    return chain


def find_chip_holders() -> Dict:
    """Scan /proc/*/fd for open handles on accelerator devices and
    lockfiles. Unreadable processes (other users, no root) are counted,
    not silently dropped — an empty holder list with a large
    ``unreadable_proc_count`` is 'unknown', not 'clean'."""
    targets = _target_paths()
    target_set = set(targets)
    self_and_ancestors = set(_ancestors(os.getpid()))
    holders: List[Dict] = []
    unreadable = 0
    for pid_dir in glob.glob("/proc/[0-9]*"):
        try:
            pid = int(os.path.basename(pid_dir))
        except ValueError:
            continue
        fd_dir = os.path.join(pid_dir, "fd")
        try:
            fds = os.listdir(fd_dir)
        except OSError:
            unreadable += 1
            continue
        held: List[str] = []
        for fd in fds:
            try:
                dest = os.readlink(os.path.join(fd_dir, fd))
            except OSError:
                continue
            if dest in target_set:
                held.append(dest)
        if held:
            holders.append(
                {
                    "pid": pid,
                    "cmdline": _cmdline(pid)[:200],
                    "age_s": _age_s(pid),
                    "targets": sorted(set(held)),
                    "is_self_tree": pid in self_and_ancestors,
                }
            )
    return {
        "targets_present": targets,
        "holders": sorted(holders, key=lambda h: h["pid"]),
        "foreign_holder_count": sum(
            1 for h in holders if not h["is_self_tree"]
        ),
        "unreadable_proc_count": unreadable,
        "self_pid": os.getpid(),
    }


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description="Report processes holding accelerator devices/lockfiles "
        "as one JSON line."
    )
    p.add_argument(
        "--fail-on-holders",
        action="store_true",
        help="exit 1 when a process OUTSIDE this process tree holds a chip",
    )
    args = p.parse_args(argv)
    report = find_chip_holders()
    print(json.dumps(report))
    if args.fail_on_holders and report["foreign_holder_count"]:
        print(
            f"chip hygiene: {report['foreign_holder_count']} foreign "
            "process(es) holding accelerator handles",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
