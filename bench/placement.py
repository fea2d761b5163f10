"""Where the benchmark's process and its host buffers sit on the host.

Read from ``/sys`` and ``/proc``, read only: the NUMA node of each CPU, the
node of the chip's PCI device, and the pages behind a host array (its
offset in its first page and its transparent huge pages).  `bind_to_chip_node` narrows this process's own CPU affinity to
the CPUs of the chip's node, so that a run does not measure which socket
the OS happened to start it on; it writes nothing under ``/proc`` or
``/sys`` and changes no setting of the machine.  Where ``/sys`` names no
node for the chip, or the host has one node, it does nothing.
"""
from __future__ import annotations

import glob
import os
import re

SYS = "/sys"
PAGE = 4096
GOOGLE_PCI_VENDOR = "0x1ae0"


def _read(path: str) -> str | None:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return None


def parse_cpulist(text: str) -> set[int]:
    """``"0-3,8,10-11"`` → {0, 1, 2, 3, 8, 10, 11}."""
    cpus: set[int] = set()
    for part in text.split(","):
        if not part.strip():
            continue
        lo, _, hi = part.partition("-")
        cpus.update(range(int(lo), int(hi or lo) + 1))
    return cpus


def format_cpulist(cpus) -> str:
    """The inverse of `parse_cpulist`, with runs folded."""
    out, run = [], []
    for c in sorted(cpus):
        if run and c == run[-1] + 1:
            run.append(c)
            continue
        if run:
            out.append(f"{run[0]}-{run[-1]}" if len(run) > 1 else str(run[0]))
        run = [c]
    if run:
        out.append(f"{run[0]}-{run[-1]}" if len(run) > 1 else str(run[0]))
    return ",".join(out)


def node_cpus(sys: str = SYS) -> dict[int, set[int]]:
    """{node: its CPUs} of the nodes that have CPUs."""
    nodes = {}
    for path in glob.glob(os.path.join(sys, "devices/system/node/node*")):
        m = re.fullmatch(r"node(\d+)", os.path.basename(path))
        text = _read(os.path.join(path, "cpulist"))
        if m and text:
            cpus = parse_cpulist(text)
            if cpus:
                nodes[int(m.group(1))] = cpus
    return nodes


def _index(path: str) -> int:
    m = re.search(r"(\d+)$", path)
    return int(m.group(1)) if m else 0


def chip_devices(sys: str = SYS) -> list[str]:
    """The chips' device directories under ``/sys``, in device order:
    ``class/accel/accel<i>/device``, or else the PCI devices of Google's
    vendor id by address."""
    accel = sorted(glob.glob(os.path.join(sys, "class/accel/accel*")),
                   key=_index)
    if accel:
        return [os.path.join(a, "device") for a in accel]
    return [d for d in sorted(glob.glob(os.path.join(sys, "bus/pci/devices/*")))
            if _read(os.path.join(d, "vendor")) == GOOGLE_PCI_VENDOR]


def chip_nodes(chips: int, sys: str = SYS) -> list[int]:
    """NUMA nodes of the first ``chips`` chips; a chip whose node ``/sys``
    does not give (-1, or no file) is left out."""
    nodes = []
    for dev in chip_devices(sys)[:chips]:
        text = _read(os.path.join(dev, "numa_node"))
        if text is not None and text.lstrip("-").isdigit() and int(text) >= 0:
            nodes.append(int(text))
    return nodes


def chip_node_cpus(chips: int, allowed: set[int],
                   sys: str = SYS) -> set[int] | None:
    """The CPUs of ``allowed`` that lie on the chips' one node, or None
    where there is nothing to narrow: one node with CPUs or none, chips
    with no node or on several, or no allowed CPU on their node."""
    nodes = node_cpus(sys)
    found = set(chip_nodes(chips, sys))
    if len(nodes) < 2 or len(found) != 1:
        return None
    cpus = nodes.get(found.pop(), set()) & set(allowed)
    if not cpus or cpus == set(allowed):
        return None
    return cpus


def bind_to_chip_node(chips: int, sys: str = SYS) -> set[int] | None:
    """Narrow this process's CPU affinity to the chips' node (before any
    thread starts, so that every thread inherits it); returns the CPUs
    bound to, or None where it did nothing."""
    cpus = chip_node_cpus(chips, os.sched_getaffinity(0), sys)
    if cpus:
        os.sched_setaffinity(0, cpus)
    return cpus


def placement_line(chips: int, bound, sys: str = SYS) -> str:
    """One notes line: the CPUs this process may run on and their nodes,
    the chips' nodes, and what the binding did."""
    cpus = os.sched_getaffinity(0)
    nodes = node_cpus(sys)
    on = sorted(n for n, c in nodes.items() if c & cpus)
    return (f"[host] cpus {format_cpulist(cpus)} on node(s) "
            f"{','.join(map(str, on)) or '?'} of {len(nodes)}; chip node(s) "
            f"{','.join(map(str, chip_nodes(chips, sys))) or '?'}; bound "
            f"{'to ' + format_cpulist(bound) if bound else 'nothing'}")


# ------------------------------------------------------- host buffers
def _mappings(addr: int, end: int, proc: str) -> list[dict]:
    """The mappings of ``/proc/self/smaps`` that overlap [addr, end), each
    {start, end, AnonHugePages, Rss} (kB)."""
    found, cur = [], None
    text = _read(os.path.join(proc, "smaps")) or ""
    for line in text.splitlines():
        m = re.match(r"^([0-9a-f]+)-([0-9a-f]+) ", line)
        if m:
            a, b = int(m.group(1), 16), int(m.group(2), 16)
            cur = {"start": a, "end": b} if a < end and b > addr else None
            if cur is not None:
                found.append(cur)
        elif cur is not None:
            k, _, v = line.partition(":")
            if k in ("AnonHugePages", "Rss"):
                cur[k] = int(v.split()[0])
    return found


def buffer_pages(arr, proc: str = "/proc/self") -> dict:
    """Where a host array's bytes lie: ``offset`` of its first byte in its
    page, and ``huge_mb`` and ``rss_mb`` of the mappings that hold it
    (whole mappings, so a neighbour's pages count too)."""
    import numpy as np
    a = np.asarray(arr)
    addr = a.__array_interface__["data"][0]
    maps = _mappings(addr, addr + a.nbytes, proc)
    return {"offset": addr % PAGE,
            "huge_mb": sum(m.get("AnonHugePages", 0) for m in maps) / 1024,
            "rss_mb": sum(m.get("Rss", 0) for m in maps) / 1024}

