"""The harness's host placement (`bench/placement.py`) on fake ``/sys``
layouts: it binds to the CPUs of the chip's NUMA node, and does nothing
where the host has one node or ``/sys`` names none for the chip; and it
reads where a host array's pages lie."""
import os

import numpy as np
import pytest

from bench import placement


def _layout(root, nodes, chips, accel=True):
    """A fake ``/sys``: ``nodes`` {node: cpulist}, ``chips`` the NUMA node
    of each chip, as ``class/accel`` devices or as Google PCI devices."""
    for n, cpus in nodes.items():
        d = root / f"devices/system/node/node{n}"
        d.mkdir(parents=True)
        (d / "cpulist").write_text(cpus + "\n")
    for i, node in enumerate(chips):
        if accel:
            d = root / f"class/accel/accel{i}/device"
        else:
            d = root / f"bus/pci/devices/0000:00:{i + 4:02x}.0"
            (root / "bus/pci/devices/0000:00:01.0").mkdir(parents=True,
                                                         exist_ok=True)
            (root / "bus/pci/devices/0000:00:01.0/vendor").write_text(
                "0x8086\n")
            (root / "bus/pci/devices/0000:00:01.0/numa_node").write_text(
                "0\n")
        d.mkdir(parents=True)
        (d / "numa_node").write_text(f"{node}\n")
        if not accel:
            (d / "vendor").write_text("0x1ae0\n")
    return str(root)


def test_cpulists_round_trip():
    assert placement.parse_cpulist("0-3,8,10-11\n") == {0, 1, 2, 3, 8, 10, 11}
    assert placement.format_cpulist({0, 1, 2, 3, 8, 10, 11}) == "0-3,8,10-11"
    assert placement.parse_cpulist("") == set()


@pytest.mark.parametrize("accel", [True, False])
def test_picks_the_cpus_of_the_chips_node(tmp_path, accel):
    sys = _layout(tmp_path, {0: "0-3", 1: "4-7"}, [1, 0], accel=accel)
    assert placement.chip_nodes(1, sys) == [1]
    assert placement.chip_node_cpus(1, set(range(8)), sys) == {4, 5, 6, 7}
    # only the CPUs the process may already run on
    assert placement.chip_node_cpus(1, {0, 1, 5}, sys) == {5}
    # two chips on two nodes: nothing to narrow
    assert placement.chip_node_cpus(2, set(range(8)), sys) is None


@pytest.mark.parametrize("nodes,chips,allowed", [
    ({0: "0-7"}, [0], set(range(8))),               # one node
    ({0: "0-3", 1: "4-7"}, [-1], set(range(8))),    # chip without a node
    ({0: "0-3", 1: "4-7"}, [], set(range(8))),      # no chip found
    ({0: "0-3", 1: "4-7"}, [1], {0, 1}),            # no allowed CPU there
    ({0: "0-3", 1: "4-7"}, [1], {4, 5}),            # already bound there
])
def test_does_nothing_without_a_second_node_or_a_chip_node(
        tmp_path, nodes, chips, allowed):
    sys = _layout(tmp_path, nodes, chips)
    assert placement.chip_node_cpus(1, allowed, sys) is None


def test_bind_sets_only_this_process_affinity(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(8)))
    monkeypatch.setattr(os, "sched_setaffinity",
                        lambda pid, cpus: calls.append((pid, set(cpus))))
    two = _layout(tmp_path / "two", {0: "0-3", 1: "4-7"}, [1])
    assert placement.bind_to_chip_node(1, two) == {4, 5, 6, 7}
    assert calls == [(0, {4, 5, 6, 7})]
    one = _layout(tmp_path / "one", {0: "0-7"}, [0])
    assert placement.bind_to_chip_node(1, one) is None
    assert placement.bind_to_chip_node(1, str(tmp_path / "empty")) is None
    assert len(calls) == 1
    line = placement.placement_line(1, {4, 5, 6, 7}, two)
    assert "chip node(s) 1" in line and "bound to 4-7" in line


def test_buffer_pages_of_a_host_array():
    a = np.ones((64, 1024, 16), np.float32)         # 4 MiB, touched
    p = placement.buffer_pages(a)
    assert p["offset"] == a.ctypes.data % placement.PAGE
    assert p["rss_mb"] >= a.nbytes / 2 ** 20 * 0.99
    assert 0 <= p["huge_mb"] <= p["rss_mb"]
