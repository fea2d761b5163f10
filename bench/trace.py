"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operations and the benchmark's own host spans, on the
profiler's one clock.

`Trace.from_xplane` reads the file with `jax.profiler.ProfileData`; `Trace`
round-trips through a small JSON form (`to_json` / `from_json`), which is
how the recorded test trace is kept.  Host-to-device copies are kept apart
from the operations (`Trace.transfers`), so that they count in no op's
busy time.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
# lines of a TPU plane that hold one event per executed HLO operation
OP_LINES = ("XLA Ops",)
# A host-to-device copy shows on the host plane as two runtime events: its
# issue on a ``pjrt-tpu-tasks`` thread and its completion, seen by the
# runtime's event thread (bench/metrics/h2d_ms.py).  The TPU plane holds
# no copy events.
TRANSFER_ISSUE = "tpu::System::TransferToDevice"
TRANSFER_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"


def parse_hlo(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an op event named by its HLO text,
    ``%name = <shape> opcode(operands), attributes``; a custom call's opcode
    is followed by its target."""
    if " = " not in text:
        return text, ""
    lhs, rhs = text.split(" = ", 1)
    rhs = rhs.lstrip()
    rest = ""
    if rhs.startswith("("):                    # tuple shape
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rhs[i + 1:]
                break
    elif " " in rhs:
        rest = rhs.split(" ", 1)[1]
    opcode = rest.strip().split("(", 1)[0].strip()
    if opcode == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', rest)
        opcode = f"custom-call:{m.group(1)}" if m else opcode
    return lhs.strip().lstrip("%"), opcode


def _span(a: float, b: float) -> tuple[float, float]:
    return a, b - a


def pair_transfers(marks) -> list:
    """Copies from their runtime events: ``marks`` [(start, dur, is_done)];
    each completion closes the earliest open issue that began before it (a
    chunk's copies complete in the order they were issued).  Each copy is an
    Op from its issue's start to its completion's end."""
    out, open_ = [], []
    for start, dur, done in sorted(marks):
        if not done:
            open_.append(start)
        elif open_:
            first = open_.pop(0)
            out.append(Op("h2d", "transfer", first, start + dur - first, 0))
    return out


def union_ns(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total if cur_e is None else total + cur_e - cur_s


@dataclass
class Op:
    name: str           # HLO instruction name, e.g. "fusion.12", "sort.3"
    category: str       # HLO opcode, e.g. "fusion", "sort"; a custom call
    #                     carries its target: "custom-call:tpu_custom_call"
    start: float        # ns, profiler clock
    dur: float          # ns
    device: int


@dataclass
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    n_devices: int = 0
    transfers: list = field(default_factory=list)   # Op: issue to done

    # ---------------------------------------------------------- reading
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        tr = cls()
        dev_ids, marks = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = dev_ids.setdefault(plane.name, len(dev_ids))
                for line in plane.lines:
                    if line.name not in OP_LINES:
                        continue
                    for ev in line.events:
                        name, opcode = parse_hlo(ev.name)
                        tr.ops.append(Op(name, opcode, float(ev.start_ns),
                                         float(ev.duration_ns), dev))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            tr.spans.append(Span(ev.name, float(ev.start_ns),
                                                 float(ev.duration_ns)))
                        elif ev.name in (TRANSFER_ISSUE, TRANSFER_DONE):
                            marks.append((float(ev.start_ns),
                                          float(ev.duration_ns),
                                          ev.name == TRANSFER_DONE))
        tr.n_devices = len(dev_ids)
        tr.ops.sort(key=lambda o: o.start)
        tr.transfers = pair_transfers(marks)
        tr.spans.sort(key=lambda s: s.start)
        return tr

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        found = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_xplane(found[-1])

    def to_json(self, path: str) -> None:
        data = {"n_devices": self.n_devices,
                "ops": [[o.name, o.category, o.start, o.dur, o.device]
                        for o in self.ops],
                "spans": [[s.name, s.start, s.dur] for s in self.spans],
                "transfers": [[o.name, o.category, o.start, o.dur, o.device]
                              for o in self.transfers]}
        with gzip.open(path, "wt") as f:
            json.dump(data, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return cls(ops=[Op(*o) for o in data["ops"]],
                   spans=[Span(*s) for s in data["spans"]],
                   n_devices=data["n_devices"],
                   transfers=[Op(*o) for o in data.get("transfers", [])])

    def crop(self, start: float, end: float) -> "Trace":
        """The part of the trace in [start, end), spans clipped to it, with
        a ``bench.window`` span over the whole crop (how the recorded test
        traces were cut down from chip runs)."""
        clip = lambda a, b: (max(a, start), min(b, end))
        ops = [Op(o.name, o.category, *_span(*clip(o.start, o.start + o.dur)),
                  o.device) for o in self.ops_in(start, end)]
        spans = [Span(s.name, *_span(*clip(s.start, s.end)))
                 for s in self.spans if s.start < end and s.end > start
                 and s.name != "bench.window"]
        spans.append(Span("bench.window", start, end - start))
        spans.sort(key=lambda s: s.start)
        transfers = [Op(o.name, o.category,
                        *_span(*clip(o.start, o.start + o.dur)), o.device)
                     for o in self.transfers
                     if o.start < end and o.start + o.dur > start]
        return Trace(ops=ops, spans=spans, n_devices=self.n_devices,
                     transfers=transfers)

    # ------------------------------------------------------- reductions
    def window(self, name: str = "bench.window") -> Span | None:
        for s in self.spans:
            if s.name == name:
                return s
        return None

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def ops_in(self, start: float, end: float, device: int | None = None):
        """Ops (sorted by start) that overlap [start, end)."""
        if getattr(self, "_starts", None) is None or \
                len(self._starts) != len(self.ops):
            self._starts = [o.start for o in self.ops]
            self._longest = max((o.dur for o in self.ops), default=0.0)
        lo = bisect.bisect_left(self._starts, start - self._longest)
        hi = bisect.bisect_left(self._starts, end)
        return [o for o in self.ops[lo:hi]
                if o.start + o.dur > start
                and (device is None or o.device == device)]

    def busy_ns(self, start: float, end: float, device: int) -> float:
        """Length of the union of ``device``'s op intervals, clipped to
        [start, end]."""
        return union_ns((max(o.start, start), min(o.start + o.dur, end))
                        for o in self.ops_in(start, end, device))

    def mean_busy_ns(self, start: float, end: float) -> float:
        """Busy time averaged over the devices that ran any operation."""
        devs = sorted({o.device for o in self.ops})
        if not devs:
            return 0.0
        return sum(self.busy_ns(start, end, d) for d in devs) / len(devs)

    def host_self_ms(self, span_name: str) -> float | None:
        """Mean over the spans named ``span_name`` of the span's length less
        the device-busy time inside it (averaged over devices), in ms."""
        spans = self.spans_named(span_name)
        if not spans:
            return None
        return sum(s.dur - self.mean_busy_ns(s.start, s.end)
                   for s in spans) / len(spans) * 1e-6

    def idle_gaps(self, start: float, end: float, device: int = 0):
        """[(gap_start, gap_end)] in which ``device`` ran nothing."""
        gaps, t = [], start
        for o in self.ops_in(start, end, device):
            if o.start > t:
                gaps.append((t, o.start))
            t = max(t, o.start + o.dur)
        if t < end:
            gaps.append((t, end))
        return gaps

    def breakdown(self, start: float, end: float, top: int = 10) -> dict:
        """The device operations that took most time (summed by name over
        all devices, in seconds) and the longest idle gaps of device 0,
        each named by the benchmark spans (on any host thread) that cover
        its middle."""
        per_op: dict[str, float] = {}
        for o in self.ops_in(start, end):
            key = f"{o.name} ({o.category})" if o.category else o.name
            per_op[key] = per_op.get(key, 0.0) + o.dur * 1e-9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(start, end),
                      key=lambda g: g[0] - g[1])[:top]
        named = []
        for g0, g1 in gaps:
            mid = 0.5 * (g0 + g1)
            cover = sorted({s.name for s in self.spans
                            if s.start <= mid <= s.end
                            and s.name != "bench.window"})
            label = "+".join(cover) if cover else "outside benchmark spans"
            named.append([label, (g1 - g0) * 1e-9])
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
