"""The per-layer metrics that read the program's own spans
(`bench/program_spans.py`): each reads the hand-computed value of a trace
recorded on the CPU and nothing when its spans are absent; the readers
that were there before read the same with the program's spans in a trace
as without them; and a traced run of each cell reports its new metrics."""
import copy
import threading
import time

import pytest

from bench import harness, program_spans
from bench.trace import Span, Trace
from bench_cells import run

ROOT = harness.ROOT
DATA = ROOT / "bench" / "testdata"

NEW = {"chunk_synth_ms": "serve_synth_4k", "log_record_ms": "serve_synth_4k",
       "lock_wait_ms": "serve_synth_4k", "put_enqueue_ms": "stream_pvc47_aurora",
       "block_dispatch_ms": "stream_pvc47_aurora",
       "mc_setup_ms": "mc_sec10_2k", "mc_stats_ms": "mc_sec10_2k"}
OLD = ("device_idle_pct", "tick_host_ms", "h2d_ms",
       "fleet_step_roofline", "mc_host_ms")
UNIT = {"serve_synth_4k": "bench.tick", "stream_pvc47_aurora": "bench.flush",
        "mc_sec10_2k": "bench.mc_run"}


def _ctx(cell):
    bm = harness.load_benchmark()
    c, entry = harness.find_cell(bm, cell)
    cfg = harness.load_config(entry)
    traffic = harness.load_traffic(c["traffic"])
    ctx = harness.driver_class(traffic)(cfg, traffic, 1, []).trace_context()
    ctx["peaks"] = harness.peaks("TPU v5 lite")
    return ctx


def _spans(name, n, pause=0.002):
    from repro.tracing import span
    for i in range(n):
        with span(name):
            time.sleep(pause * (i + 1))


def _record(tmp):
    """A profiler trace of every program span in the benchmark's layout:
    three ticks, two flushes, two experiments and five operator reads on a
    thread of their own inside ``bench.window``, and one span before it."""
    import jax
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp))
    _spans("service.chunk", 1)                      # outside the window
    with TraceAnnotation("bench.window"):
        reads = threading.Thread(target=_spans, args=(
            "service.lock_wait.snapshot", 5, 0.003))
        reads.start()
        for f in range(3):
            with TraceAnnotation("bench.tick"):
                _spans("service.chunk", 1, 0.004 * (f + 1))
                _spans("service.record", 1)
        _spans("ingest.put", 1)
        for _ in range(2):
            with TraceAnnotation("bench.flush"):
                _spans("engine.run_block", 1)
                _spans("ingest.put", 1)
        for _ in range(2):
            with TraceAnnotation("bench.mc_run"):
                _spans("mc.sample", 1)
                _spans("mc.setup", 2)
                _spans("mc.stats", 1)
        reads.join()
    jax.profiler.stop_trace()


def _raw(tmp):
    """Host events of the recorded file, read here without the module
    under test: {name: [(start, dur)]} and the window."""
    import glob

    from jax.profiler import ProfileData
    path = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    ev = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                ev.setdefault(e.name, []).append((e.start_ns, e.duration_ns))
    (win,) = ev["bench.window"]
    inside = lambda name: [d for s, d in ev.get(name, [])
                           if s >= win[0] and s + d <= win[0] + win[1]]
    return inside


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("program_spans")
    _record(tmp)
    return tmp


def _read(name, tr, cell):
    return harness.metric_reader(name)(tr, _ctx(cell))


def test_new_readers_read_the_hand_computed_values(recorded, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", recorded)
    inside = _raw(recorded)
    ms = lambda ns: ns * 1e-6
    per = lambda names, unit: ms(sum(d for n in names for d in inside(n))
                                 / len(inside(unit)))
    snap = sorted(inside("repro.service.lock_wait.snapshot"))
    want = {
        "chunk_synth_ms": per(["repro.service.chunk"], "bench.tick"),
        "log_record_ms": per(["repro.service.record"], "bench.tick"),
        "lock_wait_ms": ms(snap[3] + 0.8 * (snap[4] - snap[3])),
        "put_enqueue_ms": ms(sum(inside("repro.ingest.put")) / 3),
        "block_dispatch_ms": per(["repro.engine.run_block"], "bench.flush"),
        "mc_setup_ms": per(["repro.mc.sample", "repro.mc.setup"],
                           "bench.mc_run"),
        "mc_stats_ms": per(["repro.mc.stats"], "bench.mc_run"),
    }
    tr = Trace.from_dir(str(recorded))
    assert not any(s.name.startswith("repro.") for s in tr.spans)
    for name, cell in NEW.items():
        assert _read(name, tr, cell) == pytest.approx(want[name],
                                                      rel=1e-12), name
    # a trace that holds the program's spans already reads the same
    merged = Trace(ops=tr.ops, n_devices=tr.n_devices, spans=sorted(
        tr.spans + program_spans.spans(tr), key=lambda s: s.start))
    monkeypatch.setattr(program_spans, "TRACE_DIR", recorded / "none")
    for name, cell in NEW.items():
        assert _read(name, merged, cell) == pytest.approx(want[name],
                                                          rel=1e-12), name


def test_new_readers_return_none_without_the_program_spans(
        recorded, tmp_path, monkeypatch):
    import jax
    from jax.profiler import TraceAnnotation
    # a program that opens no span: the profile is found, and is empty
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        for unit in UNIT.values():
            with TraceAnnotation(unit):
                time.sleep(0.001)
    jax.profiler.stop_trace()
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    bare = Trace.from_dir(str(tmp_path))
    # no profile at all: the recorded chip traces, and a profile of
    # another window
    others = [Trace.from_json(str(p)) for p in DATA.glob("*.trace.json.gz")]
    stranger = Trace.from_dir(str(recorded))
    for name, cell in NEW.items():
        for tr in [bare, stranger] + others:
            assert _read(name, tr, cell) is None, name


def _with_program_spans(tr, unit):
    """``tr`` with program spans laid over it: one inside each unit, one
    over each of the first 50 idle gaps, and one over the whole window."""
    out = copy.deepcopy(tr)
    win = tr.window()
    extra = [Span("repro.engine.run_block", s.start, s.dur / 2)
             for s in tr.spans_named(unit)]
    extra += [Span("repro.service.lock_wait.snapshot", a, b - a)
              for a, b in tr.idle_gaps(win.start, win.end)[:50]]
    extra.append(Span("repro.mc.sample", win.start, win.dur))
    out.spans = sorted(out.spans + extra, key=lambda s: s.start)
    return out


@pytest.mark.parametrize("cell", sorted(UNIT))
def test_old_readers_read_the_same_with_program_spans(cell):
    tr = Trace.from_json(str(DATA / f"{cell}.trace.json.gz"))
    spanned = _with_program_spans(tr, UNIT[cell])
    ctx = _ctx(cell)
    for name in OLD:
        reader = harness.metric_reader(name)
        assert reader(spanned, ctx) == reader(tr, ctx), name


def test_old_readers_read_the_same_on_the_cpu_trace(recorded, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", recorded)
    tr = Trace.from_dir(str(recorded))
    merged = copy.deepcopy(tr)
    merged.spans = sorted(tr.spans + program_spans.spans(tr),
                          key=lambda s: s.start)
    for cell in UNIT:
        ctx = _ctx(cell)
        for name in OLD:
            reader = harness.metric_reader(name)
            assert reader(merged, ctx) == reader(tr, ctx), (cell, name)


@pytest.mark.parametrize("cell", sorted(UNIT))
def test_a_traced_run_reports_the_program_metrics(cell, tmp_path,
                                                  monkeypatch):
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path))
    r = run(cell, trace=True)
    got = r["metrics"]
    for name, c in NEW.items():
        if c == cell:
            assert got[name]["value"] > 0 and got[name]["unit"] == "ms"
        else:
            assert name not in got
    # the parts lie inside the wall time of the unit of work they split
    kept = Trace.from_json(str(tmp_path / "trace.json.gz"))
    win = kept.window()
    units = [s.dur for s in kept.spans_named(UNIT[cell])
             if s.start >= win.start and s.end <= win.end]
    unit_ms = sum(units) / len(units) * 1e-6
    parts = {"serve_synth_4k": ("chunk_synth_ms", "log_record_ms"),
             "stream_pvc47_aurora": ("block_dispatch_ms",),
             "mc_sec10_2k": ("mc_setup_ms", "mc_stats_ms")}[cell]
    assert sum(got[p]["value"] for p in parts) <= unit_ms
