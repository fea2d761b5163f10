"""The benchmark harness on the CPU: the contract's shape of
``BENCHMARK.json``, discovery by name, the result line, the refusal to run
without a TPU, the kernel bytes function, and the trace reducers against
small traces recorded on a TPU v5e."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.kernel_bytes import (experiment_bytes, fleet_step_bytes,
                                flush_bytes)
from bench.trace import Trace, parse_hlo
from bench_cells import last_json, run

ROOT = harness.ROOT
DATA = ROOT / "bench" / "testdata"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# ------------------------------------------------------- BENCHMARK.json
def test_benchmark_file_keeps_the_contract_shape():
    bm = harness.load_benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= bm["run_seconds"] <= 51
    for p in bm["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bm["paths"])
    cells = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
    assert {c for c, _ in cells} == set(configs)
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and m["layer"] and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in bm["workloads"]:
        reported = harness.cell_metrics(bm, {"name": w["name"]},
                                        "end_to_end")
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert harness.cell_metrics(bm, {"name": w["name"]}, "per_layer")


def test_every_name_in_the_benchmark_finds_its_files():
    bm = harness.load_benchmark()
    for w in bm["workloads"]:
        cell, entry = harness.find_cell(bm, w["name"])
        cfg = harness.load_config(entry)
        traffic = harness.load_traffic(cell["traffic"])
        drv = harness.driver_class(traffic)
        for k in ("setup", "run", "end_to_end", "notes", "trace_context",
                  "release", "check", "control"):
            assert callable(getattr(drv, k))
        assert set(cfg["limits"]) and all(v >= 0 for v in
                                          cfg["limits"].values())
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    with pytest.raises(harness.BenchError):
        harness.find_cell(bm, "no_such_cell")


def test_unlisted_device_is_an_error_not_a_default():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")


# ------------------------------------------------------------ the CLI
def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve_synth_4k",
         "--seed", "2147483999", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_to_run_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_cli_refuses_in_a_directory_of_only_the_benchmark(tmp_path):
    bm = harness.load_benchmark()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_result_line_schema(capsys):
    r = run("serve_synth_4k", emit=True)
    out, err = capsys.readouterr()
    line = last_json(out)
    assert line == json.loads(json.dumps(r))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert isinstance(line["correct"], bool)
    assert isinstance(line["attempted"], int)
    assert isinstance(line["failed"], int)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("[check] ") and " limit " in t for t in tail)
    assert "XLA compiles inside the window" in out


# ------------------------------------------------- adding by new files
NEW_METRIC = '''
def read(trace, ctx):
    """Flushes in the traced window."""
    n = len(trace.spans_named(ctx["unit_span"]))
    return float(n) if n else None
'''

RUN_NEW_CELL = '''
import json, sys, time
sys.path[:0] = [{tmp!r}, {src!r}]
import jax
from bench import harness
bm = harness.load_benchmark()
cell, entry = harness.find_cell(bm, "tiny_serve")
r = harness.run_cell(bm, cell, harness.load_config(entry), 2**31 + 3, 0.5,
                     True, time.perf_counter(), jax.devices(),
                     require_tpu=False, emit=False)
print(json.dumps(r))
'''


def test_a_config_mix_and_metric_are_added_by_new_files_only(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix and a
    per-layer metric as new files plus new BENCHMARK.json entries; the
    harness finds them by name and no existing file changes."""
    bm = harness.load_benchmark()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = harness.load_json(ROOT / "bench/configs/v24_serve_1tile.json")
    cfg.update(name="tiny_serve_cfg", fleet_packages=8)
    cfg["service"]["min_capacity"] = 8
    (tmp_path / "bench/configs/tiny_serve_cfg.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/tiny_reads.json").write_text(json.dumps(
        {"driver": "serve", "tenants": 2, "canary_frac": 0.5,
         "read_path": "/telemetry?last=1", "read_rate_per_s": 20.0,
         "read_workers": 4, "read_timeout_s": 60.0}))
    (tmp_path / "bench/metrics/flush_count.py").write_text(NEW_METRIC)
    bm["configs"].append({"name": "tiny_serve_cfg", "source": "test",
                          "file": "bench/configs/tiny_serve_cfg.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny_serve", "config": "tiny_serve_cfg",
                            "traffic": "tiny_reads", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"]:
        if "workloads" in m and m["name"] == "api_p95_ms":
            m["workloads"].append("tiny_serve")
    bm["per_layer"].append({"name": "flush_count", "unit": "1",
                            "better": "higher", "source": "device_trace",
                            "layer": "flush host work",
                            "moves": "pkg_steps_per_s",
                            "workloads": ["tiny_serve"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    code = RUN_NEW_CELL.format(tmp=str(tmp_path), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = last_json(p.stdout)
    assert r["correct"], r["checks"]
    assert r["metrics"]["flush_count"]["value"] >= 1
    after = {p: p.read_bytes() for p in before}
    assert after == before


# ------------------------------------------------------ kernel bytes
def test_kernel_bytes_from_hand_computed_shapes():
    # control-plane flush: 50 steps x 3,840 packages x 1 tile; state per
    # package (16 ring + 2 poles + 1 freq) + 1 events + 1 latch + 3
    # fallback = 24 words in and out, plus 1 read-only mode pin:
    # 4 * (50*3840 + 3840*(2*24 + 1)) = 1,520,640
    assert fleet_step_bytes(50, 3840, 1, 16, 2, latch=True, fallback=True,
                            mixed=True) == 1_520_640
    serve = harness.load_json(ROOT / "bench/configs/v24_serve_1tile.json")
    assert flush_bytes(serve["scheduler"], 50, 3840) == 1_520_640
    # 47 tiles at 63,744 packages: (16 + 2 + 1)*47 + 1 = 894 words of state
    # 4 * (50*63744*47 + 63744*2*894) = 1,055,090,688
    assert fleet_step_bytes(50, 63744, 47, 16, 2) == 1_055_090_688
    pvc = harness.load_json(ROOT / "bench/configs/v70_pvc47.json")
    assert flush_bytes(pvc["scheduler"], 50, 63744) == 1_055_090_688
    # a paired experiment of 2,000 trials x 3,000 steps: density read by
    # each controller, state 3 (reactive) + 1 + 64 (V24) in and out, 3
    # read-only physics words: 4 * 2000 * (2*3000 + 2*68 + 3) = 49,112,000
    assert experiment_bytes(2000, 3000, 64) == 49_112_000


def test_roofline_does_not_depend_on_how_the_work_is_split_into_calls():
    """The same window with each kernel call split in two, or with all of a
    unit's calls merged into one, reads the same share."""
    import copy

    from bench.metrics import fleet_step_roofline
    from bench.trace import Op
    tr = Trace.from_json(str(DATA / "stream_pvc47_aurora.trace.json.gz"))
    ctx = _context("stream_pvc47_aurora", "bench.flush")
    share = fleet_step_roofline.read(tr, ctx)
    split = copy.deepcopy(tr)
    kern = [o for o in split.ops if fleet_step_roofline.is_kernel(o)]
    for o in kern:
        o.dur /= 2
        split.ops.append(Op(o.name + ".b", o.category, o.start + o.dur,
                            o.dur, o.device))
    split.ops.sort(key=lambda o: o.start)
    assert fleet_step_roofline.read(split, ctx) == pytest.approx(share)
    merged = copy.deepcopy(tr)
    kern = [o for o in merged.ops if fleet_step_roofline.is_kernel(o)]
    merged.ops = [o for o in merged.ops if o not in kern] + [
        Op("k", kern[0].category, kern[0].start, sum(o.dur for o in kern),
           kern[0].device)]
    merged.ops.sort(key=lambda o: o.start)
    assert fleet_step_roofline.read(merged, ctx) == pytest.approx(share)


# -------------------------------------------------------------- traces
def test_parse_hlo_names_and_opcodes():
    assert parse_hlo("%sort.3 = f32[50,4096]{1,0:T(8,128)} sort(f32[50,"
                     "4096]{1,0} %x), dimensions={1}") == ("sort.3", "sort")
    assert parse_hlo("%while.2 = (s32[]{:T(128)}, f32[1]{0:T(128)}) while("
                     "(s32[]) %t), condition=%c") == ("while.2", "while")
    assert parse_hlo('%k.1 = (f32[8]{0}) custom-call(f32[8]{0} %a), '
                     'custom_call_target="tpu_custom_call"') == (
        "k.1", "custom-call:tpu_custom_call")


def test_trace_of_a_cpu_run_round_trips(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.sort(a * 2.0))
    f(jnp.ones(64)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path / "t"))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.flush"):
                f(jnp.ones(64)).block_until_ready()
    jax.profiler.stop_trace()
    tr = Trace.from_dir(str(tmp_path / "t"))
    assert tr.window() is not None and len(tr.spans_named("bench.flush")) == 3
    tr.to_json(str(tmp_path / "t.json.gz"))
    back = Trace.from_json(str(tmp_path / "t.json.gz"))
    assert [s.name for s in back.spans] == [s.name for s in tr.spans]


def _union(intervals):
    total, cur = 0.0, None
    for a, b in sorted(intervals):
        if cur is None or a > cur[1]:
            total += 0 if cur is None else cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    return total + (0 if cur is None else cur[1] - cur[0])


def _busy(tr, a, b):
    return _union([(max(o.start, a), min(o.start + o.dur, b))
                   for o in tr.ops if o.start < b and o.start + o.dur > a])


RECORDED = [("serve_synth_4k", "bench.tick")]
for _cell, _span in (("stream_pvc47_aurora", "bench.flush"),
                     ("mc_sec10_2k", "bench.mc_run")):
    if (DATA / f"{_cell}.trace.json.gz").exists():
        RECORDED.append((_cell, _span))


@pytest.mark.parametrize("cell,span", RECORDED)
def test_reducers_on_a_recorded_trace(cell, span):
    from bench.metrics import device_idle_pct, fleet_step_roofline, h2d_ms
    tr = Trace.from_json(str(DATA / f"{cell}.trace.json.gz"))
    win = tr.window()
    busy = _busy(tr, win.start, win.end)
    ctx = _context(cell, span)
    assert device_idle_pct.read(tr, ctx) == pytest.approx(
        100 * (1 - busy / win.dur), rel=1e-9)
    units = [s for s in tr.spans if s.name == span]
    host = sum(s.dur - _busy(tr, s.start, s.end) for s in units) / len(units)
    assert tr.host_self_ms(span) == pytest.approx(host * 1e-6, rel=1e-9)
    copies = _union([(max(o.start, win.start), min(o.start + o.dur, win.end))
                     for o in tr.transfers
                     if o.start < win.end and o.start + o.dur > win.start])
    got = h2d_ms.read(tr, ctx)
    if copies:
        assert got == pytest.approx(copies / len(units) * 1e-6)
    else:
        assert got is None
    kern = [o.dur for o in tr.ops
            if o.category == "custom-call:tpu_custom_call"]
    want = 100 * ctx["unit_bytes"] * len(units) / 819e9 / (sum(kern) * 1e-9)
    share = fleet_step_roofline.read(tr, ctx)
    assert share == pytest.approx(want) and 0 < share <= 100


def _context(cell, span):
    """The trace context a run of ``cell`` hands the readers."""
    bm = harness.load_benchmark()
    c, entry = harness.find_cell(bm, cell)
    cfg = harness.load_config(entry)
    traffic = harness.load_traffic(c["traffic"])
    drv = harness.driver_class(traffic)(cfg, traffic, 1, [])
    ctx = drv.trace_context()
    ctx["peaks"] = harness.peaks("TPU v5 lite")
    assert ctx["unit_span"] == span
    return ctx


def test_breakdown_lists_ops_and_idle_gaps():
    tr = Trace.from_json(str(DATA / "serve_synth_4k.trace.json.gz"))
    win = tr.window()
    bd = tr.breakdown(win.start, win.end)
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in bd["device_ops"])
    gaps = [s for _, s in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= win.dur * 1e-9 - _busy(tr, win.start, win.end) * 1e-9 \
        + 1e-9


def test_a_nan_never_passes_a_check():
    from bench.drivers import montecarlo, serve, stream
    assert harness.rel_err(float("nan"), 1.0) == float("inf")
    row = {k: 1.0 for k in montecarlo.SMOOTH + montecarlo.ORDER}
    bad = dict(row, v24_std_c=float("nan"))
    assert montecarlo.compare([bad], [row])["stats_err"] == float("inf")
    t = {k: 1.0 for k in stream.TELEMETRY_FIELDS + stream.EVENT_COUNTS
         + ("n_packages",)}
    assert stream.compare({0: dict(t, freq_mean=float("nan"))},
                          {0: t})["telemetry_err"] == float("inf")
    assert stream.compare({0: dict(t, n_packages=float("nan"))},
                          {0: t})["members_err"] == float("inf")
    rec = {"telemetry": {k: 1.0 for k in serve.TELEMETRY_FIELDS
                         + serve.TELEMETRY_COUNTS}, "tenants": {}}
    nan = {"telemetry": dict(rec["telemetry"], temp_p99_c=float("nan")),
           "tenants": {}}
    assert serve.compare([nan], [rec])["telemetry_err"] == float("inf")


def _upload_trace(copies, flushes=3):
    """A window of 1 s with ``flushes`` flushes, each of which uploads one
    chunk, and host-to-device copies at ``copies`` [(start, dur)] ms."""
    from bench.trace import Op, Span
    ms = 1e6
    spans = [Span("bench.window", 0.0, 1000 * ms)]
    spans += [Span("bench.flush", (100 * i + 50) * ms, 80 * ms)
              for i in range(flushes)]
    transfers = [Op("copy", "h2d", a * ms, d * ms, 0) for a, d in copies]
    return Trace(spans=sorted(spans, key=lambda s: s.start), n_devices=1,
                 transfers=transfers)


def test_h2d_ms_reads_the_mean_copy_per_upload(tmp_path, capsys):
    from bench.metrics import h2d_ms
    ctx = {"unit_span": "bench.flush", "chunk_bytes": 600e6}
    # three uploads of 60, 60 and 50 ms; the second copy in two pieces that
    # overlap by 10 ms; one copy outside the window counts nothing
    tr = _upload_trace([(10, 60), (110, 35), (135, 35), (210, 50),
                        (1200, 60)])
    assert h2d_ms.read(tr, ctx) == pytest.approx((60 + 60 + 50) / 3)
    assert "GB/s" in capsys.readouterr().out
    # a copy that runs past the window's end counts up to it
    assert h2d_ms.read(_upload_trace([(980, 60)]), ctx) == pytest.approx(
        20 / 3)
    # the copies survive the recorded form and a crop
    tr.to_json(str(tmp_path / "t.json.gz"))
    back = Trace.from_json(str(tmp_path / "t.json.gz"))
    assert h2d_ms.read(back, ctx) == pytest.approx((60 + 60 + 50) / 3)
    crop = tr.crop(0.0, 150e6)
    assert [(o.start, o.dur) for o in crop.transfers] == [
        (10e6, 60e6), (110e6, 35e6), (135e6, 15e6)]


def test_h2d_ms_reads_nothing_without_copies_or_uploads():
    from bench.metrics import h2d_ms
    ctx = {"unit_span": "bench.flush", "chunk_bytes": 600e6}
    assert h2d_ms.read(_upload_trace([]), ctx) is None
    assert h2d_ms.read(_upload_trace([(10, 60)], flushes=0), ctx) is None
    assert h2d_ms.read(_upload_trace([(1200, 60)]), ctx) is None
    for cell in ("serve_synth_4k", "mc_sec10_2k"):
        tr = Trace.from_json(str(DATA / f"{cell}.trace.json.gz"))
        assert h2d_ms.read(tr, ctx) is None


def test_copies_pair_each_completion_with_the_earliest_open_issue():
    from bench.trace import pair_transfers
    # two copies in flight at once, a completion with no issue before it
    # (its issue left the trace) closes nothing
    marks = [(5.0, 0.1, True), (10.0, 0.2, False), (40.0, 0.1, False),
             (70.0, 0.5, True), (130.0, 0.5, True), (150.0, 0.1, False)]
    got = [(o.start, o.dur) for o in pair_transfers(marks)]
    assert got == [(10.0, 60.5), (40.0, 90.5)]
