"""The program's host spans (`repro.tracing`): under the profiler each layer
boundary opens its span once per unit of work, and what the program
computes is the same with the profiler on and off."""
import glob
import json
import urllib.request

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import montecarlo
from repro.core.scheduler import SchedulerConfig
from repro.fleet import FleetEngine, stream
from repro.fleet.ingest import chunk_source
from repro.fleet.service import FleetService, serve_http

PACKAGES, STEPS, TILES, CHUNKS = 4, 10, 2, 3


def _service():
    """A served fleet of four packages: one flush, then one operator read
    over HTTP.  Returns the flush record and the read's answer."""
    svc = FleetService(SchedulerConfig(n_tiles=1), flush_every=STEPS,
                       min_capacity=PACKAGES, seed=3)
    svc.warmup(PACKAGES)
    for i in range(PACKAGES):
        svc.attach(f"pkg{i}", tenant=f"t{i % 2}")
    server, _ = serve_http(svc, port=0)
    try:
        with jax.profiler.TraceAnnotation("test.tick"):
            record = svc.tick()
        url = f"http://127.0.0.1:{server.server_address[1]}/telemetry?last=1"
        with urllib.request.urlopen(url, timeout=60) as r:
            read = json.loads(r.read())
    finally:
        server.shutdown()
        server.server_close()
    return record, read


def _stream():
    eng = FleetEngine(SchedulerConfig(n_tiles=TILES))
    trace = np.random.default_rng(0).uniform(
        0.9, 2.0, (CHUNKS * STEPS, PACKAGES, TILES)).astype(np.float32)
    _, flushed, _ = stream(eng, eng.init(PACKAGES),
                           chunk_source(trace, STEPS))
    return flushed


def _montecarlo():
    return montecarlo.run(key=jax.random.PRNGKey(5), n_trials=16,
                          n_steps=200, burn_in=50).stats()


def _everything():
    return _service(), _stream(), _montecarlo()


def _events(path):
    """[(name, start_ns, end_ns, thread, args)] of the host threads'
    ``repro.`` and ``test.`` events; ``thread`` tells host lines apart."""
    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("repro.", "test.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, (p, t),
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """(results with the profiler off, results with it on, the traced
    events).  The first pass also compiles every program."""
    off = _everything()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        on = _everything()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True))
    return off, on, _events(path[-1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_every_span_once_per_unit_of_work(runs):
    _, _, ev = runs
    want = {"repro.service.chunk": 1, "repro.service.record": 1,
            "repro.service.lock_wait.snapshot": 1,
            "repro.ingest.put": CHUNKS, "repro.engine.run_block": CHUNKS,
            "repro.mc.sample": 1, "repro.mc.setup": 2, "repro.mc.stats": 1}
    got = {}
    for e in ev:
        if e[0].startswith("repro."):
            got[e[0]] = got.get(e[0], 0) + 1
    assert got == want


def test_spans_carry_no_args(runs):
    _, _, ev = runs
    spans = [e for e in ev if e[0].startswith("repro.")]
    assert spans and all(e[4] == {} for e in spans)


def test_flush_spans_lie_inside_the_tick_and_the_read_on_its_own_thread(
        runs):
    _, _, ev = runs
    (tick,) = _named(ev, "test.tick")
    for name in ("repro.service.chunk", "repro.service.record"):
        (s,) = _named(ev, name)
        assert tick[1] <= s[1] and s[2] <= tick[2], name
    (read,) = _named(ev, "repro.service.lock_wait.snapshot")
    assert read[3] != tick[3]               # an HTTP handler's thread
    assert read[1] >= tick[2]


def test_results_are_bitwise_the_same_with_the_profiler_on(runs):
    off, on, _ = runs
    (rec_off, read_off), flushed_off, mc_off = off
    (rec_on, read_on), flushed_on, mc_on = on
    assert rec_on == rec_off and read_on == read_off
    assert flushed_on == flushed_off
    assert mc_on == mc_off
