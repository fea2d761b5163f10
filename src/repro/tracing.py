"""Host spans of the program, on the profiler's clock.

`span(name)` opens a `jax.profiler.TraceAnnotation` named ``repro.<name>``.
A profiler started around the program (`jax.profiler.start_trace`, or the
benchmark's ``--trace 1`` run) records it beside the device's operations;
with no profiler running it costs under a microsecond, so the profiler's
own on/off is the only switch.  Spans sit at layer boundaries, never inside
a per-lane, per-step or per-tile loop.  Each span has a reader, and a span
carries no args: nothing reads them.

Every span the program opens, the benchmark's per-layer metric
(``bench/metrics/<metric>.py``) that reads it, and what it covers:

- ``repro.service.chunk`` (chunk_synth_ms): `FleetService._chunk`'s
  synthesis of a flush's density chunk.
- ``repro.service.record`` (log_record_ms): `FleetService.tick` building
  the flush record, the chunk as a list among it, and logging it.
- ``repro.service.lock_wait.snapshot`` (lock_wait_ms):
  `FleetService.snapshot`'s wait for the service lock, behind
  ``GET /telemetry``.
- ``repro.ingest.put`` (put_enqueue_ms): `stream()` handing one chunk to
  `put_trace`; the copy itself runs asynchronously, outside the span.
- ``repro.engine.run_block`` (block_dispatch_ms): `FleetEngine.run_block`'s
  checks, active mask and dispatch.
- ``repro.mc.sample`` (mc_setup_ms): `montecarlo.run`'s draws, density
  traces and pole banks.
- ``repro.mc.setup`` (mc_setup_ms): one fleet's engine, parameters and
  initial state.
- ``repro.mc.stats`` (mc_stats_ms): `MCResult.stats()`, with its host
  syncs.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str) -> TraceAnnotation:
    """A host span named ``repro.<name>``."""
    return TraceAnnotation(PREFIX + name)
