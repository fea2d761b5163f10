"""Host time per control-plane flush of its record: the program's
``repro.service.record`` span (`FleetService.tick` building the flush
record, the density chunk as a list among it, and appending it to the
log), summed over the window and divided by its flushes, in ms.  Layer:
flush host work."""
from bench.program_spans import per_unit_ms


def read(trace, ctx):
    return per_unit_ms(trace, ctx, "repro.service.record")
