"""Host time per control-plane flush of the density chunk's synthesis: the
program's ``repro.service.chunk`` span (`FleetService._chunk`), summed over
the window and divided by its flushes, in ms.  Layer: flush host work."""
from bench.program_spans import per_unit_ms


def read(trace, ctx):
    return per_unit_ms(trace, ctx, "repro.service.chunk")
