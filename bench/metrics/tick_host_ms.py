"""Host time per control-plane flush: the benchmark's span around each
`FleetService.tick()` less the device-busy time inside it, mean per flush,
in ms.  Layer: flush host work (`FleetService.tick`, `_chunk`)."""


def read(trace, ctx):
    return trace.host_self_ms("bench.tick")
