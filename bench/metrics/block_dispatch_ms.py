"""Host time per flush of the engine's block call: the program's
``repro.engine.run_block`` span (`FleetEngine.run_block`: its checks, the
active mask and the dispatch), summed over the window and divided by its
flushes, in ms.  Layer: engine dispatch."""
from bench.program_spans import per_unit_ms


def read(trace, ctx):
    return per_unit_ms(trace, ctx, "repro.engine.run_block")
