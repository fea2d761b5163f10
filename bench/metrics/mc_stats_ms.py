"""Host time per Monte Carlo experiment of its statistics: the program's
``repro.mc.stats`` span (`MCResult.stats()`, with its host syncs), summed
over the window and divided by its experiments, in ms.  Layer: Monte Carlo
driver."""
from bench.program_spans import per_unit_ms


def read(trace, ctx):
    return per_unit_ms(trace, ctx, "repro.mc.stats")
