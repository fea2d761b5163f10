"""Host time per Monte Carlo experiment of its set-up: the program's
``repro.mc.sample`` span (the per-trial draws, density traces and pole
banks) and its two ``repro.mc.setup`` spans (each fleet's engine,
parameters and initial state), summed over the window and divided by its
experiments, in ms.  Layer: Monte Carlo driver (`core/montecarlo.run`)."""
from bench.program_spans import per_unit_ms


def read(trace, ctx):
    return per_unit_ms(trace, ctx, "repro.mc.sample", "repro.mc.setup")
