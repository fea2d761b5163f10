"""Host time per Monte Carlo experiment: the benchmark's span around each
`montecarlo.run` and its `.stats()` less the device-busy time inside it,
mean per experiment, in ms.  Layer: Monte Carlo driver
(`core/montecarlo.run`)."""


def read(trace, ctx):
    return trace.host_self_ms("bench.mc_run")
