"""How long an operator read waits for the service lock: the 95th
percentile of the program's ``repro.service.lock_wait.snapshot`` spans
(`FleetService.snapshot`, behind ``GET /telemetry``) in the window, in ms.
Layer: HTTP front end."""
from bench.program_spans import quantile_ms


def read(trace, ctx):
    return quantile_ms(trace, "repro.service.lock_wait.snapshot", 95)
