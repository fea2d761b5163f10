"""Device time per flush of the trace's HLO sort operations (the flush
telemetry's percentiles, `FleetEngine._traces_record`), in ms.  Layer:
engine telemetry.  Nothing is returned when the window ran no sort."""


def is_sort(op) -> bool:
    return op.category == "sort"


def read(trace, ctx):
    win = trace.window()
    units = trace.spans_named(ctx["unit_span"])
    if win is None or not units:
        return None
    sorts = [o for o in trace.ops_in(win.start, win.end) if is_sort(o)]
    if not sorts:
        return None
    devices = max(len({o.device for o in trace.ops}), 1)
    return sum(o.dur for o in sorts) / devices / len(units) * 1e-6
