"""The fleet kernel's share of its HBM roofline, in %: the least bytes of
the units of work (flushes, experiments) the window completed
(`bench/kernel_bytes.py`, from the work's shapes) over the chip's peak HBM
bandwidth, divided by the device time of the kernel's calls in the window.
Only the HBM bound applies: the kernel's arithmetic is f32 VPU work, for
which no peak is published.  Layer: kernel (`kernels/fleet_step.py`).
Nothing is returned when no kernel ran."""


def is_kernel(op) -> bool:
    """The Pallas (Mosaic) kernel: the programs' only TPU custom call."""
    return op.category == "custom-call:tpu_custom_call"


def read(trace, ctx):
    win = trace.window()
    if win is None or not ctx.get("unit_bytes") or not ctx.get("peaks"):
        return None
    units = [s for s in trace.spans_named(ctx["unit_span"])
             if s.start >= win.start and s.end <= win.end]
    ops = [o for o in trace.ops_in(win.start, win.end) if is_kernel(o)]
    if not ops or not units:
        return None
    t = sum(o.dur for o in ops) * 1e-9
    bound = ctx["unit_bytes"] * len(units) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * bound / t
