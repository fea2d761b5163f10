"""Share of the measured window in which the device ran no operation:
100 * (1 - union of device op intervals / window), averaged over the
chips used.  Layer: device.  Source: the profiler trace."""


def read(trace, ctx):
    win = trace.window()
    if win is None or not trace.ops:
        return None
    return 100.0 * (1.0 - trace.mean_busy_ns(win.start, win.end) / win.dur)
