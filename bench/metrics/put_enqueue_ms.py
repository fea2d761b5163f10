"""Host time to hand one density chunk to the device: the program's
``repro.ingest.put`` span (`stream()` calling `put_trace`), mean over the
window's uploads, in ms.  `put_trace` returns once the copy is enqueued, so
this is the host's share of an upload; the copy itself runs asynchronously
and is not in it.  Layer: ingest."""
from bench.program_spans import mean_ms


def read(trace, ctx):
    return mean_ms(trace, "repro.ingest.put")
