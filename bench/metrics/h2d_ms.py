"""Time of one density chunk's host-to-device copy, from the profile: the
union of the copies inside the window, over the flushes inside it, in ms.
A copy (`Trace.transfers`) runs from the runtime's issue event
(``tpu::System::TransferToDevice``, on a ``pjrt-tpu-tasks`` thread) to its
completion event (``...=>IssueEvent=>Done``, on the runtime's event
thread): runtime events on the host plane, since the TPU plane of a v5e
profile holds no copy events.  Each flush uploads one chunk (`stream()` puts two before the
first flush and none after the last), so this is the mean over the
window's uploads.  Layer: ingest.  It gets no share of a peak:
``bench/peaks.json`` holds no host-link peak for the chip, so the achieved
rate (the chunk's bytes over this time) is printed as a note instead.
Nothing is returned when the window holds no copy or no flush."""
from bench.harness import say
from bench.trace import union_ns


def read(trace, ctx):
    win = trace.window()
    if win is None:
        return None
    copies = [(max(o.start, win.start), min(o.start + o.dur, win.end))
              for o in trace.transfers
              if o.start < win.end and o.start + o.dur > win.start]
    uploads = [s for s in trace.spans_named(ctx["unit_span"])
               if s.start >= win.start and s.end <= win.end]
    if not copies or not uploads:
        return None
    ms = union_ns(copies) / len(uploads) * 1e-6
    if ctx.get("chunk_bytes"):
        say(f"[ingest] h2d_ms {ms:.3f} ms a chunk of "
            f"{ctx['chunk_bytes'] / 1e6:.1f} MB: "
            f"{ctx['chunk_bytes'] / ms * 1e-6:.3f} GB/s")
    return ms
