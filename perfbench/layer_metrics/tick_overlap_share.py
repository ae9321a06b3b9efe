"""Serving engine: of the window's decode ticks, the share dispatched
while the tick before was still unread (``serving.decode`` spans with
``inflight`` = 1): how often the engine kept one program in flight. Over
the spans outside the traced stretch, as ``tick_host_ms_p50`` takes them;
unlike the lifetime counters (``decode_ticks_overlapped`` against
``decode_ticks_flushed``) it leaves the set-up's drains out. None from a
program whose ``serving.decode`` carries no ``inflight``."""


def read(run):
    ticks = [s.attrs["inflight"]
             for s in run.spans_named("serving.decode", untraced_only=True)
             if "inflight" in s.attrs and s.end_s <= run.window[1]]
    return sum(1 for i in ticks if i) / len(ticks) if ticks else None
