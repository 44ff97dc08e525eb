"""Share of the traced span in which no operation ran on the device, in %:
1 - busy / span, with busy the union of the op intervals of the device's
``XLA Ops`` line in the profiler trace (``xplane.py``)."""


def read(rec):
    trace, window = rec["trace"], rec.get("trace_window_s")
    if trace is None or not window:
        return None
    return float((1.0 - trace["busy_s"] / window) * 100.0)
