"""Host time per step outside the monitor's Offload state, in ms: TALP's
per-step rows (``elapsed - offload``) of the steps in the traced span.
Moves the cell's throughput: host time between dispatches is time the
device cannot use."""


def read(rec):
    rows = rec["span"]["rows"]
    if rows is None or len(rows) == 0:
        return None
    return float((rows["elapsed"] - rows["offload"]).mean() * 1e3)
