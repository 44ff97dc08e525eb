"""The whole step's share of the chip's peak bf16 rate, in %: the
operations the model requires for the steps of the traced span (the
benchmark's own count, ``counts.py``) over the span's host-clock length
and the published peak (``peaks.py``)."""


def read(rec):
    span, peak = rec["span"], rec["peak"]
    if peak is None or not span["steps"] or span["seconds"] <= 0:
        return None
    return float(span["ops"] / span["seconds"] / peak["bf16_flops"] * 100.0)
