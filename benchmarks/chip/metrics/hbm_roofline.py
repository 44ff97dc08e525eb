"""The decode step's share of the HBM roofline, in %: the least bytes the
steps of the traced span must move (weights once, SSM state and
convolution tails read and written, keys and values of valid positions;
``counts.py``) over the span's host-clock length and the published HBM
bandwidth (``peaks.py``)."""


def read(rec):
    span, peak = rec["span"], rec["peak"]
    if peak is None or span["bytes"] is None or span["seconds"] <= 0:
        return None
    return float(span["bytes"] / span["seconds"] / peak["hbm_bytes_per_s"] * 100.0)
