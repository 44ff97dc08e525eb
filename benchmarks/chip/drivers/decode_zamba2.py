"""Zamba2 decoding cells: ``drivers/decode.py`` as it runs, with two
additions. The operations and bytes of the steps of the traced span (or of
the window) are Zamba2's (``counts_zamba2.py``), at each step's position
``prompt_len + t``. In a traced run the trace, complete once
``decode.run`` returns and deleted by ``run.py`` after this returns, is
reduced here to the model's scopes (``spans.reduce``), into
``rec["spans"]``, for the metrics that read them.
"""

from __future__ import annotations

import counts_zamba2
import harness
import spans

decode = harness.load_module("drivers", "decode")


def counted_steps(ctx, rec):
    """Indices of the decode steps the span's counts cover: the traced
    launches where the run was traced, else the window's steps. Both must
    run on from ``warm_steps`` without a gap."""
    hooks, warm = ctx["hooks"], ctx["traffic"]["warm_steps"]
    if ctx["trace"] and hooks.span and hooks.span[1]:
        steps = [int(k.split("#")[0].split("_")[1]) for k in hooks.span_calls]
    else:
        steps = list(range(warm, warm + rec["window"]["steps"]))
    if steps != list(range(warm, warm + len(steps))) or \
            len(steps) != rec["span"]["steps"]:
        raise ValueError(f"counted decode steps are not {warm}, {warm + 1}, "
                         f"... : {steps[:4]} ... ({len(steps)})")
    return steps


def run(ctx):
    rec = decode.run(ctx)
    spec, tr = ctx["spec"], ctx["traffic"]
    req, plen = tr["requests"], tr["prompt_len"]
    steps = counted_steps(ctx, rec)
    rec["span"]["ops"] = sum(counts_zamba2.decode_step_ops(spec, req, plen + t)
                             for t in steps)
    rec["span"]["bytes"] = sum(
        counts_zamba2.decode_step_bytes(spec, req, plen + t) for t in steps)
    if ctx["trace"]:
        rec["spans"] = spans.reduce(ctx["hooks"].trace_dir, len(steps))
    return rec
