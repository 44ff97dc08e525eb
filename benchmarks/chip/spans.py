"""Reduction of a ``jax.profiler`` trace to the program's layers: the
device's self time under each of the model's named scopes, and the
device's idle time under each of the program's host spans.

Scopes. Each device operation is put under the model scopes in its
``op_name`` (``jit(step)/while/body/ssm/state_update/mul`` -> ``ssm/
state_update``), or under ``unscoped`` where it has none: the loop, copies
and argument handling that the compiler adds. Op events carry no
``op_name``: it is the instruction's ``metadata.op_name`` in the compiled
module's HLO, which the trace keeps in its ``/host:metadata`` plane by
program id and module name. A TPU op event names its instruction
(``%fusion.12 = ...``), and its program is the ``XLA Modules`` event
running at its start (``jit_serve_step(1707...)``); on the CPU an op event
carries them (``hlo_op``, ``program_id``, ``hlo_module``). Where the id
is not in the plane (a CPU process that ran an executable compiled before
the one the plane describes), the module name finds it.

Spans. The program writes its phases into the trace as host events:
``talp.*`` (the monitor's regions, host states and own work), ``serve.*``
and ``train.*`` (the drivers' loops). An idle instant of the device, in
the window from its first to its last operation, is counted once, for the
innermost program span that covers it, and under ``no span`` where none
does.

A trace of a program without scopes or spans reduces all the same: every
operation is ``unscoped`` and every idle instant ``no span``; ``scoped``
and ``spans`` say so.

Run as a script, it runs one cell traced, as ``run.py --trace 1`` does,
and prints the cell's two lines and then the reduction of its trace:

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \
        --seconds <s>
"""

from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict

import xplane

#: The model's named scopes (``models/lm.py``, ``models/ssm.py``).
MODEL_SCOPES = ("embed", "ssm", "state_update", "attn", "ffn", "head")
UNSCOPED = "unscoped"
#: Host events that are the program's own spans.
SPAN_PREFIXES = ("talp.", "serve.", "train.")
NO_SPAN = "no span"
#: Operations listed by name, with their scope, in the reduction.
TOP_OPS = 8

MODULE_LINE = "XLA Modules"

_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")   # jvp(ssm), transpose(jvp(ssm))
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")      # jit_step(12)


# ---------------------------------------------------------------------------
# protobuf wire format, for the two messages read here
# ---------------------------------------------------------------------------
def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a ``memoryview`` for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _first(buf, number, default=None):
    for f, v in _fields(buf):
        if f == number:
            return v
    return default


def _text(v):
    return "" if v is None else bytes(v).decode("utf-8", "replace")


def hlo_op_names(xspace_bytes):
    """``{program id: (module name, {instruction: op_name})}`` from the HLO
    protos of the ``/host:metadata`` plane.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
    value = 2), .stat_metadata = 5; XEventMetadata.id = 1, .name = 2,
    .stats = 5; XStat.metadata_id = 1, .bytes_value = 6. HloProto.hlo_module
    = 1; HloModuleProto.name = 1, .computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2.
    """
    out = {}
    for f, plane in _fields(memoryview(xspace_bytes)):
        if f != 1 or _text(_first(plane, 2)) != "/host:metadata":
            continue
        hlo_stat = {int(_first(v, 1, 0)) for f2, e in _fields(plane) if f2 == 5
                    for v in [_first(e, 2)] if _text(_first(v, 2)) == "Hlo Proto"}
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            md = _first(entry, 2)
            proto = next((_first(st, 6) for f3, st in _fields(md)
                          if f3 == 5 and _first(st, 1) in hlo_stat), None)
            if proto is None:
                continue
            module = _first(proto, 1)
            names = {}
            for f3, comp in _fields(module):
                if f3 != 3:
                    continue
                for f4, ins in _fields(comp):
                    if f4 == 2:
                        meta = _first(ins, 7)
                        names[_text(_first(ins, 1))] = (
                            _text(_first(meta, 2)) if meta is not None else "")
            out[int(_first(md, 1, 0))] = (_text(_first(module, 1)), names)
    return out


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------
def scope_of(op_name):
    """The model scopes in an ``op_name`` path, outermost first, joined by
    ``/``; ``unscoped`` where there are none. Transformations wrap a scope
    (``transpose(jvp(ssm))``) and are unwrapped."""
    found = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in MODEL_SCOPES:
            found.append(part)
    return "/".join(found) if found else UNSCOPED


def instruction_of(event_name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return xplane.short_name(event_name).lstrip("%")


class _Programs:
    """A device plane's ``XLA Modules`` events: the program running at a
    given time."""

    def __init__(self, plane):
        evs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for line in plane.lines if line.name == MODULE_LINE
                     for e in line.events)
        self.starts = [s for s, _, _ in evs]
        self.evs = evs

    def at(self, start_ns):
        """``(program id, module name)``, or ``(None, None)``."""
        i = bisect.bisect_right(self.starts, start_ns) - 1
        m = _PROGRAM.match(self.evs[i][2]) if i >= 0 and self.evs[i][1] >= start_ns else None
        return (int(m.group(2)), m.group(1)) if m else (None, None)


def read_trace(path):
    """The trace's platform (``TPU`` where it has a TPU plane, else
    ``CPU``), device ops per device as ``((instruction, scope), start_s,
    end_s)`` and the program's spans as ``(name, start_s, end_s)``. On the
    CPU, whose operations run on host threads, the device is the host
    events that carry an ``hlo_op`` (tests only)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    modules = hlo_op_names(raw)
    by_name = {name: names for name, names in modules.values()}
    planes = list(ProfileData.from_serialized_xspace(raw).planes)
    tpu = any(p.name.startswith("/device:TPU:") for p in planes)
    scopes = {}                      # (program, module, instruction) -> scope

    def op(program, module, ins, s, t):
        key = (program, module, ins)
        if key not in scopes:
            names = modules[program][1] if program in modules else by_name.get(module, {})
            scopes[key] = scope_of(names.get(ins, ""))
        return (ins, scopes[key]), s, t

    devices, spans = {}, []
    for plane in planes:
        if tpu and plane.name.startswith("/device:TPU:"):
            running = _Programs(plane)
            devices[plane.name] = [
                op(*running.at(e.start_ns), instruction_of(e.name),
                   *xplane._event(e)[1:])
                for line in plane.lines if line.name in xplane.OP_LINES
                for e in line.events if e.duration_ns > 0]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append(xplane._event(e))
                    elif not tpu:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            devices.setdefault("/host:CPU ops", []).append(op(
                                stats.get("program_id"), stats.get("hlo_module"),
                                stats["hlo_op"], *xplane._event(e)[1:]))
    return ("TPU" if tpu else "CPU"), devices, spans


# ---------------------------------------------------------------------------
# idle time under the innermost span
# ---------------------------------------------------------------------------
def innermost_segments(spans):
    """Disjoint ``(start, end, name)`` pieces of the time the spans cover,
    each named by its innermost covering span: the one that began last
    (the shortest among equals)."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda sp: sp[1])
    out, active, k = [], [], 0
    for lo, hi in zip(bounds, bounds[1:]):
        while k < len(order) and order[k][1] <= lo:
            active.append(order[k])
            k += 1
        active = [sp for sp in active if sp[2] > lo]
        if active:
            inner = max(active, key=lambda sp: (sp[1], sp[1] - sp[2]))
            out.append((lo, hi, inner[0]))
    return out


def idle_by_span(gaps, spans):
    """Idle seconds per innermost covering span name; ``no span`` for the
    rest. ``gaps`` are disjoint and sorted."""
    by_name = defaultdict(float)
    segs = innermost_segments(spans)
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        i = j
        while i < len(segs) and segs[i][0] < g1:
            ov = min(g1, segs[i][1]) - max(g0, segs[i][0])
            if ov > 0:
                by_name[segs[i][2]] += ov
                covered += ov
            i += 1
        by_name[NO_SPAN] += (g1 - g0) - covered
    return dict(by_name)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def reduce(log_dir, steps):
    """Per step of the traced span (``steps``, the harness's count): device
    self time per scope, busy time, idle time per innermost span, in ms;
    and the share of idle time under some span, in %. ``None`` where the
    trace has no device op or the span no step."""
    platform, devices, spans = read_trace(xplane.find_xplane(log_dir))
    if not devices or not steps:
        return None
    per_op, idle = defaultdict(float), defaultdict(float)
    busy = 0.0
    for ops in devices.values():
        iv = [(s, e) for _, s, e in ops]
        busy += xplane.busy_seconds(iv)
        for op, t in xplane.self_times(ops):
            per_op[op] += t
        gaps = xplane.idle_gaps(iv, min(s for s, _ in iv), max(e for _, e in iv))
        for name, t in idle_by_span(gaps, spans).items():
            idle[name] += t
    per_scope = defaultdict(float)
    for (_, scope), t in per_op.items():
        per_scope[scope] += t
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_OPS]
    scale = 1e3 / (len(devices) * steps)
    idle_total = sum(idle.values())
    return {
        "platform": platform,
        "steps": steps,
        "scoped": any(k != UNSCOPED for k in per_scope),
        "spans": sorted({n for n, _, _ in spans}),
        "span_events_per_step": len(spans) / steps,
        "busy_ms_per_step": busy * scale,
        "scopes_ms_per_step": sum(per_scope.values()) * scale,
        "device_ms_per_step": {k: v * scale for k, v in sorted(per_scope.items())},
        "top_ops_ms_per_step": [[op, scope, t * scale] for (op, scope), t in top],
        "idle_ms_per_step": idle_total * scale,
        "idle_in_spans_share": (100.0 * (1.0 - idle.get(NO_SPAN, 0.0) / idle_total)
                                if idle_total > 0 else None),
        "idle_ms_per_step_by_span": {k: v * scale for k, v in
                                     sorted(idle.items(), key=lambda kv: -kv[1])},
    }


#: Program spans of the driver loops and of the monitor's own work.
LOOP, CAPTURE = ("serve.", "train."), "talp.capture."


def figures(red):
    """The four per-step figures of a reduction, in ms: device self time
    under the ``ssm`` scope (and below) and under no scope, where some op
    carries a scope; device idle under the loops' spans and under the
    monitor's own work, where the trace has such spans. ``None`` where
    there is nothing to read."""
    if not red:
        return dict.fromkeys(("ssm_device", "unscoped_device", "loop_idle",
                              "talp_idle"))
    dev, idle = red["device_ms_per_step"], red["idle_ms_per_step_by_span"]

    def idle_under(prefix):
        if not any(n.startswith(prefix) for n in red["spans"]):
            return None
        return sum(ms for n, ms in idle.items() if n.startswith(prefix))

    return {
        "ssm_device": (sum(ms for k, ms in dev.items()
                           if k == "ssm" or k.startswith("ssm/"))
                       if red["scoped"] else None),
        "unscoped_device": dev.get(UNSCOPED, 0.0) if red["scoped"] else None,
        "loop_idle": idle_under(LOOP),
        "talp_idle": idle_under(CAPTURE),
    }


# ---------------------------------------------------------------------------
# one cell, traced
# ---------------------------------------------------------------------------
def traced_cell(workload, seed, seconds, **kwargs):
    """``run.run_cell`` with ``--trace 1`` and its trace kept until it is
    reduced here: ``(result line, info line, reduction)``. ``kwargs`` go to
    ``run_cell`` (the smoke variants, for tests on the CPU)."""
    import shutil
    import tempfile

    import run

    keep = tempfile.mkdtemp(prefix="chip_spans_")
    base = xplane.reduce

    def reduce_and_keep(log_dir, platform="TPU"):
        shutil.copy(xplane.find_xplane(log_dir), keep)
        return base(log_dir, platform)

    xplane.reduce = reduce_and_keep
    try:
        result, info = run.run_cell(workload, seed, seconds, 1, **kwargs)
        return result, info, reduce(keep, info["span"]["steps"])
    finally:
        xplane.reduce = base
        shutil.rmtree(keep, ignore_errors=True)


def main(argv=None):
    import argparse
    import json

    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        result, info, spans = traced_cell(args.workload, args.seed, args.seconds)
    except harness.Refused as e:
        print(f"spans.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"info": info}, default=float))
    print(json.dumps(result, default=float))
    print(json.dumps({"spans": spans, "ms_per_step": figures(spans)},
                     default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
