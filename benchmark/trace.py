"""Reduce a JAX profiler trace to device busy time and idle gaps.

The harness traces one collection with `jax.profiler` and marks the
calls it makes into each layer with `TraceAnnotation` spans named
`bench.<layer>`.  From the written `.xplane.pb` this module takes:

- busy: the union of the intervals in which an operation ran on each
  chip (the "XLA Ops" line of each `/device:TPU:<n>` plane), inside
  the window span (`bench.collection` unless named), averaged over the
  chips used;
- idle gaps: the stretches of the window in which no operation ran on
  any chip used, each named by the innermost `bench.` span that holds
  its midpoint;
- device ops: the operations that took most device time, named
  `<program>/<op>` from the "XLA Modules" line that holds each.

`combine` joins the reductions of several traced stretches.
"""

import bisect
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "collection"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def merge(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for (lo, hi) in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for (lo, hi) in out]


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for (a, b) in intervals
            if b > lo and a < hi]


def op_name(event_name: str) -> str:
    """"%fusion.3 = f32[...] fusion(...), ..." -> "fusion.3"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """"jit_step(1420684457968555073)" -> "jit_step"."""
    return event_name.split("(", 1)[0]


def _name_ops(ops: list, modules: list) -> list:
    """Prefix each op with the program (module event) it runs in."""
    modules.sort()
    starts = [a for (a, _, _) in modules]
    out = []
    for (a, b, name) in ops:
        i = bisect.bisect_right(starts, a) - 1
        prog = modules[i][2] if i >= 0 and a < modules[i][1] else "?"
        out.append((a, b, f"{prog}/{name}"))
    return out


def read(path: str) -> dict:
    """Host spans and per-chip device ops from one trace file:
    {"spans": [(name, start_ns, end_ns)],
     "devices": {chip: [(start_ns, end_ns, "<program>/<op>")]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = []
    devices: dict = {}
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns, ev.end_ns))
        elif m is not None:
            (ops, modules) = ([], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(ev.start_ns, ev.end_ns, op_name(ev.name))
                            for ev in line.events]
                elif line.name == "XLA Modules":
                    modules += [(ev.start_ns, ev.end_ns,
                                 module_name(ev.name))
                                for ev in line.events]
            devices[int(m.group(1))] = _name_ops(ops, modules)
    return {"spans": spans, "devices": devices}


def reduce(path: str, chips: int, window: str = WINDOW_SPAN,
           top: int = 10) -> dict:
    """busy_s, window_s, device_ops and idle_gaps inside the span
    `bench.<window>` on the first `chips` chips; the `top` longest of
    the ops and of the gaps, or all of them where `top` is None."""
    trace = read(path)
    windows = [(a, b) for (name, a, b) in trace["spans"]
               if name == window]
    if len(windows) != 1:
        raise ValueError(f"expected one {SPAN_PREFIX}{window} "
                         f"span, found {len(windows)}")
    (w0, w1) = windows[0]
    used = sorted(trace["devices"])[:chips]
    if len(used) < chips:
        raise ValueError(f"trace holds {len(used)} TPU plane(s), the "
                         f"cell uses {chips}")
    busy = []
    every: list = []
    op_time: dict = {}
    for chip in used:
        ops = trace["devices"][chip]
        ivs = clip(merge([(a, b) for (a, b, _) in ops]), w0, w1)
        busy.append(sum(b - a for (a, b) in ivs))
        every += ivs
        for (a, b, name) in ops:
            if a >= w0 and b <= w1:
                op_time[name] = op_time.get(name, 0.0) + (b - a)
    gaps = []
    cursor = w0
    for (a, b) in merge(every) + [(w1, w1)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    named = []
    for (a, b) in gaps:
        mid = (a + b) / 2
        holders = [(e - s, name) for (name, s, e) in trace["spans"]
                   if s <= mid <= e and name != window]
        label = min(holders)[1] if holders else window
        named.append([f"{label} at {(a - w0) / 1e9:.3f}s",
                      (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(b - a for (a, b) in gaps) / 1e9,
        "device_ops": [[name, t / 1e9] for (name, t) in ops_sorted[:top]],
        "idle_gaps": named[:top],
    }


def combine(parts: list, top: int = 10) -> dict:
    """One reduction from several stretches, [(label, reduce(...,
    top=None))]: times summed, each op's time summed over the
    stretches, each gap named by its stretch's label."""
    op_time: dict = {}
    gaps: list = []
    for (label, part) in parts:
        for (name, t) in part["device_ops"]:
            op_time[name] = op_time.get(name, 0.0) + t
        gaps += [[f"{label}: {name}", t] for (name, t) in part["idle_gaps"]]
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(part["busy_s"] for (_, part) in parts),
        "window_s": sum(part["window_s"] for (_, part) in parts),
        "idle_s": sum(part["idle_s"] for (_, part) in parts),
        "device_ops": [[name, t] for (name, t) in ops_sorted[:top]],
        "idle_gaps": gaps[:top],
    }
