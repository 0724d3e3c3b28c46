"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

``reduce_trace(path)`` returns a plain dict:

* ``window_s`` and, per device, the busy seconds: the union of the intervals
  in which an operation ran, so overlapping operations count once;
* ``modules``: device seconds per XLA module (a jitted function's program),
  keyed by module name;
* ``ops``: device seconds and calls per operation, keyed by the operation's
  name, with the text a kernel can be recognised by (names and metadata);
* ``idle_gaps``: the longest stretches with no operation on the device, each
  labelled with the innermost host event running at its midpoint.

The window is the host event named ``window_event`` (the harness wraps its
measured window in one); without it, the whole trace.  On a TPU the device
operations are the events of the ``XLA Ops`` line of each ``/device:TPU:n``
plane, and modules those of ``XLA Modules``.  On the CPU backend, which has
no device plane, the operations are the host events that carry an
``hlo_module`` statistic, so the reduction can be tested without a chip.

Lookups (``module_seconds``, ``kernel_seconds``) return None for a name that
is absent: a metric then reports nothing, never 0.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union_length(intervals: Iterable[Interval]) -> float:
    """Total length covered by possibly overlapping [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi) covered by no interval."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:  # a stat of a type the reader cannot convert
        return {}


def _module_name(name: str) -> str:
    """``jit__learn(123)`` -> ``jit__learn``."""
    return name.split("(", 1)[0].strip()


_HLO = re.compile(r"^%?(?P<instr>[\w.\-]+) = (?P<shape>.+?) (?P<opcode>[a-z][\w\-]*)\(")


_OPERAND = re.compile(r"([a-z]+\d*)\[([\d,]*)\](\{[^}]*\})?\s+%")


def _dims(s: str) -> tuple:
    return tuple(int(x) for x in s.split(",") if x)


@functools.lru_cache(maxsize=None)
def parse_op(name: str) -> dict:
    """An HLO operation's trace name (``%fusion.3 = f32[8,128]{1,0} fusion(...)``)
    -> instruction, opcode, output shape without layouts, the dims of each
    array in the output (a tuple has several), and each operand's dtype, dims
    and memory space (``S(1)`` in its layout is on-chip VMEM; none is HBM)."""
    m = _HLO.match(name)
    if not m:
        return {"instr": name[:80], "opcode": "", "shape": "", "arrays": [], "operands": [],
                "out_space": 0}
    shape = re.sub(r"\{[^}]*\}", "", m["shape"]).replace("/*index=", "").replace("*/", "")
    arrays = [(dt, _dims(dims)) for dt, dims in re.findall(r"([a-z]+\d*)\[([\d,]*)\]", shape)]
    operands = []
    for dt, dims, layout in _OPERAND.findall(name[m.end():]):
        space = re.search(r"S\((\d+)\)", layout or "")
        operands.append((dt, _dims(dims), int(space.group(1)) if space else 0))
    return {"instr": m["instr"], "opcode": m["opcode"], "shape": shape, "arrays": arrays,
            "operands": operands, "out_space": 1 if re.search(r"S\(1\)", m["shape"]) else 0}


def self_times(events: List[Tuple[float, float, str]]) -> List[float]:
    """Self time of each (start, end, name) event of one timeline: its length
    less the events nested in it (a ``while`` holds its body's operations)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [e - s for s, e, _ in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def reduce_trace(path: str, window_event: str = "bench.window", n_gaps: int = 10,
                 n_devices: Optional[int] = None) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host_events: List[Tuple[float, float, str]] = []
    window: Optional[Interval] = None
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_event and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    host_events.append((ev.start_ns, ev.end_ns, ev.name))

    devices = sorted(
        (p for p in planes if p.name.startswith(DEVICE_PREFIX) and p.name[len(DEVICE_PREFIX):].isdigit()),
        key=lambda p: int(p.name[len(DEVICE_PREFIX):]),
    )
    if n_devices is not None:
        devices = devices[:n_devices]
    # device -> timeline -> [(start, end, name)]
    ops: Dict[str, Dict[str, List[Tuple[float, float, str]]]] = {}
    mods: Dict[str, List[Tuple[float, float, str]]] = {}
    if devices:
        for plane in devices:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = {line.name: [(ev.start_ns, ev.end_ns, ev.name)
                                                   for ev in line.events]}
                elif line.name == MODULES_LINE:
                    mods[plane.name] = [(ev.start_ns, ev.end_ns, _module_name(ev.name))
                                        for ev in line.events]
    else:  # CPU backend: XLA's executed operations are host events
        cpu, by_run = [], {}
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_module" in st and ev.duration_ns > 0:
                        cpu.append((ev.start_ns, ev.end_ns, ev.name, line.name))
                        # A module's time on the CPU: the span its operations cover.
                        key = (_module_name(str(st["hlo_module"])), st.get("run_id"))
                        span = by_run.setdefault(key, [ev.start_ns, ev.end_ns])
                        span[0], span[1] = min(span[0], ev.start_ns), max(span[1], ev.end_ns)
        # One timeline per host thread, so nesting is found within a thread.
        for s, e, name, line in cpu:
            ops.setdefault("cpu", {}).setdefault(line, []).append((s, e, name))
        mods["cpu"] = [(s, e, k[0]) for k, (s, e) in by_run.items()]

    if window is None:
        edges = [x for tl in ops.values() for evs in tl.values() for s, e, _ in evs for x in (s, e)]
        window = (min(edges), max(edges)) if edges else (0.0, 0.0)
    lo, hi = window
    dev_names = sorted(ops) or ["none"]

    busy_ns: Dict[str, float] = {}
    op_tot: Dict[str, dict] = {}
    for d in dev_names:
        evs = [ev for tl in ops.get(d, {}).values() for ev in tl]
        busy_ns[d] = union_length(iv for s, e, _ in evs if (iv := _clip(s, e, lo, hi)))
        dmods = sorted(mods.get(d, []))
        starts = [s for s, _, _ in dmods]
        owns = [x for tl in ops.get(d, {}).values() for x in self_times(tl)]
        for (s, e, name), own in zip(evs, owns):
            if s < lo or e > hi:
                continue
            k = bisect.bisect_right(starts, s) - 1
            module = dmods[k][2] if k >= 0 and dmods[k][1] >= e else ""
            p = dict(parse_op(name))
            label = f"{p['instr']} {p['opcode']} {p['shape']}".strip() if p["opcode"] else p["instr"]
            if module:  # one record per operation of each module
                label = f"{module}/{label}"
            rec = op_tot.setdefault(label, {"s": 0.0, "calls": 0, "module": module,
                                            "opcode": p["opcode"], "arrays": p["arrays"],
                                            "operands": p["operands"], "out_space": p["out_space"],
                                            "text": name[:300]})
            rec["s"] += own * 1e-9
            rec["calls"] += 1

    mod_tot: Dict[str, float] = {}
    for evs in mods.values():
        for s, e, name in evs:
            iv = _clip(s, e, lo, hi)
            if iv is not None:
                mod_tot[name] = mod_tot.get(name, 0.0) + (iv[1] - iv[0]) * 1e-9

    # Idle gaps of the first device, labelled by the host's innermost event.
    first = [ev for tl in ops.get(dev_names[0], {}).values() for ev in tl]
    ivs = [iv for s, e, _ in first if (iv := _clip(s, e, lo, hi))]
    idle = sorted(gaps(ivs, lo, hi), key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in idle[:n_gaps]:
        mid = 0.5 * (s + e)
        cover = [(he - hs, nm) for hs, he, nm in host_events
                 if hs <= mid < he and nm != window_event]
        labelled.append([min(cover)[1] if cover else "no host event", (e - s) * 1e-9])

    busy = [v * 1e-9 for v in busy_ns.values()]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "busy_s_by_device": {d: v * 1e-9 for d, v in busy_ns.items()},
        "modules": mod_tot,
        "ops": op_tot,
        "idle_gaps": labelled,
    }


def module_seconds(reduced: dict, fn_name: str) -> Optional[float]:
    """Device seconds of the modules compiled from jitted function ``fn_name``
    (``jit_<fn_name>`` and its numbered variants), or None if absent."""
    hits = [s for m, s in reduced["modules"].items() if in_module({"module": m}, fn_name)]
    return sum(hits) if hits else None


def in_module(rec: dict, fn_name: str) -> bool:
    """Whether an operation's record lies in a module of jitted ``fn_name``."""
    m = rec.get("module", "")
    return m == f"jit_{fn_name}" or m.startswith(f"jit_{fn_name}.")


def ms_per_kilo_unit(facts: dict, fn_name: str) -> Optional[float]:
    """Device milliseconds of ``fn_name``'s modules per 1000 trained units of
    the traced window, or None where there is no trace or no such module."""
    if not facts["trace"] or facts["units"] <= 0:
        return None
    s = module_seconds(facts["trace"], fn_name)
    return None if s is None else 1e3 * s / (facts["units"] / 1e3)


def kernel_seconds(reduced: dict, match) -> Optional[Tuple[float, int]]:
    """(device seconds, calls) of the operations that ``match``: a substring of
    the operation's trace name, or a predicate on its record (``opcode``,
    ``arrays``, ``module``).  None if no operation matches."""
    test = match if callable(match) else (lambda r: match in r["text"])
    hits = [r for r in reduced["ops"].values() if test(r)]
    if not hits:
        return None
    return sum(r["s"] for r in hits), sum(r["calls"] for r in hits)


def top_ops(reduced: dict, n: int = 10) -> List[list]:
    ranked = sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["s"])
    return [[name, rec["s"]] for name, rec in ranked[:n]]
