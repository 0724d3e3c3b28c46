"""The program's own spans in a cell's traced window, on the device's clock.

The program marks its stages with ``repro.core.metrics.span``: profiler
annotations named ``<layer>.<what>`` (``rollout.gather``, ``learner.step``,
``flow.ConcatBatches(128)``, ``weight_sync``, ...), recorded into the same
trace as the device planes.  ``load(facts)`` reads the cell's trace once
(cached by path) and returns:

* ``window``: the harness's ``bench.window`` event, as ``trace.reduce_trace``
  takes it;
* ``idle``: the first device's idle intervals in the window, the complement
  of its busy intervals built as ``trace.reduce_trace`` builds them (the
  ``XLA Ops`` line of the first TPU plane; on the CPU backend, host events
  with an ``hlo_module`` statistic), so their total is the idle time that
  ``device.idle_frac.*`` reads;
* ``lines``: per host thread line, its program spans ``(start, end, name,
  stats)``; ``driver``: the index of the line carrying ``bench.train``.

The readers (``bench/metrics/*.idle_ms.*``, ``learner.starved_frac.env``,
``weight_sync.stall_ms.env``, ``learner.policy_lag.env``) return None where
the spans they read are absent, as in a program that records none.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Tuple

from bench import harness
from bench import trace as tr

Span = Tuple[float, float, str, Optional[dict]]

PROGRAM = ("rollout.", "postprocess.", "learner.", "flow.", "weight_sync")
STATS_OF = ("learner.learn",)  # spans whose stats are read
DRIVER_EVENT = "bench.train"

# The layer each driver span's device idle time is charged to; driver time
# under no program span goes to the flow runtime.
LAYERS = {"rollout.": "rollout", "postprocess.": "rollout", "learner.": "learner",
          "flow.": "flow", "weight_sync": "flow"}


def layer_of(name: Optional[str]) -> str:
    if name is not None:
        for prefix, layer in LAYERS.items():
            if name.startswith(prefix):
                return layer
    return "flow"


def load(facts: dict) -> Optional[dict]:
    """The spans of the traced window of ``facts``' cell, or None untraced."""
    if not facts.get("trace"):
        return None
    path = tr.find_xplane(str(harness.OUT / "trace" / facts["cell"]["name"]))
    if not path:
        return None
    return read_trace(path, os.path.getmtime(path), int(facts.get("chips", 1)))


@functools.lru_cache(maxsize=2)
def read_trace(path: str, mtime: float, n_devices: int = 1) -> dict:
    """Window, first-device idle intervals and program spans per host line
    (``mtime`` keys the cache, so a new trace at the same path is read)."""
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    devices = sorted(
        (p for p in planes
         if p.name.startswith(tr.DEVICE_PREFIX) and p.name[len(tr.DEVICE_PREFIX):].isdigit()),
        key=lambda p: int(p.name[len(tr.DEVICE_PREFIX):]),
    )[:n_devices]
    window = None
    lines: List[List[Span]] = []
    driver = None
    busy: List[Tuple[float, float]] = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans: List[Span] = []
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM):
                    stats = tr._stats(ev) if name in STATS_OF else None
                    spans.append((ev.start_ns, ev.end_ns, name, stats))
                elif name == DRIVER_EVENT:
                    driver = len(lines)
                elif name == "bench.window" and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif not devices and ev.duration_ns > 0 and "hlo_module" in tr._stats(ev):
                    busy.append((ev.start_ns, ev.end_ns))  # CPU backend: XLA's operations
            lines.append(spans)
    if devices:
        for line in devices[0].lines:
            if line.name == tr.OPS_LINE:
                busy = [(ev.start_ns, ev.end_ns) for ev in line.events]
    if window is None:
        edges = [x for iv in busy for x in iv]
        window = (min(edges), max(edges)) if edges else (0.0, 0.0)
    lo, hi = window
    ivs = [iv for s, e in busy if (iv := tr._clip(s, e, lo, hi))]
    harness.log(f"spans: read {time.perf_counter() - t0:.1f} s, "
                f"{sum(len(x) for x in lines)} program spans")
    return {"window": window, "idle": tr.gaps(ivs, lo, hi), "lines": lines, "driver": driver}


def innermost(spans: List[Span], lo: float, hi: float) -> List[Tuple[float, float, Optional[str]]]:
    """[lo, hi) cut into consecutive pieces, each labelled with the innermost
    of ``spans`` (nested, as one thread's are) covering it, or None."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Span] = []
    cur = lo

    def emit(end: float, name: Optional[str]) -> None:
        nonlocal cur
        end = min(end, hi)
        if end > cur:
            out.append((cur, end, name))
            cur = end

    for sp in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= sp[0]:
            top = stack.pop()
            emit(top[1], top[2])
        emit(sp[0], stack[-1][2] if stack else None)
        stack.append(sp)
    while stack:
        top = stack.pop()
        emit(top[1], top[2])
    emit(hi, None)
    return out


def idle_by_layer(sp: dict) -> Dict[str, float]:
    """Device-idle nanoseconds of the window per layer: each idle instant is
    charged to the layer of the driver thread's innermost program span then
    (``layer_of``).  The layers' sum is the window's whole idle time."""
    lo, hi = sp["window"]
    pieces = innermost(sp["lines"][sp["driver"]], lo, hi)
    out = {"rollout": 0.0, "learner": 0.0, "flow": 0.0}
    idle, i = sp["idle"], 0
    for s, e, name in pieces:
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < e:
            out[layer_of(name)] += min(e, idle[j][1]) - max(s, idle[j][0])
            j += 1
    return out


def ms_per_kunit(facts: dict, seconds: Optional[float]) -> Optional[float]:
    """``seconds`` as milliseconds per 1000 trained units of the window."""
    if seconds is None or facts["units"] <= 0:
        return None
    return 1e3 * seconds / (facts["units"] / 1e3)


def idle_ms(facts: dict, layer: str) -> Optional[float]:
    """Device-idle milliseconds charged to ``layer`` per 1000 trained units,
    or None where the driver thread carries none of that layer's spans."""
    sp = load(facts)
    if sp is None or sp["driver"] is None:
        return None
    if not any(layer_of(name) == layer for _, _, name, _ in sp["lines"][sp["driver"]]):
        return None
    return ms_per_kunit(facts, idle_by_layer(sp)[layer] * 1e-9)


def in_window(facts: dict, name: str) -> Optional[Tuple[dict, List[Span]]]:
    """The spans named ``name`` on any thread, clipped to the window (those
    wholly outside dropped), with the loaded spans; None where there are
    none."""
    sp = load(facts)
    if sp is None:
        return None
    lo, hi = sp["window"]
    hits = [(max(s, lo), min(e, hi), n, st) for line in sp["lines"]
            for s, e, n, st in line if n == name and e > lo and s < hi]
    return (sp, hits) if hits else None
