"""Reduce a JAX profiler trace to the benchmark's device numbers.

Two steps, so that the second can be checked on a small recorded trace
(``tests/data/``) without a chip:

* ``extract(xplane_path, span_names)`` reads the ``.xplane.pb`` with
  ``jax.profiler.ProfileData`` and keeps, on one clock, the executions of
  each device's XLA modules and the benchmark's own host spans (the
  ``TraceAnnotation`` of each ``Spans`` call);
* ``reduce(events, layers)`` works out, inside the ``trace.window`` span
  from its first device event on:
  the union of each device's busy intervals, the device time of each
  module and of each layer (``layers/<layer>.json`` lists the modules of
  one layer), and the idle gaps between busy intervals, each attributed to the
  innermost host span open at its middle.

Times in the result are seconds; device numbers are means over the
devices.
"""

from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from pathlib import Path

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_MODULE_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")
# The host span that marks the traced stretch of the window.
WINDOW = "trace.window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_name(name: str) -> str:
    """``jit__simulate_scan(1234)`` -> ``jit__simulate_scan``."""
    return _MODULE_ID.sub("", name).strip()


def extract(xplane_path: str, span_names) -> dict:
    """{"devices": {plane: [[module, start_ns, end_ns], ...]},
    "host": [[span, start_ns, end_ns], ...]} from one trace file."""
    import jax

    span_names = set(span_names)
    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _MODULE_LINE:
                    devices[plane.name] = [
                        [module_name(e.name), float(e.start_ns),
                         float(e.end_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.end_ns)]
                            for e in line.events if e.name in span_names)
    return {"devices": devices, "host": host}


def load_layers(layer_dir: Path) -> list[tuple[re.Pattern, str]]:
    """(module pattern, layer) from ``layers/<layer>.json``, one file per
    layer, in name order; a module no pattern finds is in layer
    ``other``."""
    out = []
    for path in sorted(Path(layer_dir).glob("*.json")):
        with open(path) as f:
            out.extend((re.compile(p), path.stem)
                       for p in json.load(f)["modules"])
    return out


def layer_of(module: str, layers) -> str:
    for pattern, layer in layers:
        if pattern.search(module):
            return layer
    return "other"


def _union(intervals):
    """Sorted, merged copy of [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, t: float) -> str:
    """Name of the latest-opened host span that contains time ``t``."""
    best, best_start = "none", None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW and (best_start is None
                                                 or s >= best_start):
            best, best_start = name, s
    return best


def reduce(events: dict, layers, top: int = 10) -> dict:
    host = events["host"]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    if windows:
        w0, w1 = windows[0]
    else:
        w0 = min(s for evs in devices.values() for _, s, _ in evs)
        w1 = max(e for evs in devices.values() for _, _, e in evs)
    # The stretch opens at the first device event inside it: the
    # profiler's own start-up (about 0.1 s on a v5e) is no idle time of
    # the program's.
    w0 = max(w0, min((s for evs in devices.values() for _, s, e in evs
                      if e > w0 and s < w1), default=w0))
    n_dev = len(devices)
    busy_ns, module_ns = 0.0, defaultdict(float)
    layer_ns, layer_n = defaultdict(float), defaultdict(float)
    gaps = []
    for plane in sorted(devices):
        clipped = [(m, max(s, w0), min(e, w1), e - s)
                   for m, s, e in devices[plane] if e > w0 and s < w1]
        for m, s, e, full in clipped:
            module_ns[m] += e - s
            layer = layer_of(m, layers)
            layer_ns[layer] += e - s
            # An execution cut by the window's edge counts in part.
            layer_n[layer] += (e - s) / full if full > 0 else 1.0
        clipped = [(m, s, e) for m, s, e, _ in clipped]
        merged = _union([[s, e] for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in merged)
        if plane == min(devices):
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((_innermost(host, (s + e) / 2), (e - s)
                                 * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(module_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "devices": n_dev,
        "layer_s": {k: v * 1e-9 / n_dev for k, v in layer_ns.items()},
        "layer_n": {k: v / n_dev for k, v in layer_n.items()},
        "device_ops": [[m, v * 1e-9 / n_dev] for m, v in ops],
        "idle_gaps": [[name, s] for name, s in gaps[:top]],
    }
