"""From a JAX profiler trace to the numbers the device metrics read.

Two steps, so that the second can be checked on a small recorded trace:

* `extract(path, host_names)` reads the `.xplane.pb` the profiler wrote and
  keeps what the reduction needs: each TPU's op and module events, and the
  host spans named in `host_names`, all as ``[name, start_ns, end_ns]`` on
  the profiler's one clock. A TPU op event is named by its HLO text
  (``%fusion.1 = u32[8392336]{0:T(1024)} fusion(...)``); layouts are
  dropped and the leading ``%`` kept, so a reader matches an instruction by
  ``^%name``.
* `reduce(events, t0_ns, t1_ns)` sums them over the window [t0, t1): per
  chip the busy time (the union of op intervals), the time per op and per
  module name, and the idle time split by the innermost host span open
  while the chip waited.
"""
from __future__ import annotations

import bisect
import pathlib
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 200


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    """The one `.xplane.pb` a `jax.profiler` session wrote under log_dir."""
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def op_name(hlo_text: str) -> str:
    """A TPU op's trace name without layouts, at most NAME_CHARS long."""
    return _LAYOUT.sub("", _LAYOUT.sub("", hlo_text))[:NAME_CHARS]


def extract(path: str | pathlib.Path, host_names) -> dict:
    """Device ops/modules and the named host spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host_names = set(host_names)
    devices, host = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev = devices.setdefault(int(m.group(1)),
                                         {"ops": [], "modules": []})
                dev[key] += [[op_name(ev.name), ev.start_ns, ev.end_ns]
                             for ev in line.events]
            elif plane.name.startswith("/host:"):
                host += [[ev.name, ev.start_ns, ev.end_ns]
                         for ev in line.events if ev.name in host_names]
    return {"devices": {str(k): devices[k] for k in sorted(devices)},
            "host": sorted(host, key=lambda e: (e[1], -e[2]))}


def _clip(events, t0, t1):
    for name, a, b in events:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            yield name, a, b


def _union(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _innermost(host, t) -> str:
    """Name of the innermost host span open at time t ("none" if none)."""
    best = None
    for name, a, b in host:
        if a > t:
            break
        if b > t and (best is None or a >= best[1]):
            best = (name, a)
    return best[0] if best else "none"


def _host_segments(host, t0, t1) -> list[tuple[float, float, str]]:
    """[t0, t1) cut at every host span edge, each piece labelled with the
    innermost span open in it."""
    cuts = sorted({t0, t1} | {x for _, a, b in host for x in (a, b)
                              if t0 < x < t1})
    return [(a, b, _innermost(host, (a + b) / 2))
            for a, b in zip(cuts, cuts[1:])]


def reduce(events: dict, t0_ns: float, t1_ns: float) -> dict:
    """Per-chip device time over the window [t0_ns, t1_ns).

    Returns ``{"window_s", "chips": [{"id", "busy_s", "ops": {name: s},
    "modules": {name: s}, "idle": {host span: s}}]}``.
    """
    segments = _host_segments(events["host"], t0_ns, t1_ns)
    chips = []
    for cid, dev in events["devices"].items():
        ops = list(_clip(dev["ops"], t0_ns, t1_ns))
        busy = _union([a, b] for _, a, b in ops)
        starts = [a for a, _ in busy]
        done = [0.0]                        # busy time before each interval
        for a, b in busy:
            done.append(done[-1] + b - a)

        def busy_until(t):
            i = bisect.bisect_right(starts, t) - 1
            return done[i] + min(t, busy[i][1]) - busy[i][0] if i >= 0 else 0.0

        per_op: dict[str, float] = {}
        for name, a, b in ops:
            per_op[name] = per_op.get(name, 0.0) + (b - a) / 1e9
        per_mod: dict[str, float] = {}
        for name, a, b in _clip(dev["modules"], t0_ns, t1_ns):
            per_mod[name] = per_mod.get(name, 0.0) + (b - a) / 1e9
        idle: dict[str, float] = {}
        for a, b, label in segments:
            gap = (b - a) - (busy_until(b) - busy_until(a))
            if gap > 0:
                idle[label] = idle.get(label, 0.0) + gap / 1e9
        chips.append({"id": int(cid), "busy_s": done[-1] / 1e9,
                      "ops": per_op, "modules": per_mod, "idle": idle})
    return {"window_s": (t1_ns - t0_ns) / 1e9, "chips": chips}


def top(per_chip: list[dict], n: int = 10) -> list[list]:
    """The n largest entries of per-chip {name: seconds} maps, averaged over
    the chips, as ``[[name, seconds], ...]``."""
    total: dict[str, float] = {}
    for d in per_chip:
        for k, v in d.items():
            total[k] = total.get(k, 0.0) + v / len(per_chip)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
