"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain dict, and everything else works on that dict, so tests can
feed it recorded or hand-made events::

    {"window": [t0_ns, t1_ns],
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}}}

``window`` is the benchmark's own annotation around the measured window
on the host, on the trace's clock. ``ops`` are the events of a device's
"XLA Ops" line, ``modules`` those of its "XLA Modules" line (one per
execution of a compiled program).
"""

from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def read_xplane(logdir: str, anchor: str) -> dict:
    """Reduce the trace under ``logdir``; ``anchor`` names the host
    annotation that spans the window."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one profiler trace under {logdir}, found {files}")
    data = ProfileData.from_file(files[0])
    window = None
    devices: dict = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
                dev[key].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                )
            elif not is_device and window is None:
                for e in line.events:
                    if e.name == anchor:
                        window = [float(e.start_ns), float(e.start_ns + e.duration_ns)]
                        break
    if window is None:
        raise RuntimeError(f"annotation {anchor!r} is not in the trace")
    return {"window": window, "devices": devices}


def _clip(events, window) -> np.ndarray:
    """``(n, 2)`` start/end intervals of ``events`` clipped to the window."""
    t0, t1 = window
    iv = np.array([[s, s + d] for _, s, d in events], np.float64).reshape(-1, 2)
    iv = np.clip(iv, t0, t1)
    return iv[iv[:, 1] > iv[:, 0]]


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the same time as ``iv``."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


def busy_intervals(trace: dict, device: str) -> np.ndarray:
    dev = trace["devices"][device]
    return union(_clip(dev["ops"] or dev["modules"], trace["window"]))


def window_s(trace: dict) -> float:
    t0, t1 = trace["window"]
    return (t1 - t0) * 1e-9


def busy_s(trace: dict) -> float | None:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = list(trace["devices"])
    if not devs:
        return None
    total = sum(float(np.sum(np.diff(busy_intervals(trace, d), axis=1))) for d in devs)
    return total * 1e-9 / len(devs)


def module_time(trace: dict, name: str) -> tuple[float, int]:
    """Summed device seconds and count of the executions, inside the
    window, of compiled programs whose name contains ``name``."""
    total, count = 0.0, 0
    t0, t1 = trace["window"]
    for dev in trace["devices"].values():
        for mod, s, d in dev["modules"]:
            if name in mod and t0 <= s and s + d <= t1:
                total += d
                count += 1
    return total * 1e-9, count


def _short(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _module_of(dev: dict):
    """Maps an op's start to the name of the program execution holding
    it (``jit_sage_grads(12)`` -> ``jit_sage_grads``)."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    starts = np.array([m[1] for m in mods])

    def find(t: float) -> str:
        i = int(np.searchsorted(starts, t, side="right")) - 1
        if i >= 0 and t <= mods[i][1] + mods[i][2]:
            return mods[i][0].split("(", 1)[0]
        return "?"

    return find


def top_ops(trace: dict, k: int = 10) -> list[list]:
    """The ``k`` device operations, as ``program/op``, with the most time
    in the window, summed over executions and averaged over devices."""
    per: dict[str, float] = {}
    for dev in trace["devices"].values():
        module = _module_of(dev)
        for name, s, d in dev["ops"]:
            iv = _clip([[name, s, d]], trace["window"])
            if len(iv):
                key = f"{module(s)}/{_short(name)}"
                per[key] = per.get(key, 0.0) + float(iv[0, 1] - iv[0, 0])
    n = max(len(trace["devices"]), 1)
    rows = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t * 1e-9 / n] for name, t in rows]


def host_segments(spans) -> list[tuple[float, float, str]]:
    """Non-overlapping ``(start, end, name)`` segments of a host track,
    each named by the innermost span open in it. ``spans`` are properly
    nested ``(name, start, end)`` intervals on one clock."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    bounds = sorted({s[1] for s in spans} | {s[2] for s in spans})
    out: list[tuple[float, float, str]] = []
    stack: list = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][1] <= a:
            s = spans[i]
            i += 1
            while stack and stack[-1][2] <= a:
                stack.pop()
            if s[2] > a:
                stack.append(s)
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            name = stack[-1][0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


#: Name of idle time in which no program span was open on the host.
NO_SPAN = "(no program span)"


def idle_by_host(trace: dict, segments, k: int = 10) -> list[list]:
    """Idle device time in the window, by what the host was doing.

    ``segments`` come from :func:`host_segments`, on the trace's clock.
    Returns the ``k`` host activities with the most idle device time,
    as ``[name, seconds]``, averaged over devices.
    """
    t0, t1 = trace["window"]
    per: dict[str, float] = {}
    devs = list(trace["devices"])
    for d in devs:
        busy = busy_intervals(trace, d)
        edges = np.concatenate([[t0], busy.ravel(), [t1]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        j = 0
        for g0, g1 in gaps:
            covered = 0.0
            while j < len(segments) and segments[j][1] <= g0:
                j += 1
            m = j
            while m < len(segments) and segments[m][0] < g1:
                s0, s1, name = segments[m]
                ov = min(s1, g1) - max(s0, g0)
                if ov > 0:
                    per[name] = per.get(name, 0.0) + ov
                    covered += ov
                m += 1
            per[NO_SPAN] = per.get(NO_SPAN, 0.0) + (g1 - g0) - covered
    n = max(len(devs), 1)
    rows = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t * 1e-9 / n] for name, t in rows if t > 0]
