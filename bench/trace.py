"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``reduce(trace_dir)`` reads the ``.xplane.pb`` that
``jax.profiler.start_trace`` wrote and keeps three things:

  * the traced window: the harness's host span ``bench.window``;
  * on each device plane (``/device:TPU:<i>``), the events of its
    ``XLA Ops`` line (one per operation run on the device), each named
    ``<module>/<op>`` from the enclosing ``XLA Modules`` event and the
    operation's HLO name, with numbered suffixes dropped
    (``jit_sdtw_pallas/sdtw_pallas``);
  * every event of the host plane, the harness's ``bench.*`` spans and
    the runtime's own among them.

Host and device events share one clock in the trace, so a device gap
can be set beside what the host was doing in it. Busy time is the union
of the operation intervals inside the window, averaged over the devices
used; idle is the rest of the window.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE = re.compile(r"\(\d+\)$")


def options():
    """Profiler options for a benchmark window: the Python function tracer
    off (it records every Python call, and costs far more than the spans
    it would add)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _op_base(hlo: str) -> str:
    """``%sdtw_pallas.1 = (s32[...]) custom-call(...)`` -> ``sdtw_pallas``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


class Device:
    """The operations one device ran: names, starts and ends (ns)."""

    def __init__(self, names, starts, ends):
        self.names = list(names)
        self.starts = np.asarray(starts, np.float64)
        self.ends = np.asarray(ends, np.float64)


class Summary:
    """What the benchmark reads from one traced window."""

    def __init__(self, window, devices, host):
        self.t0, self.t1 = window
        self.window_s = (self.t1 - self.t0) / 1e9
        self.devices = devices
        self.host = host                # [(name, start_ns, end_ns)]

    def _clipped(self, dev: Device, mask=None):
        s = np.clip(dev.starts, self.t0, self.t1)
        e = np.clip(dev.ends, self.t0, self.t1)
        if mask is not None:
            s, e = s[mask], e[mask]
        return s, e

    def _busy_intervals(self, dev: Device):
        s, e = self._clipped(dev)
        keep = e > s
        s, e = s[keep], e[keep]
        order = np.argsort(s, kind="stable")
        merged = []
        for a, b in zip(s[order], e[order]):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return merged

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        per = [sum(b - a for a, b in self._busy_intervals(d)) / 1e9
               for d in self.devices]
        return float(np.mean(per)) if per else 0.0

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def op_seconds(self, pattern: re.Pattern) -> float:
        """Device seconds of the operations whose ``<module>/<op>`` name
        matches ``pattern``, inside the window, summed over devices."""
        total = 0.0
        for d in self.devices:
            mask = np.array([bool(pattern.search(n)) for n in d.names],
                            bool)
            if mask.any():
                s, e = self._clipped(d, mask)
                total += float(np.sum(e - s)) / 1e9
        return total

    def top_ops(self, k: int = 10) -> list:
        """``[[name, seconds], ...]``: the operations that took the most
        device time in the window, summed over devices."""
        tot: dict = {}
        for d in self.devices:
            s, e = self._clipped(d)
            for name, dur in zip(d.names, e - s):
                tot[name] = tot.get(name, 0.0) + float(dur) / 1e9
        return [[n, v] for n, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """``[[host activity, seconds], ...]``: the ``k`` longest spans of
        the window in which the first device ran nothing, each named by
        the host event that overlaps it most (ties: the shorter event,
        the more specific), or ``no host event``."""
        if not self.devices:
            return []
        busy = self._busy_intervals(self.devices[0])
        gaps, cur = [], self.t0
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if self.t1 > cur:
            gaps.append((cur, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        hs = [h for h in self.host if h[0] != WINDOW_SPAN]
        names = [h[0] for h in hs]
        hs_s = np.asarray([h[1] for h in hs], np.float64)
        hs_e = np.asarray([h[2] for h in hs], np.float64)
        out = []
        for g0, g1 in gaps[:k]:
            name = "no host event"
            if len(hs):
                ov = np.minimum(hs_e, g1) - np.maximum(hs_s, g0)
                if ov.max() > 0:
                    best = np.lexsort((hs_e - hs_s, -ov))[0]
                    name = names[best][:80]
            out.append([name, float(g1 - g0) / 1e9])
        return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce(trace_dir, n_devices: int = 1) -> Summary:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return reduce_file(files[0], n_devices)


def reduce_file(path, n_devices: int = 1) -> Summary:
    """Read one ``.xplane.pb``; keep the first ``n_devices`` TPU planes."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    dev_planes, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev_planes[int(m.group(1))] = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_events(line))
    window = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span in the trace, "
                           f"found {len(window)}")
    devices = []
    for idx in sorted(dev_planes)[:n_devices]:
        lines = {ln.name: ln for ln in dev_planes[idx].lines}
        mods = (sorted(_events(lines["XLA Modules"]), key=lambda x: x[1])
                if "XLA Modules" in lines else [])
        mod_starts = [x[1] for x in mods]
        ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else ()
        names, starts, ends = [], [], []
        for hlo, s, e in ops:
            i = bisect.bisect_right(mod_starts, s) - 1
            mod = (_MODULE.sub("", mods[i][0]) if i >= 0 and s < mods[i][2]
                   else "?")
            names.append(f"{mod}/{_op_base(hlo)}")
            starts.append(s)
            ends.append(e)
        devices.append(Device(names, starts, ends))
    return Summary(window[0], devices, host)
