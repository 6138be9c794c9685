"""Profiler trace -> numbers: device busy and idle time, device time per
op category and per kernel, and idle gaps on the device attributed to
the benchmark's own host spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<i>``; their
``XLA Ops`` line holds one event per executed HLO op and their
``XLA Modules`` line one event per program execution. Host spans are the
``jax.profiler.TraceAnnotation`` events the benchmark names ``bench.*``.
Every time is in nanoseconds on the host clock, to which the profiler
aligns the device.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops whose event spans the ops of their body (also recorded): left out,
# so that busy time is the time in which a leaf operation ran
CONTAINERS = ("while", "conditional", "call")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _is_device(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[12:].isdigit()


def _events(line, leaves_only=False):
    out = []
    for e in line.events:
        if leaves_only and short_name(e.name).split(".")[0] in CONTAINERS:
            continue
        out.append((e.start_ns, e.end_ns, e.name))
    return out


def union(intervals):
    """Merged, sorted, disjoint intervals of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def covered(merged, lo, hi) -> float:
    """Length of [lo, hi] that ``merged`` intervals cover."""
    return float(sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged))


def short_name(name: str) -> str:
    """An op's name without the HLO text the profiler appends."""
    return name.split(" = ")[0].lstrip("%")[:120]


def category(name: str) -> str:
    """The op's kind from its HLO name (``multiply_reduce_fusion.430`` ->
    ``multiply_reduce_fusion``): the TPU trace's op events carry no
    category of their own."""
    return short_name(name).split(".")[0] or "unknown"


def conv_ops(hlo_text: str) -> set:
    """Names of the ops of an optimized HLO module that are convolutions
    or fusions whose computation holds one (``compiled.as_text()``).
    Computation headers start a line; instructions are indented."""
    import re
    has_conv, fusions, convs, current = {}, {}, set(), None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "\t") and line.rstrip().endswith("{"):
            current = line.split()[1 if line.startswith("ENTRY") else 0]
            current = current.lstrip("%")
            continue
        m = re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = ", line)
        if not m:
            continue
        if " convolution(" in line:
            has_conv[current] = True
            convs.add(m.group(1))
        c = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
        if c:
            fusions[m.group(1)] = c.group(1)
    return convs | {f for f, comp in fusions.items() if has_conv.get(comp)}


class Reduction:
    """The numbers the per-layer metrics read from one traced window."""

    def __init__(self, pd):
        self.devices = {}                 # plane name -> (ops, modules)
        self.spans = []                   # (start, end, name), host
        for plane in pd.planes:
            lines = {ln.name: ln for ln in plane.lines}
            if _is_device(plane.name):
                ops = (_events(lines[OPS_LINE], leaves_only=True)
                       if OPS_LINE in lines else [])
                mods = (_events(lines[MODULES_LINE])
                        if MODULES_LINE in lines else [])
                self.devices[plane.name] = (ops, mods)
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.append((e.start_ns, e.end_ns, e.name))
        self.spans.sort()
        # the window: from the first segment's dispatch to the last span's
        # end (the last pull of losses); without spans, the ops' extent
        seg = [s for s in self.spans if s[2] == SPAN_PREFIX + "segment"]
        if seg:
            window = (seg[0][0], max(s[1] for s in self.spans))
        else:
            allops = [o for ops, _ in self.devices.values() for o in ops]
            window = ((min(o[0] for o in allops), max(o[1] for o in allops))
                      if allops else (0, 0))
        self.window = window
        self.busy = {name: union((max(s, window[0]), min(e, window[1]))
                                 for s, e, _ in ops
                                 if e > window[0] and s < window[1])
                     for name, (ops, _) in self.devices.items()}

    # -- totals over the window ------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, the union of op intervals, averaged over chips."""
        if not self.busy:
            return 0.0
        return sum(covered(m, *self.window) for m in self.busy.values()) \
            / len(self.busy) / 1e9

    def _ops(self):
        for ops, _ in self.devices.values():
            for s, e, name in ops:
                if e > self.window[0] and s < self.window[1]:
                    yield s, e, name

    def time_by(self, key) -> dict:
        """Device seconds per ``key(name)``, averaged over chips."""
        out = defaultdict(float)
        for s, e, name in self._ops():
            out[key(name)] += (e - s) / 1e9
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def op_seconds(self, pred) -> float:
        """Device seconds of the ops for which ``pred(short name)``."""
        n = max(len(self.devices), 1)
        return sum((e - s) for s, e, name in self._ops()
                   if pred(short_name(name))) / n / 1e9

    def op_calls(self, pred) -> float:
        """Executions of the ops for which ``pred(short name)``, per chip."""
        n = max(len(self.devices), 1)
        return sum(1 for _, _, name in self._ops()
                   if pred(short_name(name))) / n

    # -- idle time -------------------------------------------------------------
    def gaps(self, device=None):
        """Idle intervals of one chip inside the window."""
        if not self.busy:
            return []
        merged = self.busy[device or sorted(self.busy)[0]]
        out, t = [], self.window[0]
        for s, e in merged:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def host_span_at(self, lo, hi) -> str:
        """The innermost benchmark span that overlaps [lo, hi] the most."""
        best, best_len = "no span", 0.0
        for s, e, name in self.spans:
            ov = min(e, hi) - max(s, lo)
            if ov > 0 and (ov > best_len or (ov == best_len and
                                             name != best)):
                best, best_len = name, ov
        return best

    def idle_by_span(self) -> dict:
        out = defaultdict(float)
        for lo, hi in self.gaps():
            out[self.host_span_at(lo, hi)] += (hi - lo) / 1e9
        return dict(out)

    def main_module(self):
        """The program that ran longest in the window (the segment)."""
        if not self.devices:
            return None
        tot = defaultdict(int)
        for s, e, name in self.devices[sorted(self.devices)[0]][1]:
            if e > self.window[0] and s < self.window[1]:
                tot[name] += e - s
        return max(tot, key=tot.get) if tot else None

    def boundary_idle_s(self):
        """Device idle seconds between consecutive executions of the main
        program, one entry per boundary (first chip)."""
        main = self.main_module()
        if main is None:
            return []
        dev = sorted(self.devices)[0]
        mods = sorted((s, e) for s, e, name in self.devices[dev][1]
                      if name == main
                      and e > self.window[0] and s < self.window[1])
        merged = self.busy[dev]
        return [((b0 - a1) - covered(merged, a1, b0)) / 1e9
                for (_, a1), (b0, _) in zip(mods, mods[1:])]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.time_by(short_name).items(),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(((self.host_span_at(lo, hi), (hi - lo) / 1e9)
                       for lo, hi in self.gaps()), key=lambda kv: -kv[1])
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in gaps[:top]]}
