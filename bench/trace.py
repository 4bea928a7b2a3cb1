"""Profiler trace of the measured window, reduced to what the metric readers
need: the device's busy union, device time per operation and per program,
and the idle gaps named by what the host was doing in them.

``capture`` runs a function under ``jax.profiler`` and reduces the
``.xplane.pb`` it writes; ``reduce_events`` does the arithmetic on plain
event tuples, so it is tested without a chip. ``pallas_kernels`` names the
Pallas kernels of a compiled program: the trace names a device op by its
HLO instruction, and a Pallas kernel's instruction is a custom call whose
name says nothing of the kernel.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import base64
import re
import shutil
import tempfile
import time

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Reduced:
    window_s: float  # length of the traced window, host clock
    busy_s: float  # union of device op intervals, mean over devices
    n_devices: int
    ops: dict  # op name -> device seconds, summed over devices
    op_counts: dict  # op name -> executions, summed over devices
    modules: dict  # program name -> list of execution seconds
    gaps: list  # [(host activity, idle seconds)], largest first

    @property
    def idle_share(self) -> float | None:
        if self.window_s <= 0 or self.n_devices == 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, kernels: dict, names) -> float:
        """Device seconds of the ops whose HLO instruction ``kernels`` (from
        ``pallas_kernels``) maps to one of the kernel ``names``."""
        return sum(v for k, v in self.ops.items()
                   if kernels.get(instruction(k)) in names)

    def kernel_executions(self, kernels: dict, names) -> int:
        return sum(v for k, v in self.op_counts.items()
                   if kernels.get(instruction(k)) in names)

    def module_runs(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [d for k, v in self.modules.items() if rx.search(k) for d in v]

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


_INSTRUCTION = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
_CUSTOM_CALL = re.compile(
    r"%?([A-Za-z0-9_.\-]+) = .*custom-call\(.*custom_call_target=\"tpu_custom_call\"")
_BODY = re.compile(r'body\\?":\s*\\?"([A-Za-z0-9+/=]+)')


def instruction(op_name: str) -> str:
    """The HLO instruction a trace op names (its name or HLO text)."""
    m = _INSTRUCTION.match(op_name)
    return m.group(1) if m else op_name


def pallas_kernels(hlo_text: str) -> dict:
    """HLO instruction -> Pallas kernel name, for each Mosaic custom call of
    a compiled program's HLO text. The kernel's name is that of the Mosaic
    module its backend config carries (the ``pallas_call``'s kernel
    function), so a kernel is found by name whatever XLA calls its op."""
    from jaxlib.mlir import ir
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    out = {}
    for line in hlo_text.splitlines():
        call, body = _CUSTOM_CALL.search(line), _BODY.search(line)
        if call is None or body is None:
            continue
        mod = ir.Module.parse(base64.b64decode(body.group(1)), context=ctx)
        out[call.group(1)] = ir.StringAttr(mod.operation.attributes["sym_name"]).value
    return out


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _name_gaps(host, gaps, named: int = 256):
    """Sum idle time by what the host was doing: the shortest host event
    that spans a gap's midpoint names it. The ``named`` longest gaps are
    looked up; the rest are summed as short gaps."""
    import numpy as np
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    out = collections.defaultdict(float)
    starts = np.array([e.start_ns for e in host], np.float64)
    ends = starts + np.array([e.dur_ns for e in host], np.float64)
    for k, (lo, hi) in enumerate(gaps):
        sec = (hi - lo) * 1e-9
        if k >= named:
            out["short gaps"] += sec
            continue
        mid = 0.5 * (lo + hi)
        cover = np.flatnonzero((starts <= mid) & (ends >= mid))
        if cover.size == 0:
            out["host (no span)"] += sec
        else:
            out[host[cover[np.argmin(ends[cover] - starts[cover])]].name] += sec
    return sorted(out.items(), key=lambda kv: -kv[1])


def reduce_events(events, window_s: float, min_gap_s: float = 1e-4) -> Reduced:
    """Reduce trace events. Device events are those of ``/device:TPU:<n>``
    planes; their ``XLA Ops`` line gives the busy union and per-op time,
    ``XLA Modules`` the per-program executions. Gaps between busy intervals
    on the first device longer than ``min_gap_s`` are named by the host."""
    dev = collections.defaultdict(list)
    ops = collections.defaultdict(float)
    counts = collections.defaultdict(int)
    modules = collections.defaultdict(list)
    host = []
    for ev in events:
        if _DEVICE_PLANE.match(ev.plane):
            if ev.line == OPS_LINE:
                dev[ev.plane].append((ev.start_ns, ev.start_ns + ev.dur_ns))
                ops[ev.name] += ev.dur_ns * 1e-9
                counts[ev.name] += 1
            elif ev.line == MODULES_LINE:
                modules[ev.name].append(ev.dur_ns * 1e-9)
        elif ev.plane.startswith("/host:") and ev.dur_ns > 0:
            host.append(ev)
    busy, gaps = [], []
    for i, plane in enumerate(sorted(dev)):
        merged = _union(dev[plane])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
                    if (s1 - e0) * 1e-9 >= min_gap_s]
    return Reduced(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        n_devices=len(busy), ops=dict(ops), op_counts=dict(counts),
        modules=dict(modules),
        gaps=_name_gaps(host, gaps))


def read_xplane(path: str):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        device = _DEVICE_PLANE.match(plane.name)
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            for ev in line.events:
                yield Event(plane.name, line.name, ev.name, ev.start_ns,
                            ev.duration_ns)


def capture(fn):
    """Run ``fn()`` under the profiler; returns (fn's result, Reduced). The
    trace is written under ``TMPDIR`` and removed once reduced."""
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host TraceMe spans only: cheap to parse
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        t0 = time.perf_counter()
        try:
            out = fn()
        finally:
            window = time.perf_counter() - t0
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return out, reduce_events(read_xplane(paths[0]), window)
    finally:
        shutil.rmtree(d, ignore_errors=True)
