"""From a JAX profiler trace to device busy time, op shares and idle gaps.

`read` takes the `.xplane.pb` the profiler wrote. Device ops are the events
of the "XLA Ops" line of each `/device:` plane, each assigned to the HLO
module whose execution ("XLA Modules" line) it falls in. A trace with no
device plane (a CPU rehearsal) counts the events that name an HLO op on the
host's planes as the ops of one device, so the reduction can be rehearsed;
such numbers are never a device's. Host spans are the harness's own
`TraceAnnotation`s, found by name on the host's planes.

The TPU's trace records no op category, and XLA fuses a scatter into a
fusion named like any other. So an op's opcodes come from the program's own
optimized HLO (`index_hlo`): its own, and those of every instruction in a
computation it calls (a fusion's, an async op's). Ops of a module without
HLO fall back to the words of their instruction name.

`summarize` reduces one trace over the traced window (the extent of the
harness's host spans and the device ops, cut where a device's record ends
early): busy time is the union of a device's op intervals. A share of it
(`Summary.share`) is the union of the intervals of the ops that hold one of
the opcodes a metric names, over the busy time, averaged over the devices.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

import numpy as np

DEVICE_OPS_LINE = "XLA Ops"
DEVICE_MODULES_LINE = "XLA Modules"
# control flow: its body's ops are traced on their own
CONTAINERS = ("while", "conditional", "call")
# how far a device op may lie outside the host span that issued it
CLOCK_SKEW_NS = 10e6


@dataclasses.dataclass
class DeviceOps:
    names: list  # HLO instruction name per event, e.g. "fusion.427"
    modules: list  # HLO module per event, e.g. "jit__run_scan"; "" where unknown
    start: np.ndarray  # (k,) float64 ns
    end: np.ndarray  # (k,) float64 ns


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> DeviceOps
    spans: list  # (name, start ns, end ns) of the harness's host spans


def op_name(event_name: str) -> str:
    """"%fusion.3 = f32[8] fusion(...), ..." -> "fusion.3"."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """"jit__run_scan(8181738304984545327)" -> "jit__run_scan"."""
    return event_name.split("(", 1)[0]


def _ops(events: list) -> DeviceOps:
    """DeviceOps from (name, module, start ns, duration ns) tuples."""
    return DeviceOps(
        names=[e[0] for e in events], modules=[e[1] for e in events],
        start=np.array([e[2] for e in events], np.float64),
        end=np.array([e[2] + e[3] for e in events], np.float64))


def _in_modules(starts: list, modules: list) -> list:
    """The module running at each op start, from (name, start, end) module
    executions."""
    if not modules:
        return [""] * len(starts)
    modules = sorted(modules, key=lambda m: m[1])
    m_start = np.array([m[1] for m in modules], np.float64)
    m_end = np.array([m[2] for m in modules], np.float64)
    i = np.searchsorted(m_start, np.asarray(starts, np.float64), side="right") - 1
    return [modules[j][0] if j >= 0 and s < m_end[j] else ""
            for j, s in zip(i, starts)]


def read(path: str, span_names) -> Trace:
    """Read the newest `.xplane.pb` under `path` (a file or a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    devices, host_ops, spans = {}, [], []
    span_names = set(span_names)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    ops = [(op_name(e.name), e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == DEVICE_MODULES_LINE:
                    modules = [(module_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            if ops:
                owner = _in_modules([s for _, s, _ in ops], modules)
                devices[plane.name] = _ops([(n, m, s, d) for (n, s, d), m in zip(ops, owner)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        host_ops.append((str(stats["hlo_op"]), str(stats.get("hlo_module", "")),
                                         e.start_ns, e.duration_ns))
    if not devices and host_ops:
        devices["/host:CPU"] = _ops(host_ops)
    return Trace(devices=devices, spans=sorted(spans, key=lambda s: s[1]))


@dataclasses.dataclass
class Op:
    opcode: str
    opcodes: frozenset  # its own and those of the computations it calls
    label: str  # the last part of the JAX op name it was lowered from


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?<![\w.%\-])([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def index_hlo(texts) -> dict:
    """{module name: {instruction name: Op}} from optimized HLO module texts
    (`compiled.as_text()`)."""
    index = {}
    for text in texts:
        lines = text.splitlines()
        module = lines[0].split()[1].rstrip(",")
        comps, comp = {}, None  # computation -> [(instr, opcode, calls, label)]
        for line in lines[1:]:
            m = _INSTRUCTION.match(line)
            if m and comp is not None:
                rest = m.group(2)
                opcode = _OPCODE.search(rest)
                label = _OP_NAME.search(rest)
                comps[comp].append((m.group(1), opcode.group(1) if opcode else "",
                                    _CALLS.findall(rest),
                                    label.group(1).rsplit("/", 1)[-1] if label else ""))
                continue
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(1)
                comps[comp] = []
        memo = {}

        def opcodes_of(c, seen=()):
            if c not in memo:
                found = set()
                for _, opcode, calls, _ in comps.get(c, ()):
                    found.add(opcode)
                    for callee in calls:
                        if callee not in seen:
                            found |= opcodes_of(callee, seen + (c,))
                memo[c] = frozenset(found)
            return memo[c]

        ops = {}
        for c, instrs in comps.items():
            for name, opcode, calls, label in instrs:
                k = {opcode}
                for callee in calls:
                    k |= opcodes_of(callee)
                ops[name] = Op(opcode=opcode, opcodes=frozenset(k), label=label)
        index[module] = ops
    return index


def _classify(name: str, module: str, hlo: dict) -> tuple[frozenset, bool, str]:
    """(opcodes, is control flow, label for the breakdown) of one op."""
    op = hlo.get(module, {}).get(name)
    if op is None:
        # "all-to-all.3" -> {"all-to-all"}; "wrapped_scatter" -> {"wrapped", "scatter"}
        return frozenset(re.sub(r"\.\d+$", "", name).split("_")), False, name
    return op.opcodes, op.opcode in CONTAINERS, f"{name} {op.label}".strip()


def union(start: np.ndarray, end: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the union of [start, end)."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.r_[True, s[1:] > e[:-1]]
    last = np.r_[np.flatnonzero(new)[1:] - 1, s.size - 1]
    return s[new], e[last]


def _clip(start, end, t0, t1):
    s, e = np.maximum(start, t0), np.minimum(end, t1)
    keep = e > s
    return s[keep], e[keep]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    idle_share: float  # mean over devices of 1 - busy / window
    n_devices: int
    top_ops: list  # [[op, seconds per device], ...], control flow left out
    idle_gaps: list  # [[host span during the gap, seconds], ...] on the first device
    truncated: bool = False  # a device's ops stop before the last host span
    # per device: (start ns, end ns, index into `classes`) of its clipped ops,
    # and its busy ns
    ops: list = dataclasses.field(default_factory=list, repr=False)
    busy_ns: list = dataclasses.field(default_factory=list, repr=False)
    classes: list = dataclasses.field(default_factory=list, repr=False)  # opcode sets

    def share(self, opcodes) -> Optional[float]:
        """Mean over the devices of the time in ops that hold an opcode
        starting with one of `opcodes`, over busy time; None where no device
        spends time in one."""
        codes = tuple(opcodes)
        hit = np.array([any(o.startswith(codes) for o in c) for c in self.classes] or [False])
        shares = []
        for (s, e, cls), b in zip(self.ops, self.busy_ns):
            m = hit[cls]
            ks, ke = union(s[m], e[m])
            shares.append(float((ke - ks).sum()) / b if b > 0 else 0.0)
        return float(np.mean(shares)) if any(shares) else None


def summarize(trace: Trace, hlo: Optional[dict] = None, t0: Optional[float] = None,
              t1: Optional[float] = None, top: int = 10) -> Optional[Summary]:
    """Reduce the trace over [t0, t1) ns (default: the extent of the host
    spans and the device ops), with ops classified by `hlo` (from
    `index_hlo`). None where the trace holds no device op."""
    hlo = hlo or {}
    if not trace.devices:
        return None
    first_op = min(float(d.start.min()) for d in trace.devices.values())
    last_op = max(float(d.end.max()) for d in trace.devices.values())
    spans_start = min((s for _, s, _ in trace.spans), default=first_op)
    spans_end = max((e for _, _, e in trace.spans), default=last_op)
    # the device's clock and the host's agree to about a millisecond, so
    # the window covers both the harness's spans and the ops traced in them
    t0 = min(spans_start, first_op) if t0 is None else t0
    truncated = False
    if t1 is None:
        t1 = max(spans_end, last_op)
        # a profiler whose event buffer filled up drops the rest of the
        # window: end the window where the shortest device record ends
        shortest = min(float(d.end.max()) for d in trace.devices.values())
        if trace.spans and shortest < max(s for _, s, _ in trace.spans) - CLOCK_SKEW_NS:
            t1, truncated = shortest, True
    window = t1 - t0
    busy, idle, per_op, gaps, clipped = [], [], {}, [], []
    index, classes = {}, []  # (op, module) -> index into classes
    for i, (_, ops) in enumerate(sorted(trace.devices.items())):
        keep = np.minimum(ops.end, t1) > np.maximum(ops.start, t0)
        s, e = _clip(ops.start, ops.end, t0, t1)
        cls = []
        for n, mod, k in zip(ops.names, ops.modules, keep):
            if k:
                if (n, mod) not in index:
                    index[n, mod] = len(classes)
                    classes.append(_classify(n, mod, hlo))
                cls.append(index[n, mod])
        cls = np.array(cls, np.int64)
        clipped.append((s, e, cls))
        us, ue = union(s, e)
        b = float((ue - us).sum())
        busy.append(b)
        idle.append(1.0 - b / window)
        for c, d in zip(cls, e - s):
            _, control, label = classes[c]
            if not control:
                per_op[label] = per_op.get(label, 0.0) + float(d)
        if i == 0:
            gs, ge = np.r_[t0, ue], np.r_[us, t1]
            longest = np.argsort(gs - ge, kind="stable")[:top]
            gaps = [(_label(trace.spans, gs[j], ge[j]), float(ge[j] - gs[j]))
                    for j in longest if ge[j] > gs[j]]
    n_dev = len(trace.devices)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=window / 1e9,
        busy_s=float(np.mean(busy)) / 1e9,
        idle_share=float(np.mean(idle)),
        n_devices=n_dev,
        top_ops=[[n, t / n_dev / 1e9] for n, t in top_ops],
        idle_gaps=[[name, t / 1e9] for name, t in gaps],
        truncated=truncated,
        ops=clipped,
        busy_ns=busy,
        classes=[c[0] for c in classes],
    )


def _label(spans: list, gs: float, ge: float) -> str:
    """What the host was doing for most of the idle gap [gs, ge): the host
    span that overlaps it the most, or 'outside calls' where more of it lies
    outside every span."""
    covered = {}
    for name, s, e in spans:
        o = min(e, ge) - max(s, gs)
        if o > 0:
            covered[name] = covered.get(name, 0.0) + o
    best, label = (ge - gs) - sum(covered.values()), "outside calls"
    for name, o in covered.items():
        if o > best:
            best, label = o, name
    return label
