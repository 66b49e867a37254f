"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``: a device is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds the operations it ran and
its ``XLA Modules`` line the programs.  The harness's own spans
(``chipbench.engine_run``, ``chipbench.wait``, ``chipbench.submit``) and
the engine's (``serve.*``: admit, prefill, dispatch, read, emit, around each
piece of host work in ``repro.serve.engine``'s loop) are host events on the
same clock.

- busy: the union of a device's operation intervals; idle is the rest.
  Idle while serving leaves out the harness's ``wait`` spans (no request
  in the engine).  A span opened before the profiler started is never
  recorded, so the long ``engine_run`` spans are not relied on.
- the breakdown: time per operation, by program; control flow (``while``,
  ``conditional``, ``call``), which encloses the operations it runs, is
  left out of it.  A gap between the first device's operations is named by
  the harness's ``submit`` or ``wait`` span open at its middle, else by the
  engine span open there, else ``engine_run``.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from collections import defaultdict
from pathlib import Path
from typing import Optional

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "chipbench."
ENGINE_PREFIX = "serve."
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.|$)")
# what a gap is labelled by: a harness span open at its middle, else an
# engine span, else the engine's loop, which holds the main thread whenever
# it is not waiting
LABEL_ORDER = ("submit", "wait")
SERVING = "engine_run"
TOP = 10


@dataclasses.dataclass
class Device:
    ops: list          # (start_ns, end_ns, short name, control flow?)
    modules: list      # (start_ns, end_ns, name)

    def intervals(self):
        return _union([(s, e) for s, e, _, _ in self.ops])


@dataclasses.dataclass
class Reduced:
    n_devices: int
    window_s: float
    busy_s: float                    # mean over devices
    idle_share_serving: Optional[float]
    decode_steps: int                # decode programs on the first device
    device_ops: list
    idle_gaps: list


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv) -> float:
    return float(sum(e - s for s, e in iv))


def _overlap(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ")[0].lstrip("%")


def _short(module: str) -> str:
    return module.split("(")[0]


def read(path: Path):
    """Devices by index, the harness's host spans (start_ns, end_ns, name)
    and the engine's (start_ns, end_ns, name, args), each list by start, of
    a trace file (``.xplane.pb``, or gzipped as ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    devices, spans, engine = {}, [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device([], [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        short = _op_name(e.name)
                        dev.ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                        short, bool(CONTROL_FLOW.match(short))))
                elif line.name == MODULES_LINE:
                    dev.modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                    _short(e.name)) for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(SPAN_PREFIX):]))
                    elif e.name.startswith(ENGINE_PREFIX):
                        engine.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name, dict(e.stats)))
    if not devices or not any(d.ops for d in devices.values()):
        raise ValueError(f"{path}: no device plane with operations")
    return ([devices[k] for k in sorted(devices)], sorted(spans),
            sorted(engine, key=lambda sp: sp[0]))


def load(path: Path):
    """Devices by index and the harness's host spans of a trace file."""
    devices, spans, _ = read(path)
    return devices, spans


def _module_of(modules, t):
    for s, e, name in modules:
        if s <= t < e:
            return name
    return "no module"


def reduce(path: Path) -> Reduced:
    return reduce_devices(*read(path))


def reduce_devices(devices: list, spans: list, engine: list = ()) -> Reduced:
    """The numbers of :class:`Reduced` from devices, harness spans and
    engine spans."""
    t_lo = min(d.ops[0][0] for d in devices if d.ops)
    t_hi = max(max(o[1] for o in d.ops) for d in devices if d.ops)
    window = [[t_lo, t_hi]]
    serving = _subtract(window, _union(
        [(s, e) for s, e, n in spans if n == "wait"]))

    busy, idle = [], []
    op_time = defaultdict(float)
    for dev in devices:
        ops = dev.intervals()
        busy.append(_length(ops))
        if _length(serving):
            idle.append(1.0 - _overlap(ops, serving) / _length(serving))
        for s, e, name, control in dev.ops:
            if not control:
                op_time[f"{_module_of(dev.modules, s)}/{name}"] += e - s
    n = len(devices)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(
        n_devices=n, window_s=(t_hi - t_lo) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        idle_share_serving=sum(idle) / n if idle else None,
        decode_steps=sum(1 for m in devices[0].modules if "decode" in m[2]),
        device_ops=[[k, v / n * 1e-9] for k, v in top_ops],
        idle_gaps=[[label, secs] for secs, _, label in
                   label_gaps(devices[0], spans, engine)[:TOP]])


def _subtract(a, b):
    """Merged intervals of ``a`` not covered by ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _gaps(dev: Device):
    """Start and end (ns) of every gap between the device's operations."""
    ops = np.asarray(dev.intervals(), dtype=float).reshape(-1, 2)
    lo, hi = ops[:-1, 1], ops[1:, 0]
    keep = hi > lo
    return lo[keep], hi[keep]


def _name_open(spans: list, t: np.ndarray, labels: np.ndarray) -> None:
    """Set ``labels[i]`` to the name of the span open at ``t[i]``, where
    one is; the spans are one thread's, which do not overlap."""
    if not spans:
        return
    starts = np.array([s[0] for s in spans], dtype=float)
    ends = np.array([s[1] for s in spans], dtype=float)
    names = np.array([s[2] for s in spans], dtype=object)
    i = np.searchsorted(starts, t, side="right") - 1
    hit = (i >= 0) & (t < ends[np.maximum(i, 0)])
    labels[hit] = names[i[hit]]


def label_gaps(dev: Device, harness: list, engine: list) -> list:
    """Every gap of ``dev`` as (seconds, middle ns, label), longest first
    (ties: the later first), named by what the host was doing at its
    middle."""
    lo, hi = _gaps(dev)
    mid = (lo + hi) / 2
    labels = np.full(len(mid), SERVING, dtype=object)
    # lowest precedence first: each later one overwrites
    _name_open([(s, e, n) for s, e, n, _ in engine], mid, labels)
    for name in reversed(LABEL_ORDER):
        _name_open([sp for sp in harness if sp[2] == name], mid, labels)
    order = np.lexsort((-mid, -(hi - lo)))
    return [(float((hi[i] - lo[i]) * 1e-9), float(mid[i]), labels[i])
            for i in order]
