"""From a ``jax.profiler`` trace to the per-layer numbers of a traced run.

The reduction is fixed here so that every run computes the same number in
the same way:

* device ops are the events of each device plane's ``XLA Ops`` line, whose
  names are the HLO instruction text (``%fusion.27 = f32[...] fusion(...)``);
  the ``XLA Modules`` line says which program each op belongs to;
* each op is put in a class by the op tables under ``bench/optables/<class>/``:
  regular expressions on the instruction text (field ``hlo``) or on the JAX
  scope path that the compiled program's metadata gives the instruction
  (field ``op_name``, e.g. ``jit(fwd)/jit(fft1d)/jit(fft)``); ops that no
  table claims are the ``local`` class. Async collectives also count, as
  collective time only, with their in-flight interval from the ``Async
  XLA Ops`` line;
* a class's time on a device is the measure of the union of its ops'
  intervals, so nested or overlapping events are counted once;
* busy time is the union of all op intervals, idle time the rest of the
  traced window (the harness's ``bench.window`` host span), and each idle
  gap between device ops is labelled by the harness's host span
  (``bench.dispatch``, ``bench.wait``) that overlaps it most.

Times are averaged over the chips of the cell and divided by the steps of
the traced window where a reader asks for them per step.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import shutil
import tempfile
from pathlib import Path

OPTABLES = Path(__file__).resolve().parent / "optables"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_SPANS = ("bench.dispatch", "bench.wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


INSTR = re.compile(r"^%?([\w.\-]+) = ")
MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str             # the HLO instruction text
    start: float          # ns, on the profile's clock
    end: float
    module: str = ""      # the program (HLO module name) it ran in
    is_async: bool = False

    @property
    def instr(self) -> str:
        m = INSTR.match(self.name)
        return m.group(1) if m else self.name


@dataclasses.dataclass
class Capture:
    """What a reduction needs from one trace."""
    devices: dict          # device id -> [Op] of its ops lines
    host: list             # [(name, start, end)] harness spans, ns
    window: tuple          # (start, end) of the traced window, ns


def capture(fn):
    """Run ``fn()`` under the profiler; return ``(fn(), Capture)``.

    The trace is written to a temporary directory and deleted once read.
    """
    import jax

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb, found {paths}")
        return out, load(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def load(path) -> Capture:
    """Read a ``.xplane.pb`` file into a :class:`Capture`."""
    from jax.profiler import ProfileData

    return extract(ProfileData.from_file(str(path)))


def extract(data) -> Capture:
    devices, host, window = {}, [], None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns, e.end_ns))
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.end_ns, MODULE.match(e.name).group(1))
                         for e in lines.get(MODULES_LINE, []))

        def module_of(t):
            for s, e, name in modules:
                if s <= t <= e:
                    return name
            return ""

        devices[int(m.group(1))] = [
            Op(e.name, e.start_ns, e.end_ns, module_of(e.start_ns), line == ASYNC_LINE)
            for line in (OPS_LINE, ASYNC_LINE) for e in lines.get(line, [])]
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} span in the trace")
    return Capture(devices=devices, host=host, window=window)


def op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` from a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line.strip().removeprefix("ROOT "))
        if m:
            scope = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = scope.group(1) if scope else ""
    return out


def module_name(hlo_text: str) -> str:
    return hlo_text.split(None, 2)[1].rstrip(",")


# ---------------------------------------------------------------------------
# op tables
# ---------------------------------------------------------------------------

def load_tables(root: Path = OPTABLES) -> dict:
    """``{class: [(field, compiled regex)]}`` from every ``*.json`` under
    ``root/<class>/``. A new pattern file joins its class by being there."""
    tables = {}
    for cls_dir in sorted(p for p in root.iterdir() if p.is_dir()):
        pats = []
        for f in sorted(cls_dir.glob("*.json")):
            for m in json.loads(f.read_text())["match"]:
                pats.append((m["field"], re.compile(m["regex"])))
        tables[cls_dir.name] = pats
    return tables


def classify(op: Op, tables: dict, scopes: dict) -> str:
    """The op's class; ``scopes`` is ``{module: {instruction: op_name}}``."""
    fields = {"hlo": op.name,
              "op_name": scopes.get(op.module, {}).get(op.instr, "")}
    for cls, pats in tables.items():
        for field, rx in pats:
            if rx.search(fields[field]):
                return cls
    return "local"


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Sorted disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi) -> list:
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    """Per-chip means over the traced window; readers divide by ``steps``."""
    steps: int
    chips: int
    window_s: float
    busy_s: float
    class_s: dict          # class -> seconds (union of its ops)
    exposed_s: dict        # class -> seconds with no other class running
    op_s: dict             # op name -> seconds (summed durations)
    idle_gaps: list        # [(label, seconds)] longest first
    fft_flops: float       # algorithm's work per chip per step
    fft_bytes: float
    peaks: dict


def reduce(cap: Capture, device_ids, steps: int, tables: dict, scopes: dict,
           fft_work: tuple, peaks: dict) -> Summary:
    """Per-chip means of one traced window.

    Every device op in the trace counts: tracing starts just before the
    window's first dispatch and stops after its last wait, with nothing else
    running on the chip. The window's length is the host span's. The device
    clock is not aligned with the host's to better than about a millisecond,
    so device time is never clipped to the host span: gaps between device
    ops are labelled by the host span they fall in, and the idle time before
    the first op and after the last is reported as ``window edges``.
    """
    window = cap.window[1] - cap.window[0]
    ids = list(device_ids)
    missing = [d for d in ids if d not in cap.devices]
    if missing:
        raise RuntimeError(f"no {OPS_LINE!r} line for devices {missing}; "
                           f"trace has {sorted(cap.devices)}")
    chips = len(ids)
    class_s, exposed_s, op_s = {}, {}, {}
    busy_total, idle = 0.0, []
    for d in ids:
        by_cls, in_flight = {}, []
        for op in cap.devices[d]:
            cls = classify(op, tables, scopes)
            if op.is_async:
                if cls == "collective":
                    in_flight.append((op.start, op.end))
                continue
            by_cls.setdefault(cls, []).append((op.start, op.end))
            key = f"{op.module}:{op.instr} [{cls}]"
            op_s[key] = op_s.get(key, 0.0) + (op.end - op.start) * 1e-9 / chips
        busy = union([iv for ivs in by_cls.values() for iv in ivs])
        if not busy:
            continue
        busy_total += measure(busy)
        if in_flight:
            by_cls.setdefault("collective", []).extend(in_flight)
        for cls, ivs in by_cls.items():
            mine = union(ivs)
            others = union([iv for c, v in by_cls.items() if c != cls
                            for iv in v])
            class_s[cls] = class_s.get(cls, 0.0) + measure(mine) * 1e-9 / chips
            exposed_s[cls] = (exposed_s.get(cls, 0.0)
                              + measure(subtract(mine, others)) * 1e-9 / chips)
        for g0, g1 in gaps(busy, busy[0][0], busy[-1][1]):
            idle.append((f"{_label(cap.host, g0, g1)} tpu{d}",
                         (g1 - g0) * 1e-9))
        edges = window - (busy[-1][1] - busy[0][0])
        idle.append((f"window edges tpu{d}", max(edges, 0.0) * 1e-9))
    idle.sort(key=lambda t: -t[1])
    return Summary(
        steps=steps, chips=chips, window_s=window * 1e-9,
        busy_s=busy_total * 1e-9 / chips, class_s=class_s,
        exposed_s=exposed_s, op_s=op_s, idle_gaps=idle,
        fft_flops=fft_work[0], fft_bytes=fft_work[1], peaks=peaks)


def _label(host, g0, g1) -> str:
    best, label = 0.0, "host.other"
    for name, s, e in host:
        ov = min(e, g1) - max(s, g0)
        if ov > best:
            best, label = ov, name
    return label


def breakdown(s: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.idle_gaps[:top]]}
