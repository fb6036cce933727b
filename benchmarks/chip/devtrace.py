"""Reduction of a JAX profiler trace to device operations, for the
per-layer metric readers under ``metrics/``.

A TPU trace holds one plane per device (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO instruction; the event's
name is the instruction's text (``%fusion.2 = f32[...] fusion(...)``).
The instruction's origin in the program (its JAX name stack, such as
``jit(vic_step)/jit(fft_poisson)/jit(fft)``) is not in the event: it is
read from the ``metadata={op_name=...}`` of the same instruction in the
compiled HLO text of the program that ran, matched by module and
instruction name.

Everything here is plain Python over :class:`Op` records, so the readers
can be tested on a small recorded trace (``tests/fixtures``).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import importlib.util
import json
import pathlib
import re
import sys
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "all-to-all",
                    "collective-permute", "reduce-scatter",
                    "collective-broadcast")

_INSTR = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?"
                       r'op_name="([^"]*)"')
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
HERE = pathlib.Path(__file__).resolve().parent


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, imported by path (a metric's
    name may hold dots)."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: HLO instruction ``name`` of opcode ``kind``
    (``custom-call`` ops carry their ``target``), its JAX name stack
    ``op_name`` ('' where unknown), the ``module`` it ran in, and its
    interval on the device clock."""
    name: str
    kind: str
    target: str
    op_name: str
    module: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DeviceTrace:
    """Per-device operations of one traced window, and the host's
    annotated spans (name, start_ns, end_ns) on the same clock."""
    devices: List[List[Op]]
    host_spans: List[Tuple[str, float, float]]

    def to_json(self) -> dict:
        return {"devices": [[dataclasses.astuple(o) for o in ops]
                            for ops in self.devices],
                "host_spans": [list(s) for s in self.host_spans]}

    @classmethod
    def from_json(cls, d: dict) -> "DeviceTrace":
        return cls(devices=[[Op(*o) for o in ops] for ops in d["devices"]],
                   host_spans=[tuple(s) for s in d["host_spans"]])


def load_fixture(path: str) -> DeviceTrace:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return DeviceTrace.from_json(json.load(f))


def op_names_from_hlo(texts: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """{(module, instruction): op_name} from compiled HLO module texts."""
    out = {}
    for text in texts:
        m = _HLO_MODULE.search(text)
        module = m.group(1) if m else ""
        for line in text.splitlines():
            h = _HLO_LINE.match(line)
            if h:
                out[(module, h.group(1))] = h.group(2)
    return out


def _module_key(event_name: str) -> str:
    """'jit_step(6804859246961227334)' -> 'jit_step'."""
    return event_name.split("(")[0]


def parse_instruction(text: str) -> Tuple[str, str, str]:
    """(name, opcode, custom-call target) of an HLO instruction's text."""
    m = _INSTR.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), "", ""
    t = _TARGET.search(text) if m.group(2) == "custom-call" else None
    return m.group(1), m.group(2), t.group(1) if t else ""


def from_xplane(logdir: str, hlo_texts: Sequence[str] = (),
                host_span_names: Sequence[str] = ()) -> DeviceTrace:
    """Read the ``.xplane.pb`` the profiler wrote under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}: {paths}")
    data = ProfileData.from_file(paths[0])
    names = op_names_from_hlo(hlo_texts)
    # the module text's name may carry a suffix the event lacks (or not)
    by_instr: Dict[Tuple[str, str], str] = {}
    for (mod, instr), op_name in names.items():
        by_instr[(mod, instr)] = op_name
        by_instr[(mod.split(".")[0], instr)] = op_name
    devices, host = [], []
    wanted = set(host_span_names)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                ((e.start_ns, e.start_ns + e.duration_ns,
                  _module_key(e.name))
                 for e in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ())))
            ops = []
            mi = 0
            for e in sorted(lines["XLA Ops"].events if "XLA Ops" in lines
                            else (), key=lambda e: e.start_ns):
                while mi + 1 < len(modules) and modules[mi][1] < e.start_ns:
                    mi += 1
                mod = ""
                if modules and modules[mi][0] <= e.start_ns <= modules[mi][1]:
                    mod = modules[mi][2]
                name, kind, target = parse_instruction(e.name)
                ops.append(Op(name, kind, target,
                              by_instr.get((mod, name), ""), mod,
                              float(e.start_ns), float(e.duration_ns)))
            devices.append((plane.name, ops))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    return DeviceTrace(devices=[ops for _, ops in devices],
                       host_spans=sorted(host, key=lambda s: s[1]))


# --------------------------------------------------------------------------
# Interval arithmetic
# --------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(ops: Iterable[Op]) -> float:
    """Device time covered by ``ops`` (nested and overlapping ops, such as a
    ``while`` and the ops of its body, are counted once)."""
    return sum(e - s for s, e in union((o.start_ns, o.end_ns) for o in ops))


def select(ops: Iterable[Op], pred: Callable[[Op], bool]) -> List[Op]:
    return [o for o in ops if pred(o)]


def is_collective(op: Op) -> bool:
    return op.kind.startswith(COLLECTIVE_KINDS)


def is_pallas(op: Op) -> bool:
    return op.kind == "custom-call" and op.target == "tpu_custom_call"


def per_step_ms(ctx, pred: Callable[[Op], bool],
                over: str = "mean") -> Optional[float]:
    """Device time of the ops matching ``pred`` per window step, in ms:
    the mean (or ``over='max'``: the slowest) over the devices used. None
    where no op matches on any device."""
    per_dev = [union_ns(select(ops, pred)) for ops in ctx.trace.devices]
    if not any(per_dev):
        return None
    agg = max(per_dev) if over == "max" else sum(per_dev) / len(per_dev)
    return agg / 1e6 / ctx.steps


def idle_share_pct(ctx) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device,
    in %, averaged over the devices used."""
    busy = [union_ns(ops) for ops in ctx.trace.devices]
    if not any(busy):
        return None
    mean_busy_s = sum(busy) / len(busy) / 1e9
    return 100.0 * (1.0 - mean_busy_s / ctx.window_s)


def roofline_pct(ctx, flops: float, nbytes: float,
                 kernel_ms: Optional[float]) -> Optional[dict]:
    """Share of its roofline a kernel reached: the least time the chips
    could take for the work counted (the larger of operations over peak
    FLOP/s and bytes over HBM bandwidth, over all chips used) divided by
    the kernel's measured time per step. Returns {'value', 'bound'}."""
    if not kernel_ms:
        return None
    peak = ctx.peaks
    t_flops = flops / (ctx.chips * peak["flops_per_s"])
    t_bytes = nbytes / (ctx.chips * peak["hbm_bytes_per_s"])
    bound = "compute" if t_flops >= t_bytes else "memory"
    return {"value": 100.0 * max(t_flops, t_bytes) / (kernel_ms / 1e3),
            "bound": bound}


# --------------------------------------------------------------------------
# Breakdown for the result line
# --------------------------------------------------------------------------

def op_label(op: Op) -> str:
    """A stable label for grouping: the op's name stack without the outer
    step's ``jit(...)`` frame, else its opcode (and target)."""
    if op.op_name:
        parts = [p for p in op.op_name.split("/") if p]
        return "/".join(parts[1:4] or parts)
    return f"{op.kind}:{op.target}" if op.target else op.kind


def top_ops(trace: DeviceTrace, n: int = 10) -> List[List]:
    """[label, seconds] of the ``n`` labels that took most device time,
    summed over devices (each label's own intervals merged)."""
    groups: Dict[str, List[Op]] = {}
    for ops in trace.devices:
        for o in ops:
            if o.kind == "while":      # its body's ops are listed themselves
                continue
            groups.setdefault(op_label(o), []).append(o)
    tot = {k: union_ns(v) / 1e9 for k, v in groups.items()}
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: DeviceTrace, n: int = 10) -> List[List]:
    """[host span, seconds] of the ``n`` longest device-idle gaps of device
    0 between the first and the last host span, each named by the host
    span that was open at the gap's start ('none' where no span was)."""
    if not trace.devices or not trace.host_spans:
        return []
    window = (trace.host_spans[0][1], max(s[2] for s in trace.host_spans))
    busy = union((o.start_ns, o.end_ns) for o in trace.devices[0])
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, window[1])))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        name = "none"
        for span, hs, he in trace.host_spans:
            if hs <= s < he:
                name = span
        out.append([name, (e - s) / 1e9])
    return out
