"""Reduce a profiler trace (``.xplane.pb``) of the measured window.

The device planes (``/device:TPU:<n>``) carry two lines this reads: "XLA
Modules", one event per execution of a compiled program, named after the
jitted function (``jit_paged_decode_step(<fingerprint>)``), and "XLA Ops",
one event per HLO instruction run, named by its HLO text
(``%paged_attention.3 = bf16[32,8,4,128]... custom-call(...)``).  Ops nest
(a ``while`` holds its body's ops), so busy time is the union of their
intervals.  "Async XLA Ops" holds the asynchronous copies (``copy-start``,
``slice-start``) over the time their transfer takes.  The host plane
carries the harness's and the engine's own spans (``bench.*``,
``serving.*``) on the same clock.

A kernel call's operands that XLA placed in VMEM (``S(1)``) were moved
there by the ops that produced them, just before the call; each call keeps
those producers (``Feed``), so that a kernel's share of its roofline can
count the bytes of an operand together with the time spent moving it.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

HOST_SPAN_PREFIXES = ("bench.", "serving.")
CONTAINER_OPS = ("while", "conditional", "call")
_OP = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_LAYOUT = re.compile(r"\{[^}]*\}")


@dataclasses.dataclass(frozen=True)
class Feed:
    """The op that produced one operand of a kernel call."""
    seconds: float      # its device time; an async copy's transfer time
    reads_hbm: bool     # it read a buffer outside VMEM


@dataclasses.dataclass
class Reduction:
    window: Tuple[float, float]          # traced window, trace clock (ns)
    busy_s: float                        # device busy, mean over chips
    devices: int
    modules: Dict[str, List[float]]      # module -> durations (s)
    ops: Dict[str, List[float]]          # "base result" -> durations (s)
    kernels: Dict[str, list]             # kernel -> [(dur_s, hlo, feeds)]
    gaps: List[Tuple[str, float]]        # (host span around it, seconds)
    # breakdown()'s idle_gaps sum the gaps by the host span around them.

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def module_calls(self, prefix: str) -> List[float]:
        return [d for name, ds in self.modules.items()
                if name.split("(")[0] == prefix for d in ds]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(((k, sum(v)) for k, v in self.ops.items()),
                     key=lambda kv: -kv[1])[:top]
        by_label: Dict[str, float] = {}
        for label, sec in self.gaps:
            by_label[label] = by_label.get(label, 0.0) + sec
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


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


def _clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def op_key(name: str) -> Optional[Tuple[str, str]]:
    """(instruction base name, result type without layouts) of an "XLA
    Ops" event; a tuple result keeps its parentheses."""
    m = _OP.match(name)
    if not m:
        return None
    rest = _LAYOUT.sub("", name[m.end():])
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                return m.group(1), rest[: i + 1]
    return m.group(1), rest.split(" ", 1)[0]


_TYPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


def custom_call_types(hlo: str) -> List[Tuple[str, Tuple[int, ...], bool]]:
    """(dtype, shape, in VMEM) of a custom call's result and then each
    operand, from its HLO text.  ``S(1)`` in a layout marks a buffer that
    XLA placed in VMEM (memory space 1) before the call."""
    head, rest = hlo.split(" custom-call(", 1)
    body = rest.split("), custom_call_target", 1)[0]
    out = []
    for part in (head.split(" = ", 1)[1], body):
        for dt, dims, layout in _TYPE.findall(part):
            out.append((dt, tuple(int(d) for d in dims.split(",") if d),
                        "S(1)" in (layout or "")))
    return out


_NAME = re.compile(r"%([\w.\-]+)")
_ASYNC_DONE = (" copy-done(", " async-done(")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def _op_args(hlo: str) -> str:
    """The text of an op's operand list, between its parentheses."""
    head = hlo.split(" = ", 1)[-1]
    m = _OPCODE.search(head)
    if m is None:
        return ""
    depth = 0
    for i in range(m.end() - 1, len(head)):
        depth += {"(": 1, ")": -1}.get(head[i], 0)
        if depth == 0:
            return head[m.end(): i]
    return head[m.end():]


def operand_names(hlo: str) -> List[str]:
    """Instruction names of an op's operands, in order."""
    return _NAME.findall(_op_args(hlo))


def _reads_hbm(hlo: str) -> bool:
    """Whether an op reads an array operand that is not in VMEM."""
    return any(dims and "S(1)" not in (layout or "")
               for _, dims, layout in _TYPE.findall(_op_args(hlo)))


def _feeds(hlo: str, last: dict, async_last: dict) -> List[Optional[Feed]]:
    """For each operand of a kernel call, the op that produced it (the
    latest run of that instruction), or None where the trace has none."""
    out = []
    for name in operand_names(hlo):
        ev = last.get(name)
        if ev is None:
            out.append(None)
            continue
        dur, text = ev
        if any(k in text for k in _ASYNC_DONE):
            start = async_last.get(next(iter(operand_names(text)), None))
            dur += start[0] if start else 0.0
            out.append(Feed(dur, True))
        else:
            out.append(Feed(dur, _reads_hbm(text)))
    return out


def _label(spans, t0, t1) -> str:
    """The innermost host span that covers the gap's midpoint."""
    mid = (t0 + t1) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside host spans"


def reduce_xspace(pd, kernels=("paged_attention", "nested_lowrank_matmul"),
                  gap_min_s: float = 1e-4) -> Reduction:
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    spans = []
    for plane in host:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_SPAN_PREFIXES):
                    spans.append((e.name, e.start_ns, e.start_ns
                                  + e.duration_ns))
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    devs = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    if win:
        lo, hi = win[0]
    else:
        ends = [e.start_ns + e.duration_ns for p in devs for line in p.lines
                for e in line.events]
        lo, hi = 0.0, max(ends)
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, List[float]] = {}
    kern: Dict[str, list] = {k: [] for k in kernels}
    busy = 0.0
    gaps = []
    for plane in devs:
        intervals = []
        last: Dict[str, tuple] = {}         # instruction -> (dur_s, hlo)
        async_last: Dict[str, tuple] = {}
        lines = {line.name: line for line in plane.lines}
        async_ops = sorted(((e.start_ns, e.duration_ns, e.name)
                            for e in (lines["Async XLA Ops"].events
                                      if "Async XLA Ops" in lines else ())))
        ai = 0
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    if lo <= e.start_ns < hi:
                        modules.setdefault(e.name, []).append(
                            e.duration_ns * 1e-9)
            elif line.name == "XLA Ops":
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    while ai < len(async_ops) and async_ops[ai][0] <= \
                            e.start_ns:
                        m = _NAME.match(async_ops[ai][2])
                        if m:
                            async_last[m.group(1)] = (
                                async_ops[ai][1] * 1e-9, async_ops[ai][2])
                        ai += 1
                    m = _NAME.match(e.name)
                    if m:
                        last[m.group(1)] = (e.duration_ns * 1e-9, e.name)
                    s, t = e.start_ns, e.start_ns + e.duration_ns
                    if t <= lo or s >= hi:
                        continue
                    intervals.append((s, t))
                    key = op_key(e.name)
                    if key is None:
                        continue
                    if key[0] in kern:
                        kern[key[0]].append((e.duration_ns * 1e-9, e.name,
                                             _feeds(e.name, last,
                                                    async_last)))
                    if key[0] not in CONTAINER_OPS:
                        ops.setdefault(f"{key[0]} {key[1]}", []).append(
                            e.duration_ns * 1e-9)
        merged = _clip(_union(intervals), lo, hi)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if (b - a) * 1e-9 >= gap_min_s:
                gaps.append((_label(spans, a, b), (b - a) * 1e-9))
    return Reduction((lo, hi), busy / len(devs), len(devs), modules, ops,
                     kern, gaps)


def reduce_dir(trace_dir: str) -> Reduction:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_xspace(ProfileData.from_file(paths[-1]))
