"""From a ``jax.profiler`` trace to what the per-layer metrics read.

A device plane (``/device:TPU:<i>``) has a line of XLA operations, one
event an executed HLO instruction, named after the instruction; a ``while``
or a ``conditional`` is an event that spans the events of its body. The
phase of an instruction is the ``anat/...`` scope in its ``op_name``
metadata (the naming contract of ``oktopk_tpu/obs/anatomy.py``): read from
the event where the profiler kept it, else joined by the instruction's
name from the compiled step's own text. Host spans are the
``bench/...`` ``TraceAnnotation``s that the harness puts round each part of
its loop; they are on the trace's clock, like the device events.

All times are seconds.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from benchlib import intervals

SCOPE = "anat"
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|alltoall|allreduce|allgather", re.I)
HOST_PREFIX = "bench/"
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_BUCKET = re.compile(r"^b\d+$")
_LEVEL = re.compile(r"^lvl\d+$")


@dataclasses.dataclass
class Op:
    name: str            # HLO instruction name
    start: float
    end: float
    path: str = ""       # op_name metadata (scope path), "" if unknown
    text: str = ""       # what else is known of the instruction
    container: bool = False

    @property
    def phase(self) -> Optional[str]:
        return phase_of(self.path)

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.search(self.name))

    def mentions(self, word: str) -> bool:
        return word in self.name or word in self.path or word in self.text


def phase_of(path: str) -> Optional[str]:
    """``.../anat/b000/anat/b000/select/...`` -> ``select``: the innermost
    contract scope that names a phase. None outside the contract."""
    parts = path.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] != SCOPE:
            continue
        for j in range(i + 1, len(parts) - 1):   # the last part is the op
            p = parts[j]
            if _BUCKET.match(p) or _LEVEL.match(p):
                continue
            if p != SCOPE:
                return p
            break
    return None


def hlo_paths(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """instruction name -> (op_name, the rest of its line, cut short)."""
    out: Dict[str, Tuple[str, str]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        # a Mosaic call's line carries its whole kernel as bytes: keep the
        # head and the tail, where the names are
        rest = line if len(line) < 1500 else line[:800] + " ... " + line[-600:]
        out[m.group(1)] = (op.group(1) if op else "", rest)
    return out


def mark_containers(ops: List[Op]) -> None:
    """An op that spans another op of its line is a container (a loop, a
    branch, a call): its time is its body's, not its own."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end + 1e-12:
            stack[-1].container = True
        stack.append(op)


@dataclasses.dataclass
class Chip:
    name: str
    ops: List[Op]
    modules: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)   # executions of whole programs
    # collectives that run beside the line of operations (start to done)
    async_collectives: List[Op] = dataclasses.field(default_factory=list)

    def step_runs(self, window) -> List[Tuple[float, float]]:
        """Executions, inside the window, of the program that took most
        of it: the train step (the key split runs as often, so a count
        cannot tell the two apart). Empty without a line of programs."""
        lo, hi = window
        took: Dict[str, float] = {}
        for name, s, e in self.modules:
            if lo <= s < hi:
                took[name] = took.get(name, 0.0) + e - s
        if not took:
            return []
        top = max(took, key=took.get)
        return [(s, e) for name, s, e in self.modules
                if name == top and lo <= s < hi]

    def leaves(self, pred: Callable[[Op], bool] = lambda o: True) -> List[Op]:
        return [o for o in self.ops if not o.container and pred(o)]


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    host: List[Tuple[str, float, float]]   # (name, start, end)
    window: Tuple[float, float]
    steps: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clipped(self, ops: Iterable[Op]):
        return intervals.clip([(o.start, o.end) for o in ops], *self.window)

    def busy_s(self, chip: Chip) -> float:
        return intervals.length(self._clipped(chip.ops))

    def seconds(self, pred: Callable[[Op], bool]) -> float:
        """Device seconds of the leaf ops that ``pred`` takes, as the union
        of their intervals inside the window, averaged over the chips."""
        per = [intervals.length(self._clipped(c.leaves(pred)))
               for c in self.chips]
        return sum(per) / len(per)

    def count(self, pred: Callable[[Op], bool]) -> float:
        """Events that ``pred`` takes, containers too, a chip."""
        lo, hi = self.window
        per = [sum(1 for o in c.ops if pred(o) and lo <= o.start < hi)
               for c in self.chips]
        return sum(per) / len(per)

    def exposed_collective_s(self) -> float:
        """Time of collective ops, on the line of operations or beside it,
        during which no other operation runs on that chip, averaged over
        the chips."""
        per = []
        for c in self.chips:
            coll = self._clipped(c.leaves(lambda o: o.collective)
                                 + c.async_collectives)
            work = self._clipped(c.leaves(lambda o: not o.collective))
            per.append(intervals.length(coll)
                       - intervals.intersection_len(coll, work))
        return sum(per) / len(per)

    def steps_with(self, pred: Callable[[Op], bool]) -> float:
        """Share of the first chip's executions of the step program in
        which an event that ``pred`` takes starts."""
        chip = self.chips[0]
        runs = chip.step_runs(self.window)
        if not runs:
            # no line of programs (the sandbox's stand-in): from one
            # dispatch of the host to the next
            starts = sorted(s for n, s, _ in self.host if n == "dispatch")
            runs = list(zip(starts, starts[1:] + [self.window[1]]))
        if not runs:
            raise ValueError("no execution of the step program in the trace")
        starts = sorted(o.start for o in chip.ops if pred(o))
        hit = sum(1 for s, e in runs if any(s <= t < e for t in starts))
        return hit / len(runs)

    def idle_share(self) -> float:
        """1 - busy / window on the busiest chip: both terms the trace's."""
        return 1.0 - max(self.busy_s(c) for c in self.chips) / self.window_s

    def breakdown(self, top: int = 10):
        """The device operations that took most time (by phase where the
        contract names one, else by instruction name without its number),
        and the idle time of the first chip by what the host was doing."""
        chip = self.chips[0]
        by: Dict[str, float] = {}
        for o in chip.leaves():
            lo, hi = max(o.start, self.window[0]), min(o.end, self.window[1])
            if hi <= lo:
                continue
            label = base_name(o.name)
            if o.phase and not is_kernel(o):
                label = f"{SCOPE}/{o.phase}"
            by[label] = by.get(label, 0.0) + hi - lo
        idle: Dict[str, float] = {}
        for lo, hi in intervals.gaps([(o.start, o.end) for o in chip.ops],
                                     *self.window):
            best, cover = "none", 0.0
            for name, s, e in self.host:
                c = min(e, hi) - max(s, lo)
                if c > cover:
                    best, cover = name, c
            idle[best] = idle.get(best, 0.0) + hi - lo
        rank = lambda d: [[k, v] for k, v in
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by), "idle_gaps": rank(idle)}


def base_name(name: str) -> str:
    """``oktopk_fused_select.2`` -> ``oktopk_fused_select``."""
    return re.sub(r"\.\d+$", "", name)


def is_kernel(op: Op) -> bool:
    """A Mosaic (Pallas) call: XLA names the instruction after the
    kernel's ``name``."""
    return "tpu_custom_call" in op.text


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def _span(ev) -> Tuple[float, float]:
    start = ev.start_ns * 1e-9
    return start, start + ev.duration_ns * 1e-9


def read(profile, hlo: Dict[str, Tuple[str, str]], steps: int,
         stand_in_host_ops: bool = False) -> Trace:
    """``profile`` is a ``jax.profiler.ProfileData``. With
    ``stand_in_host_ops`` (the sandbox rehearsal, where no device plane
    exists) the host's XLA events stand in as one chip, so that the code
    runs; nothing read from them is a device number."""
    chips: List[Chip] = []
    host: List[Tuple[str, float, float]] = []
    stand_in: List[Op] = []
    for plane in profile.planes:
        device = _is_device(plane.name)
        ops: List[Op] = []
        modules: List[Tuple[str, float, float]] = []
        beside: List[Op] = []
        for line in plane.lines:
            if device and line.name == "XLA Modules":
                modules = [(ev.name, *_span(ev)) for ev in line.events]
                continue
            if device and line.name == "Async XLA Ops":
                for ev in line.events:
                    m = _HLO_LINE.match(ev.name)
                    if m and COLLECTIVE.search(m.group(1)):
                        beside.append(Op(m.group(1), *_span(ev)))
                continue
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    host.append((ev.name[len(HOST_PREFIX):], *_span(ev)))
                    continue
                # a TPU event is named by its instruction's whole text,
                # "%fusion.4 = bf16[...] fusion(...)"; the CPU's stand-in
                # carries the instruction's name in its ``hlo_op`` stat
                m = _HLO_LINE.match(ev.name)
                if device:
                    name = m.group(1) if m else ev.name
                elif stand_in_host_ops and "hlo_op" in (
                        stats := dict(ev.stats)):
                    name = str(stats["hlo_op"])
                else:
                    continue
                path, text = hlo.get(name, ("", ev.name[:300]))
                ops.append(Op(name, *_span(ev), path, text))
        if device and ops:
            mark_containers(ops)
            chips.append(Chip(plane.name, ops, modules, beside))
        else:
            stand_in += ops
    if not chips and stand_in:
        mark_containers(stand_in)
        chips = [Chip("host-stand-in", stand_in)]
    spans = [(s, e) for n, s, e in host if n == "window"]
    if not spans or not chips:
        raise ValueError(
            f"trace holds {len(chips)} device line(s) and {len(spans)} "
            "bench/window span(s); need at least one of each")
    return Trace(chips, [h for h in host if h[0] != "window"],
                 spans[-1], steps)
