"""From the profiler's trace to numbers.

``jax.profiler`` writes an ``.xplane.pb``; ``extract()`` reads it with
``jax.profiler.ProfileData`` and keeps what the metrics need as plain
lists — every event of the device planes' "XLA Modules", "XLA Ops" and
"Async XLA Ops" lines, and the host events the measured loop wrote
(``bench:*``) — so that the reduction below runs the same on a live
trace and on the trimmed extracts kept with the tests.  Times are
nanoseconds on the trace's own clock, which the profiler shares between
host and devices.

On a TPU the "XLA Ops" line holds one event per executed HLO
instruction, named by the instruction's whole text; the core runs them
one after another, except that a ``conditional`` or a ``while`` is an
event that contains its children's.  "Async XLA Ops" holds one span per
asynchronous pair, from its ``-start`` to its ``-done``: data in flight
beside whatever the core runs meanwhile.

The reduction looks at whole *step periods* of each device, from the
start of one execution of the step program to the start of the next.
The first step (the pipeline fills) and the last (the loop drains) are
left out.  Everything it reports comes from the trace alone: busy time,
the window it is a share of, and the choice of the periods kept.

While it records, the profiler slows the copies from the host to the
chips to a few hundred MB/s (the account that fits the traces of PR 22:
PERF.md, findings).  A host-fed loop then runs on the batches its input pipeline already holds, starves
for a second or two while six more trickle in, and runs again: every
sixth period is a stall, and the ones after it carry the refill.  The
profiler only ever lengthens a period, so the shortest ones are the
nearest the trace has to the program left alone: a period is kept when
it is no longer than STALL_FACTOR times the lower quartile of that
chip's own periods.  Where the input pipeline itself paces the job no
period is left alone, and the idle share of the kept ones overstates
what the untraced job has.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from benchmark import intervals as iv
from benchmark.loop import TRACE_PREFIX
from benchmark.quantiles import median, percentile

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

# An XLA op that moves data between chips, by its name or opcode;
# copied from horovod_tpu/obs/profile.py (is_comm_op).
COLLECTIVE_OP = re.compile(
    r"(all[-_]?reduce|all[-_]?gather|all[-_]?to[-_]?all|"
    r"reduce[-_]?scatter|collective[-_]?permute|"
    r"(^|[^a-z])(send|recv)([^a-z]|$))", re.IGNORECASE)

# `%name = shape opcode(operands)...`, the text of an HLO instruction
_INSTRUCTION = re.compile(
    r"^%(?P<name>[^\s=]+) = (?P<shape>.*?[\]})]) (?P<op>[\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

# A traced step period is kept when it is no longer than this many times
# the lower quartile of its chip's periods.  In the one-chip traces of
# PR 22 (ResNet-50 at 256 a chip, VGG-16 at 64) the periods this keeps
# lie within 5 % of that quartile and the next ones 13 % or more above
# it, so any factor from 1.05 to 1.10 keeps the same periods.
STALL_FACTOR = 1.1


def short_name(text: str) -> Tuple[str, bool]:
    """An op event's name as the extract keeps it — instruction name,
    opcode and shape without layouts, at most 96 characters — and
    whether the op moves data between chips.  The instruction name need
    not say so (a psum lowers to ``%psum.3 = ... all-reduce(...)``), so
    the opcode is asked too."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text[:96], bool(COLLECTIVE_OP.search(text))
    shape = _LAYOUT.sub("", m["shape"])
    collective = bool(COLLECTIVE_OP.search(m["name"])
                      or COLLECTIVE_OP.search(m["op"]))
    return f"{m['name']} {m['op']} {shape}"[:96], collective


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {logdir}, found {len(paths)}")
    return paths[0]


def extract(xplane_path: str) -> dict:
    """``{"device": {plane: {line: [Event]}}, "host": [Event],
    "collectives": [names of the ops that move data between chips]}``,
    op events under their ``short_name``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    names: Dict[str, Tuple[str, bool]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lines.setdefault(line.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
                elif line.name in (OPS_LINE, ASYNC_LINE):
                    events = lines.setdefault(line.name, [])
                    for e in line.events:
                        if e.name not in names:
                            names[e.name] = short_name(e.name)
                        events.append(
                            (names[e.name][0], e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(TRACE_PREFIX))
    return {"device": device, "host": host,
            "collectives": sorted(
                name for name, collective in names.values() if collective)}


def save_extract(ex: dict, path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(ex, f, separators=(",", ":"))


def load_extract(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _spans(events) -> List[iv.Interval]:
    return [(start, start + dur) for _, start, dur in events]


def step_program(modules: List[Event]) -> str:
    """The program that took most device time in the trace: the train
    step (the jitted step has no stable name of its own yet)."""
    seconds: Dict[str, float] = {}
    for name, _, dur in modules:
        seconds[name] = seconds.get(name, 0.0) + dur
    return max(seconds, key=seconds.get)


@dataclasses.dataclass
class DeviceReduction:
    plane: str
    periods: List[iv.Interval]           # the step periods kept, ns
    dropped: int                         # periods the profiler stretched
    step_ns: List[float]                 # device duration of each kept step
    busy_in_step_ns: List[float]         # ... and how long an op ran in it
    collective_ns: float                 # a collective ran or was in flight
    exposed_collective_ns: float         # ... and the core ran nothing else
    gaps: List[iv.Interval]              # no op ran
    op_ns: Dict[str, float]              # device time by op name

    @property
    def window_ns(self) -> float:
        return iv.total(self.periods)

    @property
    def busy_ns(self) -> float:
        return self.window_ns - iv.total(self.gaps)


def innermost(events: List[Event]) -> List[Event]:
    """The events that contain no other: the core runs one op at a
    time, so an event that is still open when the next one starts is a
    ``conditional`` or ``while`` around its children, and counting both
    would count the time twice."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2] - 1.0]


def reduce_device(plane: str, lines: Dict[str, List[Event]],
                  collectives) -> Optional[DeviceReduction]:
    modules, ops = lines.get(MODULES_LINE, []), lines.get(OPS_LINE, [])
    if not modules or not ops:
        return None
    name = step_program(modules)
    steps = sorted((e for e in modules if e[0] == name), key=lambda e: e[1])
    candidates = [(step, (step[1], nxt[1]))
                  for step, nxt in zip(steps[1:-1], steps[2:])]
    if not candidates:
        return None
    longest_ns = STALL_FACTOR * percentile(
        [period[1] - period[0] for _, period in candidates], 25.0)
    kept = [(step, period) for step, period in candidates
            if period[1] - period[0] <= longest_ns]
    if len(kept) < 3:
        return None  # too little left to call anything steady
    periods = iv.union(period for _, period in kept)
    ops = innermost(ops)
    in_flight = lines.get(ASYNC_LINE, [])
    running = iv.union(_spans(ops))
    busy = iv.intersect(running, periods)
    collective = iv.intersect(iv.union(_spans(
        e for e in ops + in_flight if e[0] in collectives)), periods)
    other = iv.intersect(iv.union(_spans(
        e for e in ops if e[0] not in collectives)), periods)
    op_ns: Dict[str, float] = {}
    for op_name, start, dur in ops:
        inside = iv.total(iv.intersect([(start, start + dur)], periods))
        if inside:
            op_ns[op_name] = op_ns.get(op_name, 0.0) + inside
    return DeviceReduction(
        plane=plane, periods=periods, dropped=len(candidates) - len(kept),
        step_ns=[step[2] for step, _ in kept],
        busy_in_step_ns=[
            iv.total(iv.intersect(running, [(step[1], step[1] + step[2])]))
            for step, _ in kept],
        collective_ns=iv.total(collective),
        exposed_collective_ns=iv.total(iv.subtract(collective, other)),
        gaps=iv.subtract(periods, busy), op_ns=op_ns)


@dataclasses.dataclass
class TraceReduction:
    devices: List[DeviceReduction]
    host: List[Event]

    def _median(self, f) -> float:
        return median([f(d) for d in self.devices])

    def _mean(self, f) -> float:
        return sum(f(d) for d in self.devices) / len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds an op ran inside the step periods kept, averaged
        over the chips."""
        return self._mean(lambda d: d.busy_ns) / 1e9

    @property
    def window_s(self) -> float:
        """Summed length of the step periods kept, on the trace's own
        clock, averaged over the chips."""
        return self._mean(lambda d: d.window_ns) / 1e9

    @property
    def idle_share(self) -> float:
        """What the driver works out of the two above."""
        return 1.0 - self.busy_s / self.window_s

    @property
    def busy_ms_per_step(self) -> float:
        """How long a step keeps a chip busy: ops running inside one
        execution of the step program, median over the kept steps of
        every chip.  The profiler does not touch it."""
        return median([ns for d in self.devices
                       for ns in d.busy_in_step_ns]) / 1e6

    @property
    def device_step_ms(self) -> float:
        return median([ns for d in self.devices for ns in d.step_ns]) / 1e6

    @property
    def collective_ms_per_step(self) -> float:
        return self._median(
            lambda d: d.collective_ns / len(d.step_ns)) / 1e6

    @property
    def exposed_collective_ms_per_step(self) -> float:
        return self._median(
            lambda d: d.exposed_collective_ns / len(d.step_ns)) / 1e6

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops with most device time on the first chip, in seconds
        over the periods kept."""
        ops = self.devices[0].op_ns
        return [[name, ops[name] / 1e9]
                for name in sorted(ops, key=ops.get, reverse=True)[:n]]

    def longest_gaps(self, n: int = 5) -> List[List]:
        """The first chip's longest idle gaps in the periods kept, each
        named by the host span that covers most of it (``other`` when
        none does).  Where the profiler stalled, its aftermath is among
        them."""
        spans = [(name[len(TRACE_PREFIX):], start, start + dur)
                 for name, start, dur in self.host]
        out = []
        gaps = sorted(self.devices[0].gaps, key=lambda g: g[0] - g[1])
        for g0, g1 in gaps[:n]:
            cover: Dict[str, float] = {}
            for name, s0, s1 in spans:
                shared = min(g1, s1) - max(g0, s0)
                if shared > 0:
                    cover[name] = cover.get(name, 0.0) + shared
            name = max(cover, key=cover.get) if cover else "other"
            if cover and cover[name] < (g1 - g0) / 2:
                name = "other"
            out.append([name, (g1 - g0) / 1e9])
        return out


def reduce(ex: dict) -> Optional[TraceReduction]:
    """None when the trace holds no device plane with three step periods
    to keep (a CPU rehearsal, a window too short)."""
    def index(plane):
        return int(DEVICE_PLANE.match(plane).group(1))

    collectives = frozenset(ex["collectives"])
    devices = [reduce_device(plane, ex["device"][plane], collectives)
               for plane in sorted(ex["device"], key=index)]
    devices = [d for d in devices if d is not None]
    if not devices:
        return None
    return TraceReduction(devices=devices, host=list(ex["host"]))
