"""From a profiler trace (xplane) to the numbers the readers use.

What a v5e trace holds (looked at by hand, PR 24): a plane
``/device:TPU:<n>`` per chip whose line ``XLA Modules`` has one event per
program run (``jit_train_step(<hash>)``) and whose line ``XLA Ops`` has
one event per HLO instruction, named by the instruction's whole text and
nested: a ``while`` covers the instructions of its body. ``Async XLA
Ops`` holds the spans of copies and collectives that run beside compute.
The plane ``/host:CPU`` has a line per thread; ``TraceAnnotation`` spans
appear there under their own names.

Busy time is the union of the leaf instructions' intervals, so a gap
inside a loop counts as idle. All times are seconds.
"""

from __future__ import annotations

import bisect
import gzip
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
_CONTAINERS = {"while", "conditional", "call"}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE = re.compile(r"([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9,]*)\]")
_MIN_GAP_S = 1e-6

Interval = Tuple[float, float]


def load(path: str):
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def parse_instruction(text: str) -> Tuple[str, str, str]:
    """(base name, opcode, output type) of an HLO instruction's text,
    ``%fusion.12 = bf16[8,128]{1,0} fusion(...)``. Text that is not an
    instruction comes back as its own name with no opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", ""
    base = re.sub(r"\.[0-9]+$", "", head.lstrip("%"))
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        out_type, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        out_type, _, tail = rest.partition(" ")
    return base, tail.partition("(")[0], out_type


def stable_name(text: str) -> str:
    """A name for an instruction that survives renumbering: its kind
    (``pallas`` for a custom call) and the shapes it writes."""
    base, opcode, out_type = parse_instruction(text)
    kind = "pallas" if opcode == "custom-call" else base
    shapes = ["_".join([dtype] + [d for d in dims.split(",") if d])
              for dtype, dims in _SHAPE.findall(out_type)[:3]]
    name = "_".join([kind] + shapes)
    return re.sub(r"[^A-Za-z0-9_.-]", "_", name)[:96]


def is_collective(text: str) -> bool:
    base, opcode, _ = parse_instruction(text)
    return any(c in opcode or c in base for c in _COLLECTIVES)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals, as sorted disjoint intervals."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cur = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def module_name(text: str) -> str:
    """``jit_train_step(123)`` -> ``train_step``."""
    name = text.partition("(")[0]
    return name[4:] if name.startswith("jit_") else name


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _covering(spans: List[Tuple[str, float, float]], t: float
              ) -> Optional[str]:
    """The shortest benchmark span that holds time ``t``."""
    best = None
    for name, start, end in spans:
        if start <= t <= end and (best is None
                                  or end - start < best[2] - best[1]):
            best = (name, start, end)
    return best[0][len(SPAN_PREFIX):] if best else None


def reduce_trace(profile) -> Dict:
    """Busy and idle time, time per operation, program runs, idle gaps
    named by the programs around them and the host span they fall in,
    and the collectives' exposure."""
    devices = []
    spans: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            devices.append({
                "ops": _events(lines["XLA Ops"]),
                "async": _events(lines["Async XLA Ops"])
                if "Async XLA Ops" in lines else [],
                "modules": _events(lines["XLA Modules"])
                if "XLA Modules" in lines else [],
            })
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    if not devices or not any(d["ops"] for d in devices):
        raise ValueError("the trace holds no operation on a TPU device")

    parsed: Dict[str, Tuple[str, bool, bool]] = {}

    def facts(text):
        if text not in parsed:
            parsed[text] = (stable_name(text),
                            parse_instruction(text)[1] in _CONTAINERS,
                            is_collective(text))
        return parsed[text]

    starts, ends = [], []
    op_s: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    busy_s = collective_s = exposed_s = 0.0
    per_device = []
    for dev in devices:
        leaves, compute, collectives = [], [], []
        for text, start, end in dev["ops"]:
            name, container, collective = facts(text)
            if container:
                continue
            leaves.append((start, end))
            (collectives if collective else compute).append((start, end))
            op_s[name][0] += 1
            op_s[name][1] += end - start
        collectives += [(s, e) for text, s, e in dev["async"]
                        if facts(text)[2]]
        busy = merge(leaves)
        starts.append(busy[0][0])
        ends.append(busy[-1][1])
        busy_s += total(busy)
        coll = merge(collectives)
        collective_s += total(coll)
        exposed_s += total(subtract(coll, merge(compute)))
        per_device.append(busy)
    n = len(devices)
    window = (min(starts), max(ends))

    first = devices[0]
    modules: Dict[str, List[float]] = defaultdict(list)
    runs = sorted((s, e, module_name(text)) for text, s, e in first["modules"])
    for start, end, name in runs:
        modules[name].append(end - start)
    run_starts = [r[0] for r in runs]

    def run_at(t):
        i = bisect.bisect_right(run_starts, t) - 1
        if i >= 0 and runs[i][0] <= t <= runs[i][1]:
            return i
        return None

    gaps: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    busy = per_device[0]
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        if gap_end - gap_start < _MIN_GAP_S:
            continue
        before, after = run_at(gap_start - 1e-9), run_at(gap_end + 1e-9)
        if before is not None and before == after:
            label = "in:" + runs[before][2]
        else:
            label = ">".join(runs[i][2] if i is not None else "none"
                             for i in (before, after))
        span = _covering(spans, (gap_start + gap_end) / 2)
        if span:
            label += "@" + span
        gaps[label][0] += 1
        gaps[label][1] += gap_end - gap_start

    host: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for name, start, end in spans:
        host[name[len(SPAN_PREFIX):]][0] += 1
        host[name[len(SPAN_PREFIX):]][1] += end - start

    def ranked(table):
        return sorted(([k, c, s] for k, (c, s) in table.items()),
                      key=lambda row: -row[2])

    return {
        "devices": n,
        "window_s": window[1] - window[0],
        "busy_s": busy_s / n,
        "collective_s": collective_s / n,
        "collective_exposed_s": exposed_s / n,
        # Seconds per chip, so a four-chip trace compares with one chip.
        "ops": [[k, c, s / n] for k, c, s in ranked(op_s)],
        "modules": dict(modules),
        "idle_gaps": ranked(gaps),
        "host_spans": {k: v for k, v in host.items()},
    }


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The contract's ``breakdown``: the operations that took most
    device time and the longest idle gaps, under stable names."""
    return {
        "device_ops": [[f"{name}_x{count}", seconds]
                       for name, count, seconds in reduced["ops"][:top]],
        "idle_gaps": [[f"{name}_x{count}", seconds]
                      for name, count, seconds in reduced["idle_gaps"][:top]],
    }
