"""Profiler trace of the timed window, reduced to what the per-layer metrics read.

:class:`Tracer` records the window with ``jax.profiler`` into a directory
inside the checkout and removes it once read.  :func:`reduce` reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` and keeps, for every TPU
device plane, the events of its ``XLA Ops`` line.  Each op is classified
by its HLO instruction, looked up by name in the compiled step's HLO text
(its opcode, its ``op_name`` metadata, which carries the ``obs.*`` named
scopes, and for a Pallas kernel the kernel's name and operand shapes):

- ``collective``: an all-reduce, all-gather, reduce-scatter, all-to-all or
  collective-permute (and their async start/done halves);
- ``codec``: any other op under ``obs.encode`` or ``obs.decode``, and the
  one-pass statistics kernels (``ef_correct_stats``, ``bucket_stats``),
  which the program runs outside those scopes;
- ``optimizer``: under ``obs.optimizer``;
- ``sync``: any other op under ``obs.collective``;
- ``model``: everything else: forward, backward, loss, and the sync
  region's unscoped bucket concatenation, split and casts, which the
  trace cannot tell apart from the model's own ops.

A ``while``, ``conditional`` or ``call`` op's event spans the ops of the
computation it runs, which have events of their own, so it is left out.

Times are the device's own: busy time is the union of op intervals, the
window runs from the first op's start to the last op's end on a device,
and every per-step figure divides by the steps the trace holds.
"""
from __future__ import annotations

import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\((.*)$")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_LAYOUTS = re.compile(r"operand_layout_constraints=\{(.*?)\}, "
                      r"(?:frontend_attributes|metadata|backend_config|custom_call)")
_EV_OPCODE = re.compile(r"^%\S+ = (?:\(.*?\)|\S+) ([\w\-]+)\(")
# ops whose event spans the ops of the computation they run (a loop body):
# their own time is their children's, so they are left out
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\b(f32|bf16|s32|u32|s8|u8|f16|pred)\[([\d,]*)\]")


class Tracer:
    def __init__(self, directory: Path):
        self.directory = Path(directory)

    def start(self) -> None:
        import jax

        if self.directory.exists():
            shutil.rmtree(self.directory)
        self.directory.mkdir(parents=True)
        jax.profiler.start_trace(str(self.directory))

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def remove(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)


@dataclass
class HloOp:
    opcode: str
    scope: str
    kernel: str | None = None
    operand_elements: list = field(default_factory=list)


def parse_hlo(text: str) -> dict:
    """HLO instruction name -> :class:`HloOp`, over every computation."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, _, opcode, rest = m.groups()
        om = _OPNAME.search(rest)
        op = HloOp(opcode=opcode, scope=om.group(1) if om else "")
        if opcode == "custom-call" and "tpu_custom_call" in rest:
            # the instruction is named after the jitted wrapper of the
            # pallas_call (``ef_correct_stats.1``); operand shapes are listed
            # in its layout constraints
            op.kernel = re.sub(r"\.\d+$", "", name)
            lm = _LAYOUTS.search(rest)
            for _, dims in _SHAPE.findall(lm.group(1) if lm else ""):
                n = 1
                for d in (dims.split(",") if dims else []):
                    n *= int(d)
                op.operand_elements.append(n)
        out[name] = op
    return out


# the one-pass statistics kernels feed the codec's plan; the program calls
# them outside the obs.encode scope
STATS_KERNELS = ("ef_correct_stats", "bucket_stats")


def classify(op: HloOp | None) -> str:
    if op is None:
        return "model"
    base = op.opcode.replace("-start", "").replace("-done", "")
    if base in COLLECTIVE_KINDS:
        return "collective"
    if "obs.encode" in op.scope or "obs.decode" in op.scope or op.kernel in STATS_KERNELS:
        return "codec"
    if "obs.optimizer" in op.scope:
        return "optimizer"
    if "obs.collective" in op.scope:
        return "sync"
    return "model"


@dataclass
class Event:
    device: int
    name: str
    start_ns: float
    dur_ns: float
    category: str
    op: HloOp | None


@dataclass
class TraceContext:
    """What a per-layer metric reader gets."""

    events: list
    devices: int
    steps: int
    window_s: float
    busy_s: float
    flops_per_step: float
    peaks: dict
    workload: dict
    config: dict
    host: list = field(default_factory=list)

    def per_step_ms(self, category: str) -> float:
        tot = sum(e.dur_ns for e in self.events if e.category == category)
        return tot / self.devices / self.steps / 1e6

    def kernel_events(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [e for e in self.events if e.op is not None and e.op.kernel
                and rx.search(e.op.kernel)]

    def breakdown(self) -> dict:
        agg = {}
        for e in self.events:
            key = f"{e.category}:{label(e)}"
            agg[key] = agg.get(key, 0.0) + e.dur_ns
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:10]
        ops = [[k, v / self.devices / 1e9] for k, v in top]
        return {"device_ops": ops, "idle_gaps": idle_gaps(self.events, self.host)}


def label(e: Event) -> str:
    """A short stable name: the kernel, else the op's scope path without the
    jit and transform wrappers, else the instruction's base name."""
    if e.op is not None and e.op.kernel:
        return e.op.kernel
    if e.op is not None and e.op.scope:
        parts = [p for p in e.op.scope.split("/")
                 if not re.match(r"^(jit|pjit|jvp|transpose|vmap|checkpoint|remat|while|body|"
                                 r"cond|rematted_computation|shard_map)\b", p)]
        if parts:
            return "/".join(parts[-3:])
    return re.sub(r"\.\d+$", "", e.name)


def idle_gaps(events: list, host: list | None = None) -> list:
    """Longest gaps between ops on the first device, each named by what the
    host's Python thread was doing across it (the innermost host span that
    covers the gap) and by the op that ended it."""
    first = min(x.device for x in events)
    evs = sorted((e for e in events if e.device == first), key=lambda e: e.start_ns)
    gaps, end = [], None
    for e in evs:
        if end is not None and e.start_ns > end:
            gaps.append((end, e.start_ns, e))
        end = e.start_ns + e.dur_ns if end is None else max(end, e.start_ns + e.dur_ns)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b, e in gaps[:10]:
        doing = None
        for hs, he, name in host or ():
            if hs <= a and he >= b and (doing is None or he - hs < doing[1] - doing[0]):
                doing = (hs, he, name)
        what = f"host {doing[2]}" if doing else "host idle"
        out.append([f"{what}; then {label(e)}", (b - a) / 1e9])
    return out


def _union_ns(intervals: list) -> float:
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def xplane_path(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def host_spans(pd) -> list:
    """(start, end, name) of the host's Python-thread events."""
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out += [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name[:120])
                        for e in line.events]
    return out


def device_events(path: Path, hlo: dict, pd=None) -> list:
    from jax.profiler import ProfileData

    pd = pd if pd is not None else ProfileData.from_file(str(path))
    events = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                # the event's name is the HLO instruction's text: "%name = ..."
                name = ev.name.split(" = ", 1)[0].lstrip("%")
                op = hlo.get(name)
                m = _EV_OPCODE.match(ev.name)
                opcode = op.opcode if op is not None else (m.group(1) if m else "")
                if opcode in CONTAINERS:
                    continue
                events.append(Event(dev, name, float(ev.start_ns), float(ev.duration_ns),
                                    classify(op), op))
    return events


def summarize(events: list, *, steps: int, flops_per_step: float, peaks: dict,
              workload: dict, config: dict, host: list | None = None) -> TraceContext:
    devs = sorted({e.device for e in events})
    busy, window = [], []
    for d in devs:
        iv = [(e.start_ns, e.start_ns + e.dur_ns) for e in events if e.device == d]
        busy.append(_union_ns(iv))
        window.append(max(b for _, b in iv) - min(a for a, _ in iv))
    n = max(len(devs), 1)
    return TraceContext(events=events, devices=n, steps=steps,
                        window_s=sum(window) / n / 1e9, busy_s=sum(busy) / n / 1e9,
                        flops_per_step=flops_per_step, peaks=peaks, workload=workload,
                        config=config, host=host or [])


def reduce(directory: Path, *, program, steps: int, flops_per_step: float,
           hlo_text: str) -> TraceContext:
    from bench import peaks as peaks_mod

    from jax.profiler import ProfileData

    hlo = parse_hlo(hlo_text)
    pd = ProfileData.from_file(str(xplane_path(directory)))
    events = device_events(None, hlo, pd)
    if not events:
        raise RuntimeError("the trace holds no TPU op")
    kind = next(iter(program.mesh.devices.flat)).device_kind
    return summarize(events, steps=steps, flops_per_step=flops_per_step,
                     peaks=peaks_mod.for_kind(kind), workload=program.workload,
                     config=program.config, host=host_spans(pd))
