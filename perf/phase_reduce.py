"""From the program's own names in a profiler trace to per-phase device
time and per-call host time.

The program (``paddle_tpu``) names what it does in two ways.  Host
work is wrapped in spans on the profiler's clock (``to_static.call``
round a compiled step's host side, with ``to_static.read_state``,
``to_static.launch`` and ``to_static.write_state`` inside it).  Device
operations carry, in their HLO ``op_name``, the phase scopes that were
live when they were traced: ``jit(train_step)/GPTForCausalLM/gpt/
block_3/attn/...`` in the forward, ``.../backward/...`` in the
backward (JAX wraps a scope that went through a transform:
``transpose(jvp(backward))``), ``optimizer`` and ``clear_grad``.  Forward
operations run again inside the backward are told two ways:
``rematted_computation`` round what ``jax.checkpoint`` runs again, and a
``jvp(...)`` scope with no ``transpose(...)`` round what the backward's
own linearisation runs (``run_backward`` builds each node's vjp at
backward time, which traces the node's forward once more: XLA merges
that copy with the forward pass's where the two are the same
computation, and where they are not, as with the flash kernel's
residual-saving forward, it runs).  A fusion has one ``op_name``, its
root's, so an operation that mixes two phases counts under one of
them; the table's note says how much time lies in such fusions.

``table(run)`` parses the run's trace once (the result is kept on
``run``) and returns a ``PhaseTable``, or None where no operation of
the window lies under ``backward``, ``optimizer`` or ``clear_grad``: a
step compiled before the scopes existed, or a backend that drops the
metadata.  The readers in ``perf/metrics`` then
return None, and no number is printed.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re

from perf import trace_reduce as tr

PHASES = ("forward", "recompute", "backward", "optimizer")
UNATTRIBUTED = "unattributed"
CALL = "to_static.call"
SPANS = (CALL, "to_static.read_state", "to_static.launch",
         "to_static.write_state")
# a component of JAX's own that says the operation is one that
# ``jax.checkpoint`` runs again in the backward (``checkpoint`` alone
# brackets the whole differentiated region, its backward included)
REMAT = "rematted_computation"
# components JAX itself puts on the name stack: not the program's scopes
JAX_OWN = ("checkpoint", REMAT, "remat2")
# the scopes only the program opens (``core/autograd.py``,
# ``optimizer/optimizer.py``): a compiled training step that carries
# the program's scopes at all carries these
MARKERS = ("backward", "optimizer", "clear_grad")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"


# ---------------------------------------------- op_name of an operation
# The v5e's trace names a device event by its HLO instruction's text
# without its metadata, and gives it no stat that holds the op_name
# (looked at by hand, PR 26).  The names are in the HLO modules the
# profiler keeps beside the events: plane "/host:metadata" holds one
# event-metadata entry per program, named "<module>(<program id>)",
# with the serialized HloProto as its stat.  jax.profiler.ProfileData
# does not show event metadata, so the few protobuf fields needed are
# read from the file's bytes.
def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            byte = buf[i]
            i += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        kind = key & 7
        if kind in (0, 2):
            value = shift = 0
            while True:
                byte = buf[i]
                i += 1
                value |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            if kind == 2:
                value, i = buf[i:i + value], i + value
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _first(buf, number, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _text(buf, number):
    return bytes(_first(buf, number, b"")).decode()


def hlo_protos(xspace_bytes):
    """{"<module>(<program id>)": serialized HloProto} from the bytes
    of a ``.xplane.pb`` (XSpace.planes=1; XPlane.name=2,
    .event_metadata=4, a map whose value=2 is an XEventMetadata with
    .name=2 and .stats=5; XStat.bytes_value=6)."""
    out = {}
    for number, plane in _fields(memoryview(xspace_bytes)):
        if number != 1 or _text(plane, 2) != METADATA_PLANE:
            continue
        for n, entry in _fields(plane):
            meta = _first(entry, 2) if n == 4 else None
            if meta is None:
                continue
            for m, stat in _fields(meta):
                proto = _first(stat, 6) if m == 5 else None
                if proto is not None:
                    out[_text(meta, 2)] = proto
    return out


def _varints(value):
    """A repeated int64 field's values: one int, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        number = shift = 0
        while True:
            byte = value[i]
            i += 1
            number |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        out.append(number)
    return out


def op_names_of(hlo_proto):
    """({instruction name: op_name}, {fusion's name: op_names of the
    instructions fused into it}) of one serialized HloProto
    (.hlo_module=1; HloModuleProto.computations=3;
    HloComputationProto.instructions=2, .id=5;
    HloInstructionProto.name=1, .opcode=2, .metadata=7,
    .called_computation_ids=38; OpMetadata.op_name=2)."""
    names, inside, calls = {}, {}, {}
    module = _first(hlo_proto, 1)
    for n, computation in (_fields(module) if module is not None else ()):
        if n != 3:
            continue
        mine = inside[_first(computation, 5)] = []
        for m, instruction in _fields(computation):
            if m != 2:
                continue
            name, metadata = _text(instruction, 1), _first(instruction, 7)
            if metadata is not None:
                names[name] = _text(metadata, 2)
                mine.append(names[name])
            if _text(instruction, 2) == "fusion":
                calls[name] = [c for k, v in _fields(instruction)
                               if k == 38 for c in _varints(v)]
    return names, {name: [op for c in called for op in inside.get(c, ())]
                   for name, called in calls.items()}


def instruction_name(event_name):
    """``%fusion.812 = bf16[...] fusion(...)`` -> ``fusion.812``."""
    return event_name.partition(" = ")[0].lstrip("%")


# ------------------------------------------------- scopes and phases
def parse(op_name):
    """(the program's scopes along an ``op_name``, outermost first; the
    transforms they went through).  The last component is the
    primitive; ``jit(...)`` components are programs and library
    functions, not scopes; a scope that went through a transform comes
    wrapped (``transpose(jvp(attn))``) and is unwrapped; an empty one
    (``jvp()``) only adds its transforms."""
    names, transforms = [], set()
    for part in op_name.split("/")[:-1]:
        wrappers = []
        while True:
            m = re.match(r"^([A-Za-z_][A-Za-z0-9_]*)\((.*)\)$", part)
            if not m:
                break
            wrappers.append(m.group(1))
            part = m.group(2)
        if "jit" in wrappers or "pjit" in wrappers:
            continue
        transforms.update(wrappers)
        if part:
            names.append(part)
    return names, transforms


def scopes(op_name):
    return parse(op_name)[0]


def phase_of(names, transforms=()):
    """The phase of an operation whose scopes are ``names``; one with
    none but JAX's own is unattributed."""
    if all(n in JAX_OWN for n in names):
        return UNATTRIBUTED
    if "optimizer" in names or "clear_grad" in names:
        return "optimizer"
    if "backward" not in names:
        return "forward"
    if REMAT in names or ("jvp" in transforms
                          and "transpose" not in transforms):
        return "recompute"
    return "backward"


@functools.lru_cache(maxsize=None)
def phase_of_op(op_name):
    return phase_of(*parse(op_name))


def is_head_or_loss(names):
    return "lm_head" in names or "loss" in names


def shown_path(names):
    """A scope path for the table: JAX's own components and the
    ``backward`` marker left out (the phase says that), the outer copy
    of a path that a transform repeated dropped, and the layers'
    numbers folded (``block_3`` -> ``block_*``)."""
    own = [n for n in names if n != "backward" and n not in JAX_OWN]
    if own:
        last = len(own) - 1 - own[::-1].index(own[0])
        own = own[last:]
    return "/".join(re.sub(r"_\d+$", "_*", n) for n in own) or "-"


# ------------------------------------------------------ the trace
def load(path, spans=SPANS):
    """The first chip's operations and the host spans named in
    ``spans``, in ``trace_reduce.load_xplane``'s form (planes -> lines
    -> events of [name, start_ns, duration_ns]) with, beside a device
    line's events, their ``op_names`` and, for a fusion, the other
    phases found among the instructions fused ``inside`` it
    ("optimizer+recompute", '' for none): the form of the recorded
    trace the tests reduce.  An operation belongs to the program whose
    "XLA Modules" event covers its start; its op_name is '' where the
    trace keeps no HLO module for that program."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        blob = f.read()
    data = ProfileData.from_serialized_xspace(blob)
    protos, parsed = hlo_protos(blob), {}

    def module(name):
        if name not in parsed:
            proto = protos.get(name)
            parsed[name] = ({}, {}) if proto is None else op_names_of(proto)
        return parsed[name]

    chips = sorted((int(m.group(1)), plane.name, plane)
                   for plane in data.planes
                   for m in [tr.DEVICE_PLANE.match(plane.name)] if m)
    planes = []
    for _, name, plane in chips[:1]:
        lines = {line.name: line for line in plane.lines}
        runs = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        starts = [r[0] for r in runs]
        events, op_names, inside = [], [], []
        for ev in (lines[tr.OPS_LINE].events if tr.OPS_LINE in lines
                   else ()):
            op, others = "", ""
            at = bisect.bisect_right(starts, ev.start_ns) - 1
            if at >= 0 and ev.start_ns < runs[at][1]:
                names, fused = module(runs[at][2])
                instruction = instruction_name(ev.name)
                op = names.get(instruction, "")
                others = "+".join(sorted(
                    {phase_of_op(o) for o in fused.get(instruction, ())}
                    - {phase_of_op(op), UNATTRIBUTED}))
            events.append([tr.short_name(ev.name), ev.start_ns,
                           ev.duration_ns])
            op_names.append(op)
            inside.append(others)
        planes.append({"name": name, "lines": [
            {"name": tr.OPS_LINE, "events": events,
             "op_names": op_names, "inside": inside}]})
    for plane in data.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, ev.start_ns, ev.duration_ns]
                      for ev in line.events if ev.name in spans]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def cut(raw, lo, hi, min_ns):
    """``raw`` cut to a recorded trace small enough to keep beside the
    tests: the operations that overlap [lo, hi] and last at least
    ``min_ns``, the host spans that lie inside it."""
    planes = []
    for plane in raw["planes"]:
        lines = []
        for line in plane["lines"]:
            names = line.get("op_names")
            if names is None:
                keep = [i for i, (_, s, d) in enumerate(line["events"])
                        if s >= lo and s + d <= hi]
            else:
                keep = [i for i, (_, s, d) in enumerate(line["events"])
                        if s + d > lo and s < hi and d >= min_ns]
            lines.append({key: [column[i] for i in keep]
                          if isinstance(column, list) else column
                          for key, column in line.items()})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def row_key(short):
    """The key ``Trace.breakdown`` groups an operation under."""
    return re.sub(r"\.\d+( |$)", r"\1", short)


# what a stretch of self time is filed under: its phase, its scope path
# as shown, its row of ``breakdown.device_ops``, whether it lies under
# ``lm_head`` or ``loss``, and the other phases fused inside it
Cell = collections.namedtuple("Cell", "phase path row head_or_loss inside")


class PhaseTable:
    """Self time of the window's device operations by phase and scope
    path, and the program's host spans inside the window."""

    def __init__(self, raw, lo, hi):
        self.lo, self.hi = lo, hi
        ops, spans = [], []
        for plane in raw["planes"]:
            for line in plane["lines"]:
                if "op_names" in line:
                    inside = line.get("inside") or [""] * len(line["events"])
                    ops += [(f"{short}\t{op}\t{others}", max(s, lo),
                             min(s + d, hi))
                            for (short, s, d), op, others in zip(
                                line["events"], line["op_names"], inside)
                            if s + d > lo and s < hi]
                elif plane["name"] == tr.HOST_PLANE:
                    spans += line["events"]
        ops.sort(key=lambda o: (o[1], -o[2]))
        self.busy_ns = tr.busy_ns([(s, e) for _, s, e in ops], lo, hi)
        self.cells = {}         # Cell -> ns
        self.marked_events = 0
        for key, ns in tr.self_times(ops).items():
            short, op, others = key.split("\t")
            names, transforms = parse(op)
            self.marked_events += any(n in MARKERS for n in names)
            cell = Cell(phase_of(names, transforms), shown_path(names),
                        row_key(short), is_head_or_loss(names), others)
            self.cells[cell] = self.cells.get(cell, 0) + ns
        self.spans = {name: [] for name in SPANS}
        for name, s, d in spans:
            if name in self.spans and s >= lo and s + d <= hi:
                self.spans[name].append(d)

    @property
    def missing(self):
        """No operation of the window lies under a scope that only the
        program opens: whatever names its op_names hold (JAX's
        ``checkpoint``, a kernel's function) are not phase scopes."""
        return self.marked_events == 0

    @property
    def calls(self):
        return len(self.spans[CALL])

    def phase_ns(self, phase):
        return sum(ns for c, ns in self.cells.items() if c.phase == phase)

    def head_loss_ns(self):
        return sum(ns for c, ns in self.cells.items() if c.head_or_loss)

    def span_ns(self, name):
        return sum(self.spans[name])

    def paths(self, phase, top=None):
        """[[scope path, seconds]] of a phase's time, largest first."""
        out = {}
        for cell, ns in self.cells.items():
            if cell.phase == phase:
                out[cell.path] = out.get(cell.path, 0) + ns
        rows = sorted(out.items(), key=lambda kv: -kv[1])[:top]
        return [[k, ns / 1e9] for k, ns in rows]

    def as_note(self, breakdown_rows, top=8, per_row=4):
        """The table as the run's note: per phase its seconds and its
        largest scope paths; per row of ``breakdown.device_ops`` the
        (phase, scope path) it falls under; and the time of fusions
        that hold instructions of another phase than their own."""
        phases = {}
        for phase in PHASES + (UNATTRIBUTED,):
            phases[phase] = {
                "seconds": self.phase_ns(phase) / 1e9,
                "top": self.paths(phase, top)}
        rows = {}
        for row in breakdown_rows:
            under = {}
            for cell, ns in self.cells.items():
                if cell.row == row:
                    key = f"{cell.phase}:{cell.path}"
                    under[key] = under.get(key, 0) + ns
            rows[row] = [[k, ns / 1e9] for k, ns in sorted(
                under.items(), key=lambda kv: -kv[1])[:per_row]]
        mixed = {}
        for cell, ns in self.cells.items():
            if cell.inside:
                key = f"{cell.phase} with {cell.inside} inside"
                mixed[key] = mixed.get(key, 0) + ns
        return {"calls": self.calls, "busy_s": self.busy_ns / 1e9,
                "head_loss_s": self.head_loss_ns() / 1e9,
                "phases": phases,
                "fusions_counted_under_one_phase_with_others_inside":
                    {k: ns / 1e9 for k, ns in sorted(
                        mixed.items(), key=lambda kv: -kv[1])},
                "breakdown_rows": rows}


def table(run):
    """The run's ``PhaseTable`` (parsed once, kept on ``run``), or None
    where the run has no trace or its trace no program scope; the
    first such call notes why."""
    if hasattr(run, "_phase_table"):
        return run._phase_table
    run._phase_table = run._phase_spans = None
    if run.trace is None:
        return None
    try:
        raw = load(tr.find_xplane(run.ctx.trace_dir))
    except FileNotFoundError:
        run.note(phase_scopes_missing="no trace file")
        return None
    t = run._phase_spans = PhaseTable(raw, run.trace.lo, run.trace.hi)
    if t.missing:
        run.note(phase_scopes_missing="no operation in the traced window "
                 "carries a program scope")
        return None
    rows = [name for name, _ in run.trace.breakdown()["device_ops"]]
    run.note(phase_table=t.as_note(rows))
    run._phase_table = t
    return t


def spans_of(run):
    """The ``PhaseTable`` for its host spans alone: these exist where
    the device's scopes do not (another backend), so they are read
    even where ``table`` gives None."""
    table(run)
    return run._phase_spans


def device_ms(run, phase):
    """Self time per ``to_static.call`` of the window's operations in
    ``phase``, in milliseconds."""
    t = table(run)
    if t is None or not t.calls:
        return None
    return t.phase_ns(phase) / t.calls / 1e6
