"""What a jax profiler trace (.xplane.pb) says about each device op.

`lib/trace.py` reads an event's name, start and duration through
`jax.profiler.ProfileData`, which yields an event's own stats only. The trace
holds more: in a device plane's `event_metadata[...].stats`, for EVERY op, its
`tf_op` (the op's `op_name` path in the compiled program: the program's
`jax.named_scope`s and the transformation that made the op), `hlo_category`,
XLA's `flops`, `model_flops` and `bytes_accessed`, `source` and `program_id`.
This file decodes those from the protobuf wire format, the few fields it
reads and nothing else (standard library only: the machine with the chip
need not hold tensorflow; benchmarks/tests/test_scope_readers.py holds the
decoder to `xplane_pb2` where that imports), and classifies an op ONCE for
every reader and for tools/device_scope_report.py:

    scope(tf_op, table)  the LAST component of the path that is a name of the
                         table (bare, or inside `jvp(...)` / `transpose(jvp(...))`,
                         as a top-level scope is written); UNSCOPED if none.
                         The table is the program's (`transformer.SCOPES`)
                         and reaches a reader as data: `lib/scopes.json`
                         (`program_scopes()`), and a metric file's
                         `args.scopes` name the rows it sums.
    phase(tf_op)         `recompute` if the path holds `rematted_computation`,
                         else `backward` if it holds `transpose(jvp`, else
                         `forward` if it holds `jvp(`, else `update` (the
                         optimizer, ZeRO: whatever lies outside the
                         gradient). A program with no gradient (serving)
                         reads `forward` throughout.

Containers (`lib/trace.py`'s `_CONTAINER`: a `while` spans its body's ops,
which are listed themselves) are left out, as everywhere. A fusion carries
ONE `tf_op`, its root's: an op that XLA fused across a scope's border counts
whole where its root lies.

Fields read (tsl/profiler/protobuf/xplane.proto): XSpace.planes = 1; XPlane
name = 2, lines = 3, event_metadata = 4, stat_metadata = 5; XLine name = 2,
timestamp_ns = 3, events = 4; XEvent metadata_id = 1, offset_ps = 2,
duration_ps = 3; XEventMetadata id = 1, name = 2, stats = 5; XStat
metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5, bytes = 6,
ref = 7; XStatMetadata id = 1, name = 2; a map entry's key = 1, value = 2.
"""

from __future__ import annotations

import functools
import gzip
import os
import re
import struct
from typing import Any, Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple

from .spec import BENCH_DIR, load_json
from .trace import _CONTAINER, Interval, measure, subtract, union

UNSCOPED = "unscoped"
PHASES = ("forward", "backward", "recompute", "update")
OP_LINES = ("XLA Ops", "Async XLA Ops")
# hlo_category values that move data and compute nothing (train_copy_share_pct)
COPY_CATEGORIES = ("data formatting", "copy", "copy-start", "copy-done")
# hlo_category values of a collective, synchronous or the start of an asynchronous one (by prefix)
COLLECTIVE_CATEGORIES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


class Op(NamedTuple):
    """One executed device op with what its event metadata says. Seconds on
    the trace's device clock; XLA's counts are per execution."""

    plane: str
    line: str
    hlo: str
    start: float
    end: float
    tf_op: str
    category: str
    model_flops: float
    flops: float
    bytes_accessed: float
    program_id: str
    source: str

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def custom_call(self) -> bool:
        return self.category == "custom-call"

    @property
    def collective(self) -> bool:
        return self.category.startswith(COLLECTIVE_CATEGORIES)


# ------------------------------------------------------------- wire format


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: varints as unsigned
    ints, 64- and 32-bit fields as their bytes, length-delimited as a view."""
    i, n, view = 0, len(buf), memoryview(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = bytes(view[i : i + 8]), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = bytes(view[i : i + size]), i + size
        elif wire == 5:
            value, i = bytes(view[i : i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane file")
        yield number, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes) -> Tuple[int, Any, bool]:
    """One XStat -> (its stat metadata id, its value, whether the value is a
    reference to another stat metadata's NAME)."""
    key, value, ref = 0, None, False
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = v.decode("utf-8", "replace")
        elif number == 6:
            value = v
        elif number == 7:
            value, ref = v, True
    return key, value, ref


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _planes(data: bytes) -> Iterator[Tuple[str, List[bytes], Dict[int, bytes], Dict[int, str]]]:
    """(name, lines, event metadata id -> its bytes, stat metadata id -> name) a plane."""
    for number, _wire, plane in _fields(data):
        if number != 1:
            continue
        name, lines, events, stats = "", [], {}, {}
        for n, _w, v in _fields(plane):
            if n == 2:
                name = v.decode()
            elif n == 3:
                lines.append(v)
            elif n == 4:
                key, value = _map_entry(v)
                events[key] = value
            elif n == 5:
                key, value = _map_entry(v)
                stats[key] = next((x.decode() for m, _t, x in _fields(value) if m == 2), "")
        yield name, lines, events, stats


def _event_metadata(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, Dict[str, Any]]:
    name, stats = "", {}
    for n, _w, v in _fields(buf):
        if n == 2:
            name = v.decode("utf-8", "replace")
        elif n == 5:
            key, value, ref = _stat(v)
            stats[stat_names.get(key, str(key))] = stat_names.get(value, "") if ref else value
    return name, stats


def _number(v: Any) -> float:
    try:
        return float(v)
    except (TypeError, ValueError):
        return 0.0


def read_ops(path: str) -> List[Op]:
    """Every event of the `XLA Ops` / `Async XLA Ops` lines of every
    `/device:TPU:<n>` plane, containers included, in file order."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    out: List[Op] = []
    for plane, plane_lines, events, stat_names in _planes(data):
        if not plane.startswith("/device:TPU:"):
            continue
        known: Dict[int, Tuple[str, Dict[str, Any]]] = {}
        for line in plane_lines:
            name, t0_ps, evs = "", 0, []
            for n, _w, v in _fields(line):
                if n == 2:
                    name = v.decode()
                elif n == 3:
                    t0_ps = _signed(v) * 1000
                elif n == 4:
                    evs.append(v)
            if name not in OP_LINES:
                continue
            for ev in evs:
                meta = offset = duration = 0
                for n, _w, v in _fields(ev):
                    if n == 1:
                        meta = v
                    elif n == 2:
                        offset = _signed(v)
                    elif n == 3:
                        duration = _signed(v)
                if meta not in known:
                    known[meta] = _event_metadata(events.get(meta, b""), stat_names)
                hlo, st = known[meta]
                start = (t0_ps + offset) * 1e-12
                out.append(Op(
                    plane, name, hlo, start, start + duration * 1e-12,
                    str(st.get("tf_op", "")), str(st.get("hlo_category", "")),
                    _number(st.get("model_flops")), _number(st.get("flops")), _number(st.get("bytes_accessed")),
                    str(st.get("program_id", "")), str(st.get("source", "")),
                ))
    return out


# ---------------------------------------------------------- classification


@functools.lru_cache(maxsize=None)
def program_scopes(key: str = "scopes") -> List[str]:
    """The program's table of scope names, as the benchmark holds it;
    `every_program`: the ones every forward opens (see `OpTable.scoped`)."""
    return load_json(os.path.join(BENCH_DIR, "lib", "scopes.json"))[key]


_WRAPPED = re.compile(r"^(?:transpose\()?jvp\((.*?)\)+$")


def scope(tf_op: str, names) -> str:
    last = UNSCOPED
    for part in tf_op.split("/"):
        m = _WRAPPED.match(part)
        if m is not None:
            part = m.group(1)
        if part in names:
            last = part
    return last


def phase(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "recompute"
    if "transpose(jvp" in tf_op:
        return "backward"
    if "jvp(" in tf_op:
        return "forward"
    return "update"


# ------------------------------------------------------- one trace's table


class OpTable:
    """The ops of one trace inside a window, shifted by the skew `lib/trace.py`
    measured: what the scope readers and the tool sum over. `window` None:
    the whole trace. `names`: the benchmark's copy of the program's table."""

    def __init__(self, path: str, window: Optional[Interval] = None, skew_s: float = 0.0):
        self.names = frozenset(program_scopes())
        self._scopes: Dict[str, str] = {}
        ops = [op._replace(start=op.start + skew_s, end=op.end + skew_s) for op in read_ops(path)]
        if window is not None:
            lo, hi = window
            ops = [op._replace(start=max(op.start, lo), end=min(op.end, hi)) for op in ops if op.end > lo and op.start < hi]
        self.chips = sorted({op.plane for op in ops if op.line == "XLA Ops"})
        self.everything = [op for op in ops if op.line == "XLA Ops"]  # containers too: a chip's busy time is their union
        self.sync = [op for op in self.everything if not _CONTAINER.match(op.hlo)]
        self.flying = [op for op in ops if op.line == "Async XLA Ops"]  # asynchronous copies and collectives, start to done
        self.differentiated = {op.program_id for op in self.sync if "jvp(" in op.tf_op}  # the programs that hold a gradient

    def busy_s(self) -> float:
        """`Trace.busy_s()`: seconds in which an op ran, the mean over the chips."""
        if not self.chips:
            return 0.0
        return sum(measure(union([(op.start, op.end) for op in self.everything if op.plane == c])) for c in self.chips) / len(self.chips)

    def scope_of(self, op: Op) -> str:
        if op.tf_op not in self._scopes:
            self._scopes[op.tf_op] = scope(op.tf_op, self.names)
        return self._scopes[op.tf_op]

    def phase_of(self, op: Op) -> str:
        """`phase`, but `forward` throughout a program with no gradient in it (serving)."""
        return phase(op.tf_op) if op.program_id in self.differentiated else "forward"

    def seconds(self, ops: Iterable[Op]) -> float:
        """Device seconds of those ops, the mean over the chips."""
        return sum(op.seconds for op in ops) / max(1, len(self.chips))

    def scoped(self) -> bool:
        """Whether the traced program carries the table: an op lies under a
        scope that every forward opens (`norm`, `embed`, `head`). A parent
        from before the table does not (its routed layers had `moe.*` scopes
        and nothing round them: 84 % of such a step would read `unscoped`),
        nor does a CPU rehearsal."""
        everywhere = set(program_scopes("every_program"))
        return any(self.scope_of(op) in everywhere for op in self.sync)

    def exposed_s(self, ops: Iterable[Op]) -> float:
        """Seconds in which one of those ops runs (an asynchronous one: is in
        flight) and no `XLA Ops` event of the same chip that is not itself a
        collective does; the worst chip. A synchronous collective has the
        chip's one line to itself, so all of it is exposed."""
        ops, worst = list(ops), 0.0
        for c in self.chips:
            theirs = union([(op.start, op.end) for op in ops if op.plane == c])
            compute = union([(op.start, op.end) for op in self.sync if op.plane == c and not op.collective])
            worst = max(worst, measure(subtract(theirs, compute)))
        return worst


def table_of(evidence: Dict[str, Any]) -> Optional[OpTable]:
    """The run's ops with their metadata, decoded once (as `trace_of` caches
    `Trace`), inside the traced window; None in a run without a device trace."""
    if "_op_table" not in evidence:
        from ..readers._common import trace_of

        tr = trace_of(evidence)
        evidence["_op_table"] = None if tr is None else OpTable(evidence["worker"]["trace_path"], tr.window(), tr.skew_s)
    return evidence["_op_table"]
