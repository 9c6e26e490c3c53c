"""Device time by model stage, read from a profiler trace.

The program names its stages with ``jax.named_scope`` (the names below;
``repro.models.layers.STAGES`` is the program's list, which a test holds
equal to this copy, so that the benchmark imports nothing new from the
program and still reads a program without them).  A scope reaches each
compiled op's ``op_name`` metadata as a path such as
``jit(serve_step)/layers/while/body/closed_call/moe.experts/dot_general``.

The device events of a trace carry no path.  The profiler keeps each
compiled program it saw in the trace, as the ``Hlo Proto`` stat of the
program's entry on the ``/host:metadata`` plane; ``load`` reads every
op's path from there, by instruction name within the program whose
launch encloses the op, and adds it to the op's record as a sixth field.
An op's program is the path's leading ``jit(<name>)``; its stage is the
innermost stage name in the path, or ``(none)`` where the path holds
none (the copies the compiler adds have no path).

``trace_reduce`` reads the same records without their sixth field; this
module adds only what the stage metrics need.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from bench import common, trace_reduce

STAGES = (
    "embed", "norm",
    "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
    "ffn.dense", "head", "layers",
)
#: Stages of the model's own work; ``layers`` is the scan over them.
MODEL_STAGES = tuple(s for s in STAGES if s != "layers")
NONE = "(none)"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


# --------------------------------------------------------------------- #
# The few protobuf messages needed, read from the wire format
# --------------------------------------------------------------------- #


def _varint(buf, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of an
    ``xla.HloProto`` (module 1 > computations 3 > instructions 2 > name 1,
    metadata 7 > op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, ins in _fields(comp):
                if f != 2:
                    continue
                name = path = ""
                for f, v in _fields(ins):
                    if f == 1:
                        name = bytes(v).decode()
                    elif f == 7:
                        for g, w in _fields(v):
                            if g == 2:
                                path = bytes(w).decode()
                out[name] = path
    return out


def programs_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Program launch name (``jit_serve_step(<id>)``) -> instruction name
    -> ``op_name``, from the programs an ``XSpace`` keeps on its metadata
    plane (planes 1 > name 2, event_metadata 4, stat_metadata 5)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == METADATA_PLANE
                   for f, v in fields):
            continue
        stat_names, events = {}, []
        for f, entry in fields:
            if f not in (4, 5):
                continue
            value = dict(_fields(entry)).get(2)      # map entry: key 1, value 2
            if value is None:
                continue
            if f == 5:                               # XStatMetadata: id 1, name 2
                meta = dict(_fields(value))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
            else:                                    # XEventMetadata
                events.append(value)
        for ev in events:
            name, protos = "", []
            for f, v in _fields(ev):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 5:                         # XStat: id 1, bytes 6
                    stat = dict(_fields(v))
                    protos.append((stat.get(1, 0), stat.get(6)))
            for sid, proto in protos:
                if stat_names.get(sid) == HLO_PROTO_STAT and proto is not None:
                    out[name] = _hlo_op_names(proto)
    return out


# --------------------------------------------------------------------- #
# Records with paths
# --------------------------------------------------------------------- #


def _instruction(event_name: str) -> str:
    """``%fusion.324 = bf16[...] fusion(...)`` -> ``fusion.324``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def _root(launch: str) -> str:
    """``jit_serve_step(12)`` -> ``jit(serve_step)``, the path's root."""
    name = trace_reduce.program_name(launch)
    return f"jit({name[4:]})" if name.startswith("jit_") else name


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str) -> list[tuple]:
    """The records ``trace_reduce.load`` gives for the newest trace under
    ``trace_dir``, each device op's with its ``op_name`` path as a sixth
    field.  An op with no path gets its program's root, ``jit(<name>)``;
    a path that does not start at the root is put under it."""
    import jax
    with open(newest_xplane(trace_dir), "rb") as f:
        raw = f.read()
    names = programs_op_names(raw)
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PREFIX)
        launches = []
        if device:
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    launches += [(int(ev.start_ns),
                                  int(ev.start_ns) + int(ev.duration_ns),
                                  ev.name) for ev in line.events]
            launches.sort()
        starts = [s for s, _, _ in launches]
        for line in plane.lines:
            ops = device and line.name == trace_reduce.OPS_LINE
            for ev in line.events:
                if not (device or ev.name.startswith(
                        trace_reduce.HOST_SPAN_PREFIX)):
                    continue
                rec = (plane.name, line.name, ev.name, int(ev.start_ns),
                       int(ev.duration_ns))
                if ops:
                    k = bisect.bisect_right(starts, rec[3]) - 1
                    if k >= 0 and rec[3] < launches[k][1]:
                        launch = launches[k][2]
                        path = names.get(launch, {}).get(
                            _instruction(ev.name), "")
                        if not path.startswith("jit("):
                            root = _root(launch)
                            path = f"{root}/{path}" if path else root
                        rec += (path,)
                out.append(rec)
    return out


def program_of(path: str) -> str:
    """The program of a path: ``jit(serve_step)/...`` -> ``serve_step``."""
    m = re.match(r"jit\(([^)]*)\)", path)
    return m.group(1) if m else "?"


def stage_of(path: str) -> str:
    """The innermost stage name of a path (of its first part, where the
    compiler joined several with ``;``), or ``(none)``."""
    for part in reversed(path.split(";", 1)[0].split("/")):
        if part in STAGES:
            return part
    return NONE


def _device_ops(events: list[tuple]):
    """(path, op name, seconds over the device count) of each device op
    that has a path; loops and conditionals left out, as in ``ops``."""
    devices = {p for p, ln, *_ in events
               if p.startswith(trace_reduce.DEVICE_PREFIX)
               and ln in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)}
    for p, ln, n, s, d, *path in events:
        if (path and p in devices and ln == trace_reduce.OPS_LINE and d > 0
                and not n.startswith(trace_reduce.CONTROL_FLOW)):
            yield path[0], n, d * 1e-9 / len(devices)


def stages(*slices: list[tuple]) -> dict[str, dict[str, float]]:
    """Program -> stage -> device seconds, summed over the slices and
    averaged over the devices the way ``trace_reduce.reduce``'s ``ops``
    is.  Five-field records (no path) give nothing."""
    out: dict[str, dict[str, float]] = {}
    for events in slices:
        for path, _, t in _device_ops(events):
            acc = out.setdefault(program_of(path), {})
            st = stage_of(path)
            acc[st] = acc.get(st, 0.0) + t
    return out


def ops(*slices: list[tuple], top: int = 10) -> list:
    """The ``top`` device ops by seconds, each named
    ``<program>:<stage> <op>``, so that the same op name in two programs
    stays two entries and the stage leads the name."""
    acc: dict[str, float] = {}
    for events in slices:
        for path, n, t in _device_ops(events):
            key = f"{program_of(path)}:{stage_of(path)} " \
                  f"{trace_reduce.op_name(n)}"
            acc[key] = acc.get(key, 0.0) + t
    return sorted(([n, t] for n, t in acc.items()), key=lambda x: -x[1])[:top]


# --------------------------------------------------------------------- #
# The run's own trace
# --------------------------------------------------------------------- #


def _slice_dirs(trace_dir: str) -> list[str]:
    subs = [s for s in os.listdir(trace_dir) if s.isdigit()]
    return [os.path.join(trace_dir, s) for s in sorted(subs, key=int)]


def _newest(trace_dir: str) -> float:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max((os.path.getmtime(f) for f in files), default=0.0)


def _launches(slices: list[list[tuple]]) -> dict[str, tuple[float, int]]:
    """Program -> (device seconds, launches), counted as
    ``trace_reduce.reduce`` counts its ``programs``."""
    out: dict[str, tuple[float, int]] = {}
    for events in slices:
        devices = {p for p, ln, *_ in events
                   if p.startswith(trace_reduce.DEVICE_PREFIX)
                   and ln in (trace_reduce.OPS_LINE,
                              trace_reduce.MODULES_LINE)}
        for p, ln, n, s, d, *_ in events:
            if p in devices and ln == trace_reduce.MODULES_LINE:
                t, c = out.get(trace_reduce.program_name(n), (0.0, 0))
                out[trace_reduce.program_name(n)] = (
                    t + d * 1e-9 / len(devices), c + 1)
    return out


def _same_launches(slices, programs: dict) -> bool:
    mine = _launches(slices)
    return mine.keys() == programs.keys() and all(
        mine[n][1] == p["count"]
        and abs(mine[n][0] - p["seconds"]) <= 1e-9 * max(p["seconds"], 1e-9)
        for n, p in programs.items())


def run_slices(ctx: dict) -> list[list[tuple]] | None:
    """The records, with paths, of the slices whose reduction the serving
    run put in ``ctx["trace"]``: the trace directory of the benchmark
    (``<cache>/trace/<config>/<slice>``), newest first, whose program
    launches add up to the same device seconds and counts."""
    t = ctx.get("trace")
    if not t:
        return None
    root = os.path.join(common.CACHE, "trace")
    dirs = [d for d in glob.glob(os.path.join(root, "*")) if os.path.isdir(d)]
    for d in sorted(dirs, key=_newest, reverse=True):
        try:
            slices = [load(s) for s in _slice_dirs(d)]
        except FileNotFoundError:
            continue
        if _same_launches(slices, t["programs"]):
            return slices
    return None


def of_run(ctx: dict) -> dict[str, dict[str, float]]:
    """``stages`` of the run's trace, read once and kept in ``ctx``; empty
    where the run made no trace or it cannot be found."""
    if "stages" not in ctx:
        slices = run_slices(ctx)
        ctx["stages"] = stages(*slices) if slices else {}
    return ctx["stages"]


def program_stages(ctx: dict, part: str) -> dict[str, float] | None:
    """Stage -> device seconds of the program whose name holds ``part``
    (``serve_step``), or None where no op of it carries a model stage, as
    in a trace of a program without the scopes."""
    for name, acc in of_run(ctx).items():
        if part in name and any(acc.get(s) for s in MODEL_STAGES):
            return acc
    return None
