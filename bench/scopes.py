"""Device time by the program's named scopes (``jax.named_scope``), read
from a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives an operation's times but not its name
stack; the XSpace keeps that as the ``tf_op`` string stat of the
operation's event metadata (``jit(train_step)/transpose(jvp())/while/
body/closed_call/checkpoint/attn/dot_general``). This module decodes the
XSpace with ``google.protobuf`` from a schema of the few fields it reads
(the wire numbers of ``tsl/profiler/protobuf/xplane.proto``), charges
each ``XLA Ops`` event of a TPU device, clipped to the ``bench:window``
span, to the innermost known scope in its name stack, and keeps the
sums per program (the ``XLA Modules`` name, ``jit_train_step``).
Loops and calls are left out, as in ``trace_reduce``: they enclose the
operations.

``split(reading)`` is what the metric readers call. It returns None, and
never raises, where the trace cannot be found or decoded, or where no
operation carries a known scope (a program without the scopes).
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import traceback
from collections import defaultdict
from typing import Dict, Optional

import trace_reduce

#: the scopes the program names (``models/lm``, ``core/kfac``,
#: ``core/soi``, ``launch/steps.make_inv_refresh``)
SCOPES = ("attn", "mlp", "head", "wu", "soi_gram", "inv")
#: an operation named after an argument carries no name stack
_STACK = re.compile(r"^jit\(")
_COMPONENT = re.compile(r"^(?:\w+\()*(%s)\)*$" % "|".join(SCOPES))
_MODULE = re.compile(r"^(.*)\((\d+)\)$")

# field numbers of tsl/profiler/protobuf/xplane.proto; maps are
# repeated entries of (key 1, value 2) on the wire
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("uint64_value", 3, "uint64", False),
              ("str_value", 5, "string", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
}


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if kind in _SCHEMA:
                f.type, f.type_name = F.TYPE_MESSAGE, ".bench_xplane." + kind
            else:
                f.type = getattr(F, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def scope_of(stack: str) -> Optional[str]:
    """The innermost known scope in a name stack, bare or under a
    transformation (``transpose(jvp(head))``); None for none."""
    stack = stack.split(";")[0]
    if not _STACK.match(stack):
        return None
    found = None
    for c in stack.split("/")[1:]:
        m = _COMPONENT.match(c)
        if m and not c.startswith("jit("):
            found = m.group(1)
    return found


def load(path: str) -> Optional[Dict[str, Dict[str, float]]]:
    """program -> scope -> device seconds in the traced window, averaged
    over the TPU devices; None where no operation carries a scope."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    window = None
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                if names.get(ev.metadata_id) == trace_reduce.WINDOW:
                    s = line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
                    iv = (s, s + ev.duration_ps * 1e-12)
                    if window is None or iv[1] - iv[0] > \
                            window[1] - window[0]:
                        window = iv
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(
        float))
    n_dev = 0
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        n_dev += 1
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        lines = {line.name: line for line in plane.lines}
        programs = {}
        for ev in getattr(lines.get("XLA Modules"), "events", ()):
            m = _MODULE.match(meta[ev.metadata_id].name)
            if m:
                programs[int(m.group(2))] = m.group(1)
        ops = lines.get("XLA Ops")
        charge = {}                     # metadata id -> (program, scope)
        for ev in getattr(ops, "events", ()):
            if ev.metadata_id not in charge:
                md = meta[ev.metadata_id]
                stats = {stat_names.get(s.metadata_id): s
                         for s in md.stats}
                tf_op, pid = stats.get("tf_op"), stats.get("program_id")
                scope = None if trace_reduce.CONTAINER.match(md.name) \
                    or tf_op is None else scope_of(tf_op.str_value)
                charge[ev.metadata_id] = (programs.get(
                    pid.uint64_value if pid else None, "?"), scope)
            program, scope = charge[ev.metadata_id]
            if scope is None:
                continue
            s = ops.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
            e = s + ev.duration_ps * 1e-12
            if window is not None:
                s, e = max(s, window[0]), min(e, window[1])
            if e > s:
                out[program][scope] += e - s
    if not out:
        return None
    return {p: {k: v / n_dev for k, v in d.items()} for p, d in out.items()}


def trace_path(reading) -> Optional[str]:
    """The run's ``.xplane.pb``: ``reading.trace_path`` where the harness
    hands it over, else the newest under the harness's trace directory
    for the cell (each traced run leaves its own there until it ends)."""
    path = getattr(reading, "trace_path", None)
    if path:
        return path
    import harness

    found = glob.glob(os.path.join(harness.trace_dir(),
                                   reading.cell.name + ".*", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def split(reading) -> Optional[Dict[str, Dict[str, float]]]:
    """``load`` of the run's trace, once per reading."""
    if "_scopes" not in reading.__dict__:
        try:
            path = trace_path(reading)
            reading._scopes = load(path) if path else None
        except Exception:
            # a reader returns None and never fails the run: say why
            traceback.print_exc()
            reading._scopes = None
    return reading._scopes


def ms_per(reading, program: str, scope: str, calls: int
           ) -> Optional[float]:
    """Device milliseconds under ``scope`` in ``program`` per call."""
    s = split(reading)
    t = (s or {}).get(program, {}).get(scope)
    if not t or not calls:
        return None
    return 1e3 * t / calls
