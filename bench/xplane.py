"""The parts of a profiler ``.xplane.pb`` that ``jax.profiler.ProfileData``
does not expose: the JAX name stack of each device operation, and the
program's own host spans.

:func:`load` returns the trace of :func:`bench.trace_reduce.load_xplane`
(``ops``, ``modules``, ``spans`` and the window they define, unchanged)
with two more keys:

* ``scopes``: for each event of ``ops``, in the same order, the name
  stack JAX gave the operation (the ``tf_op`` stat of its event metadata,
  e.g. ``jit(decode_cycle)/d2sd.verify/while/body/...``; "" where there
  is none);
* ``program_spans``: the host's ``engine.*`` annotations
  (``repro/serving/spans.py``), ``(name, start_ns, dur_ns)`` on the
  device trace's clock.

The messages are built at import from the few fields of TSL's
``xplane.proto`` this module reads; ``google.protobuf`` skips the rest.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from bench import trace_reduce

PROGRAM_PREFIX = "engine."

_F = descriptor_pb2.FieldDescriptorProto
_FIELDS = {     # message -> (name, number, type, repeated, message type)
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, True, "XPlane")],
    "XPlane": [("name", 2, _F.TYPE_STRING, False, None),
               ("lines", 3, _F.TYPE_MESSAGE, True, "XLine"),
               ("event_metadata", 4, _F.TYPE_MESSAGE, True, "EventMdEntry"),
               ("stat_metadata", 5, _F.TYPE_MESSAGE, True, "StatMdEntry")],
    "XLine": [("name", 2, _F.TYPE_STRING, False, None),
              ("timestamp_ns", 3, _F.TYPE_INT64, False, None),
              ("events", 4, _F.TYPE_MESSAGE, True, "XEvent")],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, False, None),
               ("offset_ps", 2, _F.TYPE_INT64, False, None),
               ("duration_ps", 3, _F.TYPE_INT64, False, None)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, False, None),
              ("str_value", 5, _F.TYPE_STRING, False, None),
              ("ref_value", 7, _F.TYPE_UINT64, False, None)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, False, None),
                       ("name", 2, _F.TYPE_STRING, False, None),
                       ("stats", 5, _F.TYPE_MESSAGE, True, "XStat")],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, False, None),
                      ("name", 2, _F.TYPE_STRING, False, None)],
    # map<int64, ...> fields are repeated (key = 1, value = 2) entries
    "EventMdEntry": [("key", 1, _F.TYPE_INT64, False, None),
                     ("value", 2, _F.TYPE_MESSAGE, False, "XEventMetadata")],
    "StatMdEntry": [("key", 1, _F.TYPE_INT64, False, None),
                    ("value", 2, _F.TYPE_MESSAGE, False, "XStatMetadata")],
}


def _space_class():
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for name, num, typ, rep, sub in fields:
            f = m.field.add(name=name, number=num, type=typ,
                            label=_F.LABEL_REPEATED if rep
                            else _F.LABEL_OPTIONAL)
            if sub:
                f.type_name = ".bench_xplane." + sub
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


_XSPACE = _space_class()


def _start_ns(line, ev) -> float:
    return line.timestamp_ns + ev.offset_ps / 1000.0


def read_extra(path, device: str = "/device:TPU:0") -> Dict[str, List]:
    """``ops_named`` (name of each "XLA Ops" event, in order), ``scopes``
    and ``program_spans`` of the ``.xplane.pb`` at ``path``."""
    space = _XSPACE.FromString(Path(path).read_bytes())
    names, scopes, prog = [], [], []
    for plane in space.planes:
        if plane.name == device:
            tf_op = {e.key for e in plane.stat_metadata
                     if e.value.name == "tf_op"}
            md = {e.key: e.value for e in plane.event_metadata}

            def stack(m):
                for st in m.stats:
                    if st.metadata_id in tf_op:
                        return st.str_value
                return ""
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        m = md[ev.metadata_id]
                        names.append(m.name)
                        scopes.append(stack(m))
        elif plane.name.startswith("/host:"):
            md = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    n = md.get(ev.metadata_id, "")
                    if n.startswith(PROGRAM_PREFIX):
                        prog.append((n, _start_ns(line, ev),
                                     ev.duration_ps / 1000.0))
    return {"ops_named": names, "scopes": scopes, "program_spans": prog}


def load(path, device: str = "/device:TPU:0") -> Dict[str, List]:
    """The benchmark's trace of ``path`` plus ``scopes`` and
    ``program_spans`` (module docstring)."""
    trace = trace_reduce.load_xplane(path, device)
    extra = read_extra(path, device)
    if extra["ops_named"] != [n for n, _, _ in trace["ops"]]:
        raise ValueError(f"{path}: the XLA Ops events read here and by "
                         f"jax.profiler differ; scopes cannot be matched")
    trace["scopes"] = extra["scopes"]
    trace["program_spans"] = extra["program_spans"]
    return trace


def save_json(trace: Dict[str, List], path) -> None:
    Path(path).write_text(json.dumps(
        {k: list(v) if k == "scopes" else [list(e) for e in v]
         for k, v in trace.items()}))


def load_json(path) -> Dict[str, List]:
    d = json.loads(Path(path).read_text())
    out = {k: [tuple(e) for e in d[k]]
           for k in ("ops", "modules", "spans", "program_spans")}
    out["scopes"] = list(d["scopes"])
    return out
