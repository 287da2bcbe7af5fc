"""Device time of the train step's layers, read from the profiler trace by
the name scopes the program opens (``jax.named_scope``).

Each op of a device's ``XLA Ops`` line points at its event metadata, whose
``tf_op`` stat is the HLO ``op_name``: JAX's name stack, such as
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/moe/
experts/dot_general:``.  ``jax.profiler.ProfileData`` does not expose event
metadata, so this module decodes the ``.xplane.pb`` itself, with message
classes built here from the field numbers of
``tsl/profiler/protobuf/xplane.proto`` (no TensorFlow or xprof import).

A scope such as ``moe/experts`` matches an op whose name stack holds the
segments ``moe`` and ``experts`` next to each other, as whole segments:
forward ops, the recomputed ones (``rematted_computation/...``) and the
backward ones (``transpose(jvp(...))/checkpoint/...``) alike.  A scope's
time is the union of its ops' intervals, clipped to the traced window,
averaged over the devices and divided by the traced steps.  The trace is
decoded once per run; the per-layer readers share the result through
``run.counters``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as trace_lib

# the scopes the program opens (models/, optim/); a sub-scope after its
# parent
SCOPES = ("embed", "attn", "mlp", "moe", "moe/router", "moe/dispatch",
          "moe/experts", "moe/combine", "moe/stats", "head", "optimizer")
# the outermost ones: an op belongs to the first of these on its name stack,
# or to none ("unscoped": norms, residual adds, the layer loop's slicing)
TOP = ("embed", "attn", "mlp", "moe", "head", "optimizer")
_CACHE_KEY = "scope_ms_per_step"


@dataclasses.dataclass(frozen=True)
class Op:
    start_ns: int
    end_ns: int
    tf_op: str
    source: str
    name: str


# ------------------------------------------------------------- the decoder
# message -> (field, number, scalar type or message name, repeated); the fields
# read here, by their numbers in xplane.proto.  A map<int64, M> is on the
# wire a repeated message of key = 1 and value = 2.
_MESSAGES = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False), ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "string", False)],
}
_PACKAGE = "bench_scopes"


@functools.cache
def _xspace():
    """The ``XSpace`` message class, built in a private descriptor pool."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    field = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_scopes_xplane.proto", package=_PACKAGE, syntax="proto3")
    for msg, fields in _MESSAGES.items():
        m = f.message_type.add(name=msg)
        for name, number, kind, repeated in fields:
            fd = m.field.add(name=name, number=number, label=(
                field.LABEL_REPEATED if repeated else field.LABEL_OPTIONAL))
            if kind in _MESSAGES:
                fd.type = field.TYPE_MESSAGE
                fd.type_name = f".{_PACKAGE}.{kind}"
            else:
                fd.type = getattr(field, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def decode(path, device_ids: Optional[Sequence[int]] = None
           ) -> Dict[int, List[Op]]:
    """The op events of each device plane in the trace at ``path`` (a trace
    directory or an xplane file), with their ``tf_op`` and ``source``.
    Times are whole nanoseconds on the trace's clock, truncated as
    ``ProfileData`` truncates them, so that busy time agrees with
    ``trace.reduce``."""
    space = _xspace()()
    space.ParseFromString(Path(trace_lib.find_xplane(path)).read_bytes())
    wanted = None if device_ids is None else {int(d) for d in device_ids}
    out: Dict[int, List[Op]] = {}
    for plane in space.planes:
        m = trace_lib._DEVICE_PLANE.match(plane.name)
        if not m or (wanted is not None and int(m.group(1)) not in wanted):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            stats = {}
            for s in e.value.stats:
                key = stat_names.get(s.metadata_id)
                if key in ("tf_op", "source"):
                    # a string stat is held inline or interned as the name
                    # of a stat metadata entry
                    stats[key] = s.str_value or stat_names.get(s.ref_value, "")
            meta[e.key] = (stats.get("tf_op", ""), stats.get("source", ""),
                           e.value.name)
        ops = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != trace_lib.OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps // 1000
                tf_op, source, name = meta.get(ev.metadata_id, ("", "", ""))
                ops.append(Op(start, start + ev.duration_ps // 1000, tf_op,
                              source, name))
    return out


# ------------------------------------------------------- matching by scope
_WRAPPER = re.compile(r"[\w.\-]+\(([^()]*)\)")


def name_path(tf_op: str) -> Tuple[str, ...]:
    """The name-scope segments of an op's ``tf_op``: of the names XLA joined
    with ``;`` the first, transformation wrappers taken off
    (``transpose(jvp(head))`` -> ``head``, ``jvp()`` -> nothing), and the
    op's primitive, the last segment, left out."""
    name = tf_op.split(";")[0]
    while True:
        unwrapped = _WRAPPER.sub(r"\1", name)
        if unwrapped == name:
            break
        name = unwrapped
    segments = [s for s in name.split("/") if s]
    return tuple(segments[:-1])


def in_scope(path: Tuple[str, ...], scope: str) -> bool:
    """True when the segments of ``scope`` stand next to each other in
    ``path``, as whole segments."""
    want = tuple(scope.split("/"))
    n = len(want)
    return any(path[i:i + n] == want for i in range(len(path) - n + 1))


def top_scope(path: Tuple[str, ...]) -> Optional[str]:
    """The outermost of ``TOP`` on ``path``; ``None`` for an unscoped op."""
    for segment in path:
        if segment in TOP:
            return segment
    return None


# --------------------------------------------------------------- the split
@dataclasses.dataclass
class Split:
    """Milliseconds per traced step, averaged over the devices."""

    scopes: Dict[str, Optional[float]]  # None where no op matched
    top: Dict[str, float]               # each op under its outermost scope
    unscoped: float
    unscoped_sources: List[Tuple[str, float]]   # the largest, by source
    busy: float


def split(ops: Dict[int, List[Op]], window: Tuple[float, float],
          steps: int) -> Split:
    lo, hi = window
    n_dev = max(len(ops), 1)
    # tf_op -> (the scopes it is in, its outermost scope)
    seen: Dict[str, Tuple[Tuple[str, ...], Optional[str]]] = {}
    scopes = dict.fromkeys(SCOPES, 0.0)
    matched = set()
    top = dict.fromkeys(TOP, 0.0)
    unscoped = busy = 0.0
    by_source: Dict[str, float] = collections.defaultdict(float)
    per_ms = 1e3 / (n_dev * steps)
    for dev_ops in ops.values():
        in_scopes = collections.defaultdict(list)
        in_top = collections.defaultdict(list)
        rest = collections.defaultdict(list)
        for op in dev_ops:
            if op.end_ns <= lo or op.start_ns >= hi:
                continue
            iv = (op.start_ns, op.end_ns)
            if op.tf_op not in seen:
                path = name_path(op.tf_op)
                seen[op.tf_op] = (tuple(s for s in SCOPES
                                        if in_scope(path, s)),
                                  top_scope(path))
            names, t = seen[op.tf_op]
            for s in names:
                in_scopes[s].append(iv)
            if t is None:
                rest[op.source or op.tf_op or
                     trace_lib.short_name(op.name)].append(iv)
            else:
                in_top[t].append(iv)
        for s, ivs in in_scopes.items():
            matched.add(s)
            scopes[s] += trace_lib.union_seconds(ivs, lo, hi) * per_ms
        for t, ivs in in_top.items():
            top[t] += trace_lib.union_seconds(ivs, lo, hi) * per_ms
        for src, ivs in rest.items():
            ms = trace_lib.union_seconds(ivs, lo, hi) * per_ms
            by_source[src] += ms
            unscoped += ms
        busy += trace_lib.union_seconds(
            [(op.start_ns, op.end_ns) for op in dev_ops], lo, hi) * per_ms
    largest = sorted(by_source.items(), key=lambda kv: -kv[1])[:5]
    return Split({s: (v if s in matched else None) for s, v in scopes.items()},
                 top, unscoped, largest, busy)


def _log(run, sp: Split, seconds: float, n_ops: int) -> None:
    root = str(run.root) + "/"
    run.log(f"[scopes] decoded the trace in {seconds:.3f} s: {n_ops} op "
            f"events, ms per traced step averaged over the devices")
    for s, v in sp.scopes.items():
        run.log(f"[scopes] {s} {'no op' if v is None else f'{v:.4f} ms'}")
    sources = ", ".join(f"{src.replace(root, '')} {ms:.4f} ms"
                        for src, ms in sp.unscoped_sources)
    run.log(f"[scopes] unscoped {sp.unscoped:.4f} ms; largest by source: "
            f"{sources or 'none'}")
    total = sum(sp.top.values()) + sp.unscoped
    run.log(f"[scopes] {' + '.join(TOP)} + unscoped = {total:.4f} ms; busy "
            f"{sp.busy:.4f} ms")


def ms_per_step(run, trace, scope: str) -> Optional[float]:
    """Device milliseconds a traced step spends under ``scope``; ``None``
    with no trace, no traced step or no op under the scope."""
    if _CACHE_KEY not in run.counters:
        sp = None
        steps = run.counters.get("traced_steps", 0)
        if (trace is not None and trace.device_ids and steps
                and run.trace_path is not None):
            t0 = time.perf_counter()
            ops = decode(run.trace_path, trace.device_ids)
            sp = split(ops, trace.window, steps)
            _log(run, sp, time.perf_counter() - t0,
                 sum(len(v) for v in ops.values()))
        run.counters[_CACHE_KEY] = sp
    sp = run.counters[_CACHE_KEY]
    return None if sp is None else sp.scopes.get(scope)
