"""Device self time by the program's named scopes, from a traced run's
``.xplane.pb``.

The program wraps its layers in ``jax.named_scope`` (:data:`SCOPES`).
A scope changes only the ``op_name`` metadata of the compiled program,
and the profiler keeps that metadata: each device plane's event metadata
gives every XLA operation a ``tf_op`` stat, its JAX name stack
(``jit(f)/transpose(jvp(attn))/dot_general:``), and a ``program_id``.
``ProfileData`` does not show event metadata, so :func:`op_scopes` reads
the protobuf itself. Operation names recur across programs (the
overlapped step's ``fusion.3`` is in every chunk program), so an
operation's scope is looked up by the program it ran in: the event of
its plane's ``XLA Modules`` line (``jit_<name>(<program id>)``) that it
starts in.

Times are in nanoseconds, as the trace gives them.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace

#: the program's named scopes
SCOPES = ("embed", "layers", "attn", "mlp", "head", "xent", "adamw")
_MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\((\d+)\))?$")
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost of SCOPES in a JAX name stack (``jit(f)/transpose(
    jvp(attn))/dot_general:`` gives ``attn``), or None."""
    found = None
    for part in tf_op.split(";")[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in SCOPES:
            found = part
    return found


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: a
    varint as an int, a length-delimited value as its (lo, hi) offsets,
    a fixed-width value as its bytes."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def op_scopes(raw: bytes) -> Dict[Tuple[int, str], str]:
    """(program id, operation name) -> scope, for every operation of a
    serialized trace (an ``XSpace``) whose event metadata on a TPU plane
    carries a ``tf_op`` holding one of SCOPES.

    The fields read: XSpace.planes = 1; XPlane.name = 2, .event_metadata
    = 4 and .stat_metadata = 5 (map entries: key = 1, value = 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .uint64_value = 3, .int64_value = 4,
    .str_value = 5, .ref_value = 7 (the id of a stat metadata whose name
    is the string)."""
    buf = memoryview(raw)
    text = lambda v: bytes(buf[v[0]:v[1]]).decode("utf-8", "replace")
    out: Dict[Tuple[int, str], str] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = text(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                entry = dict(_fields(buf, *v))
                if 2 in entry:
                    sm = dict(_fields(buf, *entry[2]))
                    stat_names[sm.get(1, 0)] = text(sm[2]) if 2 in sm else ""
        if not _DEVICE.match(name):
            continue
        for v in metas:
            meta = dict(_fields(buf, *v)).get(2)
            if meta is None:
                continue
            op, pid, tf_op = None, None, None
            for h, x in _fields(buf, *meta):
                if h == 2:
                    op = text(x)
                elif h == 5:
                    st = dict(_fields(buf, *x))
                    kind = stat_names.get(st.get(1, 0))
                    if kind == "program_id":
                        pid = st.get(3, st.get(4))
                    elif kind == "tf_op":
                        tf_op = (text(st[5]) if 5 in st
                                 else stat_names.get(st.get(7, 0)))
            scope = scope_of(tf_op) if tf_op else None
            if op is not None and scope is not None:
                out[(pid, op)] = scope
    return out


def device_programs(profile) -> Dict[int, List[Tuple[str, Optional[int],
                                                     float, float]]]:
    """Per chip, each executed program as (name, program id, start, end),
    in order of start: ``jit_fwd(123)`` gives ``("fwd", 123, ...)``."""
    out: Dict[int, List] = {}
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if not m:
            continue
        runs = []
        for line in plane.lines:
            if line.name != _MODULES_LINE:
                continue
            for ev in line.events:
                prog, pid = _MODULE.match(ev.name).groups()
                runs.append((prog, int(pid) if pid else None,
                             float(ev.start_ns), float(ev.end_ns)))
        out[int(m.group(1))] = sorted(runs, key=lambda r: r[2])
    return out


def self_times(profile, scopes: Dict[Tuple[int, str], str],
               window: Tuple[float, float]
               ) -> Dict[int, Dict[Tuple[Optional[str], Optional[str]],
                                   float]]:
    """Per chip, device self time in the window (``trace.self_times``'s
    rule) by (program, scope): each operation's program is the run it
    starts in, its scope that program's entry for it in ``scopes`` (None
    where it has none)."""
    lo, hi = window
    runs = device_programs(profile)
    out = {}
    for dev, (ops, _) in trace.device_ops(profile).items():
        progs = runs.get(dev, [])
        named, j = [], 0
        for name, s, e in sorted(ops, key=lambda t: t[1]):
            while j < len(progs) and progs[j][3] <= s:
                j += 1
            prog, pid = ((progs[j][0], progs[j][1])
                         if j < len(progs) and progs[j][2] <= s
                         else (None, None))
            s, e = max(s, lo), min(e, hi)
            if e > s:
                named.append(((name, prog, scopes.get((pid, name))), s, e))
        by: Dict[Tuple[Optional[str], Optional[str]], float] = {}
        for (_, prog, scope), t in trace.self_times(named).items():
            by[(prog, scope)] = by.get((prog, scope), 0.0) + t
        out[dev] = by
    return out


def load(path: str):
    """The trace at ``path``: its ``ProfileData`` and :func:`op_scopes`."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return ProfileData.from_serialized_xspace(raw), op_scopes(raw)


def scope_ms_per_step(ctx, scope: str) -> Optional[float]:
    """Device self time per step under ``scope`` in the traced window, in
    ms, averaged over the chips; None where no operation carries it. The
    trace is ``ctx.xplane`` where given, else the traced run's own."""
    s = ctx.summary
    if s is None or ctx.steps <= 0:
        return None
    path = getattr(ctx, "xplane", None)
    if path is None:
        from benchmarks.chip import harness
        path = trace.find_xplane(str(harness.TRACE_DIR))
    per_chip = self_times(*load(path), s.window)
    got = [sum(t for (_, sc), t in by.items() if sc == scope)
           for by in per_chip.values()]
    if not any(got):
        return None
    return sum(got) / len(got) / ctx.steps * 1e-6
