"""Device self time by the program's named scopes, for the per-scope
metrics of a traced training run.

The program names the training step's layers with ``jax.named_scope``
(``SCOPES``); each device op carries its scope in its ``op_name`` path,
and the op's gradient carries it under ``transpose(``.  The device's op
events name that path in the ``tf_op`` stat of their event metadata
(``jit(train_step)/jvp(seqrec.encode)/mul:``), which
``jax.profiler.ProfileData`` does not expose, so ``op_paths`` reads the
xplane's protobuf for that stat alone.  ``of_run`` pairs those paths
with the op events the harness already reduced (``trace.Reduced``), in
the order both read them, from the xplane the traced window wrote.

An op's self time is the part of its interval in which it is the
innermost op running: a ``while`` op's events contain its body's.  Self
times split the busy time among the scopes, each op to the innermost
scope on its path; ops on none of them count as unscoped.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from chip import harness, trace

# the training step's layers, as the program names them (jax.named_scope)
SCOPES = ("seqrec.encode", "item_emb.logits", "seqrec.loss_head",
          "train.optimizer")
BACKWARD = "transpose("
PATH_STAT = "tf_op"
TRACE_DIR = ".bench_traces"


# ------------------------------------------------- op_name from the file
#
# Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
# XPlane.name 2, lines 3, event_metadata 4 and stat_metadata 5 (maps:
# key 1, value 2); XLine.name 2, events 4; XEvent.metadata_id 1;
# XEventMetadata.id 1, name 2, stats 5; XStat.metadata_id 1, str_value
# 5, ref_value 7 (the id of a stat metadata whose name is the string);
# XStatMetadata.id 1, name 2.

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of the message in ``b[lo:hi]``: a varint,
    or the (start, end) of a length-delimited value; fixed-width values
    are skipped (None)."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def op_paths(path: str) -> Dict[str, List[tuple]]:
    """Per device plane, (name, op_name path) of each event of its
    ``XLA Ops`` lines, in the order ``ProfileData`` reads them; the path
    is "" where the op has none."""
    with open(path, "rb") as f:
        b = f.read()
    out: Dict[str, List[tuple]] = {}
    for field, plane in _fields(b, 0, len(b)):
        if field != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for f, v in _fields(b, *plane):
            if f == 2:
                name = _text(b, v)
            elif f == 3:
                lines.append(v)
            elif f in (4, 5):
                value = dict(_fields(b, *v)).get(2)
                if value is None:
                    continue
                if f == 4:
                    metas.append(value)
                else:
                    m = dict(_fields(b, *value))
                    stat_names[m.get(1, 0)] = _text(b, m[2]) if 2 in m \
                        else ""
        if not name.startswith("/device:"):
            continue
        ops = {}
        for value in metas:
            m_id, m_name, op = 0, "", ""
            for f, v in _fields(b, *value):
                if f == 1:
                    m_id = v
                elif f == 2:
                    m_name = _text(b, v)
                elif f == 5:
                    stat = dict(_fields(b, *v))
                    if stat_names.get(stat.get(1)) != PATH_STAT:
                        continue
                    if 5 in stat:
                        op = _text(b, stat[5])
                    elif 7 in stat:
                        op = stat_names.get(stat[7], "")
            # "<op_name>:<op_type>", the type empty for XLA's ops
            ops[m_id] = (m_name, op.rsplit(":", 1)[0])
        for line in lines:
            fl = list(_fields(b, *line))
            if not any(f == 2 and _text(b, v) == trace.DEVICE_LINE
                       for f, v in fl):
                continue
            for f, ev in fl:
                if f == 4:
                    m_id = dict(_fields(b, *ev)).get(1, 0)
                    out.setdefault(name, []).append(ops.get(m_id, ("", "")))
    return out


# ------------------------------------------------------------ reduction

def layer_of(path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an op_name path (the one named
    last), or None."""
    best, at = None, -1
    for scope in SCOPES:
        i = path.rfind(scope)
        if i > at:
            best, at = scope, i
    return best


def self_ns(ops, w: trace.Interval) -> List[int]:
    """Per op of ``ops`` [(name, start, end)], the ns of window ``w`` in
    which it is the innermost op running: of the ops open at an
    instant, the one that started last (of equal starts, the one that
    ends first).  For events nested on one device line, as a ``while``
    op and its body's events are, that is an op's duration less the
    part its nested events cover; the ops' sum is the busy time."""
    own = [0] * len(ops)
    marks = []
    for i, (_, s, e) in enumerate(ops):
        a, b = max(s, w[0]), min(e, w[1])
        if a < b:
            marks += [(a, 1, i), (b, 0, i)]
    marks.sort()                         # at one instant, ends first
    active: Dict[int, tuple] = {}
    last = None
    for t, start, i in marks:
        if active and t > last:
            own[max(active, key=active.__getitem__)] += t - last
        last = t
        if start:
            active[i] = (ops[i][1], -ops[i][2])
        else:
            del active[i]
    return own


class Scoped:
    """A reduced trace's ops with their op_name paths ("" where none)."""

    def __init__(self, reduced: trace.Reduced, paths: Dict[str, List[str]]):
        self.reduced = reduced
        self.paths = {d: paths.get(d, [""] * len(ops))
                      for d, ops in reduced.ops.items()}
        self._own: Dict[str, List[int]] = {}

    def scoped(self) -> bool:
        """Whether any op carries one of ``SCOPES`` on its path: a
        program without them leaves the per-scope metrics unread."""
        return any(layer_of(p) for ps in self.paths.values() for p in ps)

    def self_seconds(self, scope: Optional[str],
                     backward: Optional[bool] = None) -> float:
        """Self device seconds in the window, averaged over devices, of
        the ops whose innermost scope of ``SCOPES`` is ``scope`` (None:
        ops on none of them).  ``backward`` True keeps the ops under
        ``transpose(`` (the gradient), False the others, None both."""
        r = self.reduced
        total = 0
        for d, ops in r.ops.items():
            if d not in self._own:
                self._own[d] = self_ns(ops, r.window)
            for path, own in zip(self.paths[d], self._own[d]):
                if layer_of(path) == scope and (
                        backward is None or (BACKWARD in path) == backward):
                    total += own
        return total / len(r.ops) / 1e9 if r.ops else 0.0

    def pct(self, scope: Optional[str],
            backward: Optional[bool] = None) -> Optional[float]:
        """``self_seconds`` as a percentage of the window; None where no
        op of the trace carries a scope."""
        if not self.scoped():
            return None
        return 100.0 * self.self_seconds(scope, backward) / \
            self.reduced.window_s


def from_events(ev: dict) -> Scoped:
    """A ``Scoped`` of events kept with a fourth field, the op_name path
    (the form of the tests' recorded scoped trace); three-field events
    have no paths."""
    three = {"devices": {d: [x[:3] for x in evs]
                         for d, evs in ev["devices"].items()},
             "spans": ev["spans"]}
    paths = {d: [x[3] if len(x) > 3 else "" for x in evs]
             for d, evs in ev["devices"].items()}
    return Scoped(trace.Reduced(three), paths)


def trace_file(name: str, root: str = harness.ROOT) -> Optional[str]:
    """The xplane the traced window of cell ``name`` wrote, or None."""
    files = glob.glob(os.path.join(root, TRACE_DIR, name, "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def of_run(run, root: str = harness.ROOT) -> Optional[Scoped]:
    """The run's ``Scoped``, read once from its xplane; None for an
    untraced run, or where the file is missing or is not the one the
    run's reduced trace came from (other op counts)."""
    if run.trace is None:
        return None
    if not hasattr(run, "scoped"):
        run.scoped = None
        path = trace_file(run.name, root)
        if path is not None:
            named = op_paths(path)
            paths = {}
            for d, ops in run.trace.ops.items():
                got = named.get(d, [])
                if len(got) != len(ops):
                    return None
                paths[d] = [p if n == op[0] else ""
                            for (n, p), op in zip(got, ops)]
            run.scoped = Scoped(run.trace, paths)
    return run.scoped


def pct(run, scope: Optional[str],
        backward: Optional[bool] = None) -> Optional[float]:
    """A per-scope metric of ``run``: ``Scoped.pct``, or None where the
    run has no scoped trace."""
    s = of_run(run)
    return None if s is None else s.pct(scope, backward)
