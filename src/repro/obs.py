"""Spans and counters inside the program, kept in memory.

Recording is off by default.  Then :func:`span` hands back one shared
no-op context and :func:`count` and :func:`tag` return at once: an
instrumented call pays a function call and a ``with`` block, and nothing
is allocated or timed.

:func:`enable` starts a fresh store and turns recording on.  While on,
each span records its name, start and end (``time.perf_counter_ns``),
the index of the span it opened inside (its parent) and an optional tag
(:func:`tag`, e.g. ``"fail"``), in one flat array: five numbers per
span, no object per span.  Each span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
taken meanwhile shows it on the host plane, on the device ops' clock.
:func:`enable` also counts every executable JAX builds or loads from its
persistent cache (the ``/jax/core/compile/backend_compile_duration``
event) as the counter ``jax.compiles``.  :func:`disable` stops both.
:func:`snapshot` sums what was recorded per span name and per tag.

Spans in the program (each one's name says where it is):

* ``campaign.run``: a whole ``run_campaign`` call, the root of its spans;
* ``lanes.prepare``: the lane engine's set-up, the campaign's per-lane job
  copies and ``run_lanes`` up to the round loop;
* ``lanes.run``: the lane engine's round loop; its self time is the loop's
  own code, outside ``lanes.schedule`` and ``lanes.rate``;
* ``lanes.schedule``, ``lanes.rate``: one lane round's queue scan and
  placement, and its rate resolution;
* ``lanes.report``: the per-lane reports after the loop;
* ``rate.solve``: one ``phase_worst_loads`` call;
* ``place``: one strategy ``place`` call of the v1/v2 engines, tagged
  ``fail`` when it returns a ``PlacementFailure``;
* ``ocs.findclos``: the virtual-Clos search of OCS placement;
* ``ocs.rewire``: one ``RewirePlanner.ensure`` call, the circuit swaps
  that make a placement's channels, inside ``ocs.findclos`` or stage 2's
  ``place``;
* ``lanes.entries``: the lane engine's link-entry build of one placed
  job (routing its flows onto links, or a cache hit), inside
  ``lanes.schedule``.

Counters: ``ocs.candidates`` (leaf x spine factorisations the OCS search
tried), ``ocs.budget`` (port budgets the OCS search counted, one per
search, shared by its candidates), ``ocs.channels`` (channels the OCS
search asked the rewire planner for, one per ``add_channel`` call),
``jax.compiles``, and per job placed across servers by the lane engine or
v1/v2: ``flows.dp`` and ``flows.pp`` (its cross-leaf flows, source and
destination under different leafs, of the data-parallel allreduce and of
the pipeline stage boundaries) and
``groups.dp`` (its DP groups: one ring per (tp, pp) of a 3D layout).

Recording follows one thread: spans opened from several threads at once
nest wrongly.  Spans of process-pool workers are not recorded.  This
module imports JAX only inside :func:`enable`.
"""

from __future__ import annotations

from array import array
from contextlib import nullcontext
from time import perf_counter_ns as _now
from typing import Dict, List, Optional

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILES = "jax.compiles"


_NOOP = nullcontext()


# fields of one span's record in _Store.rec
_NAME, _PARENT, _START, _END, _TAG, _FIELDS = range(6)


class _Store:
    """Every span opened since :func:`enable`, in one flat array of
    ``_FIELDS`` int64 per span: the name's id in ``names``, the record
    offset of the span it opened inside (-1 at the top), start and end
    (ns; end is -1 while open) and the tag's id in ``names`` (-1
    untagged)."""

    def __init__(self, annotate) -> None:
        self.annotate = annotate
        self.ids: Dict[str, int] = {}
        self.names: List[str] = []
        self.rec = array("q")
        self.open: List[int] = []       # record offsets of open spans
        self.counters: Dict[str, int] = {}

    def intern(self, s: str) -> int:
        i = self.ids.get(s)
        if i is None:
            i = self.ids[s] = len(self.names)
            self.names.append(s)
        return i


class _Span:
    __slots__ = ("_st", "_name", "_ann")

    def __init__(self, st: _Store, name: str) -> None:
        self._st = st
        self._name = name

    def __enter__(self) -> None:
        st = self._st
        rec, opened = st.rec, st.open
        i = len(rec)
        rec.extend((st.intern(self._name), opened[-1] if opened else -1,
                    0, -1, -1))
        opened.append(i)
        self._ann = st.annotate(self._name)
        self._ann.__enter__()
        rec[i + _START] = _now()

    def __exit__(self, *exc) -> bool:
        t = _now()
        self._ann.__exit__(None, None, None)
        st = self._st
        st.rec[st.open.pop() + _END] = t
        return False


_active: Optional[_Store] = None    # the store recording, None when off
_store: Optional[_Store] = None     # the store snapshot() reads
_listener = None


def span(name: str):
    """A context that records one span named ``name`` while recording is
    on, and does nothing otherwise."""
    st = _active
    if st is None:
        return _NOOP
    return _Span(st, name)


def tag(value: str) -> None:
    """Tag the innermost open span with ``value``."""
    st = _active
    if st is not None and st.open:
        st.rec[st.open[-1] + _TAG] = st.intern(value)


def recording() -> bool:
    """Whether recording is on: callers skip work that only feeds a
    counter."""
    return _active is not None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    st = _active
    if st is not None:
        st.counters[name] = st.counters.get(name, 0) + n


def enable() -> None:
    """Clear the store and turn recording on (again)."""
    global _active, _store, _listener
    disable()
    import jax.monitoring
    from jax.profiler import TraceAnnotation

    st = _Store(TraceAnnotation)
    st.counters[COMPILES] = 0

    def on_duration(event: str, duration_secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            st.counters[COMPILES] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _active = _store = st
    _listener = on_duration


def disable() -> None:
    """Turn recording off.  What was recorded stays readable by
    :func:`snapshot` until the next :func:`enable`."""
    global _active, _listener
    if _listener is not None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(_listener)
        _listener = None
    _active = None


def snapshot() -> dict:
    """Sums of what the store holds::

        {"spans": {name: {"count": n, "total_s": s, "self_s": s,
                          "tags": {tag: {"count", "total_s", "self_s"}}}},
         "counters": {name: n}}

    ``self_s`` is ``total_s`` less the time covered by the spans opened
    directly inside.  Spans still open are left out."""
    import numpy as np

    st = _store
    if st is None:
        return {"spans": {}, "counters": {}}
    rec = np.array(st.rec, dtype=np.int64).reshape(-1, _FIELDS)
    name, start, end, tags = (rec[:, k] for k in (_NAME, _START, _END, _TAG))
    parent = np.where(rec[:, _PARENT] >= 0, rec[:, _PARENT] // _FIELDS, -1)
    n = len(rec)
    done = end >= 0
    dur = np.where(done, end - start, 0)
    covered = np.zeros(n, dtype=np.int64)
    kids = done & (parent >= 0)
    np.add.at(covered, parent[kids], dur[kids])
    own = dur - covered

    def sums(mask) -> dict:
        return {"count": int(mask.sum()),
                "total_s": float(dur[mask].sum()) / 1e9,
                "self_s": float(own[mask].sum()) / 1e9}

    spans = {}
    for i in np.unique(name[done]):
        mine = done & (name == i)
        row = sums(mine)
        row["tags"] = {st.names[t]: sums(mine & (tags == t))
                       for t in np.unique(tags[mine]) if t >= 0}
        spans[st.names[i]] = row
    return {"spans": spans, "counters": dict(st.counters)}
