"""In-memory span recorder for the serve path.

``Telemetry(enabled)`` hands out spans: ``with tel.span(name, **attrs)``
records the name, start and end on ``time.perf_counter_ns``, the span
open around it on the same thread (its parent), the invocation it
belongs to and its attributes.  A span with no parent opens a new
invocation id (0, 1, ... in the order they open); its children share
it, and a span given ``inv=`` joins that invocation instead — the async
executor's ``finalize`` runs outside the dispatch that launched it.

When enabled, every span is also a ``jax.profiler.TraceAnnotation`` of
the same name, so the spans land on the device trace's own clock
whenever a profiler runs.  When disabled (the default everywhere)
``span`` returns one shared no-op context after one attribute check.

Spans stay in memory, at most ``MAX_SPANS`` of them (later ones are only
counted in ``dropped``), and are written out at the end:
:meth:`Telemetry.invocations` sums them per invocation, and
:meth:`Telemetry.write_chrome_trace` writes Chrome trace-event JSON,
which Perfetto loads beside a profiler trace.

The serve path's spans (``tangram.`` prefix):

* ``tangram.engine.dispatch`` — the engine's call into the executor;
  attributes ``reason``, ``patches``, ``canvases``, ``t_fire`` (the engine
  instant the invoker fired at), ``t_launch`` (engine time when submit
  began) and ``arrivals`` (each patch's arrival instant);
* ``tangram.executor.launch`` with ``gather`` (crop loop), ``pack`` (the
  host stitch onto canvases, or the fused path's slot packing; attribute
  ``layout``, ``canvas`` or ``slots``), ``put`` (host-to-device copies)
  and ``enqueue`` (jit calls);
  attributes ``slot_pixels``, ``live_pixels``, ``bytes_to_device``;
* ``tangram.executor.finalize`` with ``sync`` (joining the device),
  ``fetch`` (device-to-host copies) and ``route`` (routing and the
  per-frame evidence copies); attribute ``bytes_from_device``.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, Optional

#: spans kept in memory; a longer run counts the rest in ``dropped``
MAX_SPANS = 1 << 20


class _Off:
    """The disabled recorder's one span: does nothing."""

    inv = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("tel", "name", "inv", "attrs", "id", "parent", "t0",
                 "_note")

    def __init__(self, tel: "Telemetry", name: str, inv, attrs: dict):
        self.tel, self.name, self.inv, self.attrs = tel, name, inv, attrs

    def __enter__(self):
        tel = self.tel
        stack = tel._stack()
        up = stack[-1] if stack else None
        self.parent = up.id if up is not None else None
        if self.inv is None:
            self.inv = up.inv if up is not None else next(tel._invs)
        self.id = next(tel._ids)
        stack.append(self)
        self._note = tel._annotation(self.name)
        self._note.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._note.__exit__(*exc)
        tel = self.tel
        tel._stack().pop()
        tel._record((self.id, self.parent, self.inv, self.name, self.t0, t1,
                     threading.get_ident(), self.attrs))
        return False

    def set(self, **attrs):
        """Add attributes to the open span."""
        self.attrs.update(attrs)


class Telemetry:
    """Span recorder; see the module docstring.  ``spans`` holds tuples
    ``(id, parent, inv, name, t0_ns, t1_ns, thread, attrs)`` in the order
    the spans closed."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.spans: list = []
        self.dropped = 0
        self._ids = itertools.count()
        self._invs = itertools.count()
        self._local = threading.local()
        self._lock = threading.Lock()
        if enabled:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def span(self, name: str, inv: Optional[int] = None, **attrs):
        """A context manager timing ``name``; ``inv`` joins an invocation
        other than the enclosing span's."""
        if not self.enabled:
            return _OFF
        return _Span(self, name, inv, attrs)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, row: tuple):
        with self._lock:
            if len(self.spans) < MAX_SPANS:
                self.spans.append(row)
            else:
                self.dropped += 1

    def invocations(self) -> Dict[int, dict]:
        """Per invocation id: the seconds spent in each span, keyed by
        the name's last part plus ``_s`` (summed where a name repeats),
        and every span's attributes."""
        rows: Dict[int, dict] = {}
        for _id, _parent, inv, name, t0, t1, _thread, attrs in self.spans:
            row = rows.setdefault(inv, {})
            key = name.rsplit(".", 1)[-1] + "_s"
            row[key] = row.get(key, 0.0) + (t1 - t0) * 1e-9
            row.update(attrs)
        return rows

    def write_chrome_trace(self, path, counters: Optional[dict] = None):
        """Write the spans as Chrome trace-event JSON (complete events, in
        microseconds), with ``counters`` and the dropped-span count under
        ``otherData``."""
        pid = os.getpid()
        events = [{"name": name, "ph": "X", "ts": t0 / 1e3,
                   "dur": (t1 - t0) / 1e3, "pid": pid, "tid": thread,
                   "args": dict(attrs, inv=inv, id=sid, parent=parent)}
                  for sid, parent, inv, name, t0, t1, thread, attrs
                  in self.spans]
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "otherData": {"counters": dict(counters or {}),
                                     "dropped_spans": self.dropped}}, f)
