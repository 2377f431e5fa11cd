"""Program spans and counters, on the profiler's clock.

A span is written twice: as a ``jax.profiler.TraceAnnotation`` under its
bare name, so that a device trace holds it on the device's clock, and as a
record in memory on ``time.perf_counter``: ``(name, t0, t1, parent, attrs)``,
where ``parent`` is the index of the innermost span open when it began
(None at the top) and ``attrs`` a dict such as ``{"req": 3}``. A counter
adds up integers by name. While recording is on, every compile event JAX
reports (tracing to a jaxpr, lowering to MLIR, backend compile) is kept as
``(event, t0, t1, fun_name, span)``, with ``span`` the index of the
innermost span open when the event ended.

Recording is off until ``enable()``, and free while off: each call site
tests ``ON`` first, so no clock is read, nothing is allocated and no
profiler API is called::

    with obs.span("serve.merge", req=rid) if obs.ON else obs.OFF:
        ...
    if obs.ON:
        obs.count("serve.host_syncs", n)

``drain()`` turns recording off and hands over everything recorded. The
state is the process's, like the profiler the spans are written to.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import jax

#: recording switch; call sites test it before anything else
ON = False
#: the context a call site enters while ``ON`` is false
OFF = contextlib.nullcontext()

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

_spans: List[list] = []  # [name, t0, t1, parent, attrs], in start order
_open: List[int] = []  # indices of the open spans, innermost last
_counters: Dict[str, int] = {}
_compiles: List[tuple] = []
_listening = False


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the enclosed block as span ``name`` with ``attrs``."""
    rec = [name, 0.0, 0.0, _open[-1] if _open else None, attrs]
    with jax.profiler.TraceAnnotation(name):
        _open.append(len(_spans))
        _spans.append(rec)
        rec[1] = time.perf_counter()
        try:
            yield
        finally:
            rec[2] = time.perf_counter()
            _open.pop()


def count(name: str, n: int = 1) -> None:
    _counters[name] = _counters.get(name, 0) + n


def _on_compile(event: str, start: float, end: float, fun_name: str = "", **_) -> None:
    # JAX stamps the event on ``time.time``; it ends as this listener runs,
    # so its end is now on ``perf_counter`` and its start that less its length
    if ON and event in COMPILE_EVENTS:
        t1 = time.perf_counter()
        _compiles.append((event, t1 - (end - start), t1, fun_name,
                          _open[-1] if _open else None))


def enable() -> None:
    """Start recording (the compile listener is registered the first time)."""
    global ON, _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_compile)
        _listening = True
    ON = True


def drain() -> dict:
    """Stop recording; return and clear the spans, counters and compile
    events recorded since ``enable()``. No span may be open."""
    global ON
    if _open:
        raise RuntimeError(f"drain() inside open span {_spans[_open[-1]][0]!r}")
    ON = False
    out = {"spans": [tuple(r) for r in _spans], "counters": dict(_counters),
           "compiles": list(_compiles)}
    _spans.clear()
    _counters.clear()
    _compiles.clear()
    return out
