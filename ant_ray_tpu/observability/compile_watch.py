"""Every compilation a ``jit:compile`` span: the ONE ``jax.monitoring``
listener of a process, installed by ``jax_utils.import_jax()``.

jax fires, on the thread that compiles, a time span for each of a
program's three phases — ``jaxpr_trace_duration`` (``fun_name`` the
function's own name; functions it calls fire theirs first, inside it),
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
(``fun_name`` = ``jit(<name>)``) — and between the last two the
persistent cache's verdict, which carries no name: it belongs to the
compile open on the thread that fires it.  One forced span a compiled
program comes of them:

    ``jit:compile``  ts = the start of its tracing, dur_s to the end of
    the backend compile or the cache's retrieval; attrs ``fun_name``,
    ``trace_s``, ``lower_s``, ``backend_s`` (0 on a hit), ``cache``
    (``hit`` / ``miss`` / ``off``), ``retrieval_s``

under the context current on that thread (a compile inside ``llm:init``
hangs under it, one inside a request under that request), else under a
trace id of the process's own.  Once the process's engine has completed
a step (:func:`mark_ready`) a span also says ``after_ready: true`` and
ONE warning line names the program and its seconds: the operator's
"which step recompiled".

Nothing here runs on a thread's steady state — jax fires these events
only when it compiles — and nothing here may raise into jax: every
callback is guarded whole.
"""

from __future__ import annotations

import logging
import threading

logger = logging.getLogger(__name__)

_PREFIX = "/jax/core/compile/"
_TRACE = _PREFIX + "jaxpr_trace_duration"
_LOWER = _PREFIX + "jaxpr_to_mlir_module_duration"
_BACKEND = _PREFIX + "backend_compile_duration"
_CACHE = "/jax/compilation_cache/"
_VERDICTS = {_CACHE + "cache_hits": "hit", _CACHE + "cache_misses": "miss"}
_RETRIEVAL = _CACHE + "cache_retrieval_time_sec"

_state = threading.local()      # .traced: name -> (start, seconds); .open
_installed = False
_ready = False
_process_ctx = None


def mark_ready() -> None:
    """The process's engine completed its first step: a compilation
    from here on is one a request waited for."""
    global _ready
    _ready = True


def _guard(fn):
    def guarded(*args, **kwargs):
        try:
            fn(*args, **kwargs)
        except Exception:  # noqa: BLE001 — never raise into jax
            logger.debug("compile listener failed", exc_info=True)
    return guarded


def _program(fun_name) -> str:
    name = str(fun_name or "")
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


@_guard
def _on_time_span(event: str, start: float, end: float, **kwargs) -> None:
    if not event.startswith(_PREFIX):
        return
    name = _program(kwargs.get("fun_name"))
    if event == _TRACE:
        traced = getattr(_state, "traced", None)
        if traced is None:
            traced = _state.traced = {}
        traced[name] = (start, end - start)
    elif event == _LOWER:
        traced = getattr(_state, "traced", None) or {}
        ts, trace_s = traced.get(name, (start, 0.0))
        traced.clear()              # what was traced inside it, too
        _state.open = {"fun_name": name, "ts": ts, "trace_s": trace_s,
                       "lower_s": end - start, "cache": "off",
                       "retrieval_s": 0.0}
    elif event == _BACKEND:
        compile_ = getattr(_state, "open", None)
        _state.open = None
        if compile_ is None or compile_["fun_name"] != name:
            # compiled from a lowering made earlier (``.lower()`` kept)
            compile_ = {"fun_name": name, "ts": start, "trace_s": 0.0,
                        "lower_s": 0.0, "cache": "off",
                        "retrieval_s": 0.0}
        _record(compile_, start, end)


@_guard
def _on_event(event: str, **kwargs) -> None:
    verdict = _VERDICTS.get(event)
    compile_ = getattr(_state, "open", None)
    if verdict is not None and compile_ is not None:
        compile_["cache"] = verdict


@_guard
def _on_duration(event: str, seconds: float, **kwargs) -> None:
    compile_ = getattr(_state, "open", None)
    if event == _RETRIEVAL and compile_ is not None:
        compile_["retrieval_s"] = seconds


def _record(compile_: dict, backend_start: float, end: float) -> None:
    from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

    global _process_ctx
    ts = compile_.pop("ts")
    hit = compile_["cache"] == "hit"
    if hit and not compile_["retrieval_s"]:
        compile_["retrieval_s"] = end - backend_start
    attrs = {**compile_,
             "backend_s": 0.0 if hit else end - backend_start}
    attrs = {k: round(v, 6) if isinstance(v, float) else v
             for k, v in attrs.items()}
    dur = end - ts
    if _ready:
        attrs["after_ready"] = True
        logger.warning(
            "jit:compile after the engine's first step: %s took %.3f s "
            "(trace %.3f, lower %.3f, backend %.3f, cache %s)",
            attrs["fun_name"], dur, attrs["trace_s"], attrs["lower_s"],
            attrs["backend_s"], attrs["cache"])
    ctx = tracing_plane.current()
    if ctx is None:
        if _process_ctx is None:
            _process_ctx = tracing_plane.mint(sampled=False)
        ctx = _process_ctx
    tracing_plane.record_span(ctx, "jit:compile", ts=ts, dur_s=dur,
                              attrs=attrs, forced=True)


def install(jax) -> None:
    """Register the listeners, once a process."""
    global _installed
    if _installed:
        return
    _installed = True
    monitoring = jax.monitoring
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
