"""Process hooks that record what holds the host: compiles and collections.

:class:`HostHooks` installs two process-wide hooks that record into one
:class:`~repro.obs.tracer.Tracer`, on the track of the thread the work ran
on:

* a ``jax.monitoring`` duration listener — ``jax/trace`` (jaxpr tracing),
  ``jax/lower`` (MLIR lowering) and ``jax/compile`` (backend compile, a
  persistent-cache read included).  JAX reports a duration when the stage
  ends, so a span runs from ``now - seconds`` to ``now``.  Its args name
  the function (``fn``) and, where one is open on the thread, the phase or
  admission span it ran inside (``within``).  Tracing nests (a jitted
  callee is traced inside its caller), and a start rebuilt from a duration
  can miss its parent's by a microsecond, so these spans are recorded
  ``overlap_ok``: exempt from the stack check;
* a ``gc.callbacks`` hook — ``host/gc`` for every collection, with its
  ``generation``, ``within`` as above.

Both record through :meth:`~repro.obs.tracer.Tracer.post_span`, which
takes no lock: a collection can start on a thread that holds the tracer's
lock, and a hook that waited for it would wait forever.

A tracer has one :class:`HostHooks` (:meth:`HostHooks.of`), counted by its
users: a traced :class:`~repro.serving.TMServer` installs it in ``start()``
and removes it in ``stop()``, the hooks go in with the first user and out
with the last, so servers that share a tracer record each compile and
collection once.  An untraced server never installs them.
"""

from __future__ import annotations

import gc
import threading

__all__ = ["HostHooks", "JAX_SPANS"]

# jax.monitoring event -> span name
JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/compile",
}


# guards the one HostHooks of each tracer
_OF_LOCK = threading.Lock()


class HostHooks:
    """The compile listener and the collection hook, for one tracer."""

    def __init__(self, tracer):
        self.tracer = tracer
        self._users = 0
        self._lock = threading.Lock()   # install/uninstall only
        self._gc_start = 0.0

    @classmethod
    def of(cls, tracer) -> "HostHooks":
        """The hooks of ``tracer``, made on first use."""
        with _OF_LOCK:
            if tracer.host_hooks is None:
                tracer.host_hooks = cls(tracer)
            return tracer.host_hooks

    def _on_duration(self, event: str, secs: float, **kwargs) -> None:
        name = JAX_SPANS.get(event)
        if name is None:
            return
        tracer = self.tracer
        t = tracer._clock()
        args = {"fn": str(kwargs.get("fun_name", ""))}
        within = tracer.current()
        if within is not None:
            args["within"] = within
        tracer.post_span(name, threading.current_thread().name, t - secs, t,
                         overlap_ok=True, **args)

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections never overlap (the interpreter runs one at a time)
        if phase == "start":
            self._gc_start = self.tracer._clock()
            return
        tracer = self.tracer
        args = {"generation": info.get("generation")}
        within = tracer.current()
        if within is not None:
            args["within"] = within
        tracer.post_span("host/gc", threading.current_thread().name,
                         self._gc_start, tracer._clock(), **args)

    def install(self) -> "HostHooks":
        """Add a user; the first installs both hooks."""
        import jax
        with self._lock:
            self._users += 1
            if self._users == 1:
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                gc.callbacks.append(self._on_gc)
        return self

    def uninstall(self) -> None:
        """Drop a user; the last removes both hooks."""
        import jax
        with self._lock:
            if self._users == 0:
                return
            self._users -= 1
            if self._users == 0:
                jax.monitoring.unregister_event_duration_listener(
                    self._on_duration)
                gc.callbacks.remove(self._on_gc)

    @property
    def installed(self) -> bool:
        return self._users > 0
