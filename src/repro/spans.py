"""Program spans on the profiler's clock, and compile counts by span.

``span(name)`` enters a ``jax.profiler.TraceAnnotation``: while a profiler
trace is being taken, the span lands on the trace's ``/host:CPU`` plane, on
the same clock as the device planes, so device idle time can be put down to
what the program was doing.  It also keeps ``name`` on a per-thread stack of
open spans, which :class:`CompileCounter` reads.  With no profiler running a
span costs about a microsecond.

The serving engine opens the spans in :data:`NAMES`, and no others:

  engine.admit       one per admitted request: prefill arguments to the
                     device, prefill and slot-insert dispatch, argmax and
                     the ``device_get`` of the first token
  engine.decode      the decode step's host inputs (positions,
                     temperatures, key split) to its dispatch
  engine.token_sync  the ``device_get`` of the step's tokens and the
                     per-slot bookkeeping after it
"""
from __future__ import annotations

import collections
import threading
from typing import Dict, List

import jax

ADMIT = "engine.admit"
DECODE = "engine.decode"
TOKEN_SYNC = "engine.token_sync"
NAMES = (ADMIT, DECODE, TOKEN_SYNC)
OTHER = "other"          # compile events under no open span

# JAX 0.9's compile events.  A persistent-cache restore happens inside the
# backend-compile event, so "compile" seconds include "restore" seconds.
EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile",
          "/jax/compilation_cache/cache_retrieval_time_sec": "restore"}


class _Open(threading.local):
    def __init__(self):
        self.names: List[str] = []


_open = _Open()


class span:
    """``with span(name):`` a program span (see the module docstring)."""
    __slots__ = ("name", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        _open.names.append(self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        _open.names.pop()


class CompileCounter:
    """While open, counts JAX's compile events under the innermost span open
    on the compiling thread (``"other"`` under none) and under the program
    compiled.

    ``by_span[span][kind]`` and ``by_program[program][kind]`` are
    ``[count, seconds]`` for ``kind`` in ``lower`` (jaxpr to MLIR),
    ``compile`` (backend compile, or a restore from the persistent compile
    cache) and ``restore`` (the cache reads among the compiles; JAX names
    no program for them, so ``by_program`` counts none)."""

    def __init__(self):
        def kinds():
            return {k: [0, 0.0] for k in EVENTS.values()}
        self.by_span: Dict[str, Dict[str, List]] = \
            collections.defaultdict(kinds)
        self.by_program: Dict[str, Dict[str, List]] = \
            collections.defaultdict(kinds)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        kind = EVENTS.get(event)
        if kind is None:
            return
        names = _open.names
        for key, table in ((names[-1] if names else OTHER, self.by_span),
                           (kw.get("fun_name"), self.by_program)):
            if key is not None:
                entry = table[key][kind]
                entry[0] += 1
                entry[1] += duration

    def seconds(self) -> float:
        """Seconds spent lowering and compiling or restoring, in all."""
        return sum(_seconds(k) for k in self.by_span.values())

    def summary(self) -> str:
        """One line: compiles (of which restored) and seconds by span, then
        compiles and seconds by program."""
        by_span = ", ".join(
            f"{name} {k['compile'][0]} ({k['restore'][0]} restored) "
            f"{_seconds(k):.2f} s" for name, k in sorted(self.by_span.items()))
        by_program = ", ".join(
            f"{name} {k['compile'][0]} {_seconds(k):.2f} s"
            for name, k in sorted(self.by_program.items()))
        return (f"compiles by span: {by_span or 'none'}; by program: "
                f"{by_program or 'none'}")

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _seconds(kinds: Dict[str, List]) -> float:
    return kinds["lower"][1] + kinds["compile"][1]

