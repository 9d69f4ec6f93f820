"""What one run records: host spans, compilations, and the run's state.

Host spans are named ``bench.<what>``; in a traced run they go into the
profiler's trace as ``TraceAnnotation``s, so the trace reduction can name
each idle gap of the device after the span the host was in.
Compilations are counted through JAX's monitoring events: every lowering
of a program to XLA, from the compile cache or not, is one.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts program lowerings in ``count``."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == LOWERING_EVENT:
            self.count += 1


_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one counter (JAX listeners cannot be unregistered)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


class Recorder:
    """Host spans of one run: ``TraceAnnotation``s in a traced run, which
    the trace reduction reads; nothing otherwise."""

    def __init__(self, traced: bool):
        self.traced = traced

    def span(self, name: str):
        if self.traced:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


@dataclasses.dataclass
class Run:
    """Everything a driver, a reader and the report share about one run."""

    cell: object                 # spec.Cell
    seed: int
    seconds: float
    traced: bool
    devices: list
    rec: Recorder
    t_start: float               # process start, perf_counter seconds
    jobs: list = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)   # measured window, perf_counter seconds
    setup_s: float = 0.0
    compiles_in_window: int = 0
    trace: dict | None = None    # trace.reduce(...) of the traced window
    counters: dict = dataclasses.field(default_factory=dict)
    e2e: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    info: dict = dataclasses.field(default_factory=dict)

    @property
    def chips(self) -> int:
        return self.cell.chips
