"""Measurement backends for the AT search.

The paper measures wall-clock on the target machine.  Here three executors
share one interface — ``__call__(assignment) -> cost`` — so search.py is
agnostic to how cost is obtained:

* :class:`WallClockExecutor` — times a variant callable (JAX-aware:
  ``block_until_ready`` on the result; warmup run excluded so jit tracing is
  not measured).  Used by install-time AT (Pallas interpret mode on CPU,
  real kernels on TPU).
* :class:`CostModelExecutor` — evaluates an analytic cost (``according
  estimated`` / the roofline model) without executing anything.  Used by the
  static driver against compiled dry-run artifacts.
* :class:`TableExecutor` — replays a {assignment-key: cost} table (tests,
  and the paper-count benchmarks where only the trajectory matters).

``CountingExecutor`` wraps any of them to assert evaluation counts.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from .cost import eval_expr
from .errors import OATSpecError


@dataclass
class WallClockExecutor:
    """cost = min wall-clock seconds over ``repeats`` runs of the variant.

    ``make_variant(assignment)`` returns a zero-arg callable; its result is
    blocked on (JAX async dispatch) before the clock stops.  A device
    error raised while blocking propagates: a variant that failed must
    not be timed as a fast one.
    """

    make_variant: Callable[[dict], Callable[[], Any]]
    repeats: int = 3
    warmup: int = 1

    def __call__(self, assignment: dict[str, Any]) -> float:
        fn = self.make_variant(assignment)
        for _ in range(self.warmup):
            jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best


@dataclass
class CostModelExecutor:
    """cost = analytic expression/callable over (assignment + env)."""

    cost: str | Callable[[dict], float]
    env: dict[str, Any] = field(default_factory=dict)

    def __call__(self, assignment: dict[str, Any]) -> float:
        ns = dict(self.env)
        ns.update(assignment)
        if callable(self.cost):
            return float(self.cost(ns))
        return float(eval_expr(self.cost, ns))


@dataclass
class TableExecutor:
    """cost looked up from a table keyed by sorted assignment items."""

    table: dict[tuple, float]
    default: float | None = None

    @staticmethod
    def key(assignment: dict[str, Any]) -> tuple:
        return tuple(sorted(assignment.items()))

    def __call__(self, assignment: dict[str, Any]) -> float:
        k = self.key(assignment)
        if k in self.table:
            return self.table[k]
        if self.default is not None:
            return self.default
        raise OATSpecError(f"no cost recorded for assignment {assignment}")


class CountingExecutor:
    """Wraps an executor and counts calls (paper-count assertions)."""

    def __init__(self, inner: Callable[[dict], float]):
        self.inner = inner
        self.count = 0
        self.trajectory: list[dict] = []

    def __call__(self, assignment: dict[str, Any]) -> float:
        self.count += 1
        self.trajectory.append(dict(assignment))
        return self.inner(assignment)
