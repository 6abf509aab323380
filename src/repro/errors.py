"""Typed failure taxonomy for the FMM pipeline (DESIGN.md §9).

Every loud failure path in the solver raises one of these instead of a
bare ``RuntimeError``/``ValueError``, so callers (and the guarded
execution ladder, ``repro.solver.guard``) can branch on *what* failed:

  ValidationError      caller handed us malformed arguments (shape,
                       dtype, batch layout) — never recoverable by the
                       ladder, always the caller's bug
  CapOverflowError     the connectivity caps dropped interactions — the
                       answer would be silently wrong; recoverable by
                       cap escalation (or ``core.direct`` as the floor)
  NonFiniteInputError  z or q contain NaN/Inf — garbage in; nothing
                       downstream can recover, fail before compute
  NonFiniteOutputError phi contains NaN/Inf on finite input — a kernel
                       or expansion bug; recoverable by degrading the
                       offending phase to the reference backend
  RecoveryExhaustedError  every rung of the recovery ladder failed

The classes multiply-inherit the builtin the pre-taxonomy code raised
(``ValueError`` for validation, ``RuntimeError`` for overflow), so
``except RuntimeError`` call sites written against the old contract keep
working.
"""
from __future__ import annotations


class FmmError(Exception):
    """Base class of every typed FMM failure."""


class ValidationError(FmmError, ValueError):
    """Malformed solver arguments (shape / dtype / batch layout)."""


class ShapeError(ValidationError):
    """Argument shape does not match the solver's static config."""


class DTypeError(ValidationError, TypeError):
    """Argument dtype confusion (real positions, precision loss, ...), or
    a config dtype the device cannot run (f64 on a TPU's kernels)."""


class CapOverflowError(FmmError, RuntimeError):
    """Connectivity caps overflowed: interactions would be dropped.

    Carries ``margins`` — the per-class cap margins (slots left before
    overflow; negative = entries dropped) keyed by
    ``repro.core.fmm.HEALTH_CLASSES`` — and the scalar ``overflow``.
    """

    def __init__(self, message: str, *, margins: dict | None = None,
                 overflow: int = 0):
        super().__init__(message)
        self.margins = dict(margins or {})
        self.overflow = int(overflow)


class NonFiniteInputError(FmmError, ValueError):
    """z or q contain NaN/Inf — refusing to compute on garbage."""


class NonFiniteOutputError(FmmError, ArithmeticError):
    """phi contains NaN/Inf on finite input (kernel/expansion fault)."""


class RecoveryExhaustedError(FmmError, RuntimeError):
    """Every rung of the guarded-execution ladder failed.

    Carries ``report`` — the ``GuardReport`` of the failed walk."""

    def __init__(self, message: str, *, report=None):
        super().__init__(message)
        self.report = report


class DeadlineExceededError(FmmError, TimeoutError):
    """A served request's deadline budget ran out before it could be
    dispatched (admission control, ``repro.serve``). The request was
    shed, not computed — retrying with a fresh budget is the caller's
    call."""


class OversizedRequestError(ValidationError):
    """A served request's N exceeds the bucket lattice *and* the direct
    O(N^2) fallback bound — no shape class can absorb it. Raised (or
    recorded as the typed rejection in a ``ServeReport``) by the
    serving plane's admission controller."""


class BackendDowngradeWarning(RuntimeWarning):
    """A solver entry point silently dispatches a different backend than
    requested (e.g. ``apply_batched`` on a ``batched_dispatch="fallback"``
    backend). CI promotes this to an error in the tier-1 matrix — silent
    degradation fails the build."""
