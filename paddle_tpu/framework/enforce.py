"""Typed error layer — the reference's PADDLE_ENFORCE discipline.

Reference: `paddle/common/enforce.h` (PADDLE_ENFORCE_* macros raising
typed EnforceNotMet errors with operator context) and
`paddle/phi/core/errors.h` (the error-code classes). Python analog:
typed exception classes + ``enforce``/``check_type``/``check_dtype``
helpers, and operator context attached to any exception crossing the
eager dispatch seam (``run_op`` adds a PEP-678 note naming the op), so
failures read as framework errors, not raw JAX tracebacks.
"""

from __future__ import annotations

__all__ = ["EnforceNotMet", "InvalidArgumentError", "NotFoundError",
           "OutOfRangeError", "AlreadyExistsError", "PermissionDeniedError",
           "UnimplementedError", "UnavailableError",
           "PreconditionNotMetError", "enforce", "check_type",
           "check_dtype", "attach_op_context"]


class EnforceNotMet(RuntimeError):
    """Base of all framework-raised errors (reference enforce.h:EnforceNotMet)."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PermissionDeniedError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


def enforce(condition, message, *args, exc=InvalidArgumentError):
    """PADDLE_ENFORCE: raise ``exc`` with a formatted message unless
    ``condition`` holds."""
    if not condition:
        raise exc(message.format(*args) if args else message)


def check_type(value, name, expected_type, op_name):
    """Reference: `python/paddle/base/data_feeder.py` check_type."""
    if not isinstance(value, expected_type):
        names = getattr(expected_type, "__name__", None) or ", ".join(
            t.__name__ for t in expected_type)
        raise InvalidArgumentError(
            f"The type of '{name}' in {op_name} must be {names}, "
            f"but received {type(value).__name__}.")


def check_dtype(dtype, name, expected_dtypes, op_name):
    """Reference: data_feeder.py check_dtype."""
    d = str(dtype).replace("paddle.", "")
    expected = [str(e) for e in expected_dtypes]
    if d not in expected and d.split(".")[-1] not in expected:
        raise InvalidArgumentError(
            f"The dtype of '{name}' in {op_name} must be one of "
            f"{expected}, but received {d}.")


def attach_op_context(exc, op_name):
    """Tag an in-flight exception with the operator it crossed (PEP 678
    note — the analog of enforce.h's operator-context frames). On
    Python < 3.11, where ``add_note`` doesn't exist, the ``__notes__``
    list is maintained by hand — same attribute, same traceback
    rendering under 3.11+ semantics."""
    note = f"[operator '{op_name}' of paddle_tpu]"
    try:
        if hasattr(exc, "add_note"):
            exc.add_note(note)
        else:
            notes = getattr(exc, "__notes__", None)
            if not isinstance(notes, list):
                notes = []
                exc.__notes__ = notes
            notes.append(note)
    except (TypeError, AttributeError):
        pass
    return exc
