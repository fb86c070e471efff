"""Pinned-signature guard for private jax APIs (the arealint PVT idiom).

The repo calls private jax internals positionally (megablox gmm, the
library paged-attention launch wrapper). A jax bump can silently reorder
or extend those signatures, after which positional call sites feed the
wrong argument into the wrong parameter with no error. A pinned symbol may
be a class too: a dataclass's constructor parameters are its fields, and a
field nobody fills keeps a default nobody chose.

Each call site declares the parameter tuple it was audited against as a
module-level ``_EXPECTED_*`` literal and verifies it via
:func:`pin_signature` at first use. Two layers of defense share that one
literal:

- at runtime, :func:`pin_signature` raises with a parameter diff before
  a drifted signature is ever called;
- at lint time, arealint PVT002 re-checks every ``_EXPECTED_*`` pin
  against the *installed* jax, so the drift surfaces during the jax bump
  itself as a lint finding.

``AUDITED_JAX`` is the version the pins were last audited against — the
installed one; keep it (and pyproject's documented version) in lockstep
when re-auditing.
"""

from __future__ import annotations

import inspect

AUDITED_JAX = "0.9.0"

_verified: set[tuple[int, tuple[str, ...]]] = set()


def pin_signature(obj, expected: tuple[str, ...], audited: str = AUDITED_JAX):
    """Raise unless ``obj``'s parameter names equal ``expected`` exactly.

    The FULL tuple is compared, not a prefix: an appended (defaulted)
    parameter that jax's own wrappers supply but our call sites don't must
    fail too. Verification is cached per (object, expected) pair, so
    hot-path callers pay ``inspect.signature`` once per process while a
    second call site pinning the SAME symbol against a different tuple
    still gets checked.
    """
    key = (id(obj), expected)
    if key in _verified:
        return obj
    got = tuple(inspect.signature(obj).parameters)
    if got != expected:
        missing = [p for p in expected if p not in got]
        added = [p for p in got if p not in expected]
        raise RuntimeError(
            f"private jax API {getattr(obj, '__name__', obj)!r} drifted from "
            f"the pinned signature (audited against jax {audited}): "
            f"removed {missing or 'nothing'}, added {added or 'nothing'}, "
            f"installed order {got}; re-audit every positional call site "
            "before updating the pin"
        )
    _verified.add(key)
    return obj
