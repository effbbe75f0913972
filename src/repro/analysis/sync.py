"""sync_contract(): zero implicit device→host transfers, enforced.

The round loop's performance model assumes every phase is an async
device dispatch; one stray ``float(loss)`` inserts a pipeline stall per
client and the server cost is per-client again.  This module makes the
invariant executable::

    with sync_contract("round"):
        state = runner.run_round(state)      # any implicit D2H raises

    with allowed_sync("one-per-round KD loss pull"):
        losses = np.asarray(losses)          # annotated, allowed

Two enforcement layers compose:

* ``jax.transfer_guard_device_to_host("disallow")`` — the real thing on
  accelerators, where a materialization is an actual transfer.  On
  XLA:CPU it never fires: device buffers ARE host memory (zero-copy),
  so ``float(x)`` performs no transfer and the guard stays silent.
* a portable interception of ``jax.Array`` materialization — the
  ``ArrayImpl._value`` funnel (behind ``float()``, ``int()``,
  ``bool()``, ``str()``, ``.tolist()``, ``jax.device_get``) plus
  ``.item()`` and direct ``__array__()`` calls.  Installed lazily on
  first contract entry and zero-cost when no contract is active.

Known hole, covered statically: ``np.asarray(device_array)`` on CPU
converts through the C buffer protocol and is invisible to both layers
(on TPU/GPU the transfer guard still catches it).  The AST linter
(``repro.analysis.lint`` rule RA101) flags ``np.asarray`` on hot paths
at review time instead, which is why the two halves ship together.

``allowed_sync`` scopes are thread-local; contract activation is
process-global so a violation on the async KD dispatch worker is
caught too (it surfaces through the worker's Future at resolve time,
and any swallowed violation re-raises at contract exit).
"""
from __future__ import annotations

import contextlib
import threading
import traceback
from dataclasses import dataclass
from typing import Iterator

import jax

from repro.analysis.spans import span

__all__ = ["SyncViolation", "allowed_sync", "sync_contract"]


class SyncViolation(RuntimeError):
    """An un-annotated device→host materialization inside a contract."""


_TLS = threading.local()            # per-thread allowed_sync depth
_LOCK = threading.Lock()
_ACTIVE: list["SyncScope"] = []     # process-global contract stack
_INSTALLED = False


@dataclass
class SyncRecord:
    kind: str
    thread: str
    stack: str


class SyncScope:
    """Handle yielded by ``sync_contract`` — carries observed violations."""

    def __init__(self, label: str) -> None:
        self.label = label
        self.violations: list[SyncRecord] = []


def _allow_depth() -> int:
    return getattr(_TLS, "depth", 0)


def _check(kind: str) -> None:
    """Called from the materialization funnel; raises on violation."""
    with _LOCK:
        if not _ACTIVE:
            return
        scopes = list(_ACTIVE)
        label = scopes[-1].label
    if _allow_depth() > 0:
        return
    # drop this funnel frame; keep the caller frames that name the site
    stack = "".join(traceback.format_stack(limit=10)[:-2])
    rec = SyncRecord(kind=kind, thread=threading.current_thread().name,
                     stack=stack)
    with _LOCK:
        for scope in scopes:
            scope.violations.append(rec)
    raise SyncViolation(
        f"implicit device->host sync ({kind}) inside sync_contract"
        f"[{label}] on thread {rec.thread!r} — wrap the site in "
        f"allowed_sync(\"reason\") if it is legitimate.\n{stack}")


def _install() -> None:
    """Patch the ArrayImpl materialization funnel (idempotent)."""
    global _INSTALLED
    if _INSTALLED:
        return
    _INSTALLED = True
    import jax.numpy as jnp
    cls = type(jnp.zeros(()))            # concrete ArrayImpl

    orig_value = cls._value              # property: the cached numpy view
    orig_item = cls.item
    orig_array = getattr(cls, "__array__", None)

    @property
    def guarded_value(self):  # noqa: ANN001 - matches property protocol
        _check("materialize")
        return orig_value.fget(self)

    def guarded_item(self, *args):
        _check("item")
        return orig_item(self, *args)

    cls._value = guarded_value
    cls.item = guarded_item
    if orig_array is not None:
        def guarded_array(self, *args, **kwargs):
            _check("__array__")
            return orig_array(self, *args, **kwargs)
        cls.__array__ = guarded_array


@contextlib.contextmanager
def allowed_sync(reason: str) -> Iterator[None]:
    """Annotate a legitimate device→host sync; ``reason`` is mandatory.

    Inside the scope the portable funnel and the jax transfer guard both
    stand down (this thread only).  The linter treats the lexical scope
    as exempt from RA101, so the one-line justification lives exactly
    where the sync happens.  The scope is the program span
    ``fedsdd.sync``, with the reason as its attribute.
    """
    if not reason or not reason.strip():
        raise ValueError("allowed_sync requires a non-empty reason string")
    _TLS.depth = _allow_depth() + 1
    try:
        with span("fedsdd.sync", reason=reason), \
                jax.transfer_guard_device_to_host("allow"):
            yield
    finally:
        _TLS.depth = _allow_depth() - 1


@contextlib.contextmanager
def sync_contract(label: str = "round") -> Iterator[SyncScope]:
    """Scope asserting zero un-annotated implicit D2H materializations.

    Violations raise at the offending site on the thread that synced;
    violations swallowed en route (a worker's Future that nobody
    resolves inside the scope) re-raise at contract exit.
    """
    _install()
    scope = SyncScope(label)
    with _LOCK:
        _ACTIVE.append(scope)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield scope
    finally:
        with _LOCK:
            _ACTIVE.remove(scope)
    if scope.violations:                 # clean exit but swallowed records
        first = scope.violations[0]
        raise SyncViolation(
            f"sync_contract[{label}]: {len(scope.violations)} implicit "
            f"device->host sync(s) were caught but swallowed (first: "
            f"{first.kind} on thread {first.thread!r}).\n{first.stack}")
