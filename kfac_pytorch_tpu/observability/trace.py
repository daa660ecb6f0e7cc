"""Flight recorder: per-host append-only structured event log.

Every multi-process causal chain in this repo — curvature-service
publish→refresh→install, supervisor snapshot write→commit→resume,
owner-shard replans, cadence slips — is invisible to the span/gauge
telemetry because each process only sees its own wall clock. The flight
recorder gives each process an append-only ``trace.jsonl`` of structured
events carrying *correlation keys* (``basis_version``, ``snapshot_id``,
``plan_fingerprint``) so ``scripts/merge_timeline.py`` can stitch N
hosts' files into one causally-ordered timeline after the fact.

Discipline mirrors ``telemetry.span()`` exactly: **off by default**, and
when off every call site costs one attribute lookup + no-op method on a
shared ``_NullRecorder`` singleton — no string formatting, no dict
construction beyond the kwargs already at the call site, and zero effect
on traced/jitted code (events are host-side only), so the compiled train
step is bit-identical either way.

Record schema (one JSON object per line)::

    {"ts_ns": <time.time_ns()>, "host": <int>, "pid": <os.getpid()>,
     "kind": "<event kind literal>", ...fields}

``kind`` must be a string literal at every call site — the
``scripts/check_trace_events.py`` lint keeps the docs event registry and
the emitted set in sync, same contract as the metric-name lint.

Host identity deliberately never touches jax: a caller may configure
tracing before its first device use, at which point
``jax.process_index()`` would initialize the backend.
Callers that know their rank pass ``host=``; otherwise the env fallback
(``KFAC_TRACE_HOST``/``JAX_PROCESS_ID``/``PROCESS_ID``) applies.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, IO, Optional


def _default_host() -> int:
    for var in ("KFAC_TRACE_HOST", "JAX_PROCESS_ID", "PROCESS_ID"):
        val = os.environ.get(var)
        if val is not None:
            try:
                return int(val)
            except ValueError:
                continue
    return 0


def _coerce(obj: Any) -> Any:
    """JSON fallback for numpy/jax scalars and arrays in event fields."""
    item = getattr(obj, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:
            pass
    tolist = getattr(obj, "tolist", None)
    if callable(tolist):
        try:
            return tolist()
        except Exception:
            pass
    return str(obj)


class _NullRecorder:
    """Shared no-op recorder: the disabled path is a bound-method call."""

    __slots__ = ()

    enabled = False
    path = None
    host = 0

    def event(self, kind: str, **fields: Any) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


_NULL = _NullRecorder()


class TraceRecorder:
    """Append-only JSONL event writer for one process.

    Thread-safe (the async snapshot writer and curvature-worker threads
    emit events concurrently with the training loop); each event is
    flushed immediately so a preempted process leaves a complete record
    of everything up to the kill — that is the whole point of a flight
    recorder.
    """

    enabled = True

    def __init__(self, path: str, host: Optional[int] = None) -> None:
        self.path = str(path)
        self.host = _default_host() if host is None else int(host)
        self._lock = threading.Lock()
        self._fh: Optional[IO[str]] = open(self.path, "a")

    def event(self, kind: str, **fields: Any) -> None:
        rec = {
            "ts_ns": time.time_ns(),
            "host": self.host,
            "pid": os.getpid(),
            "kind": kind,
        }
        rec.update(fields)
        line = json.dumps(rec, default=_coerce)
        with self._lock:
            fh = self._fh
            if fh is None:
                return
            fh.write(line + "\n")
            fh.flush()

    def flush(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


_GLOBAL = _NULL


def get_trace():
    """The process-global recorder (the null singleton unless configured)."""
    return _GLOBAL


def configure_trace(path: Optional[str] = None, host: Optional[int] = None):
    """Install (or tear down) the process-global flight recorder.

    ``configure_trace("<dir>/trace.jsonl", host=rank)`` starts recording;
    ``configure_trace(None)`` closes the current recorder and restores
    the null singleton. Returns the active recorder either way.
    """
    global _GLOBAL
    prev = _GLOBAL
    if isinstance(prev, TraceRecorder):
        prev.close()
    _GLOBAL = _NULL if path is None else TraceRecorder(path, host=host)
    return _GLOBAL
