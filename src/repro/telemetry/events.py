"""Structured run events: versioned JSONL records, sinks, and RunLog.

One schema for everything a run emits — environment provenance
(``run_meta``), per-phase aggregates (``phase_metrics``), and the
point events (``averaging_event``, ``fault_event``, ``resize_event``,
``checkpoint_event``). Records are flat JSON dicts stamped with
``{"v": TELEMETRY_VERSION, "type": <record type>}``; a reader refuses
records from a NEWER writer (mirroring the checkpoint ladder's
future-version refusal) and unknown record types.

Sinks implement the tiny :class:`TelemetrySink` protocol
(``emit(record)`` / ``close()``): :class:`JsonlSink` appends one JSON
line per record, :class:`MemorySink` collects them in a list (tests),
:class:`NullSink` drops them. Drivers emit unconditionally through
whatever sink they were handed.

:class:`RunLog` reads a record stream back and — via :meth:`history` —
reconstructs the legacy history dict (``loss`` / ``dispersion`` /
``disp_trace`` / ``averages`` / ``eval`` / ``worker_eval`` [/
``resizes``]) that :meth:`repro.core.engine.PhaseEngine.run` returns,
key for key: the events layer supersedes the hand-rolled hist dicts
without breaking anything that consumes them. :func:`init_history` is
the one shared constructor behind those dicts (previously four
copy-pasted literals across the engine and elastic drivers).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

#: 2: ``phase_metrics`` names the phase's ``carry`` ("plane" or "leaf");
#: a version-1 record has no such field
TELEMETRY_VERSION = 2

RECORD_TYPES = (
    "run_meta",
    "phase_metrics",
    "averaging_event",
    "fault_event",
    "resize_event",
    "checkpoint_event",
)


def init_history(*, resizes: bool = False) -> dict:
    """The engine drivers' history dict — ONE constructor for the keys
    every driver (``run``, ``run_host``, ``_run_host_faults``,
    ``run_elastic``) must agree on."""
    hist = {"loss": [], "dispersion": [], "disp_trace": [],
            "averages": 0, "eval": [], "worker_eval": []}
    if resizes:
        hist["resizes"] = []
    return hist


def make_record(rtype: str, **fields) -> dict:
    """A versioned record dict. ``rtype`` must be one of
    :data:`RECORD_TYPES`; field values must be JSON-serializable."""
    if rtype not in RECORD_TYPES:
        raise ValueError(
            f"unknown telemetry record type {rtype!r} (expected one of "
            f"{RECORD_TYPES})")
    rec = {"v": TELEMETRY_VERSION, "type": rtype}
    rec.update(fields)
    return rec


def parse_record(obj) -> dict:
    """Validate one record (a dict, or a JSON line to parse). Refuses
    records written by a newer telemetry version and unknown types —
    silently misreading a future schema is worse than failing."""
    if isinstance(obj, (str, bytes)):
        obj = json.loads(obj)
    if not isinstance(obj, dict):
        raise ValueError(f"telemetry record must be a dict, got "
                         f"{type(obj).__name__}")
    v = obj.get("v")
    if not isinstance(v, int):
        raise ValueError("telemetry record has no integer 'v' version "
                         f"field: {obj!r}")
    if v > TELEMETRY_VERSION:
        raise ValueError(
            f"telemetry record version {v} is newer than this reader "
            f"(TELEMETRY_VERSION={TELEMETRY_VERSION}) — read it with "
            "the build that wrote it")
    rtype = obj.get("type")
    if rtype not in RECORD_TYPES:
        raise ValueError(
            f"unknown telemetry record type {rtype!r} (expected one of "
            f"{RECORD_TYPES})")
    return obj


def _git_sha() -> str | None:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        if out.returncode == 0:
            return out.stdout.strip()
    except Exception:
        pass
    return None


def run_meta_record(config: dict | None = None, **extra) -> dict:
    """The provenance record every sink stream should start with: jax
    version, backend, device kind and host device count, python, git
    sha — plus the run's ``config`` dict verbatim."""
    import jax
    devices = jax.devices()
    return make_record(
        "run_meta",
        jax_version=jax.__version__,
        backend=jax.default_backend(),
        device_kind=devices[0].device_kind if devices else None,
        device_count=len(devices),
        python_version=sys.version.split()[0],
        platform=sys.platform,
        git_sha=_git_sha(),
        config=dict(config or {}),
        **extra)


# --------------------------------------------------------------------------
# Sinks
# --------------------------------------------------------------------------

class TelemetrySink:
    """Protocol: ``emit(record)`` accepts one :func:`make_record` dict;
    ``close()`` releases resources. Usable as a context manager."""

    def emit(self, record: dict) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class NullSink(TelemetrySink):
    """Drops every record — the no-telemetry sink."""

    def emit(self, record: dict) -> None:
        pass


class MemorySink(TelemetrySink):
    """Collects records in :attr:`records` (tests / in-process use)."""

    def __init__(self):
        self.records: list = []

    def emit(self, record: dict) -> None:
        self.records.append(parse_record(record))


class JsonlSink(TelemetrySink):
    """Appends one JSON line per record to ``path`` (parent directories
    created), flushing per emit so a crashed run keeps its telemetry."""

    def __init__(self, path: str):
        self.path = path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._f = open(path, "w")

    def emit(self, record: dict) -> None:
        self._f.write(json.dumps(parse_record(record), default=float))
        self._f.write("\n")
        self._f.flush()

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()


# --------------------------------------------------------------------------
# Reader
# --------------------------------------------------------------------------

class RunLog:
    """A validated, in-order view over one run's records."""

    def __init__(self, records):
        self.records = [parse_record(r) for r in records]

    @classmethod
    def load(cls, path: str) -> "RunLog":
        with open(path) as f:
            return cls(line for line in f if line.strip())

    def of_type(self, rtype: str) -> list:
        if rtype not in RECORD_TYPES:
            raise ValueError(f"unknown record type {rtype!r}")
        return [r for r in self.records if r["type"] == rtype]

    @property
    def meta(self) -> dict | None:
        metas = self.of_type("run_meta")
        return metas[0] if metas else None

    @property
    def phases(self) -> list:
        return self.of_type("phase_metrics")

    def history(self) -> dict:
        """The legacy history dict, reconstructed exactly: per-phase
        ``loss_trace`` / ``disp_trace`` entries concatenate into the
        recorded traces, averaging events carry the event-step
        dispersion and count, resize events the membership changes.
        ``eval`` / ``worker_eval`` hold host-callback results that
        never serialize; they reconstruct empty."""
        resizes = self.of_type("resize_event")
        hist = init_history(resizes=bool(resizes))
        for ph in self.phases:
            hist["loss"].extend(tuple(e) for e in ph.get("loss_trace", []))
            hist["disp_trace"].extend(
                tuple(e) for e in ph.get("disp_trace", []))
        for ev in self.of_type("averaging_event"):
            hist["dispersion"].append((ev["step"], ev["dispersion"]))
            hist["averages"] += 1
        for ev in resizes:
            hist["resizes"].append((ev["step"], ev["old_m"], ev["new_m"]))
        return hist
