"""Structured observability: spans, metrics, flight recorder, profiling.

Six coordinated pieces (none of which may perturb a compiled program —
the zero-overhead-when-off contract is pinned by ``tests/test_obs.py``):

* ``obs.span("plan.build") / obs.event / obs.notice`` — host-side span
  tracing into the flight-recorder ring (always) and a per-run JSONL
  event log under ``$DFFT_OBS_DIR`` (default off). Every span also
  enters a ``jax.profiler.TraceAnnotation`` (``dfft:<name>``) whenever
  jax is loaded, log on or off, so a profiled process has the spans on
  the device trace's clock. Each ``exec_*`` opens ``plan.exec`` with a
  ``plan.dispatch`` child around the jitted call (``tracing.py``,
  ``resilience/fallback.py``); the exchange builders open none.
* ``obs.metrics`` — process-global counters/gauges/latency histograms
  with dual per-plan vs cumulative views; tests read the per-plan
  ``snapshot()``, the Prometheus exposition renders the cumulative one
  (``metrics.py``).
* ``obs.flightrec`` — the ALWAYS-ON bounded in-memory ring of recent
  spans/events/metric deltas, dumped to JSONL on trigger
  (GuardViolation, circuit open, demotion, shed burst, SIGUSR2) — zero
  file I/O in steady state (``flightrec.py``).
* ``obs.promexp`` — Prometheus text exposition of the cumulative metrics
  view; ``dfft-serve --http`` serves it at ``GET /metrics``
  (``promexp.py``).
* ``obs.profile`` — stage-attributed device profiling: ``jax.named_scope``
  emission per declared plan-graph node and, inside a local-FFT stage,
  per transformed axis (``<node>.ax<k>``; metadata only — every
  fingerprint pin holds with scopes on), a ``jax.profiler`` xplane/
  trace-events ingester, and the graph join behind
  ``dfft-explain --profile`` (``profile.py``).
* ``dfft-explain`` — resolved-plan diagnostics without executing the FFT
  (``explain.py``; registered in pyproject.toml; ``--profile`` is the one
  mode that executes).

This package imports no jax at module import time, so ``params``-level
(device-free) usage stays possible.
"""

from . import flightrec, metrics, profile, promexp
from .tracing import (ENV_VAR, console_enabled, disable, disable_console,
                      enable, enable_console, enabled, event, event_log_path,
                      notice, obs_dir, reset_enablement, span, validate_event,
                      validate_events_dir, validate_events_file)

__all__ = [
    "ENV_VAR", "console_enabled", "disable", "disable_console", "enable",
    "enable_console", "enabled", "event", "event_log_path", "flightrec",
    "metrics", "notice", "obs_dir", "profile", "promexp",
    "reset_enablement", "snapshot", "reset", "span", "validate_event",
    "validate_events_dir", "validate_events_file",
]


def snapshot():
    """Shorthand for ``metrics.snapshot()``."""
    return metrics.snapshot()


def reset():
    """Shorthand for ``metrics.reset()`` (does not touch enablement)."""
    metrics.reset()
