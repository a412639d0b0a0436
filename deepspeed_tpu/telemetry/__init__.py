"""Process-local telemetry: event bus, crash-forensics flight recorder,
and HBM memory accounting (docs/observability.md "Telemetry events").

Import layering matters here: ``bus``, ``flight_recorder`` and
``crash_report`` are stdlib-only (no jax) so supervisors — the elastic
agent, the launcher, worker wrapper scripts — can import them without
initializing a backend, the same discipline ``runtime/sentinel.py``
established. ``memory``, ``spans`` (host spans on the profiler's clock) and
``scopes`` (which source scope a device operation belongs to) touch jax
only inside their functions.
"""

from deepspeed_tpu.telemetry.bus import TelemetryBus, publish, telemetry_bus
from deepspeed_tpu.telemetry.crash_report import (
    TELEMETRY_DIR_ENV,
    load_blackbox,
    sweep_blackbox_dumps,
    verify_blackbox,
)
from deepspeed_tpu.telemetry.flight_recorder import (
    BLACKBOX_SCHEMA,
    FlightRecorder,
    install_crash_handlers,
)
from deepspeed_tpu.telemetry.spans import span

__all__ = [
    "TelemetryBus",
    "telemetry_bus",
    "publish",
    "span",
    "FlightRecorder",
    "install_crash_handlers",
    "BLACKBOX_SCHEMA",
    "TELEMETRY_DIR_ENV",
    "sweep_blackbox_dumps",
    "load_blackbox",
    "verify_blackbox",
]
