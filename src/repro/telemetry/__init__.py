from repro.telemetry.bus import (Event, StreamSummary,  # noqa: F401
                                 TelemetryBus)
from repro.telemetry.sinks import FileSink  # noqa: F401
from repro.telemetry.spans import (Recorder, mark, recording,  # noqa: F401
                                   span)
