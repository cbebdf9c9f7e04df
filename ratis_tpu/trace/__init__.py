"""Host-path tracing subsystem: request->commit spans, stage decomposition,
Perfetto export.  See :mod:`ratis_tpu.trace.tracer` for the recording model
and :mod:`ratis_tpu.trace.export` for aggregation/export."""

from ratis_tpu.trace.tracer import (NUM_STAGES, STAGE_APPEND, STAGE_APPLY,
                                    STAGE_CLIENT, STAGE_DECODE, STAGE_ENCODE,
                                    STAGE_ENGINE, STAGE_KINDS, STAGE_NAMES,
                                    STAGE_REPLICATE, STAGE_ROUTE, STAGE_TXN,
                                    STAGE_WIRE, TILING_STAGES, TRACER,
                                    SpanRing, Tracer,
                                    configure_from_properties, get_tracer,
                                    instrument_loop, profile_dir_session)

__all__ = [
    "NUM_STAGES", "STAGE_APPEND", "STAGE_APPLY", "STAGE_CLIENT",
    "STAGE_DECODE", "STAGE_ENCODE", "STAGE_ENGINE", "STAGE_KINDS",
    "STAGE_NAMES", "STAGE_REPLICATE", "STAGE_ROUTE", "STAGE_TXN",
    "STAGE_WIRE", "TILING_STAGES", "TRACER", "SpanRing", "Tracer",
    "configure_from_properties", "get_tracer", "instrument_loop",
    "profile_dir_session",
]
