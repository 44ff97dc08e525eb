from .base import ActivityBackend, available_backends, get_backend, register_backend
from .synthetic import SyntheticBackend, SyntheticTraceBuilder
from .runtime import RuntimeBackend
from .analytical import (
    AnalyticalBackend,
    HardwareSpec,
    PEAKS,
    StepModel,
    TPU_V5E,
    device_peak,
    trace_from_step_model,
)

__all__ = [
    "ActivityBackend",
    "available_backends",
    "get_backend",
    "register_backend",
    "SyntheticBackend",
    "SyntheticTraceBuilder",
    "RuntimeBackend",
    "AnalyticalBackend",
    "HardwareSpec",
    "PEAKS",
    "StepModel",
    "TPU_V5E",
    "device_peak",
    "trace_from_step_model",
]
