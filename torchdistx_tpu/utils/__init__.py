"""Auxiliary subsystems: checkpoint/resume, failure detection/elastic
recovery, profiling, logging/metrics."""

from .checkpoint import AsyncCheckpointSaver, restore_checkpoint, save_checkpoint
from .failures import FailureDetector, device_health, run_elastic
from .logging import Metrics, get_logger
from .profiling import StepTimer, Timer, trace

__all__ = [
    "AsyncCheckpointSaver",
    "FailureDetector",
    "Metrics",
    "StepTimer",
    "Timer",
    "device_health",
    "get_logger",
    "restore_checkpoint",
    "run_elastic",
    "save_checkpoint",
    "trace",
]
