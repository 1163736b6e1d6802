"""Auxiliary subsystems: checkpoint/resume, failure detection/elastic
recovery, profiling, logging/metrics.

The public names resolve on first use (PEP 562): importing this package,
or one of its submodules such as ``utils.logging``, loads none of the
others — ``checkpoint`` brings orbax when it saves or restores,
``failures`` and ``profiling`` are the training loop's business, and a
serving replica that wants a logger pays for ``logging`` only."""

import importlib

_SUBMODULE_OF = {
    "AsyncCheckpointSaver": "checkpoint",
    "restore_checkpoint": "checkpoint",
    "save_checkpoint": "checkpoint",
    "FailureDetector": "failures",
    "device_health": "failures",
    "run_elastic": "failures",
    "Metrics": "logging",
    "get_logger": "logging",
    "StepTimer": "profiling",
    "Timer": "profiling",
    "trace": "profiling",
}

__all__ = sorted(_SUBMODULE_OF)


def __getattr__(name):
    try:
        submodule = _SUBMODULE_OF[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    value = getattr(importlib.import_module(f"{__name__}.{submodule}"), name)
    globals()[name] = value  # next lookup skips this function
    return value
