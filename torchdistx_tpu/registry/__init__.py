"""Pod-scale compile-artifact registry (docs/registry.md).

A content-addressed store of serialized init-program executables shared
across a fleet (:mod:`.store`), plus the sharded multi-host warm
scheduler that partitions compile work across a pod and fills every
host's local cache from the registry (:mod:`.scheduler`).

Activated by ``TDX_REGISTRY_DIR`` (:mod:`torchdistx_tpu.config`); every
program compiled through :func:`..compile_service.compile_program` —
both materialization engines, the serving runtime — then consults the
registry before compiling and publishes after.  All registry trouble —
flaky shared filesystems, corrupt entries, injected ``registry`` chaos
faults — degrades to a local compile, never an error.
"""

from .scheduler import (
    ProgramReport,
    ProgramSpec,
    plan_group_specs,
    shard_owner,
    warm_sharded,
)
from .store import ArtifactRegistry, env_fingerprint, env_key, registry_key

__all__ = [
    "ArtifactRegistry",
    "ProgramReport",
    "ProgramSpec",
    "env_fingerprint",
    "env_key",
    "plan_group_specs",
    "registry_key",
    "shard_owner",
    "warm_sharded",
]
