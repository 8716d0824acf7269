"""Runtimes: the round driver, its in-process / shared-memory / multi-node
backends, the typed event protocol, and (imported lazily) the trainers."""
from repro.runtime.driver import (
    InProcRuntime,
    RoundDriver,
    RoundOutcome,
    Runtime,
    ShmProcRuntime,
    make_runtime,
)
from repro.runtime.elastic import ArrivalTrace, ElasticController, ElasticEvent
from repro.runtime.events import (
    EVENT_TYPES,
    GoalReached,
    NodeJoined,
    NodeLost,
    PartialReady,
    PartialShipped,
    RoundDeadline,
    RoundEvent,
    ScaleDecision,
    TopFolded,
    UpdateArrived,
    WorkerCrashed,
    from_wire,
    to_wire,
)

_TRAINER_NAMES = ("ClientRuntime", "FederatedTrainer", "FusedFLTrainer")


def __getattr__(name):
    # lazy: the trainers import jax, and host-side processes that only
    # need the driver (netd, shmrt workers) must never load it — one
    # process per chip owns the accelerator
    if name in _TRAINER_NAMES:
        from repro.runtime import trainer

        return getattr(trainer, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "ArrivalTrace",
    "ClientRuntime",
    "ElasticController",
    "ElasticEvent",
    "EVENT_TYPES",
    "FederatedTrainer",
    "FusedFLTrainer",
    "GoalReached",
    "InProcRuntime",
    "make_runtime",
    "NodeJoined",
    "NodeLost",
    "PartialReady",
    "PartialShipped",
    "RoundDeadline",
    "RoundDriver",
    "RoundEvent",
    "RoundOutcome",
    "Runtime",
    "ScaleDecision",
    "ShmProcRuntime",
    "TopFolded",
    "UpdateArrived",
    "WorkerCrashed",
    "from_wire",
    "to_wire",
]
