from repro.runtime.fault import (
    CountInterrupted,
    FailureInjector,
    SimulatedFailure,
    StragglerMonitor,
)
from repro.runtime.elastic import RemeshPlan, tc_remesh_plan
from repro.runtime.contracts import (
    ContractViolation,
    contracts_enabled,
    max_retrace,
    max_transfers,
    no_host_sync,
)

__all__ = [
    "CountInterrupted",
    "FailureInjector",
    "SimulatedFailure",
    "StragglerMonitor",
    "RemeshPlan",
    "tc_remesh_plan",
    "ContractViolation",
    "contracts_enabled",
    "max_retrace",
    "max_transfers",
    "no_host_sync",
]
