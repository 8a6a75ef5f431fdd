# The paper's primary contribution: PiP-MColl multi-object collectives,
# two-level topology (with per-axis link metadata), alpha-beta cost models,
# the algorithm-selection subsystem (priors + measured tuning tables), the
# cached collective runtime, and the Communicator object
# API (blocking methods + persistent nonblocking ops) resolving algo="auto".
from repro.core.topology import Topology
from repro.core.autotune import Selector, TuningTable
from repro.core import mcoll, costmodel, autotune, runtime, comm
from repro.core.comm import Communicator, PersistentOp, CollHandle, PlanSpec

__all__ = ["Topology", "Selector", "TuningTable", "mcoll",
           "costmodel", "autotune", "runtime", "comm", "Communicator",
           "PersistentOp", "CollHandle", "PlanSpec"]
