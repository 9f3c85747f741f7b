"""Parameter-Server execution subsystem — the paper's actual topology.

``repro.dist`` executes DynaComm plans through symmetric ZeRO collectives
(the TPU-native adaptation); this package executes them in the paper's
own deployment shape: S server shards × W edge workers, segmented
parameter pulls down and gradient pushes up over per-worker asymmetric
links, synchronously (``PSTrainer``, bit-identical to the ZeRO trainer)
or asynchronously under a bounded staleness ``k`` (``AsyncPSTrainer``,
with server-side rejection or SSP wait-at-barrier throttling).

``TopologySchedule`` makes the fabric time-varying: the ``ps`` runtime's
re-planning loop (synchronous) and ``repro.ps.dynamic``'s driver
(asynchronous) re-derive the decomposition once per topology epoch — the
paper's run-time loop in the PS regime.
"""

from repro.ps.async_mode import (THROTTLES, AsyncPSTrainer, AsyncPushEvent,
                                 AsyncRunLog)
from repro.ps.dynamic import (AsyncRescheduleEvent, DynamicAsyncPSTrainer,
                              profiles_from_specs)
from repro.ps.server import (PSServer, PushResult, StaleVersion,
                             TransferLedger)
from repro.ps.topology import (LinkModel, PSTopology, TopologySchedule,
                               as_topology_schedule, asymmetric_link,
                               uplink_degradation)
from repro.ps.worker import PSTrainer

__all__ = [
    "LinkModel", "PSTopology", "asymmetric_link",
    "TopologySchedule", "as_topology_schedule", "uplink_degradation",
    "PSServer", "PushResult", "StaleVersion", "TransferLedger",
    "PSTrainer",
    "THROTTLES", "AsyncPSTrainer", "AsyncPushEvent", "AsyncRunLog",
    "AsyncRescheduleEvent", "DynamicAsyncPSTrainer", "profiles_from_specs",
]
