"""Online scheduler service with a what-if digital twin.

The long-lived counterpart of the offline campaigns: a daemon that admits
and places training jobs online over live fabric state, with a forked
"digital twin" answering what-if queries before anything is committed.

  state   — LiveCluster: steps the v2 engine per ingested event + durable
            event log
            (bit-identical to offline simulate(), crash-replayable)
  twin    — DigitalTwin: copy-on-fork what-if predictions, memoised by
            fabric version
  server  — JSON-lines-over-TCP daemon (asyncio, stdlib only)
  client  — blocking + asyncio protocol clients

CLI: ``python -m repro.launch.schedd serve|submit|whatif|replay``.
Full contract: ``docs/service.md``.  Not to be confused with
``repro.serve`` (inference decoding).
"""

from .state import (LiveCluster, RecordingSimulator, ServiceLog,
                    job_from_json, job_to_json, replay_trace, service_schema)
from .twin import DigitalTwin
from .server import SchedulerService, ServerThread, run_server, serve
from .client import AsyncSchedClient, SchedClient, ServiceError

__all__ = [
    "LiveCluster", "RecordingSimulator", "ServiceLog", "job_from_json",
    "job_to_json", "replay_trace", "service_schema",
    "DigitalTwin", "SchedulerService", "ServerThread", "run_server",
    "serve", "AsyncSchedClient", "SchedClient", "ServiceError",
]
