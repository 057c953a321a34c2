"""Online Morpheus runtime — the layer between the batch simulator and the
serving stack.

The batch engine (``core/engine.py``) answers "what would this whole trace
do under this fixed mode split?".  This package answers the *runtime*
question the paper's Morpheus software stack faces: how many cores should
be in cache mode for the work arriving *right now*?

  * ``stream``    — epoch-by-epoch resumable replay over an explicit
    ``EngineState`` carry, plus the warm-state handoff used when the mode
    split changes (mode transitions flush departing slices, §4.1.3).
  * ``governor``  — the adaptive mode-split governor: hill-climb /
    epsilon-greedy search over the offline policy's candidate splits,
    with hysteresis and phase-shift detection.
  * ``telemetry`` — per-epoch ring-buffer log with JSON/CSV export,
    consumed by ``benchmarks/fig_online``.
  * ``fleet``     — N replicas per dispatch: same-config replicas batch
    into one (optionally shard_map-sharded) engine step, with a shared
    split-advisor for cross-replica warm starts (docs/fleet.md).
  * ``admission`` — overload-aware admission control: when the
    per-tenant SLO budgeter says the joint SLO set is unattainable,
    shed/defer the lowest-priority tenants with aging (no starvation),
    and feed the overload pressure back into the governor (docs/qos.md).
"""
from .admission import (AdmissionConfig,  # noqa: F401
                        AdmissionController, OverloadResult, RoundPlan,
                        simulate_overload)
from .fleet import (FleetResult, ReplicaSpec,  # noqa: F401
                    SplitAdvisor, build_replicas, convergence_epoch,
                    evaluate_governors, run_serial, simulate_fleet)
from .governor import (SERVING_GCFG, Governor,  # noqa: F401
                       GovernorConfig, GovernorState, OnlineReplica,
                       OnlineResult, ServingGovernor,
                       candidates_for, demo_pool, describe_tick,
                       gcfg_from_dict, qos_reward, simulate_online,
                       tenant_epoch_costs, tenant_epoch_ipcs)
from .stream import EpochStream, HandoffReport, handoff  # noqa: F401
from .telemetry import (EpochRecord, TelemetryLog,  # noqa: F401
                        merge_logs)
