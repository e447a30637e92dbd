"""repro.core — the paper's contribution: straggler-tolerant computation
scheduling for distributed SGD (Amiri & Gündüz, IEEE TSP 2019).

``RoundConfig`` is the canonical round configuration (one validator shared
by the simulator, the trainer, and the live layer); the live execution
types (``run_live``, ``Master``, ``run_worker``, ...) are re-exported
lazily from ``repro.live`` so ``import repro.core`` stays light."""
from .spec import (RoundConfig, DEADLINE_POLICIES, validate_deadline)
from .scheduling import (MASKED, cyclic_to_matrix, staircase_to_matrix,
                         random_assignment_to_matrix, to_matrix,
                         validate_to_matrix, loads_of_matrix,
                         mask_matrix_loads, SCHEDULES,
                         greedy_row_assignment, greedy_row_assignment_batch,
                         greedy_load_rebalance, greedy_load_rebalance_batch,
                         censored_feedback_update, AdaptiveScheduler)
from .delays import (DelayModel, TruncatedGaussianDelays,
                     ShiftedExponentialDelays, BimodalStragglerDelays,
                     EmpiricalDelays, scenario1, scenario2, ec2_like)
from .cluster import (DelayProcess, IIDProcess, MarkovRegimeProcess,
                      AR1Process, as_process, heterogeneous_scales,
                      ec2_cluster, message_comm_delays, FaultProcess,
                      SpotPreemptionProcess, NetworkPartitionProcess,
                      RackFailureProcess, MessageLossProcess,
                      DiurnalLoadProcess, FAULT_SCENARIOS, make_scenario)
from .trace import (TRACE_FORMAT_VERSION, DelayTrace, TraceProcess,
                    save_trace, load_trace, validate_trace_file,
                    CalibrationReport, calibrate_trace)
from .montecarlo import (SchemeSpec, SweepResult, RoundsResult, to_spec,
                         lb_spec, pc_spec, pcmm_spec, tau_spec,
                         adaptive_spec, task_gather_plan,
                         task_arrival_times_gather, message_boundaries,
                         message_slot_map, message_group_sizes, sweep,
                         sweep_rounds, completion_samples,
                         trajectory_samples, task_arrival_samples,
                         clear_cache, cache_stats, set_cache_capacity,
                         trial_keys, ResumableSweep, resumable_sweep)
from .grid import (GridCell, GridSpec, GridResult, stream_grid,
                   GRID_FORMAT_VERSION)
from .planner import plan, PlanResult, PLAN_FORMAT_VERSION
from .completion import (slot_arrival_times, message_arrival_times,
                         message_slot_layout, task_arrival_times,
                         completion_time, lower_bound_time,
                         first_k_distinct_mask, simulate_completion, simulate_lower_bound,
                         mean_completion_time)
from .theory import (theorem1_tail_from_H, theorem1_tail_mc, theorem1_mean_mc,
                     lower_bound_tail_mc, lower_bound_mean_mc,
                     theorem1_tail_r1_independent, sum_survival_grid,
                     multimessage_marginal_cdfs, multimessage_coded_tail,
                     multimessage_coded_mean, truncated_gaussian_pdf,
                     delay_model_pdfs, operating_point_mean_lb)
from .coded import (pc_threshold, pcmm_threshold, pc_encode, pc_decode,
                    pc_worker_compute, pcmm_encode, pcmm_decode,
                    pcmm_worker_compute, simulate_pc_completion,
                    simulate_pcmm_completion)
from .round import RoundClose, make_round_close, make_scheduler
from .aggregator import StragglerAggregator

# ------------------- live-layer facade (lazy re-exports) ---------------------
# repro.live imports from repro.core's submodules, so importing it eagerly
# here would be circular; PEP 562 resolves the names on first access.
_LIVE_EXPORTS = ("run_live", "Master", "LiveResult", "RoundReport",
                 "run_worker", "sample_delay_tables", "Comm", "Listener",
                 "CommClosedError", "connect", "listen")


def __getattr__(name):
    if name in _LIVE_EXPORTS:
        from .. import live
        return getattr(live, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LIVE_EXPORTS))
