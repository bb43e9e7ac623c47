"""Core coflow-DAG scheduling library (Shafiee & Ghaderi 2020) — the paper's
contribution, implemented faithfully: BNA, DMA, DMA-SRT, DMA-RT, the
primal-dual job ordering, G-DM / G-DM-RT, the O(m)Alg baseline, backfilling,
the online driver, and the paper's workload/verification machinery."""

from .backend import (bna_pieces_many, cache_stats, clear_caches,
                      compute_alphas, configure_compile_cache,
                      group_block, grouping_prefix,
                      prefetch_bna, prefetch_plan,
                      set_alpha_backend, set_bna_backend, set_plan_backend,
                      use_alpha_backend, use_bna_backend, use_plan_backend)
from .backfill import BackfillResult, backfill
from .baseline import om_alg
from .bna import bna, verify_bna_schedule
from .dma import cached_bna, dma, isolated_job_unit
from .matching import bna_many
from .dma_srt import dma_rt, dma_srt, path_subjobs, srt_start_times
from .engine import (PlanResult, Scheduler, available_schedulers,
                     make_scheduler, plan, plan_online, register_scheduler,
                     scheduler_options)
from .fsp_reduction import fsp_to_coflow_job
from .gap_instance import (gap_bounds, gap_hand_schedule, gap_instance,
                           gap_optimal_schedule_length)
from .gdm import GammaEpoch, gdm, geometric_bucket, group_jobs
from .online import OnlineResult, simulate_online
from .session import (AdmissionPolicy, Frontier, SchedulerSession,
                      SessionSnapshot, SessionStats)
from .stream import (StreamDriver, StreamResult, arrival_times, run_stream,
                     stream_jobs)
from .ordering import OrderResult, cached_job_order, job_order
from .result import CompositeSchedule, Transcript, twct
from .simulator import verify_schedule, verify_transcript
from .timeline import FinalSchedule, UnitSchedule, merge_and_fix
from .traces import (PAPER_STATS, build_jobs, dag_edges, fb_like_coflows,
                     paper_workload, poisson_releases, port_skew,
                     sample_coflows, sample_sizes, sample_width, theta0,
                     workload_stats)
from .types import (Coflow, Instance, Job, aggregate_size, coflow_layers,
                    critical_path_size, effective_size, is_rooted_tree,
                    topological_order)

__all__ = [name for name in dir() if not name.startswith("_")]
