"""The paper's primary contribution: carbon-aware flexible job-shop
scheduling of DAG workloads (bi-level makespan -> carbon/energy protocol),
implemented as TPU-friendly JAX population search over SGS encodings.

Public API:
    instance   — FJSP instances (jobs, DAG tasks, machines) + generators
    carbon     — carbon-intensity traces (4 region profiles, CSV ingest)
    objectives — makespan / energy / carbon evaluators
    validate   — shared feasibility validator (Eqs. 4-8 + budget)
    decoder    — SGS decoders + carbon timing sweep
    solvers    — SA / exact oracle / bi-level driver / online dispatch
"""
from repro.core import carbon, decoder, instance, objectives, validate
from repro.core.instance import (Instance, Job, PackedInstance,
                                 generate_instance, pack, stack_packed)
from repro.core.carbon import CarbonTrace, REGIONS, synthesize
from repro.core.solvers import (BilevelResult, ScheduleResult, solve_bilevel,
                                solve_bilevel_batch, solve_sa)

__all__ = [
    "carbon", "decoder", "instance", "objectives", "validate",
    "Instance", "Job", "PackedInstance", "generate_instance", "pack",
    "stack_packed", "CarbonTrace", "REGIONS", "synthesize",
    "BilevelResult", "ScheduleResult", "solve_bilevel",
    "solve_bilevel_batch", "solve_sa",
]
