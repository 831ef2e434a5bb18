"""Composable component API for the harvest stack.

The paper's architecture (Slurm + modified OpenWhisk + pilot jobs +
invokers, composed non-invasively) is expressed here as five typed seams —
Router, Scaler, AdmissionPolicy, WorkloadSource, Executor — a string-keyed
component registry, and a declarative :class:`ScenarioConfig` consumed by
:meth:`Platform.build`. Layering: ``repro.core`` (paper mechanisms) knows
nothing of ``repro.faas`` (multi-tenant policies); this package composes
both and is the only construction path benchmarks/examples use.
"""
from repro.platform.interfaces import (AdmissionPolicy, Executor, Router,
                                       Scaler, WorkloadSource)
from repro.platform.registry import available, register, resolve
from repro.platform.scenario import (PlatformSection, ReliabilitySection,
                                     ScenarioConfig, SchedulingSection,
                                     TraceSection, WorkloadSection)
# component modules register themselves on import
from repro.platform.routers import (DeadlineAwareRouter, HashRouter,
                                    LeastLoadedRouter, LocalityRouter)
from repro.platform.scalers import AdaptiveJobManager, JobManager
from repro.platform.sources import SuiteLoad, UniformLoad
from repro.platform.executors import BatchedServingExecutor, SimExecutor
from repro.platform.elastic import (ElasticGangInvoker, ElasticServingExecutor,
                                    GangMember, GangPool)
from repro.platform import admission as _admission  # noqa: F401 (registers)
from repro.platform import reliability as _reliability  # noqa: F401 (registers)
from repro.platform.reliability import RetryPolicy
from repro.platform.runtime import (HarvestConfig, HarvestResult,
                                    HarvestRuntime, Platform, nan_to_none)

__all__ = [
    "AdmissionPolicy", "Executor", "Router", "Scaler", "WorkloadSource",
    "available", "register", "resolve",
    "ScenarioConfig", "TraceSection", "WorkloadSection",
    "SchedulingSection", "PlatformSection", "ReliabilitySection",
    "HashRouter", "LeastLoadedRouter", "LocalityRouter",
    "DeadlineAwareRouter", "RetryPolicy",
    "JobManager", "AdaptiveJobManager",
    "UniformLoad", "SuiteLoad",
    "SimExecutor", "BatchedServingExecutor",
    "GangMember", "ElasticGangInvoker", "GangPool", "ElasticServingExecutor",
    "HarvestConfig", "HarvestResult", "HarvestRuntime", "Platform",
    "nan_to_none",
]
