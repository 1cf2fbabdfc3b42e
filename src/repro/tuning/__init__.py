"""repro.tuning — the public API for every tuned kernel.

Offline -> online lifecycle (see docs/tuning.md):

    session = TunerSession(db_path="artifacts/tuning_db.json")
    session.tune(wl, method="bayesian")       # offline: populate the DB
    cfg = session.resolve(wl)                 # online: cached, normalized

    with overrides(scan={"radix": 4}):        # scoped experiments
        prefix_sum(x)

Kernel families declare themselves once via ``@tuned_kernel`` (space
builder, pallas impl, reference impl, config normalizer); the session is
the only component that turns a Workload into launch kwargs.

Module-level ``resolve``/``tune``/``suggest`` delegate to the process-wide
default session.
"""
from __future__ import annotations

from typing import Mapping, Optional

from repro.core.bayesian import TuneResult
from repro.core.policy import (Policy, PolicyObjective, get_policy,
                               pareto_front, policies, policy_scalar_cols)
from repro.core.space import (Config, Workload, build_space, fit_block,
                              normalize_config)
from repro.tuning.db import DEFAULT_DB_PATH, SCHEMA_VERSION, TuningDB
from repro.tuning.dispatch import plan_execution
from repro.tuning.overrides import active_overrides, overrides, overrides_active
from repro.tuning.registry import (KernelSpec, get_kernel, normalizer_for,
                                   registered_kernels, tuned_kernel)
from repro.tuning.session import (TunerSession, default_session, get_strategy,
                                  register_strategy, set_default_session,
                                  strategies)
from repro.tuning.sweep import (SweepJournal, SweepResult, config_key,
                                journal_path, prune_candidates, run_sweep)


# The online-tuning stack (repro.tuning.online) stays a lazy import, like
# the ml stack: it pulls in the sweep journal + analytical ranking, and
# the serve engine imports this package on every startup. PEP 562 keeps
# `from repro.tuning import OnlineTuner` working without the eager cost.
_ONLINE_EXPORTS = frozenset((
    "OnlineTuner", "OnlineWallClockObjective", "ReplayTrace", "StepTimer",
    "TraceRecorder", "aggregate_fleet", "attach", "fleet_prior",
    "measurements_to_incumbent", "online_search", "promote_fleet_winner",
    "replay", "replay_candidates", "warm_tuner"))


def __getattr__(name: str):
    if name in _ONLINE_EXPORTS:
        from repro.tuning import online
        return getattr(online, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def resolve(wl: Workload, *, config: Optional[Mapping[str, int]] = None,
            dims: Optional[Mapping[str, int]] = None) -> Config:
    """Resolve a launch-ready config through the default session."""
    return default_session().resolve(wl, config=config, dims=dims)


def tune(wl: Workload, method: str = "bayesian", **kw) -> TuneResult:
    """Offline-tune through the default session (persists the winner)."""
    return default_session().tune(wl, method=method, **kw)


def suggest(wl: Workload) -> Config:
    """Zero-evaluation analytical suggestion via the default session."""
    return default_session().suggest(wl)


__all__ = [
    "Config", "DEFAULT_DB_PATH", "KernelSpec", "OnlineTuner", "Policy",
    "PolicyObjective",
    "OnlineWallClockObjective", "ReplayTrace", "SCHEMA_VERSION", "StepTimer",
    "SweepJournal", "SweepResult", "TraceRecorder", "TuneResult",
    "TunerSession", "TuningDB", "Workload", "active_overrides", "attach",
    "build_space", "config_key", "default_session", "fit_block", "get_kernel",
    "get_policy", "get_strategy", "journal_path", "normalize_config",
    "normalizer_for", "online_search", "overrides",
    "overrides_active", "pareto_front", "plan_execution", "policies",
    "policy_scalar_cols", "prune_candidates",
    "register_strategy", "registered_kernels", "replay",
    "replay_candidates", "resolve", "run_sweep", "set_default_session",
    "strategies", "suggest", "tune", "tuned_kernel",
]
