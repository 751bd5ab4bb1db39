"""Chronos core: the paper's contribution as a composable JAX module.

PoCD closed forms (Thms 1/3/5), machine-time costs (Thms 2/4/6), net-utility
optimization (Section V, Algorithm 1), startup-aware completion estimation and
work-preserving handoff (Section VI).
"""
from .pareto import ParetoParams, pdf, cdf, sf, mean, sample, fit_mle, min_of_n_mean
from .pocd import pocd_clone, pocd_srestart, pocd_sresume
from .cost import cost_clone, cost_srestart, cost_sresume
from .utility import JobSpec, utility, gamma, pocd_of, cost_of
from .optimizer import (Solution, solve, solve_grid, solve_strategies,
                        solve_batch, solve_batch_jit, solve_algorithm1)
from .estimator import (ProgressReport, estimate_completion_chronos,
                        estimate_completion_naive, is_straggler, handoff_offset)
from . import theory
from . import multiwave

__all__ = [
    "ParetoParams", "pdf", "cdf", "sf", "mean", "sample", "fit_mle",
    "min_of_n_mean", "pocd_clone", "pocd_srestart", "pocd_sresume",
    "cost_clone", "cost_srestart", "cost_sresume", "JobSpec",
    "utility", "gamma", "pocd_of", "cost_of", "Solution", "solve",
    "solve_grid", "solve_strategies", "solve_batch", "solve_batch_jit",
    "solve_algorithm1",
    "ProgressReport", "estimate_completion_chronos", "multiwave",
    "estimate_completion_naive", "is_straggler", "handoff_offset", "theory",
]
