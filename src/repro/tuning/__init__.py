"""FIBER tuning drivers wired to framework knobs: install-time (kernel
block shapes), before-execute-time (layout plans), run-time (serving
bucket variants)."""
from .dynamic import (DecodeAutoTuner, divisor_block_ks,
                      page_multiple_block_ks)
from .install import register_kernel_regions, run_install_tuning
from .static import analytic_plan_cost, candidate_plans, tune_layout
__all__ = ["register_kernel_regions", "run_install_tuning", "tune_layout",
           "analytic_plan_cost", "candidate_plans", "DecodeAutoTuner",
           "divisor_block_ks", "page_multiple_block_ks"]
