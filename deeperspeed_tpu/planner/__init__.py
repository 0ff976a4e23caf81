"""Profile-guided schedule planner (ROADMAP item 3; DeepCompile's
thesis applied to this repo): one cost-model-driven search over the
whole schedule knob space — `zero_optimization.schedule` {mode,
prefetch_depth, bucket_mb, group_layers, remat}, activation
checkpointing, offload tier, quantization recipe, per-kernel block
geometries — replacing per-knob hand-tuning.

Pipeline: analytic cost model (`cost_model`) prunes the grid →
measured probe ladder (`search.ladder_pick`, offline, `ds_plan
--probe`) ranks the survivors → the winning plan is
emitted and persisted (`plan`) per (device kind, model shape) → the
engine consumes it through the `"planner"` config block (`apply`) and
`ds_plan` / `ds_report --json` surface it. See docs/planner.md.
"""

from .cost_model import Candidate, ModelShape  # noqa: F401
from .plan import (Plan, cached_plan, latest_plan,  # noqa: F401
                   latest_plan_fingerprint, load_plan, plan_cache_dir)
from .search import build_plan, enumerate_candidates  # noqa: F401
from .apply import overlay_plan  # noqa: F401
