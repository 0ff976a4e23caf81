"""DeeperSpeed-TPU: a TPU-native distributed training framework with the
capability surface of DeeperSpeed (EleutherAI's DeepSpeed v0.3.15 fork).

The public API mirrors the reference (`deepspeed/__init__.py`):
``initialize()`` returns ``(engine, optimizer, dataloader, lr_scheduler)``;
JSON configs written for the reference parse unmodified. The machinery
underneath is JAX/XLA/pjit/Pallas over a `jax.sharding.Mesh`.
"""

import time as _time

import jax  # noqa: F401  (first: its own import is not `import_s`)

_IMPORT_T0 = _time.perf_counter()   # runtime/telemetry.py, `setup_report`

import argparse

from . import moe, ops  # noqa: F401
from .elasticity import compute_elastic_config, elasticity_enabled
from .parallel.mesh import PipelineParallelGrid
from .parallel.topology import (PipeDataParallelTopology,
                                PipeModelDataParallelTopology,
                                ProcessTopology)
from .runtime import zero  # noqa: F401
from .inference import InferenceEngine
from .runtime.config import DeepSpeedConfig
from .runtime.engine import DeepSpeedEngine
from .runtime.lr_schedules import add_tuning_arguments
from .runtime.pipe import LayerSpec, PipelineModule, TiedLayerSpec
from .runtime.pipe.engine import PipelineEngine
from .runtime.sentinel import TrainingHealthError
from .runtime.telemetry import note_import as _note_import
from .utils.distributed import init_distributed
from .utils.logging import log_dist, logger
from .version import __version__

# git-style version info for parity with deepspeed.git_version_info
git_hash = None
git_branch = None


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mpu=None,
               dist_init_required=None, collate_fn=None, config=None,
               config_params=None, mesh=None, rng=None):
    """Initialize the DeepSpeed engine (reference `__init__.py:52-145`).

    Arguments match the reference; `model` is a pure
    ``loss_fn(params, batch, rng) -> loss`` (or an object exposing
    ``loss_fn``/``init_params``) instead of an ``nn.Module``, and
    ``model_parameters`` is the parameter pytree. A ``PipelineModule``
    model selects the ``PipelineEngine``.

    Returns: tuple of ``(engine, optimizer, training_dataloader,
    lr_scheduler)``.
    """
    log_dist(f"DeeperSpeed-TPU info: version={__version__}", ranks=[0])

    if dist_init_required is None or dist_init_required:
        init_distributed()

    if isinstance(model, PipelineModule):
        engine = PipelineEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                mpu=model.mpu() if mpu is None else mpu,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn,
                                config=config,
                                config_params=config_params,
                                mesh=mesh,
                                rng=rng)
    else:
        engine = DeepSpeedEngine(args=args,
                                 model=model,
                                 optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 mpu=mpu,
                                 dist_init_required=dist_init_required,
                                 collate_fn=collate_fn,
                                 config=config,
                                 config_params=config_params,
                                 mesh=mesh,
                                 rng=rng)

    return (engine, engine.optimizer, engine.training_dataloader,
            engine.lr_scheduler)


def _add_core_arguments(parser):
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag for user code)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse.SUPPRESS)  # deprecated spelling
    group.add_argument("--deepscale_config", default=None, type=str,
                       help=argparse.SUPPRESS)
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Discover rank/world from MPI")
    return parser


def add_config_arguments(parser):
    """Add DeepSpeed's argparse flags (reference `__init__.py:199`)."""
    return _add_core_arguments(parser)


# the last line: this file's imports are `setup_report()["import_s"]`
_note_import(_IMPORT_T0)
