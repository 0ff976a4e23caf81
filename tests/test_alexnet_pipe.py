"""AlexNet-as-pipeline trains to the same loss as the DP baseline
(reference `tests/unit/test_pipe.py:30` — its flagship pipeline
correctness test, on CIFAR-shaped data)."""

import numpy as np

import jax

import deeperspeed_tpu
from deeperspeed_tpu.models.vision import AlexNet, alexnet_pipe


STEPS = 5
BATCH = 16


def _batches():
    # one fixed CIFAR-shaped batch repeated: memorizable, so the loss
    # must fall, and both engines see identical data
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, BATCH, 32, 32, 3)).astype(np.float32) * 0.5
    y = rng.integers(0, 10, (1, BATCH)).astype(np.int32)
    return [(x, y)] * STEPS


def _config(gas=1):
    return {"train_batch_size": BATCH,
            "gradient_accumulation_steps": gas,
            "steps_per_print": 1000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}


def test_alexnet_pipeline_matches_dp_baseline():
    baseline = AlexNet()
    engine, *_ = deeperspeed_tpu.initialize(
        model=baseline,
        model_parameters=baseline.init_params(jax.random.PRNGKey(0)),
        config_params=_config())
    base_losses = [float(engine.train_batch(batch=b)) for b in _batches()]

    pipe = alexnet_pipe(num_stages=2)
    params = pipe.init_params(jax.random.PRNGKey(0),
                              example_input=np.zeros((1, 32, 32, 3),
                                                     np.float32))
    pipe_engine, *_ = deeperspeed_tpu.initialize(
        model=pipe, model_parameters=params,
        config_params=_config(gas=2))
    pipe_losses = []
    for x, y in _batches():
        xm = x.reshape(2, BATCH // 2, 32, 32, 3)
        ym = y.reshape(2, BATCH // 2)
        pipe_losses.append(float(pipe_engine.train_batch(batch=(xm, ym))))

    assert base_losses[-1] < base_losses[0]
    np.testing.assert_allclose(pipe_losses, base_losses, rtol=5e-3,
                               atol=5e-3)


def test_alexnet_partition_balanced():
    """parameter-balanced partitioning puts the conv stack and the dense
    head on different stages (real counts only exist after init_params
    — before that PipelineModule falls back to uniform)."""
    pipe = alexnet_pipe(num_stages=2)
    params = pipe.init_params(jax.random.PRNGKey(0),
                              example_input=np.zeros((1, 32, 32, 3),
                                                     np.float32))
    assert len(pipe.parts) == 3  # boundaries for 2 stages
    boundary = pipe.parts[1]
    assert 0 < boundary < len(pipe.forward_funcs)

    def numel(layer_params):
        return sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(layer_params))

    per_layer = [numel(p) for p in params["layers"]]
    stage_params = [sum(per_layer[:boundary]), sum(per_layer[boundary:])]
    # PARAMETER-balanced, not layer-count-balanced: a uniform 5/5 layer
    # split puts ~97% of AlexNet's params on stage 0 (convs 0-4 dwarf
    # nothing — the dense head is big); balanced must do better than 75/25
    assert min(stage_params) > 0
    assert min(stage_params) / sum(stage_params) > 0.25, stage_params
