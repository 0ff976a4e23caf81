"""Multi-output model training (parity with reference
`tests/unit/test_multi_output_model.py`: a model producing several outputs
and a weighted multi-loss trains through the engine).
"""

import numpy as np

import jax
import jax.numpy as jnp

import deeperspeed_tpu


class MultiOutputModel:
    """Two heads over a shared trunk; loss = w1*mse1 + w2*mse2."""

    def __init__(self, hidden=16, weights=(1.0, 0.5)):
        self.hidden = hidden
        self.weights = weights

    def init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        h = self.hidden
        return {
            "trunk": jax.random.normal(k1, (h, h)) * 0.1,
            "head_a": jax.random.normal(k2, (h, h)) * 0.1,
            "head_b": jax.random.normal(k3, (h, h)) * 0.1,
        }

    def outputs(self, params, x):
        t = jnp.tanh(x @ params["trunk"])
        return t @ params["head_a"], t @ params["head_b"]

    def loss_fn(self, params, batch, rng=None):
        x, ya, yb = batch
        out_a, out_b = self.outputs(params, x)
        w1, w2 = self.weights
        return (w1 * jnp.mean(jnp.square(out_a - ya)) +
                w2 * jnp.mean(jnp.square(out_b - yb)))


def test_multi_output_trains():
    model = MultiOutputModel()
    engine, *_ = deeperspeed_tpu.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        })
    rng = np.random.default_rng(0)
    batch = (rng.normal(size=(1, 8, 16)).astype(np.float32),
             rng.normal(size=(1, 8, 16)).astype(np.float32),
             rng.normal(size=(1, 8, 16)).astype(np.float32))
    losses = [float(engine.train_batch(batch=batch)) for _ in range(25)]
    assert losses[-1] < losses[0] * 0.6


def test_multi_output_forward_backward_step_api():
    """The unfused forward/backward/step path handles tuple batches too."""
    model = MultiOutputModel()
    engine, *_ = deeperspeed_tpu.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        })
    rng = np.random.default_rng(0)
    batch = (rng.normal(size=(8, 16)).astype(np.float32),
             rng.normal(size=(8, 16)).astype(np.float32),
             rng.normal(size=(8, 16)).astype(np.float32))
    l0 = float(engine(batch))
    engine.backward()
    engine.step()
    for _ in range(15):
        engine(batch)
        engine.backward()
        engine.step()
    l1 = float(engine(batch))
    engine.backward()  # clear cache
    engine.step()
    assert l1 < l0
