"""Test fixture models (parity with reference `tests/unit/simple_model.py`).

`SimpleModel` is a small MLP as a pure loss_fn + params; `LinearLayer` /
`LinearStackPipe` mirror the pipeline fixtures.
"""

import numpy as np

import jax
import jax.numpy as jnp

from deeperspeed_tpu.runtime.pipe import (LayerSpec, PipelineModule,
                                          TiedLayerSpec)


class SimpleModel:
    """MLP: hidden -> hidden (xN) -> scalar loss against targets."""

    def __init__(self, hidden_dim=16, num_layers=2, empty_grad=False):
        self.hidden_dim = hidden_dim
        self.num_layers = num_layers
        self.empty_grad = empty_grad

    def init_params(self, rng):
        params = {}
        for i in range(self.num_layers):
            rng, key = jax.random.split(rng)
            params[f"linear_{i}"] = {
                "w": jax.random.normal(key, (self.hidden_dim,
                                             self.hidden_dim),
                                      jnp.float32) * 0.1,
                "b": jnp.zeros((self.hidden_dim,), jnp.float32),
            }
        return params

    def apply(self, params, x):
        for i in range(self.num_layers):
            p = params[f"linear_{i}"]
            x = jnp.tanh(x @ p["w"] + p["b"])
        return x

    def loss_fn(self, params, batch, rng=None):
        x, y = batch
        out = self.apply(params, x)
        return jnp.mean(jnp.square(out - y))


def random_dataset(total_samples, hidden_dim, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(total_samples, hidden_dim)).astype(dtype)
    ys = rng.normal(size=(total_samples, hidden_dim)).astype(dtype)
    return [(xs[i], ys[i]) for i in range(total_samples)]


def random_batches(n_batches, batch_size, hidden_dim, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n_batches):
        x = rng.normal(size=(batch_size, hidden_dim)).astype(np.float32)
        y = rng.normal(size=(batch_size, hidden_dim)).astype(np.float32)
        yield (x, y)


class LinearLayer:
    """Pipeline layer fixture: y = tanh(xW + b)."""

    def __init__(self, dim=16, activation=True):
        self.dim = dim
        self.activation = activation

    def init(self, rng, x):
        k1, _ = jax.random.split(rng)
        return {
            "w": jax.random.normal(k1, (self.dim, self.dim),
                                   jnp.float32) * 0.1,
            "b": jnp.zeros((self.dim,), jnp.float32),
        }

    def apply(self, params, x, rng=None):
        out = x @ params["w"] + params["b"]
        return jnp.tanh(out) if self.activation else out


def mse_loss(outputs, labels):
    return jnp.mean(jnp.square(outputs - labels))


def simple_pipeline_module(num_layers=4, dim=16, num_stages=2, **kwargs):
    specs = [LayerSpec(LinearLayer, dim) for _ in range(num_layers)]
    return PipelineModule(layers=specs, num_stages=num_stages,
                          loss_fn=mse_loss, **kwargs)


def tied_pipeline_module(dim=16, num_stages=2):
    specs = [
        TiedLayerSpec("embed", LinearLayer, dim),
        LayerSpec(LinearLayer, dim),
        TiedLayerSpec("embed", LinearLayer, dim),
    ]
    return PipelineModule(layers=specs, num_stages=num_stages,
                          loss_fn=mse_loss)


def price_mfu_against_v5e(engine):
    """The CPU has no row in `profiling.hardware`, so telemetry leaves
    the MFU scalars out there; a test of the MFU arithmetic names the
    chip it prices against."""
    from deeperspeed_tpu.profiling.hardware import peak_flops_per_chip
    engine.telemetry._peak_flops = peak_flops_per_chip("TPU v5 lite")
