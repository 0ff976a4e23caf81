"""The GPT-NeoX family: a published `config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`. A new architecture adds a file beside this one and a
reference of the same name under `reference/`.
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def model_config(conf, param_dtype):
    h, i = conf["hidden_size"], conf["intermediate_size"]
    if i % h:
        raise ValueError(f"intermediate_size {i} is not a multiple of "
                         f"hidden_size {h}: GPTNeoXConfig cannot say it")
    if conf["hidden_act"] not in ("gelu", "gelu_new", "gelu_fast"):
        raise ValueError(f"GPTNeoX has no {conf['hidden_act']!r} MLP")
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=h,
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        max_seq_len=conf["max_position_embeddings"],
        rotary_pct=conf["rotary_pct"],
        rotary_emb_base=conf["rotary_emb_base"],
        intermediate_mult=i // h, layernorm_eps=conf["layer_norm_eps"],
        use_parallel_residual=conf["use_parallel_residual"],
        tie_word_embeddings=conf["tie_word_embeddings"],
        param_dtype=_DTYPES[param_dtype])


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file."""
    return GPTNeoX(model_config(conf, param_dtype), **options)


def init_params(model, seed, mesh=None):
    """The weights, made on the device in one jitted call from the seed,
    in the type the model keeps them in. Under a mesh of several devices
    every leaf is made sharded on its first dimension that the devices
    divide, so that no one device ever holds the whole float32 model
    (1.4B parameters are 5.7 GB, and the engine copies as it places)."""
    sharding = None
    if mesh is not None and mesh.size > 1:
        def shard(leaf):
            spec = [None] * leaf.ndim
            for d, n in enumerate(leaf.shape):
                if n % mesh.size == 0:
                    spec[d] = mesh.axis_names
                    break
            return NamedSharding(mesh, PartitionSpec(*spec))
        sharding = jax.tree_util.tree_map(
            shard, jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    init = jax.jit(model.init_params, out_shardings=sharding)
    return init(jax.random.PRNGKey(seed))
