"""The two jax names the framework imports from one place: ``shard_map``
and the Pallas TPU ``CompilerParams``. Plain re-exports of the installed
jax's names, not version forks."""

from jax import shard_map  # noqa: F401
from jax.experimental.pallas.tpu import CompilerParams  # noqa: F401
