"""Compile a cell's model step at its real size for a described v5e
(`jax.experimental.topologies`): what the chip's compiler would refuse,
and `memory_analysis()` of the program. Not a chip run, and nothing it
prints is a device measurement.

    python benchmarks/tests/real_size.py <cell> [<remat policy> ...]

The engine places its own state with `device_put`, which a described
device cannot take, so this compiles the part that decides whether a cell
fits: loss and gradients of the cell's model at the cell's batch, bf16
weights, under the cell's mesh (ZeRO-3: weights sharded over `data`),
and adds the state's bytes by arithmetic. `test_real_size.py` runs the
same function under pytest.
"""

import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KERNEL_MODULES = ("flash_attention", "decode_attention", "optimizer")
# bf16 weights, fp32 master and two fp32 Adam moments
STATE_BYTES_PER_PARAM = 14


def describe(topology="v5e:2x2"):
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices


def kernels_for_the_chip():
    """Force the kernels out of interpret mode; returns the undo."""
    mods = [importlib.import_module(f"deeperspeed_tpu.ops.pallas.{n}")
            for n in KERNEL_MODULES]
    saved = [m._interpret for m in mods]
    for m in mods:
        m._interpret = lambda: False
    return lambda: [setattr(m, "_interpret", s) for m, s in zip(mods, saved)]


def compile_train_step(spec, devices, policy=None):
    """Loss and gradients of the cell's model at the cell's batch,
    compiled for `devices`; returns (compiled, account dict)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import harness
    family = harness.load_module(spec["root"], "families",
                                 spec["config"]["family"])
    chips = spec["chips"]
    mesh = Mesh(np.asarray(devices[:chips]), ("data",))
    model = family.build_model(spec["config"], "bfloat16",
                               spec["cell"]["model_options"])
    policy = policy or spec["cell"]["engine"].get(
        "activation_checkpointing", {}).get("policy")
    if policy not in (None, "none"):
        model.remat_policy = policy
    stage = spec["cell"]["engine"]["zero_optimization"]["stage"]

    def shard(leaf):
        """ZeRO-3 keeps a weight sharded over `data` on its first dim
        that divides; below stage 3 the bf16 weights are replicated."""
        spec_ = [None] * leaf.ndim
        if stage == 3:
            for d, n in enumerate(leaf.shape):
                if n % chips == 0:
                    spec_[d] = "data"
                    break
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, P(*spec_)))

    params = jax.tree_util.tree_map(
        shard, jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    traffic = spec["traffic"]
    tokens = jax.ShapeDtypeStruct(
        (traffic["global_batch"], traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))

    def step(params, tokens):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.value_and_grad(model.loss_fn)(params,
                                                     (tokens, tokens))

    undo = kernels_for_the_chip()
    try:
        compiled = jax.jit(step).lower(params, tokens).compile()
    finally:
        undo()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    # ZeRO-3 shards all of it, stages 1 and 2 all but the bf16 weights
    sharded = {0: 0, 1: 12, 2: 12, 3: STATE_BYTES_PER_PARAM}[stage]
    state = n_params * (STATE_BYTES_PER_PARAM - sharded + sharded / chips)
    text = compiled.as_text()
    return compiled, {
        "policy": policy, "temp_gb": mem.temp_size_in_bytes / 1e9,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "state_gb_by_arithmetic": state / 1e9,
        "fits_16gb": (mem.temp_size_in_bytes + state) / 1e9 < 15.5,
        "mosaic_calls": text.count("tpu_custom_call"),
        "all_gathers": text.count("all-gather"),
        "reduce_scatters": text.count("reduce-scatter"),
        "all_reduces": text.count("all-reduce")}


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json

    import jax
    from benchmarks import harness
    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_cell(ROOT, sys.argv[1])
    devices = describe()
    for policy in sys.argv[2:] or [None]:
        try:
            _, account = compile_train_step(spec, devices, policy)
        except Exception as e:      # noqa: BLE001 - report, try the next
            account = {"policy": policy, "error": str(e)[:600]}
        print(json.dumps(account), flush=True)
