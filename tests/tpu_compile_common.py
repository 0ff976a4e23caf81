"""Every Pallas kernel, compiled for a TPU v5e from this CPU sandbox.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). Interpret mode
has no tiling rules, so a kernel can pass every CPU test and still be
refused by Mosaic — `paged_decode_attention_pallas` was, from PR 8 to
PR 22. Each case lowers one kernel at a shape the main path really runs,
with `_interpret` forced off, compiles it for `v5e:2x2` and asserts that
the compiled text holds a `tpu_custom_call`. Nothing runs: a compile
that passes says nothing about results or times.

This module holds what the four files of cases share
(`tests/test_tpu_compile.py`: the training step and its kernels;
`test_tpu_compile_kernels.py`: kernel geometry;
`test_tpu_compile_serving.py`: the serving programs;
`test_tpu_compile_cache_kinds.py`: the cache kinds beside K and V pages),
one file a worker under `--dist loadfile`. One process at a time may load
the TPU's library: several workers describe the chip side by side only
with `ALLOW_MULTIPLE_LIBTPU_LOAD=1` in the environment, as the tier-1
command has it (`/root/TESTS_LAST_RUN.json`); without it the first file
compiles and `v5e_2x2` skips the others' cases, by name. Never set it in
the repository, and never on the machine with the chip.

The persistent compile cache is turned off around the compiles — an
executable compiled for a described chip is written to the cache but
cannot be read back without one.
"""

import importlib
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# `ops.pallas` re-exports several functions under their module's own
# name (`flash_attention`, `grouped_matmul`, ...): fetch modules by path
(fa, decode_attention, block_sparse_attention, grouped_matmul, quant_matmul,
 optimizer, ssm, eva, gdn) = KERNEL_MODULES = tuple(
    importlib.import_module(f"deeperspeed_tpu.ops.pallas.{name}")
    for name in ("flash_attention", "decode_attention",
                 "block_sparse_attention", "grouped_matmul", "quant_matmul",
                 "optimizer", "ssm", "eva", "gdn"))

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four described chips of a v5e 2x2 host; skipped where the
    topology cannot be described (no libtpu)."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture
def on_chip(monkeypatch, v5e_2x2):
    """`compile_for_chip(fn, *shape_dtypes, sharding=one chip)` →
    compiled text, with every kernel module's `_interpret` forced off
    and the compile cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    for mod in KERNEL_MODULES:
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def compile_for_chip(fn, *args, sharding=one_chip):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype in args]
        # a fresh lambda: never a trace cached in interpret mode
        return jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()

    yield compile_for_chip
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def assert_kernel(text, at_least=1):
    assert text.count("tpu_custom_call") >= at_least, \
        "the compiled program holds no Mosaic kernel"


def kernel_names(text):
    """The `ds.*` kernel scope each Mosaic call of the program lies in."""
    return {m for line in text.splitlines() if "tpu_custom_call" in line
            for m in re.findall(r"/(ds\.[a-z0-9_]+)/pallas_call", line)}


def qkv(b, s, h, d):
    return [((b, s, h, d), BF16)] * 3


def loss_of(fn):
    """Scalar fp32 loss of an attention callable, for the backward."""
    return lambda *a: fn(*a).astype(jnp.float32).sum()


def stacked(layers, pages, heads, page_size, head_dim, quant):
    """(shape, dtype) of the engine's stacked K and V pools, then of the
    int8 pages' scale pools."""
    pool = ((layers, pages, heads, page_size, head_dim),
            jnp.int8 if quant else BF16)
    scale = ((layers, pages, heads, page_size), BF16)
    return [pool, pool] + [scale, scale] * quant


INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>.*?) (?P<op>[a-z][a-z\-]*)\(")


# what may carry a pool without moving it
CARRIES = ("parameter", "tuple", "get-tuple-element", "bitcast", "while")


ATTN_WEIGHT = re.compile(r"bf16\[(?:\d+,)?2048,(?:6144|4096|2048)\]")


def attention_weight_relayouts(text):
    """What the reshape to heads costs a program when XLA folds it into
    the projection's dot (`gpt_neox._heads_dot`): every `copy` whose
    result has the shape of a layer's attention weight or of a stack of
    them (hidden 2048: `qkv_w` [.., 2048, 6144], `kv_w` [.., 2048, 4096],
    `q_w` / `out_w` [.., 2048, 2048]), and every convolution over a
    window of heads under `ds.attn`."""
    found = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        if m["op"] == "copy" and ATTN_WEIGHT.search(m["type"]):
            found.append(("copy", m["type"][:60]))
        if m["op"] == "convolution" and "window={size=" in line \
                and "ds.attn" in line:
            found.append(("convolution", m["type"][:60]))
    return found


def pool_shaped_moves(text, shape, dtype="bf16"):
    """Instructions that produce an array of the pool's shape and are
    neither a kernel nor a way of carrying it."""
    shaped = re.compile(dtype + r"\[" + ",".join(map(str, shape)) + r"\]")
    moved = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and shaped.search(m["type"]) and m["op"] not in CARRIES and \
                "tpu_custom_call" not in line:
            moved.append((m["op"], m["type"][:60]))
    return moved
