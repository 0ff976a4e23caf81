"""Kernel geometry (`ops/autotune.py`): one deterministic rule a kernel,
a pure function of the call's shape and the device kind. Nothing here
times anything; `tests/test_tpu_compile.py` compiles what the flash rule
can return for a described v5e."""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import autotune
from deeperspeed_tpu.ops.autotune import (FLASH_BLOCK_CANDIDATES,
                                          FLASH_BLOCK_K, FLASH_BLOCK_Q,
                                          FLASH_LONG_SEQ,
                                          FLASH_LONG_SEQ_BLOCKS, fit_block,
                                          flash_blocks,
                                          flash_blocks_admitted)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "deeperspeed_tpu")
V5E = "TPU v5 lite"


@pytest.fixture(autouse=True)
def no_env_pin(monkeypatch):
    monkeypatch.delenv("DS_FLASH_BLOCKS", raising=False)
    monkeypatch.delenv("DS_FLASH_BWD_BLOCKS", raising=False)


def cell(name):
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           name + ".json")) as f:
        return json.load(f)


# [B, S, H, D] as the kernel sees it, a chip's shard where a mesh splits
# the batch: the two 2k train cells, the prefill buckets of both serve
# cells (`prefill_lengths` of their JSON), and what lies around them
SHORT_SHAPES = [(16, 2048, 16, 64), (4, 2048, 16, 128)] + \
    [(1, s, 16, 128) for s in (128, 256, 512, 1024, 1536)] + \
    [(1, 4096, 16, 64), (2, FLASH_LONG_SEQ - 128, 8, 256)]
LONG_SHAPES = [(1, 16384, 16, 64), (1, 8192, 16, 64), (1, 32768, 12, 64),
               (1, 16384, 16, 128), (2, 8320, 8, 256)]


def test_the_serve_cells_prefill_buckets_are_the_short_shapes():
    buckets = set()
    for name in ("pythia-1.4b.serve_closed32",
                 "olmoe-1b-7b.serve_fewshot32"):
        buckets |= set(cell(name)["engine"]["inference"]["prefill_lengths"])
    assert buckets <= {s[1] for s in SHORT_SHAPES}
    assert max(buckets) < FLASH_LONG_SEQ


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", SHORT_SHAPES, ids=str)
def test_under_8k_the_rule_is_the_static_default(shape, causal):
    """What every call ran before the rule existed: `BLOCK_Q`, `BLOCK_K`
    fitted to the sequence by the kernel, the backward as the forward,
    on any device."""
    s = shape[1]
    default = (fit_block(FLASH_BLOCK_Q, s), fit_block(FLASH_BLOCK_K, s))
    for kind in (V5E, "cpu", "TPU v4"):
        assert flash_blocks(shape, causal, kind) == (default, default)


def test_train_16k_on_a_v5e_is_the_cell_s_own_pin():
    """`pythia-410m.train_16k` run with its `env` unset is the program
    it is with it set."""
    spec = cell("pythia-410m.train_16k")
    pin = tuple(tuple(int(x) for x in spec["env"][name].split(","))
                for name in ("DS_FLASH_BLOCKS", "DS_FLASH_BWD_BLOCKS"))
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "pythia-410m.json")) as f:
        cfg = json.load(f)
    heads = cfg["num_attention_heads"]
    shape = (1, 16384, heads, cfg["hidden_size"] // heads)
    assert flash_blocks(shape, True, V5E) == pin
    assert FLASH_LONG_SEQ_BLOCKS[(V5E, 64, True)] == pin


@pytest.mark.parametrize("key", list(FLASH_LONG_SEQ_BLOCKS), ids=str)
def test_a_table_row_is_of_the_ladder_and_passes_the_screen(key):
    for blocks in FLASH_LONG_SEQ_BLOCKS[key]:
        assert blocks in FLASH_BLOCK_CANDIDATES
        assert flash_blocks_admitted(*blocks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", LONG_SHAPES, ids=str)
def test_at_8k_and_over_blocks_are_admitted_and_divide(shape, causal):
    s = shape[1]
    for kind in (V5E, "cpu"):
        for bq, bk in flash_blocks(shape, causal, kind):
            assert bq % 128 == 0 and bk % 128 == 0
            assert s % bq == 0 and s % bk == 0
            assert flash_blocks_admitted(bq, bk)


@pytest.mark.parametrize("shape,causal,kind", [
    ((1, 16384, 16, 128), True, V5E),     # head dim 128: no cell, no row
    ((1, 16384, 16, 64), False, V5E),     # non-causal: no cell, no row
    ((1, 16384, 16, 64), True, "TPU v4"),  # a chip nothing was measured on
    ((1, 16384, 16, 64), True, "cpu"),
    ((2, 8320, 8, 256), True, V5E)])
def test_a_shape_class_without_a_row_takes_the_fallback(shape, causal, kind):
    s = shape[1]
    first = next((fit_block(bq, s), fit_block(bk, s))
                 for bq, bk in FLASH_BLOCK_CANDIDATES
                 if flash_blocks_admitted(bq, bk))
    assert flash_blocks(shape, causal, kind) == (first, first)


def test_the_screen_drops_what_a_v5e_refuses():
    """2048 x 1024 ran out of VMEM on a described v5e at every head dim,
    forward and backward (the compile PR 23's cell met on the chip)."""
    assert not flash_blocks_admitted(2048, 1024)
    assert flash_blocks_admitted(1024, 1024)
    assert flash_blocks_admitted(2048, 512)


@pytest.mark.parametrize("shape", SHORT_SHAPES[:2] + LONG_SHAPES[:2],
                         ids=str)
def test_the_rule_is_a_pure_function(shape):
    first = flash_blocks(shape, True, V5E)
    assert all(flash_blocks(shape, True, V5E) == first for _ in range(3))
    # nothing is remembered between calls: another shape and another
    # device in between change nothing
    flash_blocks((1, 32768, 4, 128), False, "cpu")
    assert flash_blocks(shape, True, V5E) == first


def test_a_fresh_process_gives_the_same_blocks():
    shapes = SHORT_SHAPES + LONG_SHAPES
    code = ("from deeperspeed_tpu.ops.autotune import flash_blocks\n"
            f"print([flash_blocks(s, True, {V5E!r}) for s in {shapes!r}])")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               PYTHONHASHSEED="random")
    for name in ("DS_FLASH_BLOCKS", "DS_FLASH_BWD_BLOCKS"):
        env.pop(name, None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    assert out.strip().splitlines()[-1] == \
        str([flash_blocks(s, True, V5E) for s in shapes])


@pytest.mark.parametrize("name,index", [("DS_FLASH_BLOCKS", 0),
                                        ("DS_FLASH_BWD_BLOCKS", 1)])
def test_env_blocks_override(monkeypatch, name, index):
    shape = (1, 256, 2, 64)
    rule = flash_blocks(shape, True)
    monkeypatch.setenv(name, "128,128")
    got = flash_blocks(shape, True)
    assert got[index] == (128, 128)
    assert got[1 - index] == rule[1 - index]
    # at a long sequence too, over the table's row
    assert flash_blocks((1, 16384, 16, 64), True, V5E)[index] == (128, 128)
    # 100 is below the 128 grain — no dividing block fits
    monkeypatch.setenv(name, "100,128")
    with pytest.raises(ValueError, match=name):
        flash_blocks(shape, True)
    monkeypatch.setenv(name, "128;128")
    with pytest.raises(ValueError, match=name):
        flash_blocks(shape, True)


def test_a_sequence_no_block_divides_raises():
    with pytest.raises(ValueError, match="192"):
        flash_blocks((1, 192, 2, 64), True)


# ---------------------------------------------------------------------------
# the kernel's public wrappers take their geometry from the rule
# ---------------------------------------------------------------------------

def _qkv(s=256):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.standard_normal((1, s, 2, 64)), jnp.float32)
            for _ in range(3)]


def _dispatched(fn, *args):
    """(fwd, dkv) blocks the kernels ran with under `jax.grad`."""
    import importlib
    fa = importlib.import_module(
        "deeperspeed_tpu.ops.pallas.flash_attention")
    fa._LAST_BLOCKS.clear()
    jax.grad(lambda q, *rest: fn(q, *rest).sum())(*args)
    return fa._LAST_BLOCKS["fwd"], fa._LAST_BLOCKS["dkv"]


def test_a_wrapper_called_without_blocks_asks_the_rule(monkeypatch):
    from deeperspeed_tpu.ops.pallas import flash_attention
    q, k, v = _qkv()
    # one block holds the whole sequence: the rule's (256, 256)
    assert _dispatched(flash_attention, q, k, v) == ((256, 256), (256, 256))
    monkeypatch.setenv("DS_FLASH_BLOCKS", "128,128")
    assert _dispatched(flash_attention, q, k, v) == ((128, 128), (256, 256))
    monkeypatch.setenv("DS_FLASH_BWD_BLOCKS", "256,128")
    assert _dispatched(flash_attention, q, k, v) == ((128, 128), (256, 128))


def test_a_caller_s_blocks_pin_over_rule_and_environment(monkeypatch):
    from deeperspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_kbias, flash_attention_segmented)
    monkeypatch.setenv("DS_FLASH_BLOCKS", "256,256")
    monkeypatch.setenv("DS_FLASH_BWD_BLOCKS", "256,256")
    q, k, v = _qkv()
    pinned = lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128)
    assert _dispatched(pinned, q, k, v) == ((128, 128), (128, 128))
    both = lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128,
                                           (256, 128))
    assert _dispatched(both, q, k, v) == ((128, 128), (256, 128))
    bwd_only = lambda q, k, v: flash_attention(q, k, v, True,
                                               bwd_blocks=(128, 256))
    assert _dispatched(bwd_only, q, k, v) == ((256, 256), (128, 256))
    seg = jnp.ones((1, 256), jnp.int32)
    segmented = lambda q, k, v: flash_attention_segmented(
        q, k, v, seg, True, None, 128, 128)
    assert _dispatched(segmented, q, k, v) == ((128, 128), (128, 128))
    kbias = lambda q, k, v: flash_attention_kbias(
        q, k, v, jnp.zeros((1, 256)), False, None, 128, 128)
    assert _dispatched(kbias, q, k, v) == ((128, 128), (128, 128))


# ---------------------------------------------------------------------------
# where the decision lives
# ---------------------------------------------------------------------------

def _py_files(*parts):
    for base, _, names in os.walk(os.path.join(PACKAGE, *parts)):
        for name in names:
            if name.endswith(".py"):
                yield os.path.join(base, name)


def _mentions(path, *words):
    with open(path) as f:
        src = f.read()
    return any(word in src for word in words)


@pytest.mark.parametrize("subtree", ["models", "ops/transformer"])
def test_models_and_transformer_ops_import_no_block_picker(subtree):
    for path in _py_files(*subtree.split("/")):
        with open(path) as f:
            src = f.read()
        for node in ast.walk(ast.parse(src)):
            names = []
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [a.name for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            # the one thing a model asks of that module is a shape rule
            # of its own dots, no kernel's blocks
            if names[1:] in (["head_projection_plain"],
                             ["head_projection_split"]):
                continue
            assert not any("autotune" in n for n in names), path
        for word in ("BLOCK_Q", "BLOCK_K", "DS_FLASH_BLOCKS",
                     "DS_FLASH_BWD_BLOCKS"):
            assert word not in src, (path, word)


def test_the_flash_environment_names_are_read_in_one_module():
    readers = sorted(os.path.relpath(path, PACKAGE) for path in _py_files()
                     if _mentions(path, "DS_FLASH_BLOCKS",
                                  "DS_FLASH_BWD_BLOCKS"))
    assert readers == [os.path.join("ops", "autotune.py")]


def test_nothing_in_the_package_times_a_kernel_to_choose_it():
    """`Autotuner` and `ladder_pick` live with their one user, the
    planner's offline whole-step probe."""
    for name in ("Autotuner", "ladder_pick", "tuned_flash_blocks",
                 "autotune_enabled"):
        assert not hasattr(autotune, name)
    users = sorted(os.path.relpath(path, PACKAGE) for path in _py_files()
                   if _mentions(path, "Autotuner", "ladder_pick"))
    assert all(u.startswith("planner" + os.sep) for u in users), users
    with open(os.path.join(PACKAGE, "ops", "autotune.py")) as f:
        imported = {a.name for node in ast.walk(ast.parse(f.read()))
                    if isinstance(node, ast.Import) for a in node.names}
    assert "time" not in imported
