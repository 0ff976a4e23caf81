"""The program's device scopes (`deeperspeed_tpu/scopes.py`) and the serve
step's host phases.

- Every scope that a program should contain reaches some `op_name` of
  its compiled text (which is what the profiler copies into a device
  trace's `tf_op`), recomputed work is marked
  `rematted_computation/ds.block`, and the compiled programs are the
  same size with the scopes swapped for `contextlib.nullcontext` (the
  tests swap the helper; the program has no switch).
- Every `pl.pallas_call(` in `deeperspeed_tpu/` passes `name=` with a
  kernel scope of the table (or `ds.kv_write`, the region whose work is
  a kernel on a TPU).
- `engine.stats` keeps the four host phases and `decode_kv_tokens`, and
  the phases are spans of the telemetry block.

CPU, tiny sizes, Pallas in interpret mode: names and counts, never a
time.
"""

import ast
import contextlib
import os
import re

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from deeperspeed_tpu import scopes
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.ops import autotune
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

PACKAGE = os.path.dirname(os.path.abspath(deeperspeed_tpu.__file__))

# head dim 64: the smallest the flash kernels take
CFG = GPTNeoXConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=256)

MODEL_SCOPES = ["ds.embed", "ds.layers", "ds.block", "ds.attn", "ds.mlp"]
MOE_SCOPES = ["ds.moe_route", "ds.moe_dispatch", "ds.moe_combine"]
MOE_CFG = GPTNeoXConfig(
    vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
    max_seq_len=256, rotary_pct=1.0, use_parallel_residual=False,
    norm="rmsnorm", use_bias=False, qk_norm=True, hidden_act="silu",
    ffn_gated=True, ffn_width=64, moe_num_experts=8, moe_top_k=2,
    moe_dropless=True)
# a planned block (Laguna's): window and full layers over grouped KV
# heads, the per-head gate, a held share of the experts and a shared one
PLAN_CFG = GPTNeoXConfig(
    vocab_size=512, hidden_size=128, num_layers=3, num_heads=2,
    max_seq_len=256, use_parallel_residual=False, norm="rmsnorm",
    use_bias=False, hidden_act="silu", ffn_gated=True, ffn_width=64,
    layer_plan=(LayerSpec(attn="full", heads=2, ffn="dense"),
                LayerSpec(attn="window", heads=4, ffn="experts"),
                LayerSpec(attn="window", heads=4, ffn="experts")),
    attn_head_dim=64, num_kv_heads=2, attn_window=32, attn_gate="per-head",
    moe_num_experts=8, moe_top_k=2, moe_dropless=True,
    moe_norm_topk_prob=True, moe_expert_width=64, moe_shared_width=64,
    moe_routing_scale=2.5, moe_held=(0, 4))
PLAN_SCOPES = MODEL_SCOPES + MOE_SCOPES + ["ds.attn_gate", "ds.moe_shared",
                                           "ds.kv_write"]
# a latent plan (GLM-MoE-lite's): low-rank q and kv rows, expanded for
# prefill and absorbed for decode over head-less latent pages, a sigmoid
# router with its bias, a shared expert
LATENT_CFG = GPTNeoXConfig(
    vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
    max_seq_len=256, use_parallel_residual=False, norm="rmsnorm",
    use_bias=False, hidden_act="silu", ffn_gated=True, ffn_width=64,
    layer_plan=(LayerSpec(attn="latent", heads=2, ffn="dense"),
                LayerSpec(attn="latent", heads=2, ffn="experts")),
    attn_head_dim=64, num_kv_heads=2, mla_q_rank=48, mla_kv_rank=128,
    mla_nope_dim=48, mla_rope_dim=16, mla_v_dim=64,
    moe_num_experts=8, moe_top_k=2, moe_dropless=True,
    moe_norm_topk_prob=True, moe_router_score="sigmoid",
    moe_expert_width=64, moe_shared_width=64, moe_routing_scale=1.8)
# a chunk-pooled plan (EvaByte's): every layer exact inside a window of
# 128 and one pooled row a chunk of 4 behind it, a unit-offset norm, eight
# prediction heads
EVA_CFG = GPTNeoXConfig(
    vocab_size=320, hidden_size=128, num_layers=2, num_heads=2,
    max_seq_len=256, use_parallel_residual=False, norm="rmsnorm",
    use_bias=False, hidden_act="silu", ffn_gated=True, ffn_width=64,
    tie_word_embeddings=False,
    layer_plan=(LayerSpec(attn="eva", heads=2, ffn="dense"),) * 2,
    eva_window=128, eva_chunk=4, norm_unit_offset=True, num_pred_heads=8)
LATENT_SCOPES = MODEL_SCOPES + MOE_SCOPES + [
    "ds.mla_q", "ds.mla_kv", "ds.moe_shared", "ds.kv_write"]
PROGRAMS = {
    # the tiled kernels: 256 tokens in blocks of 128; the backward is the
    # one fused kernel
    "train": MODEL_SCOPES + ["ds.flash_fwd", "ds.flash_bwd", "ds.ce_head",
                             "ds.optimizer"],
    # the same with no room for the backward's dq slab: the two kernels a
    # sequence over its budget takes
    "train_two_kernels": ["ds.flash_fwd", "ds.flash_bwd_dq",
                          "ds.flash_bwd_dkv"],
    # one block of 128: the single-block backward
    "train_single_block": ["ds.flash_fwd", "ds.flash_bwd"],
    "train_xla": MODEL_SCOPES + ["ds.attn_xla", "ds.ce_head",
                                 "ds.optimizer"],
    "prefill": MODEL_SCOPES + ["ds.flash_fwd", "ds.kv_write",
                               "ds.lm_head", "ds.sample"],
    "decode": MODEL_SCOPES + ["ds.paged_decode", "ds.kv_write",
                              "ds.lm_head", "ds.sample"],
    "decode_xla": ["ds.paged_decode_xla"],
    # a dropless MoE block (OLMoE's) in the serving programs: the router,
    # the sort dispatch and the combine inside `ds.mlp`
    "moe_prefill": MODEL_SCOPES + MOE_SCOPES + ["ds.flash_fwd",
                                                "ds.kv_write"],
    "moe_decode": MODEL_SCOPES + MOE_SCOPES + ["ds.paged_decode",
                                               "ds.kv_write"],
    # a window layer's kernels run under their own names, beside the
    # full layer's
    "plan_prefill": PLAN_SCOPES + ["ds.flash_fwd", "ds.flash_fwd_window"],
    "plan_decode": PLAN_SCOPES + ["ds.paged_decode",
                                  "ds.paged_decode_window"],
    # a latent layer expands for prefill (the flash kernel the others
    # run) and absorbs for decode (its own kernel)
    # a chunk-pooled layer: the flash forward inside its own region, the
    # pooling a fusion in a prefill and a kernel in a decode step
    "eva_prefill": MODEL_SCOPES + ["ds.eva_prefill", "ds.eva_summarize",
                                   "ds.flash_fwd", "ds.kv_write"],
    "eva_decode": MODEL_SCOPES + ["ds.eva_summarize", "ds.paged_decode",
                                  "ds.kv_write"],
    "latent_prefill": LATENT_SCOPES + ["ds.mla_expand", "ds.flash_fwd"],
    "latent_decode": LATENT_SCOPES + ["ds.mla_absorb",
                                      "ds.paged_decode_latent"],
}


def op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def instruction_count(text):
    return len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", text, re.M))


def train_text(seq, use_pallas):
    model = GPTNeoX(CFG, use_pallas=use_pallas)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config_params={
            "train_batch_size": 8, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "activation_checkpointing": {"policy": "attn_residuals"}})
    tokens = np.zeros((1, 8, seq), np.int32)
    step = engine._build_train_step(1)
    return step.lower(engine.state, (tokens, tokens), engine._next_rng(),
                      engine._current_lr()).compile().as_text()


def serve_texts(kernel, cfg=None):
    model = GPTNeoX(cfg or CFG, use_pallas=True)
    engine = InferenceEngine(
        model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"inference": {
            "enabled": True, "page_size": 16, "num_pages": 40,
            "max_batch_size": 2, "token_budget": 256,
            "prefill_lengths": [128], "prefill_batch_sizes": [1],
            "decode_batch_sizes": [2], "kernel": kernel}})
    rng = jax.random.PRNGKey(0)
    pools = engine._pools()

    def text(fn, tokens, lengths, page_table, *carry):
        tables = dict.fromkeys(engine.caches, page_table)
        if engine.eva_window:
            # a chunk-pooled model's tables (`InferenceEngine._eva_tables`)
            reqs, width = [], page_table.shape[1]
            tables = engine._eva_tables(
                reqs, len(tokens), width, lengths if tokens.ndim == 2
                else None)
        return fn.lower(engine.params, engine.params_stacked, tokens,
                        lengths, tables, pools, rng,
                        *carry).compile().as_text()
    prefill = text(engine._prefill_fn(1, 128), np.zeros((1, 128), np.int32),
                   np.ones((1,), np.int32), np.zeros((1, 8), np.int32))
    decode = text(engine._decode_fn(2), np.zeros((2,), np.int32),
                  np.ones((2,), np.int32),
                  np.zeros((2, engine.n_pages_max), np.int32),
                  # the tokens of the decode in flight, and each row's
                  # place in them (-1: the host's token stands)
                  engine._zero_carry(), np.full((2,), -1, np.int32))
    return prefill, decode


SERVED = {"": None, "moe_": MOE_CFG, "plan_": PLAN_CFG,
          "latent_": LATENT_CFG, "eva_": EVA_CFG}


def lower(program):
    """{program: compiled text} of the one lowering that yields `program`
    (an engine yields its prefill and its decode together)."""
    if program in ("train_xla", "train_single_block"):
        return {program: train_text(128, program == "train_single_block")}
    if program in ("train", "train_two_kernels"):
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("DS_FLASH_BLOCKS", "128,128")
            mp.setenv("DS_FLASH_BWD_BLOCKS", "128,128")
            if program == "train_two_kernels":
                mp.setattr(autotune, "_FLASH_DQ_SLAB_BUDGET", 0)
            return {program: train_text(256, use_pallas=True)}
    if program == "decode_xla":
        return {program: serve_texts("xla")[1]}
    model = program[:program.rindex("_") + 1] if "_" in program else ""
    prefill, decode = serve_texts("pallas", SERVED[model])
    return {model + "prefill": prefill, model + "decode": decode}


class Texts(dict):
    """program -> compiled text, lowered when a test first reads it: a
    case pays for its own program, not the first case for all fifteen."""

    def __init__(self, scoped):
        super().__init__()
        self.scoped = scoped

    def __missing__(self, program):
        with pytest.MonkeyPatch.context() as mp:
            if not self.scoped:
                mp.setattr(scopes, "scope",
                           lambda name: contextlib.nullcontext())
            self.update(lower(program))
        return self[program]


@pytest.fixture(scope="module")
def texts():
    return Texts(scoped=True)


@pytest.fixture(scope="module")
def texts_without_scopes():
    return Texts(scoped=False)


@pytest.mark.parametrize("program,name", [
    (program, name) for program, names in PROGRAMS.items()
    for name in names])
def test_scope_reaches_the_compiled_program(texts, program, name):
    assert name in scopes.SCOPES
    component = re.compile(r"(^|[/(])" + re.escape(name) + r"([/)]|$)")
    assert any(component.search(n) for n in op_names(texts[program])), \
        f"no op_name of the {program} program passes through {name}"


def test_recomputed_work_is_marked(texts):
    names = op_names(texts["train"])
    assert any("rematted_computation/ds.block" in n for n in names)
    # the first pass is not: some block work lies outside the marker
    assert any("ds.block" in n and "rematted_computation" not in n
               for n in names)
    # the kernels keep their names under the transformations around them
    assert any("transpose(jvp" in n and "ds.flash_bwd" in n
               for n in names)
    assert any("transpose(jvp" in n and "ds.flash_bwd_dq" in n
               for n in op_names(texts["train_two_kernels"]))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_change_no_instruction(texts, texts_without_scopes, program):
    assert "ds.block" not in "".join(op_names(texts_without_scopes[program]))
    assert instruction_count(texts[program]) == \
        instruction_count(texts_without_scopes[program]) > 0


def test_an_unknown_scope_is_refused():
    with pytest.raises(KeyError):
        scopes.scope("ds.not_in_the_table")
    with pytest.raises(KeyError):
        scopes.scoped("flash_fwd")
    assert all(kind in ("kernel", "region", "container") and what
               for kind, what in scopes.SCOPES.values())


# -- every pallas_call is named from the table ------------------------------

def pallas_calls():
    """(file:line, the call's `name=` node, the function around it, the
    module) of every `pl.pallas_call(` in the package."""
    found = []
    for dirpath, _, files in os.walk(PACKAGE):
        for fname in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                tree = ast.parse(f.read())

            def visit(node, fn):
                if isinstance(node, ast.FunctionDef):
                    fn = node
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "pallas_call":
                    name = next((k.value for k in node.keywords
                                 if k.arg == "name"), None)
                    found.append((f"{os.path.relpath(path, PACKAGE)}:"
                                  f"{node.lineno}", name, fn, tree))
                for child in ast.iter_child_nodes(node):
                    visit(child, fn)
            visit(tree, None)
    return sorted(found, key=lambda call: call[0])


CALLS = pallas_calls()


def test_all_twenty_one_sites_are_found():
    assert len(CALLS) == 21


@pytest.mark.parametrize("where,name,fn,tree", CALLS,
                         ids=[c[0] for c in CALLS])
def test_pallas_call_is_named_from_the_table(where, name, fn, tree):
    # `ds.kv_write` is a region that is a kernel on a TPU and a scatter
    # off it: the row write's `pallas_call` carries the region's name
    kernels = {n for n, (kind, _) in scopes.SCOPES.items()
               if kind == "kernel"} | {"ds.kv_write"}
    assert name is not None, f"{where}: pallas_call without name="
    if isinstance(name, ast.Constant):
        assert name.value in kernels, f"{where}: {name.value!r}"
        return
    # the name is a parameter of the enclosing function: every call of
    # that function in its module hands in a kernel scope as a literal
    assert isinstance(name, ast.Name), f"{where}: name= is computed"
    params = [a.arg for a in fn.args.args]
    assert name.id in params, f"{where}: {name.id} is not a parameter"
    position = params.index(name.id)
    callers = [c for c in ast.walk(tree) if isinstance(c, ast.Call)
               and isinstance(c.func, ast.Name) and c.func.id == fn.name]
    assert callers, f"{where}: {fn.name} is never called"
    for call in callers:
        given = next((k.value for k in call.keywords if k.arg == name.id),
                     None) or call.args[position]
        # a literal, or a choice among literals (a window or a cross
        # layer's kernel is the full layer's under its own name)
        choices = [given]
        while any(isinstance(c, ast.IfExp) for c in choices):
            choices = [part for c in choices for part in (
                (c.body, c.orelse) if isinstance(c, ast.IfExp) else (c,))]
        assert all(isinstance(c, ast.Constant) and c.value in kernels
                   for c in choices), \
            f"{where}: {fn.name} called at line {call.lineno} without " \
            f"a kernel scope"


# -- the serve step's host phases -------------------------------------------

PHASES = ("build_inputs", "dispatch", "readback", "complete")


def tiny_server(**config):
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(config=cfg, use_pallas=False)
    config["inference"] = {
        "enabled": True, "page_size": 16, "num_pages": 64,
        "max_batch_size": 4, "token_budget": 256,
        "prefill_lengths": [16, 32], "prefill_batch_sizes": [1, 2],
        "decode_batch_sizes": [1, 2, 4]}
    return InferenceEngine(
        model, config=config,
        params=model.init_params(jax.random.PRNGKey(1))), cfg


def test_stats_hold_the_phases_from_construction():
    engine, _ = tiny_server()
    for phase in PHASES:
        assert engine.stats[phase + "_s"] == 0.0
    assert engine.stats["decode_kv_tokens"] == 0
    for counter in ("moe_rows_prefill", "moe_rows_decode",
                    "moe_buffer_rows"):
        assert engine.stats[counter] == 0


def test_phases_and_decode_kv_tokens_of_a_hand_run_schedule():
    engine, cfg = tiny_server()
    rng = np.random.default_rng(0)
    lengths = (5, 11, 17)
    new = 6
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in lengths]
    outs = engine.generate(prompts, max_new_tokens=new)
    assert [len(o) for o in outs] == [new] * len(prompts)
    stats = engine.stats
    for phase in PHASES:
        assert stats[phase + "_s"] > 0.0
    assert sum(stats[p + "_s"] for p in PHASES) <= \
        stats["prefill_s"] + stats["decode_s"]
    # by hand: the prefill samples token 1; decode step j of a request
    # (j = 1 .. new - 1) feeds token j back and attends over the prompt
    # and the j tokens generated so far
    by_hand = sum(n + j for n in lengths for j in range(1, new))
    assert stats["decode_kv_tokens"] == by_hand
    assert stats["decode_tokens"] == len(lengths) * (new - 1)
    # a dense model routes nothing (an MoE's counts by hand:
    # tests/test_olmoe.py)
    assert stats["moe_rows_prefill"] == stats["moe_buffer_rows"] == 0


def test_phases_are_spans_of_the_telemetry_block():
    engine, cfg = tiny_server(telemetry={
        "enabled": True, "goodput": False, "mfu": False, "spans": True})
    engine.generate([[3, 4, 5, 6]], max_new_tokens=3)
    phases = engine.telemetry.tracer.drain_phases()
    for name in PHASES + ("schedule", "prefill", "decode"):
        assert phases.get(name, 0.0) > 0.0, name
    inner = sum(phases[p] for p in PHASES)
    assert inner <= phases["prefill"] + phases["decode"]
    # the counters are the spans' seconds, to the clock reads around them
    for name in PHASES:
        assert engine.stats[name + "_s"] == pytest.approx(
            phases[name], rel=0.2, abs=2e-3)


def test_without_the_block_the_phases_are_the_null_span():
    engine, _ = tiny_server()
    assert engine.telemetry.span("readback") is \
        engine.telemetry.span("decode")
    engine.generate([[3, 4, 5]], max_new_tokens=2)
    assert engine.stats["readback_s"] > 0.0
