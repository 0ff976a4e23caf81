"""Profile-guided schedule planner: a cost-model-driven search, with an
offline measured probe of whole steps (`Autotuner`, `ladder_pick`).

Fast-lane file (NO `slow` marker): the cost model is pure arithmetic,
plans are JSON files, and the probe phase is exercised with injected
counting probes — nothing here compiles a training step. The engine-
consumption path is covered through `DeepSpeedConfig` directly (the
planner block resolves + overlays before the other blocks parse).
"""

import json
import os

import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.planner import cost_model as cm
from deeperspeed_tpu.planner.plan import (Plan, cached_plan,
                                          latest_plan_fingerprint,
                                          load_plan, plan_fingerprint)
from deeperspeed_tpu.planner.search import (Autotuner, analytic_ladder,
                                            build_plan, candidate_config,
                                            enumerate_candidates,
                                            probes_measurable)
from deeperspeed_tpu.runtime.config import (DeepSpeedConfig,
                                            parse_planner_block)
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError

SHAPE = cm.ModelShape(num_layers=12, hidden_size=768, num_heads=12,
                      seq_len=1024, vocab_size=50304, batch_per_chip=48)
HW = cm.hardware_profile("TPU v5 lite")


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_shape_params_estimate_and_key():
    # 125M-class geometry: embed 50304*768 + 12*12*768^2 ~= 123.6M
    assert 120e6 < SHAPE.params < 130e6
    assert SHAPE.key() == (
        f"l12-h768-a12-s1024-v50304-b48-p{SHAPE.params}")
    pinned = cm.ModelShape(num_layers=12, hidden_size=768, num_heads=12,
                           seq_len=1024, vocab_size=50304,
                           batch_per_chip=48, param_count=125_000_000)
    assert pinned.params == 125_000_000


def test_remat_costs_more_compute_quant_less():
    base = cm.Candidate()
    t0 = cm.compute_time_s(base, SHAPE, HW)
    assert t0 > 0
    t_remat = cm.compute_time_s(cm.Candidate(remat=True), SHAPE, HW)
    assert t_remat == pytest.approx(t0 * cm.REMAT_COMPUTE_FACTOR)
    t_quant = cm.compute_time_s(cm.Candidate(quant_ffn="int8"), SHAPE, HW)
    assert t_quant < t0


def test_collectives_free_at_world_one():
    for mode in ("gspmd", "explicit"):
        assert cm.collective_time_s(cm.Candidate(mode=mode), SHAPE, HW,
                                    world=1) == 0.0
    # and priced beyond it, with deeper prefetch never costing more
    cand = cm.Candidate(mode="explicit", prefetch_depth=1)
    deep = cm.Candidate(mode="explicit", prefetch_depth=4)
    t1 = cm.collective_time_s(cand, SHAPE, HW, world=8)
    t4 = cm.collective_time_s(deep, SHAPE, HW, world=8)
    assert t1 > 0
    assert t4 <= t1


def test_memory_model_remat_and_offload_shrink_residency():
    base = cm.Candidate(mode="explicit")
    m0 = cm.memory_bytes(base, SHAPE, world=8, stage=3)
    assert cm.memory_bytes(cm.Candidate(mode="explicit", remat=True),
                           SHAPE, world=8, stage=3) < m0
    assert cm.memory_bytes(cm.Candidate(mode="explicit", offload="cpu"),
                           SHAPE, world=8, stage=3) < m0
    # offload is never free in time
    assert cm.offload_time_s(cm.Candidate(offload="cpu"), SHAPE, HW,
                             world=8) > 0
    assert cm.offload_time_s(base, SHAPE, HW, world=8) == 0.0


def test_memory_feasible_analytic_none_budget_never_blocks():
    cand = cm.Candidate()
    assert cm.memory_feasible_analytic(cand, SHAPE, world=1,
                                       hbm_limit=None)
    assert not cm.memory_feasible_analytic(cand, SHAPE, world=1,
                                           hbm_limit=1)


# ---------------------------------------------------------------------------
# search: enumerate -> analytic ladder -> probe degrade
# ---------------------------------------------------------------------------

def test_enumerate_collapses_gspmd_knobs_and_gates_quant():
    cands = enumerate_candidates()
    gspmd = {c for c in cands if c.mode == "gspmd"}
    # gspmd has no prefetch/bucket/group axes: one representative per
    # (remat, offload, quant)
    assert all((c.prefetch_depth, c.bucket_mb, c.group_layers)
               == (2, 32.0, 4) for c in gspmd)
    assert len(cands) == len(set(cands))
    no_quant = enumerate_candidates(allow_quant=False)
    assert all(c.quant_ffn is None for c in no_quant)
    no_off = enumerate_candidates(allow_offload=False)
    assert all(c.offload == "none" for c in no_off)


def test_analytic_ladder_ranks_and_screens():
    rungs = analytic_ladder(SHAPE, HW, world=1, top_k=4)
    assert 1 <= len(rungs) <= 4
    steps = [s["step_s"] for _, s in rungs]
    assert steps == sorted(steps)
    # an impossible budget screens everything out -> explicit error,
    # never a silent empty ladder
    hw_tiny = dict(HW, hbm_limit=1)
    with pytest.raises(ValueError, match="memory screen"):
        analytic_ladder(SHAPE, hw_tiny, world=1)


def test_candidate_config_overlay_shape():
    cfg = candidate_config(cm.Candidate(mode="explicit", prefetch_depth=4,
                                        bucket_mb=8.0, group_layers=2,
                                        remat=True, offload="cpu",
                                        quant_ffn="int8"), stage=3)
    sched = cfg["zero_optimization"]["schedule"]
    assert sched == {"mode": "explicit", "prefetch_depth": 4,
                     "bucket_mb": 8.0, "group_layers": 2, "remat": True}
    assert cfg["activation_checkpointing"]["policy"] == "full"
    off = cfg["zero_optimization"]["offload_optimizer"]
    assert off == {"device": "cpu", "buffer_count": 5}
    assert cfg["quantization"]["ffn"]["recipe"] == "int8"
    lean = candidate_config(cm.Candidate(), stage=2)
    assert lean["zero_optimization"]["stage"] == 2
    assert "offload_optimizer" not in lean["zero_optimization"]
    assert "quantization" not in lean
    assert lean["activation_checkpointing"]["policy"] == "none"


def test_probes_measurable_degrades(monkeypatch):
    import importlib
    fa = importlib.import_module(
        "deeperspeed_tpu.ops.pallas.flash_attention")
    assert not probes_measurable(None, None)           # no probe at all
    assert probes_measurable(lambda c: None, True)     # explicit override
    assert not probes_measurable(lambda c: None, False)
    # a probe alone decides, and only where there is an accelerator
    assert not probes_measurable(lambda c: None, None)  # this CPU
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    assert probes_measurable(lambda c: None, None)
    assert not probes_measurable(None, None)


def test_autotuner_picks_fastest_and_caches():
    clock = {"t": 0.0}
    tuner = Autotuner(warmup=0, iters=1, timer=lambda: clock["t"])
    runs = []
    cost = {"a": 5.0, "b": 1.0, "c": 3.0}

    def run(c):
        runs.append(c)
        clock["t"] += cost[c]
        return jnp.zeros(())

    assert tuner.pick("k", ["a", "b", "c"], run) == "b"
    n_runs = len(runs)
    # second call: cached, no new runs
    assert tuner.pick("k", ["a", "b", "c"], run) == "b"
    assert len(runs) == n_runs


def test_autotuner_skips_failing_candidates():
    tuner = Autotuner(warmup=0, iters=1)

    def run(c):
        if c != "ok":
            raise RuntimeError("the engine refused this config")
        return jnp.zeros(())

    assert tuner.pick("k2", ["bad1", "ok", "bad2"], run) == "ok"
    with pytest.raises(RuntimeError):
        tuner.pick("k3", ["bad1", "bad2"], run)


@pytest.mark.parametrize("measurable,hosts,ladder,timed", [
    (True, 1, ["a", "b"], True),
    (False, 1, ["a", "b"], False),     # analytic only
    (True, 2, ["a", "b"], False),      # hosts could disagree
    (True, 1, ["a"], False)])          # nothing to rank
def test_ladder_pick_measures_only_what_it_may(monkeypatch, measurable,
                                               hosts, ladder, timed):
    from deeperspeed_tpu.planner.search import ladder_pick
    monkeypatch.setattr(jax, "process_count", lambda: hosts)
    tuner = Autotuner(warmup=0, iters=1)
    ran = []

    def measure(c):
        ran.append(c)
        return jnp.zeros(())

    pick = ladder_pick("k", ladder, measure, tuner, measurable)
    assert bool(ran) == timed
    if not timed:
        assert pick == ladder[0]
    assert ladder_pick("k", ladder, measure, tuner, measurable) == pick


# ---------------------------------------------------------------------------
# plan persistence
# ---------------------------------------------------------------------------

def _mini_shape():
    return cm.ModelShape(num_layers=2, hidden_size=64, num_heads=4,
                         seq_len=128, vocab_size=512, batch_per_chip=4)


def test_plan_fingerprint_stable_and_tamper_detected(tmp_path):
    payload = {"device_kind": "cpu", "shape_key": "k",
               "config": {"zero_optimization": {"stage": 3}}}
    plan = Plan(payload)
    # re-fingerprinting the fingerprinted payload is a fixed point
    assert plan_fingerprint(plan.payload) == plan.fingerprint
    path = plan.save(path=str(tmp_path / "p.json"))
    assert load_plan(path).fingerprint == plan.fingerprint
    # hand-edited plan: recorded fingerprint no longer matches content
    with open(path) as f:
        tampered = json.load(f)
    tampered["config"]["zero_optimization"]["stage"] = 2
    with open(path, "w") as f:
        json.dump(tampered, f)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        load_plan(path)


def test_cached_plan_tolerates_torn_files(tmp_path):
    assert cached_plan("cpu", "nope", cache_dir=str(tmp_path)) is None
    torn = tmp_path / "plan-cpu-torn.json"
    torn.write_text('{"version": 1, "dev')
    assert cached_plan("cpu", "torn", cache_dir=str(tmp_path)) is None
    assert latest_plan_fingerprint(cache_dir=str(tmp_path)) is None


def test_build_plan_warm_cache_skips_probes(tmp_path):
    shape = _mini_shape()
    calls = []

    def probe(cand):
        calls.append(cand)
        return jnp.zeros(())

    kwargs = dict(device_kind="TPU v5 lite", world=1, top_k=3,
                  probe=probe, measurable=True,
                  cache_dir=str(tmp_path))
    plan = build_plan(shape, tuner=Autotuner(warmup=0, iters=1),
                      **kwargs)
    assert plan.probed
    assert len(calls) >= 2          # a real ladder was raced
    assert os.path.exists(plan.cache_path(cache_dir=str(tmp_path)))
    # warm cache: a fresh tuner + the persisted plan -> ZERO probes
    calls.clear()
    again = build_plan(shape, tuner=Autotuner(warmup=0, iters=1),
                       **kwargs)
    assert calls == []
    assert again.fingerprint == plan.fingerprint
    # force=True replans (and probes again)
    build_plan(shape, tuner=Autotuner(warmup=0, iters=1), force=True,
               **kwargs)
    assert len(calls) >= 2


def test_build_plan_analytic_only_without_probe(tmp_path):
    plan = build_plan(_mini_shape(), device_kind="TPU v5 lite", world=1,
                      cache_dir=str(tmp_path),
                      tuner=Autotuner(warmup=0, iters=1))
    assert not plan.probed
    assert plan.payload["chosen"] in plan.payload["analytic"]["ladder"]
    # the chosen rung is the analytic winner when nothing was measured
    ladder = plan.payload["analytic"]["ladder"]
    best = min(ladder, key=lambda k: ladder[k]["step_s"])
    assert plan.payload["chosen"] == best
    # quant recipes are opt-in: analytic-only planning must not flip
    # training numerics on its own
    assert "quantization" not in plan.config
    assert latest_plan_fingerprint(cache_dir=str(tmp_path)) == \
        plan.fingerprint


# ---------------------------------------------------------------------------
# config plumbing: the strict "planner" block + merge-under overlay
# ---------------------------------------------------------------------------

def _base_cfg():
    return {"train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}


def test_parse_planner_block_strict():
    assert parse_planner_block({}) is None
    with pytest.raises(DeepSpeedConfigError, match="bogus"):
        parse_planner_block({"planner": {"plan_file": "x", "bogus": 1}})
    with pytest.raises(DeepSpeedConfigError, match="plan_file"):
        parse_planner_block({"planner": {"enabled": True}})
    with pytest.raises(DeepSpeedConfigError):
        parse_planner_block({"planner": {"enabled": "yes",
                                         "plan_file": "x"}})
    with pytest.raises(DeepSpeedConfigError):
        parse_planner_block({"planner": []})
    parsed = parse_planner_block({"planner": {"enabled": False}})
    assert parsed["enabled"] is False


def test_missing_plan_file_raises():
    with pytest.raises(DeepSpeedConfigError, match="does not exist"):
        DeepSpeedConfig({**_base_cfg(),
                         "planner": {"plan_file": "/nonexistent/p.json"}})


def test_config_consumes_plan_user_keys_win(tmp_path):
    plan = build_plan(_mini_shape(), device_kind="TPU v5 lite", world=1,
                      cache_dir=str(tmp_path), save=False,
                      tuner=Autotuner(warmup=0, iters=1))
    path = plan.save(path=str(tmp_path / "plan.json"))
    ds = DeepSpeedConfig({**_base_cfg(),
                          "planner": {"plan_file": path}})
    assert ds.planner_plan_fingerprint == plan.fingerprint
    sched = plan.config["zero_optimization"]["schedule"]
    assert ds.zero_config.schedule.mode == sched["mode"]
    assert ds.zero_config.schedule.prefetch_depth == \
        sched["prefetch_depth"]
    # an explicit user key beats the plan (merge-under, never override)
    ds2 = DeepSpeedConfig({**_base_cfg(),
                           "zero_optimization": {
                               "stage": 3,
                               "schedule": {"prefetch_depth": 7}},
                           "planner": {"plan_file": path}})
    assert ds2.planner_plan_fingerprint == plan.fingerprint
    assert ds2.zero_config.schedule.prefetch_depth == 7
    # disabled block: parsed, not applied
    ds3 = DeepSpeedConfig({**_base_cfg(),
                           "planner": {"enabled": False,
                                       "plan_file": path}})
    assert ds3.planner_plan_fingerprint is None
    assert ds3.planner_applied_keys == []


def test_plan_explicit_mode_degrades_for_hookless_model(tmp_path):
    """A plan-provided schedule is advisory: mode "explicit" for a
    model without build_explicit_zero3_loss degrades to gspmd with a
    warning at engine init; a USER-set "explicit" stays a hard error."""
    import deeperspeed_tpu
    from simple_model import SimpleModel
    plan = build_plan(_mini_shape(), device_kind="TPU v5 lite", world=1,
                      cache_dir=str(tmp_path), save=False,
                      tuner=Autotuner(warmup=0, iters=1))
    assert plan.config["zero_optimization"]["schedule"]["mode"] == \
        "explicit"  # default-first tie-break at world=1
    path = plan.save(path=str(tmp_path / "plan.json"))
    model = SimpleModel(hidden_dim=8)
    params = model.init_params(jax.random.PRNGKey(0))
    n = len(jax.devices())
    engine, _, _, _ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2 * n,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "planner": {"plan_file": path}})
    assert engine.plan_fingerprint == plan.fingerprint
    assert engine._config.zero_config.schedule.mode == "gspmd"
    assert engine._explicit_zero3_loss is None
    with pytest.raises(DeepSpeedConfigError,
                       match="build_explicit_zero3_loss"):
        deeperspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_batch_size": 2 * n,
                    "optimizer": {"type": "adam",
                                  "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": 3,
                        "schedule": {"mode": "explicit"}}})


def test_device_kind_mismatch_warns_or_raises(tmp_path):
    payload = dict(build_plan(_mini_shape(), device_kind="TPU v4",
                              world=1, save=False,
                              tuner=Autotuner(warmup=0, iters=1)).payload)
    path = Plan(payload).save(path=str(tmp_path / "v4.json"))
    # default: warn + apply anyway
    ds = DeepSpeedConfig({**_base_cfg(), "planner": {"plan_file": path}})
    assert ds.planner_plan_fingerprint is not None
    with pytest.raises(DeepSpeedConfigError, match="strict_device_match"):
        DeepSpeedConfig({**_base_cfg(),
                         "planner": {"plan_file": path,
                                     "strict_device_match": True}})


# ---------------------------------------------------------------------------
# ds_plan CLI
# ---------------------------------------------------------------------------

def test_ds_plan_cli_json_and_show(tmp_path, capsys, monkeypatch):
    from deeperspeed_tpu.ops import autotune
    from deeperspeed_tpu.planner.cli import main
    # the CLI plans for the chip it runs on, and the CPU has no row in
    # the hardware table (asserted below): the test names the chip
    with pytest.raises(ValueError, match="never a default"):
        main(["--preset", "125m", "--cache-dir", str(tmp_path), "--json"])
    monkeypatch.setattr(autotune, "_device_kind", lambda: "TPU v5 lite")
    rc = main(["--preset", "125m", "--cache-dir", str(tmp_path),
               "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["shape_key"].startswith("l12-h768")
    assert payload["fingerprint"]
    assert payload["config"]["zero_optimization"]["stage"] == 3
    # --show prints the newest cached plan without replanning
    rc = main(["--show", "--cache-dir", str(tmp_path), "--json"])
    assert rc == 0
    shown = json.loads(capsys.readouterr().out)
    assert shown["fingerprint"] == payload["fingerprint"]
    # human-readable mode renders the ladder
    rc = main(["--show", "--cache-dir", str(tmp_path)])
    assert rc == 0
    assert "analytic ladder" in capsys.readouterr().out
    # empty cache: --show reports, exits nonzero
    rc = main(["--show", "--cache-dir", str(tmp_path / "empty")])
    assert rc == 1


def test_ds_plan_cli_requires_shape():
    from deeperspeed_tpu.planner.cli import main
    with pytest.raises(SystemExit, match="shape"):
        main(["--layers", "2"])


def test_env_report_surfaces_plan_fingerprint(tmp_path, monkeypatch):
    from deeperspeed_tpu.env_report import env_fingerprint
    monkeypatch.setenv("DS_PLAN_CACHE", str(tmp_path))
    assert env_fingerprint()["plan_fingerprint"] is None
    plan = build_plan(_mini_shape(), device_kind="TPU v5 lite", world=1,
                      cache_dir=str(tmp_path),
                      tuner=Autotuner(warmup=0, iters=1))
    assert env_fingerprint()["plan_fingerprint"] == plan.fingerprint


# ---------------------------------------------------------------------------
# memory-screen edge cases (the planner's AOT screen inputs)
# ---------------------------------------------------------------------------

class _FakeDevice:
    """Duck-typed jax device for hbm_bytes_limit paths."""

    def __init__(self, platform="tpu", kind="TPU v5 lite", stats=None,
                 raise_stats=False):
        self.platform = platform
        self.device_kind = kind
        self._stats = stats
        self._raise = raise_stats

    def memory_stats(self):
        if self._raise:
            raise RuntimeError("unsupported")
        return self._stats


def test_hbm_bytes_limit_edge_cases():
    from deeperspeed_tpu.ops.autotune import hbm_bytes_limit
    # bytes_limit present -> authoritative, beats the kind table
    dev = _FakeDevice(stats={"bytes_limit": 123})
    assert hbm_bytes_limit(dev) == 123
    # stats dict WITHOUT bytes_limit (some runtimes report only usage):
    # fall through to the per-kind table
    dev = _FakeDevice(stats={"bytes_in_use": 5})
    assert hbm_bytes_limit(dev) == 16 << 30
    # memory_stats raising entirely degrades the same way
    dev = _FakeDevice(raise_stats=True, kind="TPU v4")
    assert hbm_bytes_limit(dev) == 32 << 30
    # non-TPU platform: no budget (screening skipped), never a guess
    assert hbm_bytes_limit(_FakeDevice(platform="cpu", kind="cpu",
                                       stats={})) is None
    # unknown TPU generation: None rather than a wrong number
    assert hbm_bytes_limit(_FakeDevice(kind="TPU v99",
                                       raise_stats=True)) is None


def test_compiled_memory_stats_abstract_only():
    from deeperspeed_tpu.ops.autotune import compiled_memory_stats
    ran = []

    def f(x):
        ran.append(True)
        return jnp.sum(x * x)

    arg = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    stats = compiled_memory_stats(f, (arg,))
    if stats is None:
        pytest.skip("backend provides no memory_analysis()")
    # AOT only: traced for lowering, never executed on real buffers
    assert stats["argument_bytes"] >= 128 * 128 * 4
    assert stats["peak"] >= stats["argument_bytes"]
    assert stats["peak"] == max(
        stats["argument_bytes"] + stats["output_bytes"]
        + stats["temp_bytes"] - stats["alias_bytes"], 0)


def test_memory_feasible_safety_margin_boundary():
    from deeperspeed_tpu.ops.autotune import memory_feasible

    def f(x):
        return x + 1.0

    arg = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    fits, stats = memory_feasible(f, (arg,), budget_bytes=1 << 30)
    assert fits
    if stats is None:
        pytest.skip("backend provides no memory_analysis()")
    peak = stats["peak"]
    # need == budget * safety is the last feasible point ...
    exact = int(-(-peak // 0.92))          # smallest b with b*0.92 >= peak
    assert memory_feasible(f, (arg,), budget_bytes=exact)[0]
    # ... and extra_bytes (resident optimizer state the program cannot
    # see) pushes the same program over the line
    over, _ = memory_feasible(f, (arg,), budget_bytes=exact,
                              extra_bytes=max(1, int(exact * 0.1)))
    assert not over
    # budget_bytes=None (CPU: hbm_bytes_limit is None) never blocks,
    # even with huge extra_bytes
    import deeperspeed_tpu.ops.autotune as at
    if at.hbm_bytes_limit() is None:
        ok, _ = memory_feasible(f, (arg,), extra_bytes=1 << 60)
        assert ok
