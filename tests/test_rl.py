"""Online-RL driver tests (docs/rl.md): the strict "rl" config block,
the PPO-clip/DPO loss registry, RolloutBuffer geometry/scoring, the
zero-recompile weight hot-swap pin, two-engine monitor co-residency,
sampler-state replay, the co-located train+serve E2E loop, and the
mid-iteration kill -> bit-exact resume subprocess drill.

Fast lane (tier-1): everything here — the kill/resume drill runs three
tiny-NeoX subprocesses but stays well inside the tier-1 budget. Run the
RL subset alone with ``-m rl``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeperspeed_tpu
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.rl import (RLDriver, RolloutBuffer, get_rl_loss,
                                token_logprobs)
from deeperspeed_tpu.runtime import constants as c
from deeperspeed_tpu.runtime.config import DeepSpeedConfig, parse_rl_block
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError

pytestmark = pytest.mark.rl

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rl_block(**kw):
    # 8 rollouts -> an update batch of 8 rows under the conftest's 8
    # virtual CPU devices (train_batch_size 8, micro 1 per device); DPO
    # at group_size 2 also lands on 8 rows (one pair per prompt group)
    block = {"enabled": True, "loss": "ppo_clip",
             "rollouts_per_iteration": 8, "group_size": 2,
             "max_new_tokens": 4}
    block.update(kw)
    return block


def _serve_config(**kw):
    block = {"enabled": True, "page_size": 16, "num_pages": 64,
             "max_batch_size": 4, "token_budget": 256,
             "prefill_lengths": [16, 32],
             "prefill_batch_sizes": [1, 2],
             "decode_batch_sizes": [1, 2, 4],
             "temperature": 1.0, "seed": 7}
    block.update(kw)
    return {"inference": block}


def _ds_config(**kw):
    cfg = {"train_batch_size": 8,
           "steps_per_print": 1000,
           "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
           "rl": _rl_block()}
    cfg.update(kw)
    return cfg


def _make_engine(config, seed=1):
    model = GPTNeoX(config=GPTNeoXConfig.tiny(), use_pallas=False)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(seed)),
        config_params=config)
    return engine


def _prompts(n=4, lo=5, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    vocab = GPTNeoXConfig.tiny().vocab_size
    return [list(map(int, rng.integers(1, vocab,
                                       size=int(rng.integers(lo, hi)))))
            for _ in range(n)]


def _reward(prompt, response):
    return float(sum(response) % 7)


# ---------------------------------------------------------------------------
# the strict "rl" config block
# ---------------------------------------------------------------------------

class TestRLConfig:
    def test_absent_and_disabled_are_false(self):
        assert parse_rl_block({}) is False
        assert parse_rl_block({"rl": {"enabled": False}}) is False

    def test_defaults(self):
        p = parse_rl_block({"rl": {"enabled": True}})
        assert p[c.RL_LOSS] == "ppo_clip"
        assert p[c.RL_ROLLOUTS_PER_ITERATION] == 8
        assert p[c.RL_GROUP_SIZE] == 1
        assert p[c.RL_MAX_NEW_TOKENS] == 16
        assert p[c.RL_SEQUENCE_LENGTH] is None
        assert p[c.RL_CLIP_RATIO] == 0.2
        assert p[c.RL_KL_COEF] == 0.05
        assert p[c.RL_BETA] == 0.1
        assert p[c.RL_CHECKPOINT_INTERVAL] == 1

    @pytest.mark.parametrize("block,match", [
        ({"enabled": True, "page_size": 4}, "Unknown"),
        ({"enabled": 1}, "boolean"),
        ({"enabled": True, "loss": "grpo"}, "loss"),
        ({"enabled": True, "rollouts_per_iteration": 0}, ">= 1"),
        ({"enabled": True, "rollouts_per_iteration": 6,
          "group_size": 4}, "multiple"),
        ({"enabled": True, "loss": "dpo"}, "group_size"),
        ({"enabled": True, "sequence_length": 1}, ">= 2"),
        ({"enabled": True, "clip_ratio": 0}, "clip_ratio"),
        ({"enabled": True, "kl_coef": -0.1}, "kl_coef"),
        ({"enabled": True, "beta": True}, "beta"),
        ({"enabled": True, "checkpoint_interval": 0}, ">= 1"),
    ])
    def test_rejects(self, block, match):
        with pytest.raises(DeepSpeedConfigError, match=match):
            parse_rl_block({"rl": block})

    def test_rides_deepspeed_config(self):
        cfg = DeepSpeedConfig(
            {"train_batch_size": 8,
             "rl": {"enabled": True, "loss": "dpo", "group_size": 4,
                    "rollouts_per_iteration": 8}},
            world_size=1)
        assert cfg.rl_enabled
        assert cfg.rl_params[c.RL_LOSS] == "dpo"
        assert cfg.rl_params[c.RL_GROUP_SIZE] == 4
        plain = DeepSpeedConfig({"train_batch_size": 8}, world_size=1)
        assert plain.rl_enabled is False


# ---------------------------------------------------------------------------
# losses: registry + token-logprob math
# ---------------------------------------------------------------------------

class TestLosses:
    def test_registry_unknown_name(self):
        with pytest.raises(DeepSpeedConfigError, match="Unknown RL loss"):
            get_rl_loss("a2c")

    def test_token_logprobs_matches_manual(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(2, 5, 11)),
                             dtype=jnp.float32)
        tokens = jnp.asarray(rng.integers(0, 11, size=(2, 5)), jnp.int32)
        got = np.asarray(token_logprobs(logits, tokens))
        ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        for b in range(2):
            for j in range(4):
                assert got[b, j] == pytest.approx(
                    ref[b, j, int(tokens[b, j + 1])], abs=1e-6)

    def test_ppo_clip_on_policy_is_minus_mean_advantage(self):
        """ratio == 1 and policy == reference: the clip term is inert
        and the KL term zero, so loss == -masked-mean advantage."""
        model = GPTNeoX(config=GPTNeoXConfig.tiny(), use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(0))
        p = parse_rl_block({"rl": _rl_block(kl_coef=0.3)})
        loss_fn = get_rl_loss("ppo_clip")(model, p)
        tokens = np.asarray(
            np.random.default_rng(1).integers(1, 64, size=(4, 8)),
            np.int32)
        logp = np.asarray(token_logprobs(
            model.apply(params, tokens), tokens))
        mask = np.zeros((4, 7), np.float32)
        mask[:, 3:6] = 1.0
        adv = np.asarray([1.0, -1.0, 0.5, 2.0], np.float32)
        batch = {"tokens": tokens, "mask": mask,
                 "behavior_logp": logp, "ref_logp": logp,
                 "advantages": adv}
        got = float(loss_fn(params, batch))
        want = -float((adv[:, None] * mask).sum() / mask.sum())
        assert got == pytest.approx(want, abs=1e-5)

    def test_dpo_zero_margin_is_ln2(self):
        model = GPTNeoX(config=GPTNeoXConfig.tiny(), use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(0))
        p = parse_rl_block({"rl": _rl_block(loss="dpo", beta=0.7)})
        loss_fn = get_rl_loss("dpo")(model, p)
        tokens = np.asarray(
            np.random.default_rng(2).integers(1, 64, size=(4, 8)),
            np.int32)
        logp = np.asarray(token_logprobs(
            model.apply(params, tokens), tokens))
        mask = np.ones((4, 7), np.float32)
        batch = {"tokens": tokens, "mask": mask, "ref_logp": logp}
        assert float(loss_fn(params, batch)) == pytest.approx(
            float(np.log(2.0)), abs=1e-5)


# ---------------------------------------------------------------------------
# RolloutBuffer: geometry, reference scoring, advantages, DPO pairing
# ---------------------------------------------------------------------------

class TestRolloutBuffer:
    def _buffer(self, group_size=2, seq_len=16, loss="ppo_clip"):
        model = GPTNeoX(config=GPTNeoXConfig.tiny(), use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(0))
        p = parse_rl_block({"rl": _rl_block(group_size=group_size,
                                            rollouts_per_iteration=2 *
                                            group_size, loss=loss)})
        return model, params, RolloutBuffer(model, params, p, seq_len)

    def test_pad_and_mask(self):
        _, _, buf = self._buffer()
        rollouts = [{"prompt": [5, 6, 7], "response": [8, 9],
                     "reward": 0.0},
                    {"prompt": [1], "response": [2, 3, 4], "reward": 0.0}]
        tokens, mask = buf.pad(rollouts)
        assert tokens.shape == (2, 16) and mask.shape == (2, 15)
        assert tokens[0, :5].tolist() == [5, 6, 7, 8, 9]
        assert not tokens[0, 5:].any()
        # transitions predicting the generated tokens (positions 3, 4)
        assert mask[0].tolist() == [0, 0, 1, 1] + [0] * 11
        assert mask[1].tolist() == [1, 1, 1] + [0] * 12

    def test_pad_overflow_and_empty_response_raise(self):
        _, _, buf = self._buffer(seq_len=4)
        with pytest.raises(DeepSpeedConfigError, match="sequence_length"):
            buf.pad([{"prompt": [1, 2, 3], "response": [4, 5],
                      "reward": 0.0}])
        with pytest.raises(DeepSpeedConfigError, match="empty response"):
            buf.pad([{"prompt": [1, 2], "response": [], "reward": 0.0}])

    def test_ref_logprobs_match_direct_forward(self):
        model, params, buf = self._buffer()
        tokens, _ = buf.pad([{"prompt": [3, 4], "response": [5, 6],
                              "reward": 0.0}])
        got = buf.ref_logprobs(tokens)
        want = np.asarray(token_logprobs(
            model.apply(params, tokens), tokens))
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_group_normalized_advantages(self):
        _, _, buf = self._buffer(group_size=2)
        adv = buf.advantages([1.0, 3.0, 10.0, 10.0])
        # group 0: centered/scaled; group 1: zero spread -> zeros
        assert adv[0] == pytest.approx(-1.0, abs=1e-3)
        assert adv[1] == pytest.approx(1.0, abs=1e-3)
        assert adv[2] == adv[3] == pytest.approx(0.0, abs=1e-6)

    def test_dpo_pairing_picks_group_extremes(self):
        _, _, buf = self._buffer(group_size=3, loss="dpo")
        rollouts = [{"prompt": [1], "response": [t], "reward": r}
                    for t, r in zip(range(10, 16),
                                    [0.5, 2.0, 1.0, 7.0, 3.0, 9.0])]
        tokens, mask = buf.pad(rollouts)
        ref = buf.ref_logprobs(tokens)
        batch = buf.build_dpo_batch(tokens, mask, ref, [r["reward"]
                                                        for r in rollouts])
        assert batch["tokens"].shape == (4, 16)
        # group 0 (rewards .5, 2, 1): chosen row 1, rejected row 0;
        # group 1 (rewards 7, 3, 9): chosen row 5, rejected row 4
        assert batch["tokens"][0, 1] == 11 and batch["tokens"][1, 1] == 10
        assert batch["tokens"][2, 1] == 15 and batch["tokens"][3, 1] == 14

    def test_state_dict_round_trip(self):
        _, _, buf = self._buffer()
        buf.consumed = 12
        state = buf.state_dict()
        _, _, fresh = self._buffer()
        fresh.load_state_dict(state)
        assert fresh.consumed == 12


# ---------------------------------------------------------------------------
# satellite 1: zero-recompile weight hot-swap (plain + int8 weights)
# ---------------------------------------------------------------------------

class TestHotSwapZeroRecompile:
    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_three_swaps_compile_delta_zero(self, quant):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(1))
        config = _serve_config()
        if quant:
            config["quantization"] = {"weights": quant}
        eng = InferenceEngine(model, config=config, params=params)
        prompts = _prompts(n=3, seed=4)
        eng.generate(prompts, max_new_tokens=4)     # warm the buckets
        warm = eng.compile_count()
        rng = jax.random.PRNGKey(9)
        for i in range(3):
            rng, sub = jax.random.split(rng)
            perturbed = jax.tree_util.tree_map(
                lambda l: l + 0.01 * i if jnp.ndim(l) >= 2 else l, params)
            out = eng.hot_swap_weights(perturbed)
            assert out["compile_delta"] == 0
            eng.generate(prompts, max_new_tokens=4)
            assert eng.compile_count() == warm

    def test_swap_invalidates_prefix_cache(self):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(1))
        eng = InferenceEngine(
            model, config=_serve_config(
                prefix_cache={"enabled": True, "max_pages": 16}),
            params=params)
        prompt = list(range(1, 33))
        eng.generate([prompt, prompt], max_new_tokens=2)
        assert eng.prefix_cache.stats["lookups"] > 0
        assert eng.prefix_cache._root.children   # pages registered
        eng.hot_swap_weights(params)
        # stale-prefix registry dropped: old-weights K/V is unshareable
        assert not eng.prefix_cache._root.children
        assert eng.prefix_cache._pages == 0


# ---------------------------------------------------------------------------
# satellite 2: two co-resident engines, one monitor
# ---------------------------------------------------------------------------

class _RecMonitor:
    def __init__(self):
        self.records = []
        self.closed = False
        self.flushes = 0

    def record(self, sample, scalars):
        self.records.append((sample, dict(scalars)))

    def observe_histogram(self, tag, value, edges=None):
        pass

    def flush(self, drain=True):
        self.flushes += 1

    def close(self):
        self.closed = True

    def tags(self):
        out = set()
        for _, sc in self.records:
            out.update(sc)
        return out


class TestMonitorCoResidency:
    def _serve(self, monitor, **kw):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(1))
        return InferenceEngine(model, config=_serve_config(),
                               params=params, monitor=monitor, **kw)

    def test_borrowed_monitor_survives_drain(self):
        mon = _RecMonitor()
        eng = self._serve(mon, owns_monitor=False)
        eng.generate(_prompts(n=2, seed=5), max_new_tokens=2)
        eng.drain()
        assert not mon.closed          # borrowed: flushed, NOT closed
        assert mon.flushes >= 1
        assert any(t.startswith("Serve/") for t in mon.tags())

    def test_owned_monitor_still_closes(self):
        mon = _RecMonitor()
        eng = self._serve(mon)         # default owns_monitor=True
        eng.drain()
        assert mon.closed

    def test_no_atexit_registration_for_borrowed_monitor(self):
        """The shared TensorBoardMonitor registers its own weak atexit
        close ONCE at construction; a borrowing InferenceEngine must not
        add a second registration (a double-register would close the
        stream under the training engine at interpreter exit)."""
        import atexit
        mon = _RecMonitor()
        seen = []
        orig = atexit.register
        try:
            atexit.register = lambda *a, **kw: seen.append(a) or a[0]
            self._serve(mon, owns_monitor=False)
        finally:
            atexit.register = orig
        assert seen == []

    def test_shared_stream_namespaces_do_not_cross(self, tmp_path):
        """Real monitor, both engines: Train/* keyed by global samples,
        Serve/* keyed by generated tokens, one open event stream; the
        serve drain must leave the training side recordable (no
        record-after-close warning, writer open)."""
        engine = _make_engine(_ds_config(
            tensorboard={"enabled": True, "output_path": str(tmp_path),
                         "job_name": "rl_co"}))
        assert engine.monitor is not None
        driver = RLDriver(engine, _prompts(seed=6), _reward,
                          _serve_config())
        assert driver.serve.monitor is engine.monitor
        driver.run_iteration()
        driver.serve.drain()
        assert engine.monitor.writer is not None   # still open
        engine.monitor.record(engine.global_samples,
                              {"Train/Samples/train_loss": 0.0})
        assert not engine.monitor._warned_closed
        engine.monitor.close()


# ---------------------------------------------------------------------------
# sampler-state replay
# ---------------------------------------------------------------------------

class TestSamplerState:
    def test_round_trip_reproduces_token_stream(self):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(1))
        prompts = _prompts(n=3, seed=8)
        a = InferenceEngine(model, config=_serve_config(), params=params)
        a.generate(prompts, max_new_tokens=4)
        snap = a.sampler_state()
        second = a.generate(prompts, max_new_tokens=4)

        b = InferenceEngine(model, config=_serve_config(), params=params)
        b.restore_sampler_state(snap)
        assert b.generate(prompts, max_new_tokens=4) == second

    def test_state_is_plain_data(self):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        eng = InferenceEngine(model, config=_serve_config(),
                              params=model.init_params(
                                  jax.random.PRNGKey(1)))
        eng.generate(_prompts(n=2, seed=9), max_new_tokens=2)
        state = eng.sampler_state()
        assert state == json.loads(json.dumps(state))


# ---------------------------------------------------------------------------
# the co-located E2E loop
# ---------------------------------------------------------------------------

class TestRLDriverE2E:
    def test_ppo_trains_and_stays_compiled(self):
        engine = _make_engine(_ds_config())
        driver = RLDriver(engine, _prompts(seed=10), _reward,
                          _serve_config())
        stats = driver.train(3)
        assert engine.global_steps == 3
        assert all(np.isfinite(s["loss"]) for s in stats)
        # warmup iteration compiles the bucket ladder; afterwards the
        # swap+rollout cycle must be compile-free
        assert stats[1]["compile_delta"] == 0
        assert stats[2]["compile_delta"] == 0
        assert all(s["swap_ms"] > 0 for s in stats)
        assert driver.buffer.consumed == 24

    def test_dpo_trains(self):
        engine = _make_engine(_ds_config(
            rl=_rl_block(loss="dpo")))
        driver = RLDriver(engine, _prompts(seed=11), _reward,
                          _serve_config())
        stats = driver.train(2)
        assert engine.global_steps == 2
        assert stats[0]["loss"] == pytest.approx(float(np.log(2.0)),
                                                 abs=1e-2)
        assert stats[1]["compile_delta"] == 0

    def test_monitor_gets_train_rl_scalars(self):
        engine = _make_engine(_ds_config())
        mon = _RecMonitor()
        engine.monitor = mon
        driver = RLDriver(engine, _prompts(seed=12), _reward,
                          _serve_config())
        driver.run_iteration()
        tags = mon.tags()
        assert "Train/RL/loss" in tags
        assert "Train/RL/rollout_tokens_per_s" in tags
        assert "Train/RL/swap_ms" in tags
        assert "Train/RL/mean_kl" in tags

    def test_batch_geometry_mismatch_rejected(self):
        engine = _make_engine(_ds_config(train_batch_size=16))
        with pytest.raises(DeepSpeedConfigError, match="train_batch_size"):
            RLDriver(engine, _prompts(), _reward, _serve_config())

    def test_requires_rl_block(self):
        engine = _make_engine({"train_batch_size": 8,
                               "optimizer": {"type": "Adam",
                                             "params": {"lr": 0.01}}})
        with pytest.raises(DeepSpeedConfigError, match="rl"):
            RLDriver(engine, _prompts(), _reward, _serve_config())


class TestEngineHookRejections:
    @pytest.mark.parametrize("extra,match", [
        ({"zero_optimization": {"stage": 3,
                                "schedule": {"mode": "explicit"}}},
         "explicit"),
        ({"zero_optimization": {"stage": 3,
                                "offload_param": {"device": "cpu"}}},
         "offload_param"),
        ({"quantization": {"ffn": {"recipe": "int8"}}},
         "quantization.ffn"),
    ])
    def test_incompatible_modes_fail_at_init(self, extra, match):
        with pytest.raises(DeepSpeedConfigError, match=match):
            _make_engine(_ds_config(**extra))

    def test_zero1_composes(self):
        engine = _make_engine(_ds_config(
            zero_optimization={"stage": 1}))
        driver = RLDriver(engine, _prompts(seed=13), _reward,
                          _serve_config())
        out = driver.run_iteration()
        assert np.isfinite(out["loss"])


# ---------------------------------------------------------------------------
# satellite 3: mid-iteration kill -> bit-exact resume (subprocess drill)
# ---------------------------------------------------------------------------

def _run_worker(workdir, log_name, total, kill):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    for var in ("RANK", "WORLD_SIZE", "LOCAL_RANK", "NODE_RANK",
                "MASTER_ADDR", "MASTER_PORT", "DS_SLOTS"):
        env.pop(var, None)
    worker = os.path.join(REPO_ROOT, "tests", "rl_worker.py")
    return subprocess.run(
        [sys.executable, worker, str(workdir), log_name, str(total),
         str(kill)], env=env, capture_output=True, text=True,
        timeout=420)


def _read_log(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class TestDeterministicResume:
    # three worker processes one after another, each with its own start-up
    # and compiles: 75 s under the driver's six workers (PR 60)
    @pytest.mark.slow
    def test_mid_iteration_kill_resumes_bit_exact(self, tmp_path):
        ref_dir = tmp_path / "ref"
        kill_dir = tmp_path / "kill"
        ref_dir.mkdir()
        kill_dir.mkdir()

        ref = _run_worker(ref_dir, "log.txt", total=4, kill=0)
        assert ref.returncode == 0, ref.stderr[-2000:]
        ref_rows = _read_log(ref_dir / "log.txt")
        assert [r["iteration"] for r in ref_rows] == [1, 2, 3, 4]

        # incarnation 0: os._exit(9) inside iteration 3's reward pass —
        # after rollout generation, before the update, nothing committed
        first = _run_worker(kill_dir, "log.txt", total=4, kill=3)
        assert first.returncode == 9, first.stderr[-2000:]
        killed_rows = _read_log(kill_dir / "log.txt")
        assert [r["iteration"] for r in killed_rows] == [1, 2]

        # incarnation 1: resume from the committed iteration-2 boundary
        # and replay the killed iteration identically
        second = _run_worker(kill_dir, "log.txt", total=4, kill=0)
        assert second.returncode == 0, second.stderr[-2000:]
        all_rows = _read_log(kill_dir / "log.txt")
        assert [r["iteration"] for r in all_rows] == [1, 2, 3, 4]

        # bit-exact: losses AND every sampled rollout token match the
        # uninterrupted reference run, including across the kill point
        for got, want in zip(all_rows, ref_rows):
            assert got == want


# ---------------------------------------------------------------------------
# resume API details
# ---------------------------------------------------------------------------

class TestDriverResume:
    def test_resume_restores_counters_and_sampler(self, tmp_path):
        prompts = _prompts(seed=14)
        engine = _make_engine(_ds_config())
        driver = RLDriver(engine, prompts, _reward, _serve_config(),
                          checkpoint_dir=str(tmp_path))
        driver.train(2)
        snap = driver.serve.sampler_state()

        fresh_engine = _make_engine(_ds_config())
        fresh = RLDriver(fresh_engine, prompts, _reward, _serve_config(),
                         checkpoint_dir=str(tmp_path))
        assert fresh.resume()
        assert fresh.iteration == 2
        assert fresh.cursor == driver.cursor
        assert fresh.serve.sampler_state() == snap
        assert fresh.buffer.consumed == driver.buffer.consumed

    def test_resume_without_checkpoint_returns_false(self, tmp_path):
        engine = _make_engine(_ds_config())
        driver = RLDriver(engine, _prompts(seed=15), _reward,
                          _serve_config(), checkpoint_dir=str(tmp_path))
        assert driver.resume() is False

    def test_ref_snapshot_written_once_and_reloaded(self, tmp_path):
        from deeperspeed_tpu.rl.driver import REF_SNAPSHOT
        prompts = _prompts(seed=16)
        engine = _make_engine(_ds_config())
        driver = RLDriver(engine, prompts, _reward, _serve_config(),
                          checkpoint_dir=str(tmp_path))
        ref_path = tmp_path / REF_SNAPSHOT
        assert ref_path.exists()
        before = ref_path.stat().st_mtime_ns
        driver.train(1)

        fresh_engine = _make_engine(_ds_config())
        RLDriver(fresh_engine, prompts, _reward, _serve_config(),
                 checkpoint_dir=str(tmp_path))
        # trained weights must NOT be re-snapshotted as "reference"
        assert ref_path.stat().st_mtime_ns == before
