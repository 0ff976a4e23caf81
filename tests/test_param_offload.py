"""ZeRO-Infinity parameter offload: layer-streamed training (reference:
`deepspeed/runtime/zero/stage3.py:916-935` NVMe param path,
`swap_tensor/partitioned_param_swapper.py:36`).

`offload_param: {device: cpu|nvme}` must actually train — params resting
off-device, streamed through the device segment by segment — with loss
parity against the wired ZeRO-Offload baseline."""

import glob
import os

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError

# passes and fits tier-1's rule (`pyproject.toml`, `slow`); `slow` for the
# whole run's budget alone, with the mechanisms no cell runs (ROADMAP D19)
pytestmark = [pytest.mark.slow, pytest.mark.offload]

STEPS = 4


def _config(extra):
    config = {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    config.update(extra)
    return config


def _engine(extra, seed=0):
    model = GPTNeoX(GPTNeoXConfig.tiny(), use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=_config(extra))
    return engine


def _train(engine, steps=STEPS, gas=1, seed=1):
    rng = np.random.default_rng(seed)
    V = engine.module_obj.config.vocab_size
    losses = []
    for _ in range(steps):
        toks = rng.integers(0, V, (gas, 16 // gas, 32), np.int32)
        losses.append(float(engine.train_batch(batch=(toks, toks))))
    return np.asarray(losses)


OFFLOAD_BASE = {"zero_optimization": {
    "stage": 2, "offload_optimizer": {"device": "cpu"}}}
PARAM_CPU = {"zero_optimization": {
    "stage": 3, "offload_optimizer": {"device": "cpu"},
    "offload_param": {"device": "cpu"}}}


@pytest.fixture(scope="module")
def baseline():
    return _train(_engine(OFFLOAD_BASE))


def test_param_offload_cpu_matches_offload_baseline(baseline, devices):
    """Streaming params from host must not change the math: same host
    CPU-Adam, same forward — trajectory parity with ZeRO-Offload."""
    engine = _engine(PARAM_CPU)
    got = _train(engine)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)
    # params really are host-resident numpy, not device arrays
    leaf = jax.tree_util.tree_leaves(engine.state.params)[0]
    assert isinstance(leaf, np.ndarray)


def test_param_offload_grad_accumulation(baseline, devices):
    cfg = dict(PARAM_CPU)
    cfg["gradient_accumulation_steps"] = 2
    got = _train(_engine(cfg), gas=2)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)


def test_param_offload_nvme(tmp_path, baseline, devices):
    """NVMe tier: segment files appear under the swap dir and training
    reads through them with unchanged results."""
    cfg = {"zero_optimization": {
        "stage": 3, "offload_optimizer": {"device": "cpu"},
        "offload_param": {"device": "nvme", "nvme_path": str(tmp_path)}}}
    engine = _engine(cfg)
    swp = glob.glob(os.path.join(str(tmp_path), "zero_stage_3", "*.swp"))
    assert len(swp) == engine.module_obj.config.num_layers + 2  # e,b*,h
    got = _train(engine)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)


def test_param_offload_eval_batch(devices):
    engine = _engine(PARAM_CPU)
    rng = np.random.default_rng(0)
    V = engine.module_obj.config.vocab_size
    toks = rng.integers(0, V, (16, 32), np.int32)
    loss = float(engine.eval_batch((toks, toks)))
    assert np.isfinite(loss) and loss > 0


def test_param_offload_checkpoint_roundtrip(tmp_path, devices):
    engine = _engine(PARAM_CPU)
    _train(engine, steps=2)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    ref = _train(engine, steps=2, seed=7)

    engine2 = _engine(PARAM_CPU, seed=5)
    engine2.load_checkpoint(str(tmp_path / "ckpt"))
    got = _train(engine2, steps=2, seed=7)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_param_offload_gathered_parameters_updates_store(devices):
    """Mutations under gathered_parameters land in the host param store
    (the next streamed forward must see them) without materializing the
    full tree on device."""
    engine = _engine(PARAM_CPU)
    with engine.gathered_parameters(modifier_rank=0) as full:
        full["final_ln"]["scale"][:] = 2.5
    # host store updated in place; state.params still the numpy store
    leaf = engine.state.params["final_ln"]["scale"]
    assert isinstance(leaf, np.ndarray)
    np.testing.assert_allclose(np.asarray(leaf, np.float32), 2.5)
    # and the streamed forward consumes the edit
    rng = np.random.default_rng(0)
    V = engine.module_obj.config.vocab_size
    toks = rng.integers(0, V, (16, 32), np.int32)
    loss = float(engine.eval_batch((toks, toks)))
    assert np.isfinite(loss)


def test_param_offload_train_steps_raises(devices):
    engine = _engine(PARAM_CPU)
    with pytest.raises(RuntimeError, match="offload_param"):
        engine.train_steps(np.zeros((2, 1, 16, 32), np.int32))


def test_param_offload_requires_optimizer_offload(devices):
    with pytest.raises(DeepSpeedConfigError, match="offload_optimizer"):
        _engine({"zero_optimization": {
            "stage": 3, "offload_param": {"device": "cpu"}}})


def test_param_offload_requires_stream_plan(devices):
    def plain_loss(params, batch, rng):
        x, y = batch
        return ((x @ params["w"]).sum() - y.sum()) ** 2

    with pytest.raises(DeepSpeedConfigError, match="stream_plan"):
        deeperspeed_tpu.initialize(
            model=plain_loss,
            model_parameters={"w": np.zeros((4, 4), np.float32)},
            config_params=_config({"zero_optimization": {
                "stage": 3, "offload_optimizer": {"device": "cpu"},
                "offload_param": {"device": "cpu"}}}))


NVME = lambda p: {"zero_optimization": {  # noqa: E731
    "stage": 3, "offload_optimizer": {"device": "cpu"},
    "offload_param": {"device": "nvme", "nvme_path": str(p)}}}


def test_param_offload_nvme_is_store_of_record(tmp_path, baseline,
                                               devices):
    """The NVMe tier keeps NO DRAM mirror (reference
    `partitioned_param_swapper.py:36,238-304`): after init the
    coordinator holds only shape/dtype templates, state.params leaves
    are zero-strided placeholders, gradients accumulate in per-segment
    NVMe files, and reads assemble through the swapper — so capacity is
    bounded by NVMe, not DRAM."""
    engine = _engine(NVME(tmp_path))
    assert engine._host_param_leaves is None
    assert engine._coord._host is None
    for leaf in jax.tree_util.tree_leaves(engine.state.params):
        assert isinstance(leaf, np.ndarray)
        assert all(s == 0 for s in leaf.strides), "placeholder must be " \
            "a zero-strided view (no model-sized DRAM)"
    got = _train(engine)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)
    # per-segment grad spill files exist
    assert glob.glob(os.path.join(str(tmp_path), "grads", "**", "*.swp"),
                     recursive=True)
    # export reads assemble real values from NVMe
    nat = engine.params_to_natural(engine.state.params)
    emb = np.asarray(jax.tree_util.tree_leaves(nat["embed"])[0],
                     np.float32)
    assert np.isfinite(emb).all() and np.abs(emb).sum() > 0
    # gathered-parameters write-back reaches the NVMe store
    with engine.gathered_parameters(modifier_rank=0) as full:
        full["final_ln"]["scale"][:] = 2.5
    nat = engine.params_to_natural(engine.state.params)
    np.testing.assert_allclose(
        np.asarray(nat["final_ln"]["scale"], np.float32), 2.5)


def test_param_offload_nvme_grad_accumulation(tmp_path, baseline,
                                              devices):
    cfg = NVME(tmp_path)
    cfg["gradient_accumulation_steps"] = 2
    got = _train(_engine(cfg), gas=2)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)


def test_param_offload_nvme_checkpoint_roundtrip(tmp_path, devices):
    cfg = NVME(tmp_path / "swap")
    engine = _engine(cfg)
    _train(engine, steps=2)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    ref = _train(engine, steps=2, seed=7)

    engine2 = _engine(NVME(tmp_path / "swap2"), seed=5)
    engine2.load_checkpoint(str(tmp_path / "ckpt"))
    got = _train(engine2, steps=2, seed=7)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# offline export (round-4 VERDICT #5): streamed-NVMe ckpt → fp32 state dict
# ---------------------------------------------------------------------------

def _export_keys_match(sd, engine):
    from deeperspeed_tpu.checkpoint.serialization import _path_key
    nat = engine.params_to_natural(engine.state.params)
    flat, _ = jax.tree_util.tree_flatten_with_path(nat)
    assert set(sd) == {_path_key(p) for p, _ in flat}
    return flat


def test_streamed_ckpt_zero_to_fp32_dram_masters(tmp_path, devices):
    """NVMe param store + DRAM optimizer tier: the export reads the
    exact fp32 masters out of the checkpoint meta."""
    from deeperspeed_tpu.checkpoint.serialization import _path_key
    from deeperspeed_tpu.utils.zero_to_fp32 import (
        get_fp32_state_dict_from_zero_checkpoint)
    engine = _engine(NVME(tmp_path / "swap"))
    _train(engine, steps=2)
    engine.save_checkpoint(str(tmp_path / "ckpt"), tag="t")
    sd = get_fp32_state_dict_from_zero_checkpoint(
        str(tmp_path / "ckpt" / "t"))
    flat = _export_keys_match(sd, engine)
    # exact fp32 masters, not upcast bf16 params
    masters = engine._host_state["master"]
    for gid, (path, leaf) in enumerate(flat):
        np.testing.assert_array_equal(
            sd[_path_key(path)].ravel(), masters[gid],
            err_msg=_path_key(path))


def test_streamed_ckpt_zero_to_fp32_nvme_masters_and_fallback(
        tmp_path, devices):
    """NVMe param + NVMe optimizer tier: export reads the raw master
    files; with the master files gone it falls back to upcasting the
    param segments (close to masters within the compute dtype)."""
    import os as _os
    from deeperspeed_tpu.checkpoint.serialization import _path_key
    from deeperspeed_tpu.utils.zero_to_fp32 import (
        get_fp32_state_dict_from_zero_checkpoint)
    cfg = {"zero_optimization": {
        "stage": 3,
        "offload_optimizer": {"device": "nvme",
                              "nvme_path": str(tmp_path / "opt")},
        "offload_param": {"device": "nvme",
                          "nvme_path": str(tmp_path / "swap")}}}
    engine = _engine(cfg)
    _train(engine, steps=2)
    ckpt = tmp_path / "ckpt"
    engine.save_checkpoint(str(ckpt), tag="t")
    sd = get_fp32_state_dict_from_zero_checkpoint(str(ckpt / "t"))
    flat = _export_keys_match(sd, engine)
    for gid, (path, leaf) in enumerate(flat):
        g = engine._host_swapper.load_group(gid)
        np.testing.assert_array_equal(
            sd[_path_key(path)].ravel(), g["master"],
            err_msg=_path_key(path))

    # drop the master files → segment-upcast fallback
    for f in glob.glob(str(ckpt / "t" / "opt_*_master.swp")):
        _os.remove(f)
    sd2 = get_fp32_state_dict_from_zero_checkpoint(str(ckpt / "t"))
    _export_keys_match(sd2, engine)
    for path, leaf in flat:
        key = _path_key(path)
        np.testing.assert_allclose(sd2[key], sd[key], rtol=1e-2,
                                   atol=1e-2, err_msg=key)


def test_streamed_ckpt_partial_masters_refused(tmp_path, devices):
    """A truncated master set must error, not silently downgrade to the
    lossy param upcast."""
    import os as _os
    from deeperspeed_tpu.utils.zero_to_fp32 import (
        get_fp32_state_dict_from_zero_checkpoint)
    cfg = {"zero_optimization": {
        "stage": 3,
        "offload_optimizer": {"device": "nvme",
                              "nvme_path": str(tmp_path / "opt")},
        "offload_param": {"device": "nvme",
                          "nvme_path": str(tmp_path / "swap")}}}
    engine = _engine(cfg)
    _train(engine, steps=1)
    ckpt = tmp_path / "ckpt"
    engine.save_checkpoint(str(ckpt), tag="t")
    _os.remove(str(ckpt / "t" / "opt_0_master.swp"))
    with pytest.raises(RuntimeError, match="incomplete"):
        get_fp32_state_dict_from_zero_checkpoint(str(ckpt / "t"))
