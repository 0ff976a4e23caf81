"""`chip_smoke.py` rehearsed on the CPU, and the one-process rules it
stands on.

The script runs on the chip only and has no option that would let it run
here, so the tests steer it from outside: they call its phase functions
with a tiny model, name the interpreted Pallas decode kernel through the
serving config (`kernel: "pallas"`; on the chip `auto` picks it), and
stand in for `memory_stats()`, which the CPU backend does not have. What
a rehearsal can show is control flow, checks and counts — never a time.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

import chip_smoke
from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# head dim 64 and a 128-multiple sequence: the shapes the flash kernel
# takes, so the dispatch checks mean here what they mean on the chip
TINY = GPTNeoXConfig(vocab_size=512, hidden_size=256, num_layers=2,
                     num_heads=4, max_seq_len=256)


def run_python(*argv, cwd=REPO, env=None, timeout=300):
    """`python *argv` on the CPU with the repo importable and the compile
    cache not placed from outside."""
    cmd = [sys.executable, *argv]
    full_env = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
    full_env["JAX_PLATFORMS"] = "cpu"
    full_env["PYTHONPATH"] = REPO
    full_env.update(env or {})
    return subprocess.run(cmd, cwd=cwd, env=full_env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.fixture(scope="module")
def trained(tmp_path_factory, devices):
    """The train phase's checkpoint and record, shared with the serve
    rehearsal as on the chip."""
    ckpt_dir = str(tmp_path_factory.mktemp("smoke_ckpt"))
    record = chip_smoke.phase_train(TINY, seed=0, batch=8, seq=256, steps=4,
                                    ckpt_dir=ckpt_dir)
    return ckpt_dir, record


def test_train_phase_and_checkpoint_round_trip(trained):
    _, record = trained
    assert record["losses"][-1] < record["losses"][0]
    assert record["loss_after_load"] == record["loss_after_save"]
    assert record["attention_backend"] == "pallas"
    # the whole step is one program, compiled once
    assert record["programs_later_steps"] == 0


def test_serve_phase_agrees_with_plain_decode(trained):
    ckpt_dir, _ = trained
    record = chip_smoke.phase_serve(
        TINY, seed=0, ckpt_dir=ckpt_dir, prompt_lens=(8, 40, 100, 128),
        max_new=6,
        inference={"enabled": True, "page_size": 16, "num_pages": 64,
                   "max_batch_size": 4, "token_budget": 256,
                   "prefill_lengths": [128, 256], "kernel": "pallas"})
    assert record["decode_backend"] == "pallas"
    assert record["prefill_attention_backend"] == "pallas"
    assert record["max_logit_shortfall"] <= chip_smoke.SERVE_LOGIT_MARGIN
    assert record["exact_match_share"] > 0.9


def test_serve_phase_fails_on_a_wrong_token(trained, monkeypatch):
    """The comparison has teeth: a plain decode that disagrees with the
    served tokens fails the phase."""
    ckpt_dir, _ = trained
    real = chip_smoke.reference_logits
    monkeypatch.setattr(chip_smoke, "reference_logits",
                        lambda *a: -real(*a))
    with pytest.raises(chip_smoke.SmokeFailure, match="below the plain"):
        chip_smoke.phase_serve(
            TINY, seed=0, ckpt_dir=ckpt_dir, prompt_lens=(8, 40),
            max_new=4,
            inference={"enabled": True, "page_size": 16, "num_pages": 64,
                       "max_batch_size": 4, "token_budget": 256,
                       "prefill_lengths": [128, 256], "kernel": "pallas"})


def test_multichip_phase_on_four_virtual_devices(devices, monkeypatch):
    # what earlier tests of this worker left live (device 0 holds most of
    # it) is not the phase's: kept referenced, so that no new array takes
    # an old one's id
    before = {id(a): a for a in jax.live_arrays()}

    def live_bytes(device):
        # the CPU backend has no memory_stats(): count the live shards of
        # the arrays made since
        return sum(s.data.nbytes for a in jax.live_arrays()
                   if id(a) not in before
                   for s in a.addressable_shards if s.device == device)

    monkeypatch.setattr(chip_smoke, "bytes_in_use", live_bytes)
    record = chip_smoke.phase_multichip(TINY, seed=0, batch=4, seq=256,
                                        steps=3, devices=devices[:4])
    assert record["mesh"] == {"data": 4}
    assert record["max_rel_loss_diff"] <= chip_smoke.MULTICHIP_LOSS_RTOL
    assert record["sharded_state_bytes"] >= 0.9 * record["state_bytes"]
    assert record["programs_later_steps"] == 0


def test_sharded_state_report_names_an_unsharded_leaf(devices):
    """A leaf that sits whole on one device of a four-device mesh is
    reported, which is what 'device 0 holds everything' would look like."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    even = jax.device_put(jnp.zeros((8, 4)), NamedSharding(mesh, P("data")))
    total, sharded, bad = chip_smoke.sharded_state_report({"w": even}, 4)
    assert (total, sharded, bad) == (even.nbytes, even.nbytes, [])
    two = Mesh(np.asarray(devices[:2]), ("data",))
    lopsided = jax.device_put(jnp.zeros((8, 4)),
                              NamedSharding(two, P("data")))
    _, _, bad = chip_smoke.sharded_state_report({"w": lopsided}, 4)
    assert len(bad) == 1 and "2 devices" in bad[0]


# ---------------------------------------------------------------------------
# the script itself, and the processes around it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("args", [(), ("--multichip",)],
                         ids=["default", "multichip"])
def test_script_exits_nonzero_without_a_tpu(args):
    proc = run_python(os.path.join(REPO, "chip_smoke.py"), *args)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_alone_exits_nonzero(tmp_path):
    """In a directory that holds the script and nothing else of the repo
    (and no installed package to find) it fails and prints no result."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = run_python(str(tmp_path / "chip_smoke.py"), cwd=str(tmp_path),
                      env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_imports_initialise_no_backend():
    """A parent that has touched a jax backend holds the chip, and the
    child it spawns then fails or hangs. Importing the package and the
    launcher (which spawns the workers) must leave the backends alone."""
    proc = run_python(
        "-c",
        "import deeperspeed_tpu\n"
        "import deeperspeed_tpu.launcher.runner\n"
        "import deeperspeed_tpu.launcher.launch\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('clean')\n")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip().endswith("clean")


@pytest.mark.parametrize("placed", [None, "/some/dir"],
                         ids=["unset", "placed_from_outside"])
def test_compile_cache_helper(placed):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads it and no code sets
    another directory; unset, the cache sits at <checkout>/.xla_cache."""
    proc = run_python(
        "-c",
        "import json, jax\n"
        "from deeperspeed_tpu.utils.compile_cache import "
        "configure_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "used = configure_compile_cache()\n"
        "print(json.dumps([before, used, "
        "jax.config.jax_compilation_cache_dir]))\n",
        env={"JAX_COMPILATION_CACHE_DIR": placed} if placed else None)
    assert proc.returncode == 0, proc.stderr
    seen = json.loads(proc.stdout.strip().splitlines()[-1])
    if placed:
        assert seen == [placed, placed, placed]
    else:
        checkout = os.path.join(REPO, ".xla_cache")
        assert seen == [None, checkout, checkout]
