"""``chip_smoke.py`` rehearsed on the CPU (rehearsal 1 of the
on-chip-measurement guide): the same phase functions the chip run drives,
at a toy size, with the pallas kernel in interpret mode — and the proof that
the script refuses anything that is not a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.abspath(chip_smoke.__file__))
TOY = {"vocab_size": 512, "max_seq_len": 128, "num_layers": 2,
       "num_heads": 2, "embed_dim": 128}


@pytest.fixture
def records():
    out = []
    yield out
    print("\n".join(json.dumps(r)[:400] for r in out))


def _train(records, attention_impl):
    # node processes inherit conftest's 8 virtual CPU devices
    return chip_smoke.train_phase(
        TOY, expected_platform="cpu", attention_impl=attention_impl,
        batch_size=8, num_steps=5, parity_shape=(1, 2, 256, 64), seed=0,
        emit=records.append,
    )


def test_train_phase_at_toy_size(records):
    device = _train(records, "flash_interpret")
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}
    run = next(r for r in records if "losses" in r)
    assert len(run["losses"]) == 5 and run["checkpoint_bytes"] > 0
    assert run["attention_parity"]["impl"] == "flash_interpret"
    assert run["attention_parity"]["auto_resolves_to"] == "xla"  # on cpu
    assert all(run["node"]["native_libs"].values())
    assert records[-1] == {"phase": "train", "ok": True,
                           "wall_seconds": records[-1]["wall_seconds"]}


def test_kernel_that_does_not_compile_fails_the_phase(records):
    """The Mosaic kernel cannot lower on the CPU backend: asked for it by
    name, the train worker raises and the phase fails — no fallback."""
    with pytest.raises(Exception) as err:
        _train(records, "flash")
    assert not any(r.get("ok") for r in records), err.value


def test_serve_phase_at_toy_size(records):
    chip_smoke.serve_phase(
        TOY, expected_platform="cpu", max_batch_slots=4,
        prefill_buckets=(32, 64), max_tokens=8, emit=records.append,
    )
    run = next(r for r in records if "completion_tokens" in r)
    assert run["completion_tokens"] == [8] * 6
    assert run["replica"]["platform"] == "cpu"
    assert run["first_tokens"][0] == run["first_tokens"][1]
    assert records[-1]["ok"] is True


def test_sharded_phase_at_toy_size(records):
    """``--chips 4``'s phase on virtual devices (rehearsal 2): the flash
    kernel inside the fsdp x tensor sharded jit, against one device."""
    device = chip_smoke.sharded_phase(
        TOY, expected_platform="cpu", attention_impl="flash_interpret",
        mesh={"data": 2, "fsdp": 2, "tensor": 2}, batch_size=8, num_steps=4,
        parity_shape=(4, 2, 256, 64), seed=0, emit=records.append,
    )
    assert device["count"] == 8
    run = next(r for r in records if "sharded_losses" in r)
    assert run["max_loss_diff"] <= chip_smoke.SHARDED_LOSS_TOL
    assert run["attention_parity"]["mesh"] == {"data": 2, "fsdp": 2,
                                               "tensor": 2}
    assert run["attention_parity"]["devices_holding_out"] == 8
    assert records[-1]["ok"] is True


@pytest.mark.parametrize("fake_chip", [False, True],
                         ids=["no_chip", "chip_resource_but_cpu_jax"])
def test_main_exits_nonzero_off_the_chip(tmp_path, fake_chip):
    """``main()`` accepts only a TPU. With no chip it stops before starting
    anything; with a node that was granted ``TPU`` while JAX runs on the CPU
    the train worker raises AcceleratorMismatchError instead of training
    there. Either way: non-zero exit, and no ``"ok": true`` line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)  # own outputs
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("RT_SESSION_DIR", None)
    if fake_chip:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,1,1"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert '"ok": true' not in proc.stdout
    if fake_chip:
        assert "AcceleratorMismatchError" in proc.stderr, proc.stderr[-3000:]
    else:
        assert "exposes 0" in proc.stderr
