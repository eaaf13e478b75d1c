"""chip_smoke.py's control flow, walked on the CPU at toy size.

The script proves the system on the chip; this proves the script: every
phase runs to its end and checks what it says it checks, and off the
chip the script exits non-zero without ever printing its ``"ok": true``
line.  The steering a chip needs none of happens here, in the test: the
Pallas kernels run in interpret mode, the four-chip phases get four of
conftest's virtual devices, and the runtime phase is told its chip count
(there is no chip for init() to find).
"""

import dataclasses
import functools
import importlib
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TOY = chip_smoke.Size(
    preset="nano", seq=128, batch=4, steps=2,
    cfg={"use_flash": True, "dtype": jnp.float32},
    attn_shapes=((1, 128, 2, 32), (1, 256, 3, 64)),
    ce_shape=(64, 64, 512, 500), scan_shapes=((1, 40, 256, 16),),
    requests=10, warm_requests=8, prefix_groups=2, prefix_len=32,
    tail_mean=6.0, tail_max=16, vocab=500, rate_rps=200.0, max_slots=4,
    new_tokens=8, prefill_bucket=16, mla_preset="nano", mla_max_seq=128,
    mla_tile=16, mla_prefills=((64, 0, 51), (64, 60, 14)),
    mla_wave=(5, 24, 3), moe_rows=((64, 32), (8, 8)), moe_held=6,
    gqa_preset="nano", gqa_max_seq=128, gqa_wave=(5, 24),
    kda_heads=(8, 128), kda_prefills=((96, 13), (160, 70)),
    kda_wave=(6, 3), delta_heads=(5, 96, 192), delta_prefills=((96, 13),),
    delta_wave=(2, 3),
    ring_waves=((3, 5, 32, 8, 2, 128, 64 ** -0.5),
                (2, 4, 16, 16, 2, 128, 128 ** -0.5)),
    banded_layers=((12, 2, 128 ** -0.5, None, 128, 32, (64,)),
                   (8, 2, 64 ** -0.5, 32, None, 32, (64,))),
    banded_tiles=(32, 32))


@pytest.fixture
def interpreted(monkeypatch):
    """Interpret-mode kernels, chosen by the test: the model's flash
    dispatch and the script's own kernel checks."""
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "flash_attention", functools.partial(
        flash.flash_attention, interpret=True))
    monkeypatch.setattr(chip_smoke, "check_kernels", functools.partial(
        chip_smoke.check_kernels, interpret=True))
    monkeypatch.setattr(chip_smoke, "check_mla_kernels", functools.partial(
        chip_smoke.check_mla_kernels, interpret=True))
    monkeypatch.setattr(chip_smoke, "check_gqa_kernels", functools.partial(
        chip_smoke.check_gqa_kernels, interpret=True))
    monkeypatch.setattr(chip_smoke, "check_kda_kernels", functools.partial(
        chip_smoke.check_kda_kernels, interpret=True))
    monkeypatch.setattr(chip_smoke, "check_ring_kernels", functools.partial(
        chip_smoke.check_ring_kernels, interpret=True))
    monkeypatch.setattr(chip_smoke, "check_banded_kernels",
                        functools.partial(chip_smoke.check_banded_kernels,
                                          interpret=True))


@pytest.fixture
def four_devices(monkeypatch):
    devices = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)


def test_train_phase(interpreted):
    out = chip_smoke.phase_train(TOY, "cpu")
    assert out["device"]["platform"] == "cpu"
    assert len(out["losses"]) == TOY.steps


def test_serve_phase(interpreted):
    out = chip_smoke.phase_serve(TOY, "cpu")
    assert out["token_identical"]


def test_mla_phase(interpreted):
    out = chip_smoke.phase_mla(TOY, "cpu")
    assert out["device"]["platform"] == "cpu"


def test_gqa_phase(interpreted):
    out = chip_smoke.phase_gqa(TOY, "cpu")
    assert out["device"]["platform"] == "cpu"


def test_kda_phase(interpreted):
    out = chip_smoke.phase_kda(TOY, "cpu")
    assert out["device"]["platform"] == "cpu"


def test_banded_phase(interpreted):
    out = chip_smoke.phase_banded(TOY, "cpu")
    assert out["device"]["platform"] == "cpu"


def test_ring_phase(interpreted):
    out = chip_smoke.phase_ring(TOY, "cpu")
    assert out["device"]["platform"] == "cpu"


def test_runtime_phase(monkeypatch, tmp_path):
    """The actor is another process, out of reach of an interpret-mode
    patch, so its toy model takes XLA attention."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    size = dataclasses.replace(
        TOY, cfg={"use_flash": False, "dtype": jnp.float32})
    out = chip_smoke.phase_runtime(size, "cpu", num_tpus=1)
    assert out["device"]["platform"] == "cpu"
    assert len(out["losses"]) == 2


@pytest.mark.parametrize("phase,preset", [
    ("mesh_train", "nano"),
    ("tensor_serve", "tiny"),    # four heads: tensor=4 really splits them
    ("fleet", "nano"),
])
def test_four_chip_phase(interpreted, four_devices, phase, preset):
    out = chip_smoke.PHASES[phase](
        dataclasses.replace(TOY, preset=preset), "cpu")
    assert out["device"]["count"] == 4


def test_phase_refuses_another_platform():
    with pytest.raises(SystemExit, match="no accelerator"):
        chip_smoke.phase_train(TOY, "tpu")


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_script_fails_off_the_chip(argv):
    """JAX_PLATFORMS=cpu (as in a sandbox without a chip): a non-zero
    exit and no result line."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *argv],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no accelerator" in proc.stderr
