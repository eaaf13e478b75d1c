"""Config validation for the ce_impl / flash_resident knobs: invalid
combinations raise ONE coherent ValueError listing every problem, with
pinned messages (issue round-6 satellite — replaces the scattered
ValueErrors the old use_streaming_ce path raised at loss time)."""

import pytest

from ray_tpu.models.gpt2 import GPT2Config, gpt2_config
from ray_tpu.models.layers import (CE_IMPLS, FLASH_RESIDENT_MODES,
                                   ce_config_problems)
from ray_tpu.models.llama import llama_config

pytestmark = pytest.mark.fast


def test_valid_configs_construct():
    for impl in CE_IMPLS:
        for res in FLASH_RESIDENT_MODES:
            cfg = gpt2_config("nano", ce_impl=impl, flash_resident=res)
            assert cfg.ce_impl == impl
            assert cfg.flash_resident == res


def test_invalid_ce_impl_message():
    with pytest.raises(ValueError) as e:
        gpt2_config("nano", ce_impl="fused")
    msg = str(e.value)
    assert msg.startswith("invalid GPT2Config: ")
    assert ("ce_impl must be one of ('dense', 'streaming_xla', 'pallas') "
            "(got 'fused')") in msg


def test_loss_chunks_with_non_dense_impl():
    with pytest.raises(ValueError) as e:
        gpt2_config("nano", ce_impl="pallas", loss_chunks=4)
    assert ("loss_chunks=4 requires ce_impl='dense' (both bound the "
            "logits footprint; pick one)") in str(e.value)


def test_seq_parallel_with_streaming_impl():
    with pytest.raises(ValueError) as e:
        gpt2_config("nano", ce_impl="streaming_xla", seq_parallel=True)
    assert ("ce_impl='streaming_xla' needs an unsharded seq axis"
            in str(e.value))


def test_invalid_flash_resident_message():
    with pytest.raises(ValueError) as e:
        gpt2_config("nano", flash_resident="yes")
    assert ("flash_resident must be one of ('auto', 'on', 'off') "
            "(got 'yes')") in str(e.value)


def test_all_problems_reported_in_one_error():
    """An invalid combo reports EVERY conflict at once, not just the
    first check to trip."""
    with pytest.raises(ValueError) as e:
        gpt2_config("nano", ce_impl="pallas", loss_chunks=2,
                    seq_parallel=True, flash_resident="maybe")
    msg = str(e.value)
    assert "loss_chunks=2 requires ce_impl='dense'" in msg
    assert "needs an unsharded seq axis" in msg
    assert "flash_resident must be one of" in msg
    assert msg.count(";") >= 2  # three problems joined into one error


def test_use_streaming_ce_alias_normalized():
    cfg = gpt2_config("nano", use_streaming_ce=True)
    assert cfg.ce_impl == "streaming_xla"
    # explicit streaming_xla + the alias is redundant but consistent
    cfg2 = gpt2_config("nano", use_streaming_ce=True,
                       ce_impl="streaming_xla")
    assert cfg2.ce_impl == "streaming_xla"


def test_use_streaming_ce_conflicts_with_pallas():
    with pytest.raises(ValueError) as e:
        gpt2_config("nano", use_streaming_ce=True, ce_impl="pallas")
    assert ("use_streaming_ce is a deprecated alias for "
            "ce_impl='streaming_xla' and conflicts with "
            "ce_impl='pallas'") in str(e.value)


def test_llama_config_validated_too():
    with pytest.raises(ValueError) as e:
        llama_config("nano", ce_impl="fused")
    assert str(e.value).startswith("invalid LlamaConfig: ")
    with pytest.raises(ValueError):
        llama_config("nano", flash_resident="always")
    cfg = llama_config("nano", ce_impl="pallas", flash_resident="on")
    assert cfg.ce_impl == "pallas"


def test_ce_config_problems_is_pure():
    assert ce_config_problems("dense", "auto") == []
    assert ce_config_problems("dense", "auto", loss_chunks=8) == []
    assert len(ce_config_problems("bogus", "bogus")) == 2


def test_frozen_config_still_frozen():
    cfg = GPT2Config()
    with pytest.raises(Exception):
        cfg.ce_impl = "pallas"
