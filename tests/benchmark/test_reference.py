"""The plain reference against ``ray_tpu.models.gpt2`` at a tiny size,
the correctness comparisons, and the arithmetic kept with the
benchmark."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, flops
from benchmark.cells import HERE, load_family, load_json
from benchmark.reference import gpt2 as reference
from ray_tpu.models import gpt2_config, gpt2_forward, gpt2_init, gpt2_loss


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    params = gpt2_init(jax.random.PRNGKey(3), cfg)
    # layer norms and biases off their initial 1/0, so a swapped one shows
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(
            jax.random.PRNGKey(x.size % 977), x.shape, x.dtype), params)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (3, 33), 0, cfg.vocab_size))
    return cfg, params, tokens


def test_reference_logits_match_the_program(tiny):
    cfg, params, tokens = tiny
    with jax.default_matmul_precision("highest"):
        want = gpt2_forward(params, tokens[:, :-1], cfg)
    got = reference.logits(params, tokens[:, :-1],
                           vocab_size=cfg.vocab_size)
    assert got.shape == (3, 32, cfg.vocab_size)
    np.testing.assert_allclose(got, want[..., :cfg.vocab_size],
                               atol=2e-4, rtol=2e-4)


def test_reference_loss_matches_the_program(tiny):
    cfg, params, tokens = tiny
    with jax.default_matmul_precision("highest"):
        want = float(gpt2_loss(params, {"tokens": tokens}, cfg))
    got = float(reference.loss(params, tokens, vocab_size=cfg.vocab_size))
    assert got == pytest.approx(want, rel=1e-5)


def test_right_padding_cannot_reach_earlier_positions(tiny):
    """Padding to the right must not reach earlier positions (the
    serving check relies on it)."""
    cfg, params, tokens = tiny
    short = reference.logits(params, tokens[:1, :10],
                             vocab_size=cfg.vocab_size)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :10] = tokens[0, :10]
    long = reference.logits(params, padded, vocab_size=cfg.vocab_size)
    np.testing.assert_allclose(long[:, :10], short, atol=1e-5)


@pytest.fixture(scope="module")
def mid():
    """Four layers at GPT-2 small's width: logits of the real scale
    (std 0.55), which a model of width 64 does not have."""
    cfg = gpt2_config("nano", n_layer=4, n_head=12, d_model=768,
                      d_ff=3072, max_seq=128, vocab_size=8192,
                      dtype=jnp.float32, use_flash=False, remat=False)
    params = gpt2_init(jax.random.PRNGKey(3), cfg)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (2, 129), 0, cfg.vocab_size))
    want = np.asarray(reference.logits(params, tokens[:, :-1],
                                       vocab_size=cfg.vocab_size))
    loss = float(reference.loss(params, tokens,
                                vocab_size=cfg.vocab_size))
    return cfg, params, tokens, want.reshape(-1, cfg.vocab_size), loss


def _no_mask(monkeypatch):
    from ray_tpu.ops import attention

    real = attention.reference_attention
    monkeypatch.setattr(
        attention, "reference_attention",
        lambda q, k, v, **kw: real(q, k, v, **{**kw, "causal": False}))


@pytest.mark.parametrize("fault,passes", [
    (None, True), ("bfloat16", True), ("float8_e4m3fn", False),
    ("float8_e5m2", False), ("no_mask", False)])
def test_a_wrong_model_fails_the_tolerances(mid, monkeypatch, fault,
                                            passes):
    """The program's own forward and loss as the system under test,
    held to the reference at the tolerances the cells use: as it is and
    with weights rounded to bf16 it passes; with weights rounded to fp8
    or its causal mask dropped it fails both checks, the token check
    even at the loosest tolerance any cell uses (48 layers: 0.06)."""
    cfg, params, tokens, want, ref_loss = mid
    if fault == "no_mask":
        _no_mask(monkeypatch)
    elif fault:
        params = jax.tree.map(
            lambda x: x.astype(fault).astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(gpt2_forward(params, tokens[:, :-1], cfg))
        sys_loss = float(gpt2_loss(params, {"tokens": tokens}, cfg))
    greedy = got[..., :cfg.vocab_size].argmax(-1).reshape(-1)
    for tol in (correct.logit_tie_tol(12), correct.logit_tie_tol(48)):
        assert correct.check_greedy(want, greedy, tol)["ok"] is passes
    falling = [ref_loss + 0.1, ref_loss]
    res = correct.check_train(sys_loss, ref_loss, falling)
    assert res["ok"] is passes, res


def test_check_greedy_near_ties():
    lg = np.zeros((3, 5), np.float32)
    lg[0, 2], lg[1, 1], lg[2, 4] = 1.0, 1.0, 1.0
    lg[1, 3] = 0.98                      # a near-tie at position 1
    ok = correct.check_greedy(lg, np.array([2, 3, 4]), tol=0.03)
    assert ok["ok"] and ok["identical"] == 2
    assert ok["max_gap"] == pytest.approx(0.02)
    bad = correct.check_greedy(lg, np.array([2, 0, 4]), tol=0.03)
    assert not bad["ok"] and bad["max_gap"] == pytest.approx(1.0)
    assert correct.logit_tie_tol(12) == pytest.approx(0.03)
    assert correct.logit_tie_tol(48) == pytest.approx(0.06)


@pytest.mark.parametrize("losses,sys_loss,ok", [
    ([10.9, 10.8, 10.7, 10.6], 10.90, True),
    ([10.9, 10.8, 10.7, 10.6], 10.898, True),      # 1.9e-4 off
    ([10.9, 10.8, 10.7, 10.6], 10.895, False),     # 4.7e-4 off
    ([10.9, 10.8, 10.7, 10.6], 10.95, False),      # off the reference
    ([10.6, 10.7, 10.8, 10.9], 10.90, False),      # rising
    ([10.9, float("nan"), 10.7, 10.6], 10.90, False),
])
def test_check_train(losses, sys_loss, ok):
    assert correct.check_train(sys_loss, 10.9001, losses)["ok"] is ok


def test_count_failed():
    rows = [{"status": "ok", "tokens": 64, "answered": True},
            {"status": "ok", "tokens": 63, "answered": True},
            {"status": "rejected", "tokens": 0, "answered": False},
            {"status": "active", "tokens": 12, "answered": False}]
    assert correct.count_failed(rows, 64) == 3


@pytest.mark.parametrize("name,total", [("gpt2-124m", 124_439_808),
                                        ("gpt2-xl", 1_557_611_200)])
def test_param_count_is_the_published_one(name, total):
    config = load_json(HERE, "configs", name + ".json")
    assert load_family("gpt2").param_count(config) == total


def test_flash_arithmetic():
    unit = flops.flash_unit_flops(288, 1024, 64)
    assert unit == 288 * 1024 * 1024 * 64
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    b = flops.flash_bytes(288, 1024, 64)
    least, bound = flops.roofline_s(7 * unit, sum(b.values()), peaks)
    assert bound == "compute"
    assert least == pytest.approx(0.6868e-3, rel=1e-3)
    assert flops.roofline_s(1.0, 819e9, peaks) == (1.0, "memory")
    xl = load_json(HERE, "configs", "gpt2-xl.json")
    # every weight once (bf16) plus K/V of the positions attended
    family = load_family("gpt2")
    assert family.decode_step_bytes(xl, 0) == pytest.approx(
        2 * (1_557_611_200 - 1024 * 1600))
    assert family.decode_step_bytes(xl, 10) - family.decode_step_bytes(
        xl, 0) == 10 * 307_200
    assert family.kv_bytes_per_token(xl) == 307_200
    small = load_json(HERE, "configs", "gpt2-124m.json")
    # 6 N + 6 L T d: 8.03e8 operations a token at 1,024
    assert family.train_flops_per_token(small, 1024) == pytest.approx(
        6 * 124_439_808 + 6 * 12 * 1024 * 768)
