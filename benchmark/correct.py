"""The comparisons that decide ``correct``.  Each runs outside the
measured window, on what the reference can hold."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import numpy as np

#: The system's loss (bf16 compute, flash attention) on the first
#: batch's rows against the float32 reference, relative.  Measured on
#: the chip (PR 24's 66 train runs, 124M on one chip and XL on four):
#: 5e-7 to 4.6e-5, PR 22 saw 3e-6; 2e-4 is four times the largest.
#: The loss is taken at random initialisation, where it is ln V + 0.17
#: whatever the model does, so the tolerance has to be this tight to
#: see anything: weights rounded to fp8 move it by 9e-4 to 1.1e-3 (they
#: would pass 2e-3) and a dropped causal mask by 4e-3
#: (tests/benchmark/test_reference.py
#: test_a_wrong_model_fails_the_tolerances holds both to this number).
LOSS_RTOL = 2e-4
#: Where the engine's greedy token is not the reference's argmax, the
#: reference's own logit for it must be this close to its maximum: a
#: near-tie.  chip_smoke.py derived 0.03 for twelve layers (bf16
#: rounding puts ~5e-3 on a logit through 12 layers; random weights give
#: nearly flat logits with top-two gaps of ~0.1); rounding noise adds in
#: quadrature over layers, so the tolerance grows with sqrt(layers/12):
#: 0.06 for the XL's 48.  What backs it: the XL's engine on the chip
#: answered 25 distinct checked requests (PR 24) with a gap of 0 in 19
#: and 0.004, 0.019, 0.022, 0.022, 0.025 and 0.026 in six, so a flat
#: 0.03 stands 14% over the largest of 25 and a check with 60 more seeds
#: would sooner or later call a correct engine wrong; weights rounded
#: to fp8 leave gaps of 0.20 to 0.28 and a dropped mask 2.1 (the same
#: test, at the loosest tolerance any cell uses).
LOGIT_TIE_TOL_12_LAYERS = 0.03


def logit_tie_tol(n_layer: int) -> float:
    return LOGIT_TIE_TOL_12_LAYERS * math.sqrt(max(n_layer, 12) / 12.0)


def check_train(system_loss: float, reference_loss: float,
                losses: Sequence[float]) -> Dict[str, Any]:
    """Loss against the reference; every loss finite; the mean of the
    last 8 below the mean of the first 8."""
    rel = abs(system_loss - reference_loss) / abs(reference_loss)
    finite = all(math.isfinite(x) for x in losses)
    k = min(8, len(losses) // 2)
    falls = k > 0 and (sum(losses[-k:]) / k) < (sum(losses[:k]) / k)
    return {"ok": bool(rel <= LOSS_RTOL and finite and falls),
            "loss_system": system_loss, "loss_reference": reference_loss,
            "rel_diff": rel, "rtol": LOSS_RTOL, "all_finite": finite,
            "falls": falls, "first_mean": sum(losses[:k]) / max(k, 1),
            "last_mean": sum(losses[-k:]) / max(k, 1)}


def check_greedy(ref_logits: np.ndarray, engine_tokens: np.ndarray,
                 tol: float) -> Dict[str, Any]:
    """ref_logits (G, V): the reference's logits at the G generated
    positions, teacher-forced on the engine's own tokens;
    engine_tokens (G,).  Every engine token must be the argmax or
    within `tol` of it."""
    ref_logits = np.asarray(ref_logits, np.float32)
    engine_tokens = np.asarray(engine_tokens)
    top = ref_logits.max(axis=-1)
    mine = ref_logits[np.arange(len(engine_tokens)), engine_tokens]
    gaps = top - mine
    return {"ok": bool(np.all(gaps <= tol)),
            "identical": int(np.sum(gaps == 0)), "of": len(gaps),
            "max_gap": float(gaps.max()), "tol": tol}


def reference_generated_logits(reference, params, out_tokens: np.ndarray,
                               prompt_len: int, *, vocab_size: int,
                               max_seq: int, **stated) -> np.ndarray:
    """The reference's logits for each generated position of one
    answered request (prompt + continuation), in one forward pass.  The
    sequence is right-padded to max_seq: under the causal mask padding
    cannot reach an earlier position, and one shape compiles once.
    `stated` is what the family reads from the configuration for its
    reference (``Cell.reference_kwargs``)."""
    n = len(out_tokens)
    padded = np.zeros((1, max_seq), np.int32)
    padded[0, :n] = out_tokens
    lg = reference.logits(params, padded, vocab_size=vocab_size, **stated)
    # logits at position i predict token i+1
    return np.asarray(lg[0, prompt_len - 1:n - 1])


def count_failed(rows: List[Dict[str, Any]], new_tokens: int) -> int:
    """Measured requests that were shed, errored, unfinished, or
    answered with other than their full count of tokens."""
    return sum(1 for r in rows
               if r.get("status") != "ok" or r.get("tokens") != new_tokens
               or not r.get("answered"))
