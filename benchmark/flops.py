"""Operations and bytes the kernels' algorithms need, from shapes
alone, and the roofline they are held to.

The yardstick's own arithmetic: nothing here asks the program or the
compiler (``cost_analysis``) what it did.  Recomputed operations
(remat, the second S = QK^T of a split backward kernel) are not counted,
so a share of peak built on these cannot be raised by doing more work.
What follows from a model family's architecture (parameters, FLOPs a
token, bytes a decode step) is in ``benchmark/families/<family>.py``.
"""

from __future__ import annotations

from typing import Dict, Tuple


def flash_unit_flops(bh: int, t: int, hd: int) -> float:
    """One T x T matmul over all batch*heads under the causal mask."""
    return 2.0 * bh * t * t * hd / 2.0


#: T x T matmuls the algorithm needs: forward S and PV; backward S
#: again, dV, dP, dQ, dK.  (The program's backward is two kernels that
#: each recompute S and dP: nine executed, seven needed.)
FLASH_UNITS = {"fwd": 2, "bwd": 5}


def flash_bytes(bh: int, t: int, hd: int, itemsize: int = 2
                ) -> Dict[str, float]:
    """HBM bytes the algorithm needs: forward reads q,k,v and writes o
    and the f32 log-sum-exp; backward reads q,k,v,o,do,lse and writes
    dq,dk,dv."""
    tensor = bh * t * hd * itemsize
    lse = bh * t * 4
    return {"fwd": 4 * tensor + lse, "bwd": 8 * tensor + lse}


def roofline_s(flops: float, nbytes: float, peaks: Dict[str, float]
               ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
