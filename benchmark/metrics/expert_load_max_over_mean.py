"""Tokens of the fullest held expert over the held experts' mean, in
the worst expert layer of a decode step, averaged over the decode
programs the engine ran (``benchmark/expert_counters.py``).  1 is an
even load; a dropless layer pays for more in time, not in tokens."""
from benchmark import expert_counters


def read(run):
    decode = expert_counters.means("decode")
    return None if decode is None else decode["load_max_over_mean"]
