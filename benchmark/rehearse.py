"""Walk the command's control flow on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse [cell ...]

Every cell of BENCHMARK.json (or those named), untraced and traced,
through the same ``benchmark.run.run_cell`` as on the chip: the same
drivers, readers and correctness checks.  What differs is data: the
cell's configuration is replaced by the tiny one of its family
(``benchmark/rehearsal/<family>.json``: two layers of width 64) and the
tiny parameters of its driver (``benchmark/rehearsal/<driver>.json``)
are laid over its traffic file.  The Pallas kernels run in interpret
mode and the four-chip cell on four virtual devices.  It checks
``correct`` and the shape of the result and prints no number under a
metric's name: a time from a CPU says nothing about the chip.  The
caller sets ``JAX_PLATFORMS=cpu``; the measuring command itself has no
CPU path.
"""

from __future__ import annotations

import functools
import importlib
import os
import sys

#: never read as a peak: the line's values are not printed
PEAKS = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
SECONDS = "2.5"


def _laid_over(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = (_laid_over(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def tiny_cell(name: str):
    """The cell `name` with its family's tiny configuration and its
    driver's tiny traffic."""
    import dataclasses

    from benchmark.cells import HERE, load_cell, load_json

    cell = load_cell(name)
    family = cell.config["program"]["family"]
    over = load_json(HERE, "rehearsal", cell.traffic["driver"] + ".json")
    return dataclasses.replace(
        cell, config=load_json(HERE, "rehearsal", family + ".json"),
        traffic=_laid_over(cell.traffic, over))


def main(argv=None) -> int:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("rehearse: set JAX_PLATFORMS=cpu; the chip is "
                         "measured by benchmark.run")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    import jax

    from benchmark import harness, run
    from benchmark.cells import load_benchmark

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    flash.flash_attention = functools.partial(flash.flash_attention,
                                              interpret=True)
    names = list(argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in load_benchmark()["workloads"]]
    failed = []
    for name in names:
        cell = tiny_cell(name)
        for trace in ("0", "1"):
            args = run.parse(["--workload", name, "--seed",
                              str(2 ** 31 + 7), "--seconds", SECONDS,
                              "--trace", trace])
            devices = jax.devices()[:cell.chips]
            real = jax.devices
            jax.devices = lambda *a, _d=devices, **k: _d
            try:
                device = harness.require_device(cell.chips, "cpu")
                line = run.run_cell(cell, args, device, PEAKS)
            finally:
                jax.devices = real
            # a CPU run never prints a number under a metric's name
            harness.say("rehearsal", correct=line["correct"],
                        attempted=line["attempted"],
                        failed=line["failed"],
                        metrics=sorted(line["metrics"]),
                        keys=sorted(line))
            if not line["correct"]:
                failed.append((name, trace))
    print("rehearsal", "FAILED " + repr(failed) if failed else "passed",
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
