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

import dataclasses
import functools
import importlib
import os
import sys

from benchmark.cells import (HERE, ROOT, load_benchmark, load_cell,
                             load_json, tree)

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


def tiny_cell(name: str, bench=None, root: str = ROOT):
    """The cell `name` with its family's tiny configuration and its
    driver's tiny traffic.  `bench` and `root` as ``load_cell`` takes
    them: the family's tiny file lies in the tree the cell does.  A
    ramp the traffic file gives in seconds (``first_send_spread_s``)
    shrinks as the window does, from ``run_seconds`` to SECONDS."""
    bench = load_benchmark(root) if bench is None else bench
    cell = load_cell(name, bench, root)
    family = cell.config["program"]["family"]
    over = load_json(HERE, "rehearsal", cell.traffic["driver"] + ".json")
    traffic = _laid_over(cell.traffic, over)
    if traffic.get("first_send_spread_s"):
        traffic["first_send_spread_s"] *= \
            float(SECONDS) / bench["run_seconds"]
    return dataclasses.replace(
        cell, config=load_json(tree(root, "rehearsal", family + ".json")),
        traffic=traffic)


def four_cpu_devices() -> None:
    """Before JAX is first imported: the four-chip cell walks on four
    virtual devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()


def interpret_kernels() -> None:
    """The Pallas kernels in interpret mode, for this process."""
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    flash.flash_attention = functools.partial(flash.flash_attention,
                                              interpret=True)


def walk(cell, trace: int):
    """One run of the tiny `cell` through ``run.run_cell`` on the CPU's
    first ``cell.chips`` devices; the result line."""
    import jax

    from benchmark import harness, run

    args = run.parse(["--workload", cell.name, "--seed", str(2 ** 31 + 7),
                      "--seconds", SECONDS, "--trace", str(trace)])
    devices = jax.devices()[:cell.chips]
    real = jax.devices
    jax.devices = lambda *a, _d=devices, **k: _d
    try:
        device = harness.require_device(cell.chips, "cpu")
        return run.run_cell(cell, args, device, PEAKS)
    finally:
        jax.devices = real


def main(argv=None) -> int:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("rehearse: set JAX_PLATFORMS=cpu; the chip is "
                         "measured by benchmark.run")
    four_cpu_devices()
    from benchmark import harness

    interpret_kernels()
    names = list(argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in load_benchmark()["workloads"]]
    failed = []
    for name in names:
        cell = tiny_cell(name)
        for trace in (0, 1):
            line = walk(cell, trace)
            # a CPU run never prints a number under a metric's name
            harness.say("rehearsal", correct=line["correct"],
                        attempted=line["attempted"],
                        failed=line["failed"],
                        metrics=sorted(line["metrics"]),
                        keys=sorted(line))
            if not line["correct"]:
                failed.append((name, str(trace)))
    print("rehearsal", "FAILED " + repr(failed) if failed else "passed",
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
