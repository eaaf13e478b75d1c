"""Release-suite runner: executes release_tests.yaml entries and grades
their JSON-line outputs against pass criteria.

Role-equivalent of the reference's ray_release harness
(``release/ray_release/glue.py:75 run_release_test`` over
``release/release_tests.yaml``) collapsed to one file: each workload is
a subprocess; its stdout JSON lines become a metrics dict; criteria
like ``<metric>_min`` / ``<metric>_max`` / exact-match keys decide
pass/fail.  Exit code = number of failed tests.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_yaml(path: str) -> dict:
    """Tiny structured-subset YAML loader (no pyyaml dependency): the
    suite file uses two-space indents, scalars, and '- name:' lists."""
    tests = []
    cur = None
    in_criteria = None
    with open(path) as f:
        for raw in f:
            line = raw.split("#", 1)[0].rstrip()
            if not line.strip():
                continue
            if line.startswith("tests:"):
                continue
            if line.strip().startswith("- name:"):
                cur = {"name": line.split(":", 1)[1].strip(),
                       "pass_criteria": {}}
                tests.append(cur)
                in_criteria = None
                continue
            if cur is None:
                continue
            key, _, val = line.strip().partition(":")
            val = val.strip()
            if key in ("pass_criteria", "fast_pass_criteria"):
                in_criteria = key
                cur.setdefault(key, {})
                continue
            if in_criteria and line.startswith("      "):
                cur[in_criteria][key] = _coerce(val)
            else:
                in_criteria = False
                cur[key] = _coerce(val)
    return {"tests": tests}


def _coerce(v: str):
    if v in ("true", "false"):
        return v == "true"
    try:
        return int(v)
    except ValueError:
        try:
            return float(v)
        except ValueError:
            return v


def _grade(metrics: dict, criteria: dict) -> list:
    failures = []
    for crit, bound in criteria.items():
        if crit.endswith("_min"):
            name = crit[:-4]
            got = metrics.get(name)
            if got is None or got < bound:
                failures.append(f"{name}={got} < required {bound}")
        elif crit.endswith("_max"):
            name = crit[:-4]
            got = metrics.get(name)
            if got is None or got > bound:
                failures.append(f"{name}={got} > allowed {bound}")
        else:
            got = metrics.get(crit)
            if got != bound:
                failures.append(f"{crit}={got} != expected {bound}")
    return failures


def run_one(test: dict, fast: bool) -> bool:
    name = test["name"]
    timeout = test.get("timeout_s", 600)
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    if fast:
        env["RELEASE_FAST"] = "1"
    if not test.get("needs_tpu"):
        # A chip belongs to one process at a time.  This runner never
        # imports JAX and runs workloads one after another, so the one
        # that declares needs_tpu owns the chip; every other workload
        # is pinned to the CPU.
        env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, test["script"])],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"FAIL  {name}: timed out after {timeout}s")
        return False
    dt = time.time() - t0
    metrics: dict = {}
    for line in proc.stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if "benchmark" in d:
            metrics[d["benchmark"]] = d.get("value")
        else:
            metrics.update({k: v for k, v in d.items()
                            if isinstance(v, (int, float, bool))})
    criteria = test.get("pass_criteria", {})
    if fast and test.get("fast_pass_criteria"):
        criteria = test["fast_pass_criteria"]
    if proc.returncode != 0:
        # a partial-failure workload (e.g. rllib_families) exits
        # nonzero for shell semantics but still prints metrics — when
        # it did AND the yaml states criteria, grade those (a
        # min-threshold criterion exists precisely to tolerate partial
        # failure); otherwise the rc is the verdict
        if not (metrics and criteria):
            detail = proc.stderr.strip().splitlines()[-1:] or ["?"]
            print(f"FAIL  {name}: rc={proc.returncode} ({detail[0]})")
            return False
        print(f"note  {name}: rc={proc.returncode}, grading printed "
              f"metrics against criteria")
    failures = _grade(metrics, criteria)
    if failures:
        print(f"FAIL  {name} ({dt:.0f}s): " + "; ".join(failures))
        return False
    print(f"PASS  {name} ({dt:.0f}s) " + json.dumps(metrics))
    return True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--filter", default="")
    ap.add_argument("--fast", action="store_true",
                    help="shrink workloads (smoke mode)")
    args = ap.parse_args()
    suite = _load_yaml(os.path.join(REPO, "release",
                                    "release_tests.yaml"))
    failed = 0
    for test in suite["tests"]:
        if args.filter and args.filter not in test["name"]:
            continue
        if not run_one(test, args.fast):
            failed += 1
    return failed


if __name__ == "__main__":
    sys.exit(main())
