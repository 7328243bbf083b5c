#!/usr/bin/env python3
"""The controls of the benchmark's correctness checks, run on the chip.

    python3 bench/tests/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 4

For each seed, one process runs the cell as a benchmark run does (set-up,
warm-up, a window of ``--seconds``) and prints, on one JSON line, the
numbers the program's check compares and the same numbers for each
control, read from the same window: the reference put in the program's
place at a precision below the configuration's (``core.Run.control``, the
way ``bench/tests/test_controls.py`` drives a control through a whole
run).

* Solver cells (float32, every contraction at HIGHEST): the ERA reference
  at ``high`` (three bf16 passes) and at ``default`` (one bf16 pass), for
  the replayed rounds and for the fused step at the window's inputs.
* Served-model cells (bfloat16): the same for the admission solves, and
  for the model the reference forward with every matmul's operands
  rounded to float8 e4m3: at each position of the sampled prompts and
  served tokens, the gap of the token the control puts first.

The limits in ``bench/configs/<config>.json`` lie between the program's
readings over a dozen seeds or more and the control's.  This is no part
of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_numbers(run, out, control):
    from bench.harness import serve, solver
    run.control = control
    numbers = solver.check_numbers(run, out.readings["log"])
    if run.config["driver"] == "serve" and control == "high":
        r = out.readings
        numbers["logit_gap"] = serve.logit_gap(
            r["weights"], r["model"], r["served"], run.seed,
            int(run.config["check_requests"]), quantized=True)
    run.control = None
    return numbers


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--cpu", action="store_true",
                    help="run at the small CPU size of bench/tests/tiny.py")
    args = ap.parse_args(argv)
    import importlib
    from bench.harness import core
    core.enable_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        cfg = None
        if args.cpu:
            from bench.tests import tiny
            bm = core.load_json(core.ROOT / "BENCHMARK.json")
            cell = {w["name"]: w for w in bm["workloads"]}[args.workload]
            cfg = tiny.tiny_config(cell["config"])
        run = core.make_run(["--workload", args.workload, "--seed",
                             str(seed), "--seconds", str(args.seconds)],
                            time.monotonic(), cfg)
        if args.cpu and "model" in cfg:
            run.traffic = dict(run.traffic, prompt_len=cfg["profile"]["seq"],
                               decode_steps=4)
        elif not args.cpu:
            core.check_device(run.cell["chips"])
        name = run.config["driver"]
        driver = importlib.import_module(f"bench.harness.{name}")
        out = driver.run(run)
        program = {k: v for k, (v, _) in out.checks.items()}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program,
                          "control": control_numbers(run, out, "high"),
                          "control_one_pass": control_numbers(
                              run, out, "default"),
                          "limits": run.config["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
