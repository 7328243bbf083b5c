#!/usr/bin/env python3
"""How a traffic mix's QoE deadlines were calibrated: the operating point
the admission solve reaches under each of several deadline ranges.

    python3 bench/tests/calibrate.py --workload <cell> --seed <n> \
        --seconds 6 --deadlines 0.2,0.6 5,25

For each range ``lo,hi`` one run of the cell's traffic, with its deadlines
drawn from U(lo, hi) and every user starting at the middle of the range,
solves admission rounds as the cell does (a solver cell: its window of
``--seconds``; a served cell: ``--rounds`` closed-loop rounds of re-posts,
solved without serving the model) and prints one JSON line: the QoE
attainment, the quartiles of the modelled latency of the installed
schedules and the split histogram, per cell.  A deadline range is sound
where attainment lies well inside (0, 1): the QoE term of the utility is
then neither saturated nor idle.  This is no part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def served_rounds(run, rounds: int):
    """Closed-loop re-post rounds of a served cell's mix, solved only."""
    import numpy as np
    from repro.core.era import Weights
    from repro.serving.cluster import SplitInferenceCluster
    from bench.harness import profiles, solver, traffic as tr
    cfg, mix = run.config, run.traffic
    net = solver._net(cfg)
    n_cells = int(cfg["cells"])
    chains = tr.ChannelChains(run.seed, net, n_cells, 1, 0.0)
    cluster = SplitInferenceCluster(
        None, None, profiles.to_program(profiles.build(cfg), "calibrate"),
        spec=solver._spec(cfg), weights=Weights(**cfg["weights"]),
        drift_threshold=cfg["drift_threshold"], clock=time.monotonic)
    ids = [cluster.add_cell(chains.scenario(b, 0), q0=mix["q0_s"])
           for b in range(n_cells)]
    cluster.start(threaded=False)
    rng = tr.np_rng(run.seed, 5)
    for _ in range(rounds):
        reposts, _ = tr.chat_round(rng, n_cells, net.n_users,
                                   mix["repost_prob"], mix["deadline_s"], 1, 2)
        for c, u, q in reposts:
            cluster.submit(ids[c], u, q)
        cluster.step()
    scheds = [cluster.installed_schedule(i) for i in ids]
    out = ([cluster.qoe_attainment(i) for i in ids],
           [np.asarray(s.pred_latency) for s in scheds],
           [np.bincount(s.split, minlength=len(profiles.build(cfg)
                                               ["layer_flops"]) + 1)
            for s in scheds])
    cluster.stop(drain=False)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--deadlines", nargs="+", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    from bench.harness import core, solver
    core.enable_compile_cache()
    for rng_s in args.deadlines:
        lo, hi = map(float, rng_s.split(","))
        run = core.make_run(["--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", str(args.seconds)],
                            time.monotonic())
        run.traffic = dict(run.traffic, deadline_s=[lo, hi],
                           q0_s=0.5 * (lo + hi))
        if run.config["driver"] == "solver":
            log = solver.simulate(run)
            point = (log.attainment, log.latency, log.splits)
        else:
            point = served_rounds(run, args.rounds)
        att, lat, splits = point
        print(json.dumps({
            "workload": args.workload, "seed": args.seed,
            "deadline_s": [lo, hi], "attainment": att,
            "latency_quartiles_s": [np.quantile(x, [0, .25, .5, .75, 1])
                                    .tolist() for x in lat],
            "splits": [h.tolist() for h in splits]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
