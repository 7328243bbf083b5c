#!/usr/bin/env python3
"""Cases of the fused-step check, taken from a cell's own windows and held
to the chip's float32 reference and to a float64 truth, run on the chip.

    python3 bench/tests/step_cases.py --workload paper.drift \
        --seeds 1,2,3 --seconds 6 --out chiprun_out/step_cases

For each seed, one window of the cell (set-up, warm-up, ``--seconds``)
is run as a benchmark run runs it, by the cell's own driver, all seeds
in one process. At the fused-step check's inputs
(``solver.step_inputs``: the channel snapshot, the thresholds and the
softened warm start of the window's first round) it computes Gamma and
the gradient at every split point six ways: the program's fused kernel;
the float32 reference at ``highest`` (what the check compares with); the
``high`` and ``default`` controls; the reference in float64 on the
host's CPU (``truth_step``); and that truth again with every float input
moved by up to half an ulp of float32 (``jitter``: how well conditioned
the step is); and, as a planted fault, the program's gradient with the
rows of the last tenth of the users scaled by 1 + 1e-4 (``rows_tenth``).
It writes, on one JSON line per lane, each side's distances from the
float32 reference and from the truth: the largest gap of a whole leaf
(``grad_rel``, not compared by the check) and Gamma's; the largest over
split points and leaves of each quantile ``QUANTILES`` of the users'
gaps (``solver.user_rel``, what the check's ``step_grad_p<NN>``
compares), each with its split point and leaf; and every (split point,
leaf) leaf gap; and prints the numbers of each side.

Each case's inputs go to ``bench/.cases/<workload>-<seed>-<lane>.npz``
(git ignores it).  Cases whose largest leaf gap between the program and
the float32 reference is under ``--keep`` are deleted at the end, except
the three largest; the ``--bring`` largest are copied beside the JSON
lines.  This is no part of a benchmark run.
"""
import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CASES = "bench/.cases"
TOP = 3
QUANTILES = (0.5, 0.75, 0.9, 0.95, 0.99)
def save_case(path, seed, lane, scn, q, alloc):
    import numpy as np
    np.savez_compressed(
        path, seed=seed, lane=lane, h_up=np.asarray(scn.h_up),
        h_dn=np.asarray(scn.h_dn), assoc=np.asarray(scn.assoc),
        up_order=np.asarray(scn.up_order),
        up_group_end=np.asarray(scn.up_group_end),
        dn_order=np.asarray(scn.dn_order),
        dn_group_end=np.asarray(scn.dn_group_end), q=np.asarray(q),
        **{k: np.asarray(v) for k, v in alloc._asdict().items()})


def jittered(scn, q, alloc, seed):
    """``scn``, ``q`` and ``alloc`` with every float entry multiplied by
    1 + U(-2**-24, 2**-24), in float64."""
    import types
    import numpy as np
    rng = np.random.default_rng(seed)

    def jit(x):
        x = np.asarray(x)
        if x.dtype.kind != "f":
            return x
        return x.astype(np.float64) * (1 + rng.uniform(-2.0 ** -24,
                                                       2.0 ** -24, x.shape))
    fields = ("h_up", "h_dn", "assoc", "up_order", "dn_order",
              "up_group_end", "dn_group_end")
    return (types.SimpleNamespace(**{k: jit(getattr(scn, k))
                                     for k in fields}),
            jit(q), type(alloc)(*map(jit, alloc)))


def truth_step(log, cfg, scn, q, alloc):
    """The reference's Gamma and gradient at every split point evaluated in
    float64 on the host's CPU, from the same float32 inputs (the scenario,
    thresholds, warm start and the reference's own float32 split
    tables)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.harness import solver
    from bench.reference import era as ref

    def to64(x):
        x = np.asarray(x)
        return jnp.asarray(x, jnp.float64 if x.dtype.kind == "f" else None)

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        s = ref.Solver(log.prof, cfg["network"], cfg["weights"],
                       cfg["solver"])
        return solver.reference_grads(s, scn, q, alloc,
                                       len(log.prof["layer_flops"]) + 1,
                                       to64)


def grad_rel(gap, norm):
    """Each leaf's gap over the larger of that leaf's norm and the median
    leaf's norm at the same split point (a leaf can be all but zero)."""
    import numpy as np
    med = np.median(norm, axis=1, keepdims=True)
    return gap / np.maximum(np.maximum(norm, med), 1e-30)


def rows_scaled(got, share=0.1, by=1e-4):
    """``got`` with the gradient rows of the last ``share`` of the users
    (at least one) scaled by 1 + ``by``."""
    import numpy as np
    val, leaves = got
    n = leaves[0].shape[1]
    k = n - int(np.ceil(share * n))
    out = []
    for x in leaves:
        x = x.copy()
        x[:, k:] *= 1 + by
        out.append(x)
    return val, out


def worst(rel, norm):
    import numpy as np
    from bench.harness import solver
    s, k = np.unravel_index(int(np.argmax(rel)), rel.shape)
    return {"value": float(rel[s, k]), "split": int(s),
            "leaf": solver.LEAVES[k], "leaf_norm": float(norm[s, k]),
            "median_norm": float(np.median(norm[s]))}


def distances(got, want):
    """``got`` from ``want``: the largest leaf gap (``grad_rel``, with its
    place), Gamma's, and for each of ``QUANTILES`` the largest over split
    points and leaves of that quantile of the users' gaps (what the
    check's ``step_grad_p<NN>`` compares), with its place; and every
    (split point, leaf) leaf gap."""
    import numpy as np
    from bench.harness import solver
    gam, gap, norm = solver.leaf_gaps(got, want)
    return {"grad_rel": worst(grad_rel(gap, norm), norm),
            "step_gamma_rel": float(np.max(gam)),
            "users": {str(q): worst(solver.user_rel(got, want, q), norm)
                      for q in QUANTILES},
            "gap": gap.tolist()}


def one_case(log, cfg, b, scn, q, alloc):
    """Every side's distances from the float32 reference and from the
    float64 truth for one lane's step."""
    import numpy as np
    from bench.harness import solver
    t0 = time.monotonic()
    got = {"program": solver.program_step(log, cfg, scn, q, alloc)}
    for side, prec in (("ref32", "highest"), ("high", "high"),
                       ("default", "default")):
        got[side] = solver.reference_step(log, cfg, scn, q, alloc, prec)
    got["rows_tenth"] = rows_scaled(got["program"])
    t1 = time.monotonic()
    truth = truth_step(log, cfg, scn, q, alloc)
    t2 = time.monotonic()
    # the truth again at every float input moved by up to half an ulp of
    # float32: how far round-off of the inputs alone moves the gradient
    jitter = truth_step(log, cfg, *jittered(scn, q, alloc, b))
    _, _, norm = solver.leaf_gaps(truth, truth)
    out = {"lane": b, "float32_s": t1 - t0, "truth_s": t2 - t1,
           "truth_norm": norm.tolist(), "truth_gamma": truth[0].tolist(),
           "nonfinite": {side: [int(np.sum(~np.isfinite(x))) for x in g[1]]
                         for side, g in got.items()},
           "vs_ref32": {side: distances(g, got["ref32"])
                        for side, g in got.items() if side != "ref32"},
           "vs_truth": {side: distances(g, truth)
                        for side, g in dict(got, jitter=jitter).items()}}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="paper.drift")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default="chiprun_out/step_cases")
    ap.add_argument("--keep", type=float, default=5e-7)
    ap.add_argument("--bring", type=int, default=1)
    args = ap.parse_args(argv)
    import importlib
    from bench.harness import core, solver
    core.enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    os.makedirs(CASES, exist_ok=True)
    lines = os.path.join(args.out, f"{args.workload}.jsonl")
    kept = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = core.make_run(["--workload", args.workload, "--seed",
                             str(seed), "--seconds", str(args.seconds)],
                            time.monotonic())
        core.check_device(run.cell["chips"])
        driver = importlib.import_module(
            f"bench.harness.{run.config['driver']}")
        log = driver.run(run).readings["log"]
        for b, scn, q, alloc in solver.step_inputs(log, run.config):
            path = os.path.join(CASES, f"{args.workload}-{seed}-{b}.npz")
            save_case(path, seed, b, scn, q, alloc)
            rec = dict(workload=args.workload, seed=seed,
                       **one_case(log, run.config, b, scn, q, alloc))
            kept.append((rec["vs_ref32"]["program"]["grad_rel"]["value"],
                         path))
            with open(lines, "a") as f:
                f.write(json.dumps(rec) + "\n")
            brief = {k: {side: {"grad_rel": v["grad_rel"]["value"],
                                "step_gamma_rel": v["step_gamma_rel"],
                                "users": {q: u["value"] for q, u
                                          in v["users"].items()}}
                         for side, v in rec[k].items()}
                     for k in ("vs_ref32", "vs_truth")}
            print(json.dumps({"seed": seed, "lane": b,
                              "truth_s": rec["truth_s"]} | brief),
                  flush=True)
    kept.sort(reverse=True)
    for i, (value, path) in enumerate(kept):
        if i < args.bring:
            shutil.copy(path, args.out)
        if value < args.keep and i >= TOP:
            os.remove(path)
    return 0

if __name__ == "__main__":
    sys.exit(main())
