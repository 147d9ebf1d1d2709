"""Readings the limits of ``correct`` are set from, and the control and the faults
judged by them (PERF.md, "How the limits were set").

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 --controls 3 --out <file.json>

One process on the chip, at the cell's own sizes. For every seed: the program's
compared steps through the driver's own build and step (the lower reading). For
the first ``--controls`` seeds also the control (the reference in the nearest
precision below the configuration's, put in the program's place) and the faults
of faults.py planted in the program. Every set of numbers also goes through
`check.judge` with the configuration's limits: the sound program has to come out
correct, the control and each fault not. A benchmark run never runs this.

    python3 benchmarks/chip/calibrate.py --workload <cell> --rejudge <file.json>

needs no chip: it puts the readings that a chip run recorded through `check.judge`
again, with the limits as the configuration file has them now (they are set after
the readings), and prints who comes out correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import ROOT, load_module, resolve_cell  # noqa: E402


def rejudge(cell, path: str) -> int:
    """Recorded readings, judged by the configuration's limits as they stand. The
    numbers of the first gradient's difference are made again from the recorded
    leaves, so a number added since is judged too. 0 if the sound program comes
    out correct on every seed and the control and every fault on none."""
    check = load_module("", "check", cell["here"])
    limits = cell["config_file"]["limits"]
    with open(path) as f:
        rows = json.load(f)
    as_expected = True
    for row in rows:
        for subject, rec in row["readings"].items():
            if "numbers" in rec:
                leaves = {k: (a * a, b * b) for k, (a, b) in rec["first_gradient_leaves"].items()}
                numbers = dict(rec["numbers"])
                for group in check.GROUPS:
                    diff = check.group_diff(leaves, group)
                    numbers[f"grad_diff.{group}"], numbers[f"grad_diff_leaf.{group}"] = diff["all"], diff["leaf"]
                verdict = check.judge(numbers, limits)
                failed = [k for k, v in verdict["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
                as_expected &= verdict["correct"] == (subject == "program")
                print(f"seed {row['seed']} {subject}: correct={verdict['correct']} failed={failed}")
    return 0 if as_expected else 1


def main(argv) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=1000)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--out")
    parser.add_argument("--rejudge", help="a file this program recorded: judge its readings by the limits of today")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    cell = resolve_cell(args.workload)
    if args.rejudge:
        return rejudge(cell, args.rejudge)
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    import jax

    import sheeprl_tpu  # noqa: F401

    if jax.devices()[0].platform != "tpu" and not args.rehearse_cpu:
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    learner = load_module("drivers", "learner", cell["here"])
    check = load_module("", "check", cell["here"])
    config = cell["config_file"]
    limits = config.get("limits", {})
    faults = load_module("", "faults", cell["here"])
    built = learner.build(cell, args.first_seed, args.rehearse_cpu)  # one build, re-seeded for every reading
    sound_train_fn = built["train_fn"]

    def compared_steps(seed, fault=None):
        """The program's compared steps, as the driver's run makes them."""
        learner.reseed(built, seed)
        built["train_fn"] = sound_train_fn if fault is None else faults.FAULTS[fault](sound_train_fn)
        step = learner.make_step(built, learner.Spans(False))
        probe = check.Probe(built)
        for i in range(int(cell["traffic_file"]["warmup_steps"])):
            _, batches, key, named = step()
            jax.block_until_ready((built["state"]["params"], built["state"]["opt_states"]))
            probe.after_step(i, batches, key, named)
        probe.finish_setup()
        built["state"].clear()  # the reference needs the room
        probe.precision = config["precision"]
        return probe

    def reading(rows_wrong, prog, ref):
        leaves = check.diff_norms(prog["first"], ref["first"])
        numbers = {"rows_wrong": rows_wrong, **check.gaps(prog, ref, leaves)}
        verdict = check.judge(numbers, limits)
        failed = [k for k, v in verdict["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
        # every leaf's |difference| and |reference|: where the difference of the first gradient sits
        return {"numbers": numbers, "correct": verdict["correct"], "failed": failed,
                "first_gradient_leaves": {k: [a ** 0.5, b ** 0.5] for k, (a, b) in leaves.items()}}

    rows = []
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        row = {"seed": seed, "readings": {}}
        out = row["readings"]
        # each subject is judged against the reference over the batches that it was fed itself
        for subject in ["program"] + (["fault_" + f for f in faults.FAULTS] if n < args.controls else []):
            probe = compared_steps(seed, None if subject == "program" else subject[len("fault_"):])
            rows_wrong = float(probe.rows_wrong())
            prog, ref = probe.program_readings(), probe.reference_readings()
            out[subject] = reading(rows_wrong, prog, ref)
            if subject == "program":
                skip = check.negligible_leaves(ref["grads"])
                out[subject]["worst_leaf"] = {
                    f"{kind}.{g}": check.worst_gap(prog[kind + "s"], ref[kind + "s"], g, skip if kind == "delta" else ())["leaf"]
                    for kind in ("grad", "delta") for g in check.GROUPS
                }
                out[subject]["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
                if n < args.controls:
                    try:
                        control = probe.reference_readings(quant=probe.reference.fake_fp8)
                        out["control_fp8"] = reading(rows_wrong, control, ref)
                        del control
                    except Exception as e:  # a control that crashes has failed, and sets no upper reading
                        out["control_fp8"] = {"error": repr(e)[:400]}
            del probe, prog, ref
        rows.append(row)
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
