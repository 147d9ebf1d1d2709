"""Readings the limits of a token-policy cell's ``correct`` are set from, and the control
and the faults judged by them (PERF.md, the calibration table of ``check_seq``).

    python3 benchmarks/chip/calibrate_seq.py --workload <cell> --seeds 4 --controls 2 --out <file.json>
    python3 benchmarks/chip/calibrate_seq.py --workload <cell> --seeds 2 --fault three_experts --out <file.json>
    python3 benchmarks/chip/calibrate_seq.py --workload <cell> --seed-list 7,11 --controls 0 --witness --out <file.json>

One process on the chip, at the cell's own sizes; what `calibrate.py` does for the
replay-fed cells. For every seed: the program's compared steps through the driver's own
build and step (the lower reading), and for the first ``--controls`` seeds also the control
(the reference in the nearest precision below the configuration's, put in the program's
place). With ``--fault`` every seed reads that fault of faults_seq.py planted in the program
instead: one fault a process, because two train programs of this size do not load beside
the state on one chip (my chip run, PR 29: RESOURCE_EXHAUSTED at the second). Every set of
numbers also goes through `check_seq.judge` with the configuration's limits: the sound
program has to come out correct, the control and each fault not. ``--witness`` reads, for
every seed, the reference once more with both operands of every matmul rounded to bfloat16
(the precision the configuration states, made by other code than the program's) against the
float32 reference: where the program reads far off on a seed and this witness does too, the
seed's later steps amplify the rounding and the program is sound (PERF.md section 5, PR 33).
A benchmark run never runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import ROOT, load_module, resolve_cell  # noqa: E402


def main(argv) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--first-seed", type=int, default=1000)
    parser.add_argument("--seed-list", help="these seeds, separated by commas, instead of --seeds from --first-seed on")
    parser.add_argument("--controls", type=int, default=2)
    parser.add_argument("--witness", action="store_true", help="also the reference with bfloat16 operands, every seed")
    parser.add_argument("--fault", help="a name of faults_seq.FAULTS: read that fault on every seed, and nothing else")
    parser.add_argument("--out", required=True)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    cell = resolve_cell(args.workload)
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    import jax

    import sheeprl_tpu  # noqa: F401

    if jax.devices()[0].platform != "tpu" and not args.rehearse_cpu:
        print("calibrate_seq: needs a TPU", file=sys.stderr)
        return 2
    driver = load_module("drivers", "seq_learner", cell["here"])
    check = load_module("", "check_seq", cell["here"])
    faults = load_module("", "faults_seq", cell["here"])
    limits = cell["config_file"].get("limits", {})
    built = driver.build(cell, args.first_seed, args.rehearse_cpu)  # one build, re-seeded for every reading
    built["sound_train_fn"] = built["train_fn"]
    if args.fault:
        built["train_fn"] = faults.FAULTS[args.fault](built)  # built once, compiled once
    compiled = {}  # and so is the reference's step, once a control

    def compared_steps(seed):
        """The program's compared steps, as the driver's run makes them."""
        driver.reseed(built, seed)
        step = driver.make_step(built, built["learner"].Spans(False))
        probe = check.Probe(built, compiled)
        for i in range(int(cell["traffic_file"]["warmup_steps"])):
            _, device_data, key, named = step()
            jax.block_until_ready((built["state"]["params"], built["state"]["opt_state"]))
            probe.after_step(i, device_data, key, named)
        probe.finish_setup()
        built["state"].clear()  # the reference needs the room
        built["player"].params = None
        return probe

    def reading(tokens_wrong, prog, ref):
        numbers = {"tokens_wrong": tokens_wrong, **check.gaps(prog, ref)}
        verdict = check.judge(numbers, limits)
        failed = [k for k, v in verdict["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
        return {"numbers": numbers, "correct": verdict["correct"], "failed": failed}

    def bf16_operands(x):
        """The witness's hook: an operand as bfloat16 holds it; the products are summed in float32.
        `reduce_precision` and not a cast there and back, which XLA:TPU takes out (it allows excess
        precision: my chip run, PR 33, read the witness equal to the reference to the last digit)."""
        return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) - x)

    seeds = [args.first_seed + 7919 * n for n in range(args.seeds)]
    if args.seed_list:
        seeds = [int(x) for x in args.seed_list.split(",")]
    rows = []
    for n, seed in enumerate(seeds):
        row = {"seed": seed, "readings": {}}
        out = row["readings"]
        # each subject is judged against the reference over the rollouts that it was fed itself
        subject = "fault_" + args.fault if args.fault else "program"
        probe = compared_steps(seed)
        tokens_wrong = float(probe.tokens_wrong())
        prog, ref = probe.program_readings(), probe.reference_readings()
        out[subject] = reading(tokens_wrong, prog, ref)
        out[subject]["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
        if not args.fault and n < args.controls:
            control = probe.reference_readings(quant=probe.reference.fake_fp8)
            out["control_fp8"] = reading(tokens_wrong, control, ref)
            del control
        if not args.fault and args.witness:
            witness = probe.reference_readings(quant=bf16_operands)
            out["witness_bf16"] = reading(tokens_wrong, witness, ref)
            out["witness_bf16"]["losses"] = witness["losses"]
            del witness
        del probe, prog, ref
        rows.append(row)
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
