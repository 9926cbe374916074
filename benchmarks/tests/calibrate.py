"""Reads the check's numbers on the chip, for setting its limits: the program
and the control, cell by cell and seed by seed, in one process.

    python benchmarks/tests/calibrate.py <cell>[,<cell>] <seed>[,<seed>...] [<dir>]

Each reading is one `train()` with the cell's own flags and a budget that
ends the run after its first chunk: the learner is built, fed and called
exactly as in a benchmark run, and the harness's own check wraps its first
call. Beside the program's numbers it computes the control's (the reference
with its operands rounded to `check.control_operands`, in the program's
place, on the same ring rows). One JSON line per reading; with <dir>, also
the TD errors of the first updates from program, reference and control
(`<dir>/<cell>.<seed>.npz`), for trying a statistic on them without a chip.
Needs the chip the cell asks for; the benchmark's own runs never run the
control.
"""

import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main(argv):
    sys.path[:0] = [ROOT, BENCH, os.path.dirname(os.path.abspath(__file__))]
    import run
    import synthetic

    bench = run.load_json(ROOT, "BENCHMARK.json")
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.train import train

    dump_dir = argv[3] if len(argv) > 3 else None
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
    for name in argv[1].split(","):
        cell = run.find_cell(bench, name)
        if run.require_chips(cell) is None:
            return 2
        config = run.load_json(BENCH, "configs", cell["config"] + ".json")
        traffic = run.load_json(BENCH, "traffic", cell["traffic"] + ".json")
        reference = importlib.import_module("reference." + config["reference"]["module"])
        for seed in (int(s) for s in argv[2].split(",")):
            check = synthetic.control_check(
                config["check"]["control_operands"],
                reference, seed, config["env"], config["reference"]["hp"], config["check"]["limits"],
                config["precision"]["products"],
                dump=dump_dir and os.path.join(dump_dir, f"{name}.{seed}.npz"),
            )
            check.install(ShardedLearner)
            flags = list(config["flags"]) + list(traffic["flags"])
            cfg = DDPGConfig.from_flags(flags + [f"--seed={seed}", "--eval_every=0", "--watchdog_s=120"])
            # the budget is met as soon as the ring is warm: one chunk, then the loop ends
            cfg = cfg.replace(total_env_steps=cfg.replay_min_size)
            try:
                summary = train(cfg)
            finally:
                check.uninstall()
            r = check.result
            print(json.dumps({
                "calibrate": name, "seed": seed, "leg": "kernel" if summary["fused_chunk_active"] else "scan",
                "ring_rows": r["ring_rows"], "updates": r["updates"], "check_s": round(check.seconds, 2),
                "program": {**{k: v["value"] for k, v in r["numbers"].items()}, **r["shown"]},
                "control": {**{k: v["value"] for k, v in r["control"]["numbers"].items()}, **r["control"]["shown"]},
                "program_ok": r["ok"], "control_ok": r["control"]["ok"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
