"""Drives `run.main()` of a copy of the benchmark where there is no chip.

    python rehearse.py <copy of benchmarks/> [--break-step] -- <run.py's arguments>

The harness has no CPU switch. This driver, which only the tests use, puts
its own answer in place of the harness's look for a chip, gives the table of
peaks a row for the CPU, and calls the harness's own `main()`. With
`--break-step` every learner the trainer builds hands its state back
unchanged, and the run has to come out not correct.
"""

import os
import sys


def main(argv):
    bench, rest = argv[1], argv[2:]
    break_step = "--break-step" in rest[: rest.index("--")]
    run_args = rest[rest.index("--") + 1 :]
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [bench, here, os.path.dirname(os.path.dirname(here))]
    import run
    from harness import peaks

    run.require_chips = lambda cell: {"platform": "cpu", "kind": "cpu", "count": cell["chips"]}
    peaks.PEAKS["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "hbm_bytes": 1e9}
    if break_step:
        import synthetic
        from distributed_ddpg_tpu.parallel.learner import ShardedLearner

        build = ShardedLearner._build_programs

        def build_broken(self):
            build(self)
            synthetic.break_learner(self)

        ShardedLearner._build_programs = build_broken
    return run.main(run_args)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
