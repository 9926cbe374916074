"""One run of one benchmark cell: `train.train()` on the chip, for a window.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up in
BENCHMARK.json; its flags come from benchmarks/configs/<config>.json and
benchmarks/traffic/<mix>.json, its reference from benchmarks/reference/,
and with `--trace 1` each per-layer metric from benchmarks/metrics/<name>.py
(`.` written `_`). Nothing here or in harness/ names a cell, a
configuration or a metric: a later PR adds them as files.

The last line of stdout is the result, one JSON object; the line before it
is `{"facts": ...}`. Without a TPU, or with another number of chips than
the cell asks for, there is no result line and the exit code is 2.

Everything that imports JAX sits under the __main__ guard: the trainer's
ActorPool spawns its workers, each re-imports this module, and a worker
must never import JAX or reach the chip (one process per chip).
"""

import argparse
import importlib
import json
import math
import multiprocessing as mp
import os
import shutil
import sys
import tempfile
import time

T_START = time.monotonic()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
INGEST_BLOCK = 1024  # the trainer's DeviceReplay block: one padded flush at most


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cache_entries(path):
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: BENCHMARK.json has no workload {name!r}")


def metrics_for(bench, group, cell_name):
    """The metrics of `group` that this cell reports."""
    return [m for m in bench[group] if cell_name in m.get("workloads", [cell_name])]


def require_chips(cell):
    """The device as JAX reports it, or None (after saying why) when it is
    not the cell's number of TPU chips. There is no CPU switch."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) != cell["chips"]:
        print(
            f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(jax.devices())} device(s) of platform {dev.platform!r}",
            file=sys.stderr,
        )
        return None
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def invariants(summary, last, device, expects, capacity):
    """chip_smoke.run_leg's checks, on the summary `train()` returns and the
    last "train" record: [(what, ok)]."""
    chips = device["count"]
    # Every env step is in the ring or still staged on the host; the only
    # surplus is the warm-up flush's padding. A ring that has wrapped holds
    # `capacity` rows and says nothing more.
    surplus = summary["buffer_fill"] + summary["ingest_queue_rows"] - summary["env_steps"]
    accounted = 0 <= surplus < 2 * INGEST_BLOCK or summary["buffer_fill"] == capacity
    checks = [
        ("trainer ran on the device the harness saw", summary["platform"] == device["platform"]),
        ("device count", summary["n_devices"] == chips),
        ("state on every chip", summary["state_devices"] == chips),
        ("ring on every chip", summary.get("replay_devices") == chips),
        ("last chunk's losses finite", all(
            math.isfinite(last.get(k, math.nan)) for k in ("critic_loss", "actor_loss"))),
        ("actor parameters moved", summary["param_checksum"] != summary["param_checksum_start"]
         and math.isfinite(summary["param_checksum"])),
        ("ring and staged rows account for every env step", accounted),
        ("no numeric failure", not summary["numeric_failed"]),
        ("no pod degradation", not summary["pod_degraded"]),
        ("no actor respawn", summary["actor_respawns"] == 0),
    ]
    checks += [(f"{k} is {v}", summary.get(k) == v) for k, v in expects.items()]
    return checks


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default="", help="directory for the run's files (default: a temporary one)")
    args = p.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")

    device = require_chips(cell)
    if device is None:
        return 2
    return run_cell(args, bench, cell, config, traffic, device)


def run_cell(args, bench, cell, config, traffic, device):
    import jax

    for path in (ROOT, BENCH):
        if path not in sys.path:
            sys.path.insert(0, path)
    from harness import check as check_lib
    from harness import flops, peaks, records, window, xplane

    # Importing the mesh module places the compile cache (the environment's
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache). Every program
    # is kept there, however short its compile: the ring-insert programs are
    # under JAX's 1 s floor and would otherwise compile in every run, some of
    # them inside the window.
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel import mesh  # noqa: F401
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.train import train

    t_imports = time.monotonic()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = cache_entries(cache_dir)

    peak = peaks.lookup(device["kind"])
    compiles = window.Compiles().install()

    out_dir = args.out or tempfile.mkdtemp(prefix="bench-run-")  # under TMPDIR
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, "records.jsonl")
    if os.path.exists(log_path):
        os.remove(log_path)

    reference = importlib.import_module("reference." + config["reference"]["module"])
    check = check_lib.ChunkCheck(
        reference, args.seed, config["env"], config["reference"]["hp"], config["check"]["limits"],
        config["precision"]["products"],
    )
    check.install(ShardedLearner)
    # How long a trace may be is the configuration's to say where its
    # program runs many operations a second, and the mix's otherwise.
    trace_s = float(config.get("trace_seconds", traffic.get("trace_seconds", 2.0)))
    tracer = xplane.Tracer(os.path.join(out_dir, "trace"), trace_s, args.seconds) if args.trace else None
    win = window.Window(log_path, args.seconds, traffic.get("warmup", {}), compiles, tracer)
    cfg = DDPGConfig.from_flags(
        list(config["flags"])
        + list(traffic["flags"])
        + [
            f"--seed={args.seed}",
            "--total_env_steps=2000000000",
            "--eval_every=0",
            "--watchdog_s=120",
            f"--log_path={log_path}",
        ]
    )
    win.start()
    t_train = time.monotonic()
    try:
        summary = train(cfg)
    finally:
        t_returned = time.monotonic()
        win.stop()
        win.join(timeout=30)
        check.uninstall()
        if tracer is not None and tracer.is_alive():
            tracer.join(timeout=240)
    left = mp.active_children()
    for child in left:
        child.terminate()
        child.join(timeout=10)

    if win.close_i is None or check.result is None:
        print(
            f"benchmark: the run did not reach a closed window "
            f"(window: {win.error}; records: {len(win.train)}; checked: {check.result is not None})",
            file=sys.stderr,
        )
        return 1

    rec_open, rec_close = win.train[win.open_i], win.train[win.close_i]
    in_window = win.window_records
    attempted = records.phase_calls(in_window, "dispatch")
    inv = invariants(summary, win.train[-1], device, config.get("expects", {}), cfg.replay_capacity)
    inv.append(("no child process left behind", not left))
    in_win = compiles.between(win.t_open, win.t_close)
    start_to_open_s = (win.t_open - T_START) - check.seconds

    run = {
        "cell": cell, "config": config, "traffic": traffic, "reference": reference, "peaks": peak, "flops": flops,
        "records": records, "open": rec_open, "close": rec_close, "window": in_window,
        "window_s": win.t_close - win.t_open,
        "summary": summary, "trace": None,
    }
    if args.trace:
        path = xplane.find(tracer.out_dir)
        run["trace"] = xplane.reduce(xplane.load(path)) if path else None
        wanted = metrics_for(bench, "per_layer", cell["name"])
    else:
        wanted = metrics_for(bench, "end_to_end", cell["name"])
    run["start_to_open_s"] = start_to_open_s
    metrics = {}
    for m in wanted:
        reader = importlib.import_module("metrics." + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device)
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()
    )
    failed = attempted if summary["numeric_failed"] else 0
    correct = bool(check.result["ok"] and all(ok for _, ok in inv) and not failed)
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    trace = run["trace"]
    if args.trace and trace is not None:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}

    facts = {
        "cell": cell["name"], "seed": args.seed,
        "leg": "kernel" if summary["fused_chunk_active"] else "scan",
        "learner_chunk": summary["learner_chunk"],
        "window_s": win.t_close - win.t_open,
        "window_s_by_records": rec_close["wall_time"] - rec_open["wall_time"],
        "window_records": len(in_window), "records": len(win.train),
        "open_s": win.t_open - T_START, "check_s": check.seconds,
        "timeline_s": {"imports_done": t_imports - T_START, "train_called": t_train - T_START,
                       "first_record": win.t_first - T_START, "window_open": win.t_open - T_START,
                       "window_close": win.t_close - T_START, "train_returned": t_returned - T_START},
        "first_chunk_s": summary.get("first_chunk_s"),
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_entries(cache_dir)},
        "programs_built_in_window": in_win,
        "programs_built": len(compiles.builds), "cache_hits": len(compiles.hits),
        "cpu_count": os.cpu_count(), "bytes_limit": stats.get("bytes_limit"),
        "env_steps": summary["env_steps"], "buffer_fill": summary["buffer_fill"],
        "ring_capacity": cfg.replay_capacity,
        "check": check.result,
        "invariants": {what: ok for what, ok in inv},
        "launches": trace and trace["launches"],
        "out": out_dir,
    }
    for name, n in check.result["numbers"].items():
        print(f"check {name}: {n['value']:.6g} (limit {n['limit']}) {'ok' if n['ok'] else 'FAIL'}")
    for what, ok in inv:
        if not ok:
            print(f"invariant failed: {what}", file=sys.stderr)
    if not args.out:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({"facts": facts}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
