"""The megakernel's static schedule, read off the TPU compiler's own dump.

The TPU trace names the whole Pallas call as one op (PERF.md section 7, note
1), so nothing measured on the chip sees inside the kernel. The compiler does:
started with `--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true` in
`LIBTPU_INIT_ARGS` it writes, for every program it schedules, the final VLIW
bundles as text (`*-final_bundles.txt`, one line a bundle) and the slots each
bundle fills (`*-final_hlo-static-per-bundle-utilization.txt`, one row a
bundle under a row of capacities). The kernel body is straight-line code, one
grid step an update, so bundles count issue cycles; what they cannot see is
stalls on MXU results and DMA waits (the static count was 80-89% of the
measured update in all three kernel cells, PR 32).

    python -m distributed_ddpg_tpu.tools.kernel_bundles \\
        benchmarks/configs/d4pg-halfcheetah.json [--window 1000] [--keep DIR]
    python -m distributed_ddpg_tpu.tools.kernel_bundles --dump DIR

The first form compiles the configuration's megakernel (chunk 800, as
tests/test_ring_layout.py does) for a described v5e in a CHILD process: the
dumper aborts the process after the text is written, on a missing HTML
template of its VMEM report, so the child's exit code says nothing and the
files are the result. Needs no chip; on no cell's path. The parent process
imports no JAX, so it can run beside one that holds the TPU library.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

CHUNK = 800  # the launch length every kernel cell runs (learner's auto size)

_BUNDLE = re.compile(r"^\s*(0x[0-9a-f]+|0)\s+(LH|LB|LE|PB|PF|CT)?:\s*>*\s*\{")
_BRANCH = re.compile(r"sbr\.rel \(!?%\w+\) target bundleno = (\d+)")


class Bundle(NamedTuple):
    addr: int  # the line's own number (final numbering)
    marker: str  # "", or a control-target key: LB loop body, PF fallthrough...
    branch_to: Optional[int]  # an sbr.rel's target, in an EARLIER numbering


class Schedule(NamedTuple):
    bundles: List[Bundle]
    names: List[str]  # unit names, as the utilisation file heads them
    capacity: List[int]
    rows: List[List[int]]  # ops issued a bundle, by unit


def parse_bundles(text: str) -> List[Bundle]:
    """One Bundle a line of a `*-final_bundles.txt`; the key block at the top
    and anything else that is no bundle is skipped."""
    out = []
    for line in text.splitlines():
        m = _BUNDLE.match(line)
        if not m:
            continue
        b = _BRANCH.search(line)
        out.append(
            Bundle(
                int(m.group(1), 16), m.group(2) or "",
                int(b.group(1)) if b else None,
            )
        )
    return out


def parse_utilization(text: str) -> Tuple[List[str], List[int], List[List[int]]]:
    """(unit names, capacities, rows) of a per-bundle utilisation file: a
    line of names, a line of capacities, then one row of counts a bundle."""
    names: List[str] = []
    numeric: List[List[int]] = []
    for line in text.splitlines():
        if "," in line and not names:
            names = [n.strip() for n in line.split(",")]
            continue
        parts = line.split()
        if names and len(parts) == len(names) and all(
            p.isdigit() for p in parts
        ):
            numeric.append([int(p) for p in parts])
    if not names or not numeric:
        raise ValueError("no utilisation table found")
    return names, numeric[0], numeric[1:]


def regions(bundles: Sequence[Bundle]) -> List[Tuple[int, int]]:
    """[start, end) of every predicated region, as line numbers: a forward
    `sbr.rel` to the fallthrough it skips to. Targets are in an earlier
    numbering than the lines (later passes only take bundles out, so a
    forward target stays above its branch's line), so branch and `PF` are
    matched by rank: the k-th smallest target is the k-th `PF` line."""
    branches = sorted(
        (b.branch_to, b.addr) for b in bundles
        if b.branch_to is not None and b.branch_to > b.addr
    )
    falls = [b.addr for b in bundles if b.marker == "PF"]
    return sorted(
        (start, end) for (_, start), end in zip(branches, falls) if end > start
    )


def phases(bundles: Sequence[Bundle]) -> Dict[str, object]:
    """Bundles in all, in the grid loop's body (its `LB` line to its back
    edge), in the `k == 0` seed (the first predicated region inside the one
    that guards the whole body), in an update (the body less the seed) and
    the (start, length) of every other region an update holds under a branch
    (the last grid step's metric, TD3's delayed actor and targets)."""
    first = next(b.addr for b in bundles if b.marker == "LB")
    last = max(
        b.addr for b in bundles
        if b.branch_to is not None and b.branch_to <= b.addr
    )
    regs = regions(bundles)
    outer_end = max((e for _, e in regs), default=0)
    inner = [(s, e) for s, e in regs if e < outer_end]
    seed = inner[0][1] - inner[0][0] if inner else 0
    body = last - first + 1
    return {
        "bundles": bundles[-1].addr + 1,
        "loop_body": body,
        "seed": seed,
        "update": body - seed,
        "branched": [(s, e - s) for s, e in inner[1:]],
        "loops": inner_loops(bundles),
    }


def inner_loops(bundles: Sequence[Bundle]) -> List[int]:
    """Bundles a trip of every loop nested in the grid loop's body (the
    pixel crop's loop over an image's rows; the megakernel has none): a
    backward `sbr.rel` other than the grid loop's own, the last. Its target
    is in an earlier numbering, so a trip is counted from the nested `LB`
    line the branch closes: the nearest one above it."""
    back = sorted(
        b.addr for b in bundles
        if b.branch_to is not None and b.branch_to <= b.addr
    )[:-1]
    heads = [b.addr for b in bundles if b.marker == "LB"][1:]
    return [
        end - max(h for h in heads if h <= end) + 1
        for end in back if any(h <= end for h in heads)
    ]


def _means(rows: Sequence[Sequence[int]]) -> List[float]:
    """Mean ops a bundle, by unit, over some rows of the utilisation table."""
    return [sum(col) / len(rows) for col in zip(*rows)]


def window_table(sched: Schedule, window: int) -> List[Tuple[int, List[float]]]:
    """(first bundle, mean ops a bundle by unit) of each window."""
    return [
        (s, _means(sched.rows[s : s + window]))
        for s in range(0, len(sched.rows), window)
    ]


def mxu_free_stretches(
    sched: Schedule, min_len: int = 200
) -> List[Tuple[int, int, List[float]]]:
    """(start, end, mean ops a bundle by unit) of every run of at least
    `min_len` bundles that issue no MXU op."""
    mxu = sched.names.index("MXU")
    out, start = [], None
    for i, r in enumerate(list(sched.rows) + [None]):
        free = r is not None and r[mxu] == 0
        if free and start is None:
            start = i
        elif not free and start is not None:
            if i - start >= min_len:
                out.append((start, i, _means(sched.rows[start:i])))
            start = None
    return out


def saturated(sched: Schedule, means: Sequence[float], unit: str,
              share: float = 0.8) -> bool:
    j = sched.names.index(unit)
    return means[j] >= share * sched.capacity[j]


def load_dump(directory: str) -> Schedule:
    """The Pallas call's schedule out of a dump directory: the one program
    whose entry bundle is a custom call and that holds the grid's loop (the
    top-level program of a jit that only calls the kernel names the custom
    call too, and loops over nothing)."""
    for path in sorted(glob.glob(os.path.join(directory, "*-final_bundles.txt"))):
        if "schedule-analysis" in os.path.basename(path):
            continue
        with open(path) as f:
            head = f.read(20000)
            if "= custom-call(" not in head:
                continue
            text = head + f.read()
        if not re.search(r"^\s*\S+\s+LB:", text, re.M):
            continue
        stem = re.sub(r"-\d+-final_bundles\.txt$", "", path)
        (util,) = glob.glob(
            glob.escape(stem) + "-*-final_hlo-static-per-bundle-utilization.txt"
        )
        with open(util) as f:
            names, capacity, rows = parse_utilization(f.read())
        return Schedule(parse_bundles(text), names, capacity, rows)
    raise FileNotFoundError(
        f"no final_bundles.txt of a custom call under {directory}"
    )


def report(sched: Schedule, window: int, min_len: int) -> str:
    ph = phases(sched.bundles)
    head = f"bundles in all {ph['bundles']}, the grid loop's body {ph['loop_body']}"
    if ph["loops"]:  # the pixel crop: a grid step is a loop over rows, no seed and no update
        head += f" with one trip of each nested loop: {ph['loops']}"
    else:
        head += (
            f": the k == 0 seed {ph['seed']}, an update {ph['update']}, of it "
            "under a branch "
            + (", ".join(f"{n} at {s}" for s, n in ph["branched"]) or "none")
        )
    lines = [
        head,
        "capacity " + " ".join(
            f"{n}={c}" for n, c in zip(sched.names, sched.capacity)
        ),
        " start " + " ".join(f"{n:>12}" for n in sched.names),
    ]
    for s, means in window_table(sched, window):
        lines.append(f"{s:6d} " + " ".join(f"{m:12.2f}" for m in means))
    lines.append(f"MXU-free stretches of {min_len} bundles or more:")
    for a, b, means in mxu_free_stretches(sched, min_len):
        bound = [
            n for n in sched.names
            if "MXU" not in n and saturated(sched, means, n)
        ]
        lines.append(
            f"  {a}-{b} ({b - a}): "
            + " ".join(f"{n}={m:.2f}" for n, m in zip(sched.names, means))
            + (f"  SATURATED: {', '.join(bound)}" if bound else "")
        )
    return "\n".join(lines)


def lower_chunk(conf: dict, replicated, chunk: int = CHUNK):
    """The megakernel chunk of a benchmark configuration (its parsed file;
    of a pixel one, which has no megakernel, its crop kernel: lower_crop),
    lowered natively for the devices `replicated` shards over: shapes only,
    so a described topology does (tests/test_ring_layout.py compiles the
    same)."""
    import jax
    import jax.numpy as jnp

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.learner import init_train_state
    from distributed_ddpg_tpu.ops import fused_chunk

    cfg = DDPGConfig.from_flags(
        [f for f in conf["flags"] if not f.startswith("--replay_capacity")]
        + (["--actor_backend=device", "--num_actors=0"] if "obs_shape" in conf["env"] else [])
    )
    env = conf["env"]
    if cfg.pixels:
        return lower_crop(cfg, env, replicated)
    obs, act = env["obs_dim"], env["act_dim"]
    assert fused_chunk.supported(cfg) and fused_chunk.fits_vmem(cfg, obs, act)
    run = fused_chunk.make_fused_chunk_fn(
        cfg, obs, act, env["action_scale"], env["action_offset"],
        chunk_size=chunk, interpret=False,
    )
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda: init_train_state(cfg, obs, act, 0)),
    )
    batches = jax.ShapeDtypeStruct(
        (chunk, cfg.batch_size, 2 * obs + act + 3), jnp.float32,
        sharding=replicated,
    )
    return jax.jit(run).lower(state, batches)


def lower_crop(cfg, env: dict, replicated):
    """A pixel configuration's kernel, which is no megakernel: the crop of
    an update's images (ops/pixels.random_shift) on its batch of rows."""
    import jax
    import jax.numpy as jnp

    from distributed_ddpg_tpu.ops import pixels
    from distributed_ddpg_tpu.types import ObsSpec

    obs = ObsSpec(tuple(env["obs_shape"]), env["obs_dtype"])
    words = jax.ShapeDtypeStruct(
        (obs.words, cfg.batch_size), jnp.float32, sharding=replicated
    )
    offsets = jax.ShapeDtypeStruct(
        (cfg.batch_size, 2), jnp.int32, sharding=replicated
    )
    return jax.jit(
        lambda w, o: pixels.random_shift(w, o, cfg.aug_pad, obs, interpret=False)
    ).lower(words, offsets)


def _child(config_path: str) -> None:
    """Compile the configuration's kernel for a described v5e. Runs in
    the child: the dumper aborts this process before compile() returns."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    with open(config_path) as f:
        conf = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    lower_chunk(conf, NamedSharding(mesh, P())).compile()


def compile_and_dump(config_path: str, directory: str) -> None:
    env = dict(os.environ)
    env["LIBTPU_INIT_ARGS"] = (
        env.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_jf_dump_to={directory} --xla_jf_dump_llo_text=true"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    # A quarter of a minute of compiling and 330 MB of text: behind whatever
    # else the machine runs (tier-1 has wall-clock tests beside this).
    nice = ["nice", "-n", "10"] if shutil.which("nice") else []
    done = subprocess.run(
        nice + [sys.executable, "-m", "distributed_ddpg_tpu.tools.kernel_bundles",
                "--child", config_path],
        env=env, capture_output=True, text=True,
    )
    if not glob.glob(os.path.join(directory, "*-final_bundles.txt")):
        # No text: the compile itself failed (a Mosaic refusal, no TPU
        # compiler here), not the dumper's abort behind it.
        sys.stderr.write(done.stderr[-4000:])
        raise RuntimeError("the child wrote no final_bundles.txt")


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="kernel_bundles", description=__doc__.splitlines()[0]
    )
    parser.add_argument("config", nargs="?", help="a benchmarks/configs/*.json")
    parser.add_argument("--dump", help="read this dump directory, compile nothing")
    parser.add_argument("--keep", help="keep the compiler's dump here")
    parser.add_argument("--window", type=int, default=1000)
    parser.add_argument("--min-stretch", type=int, default=200)
    parser.add_argument("--child", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        _child(args.child)
        return 0
    if args.dump:
        sched = load_dump(args.dump)
    elif args.config:
        with tempfile.TemporaryDirectory() as tmp:
            directory = args.keep or tmp
            os.makedirs(directory, exist_ok=True)
            compile_and_dump(args.config, directory)
            sched = load_dump(directory)
    else:
        parser.error("give a configuration file or --dump DIR")
    print(report(sched, args.window, args.min_stretch))
    return 0


if __name__ == "__main__":
    sys.exit(main())
