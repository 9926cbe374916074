"""The output check: what the timed path produces, against the plain reference.

What is compared. The trainer's learner object, the one `train()` builds and
the window then drives, makes its first call `run_sample_chunk(replay)`: K
updates in one launch, on rows it draws from the ring the actors filled.
`ChunkCheck.install` puts a wrapper round that one method. On the first call
the wrapper, holding the ring's dispatch lock so that no insert lands in
between, copies the state the chunk starts from, lets the call through
unchanged, and then has the reference (benchmarks/reference/<algo>.py:
float32, every product at Precision.HIGHEST, its own weights from the seed)
follow the same K updates on the same rows, in blocks of at most
`REF_BLOCK` updates so that the rows it gathers never outgrow what the
program's own chunk gathers. After that first call the method is the
program's own again: the window runs no harness code.

Why the whole chunk and not only "the first three steps": the program's
unit of work is the chunk. Nothing of the state is visible between its K
updates, so the first state that can be compared is the one after K. What
is visible of single updates is their TD errors, sample by sample: update
0's are the forward pass on seeded weights, and those of updates 1 to 3 show
what the first three updates (backward pass, Adam, learning rates) did to
the nets (`compare` says what each number is for). A leaf's scale is its
own change over the chunk or the median leaf's, whichever is larger, since
some leaves barely move.

The limits are data: each configuration's file carries them under
`check.limits`, set from readings on the chip (PERF.md, section 2 gives the
readings). A number without a limit in the file fails the run.
"""

import statistics
import time

REF_BLOCK = 100  # updates per reference launch
EFFECT_UPDATES = 3  # update_effect_gap reads the TD errors of updates 1..3


def program_view(state):
    """The program's TrainState under the reference's names."""
    view = {
        "actor": state.actor_params,
        "critic": state.critic_params,
        "target_actor": state.target_actor_params,
        "target_critic": state.target_critic_params,
    }
    if state.log_alpha is not None:
        view["log_alpha"] = state.log_alpha
    return view


def _leaf_norms(tree_a, tree_b=None):
    """Norm of each leaf of a (or of a - b), as floats, in tree order."""
    import jax
    import jax.numpy as jnp

    la = jax.tree.leaves(tree_a)
    lb = jax.tree.leaves(tree_b) if tree_b is not None else [None] * len(la)
    return [
        float(jnp.sqrt(jnp.sum(jnp.square(a if b is None else a - b))))
        for a, b in zip(la, lb)
    ]


def compare(prog0, prog1, ref0, ref1, prog_td, prog_metrics, ref_metrics, stated_td0, seeded_td):
    """(numbers that are judged, numbers that are only shown), from states
    before (0) and after (1) the chunk under the reference's names, the
    program's per-update TD errors [K, B] and chunk-mean metrics, the
    reference's per-update outputs (dict of [K, ...] arrays), the
    reference's update 0 at the stated precision, and the seeded state's TD
    errors on the rows of updates 1..n (`seeded_td`).

    init_gap         largest difference between the two sides' seeded weights
    td0_vs_stated    update 0's TD errors, sample by sample: the distance
                     between the program's and the reference's, over the
                     distance that the configuration's stated product
                     precision (`precision.products`) itself puts between
                     the reference and the reference: `stated_td0` is the
                     reference's update 0 with its operands rounded to that
                     precision. A program that computes as stated reads
                     about 1 whatever the env's rewards and observations, a
                     more exact one less, and the next precision down reads
                     its epsilon's multiple of that. Update 0 sees only
                     seeded weights: it is the forward pass of critic,
                     target and policy at the timed batch, and a wrong or
                     missing row moves it far beyond any limit. The same
                     ratio for updates 1 and 2 is shown, not judged: it
                     reads up to six times its median from seed to seed
                     (PERF.md, section 2).
    update_effect_gap  the update itself: backward pass, Adam and the
                     learning rates. Adam's first steps move every weight by
                     about its learning rate, the critic's final layer by a
                     third of its seeded size, so the TD errors of updates 1
                     to n on their own rows differ from what the seeded
                     state gives on those rows (`seeded_td`) by the effect
                     of the updates before them. The number is the distance
                     between the program's and the reference's TD errors of
                     updates 1..n over the size of that effect in the
                     reference: 1 for a step that hands its state back
                     unchanged, about x for learning rates off by the share
                     x, the rounding's share for a backward pass in a lower
                     precision.
    critic_loss_rel  the chunk's mean critic loss, as the program reports it
    change_gap       gap between the norms of each net's change over the
                     chunk, by the worst leaf: 1 for a step that hands its
                     state back unchanged
    Shown only: state_err (norm of the difference of the end states, which
    K chaotic updates saturate) and the chunk-mean gradient norms (which
    swing twentyfold from seed to seed under bfloat16 products)."""
    import jax
    import jax.numpy as jnp

    def rel(a, b):
        return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)

    numbers, shown = {}, {}
    nets = [k for k in ("actor", "critic", "target_actor", "target_critic", "log_alpha")
            if k in ref1 and k in prog1]
    numbers["init_gap"] = max(
        float(jnp.max(jnp.abs(a - b)))
        for k in nets
        for a, b in zip(jax.tree.leaves(prog0[k]), jax.tree.leaves(ref0[k]))
    )
    ref_td = ref_metrics["td"]
    yardstick = float(jnp.linalg.norm(stated_td0 - ref_td[0]))
    numbers["td0_vs_stated"] = float(jnp.linalg.norm(prog_td[0] - ref_td[0])) / max(yardstick, 1e-30)
    for k in range(1, min(3, ref_td.shape[0])):
        shown[f"td{k}_vs_stated"] = float(jnp.linalg.norm(prog_td[k] - ref_td[k])) / max(yardstick, 1e-30)
    shown["td0_over_td"] = float(jnp.linalg.norm(prog_td[0] - ref_td[0]) / jnp.linalg.norm(ref_td[0]))
    after = slice(1, 1 + seeded_td.shape[0])
    numbers["update_effect_gap"] = float(
        jnp.linalg.norm(prog_td[after] - ref_td[after]) / jnp.linalg.norm(ref_td[after] - seeded_td)
    )
    numbers["critic_loss_rel"] = rel(prog_metrics["critic_loss"], jnp.mean(ref_metrics["critic_loss"]))
    for k in ("critic_grad_norm", "actor_grad_norm"):
        shown[k + "_rel"] = rel(prog_metrics[k], jnp.mean(ref_metrics[k]))
    change_gap, state_err = 0.0, 0.0
    for k in nets:
        d_ref = _leaf_norms(ref1[k], ref0[k])
        d_prog = _leaf_norms(prog1[k], prog0[k])
        err = _leaf_norms(prog1[k], ref1[k])
        floor = statistics.median(d_ref)
        for dr, dp, e in zip(d_ref, d_prog, err):
            scale = max(dr, floor, 1e-30)
            change_gap = max(change_gap, abs(dp - dr) / scale)
            state_err = max(state_err, e / scale)
    numbers["change_gap"] = change_gap
    shown["state_err"] = state_err
    return numbers, shown


def follow(reference, seed, env, hp, key0, storage, size, chunk, batch, operand_dtype=None, updates=None):
    """The reference's own updates on the rows the chunk draws, all K of them
    or the first `updates`: (state before, state after, per-update outputs),
    in blocks of REF_BLOCK."""
    import jax
    import jax.numpy as jnp

    step = reference.make_step(seed, env, hp, operand_dtype)
    _, idx = reference.c.draw_indices(key0, chunk, batch, size)
    chunk = updates or chunk
    block = max(d for d in range(1, min(REF_BLOCK, chunk) + 1) if chunk % d == 0)

    @jax.jit
    def run_block(state, storage, idx_block):
        return jax.lax.scan(step, state, storage[idx_block])

    ref0 = reference.init(seed, env, hp)
    state, per_step = ref0, []
    for b0 in range(0, chunk, block):
        state, m = run_block(state, storage, idx[b0 : b0 + block])
        per_step.append(m)
    return ref0, state, {k: jnp.concatenate([m[k] for m in per_step]) for k in per_step[0]}


def seeded_td_on(reference, seed, env, hp, key0, storage, size, chunk, batch, updates=EFFECT_UPDATES):
    """TD errors the seeded state gives on the rows of updates 1..`updates`
    of the chunk, each under its own update's step count (which names the
    policy noise where an algorithm draws any): [updates, batch]."""
    import jax
    import jax.numpy as jnp

    step = reference.make_step(seed, env, hp)
    _, idx = reference.c.draw_indices(key0, chunk, batch, size)
    ref0 = reference.init(seed, env, hp)
    ks = jnp.arange(1, 1 + min(updates, chunk - 1), dtype=ref0["step"].dtype)

    @jax.jit  # state and rows are arguments: one compiled program serves every seed
    def run(ref0, rows):
        return jax.vmap(lambda k, r: step({**ref0, "step": k}, r)[1]["td"])(ks, rows)

    return run(ref0, storage[idx[ks]])


def reference_side(drawn, stated_products):
    """What the reference gives on the rows `drawn` names: (state before,
    state after, per-update outputs, update 0's TD errors at the stated
    precision, the seeded state's TD errors on the rows of updates 1..n)."""
    ref0, ref1, ref_metrics = follow(*drawn)
    stated_td0 = follow(*drawn, operand_dtype=stated_products, updates=1)[2]["td"][0]
    return ref0, ref1, ref_metrics, stated_td0, seeded_td_on(*drawn)


def judge(numbers, shown, limits):
    """Every judged number beside its limit, and the verdict; a number with
    no limit in the configuration's file, or one that is not finite, is not
    ok."""
    rows = {}
    for name, value in numbers.items():
        limit = limits.get(name)
        rows[name] = {"value": value, "limit": limit,
                      "ok": limit is not None and value == value and value <= limit}
    return {"numbers": rows, "shown": shown, "ok": all(r["ok"] for r in rows.values())}


class ChunkCheck:
    """Wraps `learner_cls.run_sample_chunk` for its first call (see the
    module docstring). `result` is None until that call has been compared."""

    def __init__(self, reference, seed, env, hp, limits, stated_products):
        self.reference = reference
        self.seed, self.env, self.hp, self.limits = seed, env, hp, limits
        self.stated_products = stated_products
        self.result = None
        self.seconds = 0.0
        self._restore = None

    def install(self, learner_cls):
        original = learner_cls.run_sample_chunk
        check = self

        def first_call(learner, replay):
            learner_cls.run_sample_chunk = original
            return check._checked_call(original, learner, replay)

        learner_cls.run_sample_chunk = first_call
        self._restore = lambda: setattr(learner_cls, "run_sample_chunk", original)

    def uninstall(self):
        if self._restore is not None:
            self._restore()

    def _checked_call(self, original, learner, replay):
        import jax
        import jax.numpy as jnp

        ref = self.reference
        chunk, batch = learner.chunk_size, learner.global_batch
        with replay.dispatch_lock:
            storage, size = replay.device_state()
            key0 = jnp.copy(learner._key)
            prog0 = jax.tree.map(jnp.copy, program_view(learner.state))
            out = original(learner, replay)
            t0 = time.monotonic()
            prog1 = jax.tree.map(jnp.copy, program_view(out.state))
            prog_td = out.td_errors
            prog_metrics = {k: float(v) for k, v in jax.device_get(out.metrics).items()}

            drawn = (ref, self.seed, self.env, self.hp, key0, storage, size, chunk, batch)
            self.result = self.verdict(
                drawn, (prog0, prog1, prog_td, prog_metrics), reference_side(drawn, self.stated_products)
            )
            self.result.update(updates=chunk, batch=batch, ring_rows=int(size))
        self.seconds = time.monotonic() - t0
        return out

    def verdict(self, drawn, prog, ref):
        """The judged numbers of the program's side `prog` = (state before,
        state after, TD errors, chunk-mean metrics) against `ref`
        (`reference_side`)."""
        prog0, prog1, prog_td, prog_metrics = prog
        return judge(*compare(prog0, prog1, ref[0], ref[1], prog_td, prog_metrics, *ref[2:]), self.limits)
