"""Updates the learner finished for each transition the actors delivered,
inside the window: the difference of `learner_steps` over that of `step`
between the window's opening and closing records. REDQ is defined at an
update-to-data ratio of 20; a free-running learner beside four actors reaches
what one chip gives, and this says how far that is. Read only where the
program says it runs a critic ensemble (`redq_policy_updates` on its
records): other programs report nothing here."""


def read(run):
    first, last = run["open"], run["close"]
    if "redq_policy_updates" not in last:
        return None
    env_steps = last["step"] - first["step"]
    return (last["learner_steps"] - first["learner_steps"]) / env_steps if env_steps else None
