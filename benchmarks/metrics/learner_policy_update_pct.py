"""The share of the learner's updates inside the window that stepped the
policy (the actor and the temperature): the counts `redq_policy_updates` over
`learner_steps`, which the trainer's records carry since step 0. 100 /
`policy_delay` where the delay holds: 5 at the paper's 20. Only a program with
a critic ensemble (`DDPGConfig.redq`) writes the key."""


def read(run):
    first, last = run["open"], run["close"]
    if "redq_policy_updates" not in last or "redq_policy_updates" not in first:
        return None
    steps = last["learner_steps"] - first["learner_steps"]
    return 100.0 * (last["redq_policy_updates"] - first["redq_policy_updates"]) / steps if steps else None
