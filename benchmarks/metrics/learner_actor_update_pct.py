"""The share of the learner's updates inside the window that moved the actor
and the targets: the counts `td3_actor_updates` over `learner_steps`, which
the trainer's records carry since step 0. 100 / `policy_delay` where the
delay holds: 50 at the paper's 2. Only a program with twin delayed critics
writes the key."""


def read(run):
    first, last = run["open"], run["close"]
    if "td3_actor_updates" not in last or "td3_actor_updates" not in first:
        return None
    steps = last["learner_steps"] - first["learner_steps"]
    return 100.0 * (last["td3_actor_updates"] - first["td3_actor_updates"]) / steps if steps else None
